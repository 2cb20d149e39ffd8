"""SPMD-tier observability: the jit-tier counterpart of the eager
timeline.

The reference's flagship observability subsystem is the timeline
(``horovod/common/timeline.cc:120`` — per-tensor activity spans written
by rank 0, viewed in chrome://tracing). Our eager tier reproduces it
(``common/timeline.py``/``core/src/timeline.h``); on the tier that
actually runs on TPU (jit/GSPMD), collectives are XLA ops inside one
compiled program, so the equivalent record is the XLA profiler trace —
this module wires it up:

* Every traced collective in ``horovod_tpu.ops.collective_ops`` runs
  under ``jax.named_scope("hvd.<op>[.<name>]")``, so its spans show up
  in profiler traces — and its ops carry the scope in lowered HLO
  metadata — under the same user-visible names the eager timeline
  records (``hvd.allreduce.DistributedOptimizer.3``, ...).
* ``trace(log_dir)`` / ``start_trace``/``stop_trace`` wrap
  ``jax.profiler`` with the reference's HOROVOD_TIMELINE-style
  env-var activation (``HOROVOD_PROFILE_DIR``).
* ``annotate(name)`` / ``step(n)`` label host-side regions and training
  steps in the same trace.
* The scope vocabulary (``SCOPE_*``, ``KERNEL_*``, :func:`collective_scope`)
  is defined here and nowhere else: the names the program plants in the
  compiled step, which a trace reader keys on.
* ``span(name)`` / ``spans()`` keep a bounded in-memory log of what set-up
  was doing (import, ``init`` and its children, mesh and placement) on
  ``time.perf_counter_ns``; each span is also a
  ``TraceAnnotation("hvd.<name>")``, so under an open profiler session
  it lies on the device trace's clock too.
* ``compile_events()`` keeps one record per jax monitoring event of the
  compile path (outermost tracing, lowering, backend compile or cache
  load, cache hits and misses) with the function's name: which program
  compiled, and when. The listeners are registered once a process by ``hvd.init()``.
* ``exchanges()`` keeps one record per gradient exchange the program
  traced (``DistributedOptimizer``, ``distributed_value_and_grad``): how
  many leaves, their bytes as held and on the wire, the axis and its
  size. It is written while the step is traced, never while it runs.

None of this has a switch: scopes are compile-time metadata, spans,
compile records and exchange records are appends to a bounded list.
"Tracing on" still means one thing, an open ``jax.profiler`` session
(``HOROVOD_PROFILE_DIR`` or an explicit ``trace(log_dir)``).

View traces with TensorBoard's profile plugin or Perfetto
(``docs/timeline.md``).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Iterator, NamedTuple, Optional

from .config import env_str

import jax

__all__ = ["trace", "start_trace", "stop_trace", "annotate", "step",
           "named_scope", "PROFILE_DIR_ENV",
           "SCOPE_EXCHANGE", "SCOPE_UPDATE", "collective_scope",
           "SCOPE_MOE_ROUTE", "SCOPE_MOE_DISPATCH", "SCOPE_MOE_EXPERTS",
           "SCOPE_MOE_COMBINE", "SCOPE_MOE_SHARED",
           "SCOPE_ATTN_FULL", "SCOPE_ATTN_WINDOW", "SCOPE_ATTN_POINTWISE",
           "SCOPE_ATTN_LATENT", "SCOPE_ATTN_LATENT_PROJ", "SCOPE_MTP",
           "SCOPE_LINATTN_CONV", "SCOPE_LINATTN_SCAN", "SCOPE_LINATTN_GATE",
           "SCOPE_SHORTCONV", "SCOPE_SHORTCONV_POINTWISE",
           "SCOPE_LOSS_HEAD", "SCOPE_LOOP_PASS", "SCOPE_LOOP_EXIT",
           "DECODE_PATHS", "decode_scope",
           "KERNEL_FLASH_FWD", "KERNEL_FLASH_BWD_DQ", "KERNEL_FLASH_BWD_DKV",
           "KERNEL_DECODE", "KERNEL_PAGED_DECODE",
           "KERNEL_SHORTCONV_FWD", "KERNEL_SHORTCONV_BWD", "KERNELS",
           "Span", "span", "record_span", "spans", "spans_dropped",
           "CompileEvent", "compile_events", "install_compile_listeners",
           "COMPILE_EVENTS",
           "ExchangeRecord", "record_exchange", "exchanges"]

PROFILE_DIR_ENV = "HOROVOD_PROFILE_DIR"

# ------------------------------------------------------- scope vocabulary
# Device side: names that reach the compiled program (docs/timeline.md
# lists them). Forward and backward carry no scope of ours: jax's name
# stack marks them ``jvp(`` and ``transpose(``; flax names the modules.

#: The gradient exchange of a step: every ``hvd.allreduce.<prefix>.<i>``
#: of ``DistributedOptimizer`` / ``distributed_value_and_grad`` and
#: compression's casts around them.
SCOPE_EXCHANGE = "hvd.exchange"
#: The wrapped optimizer's ``update`` inside ``DistributedOptimizer``.
SCOPE_UPDATE = "hvd.update"

#: The phases of the dropless expert layer
#: (``parallel/moe.py::moe_apply_held``), forward and backward alike:
#: choosing each token's experts and their weights; sorting the
#: assignments that land on this device by expert and gathering their
#: rows; the grouped products of the experts held here; the weighted sum
#: back into token order.
SCOPE_MOE_ROUTE = "hvd.moe.route"
SCOPE_MOE_DISPATCH = "hvd.moe.dispatch"
SCOPE_MOE_EXPERTS = "hvd.moe.experts"
SCOPE_MOE_COMBINE = "hvd.moe.combine"
#: The shared expert every token passes beside the routed ones
#: (``models/laguna.py``): a dense gated MLP, outside ``moe_apply_held``.
SCOPE_MOE_SHARED = "hvd.moe.shared"

#: The attention of a model whose layers differ in kind
#: (``models/laguna.py``), forward and backward alike: the attention call
#: itself (scores, softmax, context: the flash kernels where they run, so
#: that a kernel's time can be told apart by the kind of its layer) of a
#: layer that sees every earlier key, and of one that sees a window of
#: them; and the pointwise passes around it, the rotary embedding of q and
#: k and the per-head gate on the context. The projections carry no scope.
SCOPE_ATTN_FULL = "hvd.attn.full"
SCOPE_ATTN_WINDOW = "hvd.attn.window"
SCOPE_ATTN_POINTWISE = "hvd.attn.pointwise"

#: Multi-head latent attention (``models/joyai.py`` ``LatentAttention``),
#: forward and backward alike: the attention call on the expanded q, k and
#: v (q and k 192 wide, v 128: the flash kernels land here by name); and
#: everything else of the mixer but ``wo``: the two down-projections, their
#: norms, the two up-projections, the rotation of the rotary parts and the
#: copy of the one rotary key a token into every head.
SCOPE_ATTN_LATENT = "hvd.attn.latent"
SCOPE_ATTN_LATENT_PROJ = "hvd.attn.latent.proj"
#: The multi-token-prediction module (``models/joyai.py``): its two norms
#: and projection, its block, its norm and its pass through the main
#: model's head (``joyai_lm_loss``), forward and backward alike. The
#: block's own scopes (attention's, the expert layer's) lie inside it.
SCOPE_MTP = "hvd.mtp"

#: The parts of a gated delta-rule layer (``models/olmo_hybrid.py``
#: ``LinearAttentionMixer`` over ``ops/linear_attention.py``), forward and
#: backward alike: the three causal convolutions with their SiLU and the
#: q/k L2 norms; everything of ``gated_delta_rule`` (the chunk-local
#: products and triangular system, the scan over chunks, the outputs); the
#: gated per-head norm. The projections around them carry no scope, as
#: attention's carry none.
SCOPE_LINATTN_CONV = "hvd.linattn.conv"
SCOPE_LINATTN_SCAN = "hvd.linattn.scan"
SCOPE_LINATTN_GATE = "hvd.linattn.gate"

#: The gated short-convolution mixer (``models/lfm2.py``
#: ``ShortConvMixer``), forward and backward alike: the whole mixer, both
#: projections inside; and inside it the pointwise part between them, the
#: two gates and the causal convolution
#: (``ops/short_conv.gated_short_conv_packed``'s two kernels where the
#: shapes tile, ``ops/linear_attention.causal_conv`` where not).
SCOPE_SHORTCONV = "hvd.shortconv"
SCOPE_SHORTCONV_POINTWISE = "hvd.shortconv.pointwise"

#: Everything of ``models.chunked_causal_lm_loss``: the sweep over the
#: sequence's chunks (one ``while``) that applies the head and computes
#: the loss and, when differentiated, both of its gradients.
SCOPE_LOSS_HEAD = "hvd.loss.head"

#: A looped decoder (``models/ouro.py`` over
#: ``models/decoder.looped_decoder_layers``), forward and backward alike:
#: one pass of the shared stack, every layer and the final norm that ends
#: it, under the same name in every pass (the passes are unrolled, so each
#: kernel and scope inside is an operation of the compiled step); and what
#: makes the passes' exits one loss: the gate's product on every pass's
#: normed states, the exit distribution, its entropy and the stacking of
#: states and weights for the head's one sweep.
SCOPE_LOOP_PASS = "hvd.loop.pass"
SCOPE_LOOP_EXIT = "hvd.loop.exit"

#: ``name=`` of the Pallas kernels: what the Mosaic custom calls are
#: called in the compiled program and the device trace.
KERNEL_FLASH_FWD = "hvd_flash_fwd"
KERNEL_FLASH_BWD_DQ = "hvd_flash_bwd_dq"
KERNEL_FLASH_BWD_DKV = "hvd_flash_bwd_dkv"
KERNEL_DECODE = "hvd_decode"
KERNEL_PAGED_DECODE = "hvd_paged_decode"
KERNEL_SHORTCONV_FWD = "hvd_shortconv_fwd"
KERNEL_SHORTCONV_BWD = "hvd_shortconv_bwd"
#: All of them (``utils.comm_accounting.mosaic_calls_by_kernel`` counts
#: each in a lowered or compiled program).
KERNELS = (KERNEL_FLASH_FWD, KERNEL_FLASH_BWD_DQ, KERNEL_FLASH_BWD_DKV,
           KERNEL_DECODE, KERNEL_PAGED_DECODE,
           KERNEL_SHORTCONV_FWD, KERNEL_SHORTCONV_BWD)


#: The decode attention paths of ``models/llama.py``, each under
#: ``hvd.decode.<path>`` (``utils.comm_accounting.decode_path_markers``
#: counts them in a compiled program).
DECODE_PATHS = ("kernel_tp", "kernel", "einsum", "prefill", "paged_tp",
                "paged")


def decode_scope(path: str) -> str:
    """``hvd.decode.<path>`` for one of :data:`DECODE_PATHS`."""
    if path not in DECODE_PATHS:
        raise ValueError(f"unknown decode path {path!r}")
    return "hvd.decode." + path


def collective_scope(opname: str, name: Optional[str] = None) -> str:
    """``hvd.<op>[.<name>]``: the scope one traced collective runs under,
    the name the eager timeline gives the same activity."""
    return f"hvd.{opname}" + (f".{name}" if name else "")


# Re-export: model code can label its own regions with the same mechanism
# the collectives use; the labels land in HLO metadata (survive
# compilation), unlike TraceAnnotation which is host-side only.
named_scope = jax.named_scope


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None) -> Iterator[None]:
    """Capture a profiler trace of the enclosed block::

        with hvd.profiler.trace("/tmp/prof"):
            for _ in range(3):
                params, opt_state, loss = step(params, opt_state, batch)
            jax.block_until_ready(loss)

    ``log_dir`` defaults to ``$HOROVOD_PROFILE_DIR`` (the reference
    activates its timeline with the HOROVOD_TIMELINE env var the same
    way); with neither set, the block runs unprofiled — safe to leave in
    production code. Remember to block on the last output: dispatch is
    async and an un-synced trace records only enqueues."""
    log_dir = log_dir or env_str(PROFILE_DIR_ENV)
    if not log_dir:
        yield
        return
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield


def start_trace(log_dir: Optional[str] = None) -> None:
    """Non-context form of :func:`trace` (pair with :func:`stop_trace`)."""
    log_dir = log_dir or env_str(PROFILE_DIR_ENV)
    if not log_dir:
        raise ValueError(
            f"start_trace: pass log_dir or set ${PROFILE_DIR_ENV}")
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)


def stop_trace() -> None:
    jax.profiler.stop_trace()


def annotate(name: str):
    """Host-side trace span (``jax.profiler.TraceAnnotation``): labels the
    time between dispatching ops, e.g. data loading. For device-side
    labels that survive compilation use :func:`named_scope`."""
    return jax.profiler.TraceAnnotation(name)


def step(step_num: int):
    """Label one training step in the trace
    (``jax.profiler.StepTraceAnnotation``) — TensorBoard's profile
    plugin groups device activity by these."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step_num)


# ---------------------------------------------------------- the span log

#: Prefix of a span's ``TraceAnnotation`` on the profiler's host plane.
SPAN_PREFIX = "hvd."
#: Spans kept; older ones are dropped and counted.
MAX_SPANS = 4096


class Span(NamedTuple):
    """One finished region of host work, on ``time.perf_counter_ns``.
    ``parent`` is the name of the span that was open on the same thread
    when this one started, or ``None``."""
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]


_log_lock = threading.Lock()
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_spans_dropped = 0
_open = threading.local()


def record_span(name: str, start_ns: int, end_ns: int,
                parent: Optional[str] = None) -> None:
    """Append a span that was timed by its caller (the package's import
    stamps its own first and last line)."""
    global _spans_dropped
    with _log_lock:
        if len(_spans) == _spans.maxlen:
            _spans_dropped += 1
        _spans.append(Span(name, start_ns, end_ns, parent))


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Record the enclosed block in the span log and, under an open
    profiler session, as ``hvd.<name>`` on the trace's host plane::

        with hvd.profiler.span("load_checkpoint"):
            state = restore(path)

    Costs two clock reads and a list append; no switch."""
    stack = _open.__dict__.setdefault("stack", [])
    parent = stack[-1] if stack else None
    stack.append(name)
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
    finally:
        end = time.perf_counter_ns()
        stack.pop()
        record_span(name, start, end, parent)


def spans() -> list:
    """The span log, oldest first (at most :data:`MAX_SPANS`)."""
    with _log_lock:
        return list(_spans)


def spans_dropped() -> int:
    """Spans that fell off the log's old end since the process started."""
    return _spans_dropped


# ------------------------------------------------------- compile records

_COMPILE_DURATIONS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
_COMPILE_COUNTS = (
    "/jax/compilation_cache/cache_hits",
    "/jax/compilation_cache/cache_misses",
)
#: The jax monitoring events :func:`compile_events` keeps.
COMPILE_EVENTS = _COMPILE_DURATIONS + _COMPILE_COUNTS
#: Compile records kept; older ones are dropped.
MAX_COMPILE_EVENTS = 4096


class CompileEvent(NamedTuple):
    """One jax monitoring event of the compile path. ``fun_name`` is the
    jitted function's name where jax gives one (the three
    ``/jax/core/compile`` durations: ``f`` while tracing, ``jit(f)`` from
    lowering on), ``seconds`` is ``None`` for the cache-hit and cache-miss
    counts, ``at_ns`` is when the event arrived (for a duration: when it
    ended) on ``time.perf_counter_ns``. Tracing and lowering nest (an
    inner ``jit`` inside the outer one's tracing, a kernel body traced
    while it is lowered): only the outermost region of a thread is kept,
    and its record holds the inner ones' time."""
    event: str
    fun_name: Optional[str]
    seconds: Optional[float]
    at_ns: int


_compile_events: collections.deque = collections.deque(
    maxlen=MAX_COMPILE_EVENTS)
_listening = False
# jax times these three as regions (a start, then a duration) and they
# nest: every inner ``jit`` (each ``jnp`` function is one) is traced inside
# the tracing of the outer one, and lowering a kernel traces its body.
_COMPILE_REGIONS = _COMPILE_DURATIONS[:3]
_region = threading.local()


def _on_start(event: str, value: float, **kwargs) -> None:
    # jax announces the start of each timed region as a scalar.
    if event in _COMPILE_REGIONS:
        _region.depth = getattr(_region, "depth", 0) + 1


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event not in _COMPILE_DURATIONS:
        return
    if event in _COMPILE_REGIONS:
        # Thousands of inner records a step, all within the outermost
        # region of this thread, which alone is kept.
        _region.depth = max(getattr(_region, "depth", 1) - 1, 0)
        if _region.depth:
            return
    _compile_events.append(CompileEvent(
        event, kwargs.get("fun_name"), float(duration),
        time.perf_counter_ns()))


def _on_event(event: str, **kwargs) -> None:
    if event in _COMPILE_COUNTS:
        _compile_events.append(CompileEvent(
            event, kwargs.get("fun_name"), None, time.perf_counter_ns()))


def install_compile_listeners() -> None:
    """Register the jax monitoring listeners behind
    :func:`compile_events`, once a process (``hvd.init()`` calls this)."""
    global _listening
    with _log_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_scalar_listener(_on_start)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def compile_events() -> list:
    """Every compile-path event since ``hvd.init()``, oldest first. A
    ``backend_compile_duration`` record stamped after the first step is a
    recompilation: its ``fun_name`` says of what."""
    return list(_compile_events)


# ------------------------------------------------------ exchange records

#: Exchange records kept; older ones are dropped.
MAX_EXCHANGES = 4096


class ExchangeRecord(NamedTuple):
    """What one traced gradient exchange asked to be reduced: the jit
    tier's counterpart of the eager timeline's per-tensor lines. One
    record per call of the exchange on traced values (once per traced
    program, not once per step). ``prefix`` names the caller as its
    ``hvd.allreduce.<prefix>.<i>`` scopes do; ``axis_size`` is ``None``
    where the axis is not bound (plain ``jit``): every collective then
    falls back to the identity and the step exchanges nothing.
    ``bytes_asked`` counts the leaves as the gradient holds them,
    ``bytes_wire`` as the ``psum`` carries them (after compression's
    cast), ``wire_dtypes`` the latter by dtype name. ``at_ns`` is on
    ``time.perf_counter_ns``; ``parent`` is the span open on the thread,
    as a :class:`Span`'s."""
    prefix: str
    axis: str
    axis_size: Optional[int]
    leaves: int
    bytes_asked: int
    bytes_wire: int
    wire_dtypes: dict
    average: bool
    at_ns: int
    parent: Optional[str]


_exchanges: collections.deque = collections.deque(maxlen=MAX_EXCHANGES)


def _nbytes(leaf) -> int:
    return int(leaf.size) * leaf.dtype.itemsize


def record_exchange(prefix: str, axis: str, axis_size: Optional[int],
                    asked, wire, average: bool) -> None:
    """Append the record of one traced exchange. ``asked`` and ``wire``
    are its leaves as the gradient holds them and as they go on the wire
    (tracers do: only shapes and dtypes are read)."""
    wire_dtypes = {}
    for leaf in wire:
        name = str(leaf.dtype)
        wire_dtypes[name] = wire_dtypes.get(name, 0) + _nbytes(leaf)
    stack = getattr(_open, "stack", None)
    record = ExchangeRecord(
        prefix, axis, axis_size, len(asked), sum(map(_nbytes, asked)),
        sum(wire_dtypes.values()), wire_dtypes, bool(average),
        time.perf_counter_ns(), stack[-1] if stack else None)
    with _log_lock:
        _exchanges.append(record)


def exchanges() -> list:
    """Every traced exchange since the process started, oldest first.
    After the first step ``exchanges()[-1]`` says what every step
    exchanges; ``axis_size is None`` says that it exchanges nothing."""
    with _log_lock:
        return list(_exchanges)
