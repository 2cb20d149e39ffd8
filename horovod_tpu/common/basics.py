"""Process-global framework state and lifecycle (init/shutdown).

The reference keeps a singleton ``HorovodGlobalState`` owning the background
coordinator thread (``horovod/common/operations.cc:90``, ``global_state.h:44``)
and exposes a C ABI ``horovod_init/rank/size/...`` consumed through ctypes
(``horovod/common/basics.py``). The TPU-native rebuild keeps the same lifecycle
surface, but the heavy machinery differs by tier:

* **SPMD tier** (single controller process per host, jit over the device
  mesh): no negotiation is needed — XLA's SPMD model already guarantees every
  device executes the same collectives in the same order, which is exactly the
  invariant the reference's negotiation protocol establishes dynamically
  (SURVEY.md §5 "Distributed communication backend"). Collectives lower
  straight to XLA ops over ICI.
* **Eager multi-process tier** (Horovod parity for host tensors / torch): a
  background controller with tensor fusion, response cache, timeline and stall
  detection, speaking a TCP control plane instead of MPI.
"""

from __future__ import annotations

import atexit
import threading
from typing import Optional, Sequence

from . import config as config_mod
from . import hvd_logging as logging
from . import profiler
from . import retry
from .config import Config
from .topology import Topology, detect
from .. import metrics

class HorovodTpuState:
    """Python analogue of the reference ``HorovodGlobalState``
    (``horovod/common/global_state.h:44-154``): one per process, created by
    ``init()``, torn down by ``shutdown()``/interpreter exit."""

    def __init__(self, config: Config, topology: Topology):
        self.config = config
        self.topology = topology
        self.initialized = True
        self.shut_down = False
        self.mutex = threading.RLock()
        # Lazily-created subsystems (eager tier only).
        self.controller = None  # control plane + eager collectives
        self.timeline = None
        self.parameter_manager = None
        self.metrics_exporter = None  # per-rank Prometheus endpoint

    def close(self) -> None:
        with self.mutex:
            if self.shut_down:
                return
            self.shut_down = True
            self.initialized = False
            if self.controller is not None:
                if getattr(self.controller, "_failure", None) is not None:
                    # Unclean shutdown: the job died but nothing dumped yet
                    # (or the dump is stale) — rewrite the postmortem with
                    # the full ring as of teardown.
                    metrics.dump_flight_recorder("unclean_shutdown")
                self.controller.shutdown()
                self.controller = None
            if self.timeline is not None:
                self.timeline.close()
                self.timeline = None
            if self.metrics_exporter is not None:
                self.metrics_exporter.close()
                self.metrics_exporter = None


_state: Optional[HorovodTpuState] = None
_state_lock = threading.Lock()


def _preflight_coordinator(coord: str, attempts: int = 3,
                           timeout: float = 2.0) -> None:
    """Cheap TCP health probe of the distributed coordinator before the
    expensive ``jax.distributed.initialize``: a dead/unroutable coordinator
    is reported in seconds with a precise message instead of surfacing as a
    wedged init. Non-fatal — the retried initialize is the authority (the
    coordinator may legitimately come up a moment later)."""
    import socket

    from .wire import parse_addr

    try:
        host, port_no = parse_addr(coord)
    except ValueError:
        return  # let initialize() produce its own error for a bad address

    def _dial():
        socket.create_connection((host, port_no), timeout=timeout).close()

    try:
        retry.retry_call(_dial, attempts=attempts, backoff=0.2, jitter=0.0,
                         describe=f"preflight probe of coordinator {coord}",
                         retry_on=(OSError,))
    except retry.RetryError as exc:
        logging.warning(
            "preflight: distributed coordinator %s not reachable yet (%s); "
            "proceeding — jax.distributed.initialize will retry/timeout",
            coord, exc.last)


def _maybe_init_jax_distributed() -> None:
    """Join the JAX distributed runtime when the launcher requested SPMD
    multi-host mode (``horovodrun --spmd``).

    This is the TPU-native analogue of the reference's multi-node data plane
    (NCCL ring over the cluster, ``horovod/common/ops/nccl_operations.cc``):
    after ``jax.distributed.initialize`` every process sees the *global*
    device set, ``hvd.parallel.mesh()`` spans all hosts, and collectives
    inside ``jit`` ride ICI within a slice and DCN across slices — no
    per-tensor controller needed (the SPMD program itself is the negotiation,
    SURVEY.md §5).

    Preflight-probed and retried with exponential backoff under
    ``HOROVOD_TPU_INIT_RETRIES``/``_BACKOFF`` instead of wedging on the
    first dead coordinator."""
    coord = config_mod.spmd_coordinator()
    if not coord:
        return
    rank = config_mod.env_rank()
    size = config_mod.env_size()
    if rank is None or size is None:
        raise RuntimeError(
            "HOROVOD_SPMD_COORDINATOR is set but HOROVOD_RANK/HOROVOD_SIZE "
            "are not; launch through horovodrun --spmd (or export all three)")
    import jax

    if jax.distributed.is_initialized():
        return
    kwargs = {}
    raw_timeout = (config_mod.env_str("HOROVOD_START_TIMEOUT") or "").strip()
    if raw_timeout:
        # One parser for every HOROVOD_START_TIMEOUT consumer
        # (config.start_timeout_seconds): garbage falls back to the same
        # 120s default the rendezvous windows use, instead of being
        # silently dropped here and honored there. An EXPLICIT <=0 keeps
        # the historical meaning: drop the kwarg and let
        # jax.distributed.initialize apply its own (300s) default.
        try:
            explicit_off = float(raw_timeout) <= 0
        except (ValueError, OverflowError):
            explicit_off = False
        if not explicit_off:
            kwargs["initialization_timeout"] = int(
                config_mod.start_timeout_seconds())
    if rank != 0:
        # Rank 0 HOSTS the coordinator service inside initialize();
        # probing it from rank 0 before the call would always fail.
        _preflight_coordinator(coord)

    def _reset_distributed_state():
        """Best-effort teardown of a HALF-initialized jax.distributed: a
        failed connect leaves global_state.client assigned (State.initialize
        sets it before connecting), so without a reset every retry would
        trip the 'should only be called once' guard and mask the real
        error."""
        try:
            jax.distributed.shutdown()
            return
        except Exception:
            pass
        try:
            from jax._src import distributed as _dist

            _dist.global_state.client = None
            _dist.global_state.service = None
        except Exception:
            pass

    def _attempt():
        from .. import fault

        fault.hook("init_distributed")
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=size,
                process_id=rank,
                **kwargs)
        except Exception:
            _reset_distributed_state()
            raise

    attempts, backoff = retry.init_retry_env()
    retry.retry_call(_attempt, attempts=attempts, backoff=backoff,
                     seed=rank, describe="jax.distributed.initialize")


def _acquire_backend() -> None:
    """Force JAX backend (TPU runtime) acquisition under the init retry
    policy, so a wedged or flaky backend init fails fast and retries
    instead of hanging the rank forever.

    Raises :class:`~horovod_tpu.common.retry.RetryError` when every
    attempt failed: a rank that cannot reach its devices must not carry
    on — there is no CPU fallback and no zero-device mode. A rank that is
    meant to run without an accelerator is told so up front
    (``JAX_PLATFORMS=cpu``; the launcher exports it to every local rank
    it does not bind to a chip).

    Each attempt runs on the ``hvd-deadline-call`` worker thread so a
    hang inside native init surfaces as ``DeadlineExceeded`` instead of
    blocking forever; initializing libtpu from that thread is sound
    (checked on a v5e with libtpu 0.0.34 — ``chip_smoke.py``'s first
    backend touch is this call)."""
    import jax

    from .. import fault as fault_mod

    # Bounded BY DEFAULT: an init that hangs rather than raises would
    # never engage the retry policy without a deadline. 300s is ~30x a
    # healthy cold TPU init; 0 disables.
    per_attempt = config_mod._env_float("HOROVOD_TPU_INIT_TIMEOUT", 300.0)

    def _attempt():
        fault_mod.hook("init")
        # device_count materializes the platform backend.
        return retry.run_with_deadline(
            jax.local_device_count, per_attempt, "jax backend init")

    attempts, backoff = retry.init_retry_env()
    retry.retry_call(_attempt, attempts=attempts, backoff=backoff,
                     seed=config_mod.env_rank() or 0,
                     describe="jax backend acquisition")


def init(ranks: Optional[Sequence[int]] = None) -> None:
    """Initialize horovod_tpu. Idempotent, like the reference's
    ``InitializeHorovodOnce`` (``horovod/common/operations.cc:1566-1583``).

    ``ranks`` restricts the job to a subset of processes, mirroring
    ``hvd.init(ranks)`` (``horovod/common/basics.py:29-55``). mpi4py
    communicators are not supported — there is no MPI on TPU; pass ``ranks``
    or use the launcher's env instead.
    """
    global _state
    with _state_lock:
        if _state is not None and _state.initialized:
            return
        config = Config.from_env()
        logging.configure(config.log_level, config.log_hide_timestamp)
        # Launcher-spawned ranks arm the parent-death watchdog (reference
        # spark/task/mpirun_exec_fn.py:25-35): an orphaned rank must kill
        # itself, not hold ring ports until a peer timeout. Runtime import:
        # run/ imports common/ at module load.
        from ..run.watchdog import maybe_install_from_env

        maybe_install_from_env()
        profiler.install_compile_listeners()
        with profiler.span("init"):
            _init_locked(config, ranks)


def _init_locked(config: Config, ranks: Optional[Sequence[int]]) -> None:
    """The body of :func:`init`, under ``_state_lock`` and the ``init``
    span; its children say what a slow start was waiting for."""
    global _state
    with profiler.span("init.distributed"):
        _maybe_init_jax_distributed()
    with profiler.span("init.backend"):
        _acquire_backend()
    with profiler.span("init.topology"):
        topology = detect(ranks)
    with profiler.span("init.controller"):
        logging.set_rank(topology.rank)
        _state = HorovodTpuState(config, topology)
        if metrics.on():
            metrics.record_event(
                "init", size=topology.size,
                restart_epoch=config_mod._env_int(
                    "HOROVOD_RESTART_EPOCH", 0))
            # Scrape endpoint at HOROVOD_METRICS_PORT + rank (None when the
            # port knob is unset — snapshot() keeps working without it).
            _state.metrics_exporter = metrics.maybe_start_exporter(
                topology.rank)
        # Engine selection for the multi-process eager tier: the native C++
        # engine (negotiation + fusion + cache + timeline in engine.cc over
        # the TCP ring) is the default whenever the launcher exported ring
        # addresses; HOROVOD_ENGINE=python (or the star data plane) keeps the
        # Python controller. The choice must be identical on every rank —
        # both derive from launcher-exported env, so it is. Tracing
        # (HOROVOD_TRACE_DIR) no longer steers this choice: since round 14
        # the native engine stamps the same span vocabulary into its C
        # ring (docs/tracing.md), so traced jobs keep the fast path; only
        # elastic membership still requires the python controller below.
        from .config import ring_data_plane_enabled

        engine = config_mod.engine()
        if engine is None:
            engine = "native" if ring_data_plane_enabled() else "python"
        if config_mod.elastic_enabled() and engine == "native":
            # Elastic membership lives in the Python controller (the native
            # engine's ring is fixed-membership); the pin must be identical
            # on every rank — it derives from launcher-exported env, so it
            # is. horovodrun --elastic already exports the python engine.
            logging.warning(
                "HOROVOD_ELASTIC=1 requires the python controller engine; "
                "overriding the native engine selection (docs/elastic.md)")
            engine = "python"
        use_native = topology.size > 1 and engine == "native"
        if config.timeline_filename and topology.rank == 0 and not use_native:
            # Native engine writes the timeline itself (C++ writer thread).
            from .timeline import Timeline

            _state.timeline = Timeline(config.timeline_filename,
                                       mark_cycles=config.timeline_mark_cycles)
        if use_native:
            from ..controller.native import NativeController

            _state.controller = NativeController(config, topology)
        elif topology.size > 1 and config_mod.controller_addr():
            # Python controller over the TCP star.
            from ..controller.controller import Controller

            _state.controller = Controller(config, topology,
                                           timeline=_state.timeline)
        logging.debug(
            "horovod_tpu initialized: rank=%d size=%d local_rank=%d "
            "local_size=%d devices=%d/%d",
            topology.rank, topology.size, topology.local_rank,
            topology.local_size, topology.local_num_devices,
            topology.num_devices,
        )


def replace_topology(topology: Topology) -> None:
    """Elastic-reshape hook (``controller/controller.py``): swap the global
    state's topology after a membership change so ``hvd.rank()``/
    ``hvd.size()`` and the log prefix track the re-formed world. Runs on
    the controller thread (or the init thread for a joiner's admission);
    deliberately lock-free — the topology reference swap is atomic and
    ``_state_lock`` may be held by the very ``init()`` that is admitting
    a joiner."""
    if _state is not None:
        _state.topology = topology
    logging.set_rank(topology.rank)


def shutdown() -> None:
    """Tear down background services (reference ``horovod_shutdown``,
    ``operations.cc:1605-1614``)."""
    global _state
    with _state_lock:
        if _state is not None:
            _state.close()
            _state = None


atexit.register(shutdown)


def _ensure_initialized() -> HorovodTpuState:
    # The reference raises "Horovod has not been initialized; use hvd.init()"
    # from every API entry point (horovod/common/operations.cc:1587-1593).
    if _state is None or not _state.initialized:
        raise ValueError(
            "Horovod has not been initialized; use hvd.init().")
    return _state


def state() -> HorovodTpuState:
    return _ensure_initialized()


def controller():
    """The running eager-tier background controller, or a curated error.

    Shared guard for every framework adapter (torch/tf/mxnet/ops): the eager
    data plane needs the TCP controller that ``horovodrun`` bootstraps."""
    st = state()
    if st.controller is None:
        raise RuntimeError(
            "eager collectives at size > 1 require the background controller; "
            "launch through horovodrun (which exports HOROVOD_CONTROLLER_ADDR) "
            "or use the SPMD tier (collectives inside jit/shard_map over a "
            "multi-host mesh)")
    return st.controller


def is_initialized() -> bool:
    return _state is not None and _state.initialized


def rank() -> int:
    return _ensure_initialized().topology.rank


def size() -> int:
    return _ensure_initialized().topology.size


def local_rank() -> int:
    return _ensure_initialized().topology.local_rank


def local_size() -> int:
    return _ensure_initialized().topology.local_size


def cross_rank() -> int:
    return _ensure_initialized().topology.cross_rank


def cross_size() -> int:
    return _ensure_initialized().topology.cross_size


def num_devices() -> int:
    """Total accelerator chips in the job (TPU extension; the reference has no
    equivalent because rank==GPU there)."""
    return _ensure_initialized().topology.num_devices


def local_num_devices() -> int:
    return _ensure_initialized().topology.local_num_devices


def mpi_threads_supported() -> bool:
    """Parity shim for ``hvd.mpi_threads_supported()``
    (``horovod/common/basics.py:96-104``). There is no MPI in the TPU runtime;
    the controller's TCP plane is always thread-safe, so report True."""
    _ensure_initialized()
    return True
