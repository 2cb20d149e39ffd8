"""From the names the program plants in its compiled step to device time
by phase and by kernel.

A traced operation is known by its instruction name alone
(``trace_reduce.Trace``); the compiled program's text says under which
scopes that instruction was traced: every instruction line carries
``metadata={op_name="jit(step)/.../transpose(jvp(Model))/layer_3/..."}``,
and a fusion names the computation that holds its inner instructions
(``calls=%fused_computation.48``). :func:`op_names` reads both, and
:func:`phase` sorts an ``op_name`` into one phase of a training step.

The scope strings below are the benchmark's own copy of the program's
vocabulary (``horovod_tpu/common/profiler.py``), as
``hlo_text.collectives`` is of the program's regex: a program that renames
a scope reads as ``none`` here and shows in what no phase explains.
Checked on a hand-worked text and on heads recorded on the chip in
``tests/benchmark/test_scopes.py``.
"""

import functools
import re

from . import trace_reduce

# Precedence, first match wins. The exchange sits inside jax's
# ``transpose(`` when gradients are reduced where they are made, and the
# optimizer's update is traced under neither, so the program's own scopes
# go first. ``transpose(`` before ``jvp(``: the backward pass is
# ``transpose(jvp(...))``.
PHASES = (
    ("exchange", ("hvd.exchange", "hvd.allreduce")),
    ("update", ("hvd.update",)),
    ("backward", ("transpose(",)),
    ("forward", ("jvp(",)),
)
NONE, MIXED = "none", "mixed"

COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
INSTRUCTION_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")


def phase(op_name):
    """The phase of a training step an ``op_name`` belongs to."""
    for name, marks in PHASES:
        if any(mark in op_name for mark in marks):
            return name
    return NONE


@functools.lru_cache(maxsize=2)
def _parse(text):
    """``(own, calls, members)``: instruction -> its own ``op_name``,
    instruction -> the computation it calls, computation -> its
    instructions. Kept for the last texts asked about: every reader of a
    run asks about the same one."""
    own, calls, members = {}, {}, {}
    inside = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = COMPUTATION_RE.match(line)
            inside = m.group(1) if m else None
            if inside is not None:
                members[inside] = []
            continue
        m = INSTRUCTION_RE.match(line)
        if not m or inside is None:
            continue
        name = m.group(1)
        members[inside].append(name)
        found = OP_NAME_RE.search(line)
        if found:
            own[name] = found.group(1)
        called = CALLS_RE.search(line)
        if called:
            calls[name] = called.group(1)
    return own, calls, members


def own_op_names(text):
    """Instruction name -> the ``op_name`` on its own line: for a fusion,
    the one XLA chose to call the whole fusion by."""
    return _parse(text)[0]


@functools.lru_cache(maxsize=2)
def op_names(text):
    """Instruction name -> the set of ``op_name``s it stands for: its own
    and, for a fusion, those of the instructions inside the computation it
    calls (nested calls followed). Instructions with no metadata and no
    call are left out."""
    own, calls, members = _parse(text)

    def gather(name, seen):
        names = {own[name]} if name in own else set()
        target = calls.get(name)
        if target is not None and target not in seen:
            seen.add(target)
            for inner in members.get(target, ()):
                names |= gather(inner, seen)
        return names

    out = {}
    for name in set(own) | set(calls):
        names = gather(name, set())
        if names:
            out[name] = names
    return out


def phase_of(names):
    """One phase for the ``op_name``s of one instruction (a fusion's inner
    instructions).

    * Inner instructions of no phase (a cast or a constant XLA pulled in)
      do not count against the phase of the rest.
    * Forward instructions inside a fusion that also holds backward ones
      are recomputation: XLA copies cheap producers (a norm's scaling, an
      activation) into the backward fusion that consumes them, and an
      operation that holds a ``transpose(`` instruction cannot run before
      the backward pass has begun. Such a fusion is ``backward``.
    * Any other pair (the last operation of a gradient with the
      optimizer's arithmetic in its epilogue, say) is ``mixed``: the time
      belongs to both and the names cannot divide it."""
    found = {phase(n) for n in names} - {NONE}
    if "backward" in found:
        found.discard("forward")
    if not found:
        return NONE
    return found.pop() if len(found) == 1 else MIXED


@functools.lru_cache(maxsize=2)
def phases(text):
    """Instruction name -> phase, for every instruction of the text that
    says where it came from."""
    return {name: phase_of(names) for name, names in op_names(text).items()}


def phase_ns(trace, text):
    """phase -> nanoseconds in which an operation of that phase ran,
    averaged over the devices: per device the union of those operations'
    intervals, as busy time is counted. An operation the text does not
    explain is ``none``."""
    by_name = phases(text)
    out = {}
    for events in trace.devices.values():
        intervals = {}
        for name, start, duration in events:
            intervals.setdefault(by_name.get(name, NONE), []).append(
                (start, start + duration))
        for p, found in intervals.items():
            out[p] = out.get(p, 0) + trace_reduce.total(
                trace_reduce.union(found))
    return {p: ns / len(trace.devices) for p, ns in out.items()}


@functools.lru_cache(maxsize=2)
def phases_planted(text):
    """The phases some instruction of the text was traced under, alone or
    inside a fusion: what the program planted, whatever XLA fused."""
    return {phase(o) for names in op_names(text).values() for o in names}


def phase_ms_a_step(run, names):
    """Milliseconds a step in the phases ``names``, from a run's trace
    and compiled text. ``None`` where there is no device trace or the
    program plants none of them; 0.0 where it plants one and XLA left no
    operation of that phase alone (all of it fused with another's)."""
    trace = run.get("trace")
    if trace is None or not trace.devices:
        return None
    text = run["compiled_text"]
    by_phase = phase_ns(trace, text)
    if not any(n in by_phase or n in phases_planted(text) for n in names):
        return None
    return sum(by_phase.get(name, 0) for name in names) / run["steps"] / 1e6


def kernel_ms_a_step(run, kernel):
    """Milliseconds a step in the Mosaic calls named ``kernel``, or
    ``None``."""
    trace = run.get("trace")
    if trace is None:
        return None
    ns = kernel_ns(trace, run["compiled_text"], kernel)
    return None if ns is None else ns / run["steps"] / 1e6


def kernel_names(trace, text, kernel):
    """Names of the traced Mosaic calls whose ``op_name`` holds the scope
    ``kernel`` (a ``pallas_call``'s ``name=``) as a whole word."""
    word = re.compile(r"(?<![\w.])" + re.escape(kernel) + r"(?![\w.])")
    names = op_names(text)
    return {n for n in trace.kernels
            if any(word.search(op) for op in names.get(n, ()))}


def kernel_ns(trace, text, kernel):
    """Nanoseconds of the Mosaic calls of one kernel name, summed per
    device and averaged over the devices; ``None`` where the trace holds
    no such call."""
    keep = kernel_names(trace, text, kernel)
    if not keep or not trace.devices:
        return None
    sums = [sum(d for name, _, d in events if name in keep)
            for events in trace.devices.values()]
    return sum(sums) / len(sums)


def module_path(op_name, depth):
    """The flax module path inside an ``op_name``, cut to ``depth``
    components: what follows the transform wrappers and the program's own
    scopes (``jit(step)/jit(main)/transpose(jvp(BertMLM))/layer_3/
    SelfAttention_0/dot_general`` -> ``layer_3/SelfAttention_0`` at depth
    2). The last component is the primitive and is dropped."""
    parts = [p for p in op_name.split("/")[:-1]
             if "(" not in p and not p.startswith("hvd.")
             and p not in ("shard_map", "pjit", "closed_call")]
    return "/".join(parts[:depth]) or "(top)"
