"""Framework-level collective operations: allreduce / allgather / broadcast
(+ reducescatter / alltoall TPU extensions).

Reference surface: ``horovod/tensorflow/__init__.py:36-87`` (allreduce),
``horovod/torch/mpi_ops.py:124-438`` (sync + async + in-place variants,
poll/synchronize). Semantics preserved:

* ``allreduce(t, average=True)`` returns the elementwise mean (sum when
  ``average=False``) of ``t`` across all ranks.
* ``allgather(t)`` concatenates along dim 0 in rank order.
* ``broadcast(t, root_rank)`` returns root's value everywhere.

Two execution tiers (see ``horovod_tpu.common.basics``):

* **Traced/SPMD** — the argument is a JAX tracer inside ``jit``/``shard_map``:
  the op lowers directly to an XLA collective (``lax.psum`` etc.) over the
  mesh axis. This is the TPU hot path: no negotiation, no fusion engine —
  XLA fuses and schedules on ICI. The reference's dynamic negotiation exists
  to establish exactly the every-rank-runs-the-same-op invariant that SPMD
  already guarantees statically.
* **Eager** — host-driven, per-tensor, across *processes*: routed through the
  background controller (tensor fusion + response cache + timeline + stall
  detection), the parity path for the reference's
  ``EnqueueTensorAllreduce`` machinery (``horovod/common/operations.cc:1654``).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import basics, profiler
from ..common.handles import Handle, HandleManager

# Reduction op constants. The reference expresses Average as a client-side
# divide after Sum (torch/mpi_ops_v2.cc:66-72); we expose both spellings.
Sum = "Sum"
Average = "Average"

_DEFAULT_AXIS = "data"
_axis_lock = threading.Lock()

handle_manager = HandleManager()


def set_default_spmd_axis(name: str) -> None:
    """Mesh axis used when a collective is called on a traced value without an
    explicit ``axis_name``. Default ``"data"`` to match
    ``horovod_tpu.parallel.mesh``."""
    global _DEFAULT_AXIS
    with _axis_lock:
        _DEFAULT_AXIS = name


def _resolve_axis(axis_name: Optional[str]) -> str:
    return axis_name if axis_name is not None else _DEFAULT_AXIS


def _is_traced(tensor) -> bool:
    return isinstance(tensor, jax.core.Tracer)


def _traced_collective(tensor, axis_name, fn, opname: str = "collective",
                       name: Optional[str] = None):
    """Run a lax collective on a traced value.

    The op is traced under ``jax.named_scope("hvd.<opname>[.<name>]")``,
    so profiler traces and lowered HLO metadata carry the same
    user-visible names the eager timeline records — the jit-tier
    counterpart of the reference's timeline activity names
    (``horovod/common/timeline.cc:120``); see ``horovod_tpu.profiler``.

    If the axis name is not bound (plain ``jit``/pjit tracing rather than
    ``shard_map``), fall back to identity: under pjit-style automatic
    parallelism the collective is implicit — XLA derives reductions from the
    sharding annotations — and under single-process tracing (e.g. inside
    ``optax.MultiSteps``' ``lax.cond``) identity is the size-1 semantics."""
    ax = _resolve_axis(axis_name)
    try:
        with jax.named_scope(profiler.collective_scope(opname, name)):
            return fn(tensor, ax)
    except NameError:
        from ..common import hvd_logging as logging

        logging.trace(
            "collective on traced value with unbound axis %r: identity "
            "(pjit-style implicit collectives)", ax)
        return tensor


def _resolve_average(average: Optional[bool], op: Optional[str]) -> bool:
    if op is not None:
        if average is not None:
            raise ValueError("specify either average= or op=, not both")
        return op == Average
    return True if average is None else bool(average)


def _controller():
    return basics.controller()


def _wrap_for(tensor):
    """Result wrapper preserving the caller's container: jax arrays come
    back as jax arrays; anything else (numpy, lists, scalars) comes back as
    numpy with its dtype intact. Wrapping numpy through ``jnp.asarray``
    would silently truncate float64/int64 under jax's default x64-disabled
    mode — the transport preserves dtypes, the wrapper must too."""
    if isinstance(tensor, jax.Array):
        return jnp.asarray
    return np.asarray


def _wrap_value(tensor):
    """Size-1 identity result. Numpy inputs are COPIED: the result must not
    alias the caller's buffer (at size > 1 the controller always returns a
    fresh array, and training code that reuses its gradient buffers must
    behave identically on one chip)."""
    if isinstance(tensor, jax.Array):
        return jnp.asarray(tensor)
    return np.array(tensor)


# ---------------------------------------------------------------------------
# allreduce


def allreduce(tensor, average: Optional[bool] = None, name: Optional[str] = None,
              compression=None, op: Optional[str] = None,
              axis_name: Optional[str] = None):
    """Mean (or sum) of ``tensor`` over all ranks.

    Reference: ``horovod/tensorflow/__init__.py:36-87`` /
    ``horovod/torch/mpi_ops.py:124-154``. ``compression`` applies only on the
    eager tier's wire format (in SPMD, cast before calling — XLA will fuse it).
    """
    avg = _resolve_average(average, op)
    if _is_traced(tensor):
        return _traced_collective(
            tensor, axis_name,
            lambda t, ax: lax.pmean(t, ax) if avg else lax.psum(t, ax),
            opname="allreduce", name=name)
    st = basics.state()
    if st.topology.size == 1:
        return _wrap_value(tensor)
    return _controller().allreduce(tensor, average=avg, name=name,
                                   compression=compression,
                                   wrap=_wrap_for(tensor))


def allreduce_async(tensor, average: Optional[bool] = None,
                    name: Optional[str] = None, op: Optional[str] = None,
                    compression=None) -> Handle:
    """Asynchronous allreduce; join with ``synchronize(handle)``.

    Reference: ``horovod/torch/mpi_ops.py:156-198`` — returns an integer
    handle resolved by the background thread's completion callback."""
    avg = _resolve_average(average, op)
    if _is_traced(tensor):
        raise ValueError(
            "allreduce_async is an eager-tier API; inside jit use allreduce() "
            "(XLA already overlaps collectives with compute)")
    st = basics.state()
    if st.topology.size == 1:
        return handle_manager.completed(_wrap_value(tensor))
    return _controller().allreduce_async(tensor, average=avg, name=name,
                                         compression=compression,
                                         wrap=_wrap_for(tensor))


def grouped_allreduce(tensors, average: Optional[bool] = None,
                      name: Optional[str] = None,
                      op: Optional[str] = None, compression=None,
                      axis_name: Optional[str] = None):
    """Allreduce a LIST of tensors as one group, returning results in the
    same order.

    The pinned reference predates ``grouped_allreduce`` (it arrived in
    later Horovod), but the machinery is the same one Tensor Fusion
    provides: every member is enqueued in the same cycle, the coordinator
    negotiates them together, and same-dtype members pack into one fused
    buffer / one ring pass. On the SPMD tier this is a tree-wise
    ``pmean``/``psum`` — XLA fuses the group itself."""
    if not isinstance(tensors, (list, tuple)):
        raise TypeError("grouped_allreduce expects a list/tuple of tensors")
    avg = _resolve_average(average, op)
    # any(), not tensors[0]: a mixed list (constant first, traced gradient
    # later) must take the traced tier, never hand a Tracer to the
    # host-side controller.
    if any(_is_traced(t) for t in tensors):
        return [
            _traced_collective(
                t, axis_name,
                lambda t_, ax: lax.pmean(t_, ax) if avg else lax.psum(t_, ax),
                opname="grouped_allreduce",
                name=f"{name}.{i}" if name else str(i))
            for i, t in enumerate(tensors)
        ]
    handles = grouped_allreduce_async(tensors, average=avg, name=name,
                                      compression=compression)
    return [h.wait() for h in handles]


def grouped_allreduce_async(tensors, average: Optional[bool] = None,
                            name: Optional[str] = None,
                            op: Optional[str] = None,
                            compression=None) -> list:
    """Async grouped allreduce: returns one handle per member (join with
    ``synchronize``). Members are named ``{name}.{i}`` so the fusion
    engine sees the whole group at once."""
    if not isinstance(tensors, (list, tuple)):
        raise TypeError(
            "grouped_allreduce_async expects a list/tuple of tensors")
    avg = _resolve_average(average, op)
    if any(_is_traced(t) for t in tensors):
        raise ValueError(
            "grouped_allreduce_async is an eager-tier API; inside jit use "
            "grouped_allreduce()")
    st = basics.state()
    if st.topology.size == 1:
        return [handle_manager.completed(_wrap_value(t)) for t in tensors]
    ctrl = _controller()
    # Explicit name -> {name}.{i} per member; otherwise the controller's
    # autonamer keeps concurrent anonymous groups collision-free.
    return [
        ctrl.allreduce_async(t, average=avg,
                             name=None if name is None else f"{name}.{i}",
                             compression=compression, wrap=_wrap_for(t))
        for i, t in enumerate(tensors)
    ]


# ---------------------------------------------------------------------------
# allgather


def allgather(tensor, name: Optional[str] = None,
              axis_name: Optional[str] = None):
    """Concatenation of ``tensor`` from all ranks along dim 0, rank order.

    Reference: ``horovod/tensorflow/mpi_ops.py`` HorovodAllgather /
    ``horovod/torch/mpi_ops.py:200-254``. Eager tier supports differing
    first-dim sizes across ranks (the reference's allgather response carries
    per-rank first dims, ``common/message.h:170-180``); the traced tier
    requires equal shard shapes, as XLA demands static shapes."""
    if _is_traced(tensor):
        return _traced_collective(
            tensor, axis_name,
            lambda t, ax: lax.all_gather(t, ax, tiled=True),
            opname="allgather", name=name)
    st = basics.state()
    if st.topology.size == 1:
        return _wrap_value(tensor)
    return _controller().allgather(tensor, name=name, wrap=_wrap_for(tensor))


def allgather_async(tensor, name: Optional[str] = None) -> Handle:
    if _is_traced(tensor):
        raise ValueError("allgather_async is an eager-tier API")
    st = basics.state()
    if st.topology.size == 1:
        return handle_manager.completed(_wrap_value(tensor))
    return _controller().allgather_async(tensor, name=name,
                                         wrap=_wrap_for(tensor))


# ---------------------------------------------------------------------------
# broadcast


def broadcast(tensor, root_rank: int, name: Optional[str] = None,
              axis_name: Optional[str] = None):
    """Root's ``tensor``, delivered to every rank.

    Reference: ``horovod/torch/mpi_ops.py:256-332``. Traced tier: selects the
    root shard with a masked psum — on TPU this lowers to one all-reduce over
    ICI, the standard XLA broadcast idiom."""
    if _is_traced(tensor):
        def _bcast(t, ax):
            idx = lax.axis_index(ax)
            masked = jnp.where(idx == root_rank, t, jnp.zeros_like(t))
            return lax.psum(masked, ax)

        return _traced_collective(tensor, axis_name, _bcast,
                                  opname="broadcast", name=name)
    st = basics.state()
    if st.topology.size == 1:
        if root_rank != 0:
            raise ValueError(f"root_rank {root_rank} out of range for size 1")
        return _wrap_value(tensor)
    return _controller().broadcast(tensor, root_rank=root_rank, name=name,
                                   wrap=_wrap_for(tensor))


def broadcast_async(tensor, root_rank: int, name: Optional[str] = None) -> Handle:
    if _is_traced(tensor):
        raise ValueError("broadcast_async is an eager-tier API")
    st = basics.state()
    if st.topology.size == 1:
        if root_rank != 0:
            raise ValueError(f"root_rank {root_rank} out of range for size 1")
        return handle_manager.completed(_wrap_value(tensor))
    return _controller().broadcast_async(tensor, root_rank=root_rank,
                                         name=name, wrap=_wrap_for(tensor))


def barrier(name: Optional[str] = None) -> None:
    """Block until every rank has reached the barrier (later-Horovod API;
    eager tier only — inside a compiled SPMD program the lockstep schedule
    IS the barrier). Implemented as a 1-byte allreduce: completion
    requires every rank's participation by construction."""
    st = basics.state()
    if st.topology.size == 1:
        return
    _controller().allreduce(np.zeros(1, np.uint8), average=False,
                            name=name or None)


def broadcast_object(obj, root_rank: int = 0, name: Optional[str] = None):
    """Broadcast an arbitrary picklable Python object from ``root_rank``
    (later-Horovod API; eager tier only). Two collectives: the pickled
    length first — shapes must match on every rank — then the payload.
    The transport is the job's HMAC-authenticated channel; unpickling
    trusts the job's own ranks, exactly like the launcher's wire format."""
    import pickle

    st = basics.state()
    if st.topology.size == 1:
        if root_rank != 0:
            raise ValueError(f"root_rank {root_rank} out of range for size 1")
        return pickle.loads(pickle.dumps(obj))
    base = name or "broadcast_object"
    rank = st.topology.rank
    if rank == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        length = np.array([payload.size], np.int64)
    else:
        payload = None
        length = np.zeros(1, np.int64)
    ctrl = _controller()
    n = int(np.asarray(ctrl.broadcast(length, root_rank=root_rank,
                                      name=f"{base}.len"))[0])
    if payload is None:
        payload = np.zeros(n, np.uint8)
    out = np.asarray(ctrl.broadcast(payload, root_rank=root_rank,
                                    name=f"{base}.data"))
    return pickle.loads(out.tobytes())


def allgather_object(obj, name: Optional[str] = None) -> list:
    """Gather one arbitrary picklable object per rank, returned in rank
    order (later-Horovod API; eager tier only). Rides the allgather's
    variable-first-dim support: each rank contributes its pickled bytes,
    lengths are gathered alongside to split the concatenation."""
    import pickle

    st = basics.state()
    if st.topology.size == 1:
        return [pickle.loads(pickle.dumps(obj))]
    base = name or "allgather_object"
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    ctrl = _controller()
    lengths = np.asarray(ctrl.allgather(
        np.array([payload.size], np.int64), name=f"{base}.len"))
    blob = np.asarray(ctrl.allgather(payload, name=f"{base}.data"))
    out, off = [], 0
    for n in lengths:
        out.append(pickle.loads(blob[off:off + int(n)].tobytes()))
        off += int(n)
    return out


# ---------------------------------------------------------------------------
# TPU extensions (no reference equivalent; documented as such).


def reducescatter(tensor, average: Optional[bool] = None, op: Optional[str] = None,
                  axis_name: Optional[str] = None):
    """Reduce + scatter along dim 0. TPU extension: the reference has no
    user-facing reducescatter (it appears only inside
    ``NCCLHierarchicalAllreduce``, ``nccl_operations.cc:230-247``). On ICI this
    is the bandwidth-optimal half of an allreduce; the eager tier composes
    it from a negotiated allreduce + local slice
    (``controller.composed_reducescatter`` — correctness-first, 2x the
    native wire bytes)."""
    avg = _resolve_average(average, op)
    if _is_traced(tensor):
        def _rs(t, ax):
            out = lax.psum_scatter(t, ax, tiled=True)
            if avg:
                out = out / lax.psum(1, ax)
            return out

        return _traced_collective(tensor, axis_name, _rs,
                                  opname="reducescatter")
    if np.asarray(tensor).ndim == 0:
        # Validate BEFORE the size-1 shortcut: behavior must not depend on
        # world size.
        raise ValueError(
            "reducescatter requires at least one dimension (got a scalar)")
    st = basics.state()
    if st.topology.size == 1:
        return _wrap_value(tensor)
    return _controller().reducescatter(tensor, average=avg,
                                       wrap=_wrap_for(tensor))


def alltoall(tensor, axis_name: Optional[str] = None):
    """Exchange dim-0 splits between ranks. TPU extension (reference lacks
    alltoall; it arrived upstream in Horovod 0.20). Building block for
    Ulysses-style sequence parallelism (``horovod_tpu.parallel.sequence``).
    The eager tier composes it from allgathers
    (``controller.composed_alltoall``); the bandwidth-optimal
    ``lax.all_to_all`` form is the traced path."""
    if _is_traced(tensor):
        def _a2a(t, ax):
            n = lax.psum(1, ax)
            x = t.reshape((n, t.shape[0] // n) + tuple(t.shape[1:]))
            out = lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                                 tiled=False)
            return out.reshape((-1,) + tuple(t.shape[1:]))

        return _traced_collective(tensor, axis_name, _a2a,
                                  opname="alltoall")
    if np.asarray(tensor).ndim == 0:
        # Size-independent validation, as in reducescatter above.
        raise ValueError(
            "alltoall requires at least one dimension (got a scalar)")
    st = basics.state()
    if st.topology.size == 1:
        return _wrap_value(tensor)
    return _controller().alltoall(tensor, wrap=_wrap_for(tensor))


# ---------------------------------------------------------------------------
# handle resolution (reference torch/mpi_ops.py:422-438)


def synchronize(handle: Handle):
    """Block until an async op completes and return its result."""
    return handle.wait()


def poll(handle: Handle) -> bool:
    """True if the async op has completed (reference ``horovod_torch_poll``,
    ``torch/mpi_ops_v2.cc:226-229``)."""
    return handle.done()


def wait(handle: Handle):
    return handle.wait()
