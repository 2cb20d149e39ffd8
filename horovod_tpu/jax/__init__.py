"""JAX user API — the flagship adapter (the reference's equivalents are the
TF/Torch/MXNet adapters, e.g. ``horovod/torch/__init__.py``).

Key differences from the reference, by design:

* ``DistributedOptimizer`` wraps an **optax** ``GradientTransformation``: the
  gradient allreduce becomes part of the (jit-compiled) update function, so
  on TPU it lowers to XLA all-reduce over ICI fused with the optimizer math —
  there is no per-parameter hook machinery (``torch/__init__.py:95-130``)
  because SPMD needs none.
* ``broadcast_parameters``/``broadcast_optimizer_state`` keep the reference's
  checkpoint-consistency contract (rank 0 state wins,
  ``torch/__init__.py:200-343``): in multi-process mode they broadcast leaf by
  leaf through the controller; in single-controller SPMD mode state is
  already consistent and they are cheap no-ops that still validate root_rank.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import optax

from ..common import basics, profiler
from ..compression import Compression
from .zero import zero_sharded_optimizer  # noqa: F401
from .fsdp import (  # noqa: F401
    fsdp_param_specs,
    fsdp_shardings,
    fsdp_state_specs,
)
from ..ops import collective_ops as C

__all__ = [
    "DistributedOptimizer",
    "distributed_value_and_grad",
    "zero_sharded_optimizer",
    "fsdp_param_specs",
    "fsdp_state_specs",
    "fsdp_shardings",
    "broadcast_parameters",
    "broadcast_optimizer_state",
]


def _allreduce_tree(tree, average: bool, axis_name: Optional[str],
                    name_prefix: str, compression=None):
    """Allreduce every leaf. Eager tier enqueues all leaves asynchronously
    before joining so the fusion engine can pack them into one fused
    collective per ~64 MiB bucket — the JAX analogue of the reference firing
    per-parameter hooks then joining in ``synchronize()``
    (``torch/__init__.py:114-151``)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    if isinstance(leaves[0], jax.core.Tracer):
        # Under jit, compression is a dtype cast XLA fuses into the
        # collective: the psum moves half the bytes over ICI/DCN and the
        # result is cast back to the original dtype. Only worth doing when
        # the axis is actually bound (shard_map): on the pjit-style
        # identity fallback the round-trip would truncate gradients for
        # zero wire savings.
        axis = C._resolve_axis(axis_name)
        try:
            axis_size = jax.lax.axis_size(axis)
        except NameError:
            axis_size = None
        compress_traced = compression is not None and axis_size is not None
        wire, reduced = [], []
        for i, g in enumerate(leaves):
            if compress_traced:
                g, ctx = compression.compress(g)
            wire.append(g)
            # Named like the eager tier names its timeline activities:
            # the hvd.allreduce.<prefix>.<i> scope lands in HLO metadata
            # and profiler traces (see common/profiler.py).
            r = C.allreduce(g, average=average, axis_name=axis_name,
                            name=f"{name_prefix}.{i}")
            if compress_traced:
                r = compression.decompress(r, ctx)
            reduced.append(r)
        # What this exchange asked for, kept while the step is traced.
        profiler.record_exchange(name_prefix, axis, axis_size, leaves, wire,
                                 average)
        return jax.tree_util.tree_unflatten(treedef, reduced)
    st = basics.state()
    if st.topology.size == 1:
        return tree
    handles = [
        C.allreduce_async(g, average=average, name=f"{name_prefix}.{i}",
                          compression=compression)
        for i, g in enumerate(leaves)
    ]
    reduced = [h.wait() for h in handles]
    return jax.tree_util.tree_unflatten(treedef, reduced)


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    average: bool = True,
    axis_name: Optional[str] = None,
    name: str = "DistributedOptimizer",
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so gradients are averaged across ranks before
    the update (reference ``hvd.DistributedOptimizer``,
    ``horovod/torch/__init__.py:42-175`` / ``tensorflow/__init__.py:146-244``).

    ``backward_passes_per_step > 1`` reproduces the reference's local gradient
    accumulation (``torch/__init__.py:71-73``) via ``optax.MultiSteps``: the
    cross-rank reduction fires once per applied step.

    ``compression`` applies on both tiers: on the eager tier it shrinks the
    wire format; under jit it casts the gradient before the psum (XLA fuses
    the cast into the collective, halving ICI/DCN bytes for
    ``Compression.bf16``/``fp16``) and casts the result back.
    """

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(updates, state, params=None, **extra):
        with jax.named_scope(profiler.SCOPE_EXCHANGE):
            reduced = _allreduce_tree(updates, average=average,
                                      axis_name=axis_name, name_prefix=name,
                                      compression=compression)
        with jax.named_scope(profiler.SCOPE_UPDATE):
            return optimizer.update(reduced, state, params, **extra)

    tx = optax.GradientTransformation(init_fn, update_fn)
    if backward_passes_per_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
    return tx


def distributed_value_and_grad(
    fun: Callable,
    argnums=0,
    average: bool = True,
    axis_name: Optional[str] = None,
    **vag_kwargs,
) -> Callable:
    """``jax.value_and_grad`` with cross-rank gradient averaging — the JAX
    analogue of ``hvd.DistributedGradientTape``
    (``horovod/tensorflow/__init__.py:247-321``). As in the reference, only
    gradients are reduced; the returned loss stays per-rank (average it
    explicitly with ``hvd.allreduce`` if you log it)."""
    vag = jax.value_and_grad(fun, argnums=argnums, **vag_kwargs)

    def wrapped(*args, **kwargs):
        value, grads = vag(*args, **kwargs)
        with jax.named_scope(profiler.SCOPE_EXCHANGE):
            grads = _allreduce_tree(grads, average=average,
                                    axis_name=axis_name,
                                    name_prefix="DistributedGrad")
        return value, grads

    return wrapped


def broadcast_parameters(params: Any, root_rank: int = 0) -> Any:
    """Return ``params`` with every leaf replaced by root's value
    (reference ``horovod/torch/__init__.py:178-230``). Functional: JAX arrays
    are immutable, so unlike the reference this returns the new tree."""
    st = basics.state()
    if st.topology.size == 1:
        if root_rank != 0:
            raise ValueError(f"root_rank {root_rank} out of range for size 1")
        return params
    leaves, treedef = jax.tree_util.tree_flatten(params)
    handles = [
        C.broadcast_async(p, root_rank=root_rank, name=f"broadcast.param.{i}")
        for i, p in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, [h.wait() for h in handles])


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0) -> Any:
    """Broadcast optimizer state from root (reference
    ``horovod/torch/__init__.py:232-348``). optax states are pytrees of
    arrays, so this is plain tree broadcast — none of the reference's
    scalar-wrapping gymnastics are needed."""
    return broadcast_parameters(opt_state, root_rank=root_rank)
