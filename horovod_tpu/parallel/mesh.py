"""Device-mesh utilities — the TPU-native substrate for data parallelism.

The reference's unit of parallelism is the *process* (one per GPU), with NCCL
rings built at runtime (``horovod/common/ops/nccl_operations.cc:111-153``). On
TPU the unit is the *chip* on a ``jax.sharding.Mesh``: XLA lowers collectives
onto ICI rings/tori automatically from sharding annotations, so "building the
ring" is replaced by "choosing the mesh".

The reference only implements data parallelism (SURVEY.md §2.3), so the default
mesh is 1-D over every chip with axis name ``"data"``. The helpers accept
arbitrary extra axes (``model``, ``seq``, ...) because the same substrate
carries TP/SP — see ``horovod_tpu.parallel`` extensions.
"""

from __future__ import annotations

import threading
from typing import Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..common import profiler

DATA_AXIS = "data"

_lock = threading.Lock()
_global_mesh: Optional[Mesh] = None


def axis_size(axis_name: str) -> int:
    """Size of a mesh axis, callable inside ``shard_map``/``pmap``."""
    return jax.lax.psum(1, axis_name)


@profiler.span("make_mesh")
def make_mesh(
    axes: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a mesh. Default: 1-D ``("data",)`` over all visible devices.

    ``axes`` maps axis name -> size; one axis may be -1 (inferred). Axis order
    matters on hardware: earlier axes change slowest, and XLA maps the
    trailing axes onto the densest ICI dimension, so put the
    highest-bandwidth-demand axis (e.g. ``model``) last.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if not axes:
        axes = {DATA_AXIS: n}
    names = tuple(axes.keys())
    sizes = [int(s) for s in axes.values()]
    if sizes.count(-1) > 1:
        raise ValueError("at most one mesh axis may be -1")
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1])) or 1
        if n % known:
            raise ValueError(f"cannot infer axis: {n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"mesh axes {dict(zip(names, sizes))} != {n} devices")
    arr = np.array(devices).reshape(sizes)
    return Mesh(arr, names)


def make_multislice_mesh(
    n_slices: Optional[int] = None,
    dcn_axis: str = "dcn",
    ici_axis: str = "ici",
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """A 2-D ``(dcn, ici)`` mesh for multi-slice topologies: the outer axis
    crosses slice boundaries (slow DCN), the inner axis stays within a
    slice (fast ICI) — feed it to
    ``hierarchical_allreduce(inner_axis=ici_axis, outer_axis=dcn_axis)``
    (the ``NCCLHierarchicalAllreduce`` analogue; see docs/running.md).

    On a real multi-slice runtime the grouping comes from each device's
    ``slice_index``; elsewhere (virtual CPU devices, single slice split
    for testing) pass ``n_slices`` to group contiguously.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None not in slice_ids and len(slice_ids) > 1:
        by_slice = {}
        for d in devices:
            by_slice.setdefault(d.slice_index, []).append(d)
        per = {len(v) for v in by_slice.values()}
        if len(per) != 1:
            raise ValueError(
                f"unequal slice sizes {sorted(per)}: cannot build a "
                "rectangular (dcn, ici) mesh")
        if n_slices is not None and n_slices != len(by_slice):
            raise ValueError(
                f"n_slices={n_slices} but the runtime reports "
                f"{len(by_slice)} slices")
        arr = np.array([by_slice[s] for s in sorted(by_slice)])
        return Mesh(arr, (dcn_axis, ici_axis))
    if n_slices is None:
        raise ValueError(
            "n_slices is required to split these devices: they form a "
            "single slice or carry no slice_index (virtual platforms)")
    if n % n_slices:
        raise ValueError(f"{n} devices not divisible by {n_slices} slices")
    arr = np.array(devices).reshape(n_slices, n // n_slices)
    return Mesh(arr, (dcn_axis, ici_axis))


def mesh() -> Mesh:
    """The process-global mesh, lazily a 1-D data mesh over all devices."""
    global _global_mesh
    with _lock:
        if _global_mesh is None:
            _global_mesh = make_mesh()
        return _global_mesh


def set_mesh(m: Mesh) -> None:
    global _global_mesh
    with _lock:
        _global_mesh = m


def reset_mesh() -> None:
    global _global_mesh
    with _lock:
        _global_mesh = None


def sharding_axes(x) -> Optional[tuple]:
    """Per-dimension mesh-axis names of an array placed with a
    ``NamedSharding``: a tuple of axis-name tuples, one per dim (``()``
    = that dim is replicated). Returns ``None`` when the value carries
    no ``NamedSharding`` (host numpy, tracers, other sharding types) —
    callers treat that as "unknown", not "replicated".

    The decode-path classifier (``models.llama``) uses this to recognize
    the Megatron TP pattern (heads sharded on exactly one axis) without
    hard-coding axis names."""
    sh = getattr(x, "sharding", None)
    spec = getattr(sh, "spec", None)
    if spec is None or not isinstance(sh, NamedSharding):
        return None
    ndim = getattr(x, "ndim", None)
    if ndim is None:
        return None
    out = []
    for i in range(ndim):
        entry = spec[i] if i < len(spec) else None
        if entry is None:
            out.append(())
        elif isinstance(entry, str):
            out.append((entry,))
        else:
            out.append(tuple(entry))
    return tuple(out)


def common_mesh(tree) -> Optional[Mesh]:
    """The single ``Mesh`` shared by every ``NamedSharding`` leaf of
    ``tree``; ``None`` when no leaf carries one OR the leaves disagree
    (mixed meshes are "exotic" to every consumer of this helper)."""
    found = None
    for leaf in jax.tree_util.tree_leaves(tree):
        sh = getattr(leaf, "sharding", None)
        if not isinstance(sh, NamedSharding):
            continue
        if found is None:
            found = sh.mesh
        elif sh.mesh != found:
            return None
    return found


def data_sharding(m: Optional[Mesh] = None, *dims_after_batch: Optional[str]) -> NamedSharding:
    """Sharding for a batch: leading dim split over every mesh axis named
    ``data``-like; remaining dims follow ``dims_after_batch`` (default
    replicated)."""
    m = m or mesh()
    return NamedSharding(m, PartitionSpec(DATA_AXIS, *dims_after_batch))


def replicated_sharding(m: Optional[Mesh] = None) -> NamedSharding:
    m = m or mesh()
    return NamedSharding(m, PartitionSpec())


def shard_batch(tree, m: Optional[Mesh] = None):
    """Place a host pytree on the mesh, batch dim split along ``data``.

    TPU-native replacement for the reference pattern of each process loading
    its own shard (``examples/tensorflow_mnist.py`` dataset sharding by rank):
    one controller process places the global batch; XLA scatters it.
    """
    m = m or mesh()
    sh = NamedSharding(m, PartitionSpec(DATA_AXIS))
    with profiler.span("shard_batch"):
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def replicate(tree, m: Optional[Mesh] = None):
    """Replicate a pytree (params/optimizer state) across the mesh."""
    m = m or mesh()
    sh = NamedSharding(m, PartitionSpec())
    with profiler.span("replicate"):
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)
