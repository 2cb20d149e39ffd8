"""``parallel/mesh.py``: the mesh a job is placed on and the two ways a
host pytree gets there, over 1, 2, 4 and 8 virtual CPU devices."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.parallel import make_mesh, replicate, shard_batch

SIZES = [1, 2, 4, 8]


def _mesh(n):
    return make_mesh(devices=jax.devices()[:n])


@pytest.mark.parametrize("n", SIZES)
def test_default_mesh_is_one_data_axis_over_the_devices(n):
    mesh = _mesh(n)
    assert mesh.axis_names == ("data",)
    assert dict(mesh.shape) == {"data": n}
    assert list(mesh.devices.flat) == jax.devices()[:n]


@pytest.mark.parametrize("axes,shape", [
    ({"data": 2, "model": 4}, {"data": 2, "model": 4}),
    ({"data": -1, "model": 2}, {"data": 4, "model": 2}),
    ({"data": -1}, {"data": 8}),
])
def test_named_axes_in_order_with_one_inferred(axes, shape):
    mesh = make_mesh(axes)
    assert mesh.axis_names == tuple(axes)
    assert dict(mesh.shape) == shape
    # Earlier axes change slowest.
    assert list(mesh.devices.flat) == jax.devices()


@pytest.mark.parametrize("axes,message", [
    ({"data": -1, "model": -1}, "at most one mesh axis may be -1"),
    ({"data": -1, "model": 3}, "not divisible by 3"),
    ({"data": 2, "model": 2}, "!= 8 devices"),
])
def test_axes_that_do_not_fit_the_devices_are_refused(axes, message):
    with pytest.raises(ValueError, match=message):
        make_mesh(axes)


@pytest.mark.parametrize("n", SIZES)
def test_shard_batch_splits_the_leading_axis_in_device_order(n):
    mesh = _mesh(n)
    tree = {"x": np.arange(8 * 3, dtype=np.float32).reshape(8, 3),
            "y": np.arange(8, dtype=np.int32)}
    placed = shard_batch(tree, mesh)
    per = 8 // n
    for name, host in sorted(tree.items()):
        leaf = placed[name]
        assert leaf.shape == host.shape and leaf.dtype == host.dtype
        assert leaf.sharding.spec == P("data")
        assert leaf.sharding.mesh == mesh
        shards = sorted(leaf.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        assert [s.device for s in shards] == jax.devices()[:n]
        for i, s in enumerate(shards):
            np.testing.assert_array_equal(
                np.asarray(s.data), host[i * per:(i + 1) * per])


@pytest.mark.parametrize("n", SIZES)
def test_replicate_gives_every_device_the_whole_tree(n):
    mesh = _mesh(n)
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "step": np.int32(7)}
    placed = replicate(tree, mesh)
    for name, host in sorted(tree.items()):
        leaf = placed[name]
        assert leaf.shape == np.shape(host)
        assert leaf.sharding.spec == P()
        assert leaf.sharding.device_set == set(jax.devices()[:n])
        assert len(leaf.addressable_shards) == n
        for s in leaf.addressable_shards:
            np.testing.assert_array_equal(np.asarray(s.data), host)


@pytest.mark.parametrize("n", SIZES[1:])
def test_a_batch_the_devices_do_not_divide_is_refused(n):
    with pytest.raises(ValueError, match=f"divisible by {n}"):
        shard_batch(np.zeros((n + 1, 3), np.float32), _mesh(n))
