"""Torch adapter, single-process semantics (size-1 fast paths + optimizer
wiring). Cross-rank behavior is covered by the "torch" scenario in
tests/test_multiprocess_frameworks.py (reference test/test_torch.py runs
under mpirun)."""

import numpy as np
import pytest
import torch

import horovod_tpu.torch as hvd


def test_ops_size1():
    hvd.init()
    x = torch.arange(6, dtype=torch.float32)
    np.testing.assert_array_equal(hvd.allreduce(x).numpy(), x.numpy())
    np.testing.assert_array_equal(hvd.allgather(x).numpy(), x.numpy())
    np.testing.assert_array_equal(
        hvd.broadcast(x, root_rank=0).numpy(), x.numpy())
    y = x.clone()
    hvd.allreduce_(y)
    np.testing.assert_array_equal(y.numpy(), x.numpy())
    h = hvd.allreduce_async(x)
    assert hvd.poll(h)
    np.testing.assert_array_equal(hvd.synchronize(h).numpy(), x.numpy())


def test_allreduce_grad_size1():
    hvd.init()
    x = torch.ones(4, requires_grad=True)
    y = hvd.allreduce(x, average=True)
    y.sum().backward()
    np.testing.assert_array_equal(x.grad.numpy(), np.ones(4))


def test_distributed_optimizer_step_size1():
    hvd.init()
    model = torch.nn.Linear(3, 1)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    opt = hvd.DistributedOptimizer(
        opt, named_parameters=model.named_parameters())
    x = torch.ones(2, 3)
    loss = model(x).sum()
    loss.backward()
    before = model.weight.detach().clone()
    opt.step()
    assert not torch.allclose(before, model.weight)


def test_distributed_optimizer_duplicate_names():
    hvd.init()
    model = torch.nn.Linear(3, 1)
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    with pytest.raises(ValueError, match="duplicate"):
        hvd.DistributedOptimizer(
            opt, named_parameters=[("a", model.weight), ("a", model.bias)])


def test_broadcast_parameters_size1():
    hvd.init()
    model = torch.nn.Linear(2, 2)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)


def test_broadcast_optimizer_state_size1():
    hvd.init()
    model = torch.nn.Linear(2, 2)
    opt = torch.optim.Adam(model.parameters(), lr=0.01)
    # State is empty before any step: the materialization path must run.
    hvd.broadcast_optimizer_state(opt, root_rank=0)
    assert len(opt.state_dict()["state"]) > 0


def test_compression_roundtrip():
    x = torch.linspace(-2, 2, 7)
    c, ctx = hvd.Compression.fp16.compress(x)
    assert c.dtype == torch.float16
    out = hvd.Compression.fp16.decompress(c, ctx)
    assert out.dtype == torch.float32
    np.testing.assert_allclose(out.numpy(), x.numpy(), atol=1e-3)


def test_bf16_roundtrip_size1():
    hvd.init()
    t = torch.linspace(-2, 2, 8).to(torch.bfloat16)
    out = hvd.allreduce(t, average=True)
    assert out.dtype == torch.bfloat16
    np.testing.assert_allclose(out.float().numpy(), t.float().numpy())
    g = hvd.allgather(t)
    assert g.dtype == torch.bfloat16
