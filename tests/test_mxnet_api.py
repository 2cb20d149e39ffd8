"""MXNet adapter surface, size-1 semantics (reference test/test_mxnet.py
scope, minus multi-rank: test_multiprocess_frameworks.py, "mxnet").

Runs against tests/fake_mxnet.py since mxnet is EOL and absent from CI; the
fake implements only the surfaces the adapter touches, so these tests pin
the adapter's logic (rescale folding, deferred-init injection, unwrap
warning), not MXNet itself."""

import sys
import warnings

import numpy as np
import pytest

import fake_mxnet

mx = fake_mxnet.module()
sys.modules.setdefault("mxnet", mx)

import horovod_tpu.mxnet as hvd_mx  # noqa: E402


@pytest.fixture(autouse=True)
def _hvd_init():
    hvd_mx.init()
    yield


def test_ops_size1_roundtrip():
    x = mx.nd.array(np.arange(6, dtype=np.float32))
    out = hvd_mx.allreduce(x, average=True, name="ar")
    np.testing.assert_allclose(out.asnumpy(), np.arange(6))
    assert out is not x

    y = mx.nd.array(np.ones(4, dtype=np.float32))
    assert hvd_mx.allreduce_(y, average=False, name="ar_") is y

    g = hvd_mx.allgather(x, name="ag")
    np.testing.assert_allclose(g.asnumpy(), np.arange(6))

    b = hvd_mx.broadcast(x, root_rank=0, name="bc")
    np.testing.assert_allclose(b.asnumpy(), np.arange(6))
    assert hvd_mx.broadcast_(y, root_rank=0, name="bc_") is y

    assert hvd_mx.size() == 1 and hvd_mx.rank() == 0

    with pytest.raises(ValueError, match="root_rank"):
        hvd_mx.broadcast(x, root_rank=3)
    with pytest.raises(ValueError, match="root_rank"):
        hvd_mx.broadcast_(y, root_rank=1)


def test_distributed_optimizer_rescale_and_update():
    opt = mx.optimizer.Optimizer(learning_rate=0.5, rescale_grad=2.0)
    dopt = hvd_mx.DistributedOptimizer(opt)
    # size()==1: rescale_grad divided by 1 — unchanged; semantics: avg via
    # rescale (reference mxnet/__init__.py:41-43).
    assert opt.rescale_grad == 2.0

    w = mx.nd.array(np.ones(3, dtype=np.float32))
    g = mx.nd.array(np.ones(3, dtype=np.float32))
    dopt.update(0, w, g, None)
    np.testing.assert_allclose(w.asnumpy(), 1.0 - 0.5 * 2.0 * 1.0)
    assert opt.updates == [0]

    # list-of-index form triggers per-grad allreduce then one update each
    w2 = mx.nd.array(np.zeros(2, dtype=np.float32))
    dopt.update_multi_precision([1, 2], w2, [g, g], None)
    assert opt.updates == [0, [1, 2]]

    # delegation through __getattr__ and the explicit setters
    dopt.set_learning_rate(0.1)
    assert opt.lr == 0.1
    dopt.set_lr_mult({"a": 1.0})
    dopt.set_wd_mult({"a": 0.0})
    assert dopt.lr == 0.1  # __getattr__ delegation


def test_distributed_trainer_unwraps_and_scales():
    opt = mx.optimizer.Optimizer(learning_rate=1.0)
    dopt = hvd_mx.DistributedOptimizer(opt)
    p = fake_mxnet.Parameter(
        "w", data=mx.nd.array(np.ones(2, dtype=np.float32)),
        grad=mx.nd.array(np.full(2, 3.0, dtype=np.float32)))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        trainer = hvd_mx.DistributedTrainer(
            [p], dopt, optimizer_params={"rescale_grad": 4.0})
    assert any("unwrapped" in str(w.message) for w in caught)
    assert trainer._optimizer is opt
    assert trainer._scale == 4.0  # / size()==1

    trainer.step(batch_size=1)
    np.testing.assert_allclose(
        p.data().asnumpy(), 1.0 - 1.0 * 4.0 * 3.0)

    skip = fake_mxnet.Parameter("frozen", data=mx.nd.array([0.0]),
                                grad=None, grad_req="null")
    trainer2 = hvd_mx.DistributedTrainer([skip], opt)
    trainer2.step(batch_size=1)  # must not touch null-grad params
    np.testing.assert_allclose(skip.data().asnumpy(), [0.0])


def test_distributed_trainer_unwrap_no_double_divide(monkeypatch):
    """At size>1 the unwrap path must yield _scale = rescale/size, not
    rescale/size**2 (wrapper already divided rescale_grad once)."""
    monkeypatch.setattr(hvd_mx, "size", lambda: 4)
    opt = mx.optimizer.Optimizer(learning_rate=1.0, rescale_grad=2.0)
    dopt = hvd_mx.DistributedOptimizer(opt)
    assert opt.rescale_grad == 0.5  # 2.0 / 4
    p = fake_mxnet.Parameter(
        "w", data=mx.nd.array(np.ones(2, dtype=np.float32)),
        grad=mx.nd.array(np.ones(2, dtype=np.float32)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        trainer = hvd_mx.DistributedTrainer([p], dopt)
    # unwrap restored rescale_grad to 2.0, then _scale = 2.0 / 4.
    assert trainer._optimizer is opt
    assert trainer._scale == 0.5
    trainer.step(batch_size=1)
    # real-gluon semantics: step writes rescale_grad = _scale / batch_size
    assert opt.rescale_grad == 0.5


def test_broadcast_parameters_dict_and_deferred():
    d = {"b": mx.nd.array(np.ones(2)), "a": mx.nd.array(np.zeros(2))}
    hvd_mx.broadcast_parameters(d)  # size 1: no-op, must not raise

    pd = mx.gluon.parameter.ParameterDict()
    pd["ready"] = fake_mxnet.Parameter(
        "ready", data=mx.nd.array(np.ones(3)))
    deferred = fake_mxnet.Parameter("deferred")
    pd["deferred"] = deferred
    hvd_mx.broadcast_parameters(pd)

    # deferred parameter: broadcast injected into its init hook
    deferred._init_impl(np.full(3, 7.0))
    np.testing.assert_allclose(deferred.data().asnumpy(), 7.0)

    with pytest.raises(ValueError, match="invalid params"):
        hvd_mx.broadcast_parameters([1, 2, 3])


def test_resize_eval_data_iter_size1():
    class FakeIter:
        def __init__(self, n):
            self.n = n
            self.resets = 0

        def __iter__(self):
            return iter(range(self.n))

        def reset(self):
            self.resets += 1

    it = FakeIter(5)
    resized = hvd_mx.ResizeEvalDataIter(it)
    assert isinstance(resized, mx.io.ResizeIter)
    assert resized.size == 5
    assert it.resets == 1


def test_distributed_eval_metric_size1():
    Metric = hvd_mx.DistributedEvalMetric(fake_mxnet.EvalMetric)
    m = Metric()
    labels = [mx.nd.array(np.arange(4))]
    preds = [mx.nd.array(np.arange(4) + 1)]
    m.update(labels, preds)
    assert m.num_updates == 1
    np.testing.assert_allclose(m.seen[0][1][0], np.arange(4) + 1)
