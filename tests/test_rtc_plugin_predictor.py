"""RTC (Pallas user kernels), torch plugin, Predictor tests."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym, nd


def test_pallas_kernel_basic():
    from jax.experimental import pallas as pl

    def scale_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    k = mx.rtc.PallasKernel(scale_kernel, out_like=0, interpret=True)
    y = k(nd.ones((8, 128)))
    assert (y.asnumpy() == 2.0).all()


def test_pallas_kernel_two_inputs():
    def addmul_kernel(a_ref, b_ref, o_ref):
        o_ref[...] = a_ref[...] * b_ref[...] + a_ref[...]

    k = mx.rtc.PallasKernel(addmul_kernel, out_like=0, interpret=True)
    a = np.random.rand(8, 128).astype(np.float32)
    b = np.random.rand(8, 128).astype(np.float32)
    y = k(nd.array(a), nd.array(b))
    assert np.allclose(y.asnumpy(), a * b + a, rtol=1e-5)


def test_rtc_cuda_shim_errors():
    with pytest.raises(mx.MXNetError):
        mx.rtc.Rtc("k", [], [], "__global__ void k(){}")


def test_torch_module_forward_backward():
    torch = pytest.importorskip("torch")
    import torch.nn as tnn
    from mxnet_tpu.plugin.torch_module import TorchModule

    lin = tnn.Linear(4, 3)
    op = TorchModule(lin)
    x = np.random.rand(2, 4).astype(np.float32)
    y = op(nd.array(x))
    with torch.no_grad():
        expect = lin(torch.from_numpy(x)).numpy()
    assert np.allclose(y.asnumpy(), expect, rtol=1e-5)

    # symbolic with gradient through the torch module
    s = op.get_symbol(sym.Variable("data"))
    ag = nd.zeros((2, 4))
    ex = s.bind(mx.cpu(), {"data": nd.array(x)}, args_grad={"data": ag})
    ex.forward(is_train=True)
    ex.backward(out_grads=nd.ones((2, 3)))
    expect_grad = np.ones((2, 3), np.float32) @ lin.weight.detach().numpy()
    assert np.allclose(ag.asnumpy(), expect_grad, rtol=1e-4)


def test_torch_criterion():
    torch = pytest.importorskip("torch")
    import torch.nn as tnn
    from mxnet_tpu.plugin.torch_module import TorchCriterion

    crit = TorchCriterion(tnn.MSELoss())
    x = np.array([[1.0, 2.0]], np.float32)
    t = np.array([[0.0, 0.0]], np.float32)
    loss = crit(nd.array(x), nd.array(t))
    assert np.allclose(loss.asnumpy(), [(1 + 4) / 2], rtol=1e-5)


def test_predictor_roundtrip(tmp_path):
    # train a tiny model, checkpoint, predict via the standalone Predictor
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=4, name="fc")
    net = sym.SoftmaxOutput(data=net, name="softmax")
    X = np.random.rand(32, 6).astype(np.float32)
    y = (np.arange(32) % 4).astype(np.float32)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, 8), num_epoch=1)
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 1)

    pred = mx.Predictor(prefix + "-symbol.json", prefix + "-0001.params",
                        {"data": (8, 6)})
    out = pred.forward(data=X[:8]).get_output(0)
    ref = mod.predict(mx.io.NDArrayIter(X[:8], None, 8)).asnumpy()
    assert np.allclose(out.asnumpy(), ref, rtol=1e-5)


def test_kvstore_server_role_collapse(monkeypatch):
    import mxnet_tpu.kvstore_server as ks
    monkeypatch.setenv("DMLC_ROLE", "server")
    with pytest.raises(RuntimeError):
        ks.init()
    monkeypatch.setenv("DMLC_ROLE", "worker")
    ks.init()  # no coordinator env: returns without error
