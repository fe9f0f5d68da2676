"""mx.operator Custom ops + the final op-parity wave (interleaved
attention matmuls, arange_like/broadcast_like/reshape_like, nan_to_num,
SVMOutput, index ops) — reference test_operator.py custom-op section."""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, operator as mxop
from incubator_mxnet_tpu import ndarray as nd


@mxop.register("test_square")
class SquareProp(mxop.CustomOpProp):
    def create_operator(self, ctx, shapes, dtypes):
        outer = self

        class Square(mxop.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                self.assign(out_data[0], req[0],
                            in_data[0] * in_data[0])

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                self.assign(in_grad[0], req[0],
                            2.0 * in_data[0] * out_grad[0])

        return Square()


def test_custom_op_forward_backward():
    x = nd.array(np.array([1.0, -2.0, 3.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.Custom(x, op_type="test_square")
    y.backward(nd.ones_like(y))
    np.testing.assert_allclose(y.asnumpy(), [1, 4, 9], rtol=1e-6)
    np.testing.assert_allclose(x.grad.asnumpy(), [2, -4, 6], rtol=1e-6)


def test_custom_op_inside_hybridized_block():
    from incubator_mxnet_tpu.gluon import nn

    class Net(nn.HybridSequential):
        def forward(self, x):
            h = super().forward(x)
            return nd.Custom(h, op_type="test_square")

    net = Net()
    net.add(nn.Dense(4, in_units=3))
    net.initialize(init="xavier")
    x = nd.array(np.random.RandomState(0).rand(2, 3).astype(np.float32))
    eager = net(x).asnumpy()
    net.hybridize()
    net(x)                                        # compile (pure_callback)
    np.testing.assert_allclose(net(x).asnumpy(), eager, rtol=1e-5,
                               atol=1e-6)


def test_custom_op_unknown_type_raises():
    with pytest.raises(ValueError, match="no custom op"):
        nd.Custom(nd.zeros((2,)), op_type="never_registered")


def test_interleaved_selfatt_matches_reference_math():
    rng = np.random.RandomState(0)
    T, N, H, D = 5, 2, 3, 4
    qkv = rng.randn(T, N, 3 * H * D).astype(np.float32)
    att = nd.interleaved_matmul_selfatt_qk(nd.array(qkv), heads=H)
    x = qkv.reshape(T, N, H, 3, D)
    q = np.transpose(x[:, :, :, 0], (1, 2, 0, 3)).reshape(N * H, T, D)
    k = np.transpose(x[:, :, :, 1], (1, 2, 0, 3)).reshape(N * H, T, D)
    v = np.transpose(x[:, :, :, 2], (1, 2, 0, 3)).reshape(N * H, T, D)
    np.testing.assert_allclose(
        att.asnumpy(), (q / np.sqrt(D)) @ k.transpose(0, 2, 1),
        rtol=1e-4, atol=1e-5)
    w = nd.softmax(att, axis=-1)
    out = nd.interleaved_matmul_selfatt_valatt(nd.array(qkv), w, heads=H)
    want = np.transpose(
        (w.asnumpy() @ v).reshape(N, H, T, D), (2, 0, 1, 3)
    ).reshape(T, N, H * D)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


def test_interleaved_encdec():
    rng = np.random.RandomState(1)
    TQ, TK, N, H, D = 3, 5, 2, 2, 4
    q = rng.randn(TQ, N, H * D).astype(np.float32)
    kv = rng.randn(TK, N, 2 * H * D).astype(np.float32)
    att = nd.interleaved_matmul_encdec_qk(nd.array(q), nd.array(kv),
                                          heads=H)
    assert att.shape == (N * H, TQ, TK)
    w = nd.softmax(att, axis=-1)
    out = nd.interleaved_matmul_encdec_valatt(nd.array(kv), w, heads=H)
    assert out.shape == (TQ, N, H * D)
    assert np.isfinite(out.asnumpy()).all()


def test_shape_derived_and_index_ops():
    rng = np.random.RandomState(2)
    a = nd.array(rng.rand(2, 3).astype(np.float32))
    np.testing.assert_allclose(nd.arange_like(a, axis=1).asnumpy(),
                               [0, 1, 2])
    assert nd.arange_like(a).asnumpy().shape == (2, 3)
    np.testing.assert_allclose(
        nd.broadcast_like(nd.array(np.ones((1, 3), np.float32)),
                          a).shape, (2, 3))
    np.testing.assert_allclose(
        nd.reshape_like(nd.array(np.arange(6, dtype=np.float32)),
                        a).shape, (2, 3))
    np.testing.assert_allclose(
        nd.nan_to_num(nd.array(np.array([np.nan, 1.0], np.float32))
                      ).asnumpy(), [0, 1])

    data = nd.array(np.arange(6, dtype=np.float32).reshape(3, 2))
    idx = nd.array(np.array([1, 0, 1], np.float32))
    np.testing.assert_allclose(
        nd.choose_element_0index(data, idx).asnumpy(), [1, 2, 5])
    filled = nd.fill_element_0index(
        data, nd.array(np.array([9.0, 8.0, 7.0], np.float32)), idx)
    np.testing.assert_allclose(filled.asnumpy(),
                               [[0, 9], [8, 3], [4, 7]])
    updated = nd.index_copy(
        data, nd.array(np.array([2], np.float32)),
        nd.array(np.array([[70, 71]], np.float32)))
    np.testing.assert_allclose(updated.asnumpy()[2], [70, 71])


def test_svm_output_grad():
    data = nd.array(np.array([[2.0, 1.0, 0.0]], np.float32))
    label = nd.array(np.array([0.0], np.float32))
    d = data
    d.attach_grad()
    with autograd.record():
        out = nd.SVMOutput(d, label, margin=1.0)
    out.backward()
    np.testing.assert_allclose(out.asnumpy(), data.asnumpy())
    # class 1 violates margin (2-1 = 1, not > margin? 1.0 - 2.0 + 1 = 0);
    # class 2: 0 - 2 + 1 = -1 no. With margin 1: violate iff s_j - s_y + m > 0
    g = d.grad.asnumpy()[0]
    assert g[0] <= 0 and np.isfinite(g).all()


def test_sparse_retain_rows():
    data = nd.array(np.arange(8, dtype=np.float32).reshape(4, 2))
    out = nd.sparse_retain_rows(
        data, nd.array(np.array([0, 2], np.float32))).asnumpy()
    np.testing.assert_allclose(out, [[0, 1], [0, 0], [4, 5], [0, 0]])
