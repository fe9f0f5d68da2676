"""numpy-parity op wave + mx.np / mx.npx front (reference MXNet 2.x
``mx.np``/``mx.npx``, SURVEY.md §2.2 ndarray row). numpy is the oracle."""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import ndarray as nd

rs = np.random.RandomState(0)


def _chk(got, want, rtol=1e-5, atol=1e-6):
    np.testing.assert_allclose(np.asarray(got.asnumpy()), want,
                               rtol=rtol, atol=atol)


# (op call on nd, numpy oracle) pairs over shared inputs
A = rs.rand(3, 4).astype(np.float32) + 0.5
B = rs.rand(3, 4).astype(np.float32) + 0.5
V = rs.rand(7).astype(np.float32)
M = rs.rand(4, 4).astype(np.float32)

CASES = [
    ("exp2", lambda: nd.exp2(nd.array(A)), lambda: np.exp2(A)),
    ("logaddexp", lambda: nd.logaddexp(nd.array(A), nd.array(B)),
     lambda: np.logaddexp(A, B)),
    ("copysign", lambda: nd.copysign(nd.array(A), nd.array(B - 1.0)),
     lambda: np.copysign(A, B - 1.0)),
    ("fmod", lambda: nd.fmod(nd.array(A), nd.array(B)),
     lambda: np.fmod(A, B)),
    ("floor_divide", lambda: nd.floor_divide(nd.array(A * 5),
                                             nd.array(B + 0.5)),
     lambda: np.floor_divide(A * 5, B + 0.5)),
    ("std", lambda: nd.std(nd.array(A), axis=1),
     lambda: A.std(axis=1)),
    ("var_ddof", lambda: nd.var(nd.array(A), axis=0, ddof=1),
     lambda: A.var(axis=0, ddof=1)),
    ("average_w", lambda: nd.average(nd.array(A), axis=1,
                                     weights=np.arange(4.0)),
     lambda: np.average(A, axis=1, weights=np.arange(4.0))),
    ("median", lambda: nd.median(nd.array(A), axis=1),
     lambda: np.median(A, axis=1)),
    ("percentile", lambda: nd.percentile(nd.array(A), q=30.0),
     lambda: np.percentile(A, 30.0)),
    ("ptp", lambda: nd.ptp(nd.array(A), axis=0), lambda: np.ptp(A, axis=0)),
    ("cumprod", lambda: nd.cumprod(nd.array(A), axis=1),
     lambda: np.cumprod(A, axis=1)),
    ("nanmean", lambda: nd.nanmean(nd.array(A)), lambda: np.nanmean(A)),
    ("roll", lambda: nd.roll(nd.array(A), shift=2, axis=1),
     lambda: np.roll(A, 2, axis=1)),
    ("rot90", lambda: nd.rot90(nd.array(A)), lambda: np.rot90(A)),
    ("tril", lambda: nd.tril(nd.array(M)), lambda: np.tril(M)),
    ("triu_k", lambda: nd.triu(nd.array(M), k=1), lambda: np.triu(M, 1)),
    ("trace", lambda: nd.trace_op(nd.array(M)), lambda: np.trace(M)),
    ("flipud", lambda: nd.flipud(nd.array(A)), lambda: np.flipud(A)),
    ("moveaxis", lambda: nd.moveaxis(nd.array(A), source=0, destination=1),
     lambda: np.moveaxis(A, 0, 1)),
    ("diff", lambda: nd.diff(nd.array(A), axis=1),
     lambda: np.diff(A, axis=1)),
    ("kron", lambda: nd.kron(nd.array(A[:2, :2]), nd.array(M[:2, :2])),
     lambda: np.kron(A[:2, :2], M[:2, :2])),
    ("outer", lambda: nd.outer(nd.array(V), nd.array(V)),
     lambda: np.outer(V, V)),
    ("inner", lambda: nd.inner(nd.array(A), nd.array(B)),
     lambda: np.inner(A, B)),
    ("vdot", lambda: nd.vdot(nd.array(A), nd.array(B)),
     lambda: np.vdot(A, B)),
    ("tensordot", lambda: nd.tensordot(nd.array(A), nd.array(A.T), axes=1),
     lambda: np.tensordot(A, A.T, axes=1)),
    ("cross", lambda: nd.cross(nd.array(A[:, :3]), nd.array(B[:, :3])),
     lambda: np.cross(A[:, :3], B[:, :3])),
    ("polyval", lambda: nd.polyval(nd.array(V[:3]), nd.array(A)),
     lambda: np.polyval(V[:3], A)),
    ("trapz", lambda: nd.trapz(nd.array(V)), lambda: np.trapezoid(V)),
    ("convolve", lambda: nd.convolve(nd.array(V), nd.array(V[:3])),
     lambda: np.convolve(V, V[:3])),
    ("searchsorted", lambda: nd.searchsorted(nd.array(np.sort(V)),
                                             nd.array(A.ravel())),
     lambda: np.searchsorted(np.sort(V), A.ravel())),
    ("vander", lambda: nd.vander(nd.array(V), n=3),
     lambda: np.vander(V, 3)),
    ("sinc", lambda: nd.sinc(nd.array(A)), lambda: np.sinc(A)),
    ("heaviside", lambda: nd.heaviside(nd.array(A - 1.0), nd.array(B)),
     lambda: np.heaviside(A - 1.0, B)),
]


@pytest.mark.parametrize("name,got,want", CASES,
                         ids=[c[0] for c in CASES])
def test_numpy_wave_oracle(name, got, want):
    w = np.asarray(want())
    _chk(got(), w, rtol=2e-4, atol=2e-5)


def test_dynamic_shape_eager_ops():
    x = nd.array(np.array([3, 1, 3, 2, 1], np.float32))
    np.testing.assert_array_equal(nd.unique(x).asnumpy(), [1, 2, 3])
    nz = nd.nonzero(nd.array(np.array([[1, 0], [0, 2]], np.float32)))
    np.testing.assert_array_equal(nz[0].asnumpy(), [0, 1])
    np.testing.assert_array_equal(nz[1].asnumpy(), [0, 1])
    bc = nd.bincount(nd.array(np.array([0, 1, 1, 3], np.float32)))
    np.testing.assert_array_equal(bc.asnumpy(), [1, 2, 0, 1])
    h, e = nd.histogram(nd.array(np.arange(10, dtype=np.float32)), bins=5)
    np.testing.assert_array_equal(h.asnumpy(), [2, 2, 2, 2, 2])
    np.testing.assert_array_equal(
        nd.intersect1d(x, nd.array(np.array([2, 3], np.float32))).asnumpy(),
        [2, 3])


def test_numpy_wave_autograd():
    """Differentiable wave ops participate in the tape."""
    x = mx.nd.array(A)
    x.attach_grad()
    with mx.autograd.record():
        y = nd.logaddexp(x, mx.nd.array(B))
        z = nd.tril(y).sum()
    z.backward()
    g = x.grad.asnumpy()
    want = np.tril(1.0 / (1.0 + np.exp(B - A)))
    np.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-5)


def test_mx_np_namespace():
    a = mx.np.array([[1.0, 2.0], [3.0, 4.0]])
    np.testing.assert_allclose(mx.np.add(a, a).asnumpy(),
                               [[2, 4], [6, 8]])
    np.testing.assert_allclose(
        mx.np.einsum("ij,jk->ik", a, a).asnumpy(), [[7, 10], [15, 22]])
    np.testing.assert_allclose(
        mx.np.concatenate([a, a], axis=0).asnumpy().shape, (4, 2))
    np.testing.assert_allclose(mx.np.linspace(0, 1, 5).asnumpy(),
                               np.linspace(0, 1, 5))
    assert mx.np.full_like(a, 7.0).asnumpy().tolist() == [[7, 7], [7, 7]]
    g = mx.np.meshgrid(mx.np.arange(3), mx.np.arange(2))
    assert g[0].shape == (2, 3)
    s = mx.np.random.randn(3, 2)
    assert s.shape == (3, 2)
    assert isinstance(a, mx.np.ndarray)


def test_mx_npx_namespace():
    a = mx.np.array([[1.0, 2.0], [3.0, 4.0]])
    sm = mx.npx.softmax(a).asnumpy()
    np.testing.assert_allclose(sm.sum(axis=-1), [1.0, 1.0], rtol=1e-6)
    mx.npx.set_np()
    assert mx.npx.is_np_array()
    mx.npx.reset_np()
    assert not mx.npx.is_np_array()


def test_clip_by_global_norm_op():
    a = nd.array(np.ones((4,), np.float32) * 3.0)
    b = nd.array(np.ones((2,), np.float32) * 4.0)
    out_a, out_b = nd.clip_by_global_norm(a, b, max_norm=1.0)
    total = np.sqrt((out_a.asnumpy() ** 2).sum() +
                    (out_b.asnumpy() ** 2).sum())
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


def test_mx_np_positional_signatures():
    """numpy's canonical positional call shapes must work on mx.np."""
    a = mx.np.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    assert mx.np.reshape(a, (4, 3)).shape == (4, 3)
    assert mx.np.transpose(a).shape == (4, 3)
    assert mx.np.expand_dims(a, 0).shape == (1, 3, 4)
    assert mx.np.squeeze(mx.np.expand_dims(a, 0), 0).shape == (3, 4)
    np.testing.assert_allclose(mx.np.clip(a, 2.0, 5.0).asnumpy(),
                               np.clip(np.arange(12).reshape(3, 4), 2, 5))
    np.testing.assert_allclose(mx.np.roll(a, 1).asnumpy(),
                               np.roll(np.arange(12.).reshape(3, 4), 1))
    assert mx.np.moveaxis(a, 0, 1).shape == (4, 3)
    np.testing.assert_allclose(mx.np.repeat(a, 2, 1).shape, (3, 8))
    assert mx.np.tile(a, (2, 1)).shape == (6, 4)
    parts = mx.np.split(a, 2, 1)
    assert parts[0].shape == (3, 2)
    np.testing.assert_allclose(
        float(mx.np.quantile(a, 0.5).asnumpy()),
        np.quantile(np.arange(12.).reshape(3, 4), 0.5))
    np.testing.assert_allclose(
        float(mx.np.percentile(a, 30).asnumpy()),
        np.percentile(np.arange(12.).reshape(3, 4), 30), rtol=1e-6)
    assert mx.np.tensordot(a, mx.np.transpose(a), 1).shape == (3, 3)
    assert mx.np.partition(a, 1).shape == (3, 4)
    assert mx.np.resize(a, (2, 2)).shape == (2, 2)
    np.testing.assert_allclose(
        mx.np.take(a, mx.np.array([0, 5]).astype(np.int32)).asnumpy(),
        [0.0, 5.0])
    assert mx.np.trace(a).shape == ()
    assert mx.np.flip(a, 1).shape == (3, 4)
    # bool bitwise semantics (numpy): invert(bool) is logical not
    b = mx.np.array(np.array([True, False]))
    np.testing.assert_array_equal(mx.np.invert(b).asnumpy(),
                                  [False, True])


def test_np_linalg_namespace():
    a_np = np.array([[4.0, 1.0], [1.0, 3.0]], np.float32)  # SPD
    a = mx.np.array(a_np)
    np.testing.assert_allclose(mx.np.linalg.det(a).asnumpy(),
                               np.linalg.det(a_np), rtol=1e-5)
    np.testing.assert_allclose(mx.np.linalg.inv(a).asnumpy(),
                               np.linalg.inv(a_np), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        mx.np.linalg.solve(a, mx.np.array([1.0, 2.0])).asnumpy(),
        np.linalg.solve(a_np, [1.0, 2.0]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(mx.np.linalg.cholesky(a).asnumpy(),
                               np.linalg.cholesky(a_np), rtol=1e-4,
                               atol=1e-5)
    w, v = mx.np.linalg.eigh(a)
    wn, _ = np.linalg.eigh(a_np)
    np.testing.assert_allclose(w.asnumpy(), wn, rtol=1e-4, atol=1e-5)
    q, r = mx.np.linalg.qr(a)
    np.testing.assert_allclose((q.asnumpy() @ r.asnumpy()), a_np,
                               rtol=1e-4, atol=1e-5)
    u, s, vh = mx.np.linalg.svd(a)
    np.testing.assert_allclose(
        u.asnumpy() @ np.diag(s.asnumpy()) @ vh.asnumpy(), a_np,
        rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        float(mx.np.linalg.norm(a).asnumpy()), np.linalg.norm(a_np),
        rtol=1e-5)
    np.testing.assert_allclose(
        mx.np.linalg.matrix_power(a, 3).asnumpy(),
        np.linalg.matrix_power(a_np, 3), rtol=1e-4)
    assert int(mx.np.linalg.matrix_rank(a).asnumpy()) == 2


def test_np_fft_roundtrip():
    x_np = rs.rand(8, 16).astype(np.float32)
    x = mx.np.array(x_np)
    f = mx.np.fft.fft(x)
    np.testing.assert_allclose(f.asnumpy(), np.fft.fft(x_np),
                               rtol=1e-4, atol=1e-4)
    back = mx.np.fft.ifft(f)
    np.testing.assert_allclose(back.asnumpy().real, x_np, rtol=1e-4,
                               atol=1e-5)
    rf = mx.np.fft.rfft(x)
    np.testing.assert_allclose(rf.asnumpy(), np.fft.rfft(x_np),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(mx.np.fft.irfft(rf, n=16).asnumpy(), x_np,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        mx.np.fft.fftshift(x).asnumpy(), np.fft.fftshift(x_np))
    # real/imag/conj/angle surface
    np.testing.assert_allclose(nd.real(f).asnumpy(), np.fft.fft(x_np).real,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(nd.angle(f).asnumpy(),
                               np.angle(np.fft.fft(x_np)), rtol=1e-3,
                               atol=1e-3)


def test_fft_gradient():
    """FFT ops differentiate (jax lowers the adjoint FFT)."""
    x = mx.nd.array(rs.rand(8).astype(np.float32))
    x.attach_grad()
    with mx.autograd.record():
        y = nd.real(nd.invoke_op("fft", x)).sum()
    y.backward()
    # d/dx sum(Re(FFT(x))) = column sums of the real DFT matrix
    W = np.fft.fft(np.eye(8))
    want = W.real.sum(axis=0)
    np.testing.assert_allclose(x.grad.asnumpy(), want, rtol=1e-4,
                               atol=1e-4)


def test_multi_output_linalg_backward():
    """NamedTuple-returning jnp.linalg ops must present plain tuples to
    the tape (regression: QRResult broke vjp cotangent structure)."""
    a = mx.nd.array(rs.rand(6, 6).astype(np.float32))
    a.attach_grad()
    with mx.autograd.record():
        q, r = nd.invoke_op("linalg_qr", a)
        loss = (q * q).sum() + nd.triu(r).sum()
    loss.backward()
    assert np.isfinite(a.grad.asnumpy()).all()

    spd = rs.rand(6, 6).astype(np.float32)
    spd = spd @ spd.T + 6 * np.eye(6, dtype=np.float32)
    b = mx.nd.array(spd)
    b.attach_grad()
    with mx.autograd.record():
        w, v = nd.invoke_op("linalg_eigh", b)
        l2 = w.sum()
    l2.backward()
    # d(sum of eigenvalues)/dA = I for symmetric A
    np.testing.assert_allclose(b.grad.asnumpy(), np.eye(6), atol=2e-4)


def test_second_completion_wave():
    a = mx.np.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    np.testing.assert_allclose(
        mx.np.nanmedian(a).asnumpy(), 5.5)
    np.testing.assert_allclose(
        mx.np.corrcoef(a).asnumpy(),
        np.corrcoef(np.arange(12.).reshape(3, 4)), rtol=1e-4)
    np.testing.assert_allclose(
        mx.np.take_along_axis(a, mx.np.array(
            np.zeros((3, 1), np.int32)), -1).asnumpy(),
        [[0], [4], [8]])
    g = nd.gradient_op(a, axis=1)
    np.testing.assert_allclose(
        g.asnumpy(), np.gradient(np.arange(12.).reshape(3, 4), axis=1))
    e = nd.extract(nd.array(np.array([1, 0, 1, 0], np.float32)),
                   nd.array(np.arange(4, dtype=np.float32)))
    np.testing.assert_array_equal(e.asnumpy(), [0, 2])
    # put_along_axis (out-of-place)
    out = nd.put_along_axis(a, nd.array(np.zeros((3, 1), np.float32)),
                            nd.array(np.full((3, 1), 9.0, np.float32)),
                            axis=-1)
    assert out.asnumpy()[0, 0] == 9.0
    # autograd through take_along_axis
    x = mx.nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    x.attach_grad()
    with mx.autograd.record():
        y = nd.take_along_axis(x, mx.nd.array(
            np.zeros((3, 1), np.float32)), axis=-1).sum()
    y.backward()
    assert x.grad.asnumpy()[:, 0].sum() == 3.0


def test_wave2_remaining_oracles():
    x = np.array([0.0, 0.8, 6.5, 7.0], np.float32)   # wraps past pi
    np.testing.assert_allclose(
        nd.unwrap(nd.array(x)).asnumpy(), np.unwrap(x), rtol=1e-5)
    a = rs.rand(3, 4).astype(np.float32)
    a[0, 0] = np.nan
    np.testing.assert_allclose(
        float(nd.nanquantile(nd.array(a), q=0.5).asnumpy()),
        np.nanquantile(a, 0.5), rtol=1e-5)
    np.testing.assert_allclose(
        float(nd.nanpercentile(nd.array(a), q=30).asnumpy()),
        np.nanpercentile(a, 30), rtol=1e-5)
    # select/compress/fmin on a nan-free matrix
    a = rs.rand(3, 4).astype(np.float32)
    conds = np.stack([a < 0.3, a > 0.7]).astype(np.float32)
    choices = np.stack([a * 0, a * 2])
    np.testing.assert_allclose(
        nd.select(nd.array(conds), nd.array(choices), default=-1.0
                  ).asnumpy(),
        np.select([a < 0.3, a > 0.7], [a * 0, a * 2], default=-1.0),
        rtol=1e-6)
    bits = np.array([1, 0, 1, 1, 0, 0, 0, 1], np.float32)
    np.testing.assert_array_equal(
        nd.packbits(nd.array(bits)).asnumpy(),
        np.packbits(bits.astype(np.uint8)))
    np.testing.assert_array_equal(
        nd.unpackbits(nd.packbits(nd.array(bits))).asnumpy(),
        bits.astype(np.uint8))
    c = nd.compress_op(nd.array(np.array([1, 0, 1], np.float32)),
                       nd.array(a[:3]), axis=0)
    np.testing.assert_allclose(c.asnumpy(), a[[0, 2]], rtol=1e-6)
    np.testing.assert_allclose(
        nd.fmin(nd.array(a), nd.array(a * 0 + 0.5)).asnumpy(),
        np.fmin(a, 0.5), rtol=1e-6)


def test_np_dtype_helpers():
    a = mx.np.array([[1.0, 2.0]])
    assert mx.np.result_type(a, np.float64) == np.float64
    assert mx.np.can_cast("int32", "float64")
    assert mx.np.shape(a) == (1, 2)
    assert mx.np.ndim(a) == 2
    assert mx.np.size(a) == 2
    assert mx.np.issubdtype(a.dtype, np.floating)
