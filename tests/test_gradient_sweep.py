"""Numeric-gradient sweep across the differentiable op surface
(reference test_operator.py's per-op check_numeric_gradient discipline,
SURVEY.md §4 — VERDICT r2 flagged gradient checks as applied to only a
handful of ops; this file applies them systematically).

Each case: an op closure over small float inputs chosen inside the op's
smooth domain (away from kinks/branch points), reduced to a scalar; the
tape's gradient must match central finite differences.
"""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import ndarray as nd
from incubator_mxnet_tpu.test_utils import check_numeric_gradient


def _tols():
    """TPU tolerance ladder (README.md "Running it"). The noisy side on
    TPU is the FINITE DIFFERENCE, not the op: transcendental-approximation
    error on each scalar eval (~2e-4 over a summed (3,4) input) divides by
    2*eps, bounding FD noise at ~2e-2 absolute for eps=1e-2 — verified for
    log_softmax by checking the analytic grad against the exact f64
    formula (1.7e-6 agreement). Wrong-vjp bugs are O(1) off, so the
    widened bound keeps the sweep's power."""
    import jax

    if jax.default_backend() == "tpu":
        return dict(eps=1e-2, rtol=3e-2, atol=2e-2)
    return dict(eps=1e-3, rtol=1e-2, atol=1e-3)


rs = np.random.RandomState(42)

# inputs in safe smooth domains
X = rs.uniform(0.3, 0.9, (3, 4)).astype(np.float32)       # (0, 1) open
P = rs.uniform(1.2, 2.5, (3, 4)).astype(np.float32)       # > 1
S = rs.uniform(-0.8, 0.8, (3, 4)).astype(np.float32)      # symmetric
M4 = rs.uniform(0.5, 1.5, (4, 4)).astype(np.float32)
V6 = rs.uniform(0.2, 1.0, (6,)).astype(np.float32)

UNARY = [
    ("sigmoid", nd.sigmoid, S), ("tanh", nd.tanh, S),
    ("relu_smooth", nd.softrelu, S), ("gelu", nd.gelu, S),
    ("silu", nd.silu, S), ("mish", nd.mish, S),
    ("softsign", nd.softsign, S), ("log_sigmoid", nd.log_sigmoid, S),
    ("exp", nd.exp, S), ("expm1", nd.expm1, S), ("exp2", nd.exp2, S),
    ("log", nd.log, P), ("log10", nd.log10, P), ("log2", nd.log2, P),
    ("log1p", nd.log1p, X), ("sqrt", nd.sqrt, P), ("rsqrt", nd.rsqrt, P),
    ("cbrt", nd.cbrt, P), ("rcbrt", nd.rcbrt, P),
    ("square", nd.square, S), ("reciprocal", nd.reciprocal, P),
    ("sin", nd.sin, S), ("cos", nd.cos, S), ("tan", nd.tan, S),
    ("arcsin", nd.arcsin, S), ("arccos", nd.arccos, S),
    ("arctan", nd.arctan, S), ("sinh", nd.sinh, S), ("cosh", nd.cosh, S),
    ("arcsinh", nd.arcsinh, S), ("arccosh", nd.arccosh, P),
    ("arctanh", nd.arctanh, S), ("erf", nd.erf, S), ("erfc", nd.erfc, S),
    ("gamma_fn", nd.gamma, P), ("gammaln", nd.gammaln, P),
    ("digamma", nd.digamma, P), ("sinc", nd.sinc, P),
    ("softmax", lambda x: nd.softmax(x, axis=-1), S),
    ("log_softmax", lambda x: nd.log_softmax(x, axis=-1), S),
    ("logsumexp", lambda x: nd.logsumexp(x, axis=-1), S),
    ("cumsum", lambda x: nd.cumsum(x, axis=1), S),
    ("cumprod", lambda x: nd.cumprod(x, axis=1), P),
    ("std", lambda x: nd.std(x, axis=1), S),
    ("var", lambda x: nd.var(x, axis=1), S),
    ("norm", nd.norm, P),
    ("tril", nd.tril, S), ("triu", nd.triu, S),
    ("roll", lambda x: nd.roll(x, shift=1, axis=1), S),
    ("diff", lambda x: nd.diff(x, axis=1), S),
    ("l2_normalization", nd.L2Normalization, P),
    ("smooth_l1", nd.smooth_l1, S),
]

BINARY = [
    ("elemwise_mul", nd.elemwise_mul, S, S),
    ("elemwise_div", nd.elemwise_div, S, P),
    ("broadcast_power", nd.broadcast_power, P, S),
    ("broadcast_hypot", nd.broadcast_hypot, P, P),
    ("logaddexp", nd.logaddexp, S, S),
    ("copysign_fixed_sign", nd.copysign, P, P),
    ("dot", nd.dot, M4, M4),
    ("kron", nd.kron, M4[:2, :2], M4[2:, 2:]),
    ("outer", nd.outer, V6, V6),
    ("inner", nd.inner, M4, M4),
    ("tensordot", lambda a, b: nd.tensordot(a, b, axes=1), M4, M4),
    ("vdot", nd.vdot, M4, M4),
    ("polyval", nd.polyval, V6[:3], S),
    ("convolve", nd.convolve, V6, V6[:3]),
    ("maximum_sep", nd.broadcast_maximum, P, X),  # P > 1 > X: no ties
]


@pytest.mark.parametrize("name,op,arr", UNARY, ids=[c[0] for c in UNARY])
def test_unary_gradient(name, op, arr):
    check_numeric_gradient(lambda x: op(x).sum(), [nd.array(arr)],
                           **_tols())


@pytest.mark.parametrize("name,op,a,b", BINARY, ids=[c[0] for c in BINARY])
def test_binary_gradient(name, op, a, b):
    check_numeric_gradient(lambda x, y: op(x, y).sum(),
                           [nd.array(a), nd.array(b)], **_tols())


def test_loss_gradients():
    from incubator_mxnet_tpu import gluon

    y = nd.array(S)
    # label offset keeps pred-label in [-3.7, -1.5]: >=0.5 away from the
    # L1 kink (0) and the Huber transition (-1), so FD never crosses them
    t = nd.array(X + 2.0)
    for loss in (gluon.loss.L2Loss(), gluon.loss.L1Loss(),
                 gluon.loss.HuberLoss(), gluon.loss.LogisticLoss()):
        check_numeric_gradient(lambda p: loss(p, t).sum(), [y], **_tols())


def test_norm_layer_gradients():
    g = nd.array(rs.uniform(0.5, 1.5, (4,)).astype(np.float32))
    b = nd.array(rs.uniform(-0.5, 0.5, (4,)).astype(np.float32))
    x = nd.array(rs.uniform(-1, 1, (3, 4)).astype(np.float32))
    check_numeric_gradient(
        lambda xx: nd.LayerNorm(xx, g, b, axis=-1).sum(), [x],
        rtol=2e-2, atol=2e-3)
    check_numeric_gradient(
        lambda xx: nd.rms_norm(xx, g).sum(), [x], rtol=2e-2, atol=2e-3)
