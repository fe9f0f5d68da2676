"""Fused Pallas conv+BN kernel tests (interpret mode on the CPU mesh; the
same code path compiles for the chip — tests/test_chip_compile.py).

v2 coverage: every kernel variant is oracle-proven against the XLA
formulation — blocked forward (output-channel blocking forced via the
``MXTPU_CONV_OC_BLOCK`` knob), strided nb>1, 1x1 projections, and the
Pallas backward kernels (dx transpose-conv with BN-backward prologue +
da/db epilogue, dW contraction) both through the custom vjp and called
directly."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu.config import config
from incubator_mxnet_tpu.ops.pallas_conv import (_conv_bwd_dw_pallas,
                                                 _conv_bwd_dx_pallas,
                                                 _conv_part_ref,
                                                 _fused_conv_ref,
                                                 bn_scale_shift,
                                                 fused_conv_bn)


@contextlib.contextmanager
def knob(name, value):
    config.set(name, value)
    try:
        yield
    finally:
        config.unset(name)


def _rand(rs, shape, dtype=np.float32):
    return jnp.asarray(rs.randn(*shape).astype(np.float32), dtype)


@pytest.mark.parametrize("cfg", [
    # (H, Ci, Co, k, stride, pad) — the ResNet-50 conv shape family, tiny
    dict(h=8, ci=16, co=32, k=1, stride=1, pad=0),
    dict(h=8, ci=16, co=16, k=3, stride=1, pad=1),
    dict(h=9, ci=8, co=16, k=3, stride=2, pad=1),     # odd H downsample
    dict(h=8, ci=16, co=32, k=1, stride=2, pad=0),    # 1x1 downsample
    dict(h=7, ci=8, co=8, k=3, stride=1, pad=1),
])
def test_fused_conv_matches_xla(cfg):
    rs = np.random.RandomState(0)
    n = 2
    x = _rand(rs, (n, cfg["h"], cfg["h"], cfg["ci"]))
    w = _rand(rs, (cfg["k"], cfg["k"], cfg["ci"], cfg["co"])) * 0.1
    y, s, ss = fused_conv_bn(x, w, stride=cfg["stride"], pad=cfg["pad"])
    yr, sr, ssr = _fused_conv_ref(x, w, None, None, cfg["stride"],
                                  cfg["pad"], True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr),
                               rtol=1e-4, atol=1e-4)


def test_fused_conv_prologue_matches_xla():
    rs = np.random.RandomState(1)
    x = _rand(rs, (2, 8, 8, 16))
    w = _rand(rs, (3, 3, 16, 32)) * 0.1
    a = jnp.asarray(rs.rand(16).astype(np.float32) + 0.5)
    b = _rand(rs, (16,))
    for relu in (True, False):
        y, s, ss = fused_conv_bn(x, w, a, b, stride=1, pad=1, relu=relu)
        yr, sr, ssr = _fused_conv_ref(x, w, a, b, 1, 1, relu)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"relu={relu}")
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr),
                                   rtol=1e-4, atol=1e-4)


def test_fused_conv_stats_equal_batchnorm_stats():
    """The epilogue stats must reproduce exactly what a separate BatchNorm
    stat pass would compute over the conv output."""
    rs = np.random.RandomState(2)
    x = _rand(rs, (3, 8, 8, 8))
    w = _rand(rs, (3, 3, 8, 16)) * 0.1
    y, s, ss = fused_conv_bn(x, w, stride=1, pad=1)
    count = y.shape[0] * y.shape[1] * y.shape[2]
    gamma = jnp.asarray(rs.rand(16).astype(np.float32) + 0.5)
    beta = _rand(rs, (16,))
    a, b, mean, var = bn_scale_shift(s, ss, count, gamma, beta, eps=1e-5)
    y32 = np.asarray(y, np.float32)
    np.testing.assert_allclose(np.asarray(mean),
                               y32.mean(axis=(0, 1, 2)), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(var), y32.var(axis=(0, 1, 2)),
                               rtol=2e-3, atol=2e-3)
    # normalize via (a, b) == classic batchnorm
    got = y32 * np.asarray(a) + np.asarray(b)
    ref = (y32 - y32.mean((0, 1, 2))) / np.sqrt(
        y32.var((0, 1, 2)) + 1e-5) * np.asarray(gamma) + np.asarray(beta)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3)


def test_fused_conv_grads_match_xla():
    """dx, dw, da, db — including the stats cotangents (the next layer's
    BN coefficients depend on this layer's sum/sumsq)."""
    rs = np.random.RandomState(3)
    x = _rand(rs, (2, 6, 6, 8))
    w = _rand(rs, (3, 3, 8, 8)) * 0.2
    a = jnp.asarray(rs.rand(8).astype(np.float32) + 0.5)
    b = _rand(rs, (8,))

    # gentle nonlinearities: s/ss are O(10^2) channel sums, so cos(s)
    # would turn a ~1e-5 fused-vs-ref forward delta into a large
    # cotangent swing that tests float noise, not the vjp wiring
    def loss_fused(x, w, a, b):
        y, s, ss = fused_conv_bn(x, w, a, b, stride=1, pad=1)
        return (jnp.sum(jnp.sin(y.astype(jnp.float32)))
                + jnp.sum(jnp.cos(s * 1e-2))
                + jnp.sum(jnp.tanh(ss * 1e-3)))

    def loss_ref(x, w, a, b):
        y, s, ss = _fused_conv_ref(x, w, a, b, 1, 1, True)
        return (jnp.sum(jnp.sin(y.astype(jnp.float32)))
                + jnp.sum(jnp.cos(s * 1e-2))
                + jnp.sum(jnp.tanh(ss * 1e-3)))

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, w, a, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, w, a, b)
    for got, ref, name in zip(gf, gr, ("dx", "dw", "da", "db")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_conv_grads_no_prologue():
    rs = np.random.RandomState(4)
    x = _rand(rs, (2, 6, 6, 8))
    w = _rand(rs, (1, 1, 8, 16)) * 0.2

    def loss(fn):
        def f(x, w):
            y, s, ss = fn(x, w)
            return jnp.sum(jnp.sin(y)) + jnp.sum(s) * 0.1 + jnp.sum(
                jnp.sqrt(ss + 1.0))
        return f

    gf = jax.grad(loss(lambda x, w: fused_conv_bn(x, w, stride=2, pad=0)),
                  argnums=(0, 1))(x, w)
    gr = jax.grad(
        loss(lambda x, w: _fused_conv_ref(x, w, None, None, 2, 0, True)),
        argnums=(0, 1))(x, w)
    for got, ref, name in zip(gf, gr, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_conv_bf16():
    rs = np.random.RandomState(5)
    x = _rand(rs, (2, 8, 8, 16), jnp.bfloat16)
    w = _rand(rs, (3, 3, 16, 16), jnp.bfloat16) * 0.1
    y, s, ss = fused_conv_bn(x, w, stride=1, pad=1)
    yr, sr, ssr = _fused_conv_ref(x, w, None, None, 1, 1, True)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=0.05, atol=0.05)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=0.03, atol=0.5)


@pytest.mark.parametrize("bc", [8, 16])
def test_fused_conv_blocked_oc_matches_xla(bc):
    """v2 output-channel blocking: forcing a co block smaller than co
    exercises the (co-block, batch-block) grid with weight-stationary
    stats accumulation; numerics must be identical to the unblocked run."""
    rs = np.random.RandomState(7)
    x = _rand(rs, (4, 8, 8, 16))
    w = _rand(rs, (3, 3, 16, 32)) * 0.1
    a = jnp.asarray(rs.rand(16).astype(np.float32) + 0.5)
    b = _rand(rs, (16,))
    with knob("MXTPU_CONV_OC_BLOCK", bc):
        y, s, ss = fused_conv_bn(x, w, a, b, stride=1, pad=1)
    yr, sr, ssr = _fused_conv_ref(x, w, a, b, 1, 1, True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr),
                               rtol=1e-4, atol=1e-4)


def test_fused_conv_strided_multi_image_blocks():
    """v2 strided kernels take nb>1 (per-image unrolled phase
    decomposition) — batch 6 with the row target forcing nb in {2,3,6}."""
    rs = np.random.RandomState(8)
    x = _rand(rs, (6, 9, 9, 8))
    w = _rand(rs, (3, 3, 8, 16)) * 0.1
    y, s, ss = fused_conv_bn(x, w, stride=2, pad=1)
    yr, sr, ssr = _fused_conv_ref(x, w, None, None, 2, 1, True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cfg", [
    dict(h=8, ci=8, co=16, k=3, stride=1, pad=1),     # 3x3 body
    dict(h=8, ci=16, co=8, k=1, stride=1, pad=0),     # 1x1 projection
    dict(h=9, ci=8, co=8, k=3, stride=2, pad=1),      # strided (odd H)
    dict(h=8, ci=8, co=16, k=1, stride=2, pad=0),     # 1x1 downsample
])
def test_bwd_kernels_direct_vs_vjp_oracle(cfg):
    """The dx and dW Pallas kernels, called DIRECTLY with hand cotangents,
    must match jax.vjp over the XLA formulation — including the folded
    BN-statistics cotangents and the da/db prologue sums."""
    rs = np.random.RandomState(9)
    n, h, k, s, pad = 3, cfg["h"], cfg["k"], cfg["stride"], cfg["pad"]
    ci, co = cfg["ci"], cfg["co"]
    x = _rand(rs, (n, h, h, ci))
    w = _rand(rs, (k, k, ci, co)) * 0.2
    a = jnp.asarray(rs.rand(ci).astype(np.float32) + 0.5)
    b = _rand(rs, (ci,))
    y, _, _ = _fused_conv_ref(x, w, a, b, s, pad, True)
    dy = _rand(rs, y.shape) * 0.1
    ds = _rand(rs, (co,)) * 0.01
    dss = _rand(rs, (co,)) * 0.001

    # oracle: vjp of the (prologue+conv, stats) formulation
    def f(x_, w_, a_, b_):
        yy = _conv_part_ref(x_, w_, a_, b_, s, pad, True)
        y32 = yy.astype(jnp.float32)
        return yy, jnp.sum(y32, axis=(0, 1, 2)), \
            jnp.sum(y32 * y32, axis=(0, 1, 2))

    _, vjp = jax.vjp(f, x, w, a, b)
    dxr, dwr, dar, dbr = vjp((dy, ds, dss))

    dx, da, db = _conv_bwd_dx_pallas(x, w, a, b, y, dy, ds, dss, s, pad,
                                     True, True)
    dw = _conv_bwd_dw_pallas(x, w, a, b, y, dy, ds, dss, s, pad, True,
                             True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dxr),
                               rtol=1e-4, atol=1e-4, err_msg="dx")
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr),
                               rtol=1e-4, atol=1e-4, err_msg="dw")
    np.testing.assert_allclose(np.asarray(da), np.asarray(dar),
                               rtol=1e-4, atol=1e-4, err_msg="da")
    np.testing.assert_allclose(np.asarray(db), np.asarray(dbr),
                               rtol=1e-4, atol=1e-4, err_msg="db")


@pytest.mark.parametrize("mode", ["pallas", "xla"])
@pytest.mark.parametrize("cfg", [
    dict(h=8, ci=8, co=8, k=3, stride=1, pad=1),
    dict(h=8, ci=8, co=16, k=1, stride=2, pad=0),
    dict(h=9, ci=8, co=8, k=3, stride=2, pad=1),
])
def test_grads_match_across_bwd_modes(cfg, mode):
    """The custom vjp must produce oracle-equal gradients under every
    MXTPU_CONV_BWD dispatch mode — 'pallas' forces the strided dx kernel
    (the phase-stack pattern) through the interpreter too."""
    rs = np.random.RandomState(10)
    n, h, k, s, pad = 2, cfg["h"], cfg["k"], cfg["stride"], cfg["pad"]
    ci, co = cfg["ci"], cfg["co"]
    x = _rand(rs, (n, h, h, ci))
    w = _rand(rs, (k, k, ci, co)) * 0.2
    a = jnp.asarray(rs.rand(ci).astype(np.float32) + 0.5)
    b = _rand(rs, (ci,))

    def loss(fn):
        def f(x, w, a, b):
            y, s_, ss = fn(x, w, a, b)
            return (jnp.sum(jnp.sin(y.astype(jnp.float32)))
                    + jnp.sum(jnp.cos(s_ * 1e-2))
                    + jnp.sum(jnp.tanh(ss * 1e-3)))
        return f

    with knob("MXTPU_CONV_BWD", mode):
        gf = jax.grad(loss(lambda *t: fused_conv_bn(
            *t, stride=s, pad=pad)), argnums=(0, 1, 2, 3))(x, w, a, b)
    gr = jax.grad(loss(lambda x_, w_, a_, b_: _fused_conv_ref(
        x_, w_, a_, b_, s, pad, True)), argnums=(0, 1, 2, 3))(x, w, a, b)
    for got, ref, name in zip(gf, gr, ("dx", "dw", "da", "db")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name} mode={mode}")


def test_bwd_pallas_bf16():
    """bf16 residuals through the Pallas backward kernels (fp32
    accumulation inside, outputs rounded to the weight/input dtype)."""
    rs = np.random.RandomState(11)
    x = _rand(rs, (2, 8, 8, 8), jnp.bfloat16)
    w = _rand(rs, (3, 3, 8, 8), jnp.bfloat16) * 0.2

    def loss(fn):
        def f(x, w):
            y, s_, ss = fn(x, w)
            return (jnp.sum(y.astype(jnp.float32))
                    + jnp.sum(s_) * 1e-2 + jnp.sum(ss) * 1e-3)
        return f

    with knob("MXTPU_CONV_BWD", "pallas"):
        gf = jax.grad(loss(lambda x, w: fused_conv_bn(x, w, stride=1,
                                                      pad=1)),
                      argnums=(0, 1))(x, w)
    gr = jax.grad(loss(lambda x, w: _fused_conv_ref(x, w, None, None, 1,
                                                    1, True)),
                  argnums=(0, 1))(x, w)
    assert gf[0].dtype == jnp.bfloat16 and gf[1].dtype == jnp.bfloat16
    for got, ref, name in zip(gf, gr, ("dx", "dw")):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=0.1, atol=0.1, err_msg=name)


def test_bwd_blocked_ci_oc_matches_oracle():
    """ci blocking in the dx kernel + co blocking in the dW kernel
    (forced small) keep the accumulation pattern exact."""
    rs = np.random.RandomState(12)
    x = _rand(rs, (4, 6, 6, 16))
    w = _rand(rs, (3, 3, 16, 16)) * 0.2
    a = jnp.asarray(rs.rand(16).astype(np.float32) + 0.5)
    b = _rand(rs, (16,))
    y, _, _ = _fused_conv_ref(x, w, a, b, 1, 1, True)
    dy = _rand(rs, y.shape) * 0.1
    ds = _rand(rs, (16,)) * 0.01
    dss = _rand(rs, (16,)) * 0.001

    def f(x_, w_, a_, b_):
        yy = _conv_part_ref(x_, w_, a_, b_, 1, 1, True)
        y32 = yy.astype(jnp.float32)
        return yy, jnp.sum(y32, axis=(0, 1, 2)), \
            jnp.sum(y32 * y32, axis=(0, 1, 2))

    _, vjp = jax.vjp(f, x, w, a, b)
    dxr, dwr, dar, dbr = vjp((dy, ds, dss))
    with knob("MXTPU_CONV_OC_BLOCK", 8):
        dx, da, db = _conv_bwd_dx_pallas(x, w, a, b, y, dy, ds, dss, 1,
                                         1, True, True)
        dw = _conv_bwd_dw_pallas(x, w, a, b, y, dy, ds, dss, 1, 1, True,
                                 True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dxr),
                               rtol=1e-4, atol=1e-4, err_msg="dx")
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr),
                               rtol=1e-4, atol=1e-4, err_msg="dw")
    np.testing.assert_allclose(np.asarray(da), np.asarray(dar),
                               rtol=1e-4, atol=1e-4, err_msg="da")
    np.testing.assert_allclose(np.asarray(db), np.asarray(dbr),
                               rtol=1e-4, atol=1e-4, err_msg="db")


def test_bottleneck_chain_matches_unfused():
    """A ResNet bottleneck forward (1x1 -> 3x3 -> 1x1 with BN between)
    through the fused kernels == the classic conv/batchnorm chain."""
    rs = np.random.RandomState(6)
    n, h, c = 2, 8, 16
    x = _rand(rs, (n, h, h, c))
    w1 = _rand(rs, (1, 1, c, 8)) * 0.3
    w2 = _rand(rs, (3, 3, 8, 8)) * 0.3
    g1, b1 = jnp.ones((8,)), jnp.zeros((8,))
    g2, b2 = (jnp.asarray(rs.rand(8).astype(np.float32) + 0.5),
              _rand(rs, (8,)))

    y1, s1, ss1 = fused_conv_bn(x, w1, stride=1, pad=0)
    a1, sh1, m1, v1 = bn_scale_shift(s1, ss1, n * h * h, g1, b1)
    y2, s2, ss2 = fused_conv_bn(y1, w2, a1, sh1, stride=1, pad=1,
                                relu=True)
    a2, sh2, m2, v2 = bn_scale_shift(s2, ss2, n * h * h, g2, b2)
    out = np.asarray(y2, np.float32) * np.asarray(a2) + np.asarray(sh2)

    # unfused oracle
    def conv(x, w, pad):
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                            ("NHWC", "HWIO", "NHWC"))
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), [(pad, pad), (pad, pad)], dimension_numbers=dn,
            precision=jax.lax.Precision.HIGHEST)

    def bn(y, g, b):
        mu = y.mean((0, 1, 2))
        var = y.var((0, 1, 2))
        return (y - mu) / jnp.sqrt(var + 1e-5) * g + b

    r1 = jax.nn.relu(bn(conv(x, w1, 0), g1, b1))
    ref = bn(conv(r1, w2, 1), g2, b2)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# v3: residual-epilogue fusion + stride-2 layout variants
# ---------------------------------------------------------------------------

def _epi_operands(seed, n=2, h=8, ci=8, co=16, k=3, dtype=np.float32):
    rs = np.random.RandomState(seed)
    x = _rand(rs, (n, h, h, ci), dtype)
    w = _rand(rs, (k, k, ci, co), dtype) * 0.2
    a = jnp.asarray(rs.rand(ci).astype(np.float32) + 0.5)
    b = _rand(rs, (ci,))
    r = _rand(rs, (n, h, h, ci), dtype)
    ar = jnp.asarray(rs.rand(ci).astype(np.float32) + 0.5)
    br = _rand(rs, (ci,))
    return x, w, a, b, r, ar, br


@pytest.mark.parametrize("cfg", [
    dict(k=1, stride=1, pad=0),            # the bottleneck-junction conv1
    dict(k=3, stride=1, pad=1),
    dict(k=3, stride=2, pad=1),            # strided, residual streamed
])
def test_epilogue_forward_matches_xla(cfg):
    """conv+BN+ReLU+residual-add in one kernel: the v3 prologue
    ``relu(a*x + b + ar*r + br)`` plus the emitted joined activation must
    match the XLA formulation exactly."""
    from incubator_mxnet_tpu.ops.pallas_conv import _apply_prologue_host

    x, w, a, b, r, ar, br = _epi_operands(20, k=cfg["k"])
    s_, pad = cfg["stride"], cfg["pad"]
    y, s, ss, xp = fused_conv_bn(x, w, a, b, stride=s_, pad=pad,
                                 relu=True, resid=r, resid_scale=ar,
                                 resid_shift=br, emit_act=True)
    yr, sr, ssr = _fused_conv_ref(x, w, a, b, s_, pad, True, r=r, ar=ar,
                                  br=br)
    xpr = _apply_prologue_host(x, a, b, r=r, ar=ar, br=br, relu=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(xp), np.asarray(xpr),
                               rtol=1e-5, atol=1e-5, err_msg="emit_act")
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr),
                               rtol=1e-4, atol=1e-4)


def test_epilogue_identity_residual_defaults():
    """resid without scale/shift = the identity shortcut (ar=1, br=0)."""
    x, w, a, b, r, _, _ = _epi_operands(21)
    y, s, ss = fused_conv_bn(x, w, a, b, stride=1, pad=1, relu=True,
                             resid=r)
    ones = jnp.ones((x.shape[-1],), jnp.float32)
    zeros = jnp.zeros((x.shape[-1],), jnp.float32)
    yr, sr, ssr = _fused_conv_ref(x, w, a, b, 1, 1, True, r=r, ar=ones,
                                  br=zeros)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5)


def test_emit_act_requires_resid():
    x, w, a, b, _, _, _ = _epi_operands(22)
    with pytest.raises(ValueError, match="emit_act requires"):
        fused_conv_bn(x, w, a, b, stride=1, pad=1, emit_act=True)


@pytest.mark.parametrize("mode", ["pallas", "xla"])
@pytest.mark.parametrize("cfg", [
    dict(k=1, stride=1, pad=0),
    dict(k=3, stride=1, pad=1),
    dict(k=3, stride=2, pad=1),
])
def test_epilogue_grads_match_oracle(cfg, mode):
    """The v3 custom vjp — dx, dw, da, db AND the residual cotangents
    (dr pass-through, dar, dbr) plus the emitted activation's incoming
    cotangent — must match jax.vjp over the XLA formulation under every
    MXTPU_CONV_BWD dispatch mode."""
    from incubator_mxnet_tpu.ops.pallas_conv import _apply_prologue_host

    x, w, a, b, r, ar, br = _epi_operands(23, k=cfg["k"])
    s_, pad = cfg["stride"], cfg["pad"]

    def loss_fused(x, w, a, b, r, ar, br):
        y, s, ss, xp = fused_conv_bn(x, w, a, b, stride=s_, pad=pad,
                                     relu=True, resid=r, resid_scale=ar,
                                     resid_shift=br, emit_act=True)
        return (jnp.sum(jnp.sin(y.astype(jnp.float32)))
                + jnp.sum(jnp.cos(s * 1e-2))
                + jnp.sum(jnp.tanh(ss * 1e-3))
                + jnp.sum(jnp.sin(xp.astype(jnp.float32) * 0.7)))

    def loss_ref(x, w, a, b, r, ar, br):
        y = _conv_part_ref(x, w, a, b, s_, pad, True, r=r, ar=ar, br=br)
        xp = _apply_prologue_host(x, a, b, r=r, ar=ar, br=br, relu=True)
        y32 = y.astype(jnp.float32)
        return (jnp.sum(jnp.sin(y32))
                + jnp.sum(jnp.cos(jnp.sum(y32, (0, 1, 2)) * 1e-2))
                + jnp.sum(jnp.tanh(jnp.sum(y32 * y32, (0, 1, 2)) * 1e-3))
                + jnp.sum(jnp.sin(xp.astype(jnp.float32) * 0.7)))

    with knob("MXTPU_CONV_BWD", mode):
        gf = jax.grad(loss_fused, argnums=tuple(range(7)))(x, w, a, b, r,
                                                           ar, br)
    gr = jax.grad(loss_ref, argnums=tuple(range(7)))(x, w, a, b, r, ar,
                                                     br)
    for got, ref, name in zip(gf, gr,
                              ("dx", "dw", "da", "db", "dr", "dar",
                               "dbr")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{name} mode={mode}")


def test_epilogue_drelu_mask_at_zero_crossings():
    """dReLU convention at EXACT zero crossings of the joined
    pre-activation: the kernels use the strict ``lin > 0`` mask — a zero
    pre-activation contributes NOTHING to dx/dr/da/db. Hand-built oracle
    (jnp.maximum's vjp splits 0.5/0.5 at ties, which is exactly the
    divergence this test pins down)."""
    ci, co, n, h = 4, 8, 1, 4
    x = jnp.zeros((n, h, h, ci), jnp.float32)
    # lin = a*x + b + ar*r + br with a=1, b=row pattern, r=0, ar=1, br=0:
    # channel 0 lin = -1 (masked), channel 1 lin = 0 (EXACT crossing,
    # masked by the strict convention), channels 2/3 lin = +1 (pass)
    b = jnp.asarray([-1.0, 0.0, 1.0, 1.0], jnp.float32)
    a = jnp.ones((ci,), jnp.float32)
    r = jnp.zeros_like(x)
    ar = jnp.ones((ci,), jnp.float32)
    br = jnp.zeros((ci,), jnp.float32)
    w = jnp.ones((1, 1, ci, co), jnp.float32) * 0.5

    with knob("MXTPU_CONV_BWD", "pallas"):
        def loss(x, r, b):
            y, s, ss = fused_conv_bn(x, w, a, b, stride=1, pad=0,
                                     relu=True, resid=r, resid_scale=ar,
                                     resid_shift=br)
            return jnp.sum(y)

        dx, dr, db = jax.grad(loss, argnums=(0, 1, 2))(x, r, b)
    # cotangent of lin per channel = sum over co of w = 4.0 where the
    # mask passes, 0 where lin <= 0 (strictly: the lin == 0 channel too)
    expect = np.array([0.0, 0.0, 4.0, 4.0], np.float32)
    np.testing.assert_array_equal(np.asarray(dx[0, 0, 0]), expect)
    np.testing.assert_array_equal(np.asarray(dr[0, 0, 0]), expect)
    np.testing.assert_array_equal(np.asarray(db), expect * n * h * h)


def test_epilogue_residual_cotangent_passthrough():
    """With relu=False the residual cotangent is a pure affine
    pass-through: dr == dlin * ar exactly (no mask)."""
    x, w, a, b, r, ar, br = _epi_operands(24, k=1)
    dy = _rand(np.random.RandomState(25), (2, 8, 8, 16)) * 0.1
    ds = jnp.zeros((16,), jnp.float32)
    dss = jnp.zeros((16,), jnp.float32)
    from incubator_mxnet_tpu.ops.pallas_conv import _conv_bwd_dx_pallas

    y, _, _ = _fused_conv_ref(x, w, a, b, 1, 0, False, r=r, ar=ar, br=br)
    dx, da, db, dr, dar = _conv_bwd_dx_pallas(
        x, w, a, b, y, dy, ds, dss, 1, 0, False, True, r=r, ar=ar, br=br)
    # dlin = transpose-conv(dy, w); dx = dlin*a, dr = dlin*ar — so
    # dr/ar == dx/a elementwise
    np.testing.assert_allclose(
        np.asarray(dr) / np.asarray(ar), np.asarray(dx) / np.asarray(a),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("variant", ["unroll", "prephase"])
@pytest.mark.parametrize("cfg", [
    dict(h=8, ci=8, co=16, k=3, pad=1),
    dict(h=9, ci=8, co=8, k=3, pad=1),     # odd H
    dict(h=8, ci=16, co=32, k=1, pad=0),   # 1x1 downsample
])
def test_stride2_layout_variants_match_xla(cfg, variant):
    """Both stride-2 layouts (v2 per-image unroll, v3 host prephase)
    must be oracle-equal — incl. odd sizes, 1x1 projections, multi-image
    blocks and the residual operands."""
    rs = np.random.RandomState(26)
    x = _rand(rs, (6, cfg["h"], cfg["h"], cfg["ci"]))
    w = _rand(rs, (cfg["k"], cfg["k"], cfg["ci"], cfg["co"])) * 0.1
    a = jnp.asarray(rs.rand(cfg["ci"]).astype(np.float32) + 0.5)
    b = _rand(rs, (cfg["ci"],))
    r = _rand(rs, x.shape)
    with knob("MXTPU_CONV_STRIDE2", variant):
        y, s, ss = fused_conv_bn(x, w, a, b, stride=2, pad=cfg["pad"],
                                 relu=True)
        ye, se, sse, xpe = fused_conv_bn(
            x, w, a, b, stride=2, pad=cfg["pad"], relu=True, resid=r,
            emit_act=True)
    yr, sr, ssr = _fused_conv_ref(x, w, a, b, 2, cfg["pad"], True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-5, atol=1e-5, err_msg=variant)
    np.testing.assert_allclose(np.asarray(ss), np.asarray(ssr),
                               rtol=1e-4, atol=1e-4, err_msg=variant)
    ones = jnp.ones((cfg["ci"],), jnp.float32)
    zer = jnp.zeros((cfg["ci"],), jnp.float32)
    yer, _, _ = _fused_conv_ref(x, w, a, b, 2, cfg["pad"], True, r=r,
                                ar=ones, br=zer)
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yer),
                               rtol=1e-5, atol=1e-5,
                               err_msg=f"{variant} resid")


def test_stride2_auto_heuristic_picks_by_row_target():
    """auto = prephase exactly where the unroll nb cap (8) would starve
    the MXU: small spatial extents flip, large ones keep the unroll."""
    from incubator_mxnet_tpu.ops.pallas_conv import _stride2_variant

    assert _stride2_variant(1, 56, 56) == "none"
    # l2.3x3s: 28x28 out -> 2048/784 = 2 images wanted, cap unbound
    assert _stride2_variant(2, 28, 28) == "unroll"
    # l3/l4 strided shapes: 14x14 wants 10, 7x7 wants 41 -> prephase
    assert _stride2_variant(2, 14, 14) == "prephase"
    assert _stride2_variant(2, 7, 7) == "prephase"
    with knob("MXTPU_CONV_STRIDE2", "unroll"):
        assert _stride2_variant(2, 7, 7) == "unroll"
    with knob("MXTPU_CONV_STRIDE2", "prephase"):
        assert _stride2_variant(2, 28, 28) == "prephase"


def test_epilogue_bf16():
    x, w, a, b, r, ar, br = _epi_operands(27, dtype=jnp.bfloat16)
    y, s, ss, xp = fused_conv_bn(x, w, a, b, stride=1, pad=1, relu=True,
                                 resid=r, resid_scale=ar, resid_shift=br,
                                 emit_act=True)
    assert y.dtype == jnp.bfloat16 and xp.dtype == jnp.bfloat16
    yr, sr, ssr = _fused_conv_ref(x, w, a, b, 1, 1, True, r=r, ar=ar,
                                  br=br)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               rtol=0.05, atol=0.05)
