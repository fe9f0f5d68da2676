"""Pallas flash-attention kernel tests (the RTC/custom-kernel tier,
SURVEY.md §2.1). On the CPU test mesh the kernel runs through the Pallas
interpreter; the same code path compiles on a real TPU."""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd
from incubator_mxnet_tpu import ndarray as nd
from incubator_mxnet_tpu.models import MultiHeadAttention


@pytest.fixture(autouse=True)
def _pin_pallas_path(request, monkeypatch):
    """These tests exercise the KERNELS at tiny shapes, where the rule
    (``_kernel_pays``) takes the XLA path: every call through the
    registered op is given ``interpret=``, which pins the kernels, except
    in the tests of the rule itself."""
    import functools

    from incubator_mxnet_tpu.ops import pallas_attention as pa
    from incubator_mxnet_tpu.ops import registry

    if request.node.get_closest_marker("the_rule"):
        return
    opdef = registry.get("flash_attention")
    monkeypatch.setattr(opdef, "fn", functools.partial(
        opdef.fn, interpret=not pa.pallas_available()))


def _kernel_calls(fn, *shapes, dtype="bfloat16"):
    """Kernel launches in ``fn``'s jaxpr over operands of ``shapes``: a
    call the rule leaves to the platform holds the compiled kernels and
    the interpreted ones as the two branches of one switch, so the count
    is of the first branch where there is one."""
    import jax

    text = str(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(s, dtype) for s in shapes)))
    return text.count("pallas_call") // (2 if "platform_index" in text else 1)


# (B, H, Tq, Tk, D) per corner of the rule, whether the call is
# differentiated, keywords, and how many Pallas launches the call's program
# holds (a forward: 1; a differentiated call: the forward that saves the
# log-sum-exp, dQ, dK/dV)
_RULE = [
    # not differentiated: the forward kernel's crossover, 19 x 2**20
    # scores a call and 512 positions
    pytest.param((1, 25, 512, 512, 64), False, {}, 0, id="forward-under-512"),
    pytest.param((1, 17, 1024, 1024, 64), False, {}, 0, id="forward-under"),
    pytest.param((1, 25, 1024, 1024, 64), False, {}, 1, id="forward-over"),
    pytest.param((16, 16, 512, 512, 64), False, {}, 1,
                 id="forward-over-batch-512"),
    pytest.param((256, 12, 128, 128, 64), False, {}, 0,
                 id="forward-short-rows"),
    pytest.param((16, 25, 1, 1024, 64), False, {}, 0,
                 id="forward-decode-row"),
    # differentiated: the backward pair's crossover, the parent's
    pytest.param((4, 16, 1024, 1024, 64), True, {}, 0,
                 id="differentiated-under"),
    pytest.param((1, 2, 2048, 2048, 64), True, {}, 3,
                 id="differentiated-over"),
    # a dense fp32 score tensor over 1 GiB takes the kernels whatever
    # the speed, in both directions
    pytest.param((128, 128, 128, 128, 8), False, {}, 0, id="forward-cap"),
    pytest.param((128, 129, 128, 128, 8), False, {}, 1,
                 id="forward-over-cap"),
    pytest.param((16, 16, 1024, 1024, 8), True, {}, 0,
                 id="differentiated-cap"),
    pytest.param((16, 17, 1024, 1024, 8), True, {}, 3,
                 id="differentiated-over-cap"),
    # an explicit interpret= pins the kernels at any shape
    pytest.param((1, 1, 16, 16, 16), False, {"interpret": True}, 1,
                 id="forward-pinned"),
    pytest.param((1, 1, 16, 16, 16), True, {"interpret": True}, 3,
                 id="differentiated-pinned"),
]


@pytest.mark.the_rule
@pytest.mark.parametrize("shape,differentiated,kw,kernels", _RULE)
def test_flash_dispatch_size_aware(shape, differentiated, kw, kernels):
    """Which implementation ``flash_attention`` takes is decided per
    direction and from the call's shapes (the cuDNN algo-selection
    analog; VERDICT r4 item 3: no silent sub-crossover Pallas
    regression): read from the jaxpr, a ``pallas_call`` or none."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops import pallas_attention as pa

    b, h, tq, tk, d = shape

    def fwd(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, **kw)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if differentiated else fwd
    labels = dict(path="kernel" if kernels else "dense",
                  direction="differentiated" if differentiated
                  else "forward")
    counter = telemetry.counter("mxtpu_flash_dispatch_total", **labels)
    before = counter.value
    assert _kernel_calls(fn, (b, h, tq, d), (b, h, tk, d),
                         (b, h, tk, d)) == kernels
    assert counter.value == before + 1


@pytest.mark.the_rule
def test_trainer_step_counts_its_attention_as_differentiated():
    """A training step differentiates every layer's call, so each is
    traced through the rule's ``differentiated`` side (dense under 2,048
    positions: the parent's program) and none through ``forward``, which
    is what a plain forward of the same block counts."""
    from incubator_mxnet_tpu import gluon, parallel, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo import get_gpt

    layers, vocab = 2, 61
    net = get_gpt("gpt_decoder_tiny", vocab_size=vocab, units=32,
                  num_layers=layers, max_length=16, dropout=0.0)
    net.initialize(init="xavier")

    def traced():
        return {(p, d): telemetry.counter(
            "mxtpu_flash_dispatch_total", path=p, direction=d).value
            for p in ("kernel", "dense")
            for d in ("forward", "differentiated")}

    rng = np.random.RandomState(0)
    tokens = rng.randint(0, vocab, (8, 16)).astype(np.int32)
    start = traced()
    net(nd.array(tokens[:1], dtype="int32"))
    before = traced()
    assert before[("dense", "forward")] == start[("dense", "forward")] \
        + layers
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=parallel.make_mesh({"data": -1}))
    trainer.step(tokens, rng.randint(0, vocab, (8, 16)).astype(np.float32))
    after = traced()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {("dense", "differentiated"): layers}, moved


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("t", [512, 1024])
@pytest.mark.parametrize("heads", [25, 16])
def test_forward_kernel_matches_dense_at_served_widths(heads, t, dtype):
    """The forward kernel against the dense chain at a served GPT's
    prefill shapes (GPT-2 XL's 25 heads of 64 and GPT-2 medium's 16, the
    512 and 1024 buckets, causal), in the interpreter, with the block
    sizes the kernel runs with (``bq`` 256, ``bk`` 512)."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import pallas_attention as pa

    d = 64
    rng = np.random.RandomState(heads + t)
    q, k, v = (jnp.asarray(rng.randn(1, heads, t, d), dtype)
               for _ in range(3))
    got = pa.flash_attention(q, k, v, causal=True, interpret=True)
    want = pa._xla_reference(q, k, v, None, d ** -0.5, True)
    assert got.shape == want.shape == (1, heads, t, d)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def _grad_tols():
    """f32 gradient tolerances: tight under the CPU interpreter; looser on
    the chip, where kernel and XLA reference take different MXU passes
    (observed max rel diff ~6e-3 on compiled f32 matmuls)."""
    import jax

    if jax.default_backend() == "tpu":
        return dict(rtol=2e-2, atol=5e-4)
    return dict(rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape,causal", [
    ((2, 3, 64, 32), False),
    ((1, 2, 100, 16), True),     # non-multiple-of-block T exercises padding
    ((1, 1, 256, 64), True),
])
def test_flash_matches_xla_sdpa(shape, causal):
    rng = np.random.RandomState(0)
    b, h, t, d = shape
    q = nd.array(rng.randn(b, h, t, d).astype(np.float32))
    k = nd.array(rng.randn(b, h, t, d).astype(np.float32))
    v = nd.array(rng.randn(b, h, t, d).astype(np.float32))
    out = nd.flash_attention(q, k, v, causal=causal).asnumpy()
    ref = nd.scaled_dot_product_attention(q, k, v, causal=causal).asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_flash_attention_grad_matches_xla():
    rng = np.random.RandomState(1)
    q = nd.array(rng.randn(1, 2, 32, 16).astype(np.float32))
    k = nd.array(rng.randn(1, 2, 32, 16).astype(np.float32))
    v = nd.array(rng.randn(1, 2, 32, 16).astype(np.float32))
    grads = []
    for fn in (nd.flash_attention, nd.scaled_dot_product_attention):
        q.attach_grad()
        with autograd.record():
            out = fn(q, k, v, causal=True)
        out.backward(nd.ones_like(out))
        grads.append(q.grad.asnumpy())
    np.testing.assert_allclose(grads[0], grads[1], rtol=1e-4, atol=1e-5)


def test_mha_pallas_impl_matches_xla():
    rng = np.random.RandomState(2)
    x = nd.array(rng.randn(2, 24, 32).astype(np.float32))
    mha_x = MultiHeadAttention(32, 4, attention_impl="xla")
    mha_x.initialize(init="xavier")
    mha_p = MultiHeadAttention(32, 4, attention_impl="pallas",
                               params=mha_x.collect_params())
    np.testing.assert_allclose(mha_p(x).asnumpy(), mha_x(x).asnumpy(),
                               rtol=1e-4, atol=1e-4)


def test_pallas_feature_flag_is_honest():
    import jax

    from incubator_mxnet_tpu import runtime

    feats = runtime.Features()
    on_tpu = jax.devices()[0].platform == "tpu"
    assert feats.is_enabled("PALLAS") == on_tpu


def test_flash_causal_cross_attention_alignment():
    # tq != tk: causal must use bottom-right alignment (tril k=tk-tq)
    # exactly like the XLA reference — decode-style steps see all history
    rng = np.random.RandomState(3)
    q = nd.array(rng.randn(1, 1, 4, 16).astype(np.float32))
    k = nd.array(rng.randn(1, 1, 8, 16).astype(np.float32))
    v = nd.array(rng.randn(1, 1, 8, 16).astype(np.float32))
    out = nd.flash_attention(q, k, v, causal=True).asnumpy()
    ref = nd.scaled_dot_product_attention(q, k, v, causal=True).asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_flash_lengths_matches_masked_xla():
    rng = np.random.RandomState(4)
    b, h, t, d = 3, 2, 48, 16
    q = nd.array(rng.randn(b, h, t, d).astype(np.float32))
    k = nd.array(rng.randn(b, h, t, d).astype(np.float32))
    v = nd.array(rng.randn(b, h, t, d).astype(np.float32))
    lengths = nd.array(np.array([48, 17, 5], np.float32))
    out = nd.invoke_op("flash_attention", q, k, v, lengths).asnumpy()
    mask = (np.arange(t)[None, None, None, :]
            < np.array([48, 17, 5]).reshape(-1, 1, 1, 1))
    ref = nd.scaled_dot_product_attention(
        q, k, v, mask=nd.array(mask.astype(np.float32))).asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_bert_valid_length_uses_pallas_and_matches_xla():
    from incubator_mxnet_tpu import models

    rng = np.random.RandomState(5)
    tok = nd.array(rng.randint(0, 50, (2, 24)).astype(np.int32))
    vl = nd.array(np.array([24, 9], np.int32))
    kw = dict(vocab_size=50, units=32, hidden_size=64, num_layers=2,
              num_heads=2, max_length=32, dropout=0.0, use_pooler=False,
              use_decoder=False, use_classifier=False)
    net_x = models.BERTModel(attention_impl="xla", **kw)
    net_x.initialize(init="xavier")
    net_p = models.BERTModel(attention_impl="pallas",
                             params=net_x.collect_params(), **kw)
    out_x = net_x(tok, None, vl)[0].asnumpy()
    out_p = net_p(tok, None, vl)[0].asnumpy()
    np.testing.assert_allclose(out_p, out_x, rtol=1e-4, atol=1e-4)


def test_ring_attention_pallas_matches_xla_ring():
    """Pallas-kernel ring attention (CP over the seq axis) must match the
    differentiable jnp ring path, causal and non-causal."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.ring_attention import (
        ring_attention_sharded)

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    mesh = parallel.make_mesh({"seq": 4},
                              devices=jax.devices()[:4])
    rs = np.random.RandomState(3)
    B, H, T, D = 2, 2, 64, 16
    q = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    for causal in (False, True):
        ref = np.asarray(ring_attention_sharded(
            q, k, v, mesh, causal=causal, impl="xla"))
        got = np.asarray(ring_attention_sharded(
            q, k, v, mesh, causal=causal, impl="pallas"))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5,
                                   err_msg=f"causal={causal}")


@pytest.mark.parametrize("shape,causal", [
    ((2, 2, 64, 32), False),
    ((1, 2, 100, 16), True),     # non-multiple-of-block T: padded rows
    ((2, 1, 256, 64), True),
])
def test_flash_bwd_full_grads_match_xla(shape, causal):
    """dq, dk AND dv from the streaming Pallas backward vs jax.grad of the
    XLA reference (round 4: the backward is a Pallas kernel pair, not an
    XLA recompute)."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (
        _flash_core, _xla_reference, pallas_available)

    interp = not pallas_available()   # compiled kernel on the chip tier

    rng = np.random.RandomState(7)
    b, h, t, d = shape
    q = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    s = 1.0 / float(np.sqrt(d))

    def loss_flash(q, k, v):
        o = _flash_core(q, k, v, None, s, causal, interp)
        return jnp.sum(jnp.sin(o))          # non-uniform cotangent

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_xla_reference(q, k, v, None, s, causal)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   err_msg=f"d{name}", **_grad_tols())


def test_flash_bwd_lengths_grads_match_xla():
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (
        _flash_core, _xla_reference, pallas_available)

    interp = not pallas_available()   # compiled kernel on the chip tier

    rng = np.random.RandomState(8)
    b, h, t, d = 3, 2, 48, 16
    q = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    lens = jnp.asarray(np.array([48, 17, 5], np.int32))
    s = 1.0 / float(np.sqrt(d))

    gf = jax.grad(lambda *a: jnp.sum(jnp.cos(
        _flash_core(*a, lens, s, False, interp))), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(jnp.cos(
        _xla_reference(*a, lens, s, False))), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   err_msg=f"d{name}", **_grad_tols())


def test_flash_bwd_cross_attention_grads():
    # tq != tk with bottom-right causal alignment in BOTH kernels
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (
        _flash_core, _xla_reference, pallas_available)

    interp = not pallas_available()   # compiled kernel on the chip tier

    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 2, 20, 16).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 52, 16).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 52, 16).astype(np.float32))
    s = 0.25

    gf = jax.grad(lambda *a: jnp.sum(jnp.sin(
        _flash_core(*a, None, s, True, interp))), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(jnp.sin(
        _xla_reference(*a, None, s, True))), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   err_msg=f"d{name}", **_grad_tols())


def test_flash_bwd_bf16():
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (
        _flash_core, _xla_reference, pallas_available)

    interp = not pallas_available()   # compiled kernel on the chip tier

    rng = np.random.RandomState(10)
    q = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 2, 64, 32), jnp.bfloat16)
    s = 1.0 / float(np.sqrt(32))

    gf = jax.grad(lambda *a: jnp.sum(
        _flash_core(*a, None, s, True, interp).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        _xla_reference(*a, None, s, True).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            rtol=0.08, atol=0.08, err_msg=f"d{name}")


def test_ring_pallas_grads_match_xla_ring():
    """SURVEY §2.4 CP row: ring_attention_sharded(impl='pallas') must be
    usable under jax.grad — the round-3 gap (forward-only Pallas ring)."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.ring_attention import (
        ring_attention_sharded)

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    mesh = parallel.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    rs = np.random.RandomState(11)
    B, H, T, D = 2, 2, 64, 16
    q = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))

    for causal in (False, True):
        def loss(impl):
            return lambda q, k, v: jnp.sum(jnp.sin(ring_attention_sharded(
                q, k, v, mesh, causal=causal, impl=impl)))

        gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for a, b_, name in zip(gp, gx, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), err_msg=f"causal={causal} d{name}", **_grad_tols())


def test_ulysses_pallas_grads_match_xla():
    """Ulysses impl='pallas' under jax.grad (round 4: routed through the
    custom-vjp flash core instead of the raw forward kernel)."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.ring_attention import (
        ulysses_attention_sharded)

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    mesh = parallel.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    rs = np.random.RandomState(12)
    B, H, T, D = 2, 4, 64, 16
    q = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))

    def loss(impl):
        return lambda q, k, v: jnp.sum(jnp.sin(ulysses_attention_sharded(
            q, k, v, mesh, causal=True, impl=impl)))

    gp = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gp, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=3e-4, atol=3e-5,
                                   err_msg=f"d{name}")


def test_ulysses_pallas_matches_xla():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel.ring_attention import (
        ulysses_attention_sharded)

    if len(jax.devices()) < 4:
        pytest.skip("needs >=4 devices")
    mesh = parallel.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    rs = np.random.RandomState(4)
    B, H, T, D = 2, 4, 64, 16
    q = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rs.rand(B, H, T, D).astype(np.float32))
    for causal in (False, True):
        ref = np.asarray(ulysses_attention_sharded(
            q, k, v, mesh, causal=causal, impl="xla"))
        got = np.asarray(ulysses_attention_sharded(
            q, k, v, mesh, causal=causal, impl="pallas"))
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5,
                                   err_msg=f"causal={causal}")


# ---------------------------------------------------------------------------
# causal-mask-with-cache-offset path (ISSUE 12: KV-cache decode alignment)
# ---------------------------------------------------------------------------
def _brute_cache_offset(q, k, v, lens, scale):
    """Numpy oracle: query row i of sample b sits at absolute position
    lens[b] - tq + i and attends keys [0, lens[b] - tq + i] EXACTLY."""
    B, H, tq, D = q.shape
    out = np.zeros_like(q, dtype=np.float64)
    for b in range(B):
        for h in range(H):
            for i in range(tq):
                pos = lens[b] - tq + i
                s = (q[b, h, i].astype(np.float64)
                     @ k[b, h, :pos + 1].astype(np.float64).T) * scale
                w = np.exp(s - s.max())
                w /= w.sum()
                out[b, h, i] = w @ v[b, h, :pos + 1].astype(np.float64)
    return out.astype(np.float32)


@pytest.mark.parametrize("tq", [1, 4])
def test_cache_offset_attends_prefix_exactly(tq):
    """Decode step t attends [0, t] exactly — both the Pallas kernel
    (interpreter) and the XLA dense path against the numpy oracle, over
    a PADDED key buffer with mixed per-slot fill levels."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (_xla_reference,
                                                          flash_attention)

    rs = np.random.RandomState(0)
    B, H, D, Tbuf = 3, 2, 8, 32
    lens = np.array([20, tq, 32], np.int32)      # incl. a fresh sequence
    q = rs.randn(B, H, tq, D).astype(np.float32)
    k = rs.randn(B, H, Tbuf, D).astype(np.float32)
    v = rs.randn(B, H, Tbuf, D).astype(np.float32)
    scale = 1.0 / np.sqrt(D)
    ref = _brute_cache_offset(q, k, v, lens, scale)
    got_p = flash_attention(q, k, v, lengths=jnp.asarray(lens),
                            cache_offset=True, interpret=True)
    got_x = _xla_reference(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                           jnp.asarray(lens), scale, True,
                           cache_offset=True)
    np.testing.assert_allclose(np.asarray(got_p), ref, rtol=2e-5,
                               atol=2e-6, err_msg="pallas")
    np.testing.assert_allclose(np.asarray(got_x), ref, rtol=2e-5,
                               atol=2e-6, err_msg="xla")


def test_cache_offset_matches_full_sequence_forward():
    """The decode contract: attention of the single token at position t
    over a padded cache with lengths=t+1 equals row t of the causal
    full-sequence forward (the oracle the decode tier is bit-exact-greedy
    against), for every t, on both implementations."""
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (_xla_reference,
                                                          flash_attention)

    rs = np.random.RandomState(1)
    B, H, D, T, Tbuf = 2, 2, 8, 12, 16
    q = rs.randn(B, H, T, D).astype(np.float32)
    k = rs.randn(B, H, T, D).astype(np.float32)
    v = rs.randn(B, H, T, D).astype(np.float32)
    scale = 1.0 / np.sqrt(D)
    full = np.asarray(_xla_reference(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), None, scale, True))
    kp = np.zeros((B, H, Tbuf, D), np.float32)
    vp = np.zeros((B, H, Tbuf, D), np.float32)
    kp[:, :, :T], vp[:, :, :T] = k, v
    for t in range(T):
        lens = jnp.full((B,), t + 1, jnp.int32)
        for name, dec in (
                ("xla", _xla_reference(
                    jnp.asarray(q[:, :, t:t + 1]), jnp.asarray(kp),
                    jnp.asarray(vp), lens, scale, True,
                    cache_offset=True)),
                ("pallas", flash_attention(
                    q[:, :, t:t + 1], kp, vp, lengths=lens,
                    cache_offset=True, interpret=True))):
            np.testing.assert_allclose(
                np.asarray(dec)[:, :, 0], full[:, :, t], rtol=1e-5,
                atol=5e-6, err_msg=f"{name} t={t}")


def test_cache_offset_grads_match_xla():
    """The cache-offset backward kernels (dq over KV blocks, dk/dv over
    Q blocks with the per-sample diagonal) agree with autodiff through
    the XLA reference."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.pallas_attention import (_xla_reference,
                                                          flash_attention)

    rs = np.random.RandomState(2)
    B, H, tq, D, Tbuf = 2, 2, 4, 8, 24
    lens = jnp.asarray(np.array([17, 9], np.int32))
    q = jnp.asarray(rs.randn(B, H, tq, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, H, Tbuf, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, H, Tbuf, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    def loss_p(q, k, v):
        return jnp.sum(flash_attention(q, k, v, lengths=lens,
                                       cache_offset=True,
                                       interpret=True) ** 2)

    def loss_x(q, k, v):
        return jnp.sum(_xla_reference(q, k, v, lens, scale, True,
                                      cache_offset=True) ** 2)

    gp = jax.grad(loss_p, (0, 1, 2))(q, k, v)
    gx = jax.grad(loss_x, (0, 1, 2))(q, k, v)
    for a, b, name in zip(gp, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **_grad_tols(), err_msg=f"d{name}")


def test_cache_offset_requires_lengths():
    rs = np.random.RandomState(3)
    x = rs.randn(1, 1, 4, 8).astype(np.float32)
    with pytest.raises(ValueError, match="lengths"):
        nd.invoke_op("flash_attention", nd.array(x), nd.array(x),
                     nd.array(x), cache_offset=True)
