"""Mixture-of-experts / expert parallelism (EP) tests.

SURVEY.md §2.4 EP row: new capability (reference has no MoE). Oracle: with
k == num_experts and unbounded capacity the MoE output equals the dense
softmax mixture of all expert FFNs computed in numpy.
"""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, parallel
from incubator_mxnet_tpu import ndarray as nd
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.contrib.nn import MoEFFN


def _softmax(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


def _gelu(x):
    return 0.5 * x * (1.0 + np.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def test_moe_ffn_dense_mixture_oracle():
    """k=E + unbounded capacity == dense mixture sum_e p_e * ffn_e(x)."""
    rs = np.random.RandomState(0)
    N, D, H, E = 6, 8, 16, 4
    x = rs.randn(N, D).astype(np.float32)
    gw = rs.randn(D, E).astype(np.float32) * 0.5
    w1 = rs.randn(E, D, H).astype(np.float32) * 0.3
    b1 = rs.randn(E, H).astype(np.float32) * 0.1
    w2 = rs.randn(E, H, D).astype(np.float32) * 0.3
    b2 = rs.randn(E, D).astype(np.float32) * 0.1

    y, aux = nd.invoke_op(
        "moe_ffn", nd.array(x), nd.array(gw), nd.array(w1), nd.array(b1),
        nd.array(w2), nd.array(b2), k=E, capacity=N * E,
        activation="gelu")

    p = _softmax(x @ gw)                               # (N, E)
    ref = np.zeros_like(x)
    for e in range(E):
        he = _gelu(x @ w1[e] + b1[e])
        ref += p[:, e:e + 1] * (he @ w2[e] + b2[e])
    np.testing.assert_allclose(y.asnumpy(), ref, rtol=2e-3, atol=2e-3)
    # perfectly uniform router load => aux ~ E * sum_e (1/E * 1/E) = 1 only
    # for uniform p; here just check finiteness and positivity
    assert float(aux.asnumpy()) > 0


def test_moe_capacity_drops_tokens():
    """capacity=1 with a router forced onto one expert: only one token per
    expert survives; dropped tokens output zero."""
    N, D, H, E = 4, 4, 4, 2
    x = np.ones((N, D), np.float32)
    gw = np.zeros((D, E), np.float32)
    gw[:, 0] = 10.0                       # every token routes to expert 0
    w1 = np.zeros((E, D, H), np.float32)
    b1 = np.ones((E, H), np.float32)
    w2 = np.zeros((E, H, D), np.float32)
    b2 = np.ones((E, D), np.float32)

    y, _ = nd.invoke_op(
        "moe_ffn", nd.array(x), nd.array(gw), nd.array(w1), nd.array(b1),
        nd.array(w2), nd.array(b2), k=1, capacity=1, activation="relu")
    out = y.asnumpy()
    # token 0 got the single slot (output = b2 = 1s); tokens 1..3 dropped
    np.testing.assert_allclose(out[0], np.ones(D), rtol=1e-5)
    np.testing.assert_allclose(out[1:], np.zeros((N - 1, D)), atol=1e-6)


def test_moe_layer_autograd():
    """Gradients flow to gate and expert weights through the tape."""
    mx.random.seed(0)
    np.random.seed(0)
    layer = MoEFFN(units=8, hidden_size=16, num_experts=4, k=2,
                   capacity_factor=2.0, return_aux=True)
    layer.initialize(init="xavier")
    x = mx.nd.uniform(shape=(4, 6, 8))
    with mx.autograd.record():
        y, aux = layer(x)
        loss = y.sum() + 0.01 * aux
    loss.backward()
    g_gate = layer.gate_weight.grad().asnumpy()
    g_w1 = layer.expert_w1.grad().asnumpy()
    assert np.isfinite(g_gate).all() and np.abs(g_gate).max() > 0
    assert np.isfinite(g_w1).all() and np.abs(g_w1).max() > 0


def test_moe_expert_parallel_spmd():
    """EP: expert weights sharded P('expert') on an expert x data mesh;
    fused SPMD training step runs and converges."""
    import jax
    from jax.sharding import PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    np.random.seed(1)
    mx.random.seed(1)

    D, H, E, C = 8, 16, 4, 3

    class MoENet(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.moe = MoEFFN(units=D, hidden_size=H, num_experts=E,
                                  k=2, capacity_factor=2.0, return_aux=True)
                self.head = nn.Dense(C, in_units=D)

        def forward(self, x):
            y, aux = self.moe(x)
            return self.head(y.reshape((x.shape[0], -1))[:, :D] + 0), aux

    net = MoENet()
    net.initialize(init="xavier")
    net(mx.nd.zeros((2, 3, D)))

    mesh = parallel.make_mesh({"expert": E, "data": 2})
    parallel.shard_params(net, {
        r"expert_(w1|b1|w2|b2)": P("expert"),
    })
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(logits, aux, label):
        return ce(logits, label) + 0.01 * aux

    st = parallel.SPMDTrainer(net, loss_fn, "adam",
                              {"learning_rate": 5e-3}, mesh=mesh)
    spec = str(st.params[[n for n in st.params
                          if "expert_w1" in n][0]].sharding.spec)
    assert "expert" in spec, spec

    x = np.random.rand(16, 3, D).astype(np.float32)
    y = np.random.randint(0, C, (16,)).astype(np.float32)
    losses = [float(st.step(x, y)) for _ in range(30)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses[::10]


# -- the held experts' two forms (``ops.moe.moe_held_ffn``) ------------------

def _relu2(a):
    import jax

    return jax.numpy.square(jax.nn.relu(a))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("first,masked", [(0, False), (4, True)])
def test_dense_held_experts_equal_the_grouped_form(dtype, gated, first,
                                                   masked):
    """The same rows, choices and weights through both forms of
    ``moe_held_ffn``, picked by the rule's own input (the router's width:
    12 experts, which 16 rows choosing 3 hit ~99% of, or 4096, which they
    would not): ``y`` agrees, float32 to its summation order and bfloat16
    to its rounding, and the routing's counts are identical. The share of
    experts 4-7 of 12 sees choices that fell on experts held elsewhere and
    a ``live`` mask; a gated (SiLU) and an ungated (relu2) expert."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.ops.moe import held_form, moe_held_ffn

    rng = np.random.default_rng(3)
    n, c, f, e, k = 16, 12, 20, 4, 3
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.standard_normal((n, c)), dt)
    w_gate, w_up, w_down = (
        jnp.asarray(rng.standard_normal(s) * 0.3, dt)
        for s in ((e, c, f), (e, c, f), (e, f, c)))
    idx = jnp.asarray(np.stack([rng.choice(12, k, replace=False)
                                for _ in range(n)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.2, 1.0, (n, k)), jnp.float32)
    live = jnp.asarray(rng.random(n) < 0.7) if masked else None
    act = jax.nn.silu if gated else _relu2
    out = {}
    for width, form in ((12, "dense"), (4096, "grouped")):
        assert held_form(n, k, width) == form
        counter = telemetry.counter("mxtpu_moe_held_form_total", form=form)
        before = counter.value
        out[form] = moe_held_ffn(x, idx, weights, w_gate if gated else None,
                                 w_up, w_down, first_expert=first, live=live,
                                 act=act, width=width)
        assert counter.value == before + 1
    (yd, cd), (yg, cg) = out["dense"], out["grouped"]
    assert yd.dtype == yg.dtype == jnp.float32
    scale = float(jnp.abs(yg).max())
    assert scale > 0
    tol = 1e-5 if dtype == "float32" else 4e-3   # a bfloat16 ulp
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yg), rtol=tol,
                               atol=tol * scale)
    assert {name: int(v) for name, v in cd.items()} == \
        {name: int(v) for name, v in cg.items()}
    local = np.asarray(idx) - first
    on = (local >= 0) & (local < e)
    if masked:
        on &= np.asarray(live)[:, None]
    assert int(cd["routed_here"]) == int(on.sum())


#: the three sparse configurations of the benchmark: the decoder spec
#: (router width, top-k), the tiny spec whose widths stand in for it in a
#: jaxpr, and the form their decode step takes
_SPARSE = [("nemotron3_super_ep4", "nemotron_h_tiny", "dense"),
           ("k_exaone_236b_ep8", "exaone_moe_tiny", "grouped"),
           ("xing4_29b_ep8", "xing4_tiny", "grouped")]


@pytest.mark.parametrize("config,tiny,form", _SPARSE)
def test_held_form_rule_over_the_served_shapes(config, tiny, form):
    """The rule at each sparse configuration's served shapes: its decode
    step (``max_slots`` rows) takes ``form`` and every served prefill
    bucket (128 rows and more) the grouped form. The decode step itself,
    traced on the CPU at tiny widths with the published router width,
    top-k and slots: Nemotron's holds no ``ragged_dot``, K-EXAONE's and
    Xing4's three a sparse layer (gate, up, down), as before the dense
    form existed, and ``mxtpu_moe_held_form_total`` counts a traced call
    a sparse layer under the form taken."""
    import json
    import pathlib

    import jax

    from incubator_mxnet_tpu import serving, telemetry
    from incubator_mxnet_tpu.gluon.model_zoo import decoder, get_decoder
    from incubator_mxnet_tpu.ops.moe import held_form

    cfg = json.loads((pathlib.Path(__file__).parents[1] / "chipbench" /
                      "configs" / f"{config}.json").read_text())
    spec = decoder._SPECS[cfg["zoo"]["spec"]]
    width, k = spec["num_experts"], spec["experts_per_token"]
    slots = cfg["serving"]["max_slots"]
    assert held_form(slots, k, width) == form
    buckets = cfg["serving"]["prefill_buckets"]
    assert min(buckets) >= 128
    assert {held_form(b, k, width) for b in buckets} == {"grouped"}
    assert held_form(128, 22, 512) == "grouped"

    net = get_decoder(tiny, num_experts=width, experts_held=4,
                      experts_per_token=k, expert_share=0)
    net.initialize(init="xavier")
    sparse = sum(ffn == "sparse" for _, ffn in net._kinds)
    assert sparse >= 2
    counters = {f: telemetry.counter("mxtpu_moe_held_form_total", form=f)
                for f in ("dense", "grouped")}
    with serving.DecodeSession(net, max_slots=slots, max_len=32,
                               prefill_buckets=(16,), name=f"form_{tiny}",
                               artifact_dir="") as sess:
        caches = [jax.ShapeDtypeStruct(s.shape, s.dtype)
                  for s in sess._kv.specs()]
        vec = jax.ShapeDtypeStruct((slots,), np.int32)
        before = {f: c.value for f, c in counters.items()}
        text = str(jax.make_jaxpr(sess._decode_apply)(
            sess._params, *caches, vec, vec))
    assert {f: c.value - before[f] for f, c in counters.items()} == {
        f: sparse if f == form else 0 for f in counters}
    ragged = text.count("ragged_dot_general[")
    assert ragged == (0 if form == "dense" else 3 * sparse), ragged
