"""The data-built decoder's latent-attention layer under hyper-connections
(``gluon/model_zoo/decoder.py``: ``xing4_*`` specs) served through
``serving.DecodeSession``: a cache group of ONE tensor, prefill expanded
and the decode step absorbed, four Sinkhorn-mixed streams, against the
plain float32 reference (``chipbench/references/xing4.py``, which shares
no code with it). Small sizes, seeded weights, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import serving, telemetry
from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
from incubator_mxnet_tpu.gluon.model_zoo.decoder import rms_norm
from incubator_mxnet_tpu.ops import hyper_connection, kv_cache
from chipbench import manifest as mf
from chipbench.harness import leaf_targets
from chipbench.references import xing4 as ref
from test_hybrid_decoder import _ByHand, _aliased_outputs, _close

VOCAB, ROW = 97, 128


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(
        mf.config_file(mf.load_manifest(), "xing4_29b_ep8"), True)


def _set(net, cfg, g, layers):
    targets, params = leaf_targets(cfg), net._collect_params_with_prefix()
    left = set(params)
    for leaf, arr in ref.flatten_leaves(g, layers).items():
        params[targets[leaf]].set_data(mx.nd.NDArray(arr))
        left.discard(targets[leaf])
    assert not left


def _build(cfg, seed=5):
    """The zoo's tiny decoder with the reference's seeded leaves set into
    it by the configuration's own name map, in float32."""
    model = dict(cfg["model"])
    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    g, lazy = ref.draw_all(model, seed, "float32")
    layers = [lazy[i] for i in range(len(lazy))]
    _set(net, cfg, g, layers)
    return net, model, g, layers


def _want(model, g, layers, tokens):
    """The reference's logits (T, V) of one whole sequence."""
    fwd = jax.jit(lambda g_, layers_, t: ref.forward(model, g_, layers_, t))
    return np.asarray(fwd(g, layers, jnp.asarray(tokens, jnp.int32)[None]))[0]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 6])
def test_forward_agrees_with_the_reference(cfg, seed):
    net, model, g, layers = _build(cfg, seed)
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 24))
    got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, want in zip(got, (_want(model, g, layers, t) for t in toks)):
        _close(row, want)
    # the layer-by-layer path the chip uses gives the same logits
    again = np.asarray(ref.sequence_logits(model, seed, "float32", toks))
    _close(again[0], _want(model, g, layers, toks[0]))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_absorbed_and_expanded_attention_give_the_same_output(cfg, layer):
    """One layer's attention over 20 positions, expanded (every head's key
    and value from the latent), against the absorbed step of the 20th
    token over a cache that holds the first 19 rows: the same output, and
    the step's new row is the expanded pass's 20th."""
    net, *_ = _build(cfg)
    lp = {k: jnp.asarray(v.asnumpy()) for k, v in net._layer(
        dict(zip(*net._arrays())), layer).items()}
    t, slots = 20, 3
    x = jax.random.normal(jax.random.PRNGKey(layer), (1, t, 64), jnp.float32)
    positions = jnp.arange(t, dtype=jnp.int32)[None]
    want, rows = net._latent_expanded(lp, x, positions)
    assert rows.shape == (1, 1, t, ROW)
    assert not np.asarray(rows[..., 40:]).any()      # 32 + 8, the rest zero
    cache = jnp.zeros((3, slots, 1, 32, ROW), jnp.float32)
    cache = cache.at[layer, 1, :, :t - 1].set(rows[0, :, :t - 1])
    lens = jnp.asarray([0, t - 1, 0], jnp.int32)
    xs = jnp.zeros((slots, 1, 64), jnp.float32).at[1].set(x[0, t - 1:])
    got, new = net._latent_absorbed(lp, xs, lens, cache, layer,
                                    kv_cache.address(lens, 32, "latent"))
    _close(np.asarray(got[1, 0]), np.asarray(want[0, t - 1]))
    _close(np.asarray(new[1, 0, 0]), np.asarray(rows[0, 0, t - 1]))


@pytest.mark.parametrize("n_prompt,n_new", [
    (3, 3),         # a prompt far under its bucket
    (16, 4),        # the prompt fills its bucket exactly
    (11, 8),        # a padded 16-bucket
    (21, 14),       # a 32-bucket, a longer decode
])
def test_prefill_then_decode_logits_agree_with_the_full_forward(
        cfg, n_prompt, n_new):
    """Prefill (expanded) then decode steps (absorbed) through the
    one-tensor cache against the reference's full forward (expanded)."""
    net, model, g, layers = _build(cfg)
    seq = np.random.default_rng(n_prompt).integers(0, VOCAB,
                                                   n_prompt + n_new)
    want = _want(model, g, layers, seq)
    hand = _ByHand(net)
    try:
        last, planes = hand.join(1, seq[:n_prompt])
        assert [p.shape for p in planes] == [
            (3, 1, hand.sess._prefill.bucket_for(n_prompt), ROW)]
        _close(last, want[n_prompt - 1])
        for j in range(n_prompt, n_prompt + n_new):
            got, _ = hand.step({1: seq[j]})
            _close(got[1], want[j])
    finally:
        hand.close()


def test_slots_join_and_leave_mid_stream(cfg):
    """Three sequences of different ages share the step: one joins while
    the others decode, one leaves and its slot is joined again; every
    logit agrees with that sequence's own full forward, and the step's
    counters count the occupied slots only."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(3)
    seqs = {s: rng.integers(0, VOCAB, n) for s, n in
            ((0, 30), (1, 26), (2, 40), (3, 22))}
    want = {s: _want(model, g, layers, t) for s, t in seqs.items()}
    hand = _ByHand(net)
    at = {}

    def join(slot, who, n):
        last, _ = hand.join(slot, seqs[who][:n])
        _close(last, want[who][n - 1])
        at[slot] = [who, n]

    def step():
        got, counters = hand.step({s: seqs[w][j] for s, (w, j) in at.items()})
        for s, (w, j) in at.items():
            _close(got[s], want[w][j])
            at[s][1] += 1
        sparse = model["mlp_layer_types"].count("sparse")
        assert counters[1] == len(at) * model["num_experts_per_tok"] * sparse
        assert 0 <= counters[0] <= counters[1]
        assert counters[2] <= model["num_experts"] * sparse
        assert counters[3] <= len(at)

    try:
        join(0, 0, 12)
        step(), step()
        join(2, 2, 19)              # joins while slot 0 decodes
        for _ in range(5):
            step()
        join(1, 1, 9)
        for _ in range(6):
            step()
        del at[0]                   # leaves; its slot is taken again
        hand.leave(0)
        join(0, 3, 4)
        for _ in range(10):
            step()
    finally:
        hand.close()


def test_session_streams_the_references_greedy_tokens_across_churn(cfg):
    """Through the scheduler: more requests than slots, of mixed lengths;
    every stream is the greedy continuation the reference's full forward
    gives; the ledger's step records carry the routing counts and the
    cache's rows and bytes."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, n) for n in (5, 13, 9, 30, 17, 3)]
    news = [12, 7, 15, 9, 11, 14]
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16, 32),
                               name="churn4") as sess:
        handles = [sess.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, news)]
        got = [h.result(120.0) for h in handles]
    for prompt, m, out in zip(prompts, news, got):
        logits = _want(model, g, layers, list(prompt) + out)
        assert len(out) == m
        assert out == np.argmax(logits[len(prompt) - 1:-1], -1).tolist()
    steps = [r for r in telemetry.trace.ring()["steps"]
             if r.get("site") == "decode.churn4"
             and r.get("kind") != "prefill"]
    assert steps and all(
        {"routed_here", "routed_all", "experts_hit", "expert_load_max",
         "kv_live_rows", "kv_read_rows", "kv_read_bytes", "kv_rows"}
        <= set(r) for r in steps)
    assert all(r["routed_all"] == r["active"] * 2 * 2 for r in steps)
    # the dense path off the TPU: the whole plane of every active slot,
    # one tensor of a tile of 128 float32 lanes a row
    assert all(r["kv_read_rows"] == 3 * 64 * r["active"] for r in steps)
    assert all(r["kv_read_bytes"] == r["kv_read_rows"] * ROW * 4
               for r in steps)
    assert steps[0]["kv_rows"] == 3 * 3 * 64


# -- the residual path --------------------------------------------------------

def test_h_res_is_doubly_stochastic_and_moves_from_token_to_token(cfg):
    """The seeded coefficients over a prompt: every ``H_res`` has row and
    column sums within 1e-4 of 1, is neither the identity nor uniform, and
    differs from token to token; the program's are the reference's."""
    net, model, g, layers = _build(cfg)
    s = ref.sizes(model)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, (1, 40)))
    x = ref.embed(g, toks, s)
    x = ref.block(layers[0], x, s, s["ffn"][0])[0]     # streams that differ
    pre, post, res = ref.coefficients(layers[1], "ha", x, s)
    res = np.asarray(res)[0]                           # (T, n, n)
    assert np.abs(res.sum(-1) - 1).max() < 1e-4
    assert np.abs(res.sum(-2) - 1).max() < 1e-4
    assert np.abs(res - np.eye(4)).max(axis=(1, 2)).min() > 0.2
    assert (res.max(axis=(1, 2)) - res.min(axis=(1, 2))).min() > 0.1
    assert np.abs(res - res[:1]).max() > 0.02
    assert 0 < np.asarray(pre).min() and np.asarray(pre).max() < 1
    lp = {k: jnp.asarray(v.asnumpy()) for k, v in net._layer(
        dict(zip(*net._arrays())), 1).items()}
    got = hyper_connection.coefficients(
        jnp.moveaxis(x, 2, 0), lp["hc_attn_w"], lp["hc_attn_scale"],
        lp["hc_attn_bias"], 20, 1e-6, (-30.0, 30.0))
    for a, b in zip(got, (jnp.moveaxis(pre, -1, 0), jnp.moveaxis(post, -1, 0),
                          jnp.moveaxis(res[None], (-2, -1), (0, 1)))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=2e-6)


def test_the_residual_path_is_the_written_out_loop():
    """``read`` and ``write`` against loops over streams and channels,
    and ``sinkhorn`` against its definition entry by entry."""
    rs = np.random.RandomState(0)
    n, t, c = 4, 3, 5
    x = rs.standard_normal((n, t, c)).astype(np.float32)
    y = rs.standard_normal((t, c)).astype(np.float32)
    pre, post = rs.uniform(0.2, 0.8, (2, n, t)).astype(np.float32)
    m = np.exp(rs.standard_normal((n, n, t))).astype(np.float32)
    res = np.asarray(hyper_connection.sinkhorn(jnp.asarray(m), 20, 1e-6))
    want = m.astype(np.float64)
    for _ in range(20):
        for j in range(n):
            want[:, j] /= want[:, j].sum(axis=0) + 1e-6
        for i in range(n):
            want[i] /= want[i].sum(axis=0) + 1e-6
    np.testing.assert_allclose(res, want, rtol=1e-5)
    u = np.zeros((t, c))
    out = np.zeros((n, t, c))
    for tok in range(t):
        for ch in range(c):
            for i in range(n):
                u[tok, ch] += pre[i, tok] * x[i, tok, ch]
                out[i, tok, ch] = post[i, tok] * y[tok, ch] + sum(
                    res[i, j, tok] * x[j, tok, ch] for j in range(n))
    np.testing.assert_allclose(
        np.asarray(hyper_connection.read(jnp.asarray(x), jnp.asarray(pre))),
        u, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(hyper_connection.write(jnp.asarray(x), jnp.asarray(res),
                                          jnp.asarray(post), jnp.asarray(y))),
        out, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tokens", [5, 130])
def test_the_coefficient_kernel_is_the_plain_statement(tokens):
    """``activate`` as the TPU's kernel (in the interpreter) against its
    plain statement, the clamp reached on some entries."""
    z = 1.5 * jax.random.normal(jax.random.PRNGKey(tokens), (24, tokens))
    z = z.at[9, 0].set(50.0).at[20, 1].set(-50.0)
    want = hyper_connection._activate_plain(z, 4, 20, 1e-6, (-30.0, 30.0))
    got = hyper_connection.activate_kernel(z, 4, 20, 1e-6, (-30.0, 30.0),
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-7)
    # rows exactly (the last half-round), columns as far as 20 rounds get
    res = np.asarray(got[8:]).reshape(4, 4, tokens)[..., 2:]   # unclamped
    assert np.abs(res.sum(1) - 1).max() < 1e-4
    assert np.abs(res.sum(0) - 1).max() < 2e-2


def test_the_eight_shares_add_up_to_the_uncut_layer(cfg):
    """The new block's FFN sub-layer, 16 experts top-3, cut into eight
    shares of two: the routed parts all the shares compute (the program's
    ``_ffn`` of each share less the shared expert, which every chip
    computes alike), summed, plus the shared expert counted once, written
    back through the hyper-connection, are the uncut reference's
    sub-layer."""
    model = dict(cfg["model"], num_hidden_layers=1, n_layer=1,
                 first_k_dense_replace=0, n_routed_experts=16,
                 num_experts=16, num_experts_per_tok=3,
                 expert_share={"index": 0, "of": 1},
                 mlp_layer_types=["sparse"])
    s = ref.sizes(model)
    p = ref.draw_layer(model, ref.root_key(9), 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 4, 64), jnp.float32)
    whole = ref.sub_layer(
        p, "hf", "fn_g", x, s,
        lambda u: ref.sparse_ffn(p, u, s, ref._mm_f32, lambda a: a)[0],
        lambda a: a)
    xs = jnp.moveaxis(x[0], 1, 0)                       # (n, T, C)
    pre, post, res = hyper_connection.coefficients(
        xs, p["hf_w"], p["hf_a"], p["hf_b"], 20, 1e-6, (-30.0, 30.0))
    u = rms_norm(hyper_connection.read(xs, pre), p["fn_g"], 1e-6)
    shared = ref.gated(u, p["sg_w"], p["su_w"], p["sd_w"], ref._mm_f32,
                       lambda a: a)
    routed, here = 0.0, 0
    for share in range(8):
        net = get_decoder("xing4_tiny", num_layers=1, dense_layers=0,
                          num_experts=16, experts_held=2,
                          experts_per_token=3, expert_share=share)
        held = slice(2 * share, 2 * share + 2)
        lp = dict(router=p["router_w"], router_bias=p["router_b"],
                  experts_gate=p["eg_w"][held], experts_up=p["eu_w"][held],
                  experts_down=p["ed_w"][held], shared_gate=p["sg_w"],
                  shared_up=p["su_w"], shared_down=p["sd_w"])
        y, counts = net._ffn(lp, u)
        routed, here = routed + (y - shared), here + int(
            counts["routed_here"])
    assert here == 20 * 3               # every choice lies in one share
    got = hyper_connection.write(xs, res, post, routed + shared)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(got, 0, 1)),
                               np.asarray(whole[0]), rtol=2e-5, atol=2e-5)


# -- the cache ----------------------------------------------------------------

def test_donated_decode_step_aliases_the_one_tensor_group(cfg):
    """Lowered as a donating session lowers it: ONE cache array for this
    model (its three layers' rows), aliased to an output; the compiled
    program keeps the alias."""
    net, *_ = _build(cfg)
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16,), name="alias4",
                               donate=True) as sess:
        assert sess._kv.shapes == [(3, 3, 1, 64, ROW)]
        assert len(sess._kv.arrays) == 1 and sess._kv.kinds == ["latent"]
        with pytest.raises(ValueError):
            sess._kv.k                  # no K/V pair to name
        lowered = sess._lower_decode()
        assert _aliased_outputs(lowered) == 1
        n = len(sess._params)
        assert f"{{1}}: ({n}, {{}}" in lowered.compile().as_text()


def test_kv_cache_counts_rows_and_bytes_of_a_latent_group():
    """One tensor a latent group, two a full one or a ring; rows are
    positions of a layer whatever they store; bytes are the stored
    rows'."""
    kv = serving.KVCache(
        [dict(layers=2, heads=2, rows=16, head_dim=4, kind="full"),
         dict(layers=0, heads=1, rows=4, head_dim=4, kind="ring"),
         dict(layers=3, heads=1, rows=16, head_dim=10, kind="latent")],
        slots=2)
    assert kv.shapes == [(2, 2, 2, 16, 4), (3, 2, 1, 16, 10)]
    assert kv.kinds == ["full", "latent"]
    assert kv.array_kinds == ["full", "full", "latent"]
    assert [a.shape for a in kv.arrays] == [s.shape for s in kv.specs()] \
        == [(2, 2, 2, 16, 4)] * 2 + [(3, 2, 1, 16, 10)]
    assert kv.rows == 2 * 2 * 16 + 3 * 2 * 16 and kv.max_len == 16
    assert kv.nbytes == 4 * (2 * 2 * 2 * 2 * 16 * 4 + 3 * 2 * 16 * 10)
    assert kv.live_rows([3, 9]) == (2 + 3) * (3 + 9)
    # off the TPU every group is read whole: both slots' planes
    assert kv.read([3, 9]) == (kv.rows, kv.nbytes)


@pytest.mark.parametrize("lens", [[0, 0], [1, 127], [128, 129], [255, 256]])
def test_block_kernel_over_one_tensor_is_the_dense_attention(lens):
    """``attend_blocks`` over a latent group (the kernel, in the
    interpreter: 4 queries a row, the values the first 128 lanes of the
    fetched key block) against ``read`` + ``attend``; dead blocks NaN."""
    rows, w, vw, layer = 2 * kv_cache.BLOCK, 256, 128, 1
    rs = np.random.RandomState(sum(lens))
    normal = lambda *shape: jnp.asarray(
        rs.standard_normal(shape).astype(np.float32))
    cache = np.asarray(normal(2, 2, 1, rows, w)).copy()
    for s, n in enumerate(lens):
        dead = -(-min(n, rows - 1) // kv_cache.BLOCK) * kv_cache.BLOCK
        cache[:, s, :, dead:] = np.nan
    n = jnp.asarray(lens, jnp.int32)
    q, new = normal(2, 1, 4, w), normal(2, 1, 1, w)
    got = kv_cache.attend_blocks(q, jnp.asarray(cache), None, layer, new,
                                 None, n, w, scale=0.11, v_width=vw,
                                 interpret=True)
    _, here, see = kv_cache.address(n, rows, "latent")
    k = kv_cache.read(jnp.nan_to_num(jnp.asarray(cache)), layer, new, here)
    want = kv_cache.attend(q, k, k[..., :vw], see, w, 0.11)
    assert got.shape == (2, 1, 4, vw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
