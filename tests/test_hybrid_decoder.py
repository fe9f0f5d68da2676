"""The data-built decoder (``gluon/model_zoo/decoder.py``) served through
``serving.DecodeSession``: window rings beside full K/V rows, dropless
routing over a share of the experts, against the plain float32 reference
(``chipbench/references/exaone_moe.py``, which shares no code with it).
Small sizes, seeded weights, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import serving, telemetry
from incubator_mxnet_tpu.gluon.model_zoo import get_decoder, get_gpt
from incubator_mxnet_tpu.ops.moe import moe_held_ffn, moe_route
from chipbench import manifest as mf
from chipbench.harness import leaf_targets
from chipbench.references import exaone_moe as ref

WINDOW, VOCAB = 8, 97


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(
        mf.config_file(mf.load_manifest(), "k_exaone_236b_ep8"), True)


def _build(cfg, seed=5, **zoo_args):
    """The zoo's tiny decoder with the reference's seeded leaves set into
    it by the configuration's own name map, in float32."""
    model = dict(cfg["model"])
    net = get_decoder(cfg["zoo"]["spec"], **dict(cfg["zoo"]["args"],
                                                 **zoo_args))
    g, lazy = ref.draw_all(model, seed, "float32")
    layers = [lazy[i] for i in range(len(lazy))]
    targets, params = leaf_targets(cfg), net._collect_params_with_prefix()
    left = set(params)
    for leaf, arr in ref.flatten_leaves(g, layers).items():
        params[targets[leaf]].set_data(mx.nd.NDArray(arr))
        left.discard(targets[leaf])
    assert not left
    return net, model, g, layers


def _want(model, g, layers, tokens):
    """The reference's logits (T, V) of one whole sequence."""
    fwd = jax.jit(lambda g_, layers_, t: ref.forward(model, g_, layers_, t))
    return np.asarray(fwd(g, layers, jnp.asarray(tokens, jnp.int32)[None]))[0]


def _close(got, want, tol=2e-5):
    assert np.abs(got - want).max() < tol * (1 + np.abs(want).max())


def test_forward_agrees_with_the_reference(cfg):
    net, model, g, layers = _build(cfg)
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 24))
    got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, want in zip(got, (_want(model, g, layers, t) for t in toks)):
        _close(row, want)
    # the layer-by-layer path the chip uses gives the same logits
    again = np.asarray(ref.sequence_logits(model, 5, "float32", toks))
    _close(again[0], _want(model, g, layers, toks[0]))


def test_window_layers_see_only_the_window():
    """One window layer: a changed token moves the logits of the
    positions less than ``window`` behind it and of no later one."""
    net = get_decoder("exaone_moe_tiny", num_layers=1, pattern="L")
    net.initialize(init="xavier")
    toks = np.random.default_rng(1).integers(1, VOCAB, (1, 24))
    other = toks.copy()
    other[0, 5] = (other[0, 5] + 1) % VOCAB
    a = net(mx.nd.array(toks, dtype="int32")).asnumpy()[0]
    b = net(mx.nd.array(other, dtype="int32")).asnumpy()[0]
    moved = np.abs(a - b).max(-1) > 0
    assert moved[5:5 + WINDOW].all() and not moved[:5].any()
    assert not moved[5 + WINDOW:].any()


class _ByHand:
    """The session's own executables driven without its scheduler, so
    that a test sees logits: prefill, join into a slot, decode steps."""

    def __init__(self, net, slots=3, max_len=64, buckets=(16, 32)):
        self.sess = serving.DecodeSession(
            net, max_slots=slots, max_len=max_len, prefill_buckets=buckets,
            name="byhand", donate=False)
        self.net, self.slots = net, slots
        self.lens = np.zeros(slots, np.int32)
        self.run = self.sess._run

    def join(self, slot, prompt):
        n = len(prompt)
        bucket = self.sess._prefill.bucket_for(n)
        padded = np.zeros(bucket, np.int32)
        padded[:n] = prompt
        last, *planes = self.run(self.net.serve_prefill, self.sess._params,
                                 jnp.asarray(padded), jnp.int32(n))
        kv = self.sess._kv
        kv.arrays = list(self.sess._join_exec(bucket)(
            *kv.arrays, *planes, jnp.asarray([slot, n], jnp.int32)))
        self.lens[slot] = n
        return np.asarray(last), planes

    def step(self, tokens):
        """``tokens`` {slot: token}; returns {slot: logits}, counters."""
        vec = np.zeros(self.slots, np.int32)
        for s, t in tokens.items():
            vec[s] = t
        kv = self.sess._kv
        logits, counters, *kv.arrays = self.run(
            self.net.serve_step, self.sess._params, jnp.asarray(vec),
            jnp.asarray(self.lens), *kv.arrays)
        for s in tokens:
            self.lens[s] += 1
        return {s: np.asarray(logits[s]) for s in tokens}, \
            np.asarray(counters)

    def leave(self, slot):
        self.lens[slot] = 0

    def close(self):
        self.sess.close()


@pytest.mark.parametrize("n_prompt,n_new", [
    (3, 3),         # all below the window
    (WINDOW, 4),    # the prompt fills the ring exactly
    (11, 8),        # past the window: a padded 16-bucket, ring wraps once
    (21, 14),       # a 32-bucket, more than one whole wrap while decoding
])
def test_prefill_then_decode_logits_agree_with_the_full_forward(
        cfg, n_prompt, n_new):
    net, model, g, layers = _build(cfg)
    seq = np.random.default_rng(n_prompt).integers(0, VOCAB,
                                                   n_prompt + n_new)
    want = _want(model, g, layers, seq)
    hand = _ByHand(net)
    try:
        last, _ = hand.join(1, seq[:n_prompt])
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


def test_ring_join_takes_the_last_rows_below_the_true_length(cfg):
    """A prompt of 11 in a bucket of 16: the ring (8 rows) holds positions
    3..10, each at ``position mod 8``, and nothing of the padding; the
    full group holds the plane from row 0."""
    net, *_ = _build(cfg)
    hand = _ByHand(net)
    try:
        prompt = np.random.default_rng(2).integers(0, VOCAB, 11)
        _, planes = hand.join(2, prompt)
        full_k, _, ring_k, ring_v = hand.sess._kv.arrays
        plane_full, plane_ring = np.asarray(planes[0]), np.asarray(planes[2])
        np.testing.assert_array_equal(np.asarray(full_k)[:, 2, :, :16],
                                      plane_full)
        for p in range(3, 11):
            np.testing.assert_array_equal(
                np.asarray(ring_k)[:, 2, :, p % WINDOW], plane_ring[:, :, p])
            np.testing.assert_array_equal(
                np.asarray(ring_v)[:, 2, :, p % WINDOW],
                np.asarray(planes[3])[:, :, p])
        assert hand.sess._kv.shapes == [(1, 3, 2, 64, 16), (2, 3, 2, 8, 16)]
    finally:
        hand.close()


def test_session_streams_the_references_greedy_tokens_across_churn(cfg):
    """Through the scheduler: more requests than slots, of mixed lengths;
    every stream is the greedy continuation the reference's full forward
    gives, the ledger's step records carry the routing and cache counts."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, n) for n in (5, 13, 9, 30, 17, 3)]
    news = [12, 7, 15, 9, 11, 14]

    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16, 32),
                               name="churn") as sess:
        handles = [sess.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, news)]
        got = [h.result(120.0) for h in handles]
    for prompt, m, out in zip(prompts, news, got):
        # greedy: each token is the reference's best after what came before
        logits = _want(model, g, layers, list(prompt) + out)
        assert len(out) == m
        assert out == np.argmax(logits[len(prompt) - 1:-1], -1).tolist()
    steps = [r for r in telemetry.trace.ring()["steps"]
             if r.get("site") == "decode.churn" and r.get("kind") != "prefill"]
    assert steps and all(
        {"routed_here", "routed_all", "experts_hit", "expert_load_max",
         "kv_live_rows", "kv_read_rows", "kv_rows"} <= set(r) for r in steps)
    assert all(r["routed_all"] == r["active"] * 2 * 2 for r in steps)
    assert all(0 < r["kv_live_rows"] <= r["kv_read_rows"] <= r["kv_rows"]
               for r in steps)
    # the dense path (full group and rings alike, off the TPU): the whole
    # plane of every active slot
    assert all(r["kv_read_rows"] * 3 == r["kv_rows"] * r["active"]
               for r in steps)
    assert steps[0]["kv_rows"] == 3 * (1 * 64 + 2 * WINDOW)


# -- the expert layer ----------------------------------------------------------

def _expert_weights(rng, e, c, f):
    return tuple(rng.standard_normal(s).astype(np.float32) * 0.2
                 for s in ((e, c, f), (e, c, f), (e, f, c)))


def _dense_experts(x, idx, w, wg, wu, wd, first):
    """Every held expert for every token, masked by the choice."""
    y = np.zeros_like(x)
    for e in range(wg.shape[0]):
        gate = x @ wg[e]
        out = (gate / (1 + np.exp(-gate)) * (x @ wu[e])) @ wd[e]
        y += (w * (idx == first + e)).sum(-1, keepdims=True) * out
    return y


def test_expert_layer_drops_nothing_under_uneven_routing():
    """12 of 16 tokens choose expert 5, none chooses expert 6, the rest are
    spread over held and absent experts: every choice on a held expert is
    served (no capacity), the counts say so."""
    rng = np.random.default_rng(0)
    n, c, f, k, first = 16, 12, 10, 2, 4
    x = rng.standard_normal((n, c)).astype(np.float32)
    wg, wu, wd = _expert_weights(rng, 4, c, f)          # experts 4..7 of 12
    idx = np.stack([np.where(np.arange(n) < 12, 5, 7),
                    rng.choice([0, 1, 4, 9, 11], n)], -1).astype(np.int32)
    w = rng.uniform(0.2, 1.0, (n, k)).astype(np.float32)
    y, counts = moe_held_ffn(jnp.asarray(x), jnp.asarray(idx),
                             jnp.asarray(w), wg, wu, wd, first_expert=first)
    np.testing.assert_allclose(np.asarray(y),
                               _dense_experts(x, idx, w, wg, wu, wd, first),
                               rtol=1e-4, atol=1e-5)
    on_4 = int((idx == 4).sum())
    assert int(counts["routed_here"]) == 16 + on_4
    assert int(counts["load_max"]) == 12
    assert int(counts["experts_hit"]) == 2 + (on_4 > 0)


def test_routing_is_the_published_rule():
    """sigmoid scores, the k largest of score + bias chosen, weights the
    chosen scores over their sum, times the scale: the bias decides a
    choice and does not enter a weight."""
    logits = jnp.asarray([[2.0, 1.0, 0.9, -1.0]])
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    idx, w = moe_route(logits, 2, bias=bias, scale=2.5)
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 2]
    want = 2.5 * s[np.asarray(idx[0])] / (s[0] + s[2])
    np.testing.assert_allclose(np.asarray(w[0]), want, rtol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One sparse layer, 16 experts top-3, cut into eight shares of two:
    the routed parts all the shares compute, summed, plus the shared
    expert counted once, are the uncut reference layer."""
    model = dict(hidden_size=24, num_hidden_layers=1, num_attention_heads=2,
                 num_key_value_heads=1, head_dim=8, intermediate_size=16,
                 moe_intermediate_size=12, num_experts=16,
                 expert_share={"index": 0, "of": 1}, num_experts_per_tok=3,
                 vocab_size=11, sliding_window=4,
                 rope_parameters={"rope_theta": 1e4}, rms_norm_eps=1e-5,
                 routed_scaling_factor=2.5, layer_types=["full_attention"],
                 mlp_layer_types=["sparse"])
    s = ref.sizes(model)
    p = ref.draw_layer(model, ref.root_key(9), 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 24), jnp.float32)
    whole = ref.sparse_ffn(p, x, s, ref._mm_f32, lambda a: a)[0]
    shared = ref.gated(x, p["sg_w"], p["su_w"], p["sd_w"], ref._mm_f32,
                       lambda a: a)
    logits = jnp.einsum("ni,ei->ne", x[0], p["router_w"],
                        precision=jax.lax.Precision.HIGHEST)
    idx, w = moe_route(logits, 3, bias=p["router_b"], scale=2.5)
    total, here = 0.0, 0
    for share in range(8):
        held = slice(2 * share, 2 * share + 2)
        y, counts = moe_held_ffn(x[0], idx, w, p["eg_w"][held],
                                 p["eu_w"][held], p["ed_w"][held],
                                 first_expert=2 * share)
        total, here = total + y, here + int(counts["routed_here"])
    assert here == 20 * 3               # every choice lies in one share
    np.testing.assert_allclose(np.asarray(total + shared[0]),
                               np.asarray(whole[0]), rtol=2e-5, atol=2e-6)


# -- the cache -------------------------------------------------------------------

def _aliased_outputs(lowered):
    return lowered.as_text().count("tf.aliasing_output")


def test_donated_decode_step_aliases_every_cache_array(cfg):
    """Lowered as a donating session lowers it: four cache arrays (K and V
    of the full group and of the ring) for this model, two for GPT-2, each
    aliased to an output; the compiled program keeps every alias."""
    net, *_ = _build(cfg)
    gpt = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, max_length=64,
                  dropout=0.0)
    gpt.initialize(init="xavier")
    for block, arrays in ((net, 4), (gpt, 2)):
        with serving.DecodeSession(block, max_slots=3, max_len=64,
                                   prefill_buckets=(16,), name="alias",
                                   donate=True) as sess:
            assert len(sess._kv.arrays) == arrays
            lowered = sess._lower_decode()
            assert _aliased_outputs(lowered) == arrays
            text = lowered.compile().as_text()
            n = len(sess._params)
            for j in range(arrays):
                assert f"{{{j + 1}}}: ({n + j}, {{}}" in text


def test_gpt2_through_the_cache_groups_gives_the_tokens_it_gave():
    """GPT-2 declares one full group: the cache is one array pair (in
    the stored form: the tiny spec's 4 heads of 16 side by side in one
    row of 128 lanes), and the greedy stream is the full forward's."""
    np.random.seed(0)
    mx.random.seed(0)
    net = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, max_length=48,
                  dropout=0.0)
    net.initialize(init="xavier")
    prompt = np.random.RandomState(3).randint(1, VOCAB, 7).astype(np.int32)
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="one") as sess:
        assert sess._kv.shapes == [(2, 2, 1, 48, 128)]
        assert sess._kv.shape == (2, 2, 1, 48, 128)
        assert sess._kv.k.shape == sess._kv.v.shape == sess._kv.shape
        got = sess.generate(prompt, max_new_tokens=6)
    seq, want = list(prompt), []
    for _ in range(6):
        lg = net(mx.nd.array(np.array(seq)[None], dtype="int32")).asnumpy()
        want.append(int(np.argmax(lg[0, -1])))
        seq.append(want[-1])
    assert got == want


def test_kv_cache_counts_its_rows():
    kv = serving.KVCache(
        [dict(layers=2, heads=1, rows=16, head_dim=4, kind="full"),
         dict(layers=0, heads=1, rows=4, head_dim=4, kind="ring"),
         dict(layers=3, heads=1, rows=4, head_dim=4, kind="ring")], slots=2)
    assert kv.shapes == [(2, 2, 1, 16, 4), (3, 2, 1, 4, 4)]
    assert kv.rows == 2 * 2 * 16 + 3 * 2 * 4 and kv.max_len == 16
    assert kv.nbytes == 2 * 4 * 4 * kv.rows
    assert kv.live_rows([3, 9]) == 2 * (3 + 9) + 3 * (3 + 4)
    assert kv.read([3, 9]) == (kv.rows, kv.nbytes)
