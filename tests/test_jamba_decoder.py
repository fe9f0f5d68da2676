"""The data-built decoder's selective state-space layer beside multi-query
attention (``gluon/model_zoo/decoder.py``: ``jamba*`` specs) served through
``serving.DecodeSession``: a ``state`` cache group that a step replaces,
prefill by chunks at the true length of a padded bucket and the decode
step one position at a time, against the plain float32 reference
(``chipbench/references/jamba.py``: one ``lax.scan`` over positions, which
shares no code with it). Small sizes, seeded weights, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import serving, telemetry
from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
from chipbench import manifest as mf
from chipbench.harness import leaf_targets
from chipbench.references import jamba as ref
from test_hybrid_decoder import _ByHand, _aliased_outputs, _close

VOCAB = 97


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(
        mf.config_file(mf.load_manifest(), "jamba2_3b"), True)


def _build(cfg, seed=5):
    """The zoo's tiny decoder with the reference's seeded leaves set into
    it by the configuration's own name map, in float32."""
    model = dict(cfg["model"])
    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
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


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 6])
def test_forward_agrees_with_the_reference(cfg, seed):
    net, model, g, layers = _build(cfg, seed)
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 70))
    got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, want in zip(got, (_want(model, g, layers, t) for t in toks)):
        _close(row, want)
    # the layer-by-layer path the chip uses gives the same logits
    again = np.asarray(ref.sequence_logits(model, seed, "float32", toks))
    _close(again[0], _want(model, g, layers, toks[0]))
    # and the seeded recurrence does work: a changed first token moves the
    # last position's logits through the state-space layers alone
    other = toks.copy()
    other[0, 0] = (other[0, 0] + 1) % VOCAB
    moved = net(mx.nd.array(other, dtype="int32")).asnumpy()[0, -1]
    assert np.abs(moved - got[0, -1]).max() > 1e-4


def test_the_layers_come_from_the_sources_keys():
    """The published spec, shapes only (nothing is allocated): attention
    where ``i % 14 == 7``, 20 query heads over ONE K/V head of 128 with no
    per-head norm, every FFN dense 8192, the mixer 5120 / 16 / 160 / 4
    with its three small norms and the convolution's bias, a tied table."""
    net = get_decoder("jamba2_3b", max_length=4096)
    kinds = [a for a, _ in net._kinds]
    assert [i for i, k in enumerate(kinds) if k == "full_attention"] == [7, 21]
    assert kinds.count("mamba") == 26 and len(kinds) == 28
    assert {f for _, f in net._kinds} == {"dense"}
    shapes = {n: p.shape for n, p in
              net._collect_params_with_prefix().items()}
    assert "head" not in shapes and shapes["embed"] == (65536, 2560)
    assert {n[len("layer7_"):]: s for n, s in shapes.items()
            if n.startswith("layer7_")} == dict(
        attn_norm=(2560,), ffn_norm=(2560,), q=(2560, 2560), k=(128, 2560),
        v=(128, 2560), o=(2560, 2560), gate=(8192, 2560), up=(8192, 2560),
        down=(2560, 8192))
    assert {n[len("layer0_"):]: s for n, s in shapes.items()
            if n.startswith("layer0_")} == dict(
        attn_norm=(2560,), ffn_norm=(2560,), in_proj=(10240, 2560),
        conv_w=(4, 5120), conv_bias=(5120,), x_proj=(192, 5120),
        dt_norm=(160,), b_norm=(16,), c_norm=(16,), dt_proj=(5120, 160),
        dt_bias=(5120,), a_log=(16, 5120), d_skip=(5120,),
        out_proj=(2560, 5120), gate=(8192, 2560), up=(8192, 2560),
        down=(2560, 8192))
    assert net.cache_groups(4096) == [
        dict(layers=2, heads=1, rows=4096, head_dim=128, kind="full"),
        dict(layers=26, kind="state", width=5120, state=16, taps=3,
             dtype="float32")]


@pytest.mark.parametrize("prompts,n_new", [
    ((3, 21), 6),       # far under a 16-bucket beside a padded 32-bucket
    ((16, 32), 4),      # both prompts fill their buckets exactly
    ((1, 17), 9),       # one token: the taps are zeros but the last
])
def test_prefill_then_decode_logits_agree_with_the_full_forward(
        cfg, prompts, n_new):
    """Prompts in two different buckets prefilled (the scan by chunks, the
    state taken at the true length) and decoded side by side (one position
    a step through the cached state) against the reference's full forward
    pass, logits at every generated position."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(sum(prompts))
    seqs = {s: rng.integers(0, VOCAB, n + n_new)
            for s, n in zip((2, 0), prompts)}
    want = {s: _want(model, g, layers, t) for s, t in seqs.items()}
    hand = _ByHand(net)
    try:
        for (s, seq), n in zip(seqs.items(), prompts):
            last, planes = hand.join(s, seq[:n])
            bucket = hand.sess._prefill.bucket_for(n)
            assert [p.shape for p in planes] == [
                (1, 1, bucket, 16), (1, 1, bucket, 16), (3, 8, 128),
                (3, 3, 128)]
            _close(last, want[s][n - 1])
        for j in range(n_new):
            got, _ = hand.step({s: seqs[s][n + j]
                                for s, n in zip(seqs, prompts)})
            for s, n in zip(seqs, prompts):
                _close(got[s], want[s][n + j])
    finally:
        hand.close()


def test_a_freed_slot_answers_as_a_fresh_one(cfg):
    """A slot that served a long sequence is left (nothing is zeroed) and
    joined again: the new sequence's logits are its own full forward's,
    while its neighbour decodes on undisturbed."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(9)
    seqs = {w: rng.integers(0, VOCAB, n) for w, n in
            (("old", 40), ("stays", 44), ("new", 20))}
    want = {w: _want(model, g, layers, t) for w, t in seqs.items()}
    hand = _ByHand(net)
    at = {}

    def join(slot, who, n):
        last, _ = hand.join(slot, seqs[who][:n])
        _close(last, want[who][n - 1])
        at[slot] = [who, n]

    def step():
        got, _ = hand.step({s: seqs[w][j] for s, (w, j) in at.items()})
        for s, (w, j) in at.items():
            _close(got[s], want[w][j])
            at[s][1] += 1

    try:
        join(1, "old", 18)
        join(0, "stays", 5)
        for _ in range(20):
            step()
        state = [np.asarray(a[:, 1]) for a in hand.sess._kv.arrays[2:]]
        assert all(np.abs(s).max() > 0 for s in state)
        del at[1]
        hand.leave(1)
        step(), step()              # the free slot computes on garbage
        join(1, "new", 7)
        for _ in range(12):
            step()
    finally:
        hand.close()


def test_session_streams_the_references_greedy_tokens_across_churn(cfg):
    """Through the scheduler: more requests than slots, of mixed lengths
    and buckets, slots reused; every stream is the greedy continuation the
    reference's full forward gives; the ledger's step records carry the
    state's bytes beside the K/V rows of the one attention layer."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, n) for n in (5, 13, 9, 30, 17, 3)]
    news = [12, 7, 15, 9, 11, 14]
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16, 32),
                               name="churn5") as sess:
        slot_bytes = sess._kv.state_bytes(1)
        handles = [sess.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, news)]
        got = [h.result(120.0) for h in handles]
    for prompt, m, out in zip(prompts, news, got):
        logits = _want(model, g, layers, list(prompt) + out)
        assert len(out) == m
        assert out == np.argmax(logits[len(prompt) - 1:-1], -1).tolist()
    steps = [r for r in telemetry.trace.ring()["steps"]
             if r.get("site") == "decode.churn5"
             and r.get("kind") != "prefill"]
    # three state-space layers of 128 channels: 8 float32 of state and 3
    # float32 taps a channel (a float32 model), in and out
    assert slot_bytes == 2 * 3 * 128 * (8 + 3) * 4
    assert steps and all(r["state_bytes"] == r["active"] * slot_bytes
                         for r in steps)
    # the dense path off the TPU: the one attention layer's whole plane of
    # every active slot; the state group has no rows
    assert all(r["kv_read_rows"] == 64 * r["active"] for r in steps)
    assert steps[0]["kv_rows"] == 1 * 3 * 64


def test_a_block_without_a_state_group_writes_no_state_bytes():
    net = get_decoder("exaone_moe_tiny")
    net.initialize(init="xavier")
    with serving.DecodeSession(net, max_slots=2, max_len=32,
                               prefill_buckets=(8,), name="nostate") as sess:
        sess.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    steps = [r for r in telemetry.trace.ring()["steps"]
             if r.get("site") == "decode.nostate"
             and r.get("kind") != "prefill"]
    assert steps and not any("state_bytes" in r for r in steps)


def test_donated_decode_step_aliases_rows_and_state(cfg):
    """Lowered as a donating session lowers it: K and V of the attention
    layer, the state and the taps of the three state-space layers, each
    with a type of its own and each aliased to an output; the compiled
    program keeps every alias."""
    net, *_ = _build(cfg)
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16,), name="alias5",
                               donate=True) as sess:
        assert sess._kv.kinds == ["full", "state"]
        assert sess._kv.shapes == [(1, 3, 1, 64, 16), (3, 3, 8, 128)]
        assert [s.shape for s in sess._kv.specs()] == [
            (1, 3, 1, 64, 16), (1, 3, 1, 64, 16), (3, 3, 8, 128),
            (3, 3, 3, 128)]
        lowered = sess._lower_decode()
        assert _aliased_outputs(lowered) == 4
        text, n = lowered.compile().as_text(), len(sess._params)
        for j in range(4):
            assert f"{{{j + 1}}}: ({n + j}, {{}}" in text
