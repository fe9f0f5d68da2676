"""The data-built decoder run several times a token over the same weights
(``gluon/model_zoo/decoder.py``: ``ouro*`` specs, ``passes`` 4) served through
``serving.DecodeSession``: a K/V cache per (pass, layer), sandwich norms and
rotary positions on full-attention layers, against the plain float32
reference (``chipbench/references/ouro.py``: the whole sequence through
every pass at once, which shares no code with it). Small sizes, seeded
weights, float32 on the CPU.

Tolerance: 2e-5 of the logits' largest magnitude, as for the other
data-built decoders. The program and the reference compute the same
float32 numbers summed in other orders (one token's attention against the
whole sequence's, products blocked otherwise); over 4 x 3 layer instances
the differences stay under 2e-6 on logits of 4-5 (measured on these seeds),
while the smallest mistake in what a pass reads moves a logit by 1e-2 or
more (``test_a_cache_shared_across_passes_fails_the_comparison``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import serving, telemetry
from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
from chipbench import manifest as mf
from chipbench.harness import leaf_targets
from chipbench.references import ouro as ref
from test_hybrid_decoder import _ByHand, _aliased_outputs, _close

VOCAB, PASSES, LAYERS = 97, 4, 3


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(
        mf.config_file(mf.load_manifest(), "ouro_2p6b"), True)


def _build(cfg, seed=5, model=None, **zoo_args):
    """The zoo's tiny decoder with the reference's seeded leaves set into
    it by the configuration's own name map, in float32."""
    model = dict(model or cfg["model"])
    net = get_decoder(cfg["zoo"]["spec"], **dict(cfg["zoo"]["args"],
                                                 **zoo_args))
    g, lazy = ref.draw_all(model, seed, "float32")
    layers = [lazy[i] for i in range(len(lazy))]
    targets = leaf_targets(dict(cfg, model=model))
    params = net._collect_params_with_prefix()
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
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 40))
    got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, want in zip(got, (_want(model, g, layers, t) for t in toks)):
        _close(row, want)
    # the layer-by-layer path the chip uses gives the same logits
    again = np.asarray(ref.sequence_logits(model, seed, "float32", toks))
    _close(again[0], _want(model, g, layers, toks[0]))


def test_the_layers_come_from_the_sources_keys():
    """The published spec, shapes only (nothing is allocated): 48 full
    layers of 16 heads of 128 over as many K/V heads with no per-head
    norm, four norm gains a layer, every FFN dense 5632, an untied head,
    the stack run 4 times; the cache one group of 4 x 48 instances; the
    bytes a step reads: the stack and the final norm 4 times, the head
    once, a table row a slot."""
    net = get_decoder("ouro_2p6b", max_length=512)
    assert net.passes == 4
    assert {k for k, _ in net._kinds} == {"full_attention"}
    assert len(net._kinds) == 48 and {f for _, f in net._kinds} == {"dense"}
    shapes = {n: p.shape for n, p in
              net._collect_params_with_prefix().items()}
    assert shapes["embed"] == shapes["head"] == (49152, 2048)
    assert {n[len("layer47_"):]: s for n, s in shapes.items()
            if n.startswith("layer47_")} == dict(
        attn_norm=(2048,), attn_out_norm=(2048,), ffn_norm=(2048,),
        ffn_out_norm=(2048,), q=(2048, 2048), k=(2048, 2048),
        v=(2048, 2048), o=(2048, 2048), gate=(5632, 2048),
        up=(5632, 2048), down=(2048, 5632))
    assert net.cache_groups(512) == [
        dict(layers=192, heads=16, rows=512, head_dim=128, kind="full")]
    net.cast("bfloat16")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert net.step_weight_bytes(8) == 2 * (
        4 * (48 * layer + 2048) + 49152 * 2048 + 8 * 2048)
    assert round(net.step_weight_bytes(8) / 1e9, 2) == 19.93


def test_the_block_refuses_what_it_does_not_serve():
    with pytest.raises(ValueError, match="early exit"):
        get_decoder("ouro_tiny", exit_threshold=0.9)
    with pytest.raises(ValueError, match="passes over 1"):
        get_decoder("exaone_moe_tiny", passes=2)
    with pytest.raises(ValueError, match="sandwich or pre_norm"):
        get_decoder("ouro_tiny", pre_norm=True)
    # a block that routes declares no bytes a step reads
    assert get_decoder("exaone_moe_tiny").step_weight_bytes(4) is None


def test_the_sandwich_rule_and_rotary_on_full_layers(cfg):
    """One layer, one pass: the program agrees with the reference, and
    each of the two rules is seen by that comparison: the same weights
    without rotary positions on the full layer, or a reference without
    the output norms, give other logits."""
    model = dict(cfg["model"], num_hidden_layers=1, n_layer=1,
                 total_ut_steps=1)
    toks = np.random.default_rng(7).integers(0, VOCAB, (1, 24))
    net, model, g, layers = _build(cfg, model=model, num_layers=1,
                                   dense_layers=1, passes=1)
    want = _want(model, g, layers, toks[0])
    _close(net(mx.nd.array(toks, dtype="int32")).asnumpy()[0], want)
    plain, *_ = _build(cfg, model=model, num_layers=1, dense_layers=1,
                       passes=1, rope_full=False)
    off = plain(mx.nd.array(toks, dtype="int32")).asnumpy()[0]
    assert np.abs(off - want).max() > 1e-2
    assert np.abs(off[0] - want[0]).max() < 1e-4    # position 0 turns not
    loud = [dict(p, ao_g=jnp.ones_like(p["ao_g"]) * 1e3,
                 fo_g=jnp.ones_like(p["fo_g"]) * 1e3) for p in layers]
    assert np.abs(_want(model, g, loud, toks[0]) - want).max() > 1e-2


@pytest.mark.parametrize("prompts,n_new", [
    ((3, 21), 16),      # far under a 16-bucket beside a padded 32-bucket
    ((16, 32), 17),     # both prompts fill their buckets exactly
])
def test_prefill_then_decode_logits_agree_with_the_full_forward(
        cfg, prompts, n_new):
    """Prompts in two different buckets prefilled (every pass's planes, in
    pass order) and decoded side by side through the cache of 4 x 3
    (pass, layer) instances, against the reference's full forward pass,
    logits at every generated position."""
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
                (PASSES * LAYERS, 4, bucket, 16)] * 2
            _close(last, want[s][n - 1])
        for j in range(n_new):
            got, _ = hand.step({s: seqs[s][n + j]
                                for s, n in zip(seqs, prompts)})
            for s, n in zip(seqs, prompts):
                _close(got[s], want[s][n + j])
    finally:
        hand.close()


def test_a_cache_shared_across_passes_fails_the_comparison(cfg):
    """What the paper's shared-cache decoding keeps: ONE row a (layer,
    position), the last pass's, which every pass then reads. Laid into
    the cache after the join and after every step, it moves the logits of
    the very next step far outside the tolerance."""
    net, model, g, layers = _build(cfg)
    seq = np.random.default_rng(3).integers(0, VOCAB, 30)
    want = _want(model, g, layers, seq)
    hand = _ByHand(net)

    def share():
        kv = hand.sess._kv
        kv.arrays = [jnp.tile(a[(PASSES - 1) * LAYERS:], (PASSES, 1, 1, 1, 1))
                     for a in kv.arrays]

    try:
        hand.join(1, seq[:12])
        got, _ = hand.step({1: seq[12]})
        _close(got[1], want[12])        # served as it is: agrees
        share()
        got, _ = hand.step({1: seq[13]})
        assert np.abs(got[1] - want[13]).max() > 1e-2 * (
            1 + np.abs(want[13]).max())
    finally:
        hand.close()


def test_pass_r_of_layer_l_reads_and_writes_index_r_L_plus_l(cfg):
    """The prefill's planes and a decode step's new rows lie at index
    ``r * L + l`` of the group: there they equal the rotated keys and the
    values that pass ``r`` of layer ``l`` computes in the reference, and
    the passes' keys differ from one another."""
    net, model, g, layers = _build(cfg)
    seq = np.random.default_rng(11).integers(0, VOCAB, 14)
    n = 13
    hand = _ByHand(net)
    try:
        hand.join(0, seq[:n])
        hand.step({0: seq[n]})
        k_cache, v_cache = (np.asarray(a) for a in hand.sess._kv.arrays)
    finally:
        hand.close()
    want = ref.pass_keys(model, g, layers, jnp.asarray(seq)[None])
    assert len(want) == PASSES * LAYERS
    for i, (k, v) in enumerate(want):
        _close(k_cache[i, 0, :, :n + 1], np.asarray(k[0]))
        _close(v_cache[i, 0, :, :n + 1], np.asarray(v[0]))
    first, second = np.asarray(want[0][0]), np.asarray(want[LAYERS][0])
    assert np.abs(first - second).max() > 0.1


def test_session_streams_the_references_greedy_tokens_across_churn(cfg):
    """Through the scheduler: more requests than slots, of mixed lengths
    and buckets, slots reused; every stream is the greedy continuation the
    reference's full forward gives. The ledger's records carry ``passes``
    and the step's ``weight_bytes``, the registry counts the pass loops
    traced, by entry point."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, n) for n in (5, 13, 9, 30, 17, 3)]
    news = [12, 7, 15, 9, 11, 14]
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16, 32),
                               name="loop4") as sess:
        handles = [sess.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, news)]
        got = [h.result(120.0) for h in handles]
    for prompt, m, out in zip(prompts, news, got):
        logits = _want(model, g, layers, list(prompt) + out)
        assert len(out) == m
        assert out == np.argmax(logits[len(prompt) - 1:-1], -1).tolist()
    recs = [r for r in telemetry.trace.ring()["steps"]
            if r.get("site") == "decode.loop4"]
    steps = [r for r in recs if r.get("kind") != "prefill"]
    prefills = [r for r in recs if r.get("kind") == "prefill"]
    assert steps and prefills
    assert all(r["passes"] == PASSES for r in recs)
    assert {r["weight_bytes"] for r in steps} == {net.step_weight_bytes(3)}
    # every pass's plane of every active slot, read whole off the TPU
    assert all(r["kv_read_rows"] == PASSES * LAYERS * 64 * r["active"]
               for r in steps)
    assert steps[0]["kv_rows"] == PASSES * LAYERS * 3 * 64
    loops = {e: telemetry.counter("mxtpu_loop_passes_total",
                                  entry=e).value
             for e in ("step", "prefill")}
    # one step executable, one prefill a bucket
    assert loops == {"step": 1, "prefill": 2}


def test_a_block_that_runs_its_stack_once_writes_no_passes():
    net = get_decoder("exaone_moe_tiny")
    net.initialize(init="xavier")
    with serving.DecodeSession(net, max_slots=2, max_len=32,
                               prefill_buckets=(8,), name="once") as sess:
        sess.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    recs = [r for r in telemetry.trace.ring()["steps"]
            if r.get("site") == "decode.once"]
    assert recs and not any("passes" in r or "weight_bytes" in r
                            for r in recs)


def test_donated_decode_step_aliases_the_caches_of_every_pass(cfg):
    """Lowered as a donating session lowers it: K and V of the 4 x 3
    instances, each aliased to an output; the compiled program keeps
    both aliases."""
    net, *_ = _build(cfg)
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16,), name="alias4",
                               donate=True) as sess:
        assert sess._kv.shapes == [(PASSES * LAYERS, 3, 4, 64, 16)]
        lowered = sess._lower_decode()
        assert _aliased_outputs(lowered) == 2
        assert lowered.as_text().count("stablehlo.while") == 1
        text, n = lowered.compile().as_text(), len(sess._params)
        for j in range(2):
            assert f"{{{j + 1}}}: ({n + j}, {{}}" in text
