"""The data-built decoder's Nemotron-H layers (``gluon/model_zoo/decoder.py``:
``nemotron*`` specs): layers that are a Mamba-2 mixer, a latent expert layer
or attention alone, served through ``serving.DecodeSession`` (prefill by the
state-space dual form at the true length of a padded bucket, then
one-position steps through the ``state`` cache group and the K/V rows),
against the plain float32 reference (``chipbench/references/nemotron_h.py``:
one ``lax.scan`` over positions, every held expert for every token, which
shares no code with it). Small sizes, seeded weights, float32 on the CPU."""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import serving, telemetry
from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
from incubator_mxnet_tpu.gluon.model_zoo.decoder import group_rms_norm
from incubator_mxnet_tpu.ops import state_space
from chipbench import manifest as mf
from chipbench.harness import leaf_targets
from chipbench.references import nemotron_h as ref
from test_hybrid_decoder import _ByHand, _close

VOCAB = 97


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(
        mf.config_file(mf.load_manifest(), "nemotron3_super_ep4"), True)


def _build(cfg, seed=5, model=None, **zoo_args):
    """The zoo's tiny decoder with the reference's seeded leaves set into
    it by the configuration's own name map, in float32."""
    model = dict(model or cfg["model"])
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


@functools.lru_cache(maxsize=None)
def _forward(model_json):
    model = json.loads(model_json)
    return jax.jit(lambda g, layers, t: ref.forward(model, g, layers, t))


def _want(model, g, layers, tokens, pad=64):
    """The reference's logits (T, V) of one whole sequence, run padded on
    the right to ``pad`` positions (one compile for every length: no
    position looks right of itself)."""
    n = len(tokens)
    padded = np.zeros((1, pad), np.int32)
    padded[0, :n] = tokens
    fwd = _forward(json.dumps(model, sort_keys=True))
    return np.asarray(fwd(g, layers, jnp.asarray(padded)))[0, :n]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 6])
def test_forward_agrees_with_the_reference(cfg, seed):
    net, model, g, layers = _build(cfg, seed)
    toks = np.random.default_rng(0).integers(0, VOCAB, (2, 30))
    got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    for row, want in zip(got, (_want(model, g, layers, t) for t in toks)):
        _close(row, want)
    # the layer-by-layer path the chip uses gives the same logits
    again = np.asarray(ref.sequence_logits(model, seed, "float32", toks))
    _close(again[0], _want(model, g, layers, toks[0]))


def test_the_layers_come_from_the_sources_keys():
    """The published spec cut as the configuration cuts it, shapes only
    (nothing is allocated): layers 33-43 ``MEM*EMEMEME``, each ONE
    sub-layer with its own norm; the mixer 128 heads of 64 over 8 groups
    of state 128 with a convolution over ``x``, ``B`` and ``C``; 128 of
    512 experts held, relu2 with no gate in a 1024 latent; an untied
    head over a quarter of the vocabulary; the state group's taps wider
    than its state."""
    net = get_decoder("nemotron3_super", hybrid_override_pattern="MEM*EMEMEME",
                      experts_held=128, expert_share=0, vocab_size=32768,
                      max_length=4096)
    assert [k for k in net._kinds] == [
        ("mamba", None), (None, "sparse"), ("mamba", None),
        ("full_attention", None)] + [(None, "sparse"), ("mamba", None)] * 3 \
        + [(None, "sparse")]
    shapes = {n: p.shape for n, p in
              net._collect_params_with_prefix().items()}
    layer = lambda i: {n[len(f"layer{i}_"):]: s for n, s in shapes.items()
                       if n.startswith(f"layer{i}_")}
    assert shapes["embed"] == shapes["head"] == (32768, 4096)
    assert layer(0) == dict(
        attn_norm=(4096,), in_proj=(8192 + 10240 + 128, 4096),
        conv_w=(4, 10240), conv_bias=(10240,), dt_bias=(128,),
        a_log=(128,), d_skip=(128,), mamba_norm=(8192,),
        out_proj=(4096, 8192))
    assert layer(1) == dict(
        ffn_norm=(4096,), router=(512, 4096), router_bias=(512,),
        latent_down=(1024, 4096), latent_up=(4096, 1024),
        experts_up=(128, 1024, 2688), experts_down=(128, 2688, 1024),
        shared_up=(5376, 4096), shared_down=(4096, 5376))
    assert layer(3) == dict(attn_norm=(4096,), q=(4096, 4096),
                            k=(256, 4096), v=(256, 4096), o=(4096, 4096))
    assert net.cache_groups(4096) == [
        dict(layers=1, heads=2, rows=4096, head_dim=128, kind="full"),
        dict(layers=5, kind="state", width=1024, state=128 * 8, taps=3,
             taps_width=10240, dtype="float32")]
    assert net.step_weight_bytes(64) is None
    assert net.held_expert_bytes == 2 * 1024 * 2688 * 4


@pytest.mark.parametrize("prompts,n_new", [
    ((3, 21), 6),       # far under a 16-bucket beside a padded 32-bucket
    ((16, 32), 4),      # both prompts fill their buckets exactly
    ((1, 19), 9),       # one token: the taps are zeros but the last
])
def test_prefill_then_decode_logits_agree_with_the_full_forward(
        cfg, prompts, n_new):
    """Prompts in two different buckets prefilled (the dual form by
    chunks, the state taken at the true length) and decoded side by side
    (one position a step through the cached state and K/V rows) against
    the reference's full forward pass, logits at every generated
    position."""
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
                (1, 2, bucket, 16), (1, 2, bucket, 16), (3, 16 * 2, 32),
                (3, 3, 128)]
            _close(last, want[s][n - 1])
        for j in range(n_new):
            got, counters = hand.step({s: seqs[s][n + j]
                                       for s, n in zip(seqs, prompts)})
            for s, n in zip(seqs, prompts):
                _close(got[s], want[s][n + j])
            # two live slots, 4 of 16 experts each, three expert layers
            routed_here, routed_all, hit, top = counters
            assert routed_all == 2 * 4 * 3 and 0 < routed_here <= 24
            assert 0 < hit <= routed_here and 1 <= top <= 2
    finally:
        hand.close()


def _ssd_inputs(t, seed):
    rng = np.random.default_rng(seed)
    b, h, p, g, n = 2, 4, 3, 2, 5
    x = rng.standard_normal((b, t, h * p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.standard_normal((b, t, h)) - 2)).astype(
        np.float32)
    a = -np.exp(rng.uniform(0, 2, h)).astype(np.float32)
    bc = rng.standard_normal((2, b, t, g, n)).astype(np.float32)
    d = rng.standard_normal(h).astype(np.float32)
    h0 = rng.standard_normal((b, n, g, h * p // g)).astype(np.float32)
    return x, dt, a, bc[0], bc[1], d, h0


def _ssd_by_hand(x, dt, a, b, c, d, h0, n):
    """The recurrence in float64 numpy, one position and one head at a
    time, positions from ``n`` on leaving the state alone; the state
    ``(B, N, G, Q)``, a group's heads side by side in its ``Q``."""
    bsz, t, e = x.shape
    heads, groups = dt.shape[-1], b.shape[2]
    p, per = e // heads, heads // groups
    state = h0.astype(np.float64).reshape(bsz, -1, heads, p).copy()
    ys = []
    for i in range(t):
        y = np.zeros((bsz, heads, p))
        for hd in range(heads):
            g = hd // per
            step = dt[:, i, hd] if i < n else np.zeros(bsz)
            xh = x[:, i, hd * p:(hd + 1) * p]
            state[:, :, hd] = np.exp(step * a[hd])[:, None, None] \
                * state[:, :, hd] + (step[:, None] * xh)[:, None, :] \
                * b[:, i, g][:, :, None]
            y[:, hd] = np.einsum("bnp,bn->bp", state[:, :, hd], c[:, i, g]) \
                + d[hd] * xh
        ys.append(y.reshape(bsz, e))
    return np.stack(ys, 1), state.reshape(h0.shape)


@pytest.mark.parametrize("t,n,chunk", [(13, 13, 4), (13, 9, 4), (16, 5, 8),
                                       (7, 7, 16)])
def test_the_dual_form_by_chunks_is_the_one_position_recurrence(t, n, chunk):
    """``ssd_sequence`` from a state that is not zero, at a true length
    under the padded one, a chunk longer than the sequence; ``ssd_step``
    position after position gives the same."""
    x, dt, a, b, c, d, h0 = _ssd_inputs(t, seed=t + n)
    y, h = state_space.ssd_sequence(*map(jnp.asarray, (x, dt, a, b, c, d)),
                                    n=n, h0=jnp.asarray(h0), chunk=chunk)
    want_y, want_h = _ssd_by_hand(x, dt, a, b, c, d, h0, n)
    assert h.dtype == jnp.float32 and h.shape == h0.shape
    _close(np.asarray(y)[:, :n], want_y[:, :n], 1e-5)
    _close(np.asarray(h), want_h, 1e-5)
    state = jnp.asarray(h0)
    for i in range(n):
        y1, state = state_space.ssd_step(
            state, *map(jnp.asarray, (x[:, i], dt[:, i], a, b[:, i],
                                      c[:, i], d)))
        _close(np.asarray(y1), want_y[:, i], 1e-5)
    _close(np.asarray(state), want_h, 1e-5)


def test_the_gated_norm_normalises_each_group_alone():
    """Scaling one group of channels leaves the others' output as it was
    and its own (scale-free) too; one norm over the whole width would
    move every channel."""
    x = np.random.default_rng(3).standard_normal((2, 5, 32)).astype(
        np.float32)
    g = np.ones(32, np.float32)
    base = np.asarray(group_rms_norm(jnp.asarray(x), jnp.asarray(g), 4,
                                     1e-5))
    y = x.copy()
    y[..., 8:16] *= 50.0
    moved = np.asarray(group_rms_norm(jnp.asarray(y), jnp.asarray(g), 4,
                                      1e-5))
    _close(moved, base, 1e-5)
    part = x[..., 8:16]
    _close(base[..., 8:16], part / np.sqrt((part ** 2).mean(-1,
                                                            keepdims=True)
                                           + 1e-5), 1e-5)
    whole = np.asarray(group_rms_norm(jnp.asarray(y), jnp.asarray(g), 1,
                                      1e-5))
    assert np.abs(whole[..., :8] - base[..., :8]).max() > 0.1


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(cfg):
    """Every chip's share of one expert layer (8 of 16 experts, shares 0
    and 1) through the program's FFN, with the shared expert that each
    computes alike counted once, sums to the uncut reference layer."""
    model = dict(cfg["model"], n_routed_experts=16,
                 expert_share={"index": 0, "of": 1})
    layer = ref.draw_layer(model, ref.root_key(11), 1)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((12, 64)),
                    jnp.float32)
    want, _ = ref.experts(layer, x, ref.sizes(model), ref._mm_f32,
                          lambda a: a)
    names = dict(router_w="router", router_b="router_bias",
                 ld_w="latent_down", lu_w="latent_up", su_w="shared_up",
                 sd_w="shared_down")
    total = shared = None
    for share in (0, 1):
        net = get_decoder("nemotron_h_tiny", experts_held=8,
                          expert_share=share)
        lp = {names[k]: v for k, v in layer.items() if k in names}
        lp["experts_up"] = layer["eu_w"][8 * share:8 * share + 8]
        lp["experts_down"] = layer["ed_w"][8 * share:8 * share + 8]
        with jax.default_matmul_precision("highest"):
            y, counts = net._ffn(lp, x)
            shared = net._dense(lp, x, "shared_")
        total = y - shared if total is None else total + y - shared
        assert 0 < int(counts["routed_here"]) < 12 * 4
    _close(np.asarray(total + shared), np.asarray(want), 1e-5)


def test_session_streams_the_references_greedy_tokens_across_churn(cfg):
    """Through the scheduler: more requests than slots, of mixed lengths
    and buckets, slots reused; every stream is the greedy continuation the
    reference's full forward gives; the ledger's step records carry the
    state's bytes and the held-expert bytes the step read."""
    net, model, g, layers = _build(cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, VOCAB, n) for n in (5, 13, 9, 30, 17)]
    news = [12, 7, 15, 9, 11]
    with serving.DecodeSession(net, max_slots=3, max_len=64,
                               prefill_buckets=(16, 32),
                               name="churn42") as sess:
        slot_bytes = sess._kv.state_bytes(1)
        handles = [sess.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, news)]
        got = [h.result(120.0) for h in handles]
    for prompt, m, out in zip(prompts, news, got):
        logits = _want(model, g, layers, list(prompt) + out)
        assert len(out) == m
        assert out == np.argmax(logits[len(prompt) - 1:-1], -1).tolist()
    steps = [r for r in telemetry.trace.ring()["steps"]
             if r.get("site") == "decode.churn42"
             and r.get("kind") != "prefill"]
    # three mixers: 16 x 2 groups x 32 float32 of state and 3 x 128
    # float32 taps (a float32 model), in and out
    assert slot_bytes == 2 * 3 * (2 * 16 * 32 + 3 * 128) * 4
    assert steps and all(r["state_bytes"] == r["active"] * slot_bytes
                         for r in steps)
    # an expert of 24 -> 32 -> 24 float32 for every held expert hit
    assert all(r["expert_bytes"] == r["experts_hit"] * 2 * 24 * 32 * 4
               for r in steps)
