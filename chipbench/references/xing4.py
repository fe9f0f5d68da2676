"""Plain float32 reference of the Xing4.0 decoder layer (Xing4.0-29B-A4B as
its ``config.json`` publishes it, ``model_type`` ``xing4_0``), and the
seeded weights.

Nothing here imports the program: no cache, no absorbed products, no
kernels. A whole sequence goes through at once under an explicit causal
mask, the latent is EXPANDED to every head's key and value, every expert
HELD HERE is computed for every token and masked by the router's choice
(``references/exaone_moe.py``'s routed FFN, which is the same rule at other
numbers, with its rounding modes and controls). ``model`` is the
configuration file's ``model`` block (the source's key names).

The equations (``C`` hidden, ``n = hc_mult`` streams, ``H`` heads; what the
config does not say is DeepSeek-V2/V3's (arXiv:2405.04434, 2412.19437) and
mHC's (arXiv:2512.24880) and listed under ``assumed`` in the configuration
file):

- streams: ``X_0 = [e, e, .., e]`` (the embedding row ``n`` times), ``X``
  (n, C) a token; after the last layer ``h = sum_i X[i]``, ``logits =
  RMSNorm(h) W_head``;
- a sub-layer ``F`` (attention, then the FFN; each with its own
  parameters): ``x' = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)`` over all
  ``n C`` values, no gain; ``Hpre~ = a_pre (x' P_pre) + b_pre`` (n),
  ``Hpost~ = a_post (x' P_post) + b_post`` (n), ``Hres~ = a_res mat(x'
  P_res) + b_res`` (n, n); ``H_pre = sigmoid(Hpre~)``, ``H_post = 2
  sigmoid(Hpost~)``, ``M = exp(clip(Hres~, clamp_min, clamp_max))``, then
  ``hc_sinkhorn_iters`` times ``M <- M / (colsum(M) + hc_eps)``, ``M <- M
  / (rowsum(M) + hc_eps)``, ``H_res = M``; ``u = H_pre X`` (C), ``y =
  F(RMSNorm(u))``, ``X <- H_res X + H_post^T y``. Coefficients float32;
- latent attention on ``u~ = RMSNorm(u)``: ``c_q = RMSNorm(u~ W_qa)``,
  ``[q_nope | q_rope]_h = c_q W_qb``; ``[c | k_r] = u~ W_kva``, ``c <-
  RMSNorm(c)``, ``k_r <- RoPE(k_r)``, ``q_rope <- RoPE(q_rope)``;
  ``[k_nope | v]_h = c W_kvb``; scores ``(q_nope . k_nope + q_rope . k_r)
  s``, ``s = (nope + rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor)
  + 1``; causal softmax; ``o = concat_h(sum w v_h) W_o``. RoPE: the
  half-split convention over YaRN's inverse frequencies (``theta^(-2i/d)``
  where a frequency turns more than ``beta_fast`` times over the original
  positions, that over ``factor`` below ``beta_slow`` turns, a linear ramp
  between), cos and sin unscaled (``mscale`` = ``mscale_all_dim``);
- FFN: the first ``first_k_dense_replace`` layers ``W_d (silu(W_g x) * W_u
  x)``; the others ``exaone_moe.sparse_ffn``: sigmoid scores over the
  ``router width = n_routed_experts * expert_share["of"]`` experts, the
  ``num_experts_per_tok`` largest of score + bias chosen, weights the
  chosen scores over their sum times ``routed_scaling_factor``, the experts
  held here (``expert_share["index"]``'s) and one shared expert.

Leaf names are this file's own. Dense weights are (out, in): ``y = x @
w.T``. Weights: ``wte`` N(0, 1), projections N(0, 0.02), ``router_w`` N(0,
0.016) (logits of about unit spread), ``router_b`` N(0, 0.01), gains 1 +
N(0, 0.02). The hyper-connections' are drawn so that the coefficients
move: ``h?_w`` N(0, 2.4 / sqrt(n C)) (0.02 at the published widths: ``x'
P`` of spread 2.4), ``h?_a`` (0.35, 0.35, 0.4) + N(0, 0.02), ``h?_b`` N(0,
0.5): ``H_pre`` then spreads over about 0.2-0.8 and ``H_res``'s entries
over about 0.05-0.6, neither the identity nor uniform, and they differ
from token to token.
"""

from __future__ import annotations

import functools
import gc
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from .exaone_moe import (CONTROLS, F32ROUTE, HIGHEST,  # noqa: F401
                         _kit, _mm_f32, _through, flatten_leaves, gated,
                         rms_norm, root_key, sparse_ffn)

ATTN_LEAVES = ("qa_w", "qan_g", "qb_w", "kva_w", "kvn_g", "kvb_w", "o_w",
               "an_g", "fn_g")
HC_LEAVES = ("ha_w", "ha_a", "ha_b", "hf_w", "hf_a", "hf_b")
DENSE_LEAVES = ("gate_w", "up_w", "down_w")
SPARSE_LEAVES = ("router_w", "router_b", "eg_w", "eu_w", "ed_w", "sg_w",
                 "su_w", "sd_w")
LAYER_LEAVES = ATTN_LEAVES + HC_LEAVES + DENSE_LEAVES + SPARSE_LEAVES
GLOBAL_LEAVES = ("wte", "lnf_g", "head_w")
_STD = {"wte": 1.0, "router_w": 0.016, "router_b": 0.01, "ha_a": 0.02,
        "hf_a": 0.02, "ha_b": 0.5, "hf_b": 0.5}
_HC_A = (0.35, 0.35, 0.4)
#: queries per block of the masked attention
_Q_ROWS = 512


def sizes(model: dict) -> dict:
    n = int(model["num_hidden_layers"])
    share = model["expert_share"]
    held = int(model["n_routed_experts"])
    return {
        "C": int(model["hidden_size"]), "L": n,
        "H": int(model["num_attention_heads"]),
        "rq": int(model["q_lora_rank"]), "rank": int(model["kv_lora_rank"]),
        "dn": int(model["qk_nope_head_dim"]),
        "dr": int(model["qk_rope_head_dim"]), "dv": int(model["v_head_dim"]),
        "F": int(model["intermediate_size"]),
        "Fe": int(model["moe_intermediate_size"]),
        "E": held, "R": held * int(share["of"]),
        "first": held * int(share["index"]),
        "K": int(model["num_experts_per_tok"]),
        "V": int(model["vocab_size"]),
        "theta": float(model["rope_theta"]), "yarn": model["rope_scaling"],
        "eps": float(model["rms_norm_eps"]),
        "scale": float(model["routed_scaling_factor"]),
        "n": int(model["hc_mult"]), "iters": int(model["hc_sinkhorn_iters"]),
        "hc_eps": float(model["hc_eps"]),
        "clamp": (float(model["mhc_h_res_clamp_min"]),
                  float(model["mhc_h_res_clamp_max"])),
        "ffn": tuple("dense" if i < int(model["first_k_dense_replace"])
                     else "sparse" for i in range(n))}


def layer_leaves(model: dict, i: int) -> tuple:
    """Names of layer ``i``'s leaves: attention, the two sets of
    hyper-connection parameters and its kind of FFN."""
    return ATTN_LEAVES + HC_LEAVES + (
        DENSE_LEAVES if sizes(model)["ffn"][i] == "dense" else SPARSE_LEAVES)


def leaf_shapes(model: dict) -> dict:
    s = sizes(model)
    c, f, fe, e, h, n = s["C"], s["F"], s["Fe"], s["E"], s["H"], s["n"]
    coef = 2 * n + n * n
    return {"wte": (s["V"], c), "lnf_g": (c,), "head_w": (s["V"], c),
            "qa_w": (s["rq"], c), "qan_g": (s["rq"],),
            "qb_w": (h * (s["dn"] + s["dr"]), s["rq"]),
            "kva_w": (s["rank"] + s["dr"], c), "kvn_g": (s["rank"],),
            "kvb_w": (h * (s["dn"] + s["dv"]), s["rank"]),
            "o_w": (c, h * s["dv"]), "an_g": (c,), "fn_g": (c,),
            "ha_w": (coef, n * c), "ha_a": (3,), "ha_b": (coef,),
            "hf_w": (coef, n * c), "hf_a": (3,), "hf_b": (coef,),
            "gate_w": (f, c), "up_w": (f, c), "down_w": (c, f),
            "router_w": (s["R"], c), "router_b": (s["R"],),
            "eg_w": (e, c, fe), "eu_w": (e, c, fe), "ed_w": (e, fe, c),
            "sg_w": (fe, c), "su_w": (fe, c), "sd_w": (c, fe)}


@functools.lru_cache(maxsize=None)
def _leaf_fn(name: str, shape: tuple, dtype: str):
    """One leaf drawn in float32 and cast, in one jitted call of its own."""
    std = 2.4 / math.sqrt(shape[1]) if name in ("ha_w", "hf_w") \
        else _STD.get(name, 0.02)
    mean = jnp.asarray(_HC_A, jnp.float32) if name in ("ha_a", "hf_a") \
        else (1.0 if name.endswith("_g") else 0.0)

    @jax.jit
    def draw(key):
        return (mean + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    return draw


def draw_leaf(model: dict, key, layer: int, name: str, dtype: str):
    """Leaf ``name`` of ``layer`` (-1: a global leaf) in ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           (GLOBAL_LEAVES + LAYER_LEAVES).index(name))
    return _leaf_fn(name, leaf_shapes(model)[name], dtype)(k)


def draw_globals(model: dict, key, dtype: str = "float32") -> dict:
    return {n: draw_leaf(model, key, -1, n, dtype) for n in GLOBAL_LEAVES}


def draw_layer(model: dict, key, i: int, dtype: str = "float32") -> dict:
    return {n: draw_leaf(model, key, i, n, dtype)
            for n in layer_leaves(model, i)}


class Layers:
    """The layers' weights, each DRAWN WHEN IT IS ASKED FOR: iterating
    holds one layer at a time."""

    def __init__(self, model: dict, seed: int, dtype: str):
        self.model, self.key, self.dtype = model, root_key(seed), dtype

    def __len__(self):
        return sizes(self.model)["L"]

    def __getitem__(self, i: int) -> dict:
        if not 0 <= i < len(self):
            raise IndexError(i)
        return draw_layer(self.model, self.key, i, self.dtype)


def draw_all(model: dict, seed: int, dtype: str):
    """``(globals, layers)`` in the type they are served in; ``layers``
    draws a layer when it is indexed (see :class:`Layers`)."""
    return (draw_globals(model, root_key(seed), dtype),
            Layers(model, seed, dtype))


# -- the layer ----------------------------------------------------------------

def yarn_inv_freq(dim: int, theta: float, yarn: dict):
    """The ``dim / 2`` inverse frequencies of the rotary part."""
    orig = float(yarn["original_max_position_embeddings"])

    def turns_at(n):
        return dim * math.log(orig / (n * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(turns_at(float(yarn["beta_slow"]))), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-2.0 * i / dim)
    return jnp.asarray(plain / float(yarn["factor"]) * ramp
                       + plain * (1.0 - ramp), jnp.float32)


def softmax_scale(s: dict) -> float:
    m = 0.1 * float(s["yarn"].get("mscale_all_dim", 0)) \
        * math.log(float(s["yarn"]["factor"])) + 1.0
    return (s["dn"] + s["dr"]) ** -0.5 * m * m


def rotate(x, inv):
    """RoPE of ``x`` (B, T, ..., D) at positions 0..T-1 (axis 1)."""
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (half,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(p, x, s: dict, mm, rnd):
    """Latent attention, expanded: ``x`` (B, T, C) normed -> (B, T, C)."""
    b, t, _ = x.shape
    h, dn, rank = s["H"], s["dn"], s["rank"]
    inv = yarn_inv_freq(s["dr"], s["theta"], s["yarn"])
    c_q = rnd(rms_norm(rnd(mm(x, p["qa_w"])), p["qan_g"], s["eps"]))
    q = rnd(mm(c_q, p["qb_w"])).reshape(b, t, h, dn + s["dr"])
    ckr = rnd(mm(x, p["kva_w"]))
    c = rnd(rms_norm(ckr[..., :rank], p["kvn_g"], s["eps"]))
    k_r = rnd(rotate(ckr[..., rank:], inv))                  # (B, T, dr)
    q = jnp.concatenate([q[..., :dn], rnd(rotate(q[..., dn:], inv))], -1)
    kv = rnd(mm(c, p["kvb_w"])).reshape(b, t, h, dn + s["dv"])
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r[:, :, None], (b, t, h, s["dr"]))], -1)
    v = kv[..., dn:]
    outs = []
    for q0 in range(0, t, _Q_ROWS):
        qi = jnp.arange(q0, min(t, q0 + _Q_ROWS))[:, None]
        mask = jnp.arange(t)[None, :] <= qi
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, q0:q0 + _Q_ROWS], k,
                        precision=HIGHEST) * softmax_scale(s)
        w = rnd(jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1))
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST))
    a = rnd(jnp.concatenate(outs, axis=1)).reshape(b, t, h * s["dv"])
    return rnd(mm(a, p["o_w"]))


def coefficients(p, which: str, x, s: dict):
    """``x`` (B, T, n, C) -> ``H_pre`` (B, T, n), ``H_post`` (B, T, n),
    ``H_res`` (B, T, n, n) of sub-layer ``which`` (``"ha"`` | ``"hf"``)."""
    n = s["n"]
    xv = x.reshape(x.shape[:2] + (-1,))
    xn = xv * jax.lax.rsqrt(jnp.mean(xv * xv, axis=-1, keepdims=True)
                            + s["hc_eps"])
    raw = _mm_f32(xn, p[which + "_w"])
    a, b = p[which + "_a"], p[which + "_b"]
    pre = jax.nn.sigmoid(a[0] * raw[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * raw[..., 2 * n:] + b[2 * n:], *s["clamp"]))
    m = m.reshape(m.shape[:2] + (n, n))
    for _ in range(s["iters"]):
        m = m / (m.sum(axis=-2, keepdims=True) + s["hc_eps"])
        m = m / (m.sum(axis=-1, keepdims=True) + s["hc_eps"])
    return pre, post, m


def sub_layer(p, which: str, gain: str, x, s: dict, f, rnd):
    """``X <- H_res X + H_post^T f(RMSNorm(H_pre X))``."""
    pre, post, res = coefficients(p, which, x, s)
    u = rnd(jnp.einsum("bti,btic->btc", pre, x, precision=HIGHEST))
    y = f(rnd(rms_norm(u, p[gain], s["eps"])))
    return rnd(jnp.einsum("btij,btjc->btic", res, x, precision=HIGHEST)
               + post[..., None] * y[:, :, None, :])


def block(p, x, s: dict, kind: str, mm=None, rnd=None, weights=None):
    """A layer whose FFN is of ``kind``: x (B, T, n, C) float32 -> ``(x,
    tie, weights)`` (``tie``, ``weights``, ``mm``, ``rnd`` as
    ``exaone_moe.block`` has them)."""
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    x = sub_layer(p, "ha", "an_g", x, s,
                  lambda u: attention(p, u, s, mm, rnd), rnd)
    note = []

    def ffn(u):
        if kind == "dense":
            note.append((None, None))
            return gated(u, p["gate_w"], p["up_w"], p["down_w"], mm, rnd)
        y, tie, w = sparse_ffn(p, u, s, mm, rnd, weights)
        note.append((tie, w))
        return y

    x = sub_layer(p, "hf", "fn_g", x, s, ffn, rnd)
    return x, note[0][0], note[0][1]


def embed(g, tokens, s: dict):
    e = g["wte"][tokens]
    return jnp.broadcast_to(e[..., None, :], e.shape[:-1] + (s["n"],)
                            + e.shape[-1:])


def head(g, x, s: dict, mm=None, rnd=None):
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    h = rnd(x.sum(axis=-2))
    return rnd(mm(rnd(rms_norm(h, g["lnf_g"], s["eps"])), g["head_w"]))


def forward(model: dict, g, layers, tokens):
    """tokens (B, T) -> logits (B, T, V), all of it at once (small sizes)."""
    s = sizes(model)
    x = embed(g, tokens, s)
    for i in range(s["L"]):
        x = block(layers[i], x, s, s["ffn"][i])[0]
    return head(g, x, s)


# -- serving: whole sequences, layer by layer, every mode at once ------------

@functools.lru_cache(maxsize=None)
def _seq_fns(model_json: str, dtype: str, modes: tuple):
    model = json.loads(model_json)
    s = sizes(model)
    through = _through(dtype)
    kits = [_kit(dtype, m) for m in modes]
    same = lambda a: a

    @jax.jit
    def start(g, tokens):
        x = embed({"wte": through(g["wte"])}, tokens, s)
        return tuple((rnd or same)(x) for _, rnd in kits)

    @functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
    def layer(p, xs, kind):
        p = jax.tree_util.tree_map(through, p)
        out = []
        for x, (mm, rnd), mode in zip(xs, kits, modes):
            led = out[0][2] if mode.endswith(F32ROUTE) else None
            out.append(block(p, x, s, kind, mm, rnd, led))
        tie = out[0][1]
        near = jnp.zeros((), jnp.int32) if tie is None \
            else (tie < 1e-3).sum().astype(jnp.int32)
        return tuple(o[0] for o in out), near

    @jax.jit
    def finish(g, xs, at):
        """Logits (R, M, V) of each mode at the positions ``at`` (R, M)."""
        g = jax.tree_util.tree_map(through, g)
        rows = jnp.arange(at.shape[0])[:, None]
        return tuple(head(g, x[rows, at], s, mm, rnd)
                     for x, (mm, rnd) in zip(xs, kits))

    return start, layer, finish


def logits_at(model: dict, seed: int, dtype: str, blocks, at,
              modes=("f32",), notes=None):
    """``exaone_moe.logits_at`` for this layer: for each block of rows
    ``blocks[b]`` (R, T) int32 and positions ``at[b]`` (R, M) the logits
    (R, M, V) float32 of every mode there, one tuple (a mode each) per
    block; a layer's weights are drawn once and live one layer at a time.
    ``notes`` receives ``router_near_ties``."""
    start, layer, finish = _seq_fns(json.dumps(model, sort_keys=True), dtype,
                                    tuple(modes))
    key = root_key(seed)
    g = draw_globals(model, key, dtype)
    xs = [start(g, jnp.asarray(t, jnp.int32)) for t in blocks]
    near, s = 0, sizes(model)
    for i in range(s["L"]):
        p = draw_layer(model, key, i, dtype)
        stepped = [layer(p, x, s["ffn"][i]) for x in xs]
        xs = [x for x, _ in stepped]
        near += sum(int(n) for _, n in stepped)
    if notes is not None:
        routed = sum(int(np.asarray(t).size) for t in blocks) \
            * sum(1 for kind in s["ffn"] if kind == "sparse")
        notes["router_near_ties"] = {"under_1e-3": near, "of": routed}
    for x, a in zip(xs, at):
        yield finish(g, x, jnp.asarray(a, jnp.int32))


def sequence_logits(model: dict, seed: int, dtype: str, tokens,
                    mode: str = "f32"):
    """Logits (B, T, V) float32 of ``tokens`` (B, T) in one mode."""
    tokens = np.asarray(tokens, np.int32)
    at = np.broadcast_to(np.arange(tokens.shape[1]), tokens.shape)
    (out,), = logits_at(model, seed, dtype, [tokens], [at], (mode,))
    return out


def served_gaps(model: dict, seed: int, dtype: str, samples, pad_to: int,
                modes=(), rows_per_block: int = 2) -> dict:
    """``exaone_moe.served_gaps`` over this file's ``logits_at``: how far
    below the float32 reference's best logit the tokens lie, at every
    position that predicted a served token of ``samples`` (pairs
    ``(prompt, served_tokens)``): flat float arrays under ``"served"``,
    under each of ``modes`` (the token that mode puts first there) and
    under ``"margin"`` (the reference's best less its second best), and
    the note ``"router_near_ties"``."""
    gc.collect()        # a dropped session still holds its memory
    modes = tuple(modes)
    width = max(len(out) for _, out in samples)
    blocks, at, ids, live = [], [], [], []
    for r0 in range(0, len(samples), rows_per_block):
        toks = np.zeros((rows_per_block, pad_to), np.int32)
        pos = np.zeros((rows_per_block, width), np.int32)
        nxt = np.zeros((rows_per_block, width), np.int32)
        use = np.zeros((rows_per_block, width), bool)
        for r, (prompt, out) in enumerate(samples[r0:r0 + rows_per_block]):
            n, m = len(prompt), len(out)
            toks[r, :n + m] = np.concatenate([prompt, out])[:pad_to]
            # logits at position j predict token j + 1
            pos[r, :m] = np.arange(n - 1, n + m - 1)
            nxt[r, :m] = out
            use[r, :m] = True
        blocks.append(toks), at.append(pos), ids.append(nxt), live.append(use)
    out = {k: [] for k in ("served", "margin") + modes}
    notes: dict = {}
    every = logits_at(model, seed, dtype, blocks, at, ("f32",) + modes,
                      notes=notes)
    for (ref, *low), nxt, use in zip(every, ids, live):
        top2 = jax.lax.top_k(ref, 2)[0]

        def below_best(tok):
            return np.asarray(top2[..., 0] - jnp.take_along_axis(
                ref, tok[..., None], -1)[..., 0])[use]

        out["served"].append(below_best(jnp.asarray(nxt)))
        out["margin"].append(np.asarray(top2[..., 0] - top2[..., 1])[use])
        for mode, logits in zip(modes, low):
            out[mode].append(below_best(jnp.argmax(logits, axis=-1)))
    print(f"reference router_near_ties = {json.dumps(notes)}",
          file=sys.stderr)
    return dict({k: np.concatenate(v) for k, v in out.items()}, **notes)
