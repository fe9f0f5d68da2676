"""Plain float32 reference of the Ouro LoopLM decoder (Ouro-2.6B as its
``config.json`` publishes it, ``model_type`` ``ouro``; the architecture of
*Scaling Latent Reasoning via Looped Language Models*, arXiv:2510.25741),
and the seeded weights.

Nothing here imports the program: no cache, no kernel, no one-token form.
A whole sequence goes through at once, ``total_ut_steps`` times: every
(pass, layer) computes its own keys and values from its own input and
attends over them under an explicit causal mask. ``model`` is the
configuration file's ``model`` block (the source's key names).

The equations (``C`` = ``hidden_size``, ``H`` heads of ``D`` =
``head_dim``, ``R`` = ``total_ut_steps``):

- ``h0 = E[tokens]``, no scale;
- each pass ``r = 1..R`` starts from ``x = h(r-1)``; each layer:
  ``x = x + RMSNorm_ao(W_o attn(RMSNorm_an(x)))``, ``x = x +
  RMSNorm_fo(W_d (silu(W_g b) * W_u b))`` with ``b = RMSNorm_fn(x)``;
  attention: ``q, k, v = W_q a, W_k a, W_v a`` (no bias), RoPE at
  ``rope_theta`` (the half-split convention, all ``D`` dims, the same
  positions in every pass) on ``q`` and ``k``, causal softmax of ``q k^T /
  sqrt(D)``; after the last layer ``h(r) = RMSNorm_final(x)``;
- ``logits = h(R) W_head^T`` (``tie_word_embeddings`` false), no norm after
  ``h(R)``.

Departures from ``modeling_ouro.py``: the early-exit gate is not computed
(at ``early_exit_threshold`` 1 only the last pass reaches the threshold,
so it selects nothing); leaf names are this file's own (``an_g`` / ``ao_g``
are its ``input_layernorm`` / ``input_layernorm_2``, ``fn_g`` / ``fo_g`` its
``post_attention_layernorm`` / ``post_attention_layernorm_2``); the head is
applied where a logit is asked for; no sliding window (``use_sliding_window``
false).

Dense weights are (out, in): ``y = x @ w.T``. Weights: the table N(0, 1)
(a token's row of unit size), every projection N(0, ``in ** -0.5``) (``in``
its input width: unit-sized outputs of unit-sized inputs, so the logits of
a normed ``h(R)`` have about unit spread), the input norms' and the final
norm's gains 1 + N(0, 0.02), the OUTPUT norms' gains (``ao_g``, ``fo_g``)
0.5 + N(0, 0.02): each sub-layer adds half a unit to the stream. At gains
of 1 the random stack of 192 (pass, layer) instances is chaotic: bfloat16
rounding alone moved the served logits by a noise of 0.39-0.57 and a mean
gap of 0.08-0.11 on the chip, and int8 read the estimate's ceiling, so the
noise could not tell the two apart; at 0.5 the program reads a noise of
0.10-0.12 and int8 0.76 on the chip (the configuration's ``readings``).
"""

from __future__ import annotations

import functools
import gc
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .exaone_moe import (HIGHEST, _kit, _mm_f32, _through,  # noqa: F401
                         flatten_leaves, gated, rms_norm, root_key, rotate)

LAYER_LEAVES = ("q_w", "k_w", "v_w", "o_w", "an_g", "ao_g", "fn_g", "fo_g",
                "gate_w", "up_w", "down_w")
GLOBAL_LEAVES = ("wte", "lnf_g", "head_w")
#: the mean of the output norms' gains (the module docstring says why)
OUT_GAIN = 0.5
#: queries per block of the masked attention
_Q_ROWS = 512


def sizes(model: dict) -> dict:
    return {
        "C": int(model["hidden_size"]), "L": int(model["num_hidden_layers"]),
        "R": int(model["total_ut_steps"]),
        "Hq": int(model["num_attention_heads"]),
        "Hkv": int(model["num_key_value_heads"]), "D": int(model["head_dim"]),
        "F": int(model["intermediate_size"]), "V": int(model["vocab_size"]),
        "eps": float(model["rms_norm_eps"]),
        "theta": float(model["rope_theta"])}


def leaf_shapes(model: dict) -> dict:
    s = sizes(model)
    c, f = s["C"], s["F"]
    hq, hkv = s["Hq"] * s["D"], s["Hkv"] * s["D"]
    return {"wte": (s["V"], c), "lnf_g": (c,), "head_w": (s["V"], c),
            "q_w": (hq, c), "k_w": (hkv, c), "v_w": (hkv, c), "o_w": (c, hq),
            "an_g": (c,), "ao_g": (c,), "fn_g": (c,), "fo_g": (c,),
            "gate_w": (f, c), "up_w": (f, c), "down_w": (c, f)}


@functools.lru_cache(maxsize=None)
def _leaf_fn(name: str, shape: tuple, dtype: str):
    """One leaf drawn in float32 and cast, in one jitted call of its own."""
    std = 1.0 if name == "wte" else (
        0.02 if name.endswith("_g") else shape[1] ** -0.5)
    mean = OUT_GAIN if name in ("ao_g", "fo_g") else 1.0

    @jax.jit
    def draw(key):
        x = std * jax.random.normal(key, shape, jnp.float32)
        return (mean + x if name.endswith("_g") else x).astype(dtype)

    return draw


def draw_leaf(model: dict, key, layer: int, name: str, dtype: str):
    """Leaf ``name`` of ``layer`` (-1: a global leaf) in ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           (GLOBAL_LEAVES + LAYER_LEAVES).index(name))
    return _leaf_fn(name, leaf_shapes(model)[name], dtype)(k)


def draw_globals(model: dict, key, dtype: str = "float32") -> dict:
    return {n: draw_leaf(model, key, -1, n, dtype) for n in GLOBAL_LEAVES}


def draw_layer(model: dict, key, i: int, dtype: str = "float32") -> dict:
    return {n: draw_leaf(model, key, i, n, dtype) for n in LAYER_LEAVES}


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

def keys_values(p, a, s: dict, mm=None, rnd=None):
    """A layer's rotated keys and its values (B, Hkv, T, D) of its normed
    input ``a`` (B, T, C): what that (pass, layer) attends over."""
    mm, rnd = mm or _mm_f32, rnd or (lambda v: v)
    b, t, _ = a.shape
    split = lambda v: v.reshape(b, t, s["Hkv"], s["D"]).transpose(0, 2, 1, 3)
    k = rnd(rotate(split(rnd(mm(a, p["k_w"]))), s["theta"]))
    return k, split(rnd(mm(a, p["v_w"])))


def attention(p, a, s: dict, mm, rnd):
    """Causal attention with rotary positions, K/V head ``j`` serving
    query heads ``j*G .. j*G+G-1``: (B, T, C) normed -> (B, T, C)."""
    b, t, _ = a.shape
    q = rnd(mm(a, p["q_w"])).reshape(b, t, s["Hq"], s["D"]).transpose(
        0, 2, 1, 3)
    q = rnd(rotate(q, s["theta"]))
    k, v = keys_values(p, a, s, mm, rnd)
    group = s["Hq"] // s["Hkv"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for q0 in range(0, t, _Q_ROWS):
        qi = jnp.arange(q0, min(t, q0 + _Q_ROWS))[:, None]
        mask = jnp.arange(t)[None, :] <= qi
        sc = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, q0:q0 + _Q_ROWS], k,
                        precision=HIGHEST) / math.sqrt(s["D"])
        w = rnd(jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1))
        outs.append(jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=HIGHEST))
    o = rnd(jnp.concatenate(outs, axis=2))
    o = o.transpose(0, 2, 1, 3).reshape(b, t, s["Hq"] * s["D"])
    return rnd(mm(o, p["o_w"]))


def block(p, x, s: dict, mm=None, rnd=None):
    """One layer under the sandwich rule: x (B, T, C) float32 -> the same.
    ``mm`` does the dense products (float32 at ``highest`` by default);
    ``rnd``, where given, rounds every tensor a program would hold between
    two operations (norms, softmax, SiLU and RoPE stay float32 inside)."""
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    a = attention(p, rnd(rms_norm(x, p["an_g"], s["eps"])), s, mm, rnd)
    x = rnd(x + rnd(rms_norm(a, p["ao_g"], s["eps"])))
    y = gated(rnd(rms_norm(x, p["fn_g"], s["eps"])), p["gate_w"],
              p["up_w"], p["down_w"], mm, rnd)
    return rnd(x + rnd(rms_norm(y, p["fo_g"], s["eps"])))


def end_pass(g, x, s: dict, rnd=None):
    """``h(r)``: the final norm of a pass's last layer's output."""
    rnd = rnd or (lambda a: a)
    return rnd(rms_norm(x, g["lnf_g"], s["eps"]))


def head(g, h, s: dict, mm=None, rnd=None):
    """Logits of the last pass's normed state (no norm here)."""
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    return rnd(mm(h, g["head_w"]))


def forward(model: dict, g, layers, tokens):
    """tokens (B, T) -> logits (B, T, V), all of it at once (small sizes)."""
    s = sizes(model)
    x = g["wte"][tokens]
    for _ in range(s["R"]):
        for i in range(s["L"]):
            x = block(layers[i], x, s)
        x = end_pass(g, x, s)
    return head(g, x, s)


def pass_keys(model: dict, g, layers, tokens):
    """Per (pass, layer), in that order, the rotated keys and the values
    (B, Hkv, T, D) that (pass, layer) attends over (small sizes)."""
    s = sizes(model)
    x, out = g["wte"][tokens], []
    for _ in range(s["R"]):
        for i in range(s["L"]):
            out.append(keys_values(layers[i],
                                   rms_norm(x, layers[i]["an_g"], s["eps"]),
                                   s))
            x = block(layers[i], x, s)
        x = end_pass(g, x, s)
    return out


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
        x = through(g["wte"])[tokens]
        return tuple((rnd or same)(x) for _, rnd in kits)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def layer(p, xs):
        p = jax.tree_util.tree_map(through, p)
        return tuple(block(p, x, s, mm, rnd) for x, (mm, rnd) in zip(xs, kits))

    @functools.partial(jax.jit, donate_argnums=(1,))
    def between(g, xs):
        g = jax.tree_util.tree_map(through, g)
        return tuple(end_pass(g, x, s, rnd) for x, (_, rnd) in zip(xs, kits))

    @jax.jit
    def finish(g, xs, at):
        """Logits (R, M, V) of each mode at the positions ``at`` (R, M)."""
        g = jax.tree_util.tree_map(through, g)
        rows = jnp.arange(at.shape[0])[:, None]
        return tuple(head(g, x[rows, at], s, mm, rnd)
                     for x, (mm, rnd) in zip(xs, kits))

    return start, layer, between, finish


def logits_at(model: dict, seed: int, dtype: str, blocks, at,
              modes=("f32",)):
    """For each block of rows ``blocks[b]`` (R, T) int32 and positions
    ``at[b]`` (R, M): the logits (R, M, V) float32 of every mode there
    (``exaone_moe._kit``'s: ``f32``, ``stated``, a control), with the
    weights of ``seed`` as the program holds them. Yields one tuple (a
    mode each) per block. A layer's weights are drawn once a pass and live
    one layer at a time; every block's activations stay on the device."""
    start, layer, between, finish = _seq_fns(
        json.dumps(model, sort_keys=True), dtype, tuple(modes))
    key = root_key(seed)
    g = draw_globals(model, key, dtype)
    xs = [start(g, jnp.asarray(t, jnp.int32)) for t in blocks]
    s = sizes(model)
    for _ in range(s["R"]):
        for i in range(s["L"]):
            p = draw_layer(model, key, i, dtype)
            xs = [layer(p, x) for x in xs]
            del p
        xs = [between(g, x) for x in xs]
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
    """How far below the float32 reference's best logit the tokens lie, at
    every position that predicted a served token of ``samples`` (pairs
    ``(prompt, served_tokens)``): flat float arrays under ``"served"``,
    under each of ``modes`` (the token that mode puts first there) and
    under ``"margin"`` (the reference's best less its second best). Rows
    are padded to ``pad_to`` (nothing here looks right of a position)."""
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
    every = logits_at(model, seed, dtype, blocks, at, ("f32",) + modes)
    for (ref, *low), nxt, use in zip(every, ids, live):
        top2 = jax.lax.top_k(ref, 2)[0]

        def below_best(tok):
            return np.asarray(top2[..., 0] - jnp.take_along_axis(
                ref, tok[..., None], -1)[..., 0])[use]

        out["served"].append(below_best(jnp.asarray(nxt)))
        out["margin"].append(np.asarray(top2[..., 0] - top2[..., 1])[use])
        for mode, logits in zip(modes, low):
            out[mode].append(below_best(jnp.argmax(logits, axis=-1)))
    return {k: np.concatenate(v) for k, v in out.items()}
