"""Plain float32 reference of the Jamba decoder (AI21-Jamba2-3B as its
``config.json`` publishes it, ``model_type`` ``jamba``), and the seeded
weights.

Nothing here imports the program: no cache, no chunks, no one-token forms.
A whole sequence goes through at once: attention under an explicit causal
mask, the selective scan as a ``lax.scan`` over positions, ONE position at
a time, from a zero state. ``model`` is the configuration file's ``model``
block (the source's key names). The published description is
``transformers``' ``JambaMambaMixer`` / ``JambaAttentionDecoderLayer``.

The equations (``C`` = ``hidden_size``, ``E`` = ``mamba_expand`` x ``C``,
``N`` = ``mamba_d_state``, ``R`` = ``mamba_dt_rank``, ``K`` =
``mamba_d_conv``):

- layer ``i`` is attention where ``i % attn_layer_period ==
  attn_layer_offset``, else ``mamba`` (``JambaConfig.layers_block_type``);
  with ``num_experts`` 1 every FFN is the dense ``W_d (silu(W_g x) * W_u
  x)``. Both kinds: ``h = h + mix(RMSNorm(h))``, ``h = h + FFN(RMSNorm(h))``;
  a final RMSNorm; logits over the tied table;
- mixer: ``[x, z] = W_in u`` (each ``E``); ``x_t = silu(b_c + sum_{k<K}
  w_c[k] * x_{t-K+1+k})`` (depthwise, causal, zeros before the sequence);
  ``[d, B, C_] = W_x x_t`` (``R``, ``N``, ``N``), each RMS-normed with its
  own gain; ``dt = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``; ``h_t =
  exp(dt (x) A) * h_{t-1} + (dt * x_t) (x) B`` (``h_{-1} = 0``); ``y_t =
  h_t C_ + D * x_t``; out ``= W_out (y_t * silu(z_t))``; no projection bias;
- attention: ``num_attention_heads`` query heads of ``hidden_size /
  num_attention_heads`` over ``num_key_value_heads`` K/V heads, no bias, no
  positions of any kind, no per-head norm, causal, scale ``head ** -0.5``.

Departures from ``transformers``' module: ``a_log`` is held ``(N, E)`` and
``conv_w`` ``(K, E)`` (its ``A_log`` is ``(E, N)``, its ``conv1d.weight``
``(E, 1, K)``: the transposes, so that a leaf has the shape of the
program's parameter it is set into) and the state is carried ``(N, E)``;
the scan is the plain recurrence (its slow path, not the fused kernel
``use_mamba_kernels`` asks for: the same arithmetic); ``dt_proj``'s bias is
a leaf of its own, added inside the softplus, as the slow path does; no
MoE router exists to leave out (``num_experts`` 1); ``num_logits_to_keep``
is the caller's (logits are computed where they are asked for).

Leaf names are this file's own. Dense weights are (out, in): ``y = x @
w.T``. Weights: the table and every projection N(0, ``hidden_size ** -0.5``):
0.0198 at the published 2560, and of the same effect at a test's 64 (the
table is the head too: at N(0, 1) a token's own row would win every logit
by hundreds, and at N(0, 0.02) over 64 channels the layers would add
nothing to the stream, so that no precision could be told from another;
so drawn, the logits have about unit spread and a token's own row no
head start); gains 1 + N(0, 0.02), ``conv_w`` N(0, 0.3) (the mixer's own
initialisation is uniform of that spread), ``conv_b`` N(0, 0.02), ``a_log`` = ``log(1..N)`` a channel
(the mixer's own) + N(0, 0.02), ``d_skip`` 1 + N(0, 0.02), ``dt_b`` the
inverse softplus of a log-uniform draw in [1e-3, 1e-1] (the mixer's own):
a channel forgets over tens to thousands of positions, so the state at
position 3000 still depends on the prompt.
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

from .exaone_moe import (HIGHEST, _kit, _mm_f32, _through,  # noqa: F401
                         flatten_leaves, gated, rms_norm, root_key)

MAMBA_LEAVES = ("in_w", "conv_w", "conv_b", "x_w", "dtn_g", "bn_g", "cn_g",
                "dt_w", "dt_b", "a_log", "d_skip", "out_w")
ATTN_LEAVES = ("q_w", "k_w", "v_w", "o_w")
BOTH_LEAVES = ("an_g", "fn_g", "gate_w", "up_w", "down_w")
LAYER_LEAVES = MAMBA_LEAVES + ATTN_LEAVES + BOTH_LEAVES
GLOBAL_LEAVES = ("wte", "lnf_g")
#: leaves drawn N(0, hidden_size ** -0.5); ``conv_w`` N(0, 0.3); the rest
#: N(0, 0.02) about their mean
PROJECTIONS = ("wte", "in_w", "x_w", "dt_w", "out_w", "q_w", "k_w", "v_w",
               "o_w", "gate_w", "up_w", "down_w")
_DT_RANGE = (1e-3, 1e-1)
#: queries per block of the masked attention
_Q_ROWS = 512


def sizes(model: dict) -> dict:
    n, c = int(model["num_hidden_layers"]), int(model["hidden_size"])
    period, offset = (int(model["attn_layer_period"]),
                      int(model["attn_layer_offset"]))
    return {
        "C": c, "L": n, "E": int(model["mamba_expand"]) * c,
        "N": int(model["mamba_d_state"]), "R": int(model["mamba_dt_rank"]),
        "K": int(model["mamba_d_conv"]),
        "Hq": int(model["num_attention_heads"]),
        "Hkv": int(model["num_key_value_heads"]),
        "D": c // int(model["num_attention_heads"]),
        "F": int(model["intermediate_size"]), "V": int(model["vocab_size"]),
        "eps": float(model["rms_norm_eps"]),
        "kinds": tuple("attention" if i % period == offset else "mamba"
                       for i in range(n))}


def layer_leaves(model: dict, i: int) -> tuple:
    """Names of layer ``i``'s leaves: its mixer's and the FFN's."""
    return (ATTN_LEAVES if sizes(model)["kinds"][i] == "attention"
            else MAMBA_LEAVES) + BOTH_LEAVES


def leaf_shapes(model: dict) -> dict:
    s = sizes(model)
    c, e, n, r, f = s["C"], s["E"], s["N"], s["R"], s["F"]
    hq, hkv = s["Hq"] * s["D"], s["Hkv"] * s["D"]
    return {"wte": (s["V"], c), "lnf_g": (c,),
            "in_w": (2 * e, c), "conv_w": (s["K"], e), "conv_b": (e,),
            "x_w": (r + 2 * n, e), "dtn_g": (r,), "bn_g": (n,),
            "cn_g": (n,), "dt_w": (e, r), "dt_b": (e,), "a_log": (n, e),
            "d_skip": (e,), "out_w": (c, e),
            "q_w": (hq, c), "k_w": (hkv, c), "v_w": (hkv, c), "o_w": (c, hq),
            "an_g": (c,), "fn_g": (c,),
            "gate_w": (f, c), "up_w": (f, c), "down_w": (c, f)}


@functools.lru_cache(maxsize=None)
def _leaf_fn(name: str, shape: tuple, dtype: str, width: int):
    """One leaf drawn in float32 and cast, in one jitted call of its own."""
    std = width ** -0.5 if name in PROJECTIONS \
        else (0.3 if name == "conv_w" else 0.02)

    @jax.jit
    def draw(key):
        x = std * jax.random.normal(key, shape, jnp.float32)
        if name == "dt_b":
            lo, hi = (math.log(v) for v in _DT_RANGE)
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
            x = dt + jnp.log(-jnp.expm1(-dt))       # softplus(x) = dt
        elif name == "a_log":
            x = x + jnp.log(jnp.arange(1, shape[0] + 1,
                                       dtype=jnp.float32))[:, None]
        elif name.endswith("_g") or name == "d_skip":
            x = 1.0 + x
        return x.astype(dtype)

    return draw


def draw_leaf(model: dict, key, layer: int, name: str, dtype: str):
    """Leaf ``name`` of ``layer`` (-1: a global leaf) in ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           (GLOBAL_LEAVES + LAYER_LEAVES).index(name))
    return _leaf_fn(name, leaf_shapes(model)[name], dtype,
                    int(model["hidden_size"]))(k)


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

def mixer(p, u, s: dict, mm, rnd):
    """The state-space mixer: ``u`` (B, T, C) normed -> (B, T, C)."""
    t, k = u.shape[1], s["K"]
    xz = rnd(mm(u, p["in_w"]))
    x, z = xz[..., :s["E"]], xz[..., s["E"]:]
    before = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    x = rnd(jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * before[:, j:j + t] for j in range(k))))
    dbc = rnd(mm(x, p["x_w"]))
    r, n = s["R"], s["N"]
    d = rnd(rms_norm(dbc[..., :r], p["dtn_g"], s["eps"]))
    b = rnd(rms_norm(dbc[..., r:r + n], p["bn_g"], s["eps"]))
    c = rnd(rms_norm(dbc[..., r + n:], p["cn_g"], s["eps"]))
    dt = jax.nn.softplus(mm(d, p["dt_w"]) + p["dt_b"])
    a = -jnp.exp(p["a_log"])                                # (N, E)

    def position(h, at):
        x_t, dt_t, b_t, c_t = at                    # (B, E) (B, E) (B, N)
        h = jnp.exp(dt_t[:, None, :] * a) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.einsum("bne,bn->be", h, c_t, precision=HIGHEST)

    h0 = jnp.zeros((u.shape[0], n, s["E"]), jnp.float32)
    _, y = jax.lax.scan(position, h0, tuple(
        v.swapaxes(0, 1) for v in (x, dt, b, c)))
    y = rnd(y.swapaxes(0, 1) + p["d_skip"] * x)
    return rnd(mm(rnd(y * jax.nn.silu(z)), p["out_w"]))


def attention(p, x, s: dict, mm, rnd):
    """Causal attention with no positions and no per-head norm, K/V head
    ``j`` serving query heads ``j*G .. j*G+G-1``: (B, T, C) -> (B, T, C)."""
    b, t, _ = x.shape
    split = lambda a, h: a.reshape(b, t, h, s["D"]).transpose(0, 2, 1, 3)
    q = split(rnd(mm(x, p["q_w"])), s["Hq"])
    k = split(rnd(mm(x, p["k_w"])), s["Hkv"])
    v = split(rnd(mm(x, p["v_w"])), s["Hkv"])
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
    a = rnd(jnp.concatenate(outs, axis=2))
    a = a.transpose(0, 2, 1, 3).reshape(b, t, s["Hq"] * s["D"])
    return rnd(mm(a, p["o_w"]))


def block(p, x, s: dict, kind: str, mm=None, rnd=None):
    """A layer of ``kind`` (``"mamba"`` | ``"attention"``): x (B, T, C)
    float32 -> the same. ``mm`` does the dense products (float32 at
    ``highest`` by default); ``rnd``, where given, rounds every tensor a
    program would hold between two operations (norms, softmax, SiLU,
    ``dt`` and the recurrence stay float32 inside)."""
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    mix = mixer if kind == "mamba" else attention
    x = rnd(x + mix(p, rnd(rms_norm(x, p["an_g"], s["eps"])), s, mm, rnd))
    y = gated(rnd(rms_norm(x, p["fn_g"], s["eps"])), p["gate_w"],
              p["up_w"], p["down_w"], mm, rnd)
    return rnd(x + y)


def head(g, x, s: dict, mm=None, rnd=None):
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    return rnd(mm(rnd(rms_norm(x, g["lnf_g"], s["eps"])), g["wte"]))


def forward(model: dict, g, layers, tokens):
    """tokens (B, T) -> logits (B, T, V), all of it at once (small sizes)."""
    s = sizes(model)
    x = g["wte"][tokens]
    for i in range(s["L"]):
        x = block(layers[i], x, s, s["kinds"][i])
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
        x = through(g["wte"])[tokens]
        return tuple((rnd or same)(x) for _, rnd in kits)

    @functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(1,))
    def layer(p, xs, kind):
        p = jax.tree_util.tree_map(through, p)
        return tuple(block(p, x, s, kind, mm, rnd)
                     for x, (mm, rnd) in zip(xs, kits))

    @jax.jit
    def finish(g, xs, at):
        """Logits (R, M, V) of each mode at the positions ``at`` (R, M)."""
        g = jax.tree_util.tree_map(through, g)
        rows = jnp.arange(at.shape[0])[:, None]
        return tuple(head(g, x[rows, at], s, mm, rnd)
                     for x, (mm, rnd) in zip(xs, kits))

    return start, layer, finish


def logits_at(model: dict, seed: int, dtype: str, blocks, at,
              modes=("f32",)):
    """For each block of rows ``blocks[b]`` (R, T) int32 and positions
    ``at[b]`` (R, M): the logits (R, M, V) float32 of every mode there
    (``exaone_moe._kit``'s: ``f32``, ``stated``, a control), with the
    weights of ``seed`` as the program holds them. Yields one tuple (a
    mode each) per block. A layer's weights are drawn once and live one
    layer at a time; every block's activations stay on the device."""
    start, layer, finish = _seq_fns(json.dumps(model, sort_keys=True), dtype,
                                    tuple(modes))
    key = root_key(seed)
    g = draw_globals(model, key, dtype)
    xs = [start(g, jnp.asarray(t, jnp.int32)) for t in blocks]
    s = sizes(model)
    for i in range(s["L"]):
        p = draw_layer(model, key, i, dtype)
        xs = [layer(p, x, s["kinds"][i]) for x in xs]
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
    are padded to ``pad_to`` (nothing here looks right of a position).
    Beside them the note ``"gap_by_quarter"``, which goes to standard
    error too: the mean gap of the served tokens (or the one mode's) over
    the first and over the last quarter of each request's generated
    positions, since an error in the scan's state can build with
    position."""
    gc.collect()        # a dropped session still holds its memory
    modes = tuple(modes)
    width = max(len(out) for _, out in samples)
    blocks, at, ids, live, quarter = [], [], [], [], []
    for r0 in range(0, len(samples), rows_per_block):
        toks = np.zeros((rows_per_block, pad_to), np.int32)
        pos = np.zeros((rows_per_block, width), np.int32)
        nxt = np.zeros((rows_per_block, width), np.int32)
        use = np.zeros((rows_per_block, width), bool)
        part = np.zeros((rows_per_block, width), np.int8)
        for r, (prompt, out) in enumerate(samples[r0:r0 + rows_per_block]):
            n, m = len(prompt), len(out)
            toks[r, :n + m] = np.concatenate([prompt, out])[:pad_to]
            # logits at position j predict token j + 1
            pos[r, :m] = np.arange(n - 1, n + m - 1)
            nxt[r, :m] = out
            use[r, :m] = True
            part[r, :m] = np.minimum(4 * np.arange(m) // max(m, 1), 3)
        blocks.append(toks), at.append(pos), ids.append(nxt)
        live.append(use), quarter.append(part[use])
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
    out = {k: np.concatenate(v) for k, v in out.items()}
    part = np.concatenate(quarter)
    notes = {"gap_by_quarter": {
        k: {"first": float(out[k][part == 0].mean()),
            "last": float(out[k][part == 3].mean())}
        for k in ("served",) + modes}}
    print(f"reference gap_by_quarter = {json.dumps(notes)}", file=sys.stderr)
    return dict(out, **notes)
