"""Plain float32 reference of the EXAONE-MoE decoder layer (K-EXAONE-236B-A23B
as its ``config.json`` publishes it), and the seeded weights.

Nothing here imports the program: no cache, no sorting, no grouped product.
A whole sequence goes through at once under an explicit causal (and, on
``sliding_attention`` layers, window) mask; every expert HELD HERE is
computed for every token and masked by the router's choice. ``model`` is the
configuration file's ``model`` block (Hugging Face key names); the lists
``layer_types`` / ``mlp_layer_types`` are read up to ``num_hidden_layers``.

The layer, with ``h`` the residual stream (what the config does not say is
EXAONE 4.0's and listed under ``assumed`` in the configuration file):

- attention: ``q, k, v = W_q x, W_k x, W_v x`` (no bias), RMSNorm over each
  q and k head, RoPE (theta from ``rope_parameters``, the half-split
  convention, float32) on window layers and none on full ones, K/V head
  ``j`` serves query heads ``j*G .. j*G+G-1``, scores / sqrt(head_dim);
- ``h = h + RMSNorm(attn(h))``, ``h = h + RMSNorm(ffn(h))``;
- dense FFN ``W_d (silu(W_g x) * W_u x)``;
- sparse FFN: ``s = sigmoid(W_r x)`` over all ``router_width`` experts, the
  ``num_experts_per_tok`` largest of ``s + b`` chosen, ``w_e =
  routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)``, ``y = sum
  over the chosen e HELD HERE of w_e E_e(x) + E_shared(x)``. The experts
  held are ``expert_share["index"] * num_experts ..`` of the layer's
  ``router_width = num_experts * expert_share["of"]``; what the others
  would add is left out, as on that chip before the exchange.

Leaf names are this file's own. Dense weights are (out, in): ``y = x @
w.T``; expert stacks are ``eg_w``/``eu_w`` (E, in, out) and ``ed_w``
(E, out_of_gate, hidden). Weights: ``wte`` N(0, 1) (the stream's other
terms leave a norm at unit size), projections N(0, 0.02), ``router_w``
N(0, 0.006) (logits of about unit spread, so scores spread over (0, 1)),
``router_b`` N(0, 0.01) (the spacing of the 8th and 9th of 128 scores, so
it decides some choices), gains 1 + N(0, 0.02).
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

ATTN_LEAVES = ("q_w", "k_w", "v_w", "o_w", "qn_g", "kn_g", "an_g", "fn_g")
DENSE_LEAVES = ("gate_w", "up_w", "down_w")
SPARSE_LEAVES = ("router_w", "router_b", "eg_w", "eu_w", "ed_w", "sg_w",
                 "su_w", "sd_w")
LAYER_LEAVES = ATTN_LEAVES + DENSE_LEAVES + SPARSE_LEAVES
GLOBAL_LEAVES = ("wte", "lnf_g", "head_w")
HIGHEST = jax.lax.Precision.HIGHEST
_STD = {"wte": 1.0, "router_w": 0.006, "router_b": 0.01}
#: queries per block of the masked attention, so 4096 positions fit
_Q_ROWS = 512


def sizes(model: dict) -> dict:
    n = int(model["num_hidden_layers"])
    share = model["expert_share"]
    return {
        "C": int(model["hidden_size"]), "L": n,
        "Hq": int(model["num_attention_heads"]),
        "Hkv": int(model["num_key_value_heads"]), "D": int(model["head_dim"]),
        "F": int(model["intermediate_size"]),
        "Fe": int(model["moe_intermediate_size"]),
        "E": int(model["num_experts"]),
        "R": int(model["num_experts"]) * int(share["of"]),
        "first": int(model["num_experts"]) * int(share["index"]),
        "K": int(model["num_experts_per_tok"]),
        "V": int(model["vocab_size"]), "W": int(model["sliding_window"]),
        "theta": float(model["rope_parameters"]["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "scale": float(model["routed_scaling_factor"]),
        "attn": tuple(model["layer_types"][:n]),
        "ffn": tuple(model["mlp_layer_types"][:n])}


def layer_leaves(model: dict, i: int) -> tuple:
    """Names of layer ``i``'s leaves: its attention and its kind of FFN."""
    return ATTN_LEAVES + (DENSE_LEAVES if sizes(model)["ffn"][i] == "dense"
                          else SPARSE_LEAVES)


def leaf_shapes(model: dict) -> dict:
    s = sizes(model)
    c, d, f, fe, e = s["C"], s["D"], s["F"], s["Fe"], s["E"]
    hq, hkv = s["Hq"] * d, s["Hkv"] * d
    return {"wte": (s["V"], c), "lnf_g": (c,), "head_w": (s["V"], c),
            "q_w": (hq, c), "k_w": (hkv, c), "v_w": (hkv, c), "o_w": (c, hq),
            "qn_g": (d,), "kn_g": (d,), "an_g": (c,), "fn_g": (c,),
            "gate_w": (f, c), "up_w": (f, c), "down_w": (c, f),
            "router_w": (s["R"], c), "router_b": (s["R"],),
            "eg_w": (e, c, fe), "eu_w": (e, c, fe), "ed_w": (e, fe, c),
            "sg_w": (fe, c), "su_w": (fe, c), "sd_w": (c, fe)}


def root_key(seed: int):
    """--seed may pass 2**31: fold it in as two 31-bit halves."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _leaf_fn(name: str, shape: tuple, dtype: str):
    """One leaf drawn in float32 and cast, in one jitted call of its own:
    a 16 x 6144 x 2048 expert stack never lies beside its siblings in
    float32."""
    std = _STD.get(name, 0.02)

    @jax.jit
    def draw(key):
        x = std * jax.random.normal(key, shape, jnp.float32)
        return (1.0 + x if name.endswith("_g") else x).astype(dtype)

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
    holds one layer at a time, so the program's copy of all of them and
    the one in flight are all the device ever holds."""

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


class _Leaves:
    """``items()`` of ``{"wte": a, ..., "h0.q_w": a, ...}``, a layer's
    leaves made as the walk reaches it."""

    def __init__(self, tree_globals, tree_layers):
        self.g, self.layers = tree_globals, tree_layers

    def items(self):
        yield from self.g.items()
        for i in range(len(self.layers)):
            for n, a in self.layers[i].items():
                yield f"h{i}.{n}", a


def flatten_leaves(tree_globals, tree_layers) -> _Leaves:
    return _Leaves(tree_globals, tree_layers)


def _through(dtype: str):
    """The numbers the program holds: rounded to its type, read as f32."""
    return lambda a: a.astype(dtype).astype(jnp.float32)


# -- the layer ----------------------------------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _mm_f32(x, w):
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)


def rotate(x, theta: float):
    """RoPE of ``x`` (B, H, T, D) at positions 0..T-1."""
    d, t = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    half = d // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def attention(p, x, s: dict, kind: str, mm, rnd):
    b, t, _ = x.shape
    split = lambda a, h: a.reshape(b, t, h, s["D"]).transpose(0, 2, 1, 3)
    q = rnd(rms_norm(split(rnd(mm(x, p["q_w"])), s["Hq"]), p["qn_g"],
                     s["eps"]))
    k = rnd(rms_norm(split(rnd(mm(x, p["k_w"])), s["Hkv"]), p["kn_g"],
                     s["eps"]))
    v = split(rnd(mm(x, p["v_w"])), s["Hkv"])
    window = kind == "sliding_attention"
    if window:
        q, k = rnd(rotate(q, s["theta"])), rnd(rotate(k, s["theta"]))
    group = s["Hq"] // s["Hkv"]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    outs = []
    for q0 in range(0, t, _Q_ROWS):
        qi = jnp.arange(q0, min(t, q0 + _Q_ROWS))[:, None]
        kj = jnp.arange(t)[None, :]
        mask = kj <= qi
        if window:
            mask &= qi - kj < s["W"]
        sc = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, q0:q0 + _Q_ROWS], k,
                        precision=HIGHEST) / math.sqrt(s["D"])
        w = rnd(jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1))
        outs.append(jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=HIGHEST))
    a = rnd(jnp.concatenate(outs, axis=2))
    a = a.transpose(0, 2, 1, 3).reshape(b, t, s["Hq"] * s["D"])
    return rnd(mm(a, p["o_w"]))


def gated(x, wg, wu, wd, mm, rnd):
    return rnd(mm(rnd(jax.nn.silu(rnd(mm(x, wg))) * rnd(mm(x, wu))), wd))


def route(p, x, s: dict):
    """Per token the weight of every one of the ``R`` experts (0 where it
    was not chosen), and the gap between the 8th and the 9th of ``s + b``
    (how near the choice was to another)."""
    scores = jax.nn.sigmoid(_mm_f32(x, p["router_w"]))
    top, idx = jax.lax.top_k(scores + p["router_b"], s["K"] + 1)
    chosen = jax.nn.one_hot(idx[..., :s["K"]], s["R"],
                            dtype=jnp.float32).sum(-2)
    picked = scores * chosen
    w = s["scale"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return w, top[..., s["K"] - 1] - top[..., s["K"]]


def sparse_ffn(p, x, s: dict, mm, rnd, weights=None):
    """Every held expert for every token, masked by the choice (by
    ``weights`` where given: another stream's choice, see ``_kit``).
    Returns ``(y, tie, weights)``."""
    w, tie = route(p, x, s)
    w = w if weights is None else weights
    y = gated(x, p["sg_w"], p["su_w"], p["sd_w"], mm, rnd)
    for e in range(s["E"]):
        out = gated(x, p["eg_w"][e].T, p["eu_w"][e].T, p["ed_w"][e].T, mm,
                    rnd)
        y = y + w[..., s["first"] + e, None] * out
    return rnd(y), tie, w


def block(p, x, s: dict, kinds: tuple, mm=None, rnd=None, weights=None):
    """A layer of ``kinds`` (attention kind, FFN kind): x (B, T, C) float32
    -> ``(x, tie, weights)``; ``tie`` (B, T) is the router's 8th-to-9th gap
    and ``weights`` its choice (both None on a dense layer); ``weights``,
    where given, is used in the router's place. ``mm`` does the
    dense products (float32 at ``highest`` by default); ``rnd``, where
    given, rounds every tensor a program would hold between two operations
    (norms, softmax, SiLU, RoPE and the router stay float32 inside)."""
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    a = attention(p, x, s, kinds[0], mm, rnd)
    x = rnd(x + rnd(rms_norm(a, p["an_g"], s["eps"])))
    if kinds[1] == "dense":
        y = gated(x, p["gate_w"], p["up_w"], p["down_w"], mm, rnd)
        tie = weights = None
    else:
        y, tie, weights = sparse_ffn(p, x, s, mm, rnd, weights)
    return rnd(x + rnd(rms_norm(y, p["fn_g"], s["eps"]))), tie, weights


def head(g, x, s: dict, mm=None, rnd=None):
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    return rnd(mm(rnd(rms_norm(x, g["lnf_g"], s["eps"])), g["head_w"]))


def forward(model: dict, g, layers, tokens):
    """tokens (B, T) -> logits (B, T, V), all of it at once (small sizes)."""
    s = sizes(model)
    x = g["wte"][tokens]
    for i in range(s["L"]):
        x = block(layers[i], x, s, (s["attn"][i], s["ffn"][i]))[0]
    return head(g, x, s)


# -- lower precisions: the stated type computed plainly, and the steps below it

def _mm_low(x, w):
    """Operands that bfloat16 holds exactly (bfloat16 itself, int8, float8)
    multiplied on the matrix unit and summed in float32."""
    return jnp.einsum("...i,oi->...o", x.astype(jnp.bfloat16),
                      w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _scaled(a, top: float):
    s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / top
    return jnp.where(s == 0, 1.0, s)


def _mm_int8(x, w):
    """The step below bfloat16: weights int8 per output channel,
    activations int8 per token, products summed in float32."""
    xs, ws = _scaled(x, 127.0), _scaled(w, 127.0)
    q = lambda a, sc: jnp.clip(jnp.round(a / sc), -127, 127)
    return _mm_low(q(x, xs), q(w, ws)) * xs * ws[:, 0]


def _mm_fp8(x, w):
    """The other step below bfloat16: both operands rounded to float8
    (e4m3, 3 bits of mantissa) with one scale per row."""
    xs, ws = _scaled(x, 448.0), _scaled(w, 448.0)
    q = lambda a, sc: (a / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return _mm_low(q(x, xs), q(w, ws)) * xs * ws[:, 0]


CONTROLS = {"int8": _mm_int8, "fp8": _mm_fp8}
F32ROUTE = "+f32route"


def _kit(dtype: str, mode: str):
    """``(mm, rnd)`` of a mode. ``"f32"`` is the reference. ``"stated"`` is
    the same layer as a plain program of the configuration's type computes
    it: every tensor between two operations rounded to the type, products
    of the type's operands summed in float32. A control is ``"stated"``
    with its dense products a step lower (of a float32 configuration, the
    CPU rehearsal's, too: the quantised operands are exact in bfloat16).
    A mode named ``<mode>+f32route`` is ``<mode>`` with every router's
    choice taken from the first mode's stream (the float32 reference's):
    what is left of its distance to the reference is not the routing's."""
    mode = mode.removesuffix(F32ROUTE)
    if mode == "f32":
        return None, None
    stated = _mm_low if dtype == "bfloat16" else _mm_f32
    return (stated if mode == "stated" else CONTROLS[mode]), _through(dtype)


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
    def layer(p, xs, kinds):
        p = jax.tree_util.tree_map(through, p)
        out = []
        for x, (mm, rnd), mode in zip(xs, kits, modes):
            led = out[0][2] if mode.endswith(F32ROUTE) else None
            out.append(block(p, x, s, kinds, mm, rnd, led))
        # the first mode's near-ties: gaps under a thousandth
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
    """For each block of rows ``blocks[b]`` (R, T) int32 and positions
    ``at[b]`` (R, M): the logits (R, M, V) float32 of every mode there, with
    the weights of ``seed`` as the program holds them. Yields one tuple
    (a mode each) per block. A layer's weights are drawn once and live one
    layer at a time; every block's activations stay on the device.
    ``notes``, where given, receives ``router_near_ties``: of the first
    mode's routing decisions (positions x sparse layers, padding
    included), how many had the 8th and 9th of ``s + b`` within 1e-3."""
    start, layer, finish = _seq_fns(json.dumps(model, sort_keys=True), dtype,
                                    tuple(modes))
    key = root_key(seed)
    g = draw_globals(model, key, dtype)
    xs = [start(g, jnp.asarray(t, jnp.int32)) for t in blocks]
    near, s = 0, sizes(model)
    for i in range(s["L"]):
        p = draw_layer(model, key, i, dtype)
        stepped = [layer(p, x, (s["attn"][i], s["ffn"][i])) for x in xs]
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
    """How far below the float32 reference's best logit the tokens lie, at
    every position that predicted a served token of ``samples`` (pairs
    ``(prompt, served_tokens)``). Flat float arrays under ``"served"`` (the
    tokens that were served), under each of ``modes`` (the token that mode
    puts first there, read at the same prompts and tokens), and under
    ``"margin"`` (the reference's best less its second best). Beside them,
    as a note, ``"router_near_ties"`` (see :func:`logits_at`): routing
    decisions the reference cannot tell from their neighbour; it goes to
    standard error too.

    Rows are padded to ``pad_to`` (causal attention never looks right, so
    padding changes nothing left of it) and go in blocks of
    ``rows_per_block``; the head is computed at the served positions only.
    """
    # a served program that was dropped but not yet collected (its session
    # is a reference cycle) still holds its 13 GB: collect it first
    gc.collect()
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
            # logits at position j predict token j + 1: the served tokens
            # sit at n .. n + m - 1, predicted from n - 1 .. n + m - 2
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
