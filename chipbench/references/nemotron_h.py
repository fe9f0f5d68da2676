"""Plain float32 reference of the Nemotron-H decoder (NVIDIA-Nemotron-3-Super-
120B-A12B as its ``config.json`` publishes it, ``model_type`` ``nemotron_h``),
one chip's share of it, and the seeded weights.

Nothing here imports the program: no cache, no chunks, no sorting, no
batching of experts. A whole sequence goes through at once: attention under
an explicit causal mask, the Mamba-2 recurrence as a ``lax.scan`` over
positions, ONE position at a time, from a zero state, and every expert HELD
HERE computed for every token and weighted by the router's choice (0 where
it was not chosen). ``model`` is the configuration file's ``model`` block
(the source's key names, and ``expert_share``: the router is
``n_routed_experts * expert_share["of"]`` wide and the experts held are
``expert_share["index"] * n_routed_experts ..``; what the others would add
is left out, as on that chip before the exchange). The published
description is ``transformers``' ``NemotronHMamba2Mixer``,
``NemotronHAttention``, ``NemotronHMOE`` and ``NemotronHBlock``.

The equations (``C`` = ``hidden_size``, ``H`` = ``mamba_num_heads`` of
``P`` = ``mamba_head_dim``, ``E = H P``, ``G`` = ``n_groups``, ``N`` =
``ssm_state_size``, ``K`` = ``conv_kernel``):

- layer ``i`` is ONE of: a mixer (``M``), a latent expert layer (``E``) or
  attention (``*``), ``hybrid_override_pattern[i]``; every layer is ``h =
  h + f(RMSNorm(h))``; a final RMSNorm; logits over the untied head;
- ``M``: ``[z, xBC, dt] = W_in u`` (``E``, ``E + 2 G N``, ``H``); ``xBC =
  silu(b_c + sum_{k<K} w_c[k] xBC_{t-K+1+k})`` (depthwise, causal, zeros
  before the sequence); ``[x, B, C] = xBC`` (``E``, ``G N``, ``G N``);
  ``dt_h = softplus(dt_h + b_dt,h)``; ``A_h = -exp(A_log,h)``; head ``h``
  of group ``g = h // (H / G)``: ``S_h,t = exp(dt_h A_h) S_h,t-1 + dt_h
  x_h,t (x) B_g,t`` (``P x N``, ``S_-1 = 0``), ``y_h,t = S_h,t C_g,t + D_h
  x_h,t``; ``y = GroupRMSNorm_G(y * silu(z)) * w``; out ``= W_out y``; no
  bias but the convolution's;
- ``*``: ``num_attention_heads`` query heads over ``num_key_value_heads``
  K/V heads of ``head_dim``, no bias, no positions, no per-head norm,
  causal, scale ``head_dim ** -0.5``;
- ``E``: ``s = sigmoid(W_r x)`` over all the router's experts, float32;
  the ``num_experts_per_tok`` largest of ``s + b`` chosen (``n_group`` 1:
  no group stage); ``w_e = routed_scaling_factor * s_e / (sum of the
  chosen s + 1e-20)``; ``l = W_lat_down x``; ``y = W_lat_up sum over the
  chosen e HELD HERE of w_e W_down,e relu(W_up,e l) ** 2``, plus the
  shared expert ``W_sd relu(W_su x) ** 2`` at full width.

Departures from ``transformers``' modules: ``conv_w`` is held ``(K, E + 2 G
N)`` (its ``conv1d.weight`` is ``(E + 2 G N, 1, K)``: the transpose, so
that a leaf has the shape of the program's parameter) and the expert
stacks ``(E_held, in, out)``; the scan is the plain recurrence (its slow
path, not the fused kernels ``use_mamba_kernels`` asks for nor its own
chunked form: the same arithmetic); ``time_step_limit`` (0, inf) clamps
nothing and is left out; the multi-token-prediction layer
(``num_nextn_predict_layers``) is left out (the next-token logits do not
depend on it); ``num_logits_to_keep`` is the caller's (logits are computed
where they are asked for). What the config's keys do not state is read as
``transformers``' ``nemotron_h`` has it and listed under ``assumed`` in the
configuration file: attention carries no rotary positions (``rope_theta``
and ``partial_rotary_factor`` are read by no layer); the router reads the
full-width ``x`` and nothing but the two latent projections lies between
``x`` and the experts; ``b`` is a choice-only bias; the state is float32.

Leaf names are this file's own. Dense weights are (out, in): ``y = x @
w.T``. Weights: ``wte`` N(0, 1) (the table is not the head: the stream
starts at unit size), every projection N(0, fan_in ** -0.5) (``head_w``,
``router_w`` too: the logits and the router's logits have about unit
spread, and through relu2 an expert's output stays of the size of its
input), gains 1 + N(0, 0.02), the relu2 down projections (``sd_w``,
``ed_w``) with each output's weights centred to sum to zero (relu2 has a
positive mean, 0.5 a unit at unit input, so an uncentred draw adds one
fixed vector to every token in every expert layer, and after a few such
layers the router's choice is skewed by the seed: in a narrowed stand-in
77-85% of the held experts hit by 64 tokens and the busiest 5-7 times
the mean, where an even router hits 94%; centred, 88-95% and ~3 times on
every seed, as a router balanced by its correction bias is), ``router_b``
N(0, 0.01) (as K-EXAONE's:
about the spacing of the 22nd and 23rd of 512 scores, so it decides some
choices); the mixer's own initialisation for the rest: ``conv_w`` N(0,
0.3) (uniform of that spread), ``conv_b`` N(0, 0.02), ``A_log`` the log of
a uniform draw in [1, 16] a head, ``dt`` log-uniform in [``time_step_min``,
``time_step_max``] floored at ``time_step_floor`` a head and ``b_dt`` its
inverse softplus, ``D`` 1 + N(0, 0.02) (the published ones, perturbed as a
gain is).
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
                         flatten_leaves, rms_norm, root_key)

MAMBA_LEAVES = ("an_g", "in_w", "conv_w", "conv_b", "dt_b", "a_log",
                "d_skip", "mn_g", "out_w")
ATTN_LEAVES = ("an_g", "q_w", "k_w", "v_w", "o_w")
MOE_LEAVES = ("fn_g", "router_w", "router_b", "ld_w", "lu_w", "eu_w", "ed_w",
              "su_w", "sd_w")
LAYER_LEAVES = tuple(dict.fromkeys(MAMBA_LEAVES + ATTN_LEAVES + MOE_LEAVES))
GLOBAL_LEAVES = ("wte", "lnf_g", "head_w")
_LEAVES = {"M": MAMBA_LEAVES, "*": ATTN_LEAVES, "E": MOE_LEAVES}
#: the relu2 down projections and their input axis, along which each
#: output's weights are centred (the module docstring says why)
_CENTRED = {"sd_w": -1, "ed_w": -2}
#: queries per block of the masked attention
_Q_ROWS = 512


def sizes(model: dict) -> dict:
    n = int(model["num_hidden_layers"])
    held, share = int(model["n_routed_experts"]), model["expert_share"]
    h, p = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    g, ns = int(model["n_groups"]), int(model["ssm_state_size"])
    return {
        "C": int(model["hidden_size"]), "L": n, "H": h, "P": p, "E": h * p,
        "G": g, "N": ns, "K": int(model["conv_kernel"]),
        "conv": h * p + 2 * g * ns,
        "Hq": int(model["num_attention_heads"]),
        "Hkv": int(model["num_key_value_heads"]),
        "D": int(model["head_dim"]), "V": int(model["vocab_size"]),
        "held": held, "R": held * int(share["of"]),
        "first": held * int(share["index"]),
        "k": int(model["num_experts_per_tok"]),
        "Fe": int(model["moe_intermediate_size"]),
        "Lt": int(model["moe_latent_size"]),
        "Fs": int(model["moe_shared_expert_intermediate_size"]),
        "scale": float(model["routed_scaling_factor"]),
        "eps": float(model["layer_norm_epsilon"]),
        "kinds": tuple(model["hybrid_override_pattern"][:n])}


def layer_leaves(model: dict, i: int) -> tuple:
    """Names of layer ``i``'s leaves: its one sub-layer's and its norm."""
    return _LEAVES[sizes(model)["kinds"][i]]


def leaf_shapes(model: dict) -> dict:
    s = sizes(model)
    c, e, h, lt = s["C"], s["E"], s["H"], s["Lt"]
    hq, hkv = s["Hq"] * s["D"], s["Hkv"] * s["D"]
    return {"wte": (s["V"], c), "lnf_g": (c,), "head_w": (s["V"], c),
            "an_g": (c,), "fn_g": (c,),
            "in_w": (e + s["conv"] + h, c), "conv_w": (s["K"], s["conv"]),
            "conv_b": (s["conv"],), "dt_b": (h,), "a_log": (h,),
            "d_skip": (h,), "mn_g": (e,), "out_w": (c, e),
            "q_w": (hq, c), "k_w": (hkv, c), "v_w": (hkv, c), "o_w": (c, hq),
            "router_w": (s["R"], c), "router_b": (s["R"],),
            "ld_w": (lt, c), "lu_w": (c, lt),
            "eu_w": (s["held"], lt, s["Fe"]), "ed_w": (s["held"], s["Fe"], lt),
            "su_w": (s["Fs"], c), "sd_w": (c, s["Fs"])}


def _std(name: str, shape: tuple) -> float:
    """A leaf's spread: see the module docstring."""
    if name == "wte":
        return 1.0
    if name == "conv_w":
        return 0.3
    if name == "router_b":
        return 0.01
    if name.endswith("_w"):
        # (out, in) or an expert stack (E, in, out)
        return (shape[1] if len(shape) == 3 else shape[-1]) ** -0.5
    return 0.02


@functools.lru_cache(maxsize=None)
def _leaf_fn(name: str, shape: tuple, dtype: str, dt_range: tuple):
    """One leaf drawn in float32 and cast, in one jitted call of its own:
    a 128 x 1024 x 2688 expert stack never lies beside its siblings in
    float32."""
    std = _std(name, shape)

    @jax.jit
    def draw(key):
        x = std * jax.random.normal(key, shape, jnp.float32)
        if name == "dt_b":
            lo, hi, floor = dt_range
            dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                            math.log(lo), math.log(hi)))
            dt = jnp.maximum(dt, floor)
            x = dt + jnp.log(-jnp.expm1(-dt))       # softplus(x) = dt
        elif name == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                           16.0))
        elif name.endswith("_g") or name == "d_skip":
            x = 1.0 + x
        elif name in _CENTRED:
            x = x - x.mean(axis=_CENTRED[name], keepdims=True)
        return x.astype(dtype)

    return draw


def draw_leaf(model: dict, key, layer: int, name: str, dtype: str):
    """Leaf ``name`` of ``layer`` (-1: a global leaf) in ``dtype``."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 1),
                           (GLOBAL_LEAVES + LAYER_LEAVES).index(name))
    dt_range = tuple(float(model[n]) for n in (
        "time_step_min", "time_step_max", "time_step_floor"))
    return _leaf_fn(name, leaf_shapes(model)[name], dtype, dt_range)(k)


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


# -- the layers ---------------------------------------------------------------

def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mixer(p, u, s: dict, mm, rnd):
    """The Mamba-2 mixer: ``u`` (B, T, C) normed -> (B, T, C)."""
    bsz, t = u.shape[:2]
    e, h, g, ns, k = s["E"], s["H"], s["G"], s["N"], s["K"]
    zxd = rnd(mm(u, p["in_w"]))
    z, xbc, dt = zxd[..., :e], zxd[..., e:e + s["conv"]], \
        zxd[..., e + s["conv"]:]
    before = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = rnd(jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * before[:, j:j + t] for j in range(k))))
    x = xbc[..., :e].reshape(bsz, t, h, s["P"])
    b = xbc[..., e:e + g * ns].reshape(bsz, t, g, ns)
    c = xbc[..., e + g * ns:].reshape(bsz, t, g, ns)
    dt = jax.nn.softplus(dt + p["dt_b"])                   # (B, T, H)
    a = -jnp.exp(p["a_log"])                                # (H,)
    # each head reads its group's B and C
    per_head = lambda v: jnp.repeat(v, h // g, axis=1)

    def position(state, at):
        x_t, dt_t, b_t, c_t = at            # (B, H, P) (B, H) (B, G, N) x2
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] \
            * per_head(b_t)[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, per_head(c_t),
                                 precision=HIGHEST)

    s0 = jnp.zeros((bsz, h, s["P"], ns), jnp.float32)
    _, y = jax.lax.scan(position, s0, tuple(
        v.swapaxes(0, 1) for v in (x, dt, b, c)))
    y = rnd((y.swapaxes(0, 1) + p["d_skip"][:, None] * x).reshape(bsz, t, e))
    gated = (y * jax.nn.silu(z)).reshape(bsz, t, g, e // g)
    gated = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1,
                                           keepdims=True) + s["eps"])
    return rnd(mm(rnd(gated.reshape(bsz, t, e) * p["mn_g"]), p["out_w"]))


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


def route(p, x, s: dict):
    """Per token the weight of every one of the router's ``R`` experts (0
    where it was not chosen), and the gap between the ``k``-th and the
    ``k+1``-th of ``s + b`` (how near the choice was to another)."""
    scores = jax.nn.sigmoid(_mm_f32(x, p["router_w"]))
    top, idx = jax.lax.top_k(scores + p["router_b"], s["k"] + 1)
    chosen = jax.nn.one_hot(idx[..., :s["k"]], s["R"],
                            dtype=jnp.float32).sum(-2)
    picked = scores * chosen
    w = s["scale"] * picked / (picked.sum(-1, keepdims=True) + 1e-20)
    return w, top[..., s["k"] - 1] - top[..., s["k"]]


def experts(p, x, s: dict, mm, rnd):
    """The latent expert layer: ``x`` (B, T, C) normed -> ``(y, tie)``.
    Every held expert runs over every token in the latent and is weighted
    by the choice (a loop over the experts, one at a time); the shared
    expert runs at full width."""
    w, tie = route(p, x, s)
    lat = rnd(mm(x, p["ld_w"]))

    def one(acc, e):
        out = rnd(mm(rnd(relu2(rnd(mm(lat, p["eu_w"][e].T)))),
                     p["ed_w"][e].T))
        return acc + w[..., s["first"] + e, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                        jnp.arange(s["held"], dtype=jnp.int32))
    y = rnd(mm(rnd(y), p["lu_w"]))
    shared = rnd(mm(rnd(relu2(rnd(mm(x, p["su_w"])))), p["sd_w"]))
    return rnd(y + shared), tie


def block(p, x, s: dict, kind: str, mm=None, rnd=None):
    """A layer of ``kind`` (``M``, ``*`` or ``E``): x (B, T, C) float32 ->
    ``(x, tie)``; ``tie`` is the router's gap (None but on ``E``). ``mm``
    does the dense products (float32 at ``highest`` by default); ``rnd``,
    where given, rounds every tensor a program would hold between two
    operations (norms, softmax, the activations, ``dt``, the router and
    the recurrence stay float32 inside)."""
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    if kind == "E":
        y, tie = experts(p, rnd(rms_norm(x, p["fn_g"], s["eps"])), s, mm,
                         rnd)
        return rnd(x + y), tie
    f = mixer if kind == "M" else attention
    return rnd(x + f(p, rnd(rms_norm(x, p["an_g"], s["eps"])), s, mm,
                     rnd)), None


def head(g, x, s: dict, mm=None, rnd=None):
    mm, rnd = mm or _mm_f32, rnd or (lambda a: a)
    return rnd(mm(rnd(rms_norm(x, g["lnf_g"], s["eps"])), g["head_w"]))


def forward(model: dict, g, layers, tokens):
    """tokens (B, T) -> logits (B, T, V), all of it at once (small sizes)."""
    s = sizes(model)
    x = g["wte"][tokens]
    for i in range(s["L"]):
        x = block(layers[i], x, s, s["kinds"][i])[0]
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
        out = [block(p, x, s, kind, mm, rnd)
               for x, (mm, rnd) in zip(xs, kits)]
        # the first mode's near-ties: gaps under a thousandth
        tie = out[0][1]
        near = jnp.zeros((), jnp.int32) if tie is None \
            else (tie < 1e-3).sum().astype(jnp.int32)
        return tuple(x for x, _ in out), near

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
    ``at[b]`` (R, M): the logits (R, M, V) float32 of every mode there
    (``exaone_moe._kit``'s: ``f32``, ``stated``, a control), with the
    weights of ``seed`` as the program holds them. Yields one tuple (a
    mode each) per block. A layer's weights are drawn once and live one
    layer at a time; every block's activations stay on the device.
    ``notes``, where given, receives ``router_near_ties``: of the first
    mode's routing decisions (positions x expert layers, padding
    included), how many had the ``k``-th and ``k+1``-th of ``s + b``
    within 1e-3."""
    start, layer, finish = _seq_fns(json.dumps(model, sort_keys=True), dtype,
                                    tuple(modes))
    key = root_key(seed)
    g = draw_globals(model, key, dtype)
    xs = [start(g, jnp.asarray(t, jnp.int32)) for t in blocks]
    near, s = 0, sizes(model)
    for i in range(s["L"]):
        p = draw_layer(model, key, i, dtype)
        stepped = [layer(p, x, s["kinds"][i]) for x in xs]
        xs = [x for x, _ in stepped]
        near += sum(int(n) for _, n in stepped)
        del p
    if notes is not None:
        notes["router_near_ties"] = {
            "under_1e-3": near,
            "of": sum(int(np.asarray(t).size) for t in blocks)
            * s["kinds"].count("E")}
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
    Beside them two notes, which go to standard error too:
    ``"router_near_ties"`` (:func:`logits_at`) and ``"gap_by_quarter"``,
    the mean gap of the served tokens (or the one mode's) over the first
    and over the last quarter of each request's generated positions,
    since an error in the scan's state can build with position."""
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
    out = {k: np.concatenate(v) for k, v in out.items()}
    part = np.concatenate(quarter)
    notes["gap_by_quarter"] = {
        k: {"first": float(out[k][part == 0].mean()),
            "last": float(out[k][part == 3].mean())}
        for k in ("served",) + modes}
    print(f"reference notes = {json.dumps(notes)}", file=sys.stderr)
    return dict(out, **notes)
