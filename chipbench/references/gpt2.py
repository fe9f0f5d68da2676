"""Plain float32 reference of the GPT-2 block, and the seeded weights.

Nothing here imports the program. The weights are drawn from ``--seed`` by
``draw_layer``/``draw_globals``; the runners hand the program a copy cast to
the configuration's type, and the reference draws the same numbers again,
rounds them through that type and computes in float32 at ``highest``
precision, layer by layer, so that it fits beside nothing else on the chip.

GPT-2 as published (Radford et al. 2019; Hugging Face ``GPT2Model``):
pre-norm blocks ``x + attn(ln1(x))``, ``x + mlp(ln2(x))``, learned position
embeddings, a fused QKV projection split in thirds and then into heads, a
4x MLP, LayerNorm eps 1e-5. Departures are listed in the configuration files
under ``assumed``: an untied output head, and the exact (erf) GELU.

Leaf names are this file's own: ``wte wpe lnf_g lnf_b head_w`` and, per
layer, ``ln1_g ln1_b qkv_w qkv_b proj_w proj_b ln2_g ln2_b fc_w fc_b fc2_w
fc2_b``. Dense weights are (out, in): ``y = x @ w.T + b``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                "ln2_g", "ln2_b", "fc_w", "fc_b", "fc2_w", "fc2_b")
GLOBAL_LEAVES = ("wte", "wpe", "lnf_g", "lnf_b", "head_w")
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(model: dict) -> dict:
    c = int(model["n_embd"])
    return {"C": c, "L": int(model["n_layer"]), "H": int(model["n_head"]),
            "V": int(model["vocab_size"]), "T": int(model["n_positions"]),
            "F": int(model.get("n_inner") or 4 * c)}


def layer_shapes(model: dict) -> dict:
    s = sizes(model)
    c, f = s["C"], s["F"]
    return {"ln1_g": (c,), "ln1_b": (c,), "qkv_w": (3 * c, c),
            "qkv_b": (3 * c,), "proj_w": (c, c), "proj_b": (c,),
            "ln2_g": (c,), "ln2_b": (c,), "fc_w": (f, c), "fc_b": (f,),
            "fc2_w": (c, f), "fc2_b": (c,)}


def global_shapes(model: dict) -> dict:
    s = sizes(model)
    return {"wte": (s["V"], s["C"]), "wpe": (s["T"], s["C"]),
            "lnf_g": (s["C"],), "lnf_b": (s["C"],),
            "head_w": (s["V"], s["C"])}


def root_key(seed: int):
    """--seed may pass 2**31: fold it in as two 31-bit halves."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, name: str, shape, n_layer: int):
    """One leaf in float32: gains 1 + N(0, .02), everything else
    N(0, .02), the two residual projections scaled by 1/sqrt(2L) as GPT-2
    initialises them."""
    x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_g"):
        return 1.0 + x
    if name in ("proj_w", "fc2_w"):
        return x / math.sqrt(2.0 * n_layer)
    return x


def draw_layer(model: dict, key, i):
    """The leaves of layer ``i`` (``i`` may be traced, under ``vmap``)."""
    shapes = layer_shapes(model)
    k = jax.random.fold_in(key, i + 1)
    return {n: _draw(jax.random.fold_in(k, j), n, shapes[n],
                     int(model["n_layer"]))
            for j, n in enumerate(LAYER_LEAVES)}


def draw_globals(model: dict, key):
    shapes = global_shapes(model)
    k = jax.random.fold_in(key, 0)
    return {n: _draw(jax.random.fold_in(k, j), n, shapes[n],
                     int(model["n_layer"]))
            for j, n in enumerate(GLOBAL_LEAVES)}


def draw_all(model: dict, seed: int, dtype: str):
    """Every leaf on the device in ONE jitted call, in the type it is
    served or trained in: ``(globals, [layer 0, layer 1, ...])``."""
    n_layer = int(model["n_layer"])

    @jax.jit
    def make(key):
        g = draw_globals(model, key)
        stacked = jax.vmap(lambda i: draw_layer(model, key, i))(
            jnp.arange(n_layer))
        cast = lambda t: jax.tree_util.tree_map(
            lambda a: a.astype(dtype), t)
        return cast(g), [cast({n: a[i] for n, a in stacked.items()})
                         for i in range(n_layer)]

    return make(root_key(seed))


def _through(dtype: str):
    """The numbers the program holds: rounded to its type, read as f32."""
    return lambda a: a.astype(dtype).astype(jnp.float32)


# -- the block ---------------------------------------------------------------

def layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def dense(x, w, b=None, mm=None):
    y = (mm or _mm_f32)(x, w)
    return y if b is None else y + b


def _mm_f32(x, w):
    return jnp.einsum("...i,oi->...o", x, w, precision=HIGHEST)


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def block(p, x, n_head: int, mm=None, rnd=None):
    """x (B, T, C) float32 -> (B, T, C); full causal attention. ``mm``
    does the dense products (float32 at ``highest`` by default); ``rnd``,
    where given, rounds every tensor a program would hold between two
    operations (LayerNorm, softmax and GELU stay float32 inside)."""
    rnd = rnd or (lambda a: a)
    b, t, c = x.shape
    d = c // n_head
    h = rnd(layer_norm(x, p["ln1_g"], p["ln1_b"]))
    qkv = rnd(dense(h, p["qkv_w"], p["qkv_b"], mm))
    q, k, v = (a.reshape(b, t, n_head, d).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    s = s / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    w = rnd(jax.nn.softmax(s, axis=-1))
    a = rnd(jnp.einsum("bhqk,bhkd->bhqd", w, v, precision=HIGHEST))
    a = a.transpose(0, 2, 1, 3).reshape(b, t, c)
    x = rnd(x + rnd(dense(a, p["proj_w"], p["proj_b"], mm)))
    h = rnd(layer_norm(x, p["ln2_g"], p["ln2_b"]))
    h = rnd(gelu(rnd(dense(h, p["fc_w"], p["fc_b"], mm))))
    return rnd(x + rnd(dense(h, p["fc2_w"], p["fc2_b"], mm)))


def embed(g, tokens):
    t = tokens.shape[-1]
    return g["wte"][tokens] + g["wpe"][jnp.arange(t)]


def head(g, x, mm=None, rnd=None):
    rnd = rnd or (lambda a: a)
    return rnd(dense(rnd(layer_norm(x, g["lnf_g"], g["lnf_b"])),
                     g["head_w"], None, mm))


def forward(g, layers, tokens, n_head: int):
    """tokens (B, T) -> logits (B, T, V), all of it at once (small sizes)."""
    x = embed(g, tokens)
    for p in layers:
        x = block(p, x, n_head)
    return head(g, x)


# -- lower precisions: the stated type computed plainly, and the steps below it

def _mm_low(x, w):
    """Operands that bfloat16 holds exactly (bfloat16 itself, int8, float8)
    multiplied on the matrix unit and summed in float32."""
    return jnp.einsum("...i,oi->...o", x.astype(jnp.bfloat16),
                      w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _q8(a, axis):
    """Symmetric int8 with one scale per row along ``axis``."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(a / s), -127, 127), s


def _mm_int8(x, w, dot=_mm_f32):
    """The step below bfloat16: weights int8 per output channel,
    activations int8 per token, products summed in float32."""
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w, -1)
    return dot(xq, wq) * xs * ws[:, 0]


def _mm_fp8(x, w, dot=_mm_f32):
    """The other step below bfloat16: both operands rounded to float8
    (e4m3, 3 bits of mantissa) with one scale per row."""
    def q(a):
        s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s

    xq, xs = q(x)
    wq, ws = q(w)
    return dot(xq, wq) * xs * ws[:, 0]


# in serving the quantised operands go through the matrix unit in one pass
CONTROLS = {"int8": functools.partial(_mm_int8, dot=_mm_low),
            "fp8": functools.partial(_mm_fp8, dot=_mm_low)}


def _kit(dtype: str, mode: str):
    """``(mm, rnd)`` of a mode. ``"f32"`` is the reference. ``"stated"`` is
    the same block as a plain program of the configuration's type computes
    it: every tensor between two operations rounded to the type, products
    of the type's operands summed in float32. A control is ``"stated"``
    with its dense products a step lower."""
    if mode == "f32":
        return None, None
    if dtype != "bfloat16":
        raise ValueError(f"no lower-precision path for a {dtype} model")
    return (_mm_low if mode == "stated" else CONTROLS[mode]), _through(dtype)


# -- serving: whole sequences, layer by layer, every mode at once ------------

@functools.lru_cache(maxsize=None)
def _seq_fns(model_key, dtype: str, modes: tuple):
    model = dict(model_key)
    n_head = int(model["n_head"])
    through = _through(dtype)
    kits = [_kit(dtype, m) for m in modes]
    same = lambda a: a

    @jax.jit
    def globals_(key):
        return jax.tree_util.tree_map(through, draw_globals(model, key))

    @jax.jit
    def weights(key, i):
        return jax.tree_util.tree_map(through, draw_layer(model, key, i))

    @jax.jit
    def start(g, tokens):
        x = embed(g, tokens)
        return tuple((rnd or same)(x) for _, rnd in kits)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def layer(p, xs):
        return tuple(block(p, x, n_head, mm, rnd)
                     for x, (mm, rnd) in zip(xs, kits))

    @jax.jit
    def finish(g, xs, at):
        """Logits (R, M, V) of each mode at the positions ``at`` (R, M)."""
        rows = jnp.arange(at.shape[0])[:, None]
        return tuple(head(g, x[rows, at], mm, rnd)
                     for x, (mm, rnd) in zip(xs, kits))

    return globals_, weights, start, layer, finish


def logits_at(model: dict, seed: int, dtype: str, blocks, at,
              modes=("f32",)):
    """For each block of rows ``blocks[b]`` (R, T) int32 and positions
    ``at[b]`` (R, M): the logits (R, M, V) float32 of every mode there, with
    the weights of ``seed`` as the program holds them. Yields one tuple
    (a mode each) per block. A layer's weights are drawn once and live one
    layer at a time; every block's activations stay on the device."""
    globals_, weights, start, layer, finish = _seq_fns(
        tuple(sorted(model.items())), dtype, tuple(modes))
    key = root_key(seed)
    g = globals_(key)
    xs = [start(g, jnp.asarray(t, jnp.int32)) for t in blocks]
    for i in range(int(model["n_layer"])):
        p = weights(key, jnp.int32(i))
        xs = [layer(p, x) for x in xs]
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
                modes=(), rows_per_block: int = 8) -> dict:
    """How far below the float32 reference's best logit the tokens lie, at
    every position that predicted a served token of ``samples`` (pairs
    ``(prompt, served_tokens)``). Flat float arrays under ``"served"`` (the
    tokens that were served), under each of ``modes`` (the token that mode
    puts first there, read at the same prompts and tokens), and under
    ``"margin"`` (the reference's best less its second best).

    Rows are padded to ``pad_to`` (causal attention never looks right, so
    padding changes nothing left of it) and go in blocks of
    ``rows_per_block``; the head is computed at the served positions only.
    """
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


# -- training: loss, gradients and AdamW, rows in blocks ----------------------

@jax.custom_vjp
def _mm_int8_train(x, w):
    return _mm_int8(x, w)


def _mm_int8_train_fwd(x, w):
    return _mm_int8(x, w), (x, w)


def _mm_int8_train_bwd(res, dy):
    """Both products of the backward pass in int8 too, as int8 training
    does them: dx = q(dy) q(w), dw = q(dy)^T q(x)."""
    x, w = res
    dx = _mm_int8(dy, w.T)
    x2 = x.reshape(-1, x.shape[-1])
    dy2 = dy.reshape(-1, dy.shape[-1])
    return dx, _mm_int8(dy2.T, x2.T)


_mm_int8_train.defvjp(_mm_int8_train_fwd, _mm_int8_train_bwd)


def draw_stacked(model: dict, key):
    """``(globals, layers stacked on a leading axis)`` in float32."""
    stacked = jax.vmap(lambda i: draw_layer(model, key, i))(
        jnp.arange(int(model["n_layer"])))
    return draw_globals(model, key), stacked


def lm_loss_sum(params, tokens, labels, n_head: int, mm=None):
    """Summed next-token cross-entropy of a block of rows. The layers are
    scanned and rematerialised, so a block's float32 activations fit."""
    g, stacked = params
    body = jax.checkpoint(lambda x, p: (block(p, x, n_head, mm), None))
    x, _ = jax.lax.scan(body, embed(g, tokens), stacked)
    logits = head(g, x, mm)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def adamw_update(p, g, m, v, step, lr, b1, b2, eps, wd):
    """optax.adamw's arithmetic for one leaf, in float32."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** step)
    vh = v / (1 - b2 ** step)
    return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), m, v


class TrainReference:
    """Follows the first steps of training in float32: the mean loss over
    the batch's rows, its gradient, AdamW. Rows go through in blocks of
    ``rows_per_block`` and their gradients are summed. ``control`` runs
    every matrix product, forward and backward, in int8; ``rows`` keeps
    only that many rows of each batch (a planted fault, for the tests)."""

    def __init__(self, model: dict, seed: int, param_dtype: str, opt: dict,
                 rows_per_block: int = 2, control: bool = False,
                 rows: int = 0):
        n_head = int(model["n_head"])
        through = _through(param_dtype)
        mm = _mm_int8_train if control else None
        self.block_rows, self.keep_rows = int(rows_per_block), int(rows)
        self.steps = 0

        @jax.jit
        def init(key):
            return jax.tree_util.tree_map(through, draw_stacked(model, key))

        self.params = init(root_key(seed))
        self.start = jax.tree_util.tree_map(jnp.copy, self.params)
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, self.params)
        self.m, self.v = zeros(), zeros()

        @jax.jit
        def grad_block(params, tokens, labels):
            return jax.value_and_grad(lm_loss_sum)(params, tokens, labels,
                                                   n_head, mm)

        @jax.jit
        def add(a, b):
            return jax.tree_util.tree_map(jnp.add, a, b)

        @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
        def update(params, grads, m, v, step):
            leaves, tree = jax.tree_util.tree_flatten(params)
            out = [adamw_update(p, g, m_, v_, step, opt["learning_rate"],
                                opt["beta1"], opt["beta2"], opt["epsilon"],
                                opt["wd"])
                   for p, g, m_, v_ in zip(
                       leaves, tree.flatten_up_to(grads),
                       tree.flatten_up_to(m), tree.flatten_up_to(v))]
            return tuple(tree.unflatten([o[i] for o in out])
                         for i in range(3))

        self._grad_block, self._add, self._update = grad_block, add, update

    def step(self, tokens, labels):
        """One optimizer step; returns ``(mean loss, mean gradient)``."""
        tokens = np.asarray(tokens, np.int32)
        labels = np.asarray(labels, np.int32)
        if self.keep_rows:
            tokens, labels = tokens[:self.keep_rows], labels[:self.keep_rows]
        total, grads = 0.0, None
        for r in range(0, tokens.shape[0], self.block_rows):
            l, g = self._grad_block(self.params,
                                    tokens[r:r + self.block_rows],
                                    labels[r:r + self.block_rows])
            total += float(l)
            grads = g if grads is None else self._add(grads, g)
        scale = 1.0 / tokens.size
        grads = jax.tree_util.tree_map(lambda a: a * scale, grads)
        self.steps += 1
        self.params, self.m, self.v = self._update(
            self.params, grads, self.m, self.v, float(self.steps))
        return total * scale, grads

    def change(self):
        """Parameters now less parameters at the start."""
        return jax.tree_util.tree_map(jnp.subtract, self.params, self.start)


def flatten_leaves(tree_globals, tree_layers) -> dict:
    """``{"wte": a, ..., "h0.qkv_w": a, ...}``: this file's leaf names.
    ``tree_layers`` is a list of layers or one dict stacked on axis 0."""
    out = dict(tree_globals)
    if isinstance(tree_layers, dict):
        n = next(iter(tree_layers.values())).shape[0]
        tree_layers = [{k: a[i] for k, a in tree_layers.items()}
                       for i in range(n)]
    for i, p in enumerate(tree_layers):
        out.update({f"h{i}.{n}": a for n, a in p.items()})
    return out


def compare_leaves(flat: dict) -> dict:
    """The leaves as they are compared: the fused QKV weight and bias in
    their three thirds, because the key's bias has no gradient under
    softmax and would hide in a leaf it shares."""
    out = {}
    for name, a in flat.items():
        if name.endswith(("qkv_w", "qkv_b")):
            for part, piece in zip("qkv", jnp.split(a, 3, axis=0)):
                out[f"{name}.{part}"] = piece
        else:
            out[name] = a
    return out


@jax.jit
def _norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for n, a in tree.items()}


def leaf_norms(flat: dict) -> dict:
    """Euclidean norm of every compared leaf of ``{name: array}``."""
    return {n: float(x) for n, x in _norms(compare_leaves(flat)).items()}


def worst_leaf_gap(got: dict, want: dict, skip=()) -> tuple:
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf or
    of the median leaf, whichever is larger. ``(gap, leaf)``."""
    names = [n for n in want if n not in skip]
    med = float(np.median([want[n] for n in names]))
    worst, at = 0.0, ""
    for n in names:
        gap = abs(got[n] - want[n]) / max(want[n], med)
        if not gap <= worst:        # a NaN is the worst there is
            worst, at = gap, n
    return worst, at
