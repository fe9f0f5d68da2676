"""The slot K/V cache's rows: where one lies, how a decode step reads,
attends and writes it, and how a prefilled plane joins a slot. Every rule
of ``serving.KVCache``'s arrays ``[L, S, H, rows, W]`` is here once; the
served blocks (``gluon/model_zoo/gpt.py``, ``decoder.py``) and the join of
``serving/decode.py`` call it. Plain ``jax.numpy`` over arrays, but for
the one kernel ``attend_row`` takes on the TPU (``ops/pallas_decode.py``).

A group of layers is ``"full"`` (position ``p`` at row ``p``), a
``"ring"`` (the last ``rows`` positions, ``p`` at row ``p mod rows``) or
``"latent"``: addressed as ``full``, but ONE tensor a layer and not a K/V
pair (``tensors``): a row is read as the key and its first ``v_width``
lanes as the value (latent attention's compressed row ``[c | k_r]``, over
which every query head attends: ``H`` 1, ``G`` the heads). A
step never writes before it reads: each layer attends over its plane with
the new token's row SELECTED in, and after the last layer all layers'
rows go into the donated cache, which XLA then updates where it lies. The
rule's plain statement is ``read`` + ``attend`` (a static leading-axis
slice and a ``where``, which fuse into the attention: what a
write-then-read would see, bit for bit); ``attend_row`` is the step's one
entry and, where ``blocked`` says so (a full or latent group of more than
one block of rows) and the step is lowered for the TPU, fetches only the
blocks below each slot's ``cache_len`` and joins the new row in the
kernel instead.

A fourth kind, ``"state"``, has no rows: what a recurrent layer carries from
one position to the next, the same size at every length (a selective
state-space layer's, ``ops/state_space.py``). A layer keeps two tensors
``[L, S, n, E]`` (``layout``): the scan's ``h`` (``n`` = ``state``, the
group's own ``dtype``: it is summed into at every step) and the
convolution's last inputs (``n`` = ``taps``, the cache's type), ``E``
minor. A step REPLACES a slot's state where a row group appends a row:
each layer reads its own and writes the new one in its place in the
donated array (``advance``), and a prefill's state replaces a slot's whole
(``join``, whatever the prompt's length: a freed slot leaves nothing
behind). ``address`` / ``read`` / ``attend`` / ``write`` do not apply.

The stored row: the TPU keeps a minor dimension of whole 128-lane tiles
minor, so a new row is few tiles; a minor dimension of 64 it laid out
``T``-minor, a hundred tiles a new row (PERF.md PR 29). Heads narrower
than ``LANES`` therefore lie ``g = pack(head_dim)`` side by side in a row
of ``W = g * D``, ``ceil(H / g)`` rows a position, what is left of the
last one zero. The minor ``W`` is never reshaped (on a tiled plane that
is a relayout); ``attend`` takes the ``g`` heads of a row as ``g`` queries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import pallas_decode

#: lanes of a TPU tile: a stored K/V row is a whole number of them
LANES = 128
#: rows of a block where a plane is read by blocks of live rows
BLOCK = pallas_decode.BLOCK


def tensors(kind):
    """Arrays a group of ``kind`` keeps a layer: K and V, the one tensor
    of a ``latent`` group that is read as both, or a ``state`` group's
    two (the scan's state, the convolution's taps)."""
    return 1 if kind == "latent" else 2


def layout(group, slots, dtype):
    """``(shape, dtype)`` of each array a declared ``group`` keeps for
    ``slots`` slots in a cache of type ``dtype``: a row group's
    ``[L, S, H, rows, W]`` K and V (or one), a ``state`` group's
    ``[L, S, state, E]`` in its own ``dtype`` and ``[L, S, taps, E]``."""
    layers, dtype = int(group["layers"]), jnp.dtype(dtype)
    if group["kind"] == "state":
        width = int(group["width"])
        return [((layers, slots, int(group["state"]), width),
                 jnp.dtype(group["dtype"])),
                ((layers, slots, int(group["taps"]), width), dtype)]
    shape = (layers, slots, int(group["heads"]), int(group["rows"]),
             int(group["head_dim"]))
    return [(shape, dtype)] * tensors(group["kind"])


def plane_shape(shape, kind, bucket):
    """What a prefill of ``bucket`` positions hands the join for a cache
    array of ``shape``: ``[L, H, bucket, W]``, or a ``state`` array's one
    slot ``[L, n, E]`` (it has no position axis)."""
    if kind == "state":
        return (shape[0],) + tuple(shape[2:])
    return (shape[0], shape[2], bucket, shape[4])


def pack(head_dim):
    """Heads side by side in one stored row: as many as fill ``LANES`` (2
    for GPT-2's 64), and 1 (a row is a head) where a head is that wide
    already or does not divide it."""
    return LANES // head_dim \
        if head_dim < LANES and LANES % head_dim == 0 else 1


def whole_tiles(width):
    """The lanes a row of ``width`` values is stored at: whole 128-lane
    tiles, the rest zero (a latent row of 512 + 64 is kept 640 wide; at
    576 the TPU lays the cache rows-minor and copies every plane)."""
    return -(-width // LANES) * LANES


def store_rows(x, heads, g):
    """K, V or Q as a fused product gives it, ``x`` (B, T, H*D), in the
    stored form (B, P, T, W): one pad, one reshape of the product's minor
    ``H*D`` (not a cache's) and one transpose."""
    b, t, c = x.shape
    w = c // heads * g
    p = -(-heads // g)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, p * w - c)))
    return x.reshape(b, t, p, w).transpose(0, 2, 1, 3)


def address(cache_len, rows, kind):
    """Once per group and step, from ``cache_len`` (S,), the tokens each
    slot has cached: ``row`` (S,) where the new token's row lies, ``here``
    (S, rows) the one-hot of it, ``see`` (S, rows) the rows the slot may
    read with its new token (the first ``min(cache_len + 1, rows)``). A
    freed slot's stale ``cache_len`` of ``rows`` lands on its own last
    row."""
    n = cache_len.astype(jnp.int32)
    row = n % rows if kind == "ring" else jnp.clip(n, 0, rows - 1)
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    return row, r == row[:, None], r < jnp.minimum(n + 1, rows)[:, None]


def read(cache, layer, new, here):
    """Layer ``layer``'s plane (S, H, rows, W) of the stacked ``cache``
    with each slot's ``new`` row (S, H, 1, W) in place, without writing
    it."""
    return jnp.where(here[:, None, :, None], new, cache[layer])


def _heads_of_a_row(fn, q, w, head_dim):
    """``fn`` (queries (S, H, G, W) -> the same shape) over ``q``: as it
    is where a stored row is one head. Where a row holds
    ``g = w // head_dim > 1`` heads, ``q`` is one stored row (S, H, 1, W)
    and its ``g`` heads are the queries, each zero outside its own ``D``
    lanes: the other lanes hold another head's finite values or the pad's
    zeros and add exact zeros to a score, and of an output row each head
    keeps its own lanes."""
    g = w // head_dim
    if g == 1:
        return fn(q)
    own = jnp.arange(w, dtype=jnp.int32)[None, :] // head_dim \
        == jnp.arange(g, dtype=jnp.int32)[:, None]                # (g, W)
    return jnp.where(own, fn(jnp.where(own, q, 0)), 0).sum(
        axis=2, keepdims=True)


def attend(q, k, v, see, head_dim, scale=None):
    """One token's attention over planes ``k``/``v`` (S, H, rows, W) under
    the mask ``see``: float32 scores scaled by ``scale`` (``head_dim **
    -0.5`` where none is given), float32 softmax cast to the values' type.
    ``q`` is (S, H, G, W), ``G`` queries a K/V head, or one stored row of
    several heads (``_heads_of_a_row``, which does ``g`` times the useful
    work inside a fusion that waits on the plane's bytes); returns ``q``'s
    shape, at ``v``'s width where ``v`` is narrower than ``k``."""
    scale = 1.0 / head_dim ** 0.5 if scale is None else scale

    def dense(q):
        sc = jnp.einsum("shgd,shtd->shgt", q, k,
                        preferred_element_type=jnp.float32)
        sc = jnp.where(see[:, None, None, :], sc * scale, -jnp.inf)
        return jnp.einsum("shgt,shtd->shgd",
                          jax.nn.softmax(sc, axis=-1).astype(v.dtype), v)

    return _heads_of_a_row(dense, q, k.shape[-1], head_dim)


def blocked(rows, kind):
    """Whether a step's attention over a plane of ``rows``, lowered for
    the TPU, goes by blocks of live rows (``pallas_decode``): a full or
    latent group of more than one block. A ring is read whole (its rows
    are all live once it has wrapped, and few), as is a plane of one
    block or less, or of no whole number of blocks (the kernel's copies
    start on a block's edge)."""
    return kind != "ring" and rows > BLOCK and rows % BLOCK == 0


def fetched_rows(cache_len, rows, by_blocks):
    """On the host, the rows ``attend_row`` reads for each slot of
    ``cache_len`` (numpy) over one plane of ``rows``: every row where the
    plane is read whole; ``by_blocks``, the whole blocks that hold a
    cached row and the new token's row (which comes from the step, not
    the cache), so never fewer than the slot's ``cache_len + 1`` live
    rows."""
    if not by_blocks:
        return np.full(cache_len.shape, rows, np.int64)
    return -(-pallas_decode.cached_rows(cache_len, rows) // BLOCK) * BLOCK + 1


def attend_blocks(q, k_cache, v_cache, layer, k_new, v_new, cache_len,
                  head_dim, scale=None, v_width=None, interpret=None):
    """``attend_row`` by blocks of live rows: ``pallas_decode.attend``
    over the heads of a stored row (``interpret`` is the kernel's)."""
    return _heads_of_a_row(
        lambda q: pallas_decode.attend(q, k_cache, v_cache, layer, k_new,
                                       v_new, cache_len, head_dim,
                                       scale=scale, v_width=v_width,
                                       interpret=interpret),
        q, k_cache.shape[-1], head_dim)


def attend_row(q, k_cache, v_cache, layer, k_new, v_new, cache_len, at,
               kind, head_dim, scale=None, v_width=None):
    """Layer ``layer``'s one-token attention over the stacked caches
    (L, S, H, rows, W) with the new token's rows ``k_new``/``v_new``
    (S, H, 1, W) where ``at`` (the group's ``address`` of ``cache_len``)
    puts them: what writing the rows and then ``attend`` over the plane
    gives, to float tolerance, without the write. ``q`` as ``attend``
    takes it. A ``latent`` group has no ``v_cache``/``v_new`` (None): the
    values are the first ``v_width`` lanes of the keys, and ``head_dim``
    is the row's whole width (a row is one head). ``read`` + ``attend``
    is the rule's plain statement and the path of every plane that is not
    ``blocked`` and of every platform but the TPU; a ``blocked`` plane
    lowered for the TPU goes by blocks of live rows (the platform is the
    lowering's to know, not the host's default backend)."""
    _, here, see = at

    def dense(q, cache_len, k_cache, k_new, v_cache=None, v_new=None):
        k = read(k_cache, layer, k_new, here)
        v = k[..., :v_width] if v_cache is None \
            else read(v_cache, layer, v_new, here)
        return attend(q, k, v, see, head_dim, scale)

    def by_blocks(q, cache_len, k_cache, k_new, v_cache=None, v_new=None):
        return attend_blocks(q, k_cache, v_cache, layer, k_new, v_new,
                             cache_len, head_dim, scale, v_width,
                             interpret=False)

    operands = (q, cache_len, k_cache, k_new) \
        + (() if v_cache is None else (v_cache, v_new))
    if not blocked(k_cache.shape[3], kind):
        return dense(*operands)
    return jax.lax.platform_dependent(*operands, tpu=by_blocks,
                                      default=dense)


def write(cache, new, row):
    """All layers' ``new`` rows (``L`` of (S, H, 1, W)) into ``cache``
    (L, S, H, rows, W) at ``row`` (S,): one ``dynamic_update_slice`` a
    slot, slot static, chained on the whole cache. They are the cache's
    only writers in a step, so nothing of its or a plane's shape is built
    beside it. (On the v5e an update's time goes by tiles touched, not by
    calls; a scatter, which a vmapped update also lowers to, makes the TPU
    compiler relayout its whole operand: PERF.md PR 26.)"""
    u = jnp.stack(new, axis=0)                          # (L, S, H, 1, W)
    for s in range(cache.shape[1]):
        cache = jax.lax.dynamic_update_slice(cache, u[:, s:s + 1],
                                             (0, s, 0, row[s], 0))
    return cache


def advance(cache, layer, new):
    """Layer ``layer``'s ``new`` state (S, n, E) in the place of the one
    it read from the stacked ``cache`` (L, S, n, E): one static
    ``dynamic_update_slice`` on the donated array. A layer reads
    ``cache[layer]`` of the array the layer before it handed on and
    nothing reads that slice again, so XLA updates the array where it
    lies: a step reads the state once and writes it once."""
    return jax.lax.dynamic_update_slice(cache, new[None].astype(cache.dtype),
                                        (layer, 0, 0, 0))


def join(cache, plane, slot, n, kind):
    """A prompt's prefilled ``plane`` (L, H, T, W) into slot ``slot``
    (traced) of ``cache``: a full or latent group from row 0; a ring the
    last ``rows`` positions below the TRUE length ``n`` (traced), each at
    ``position mod rows`` (rows no position below ``n`` maps to hold
    garbage that ``see`` masks). A ``state`` group's ``plane`` (L, n, E) is
    the state after position ``n - 1`` already and replaces the slot's."""
    if kind == "state":
        return jax.lax.dynamic_update_slice(
            cache, plane[:, None].astype(cache.dtype), (0, slot, 0, 0))
    if kind == "ring":
        rows = cache.shape[3]
        r = jnp.arange(rows, dtype=jnp.int32)
        p = n - 1 - (n - 1 - r) % rows
        plane = jnp.take(plane, jnp.clip(p, 0, plane.shape[2] - 1), axis=2)
    return jax.lax.dynamic_update_slice(cache, plane[:, None],
                                        (0, slot, 0, 0, 0))
