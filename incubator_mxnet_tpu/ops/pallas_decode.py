"""One token's attention over a slot K/V cache that fetches each slot's
LIVE rows and no others: the Pallas kernel behind
``ops/kv_cache.py::attend_row`` (which owns the rule and chooses between
this and the dense ``read`` + ``attend``).

The stacked cache ``[L, S, H, rows, W]`` stays in HBM and is handed over
whole; the kernel indexes the layer itself (a ``cache[layer]`` ahead of a
custom call would be a copy of the plane). Rows go by blocks of
``BLOCK``: the caller's ``cache_len`` (S,) becomes a flat list
of the (slot, block) pairs that hold a cached position, in slot order,
and one loop walks it with the next pair's K and V blocks (H, block, W)
in flight while this pair's are computed, across slot boundaries, so the
memory never waits for a grid step. Blocks at or above
``ceil(cache_len / block)`` are neither fetched nor computed; rows at or
above ``cache_len`` inside a slot's last block are masked.

The new token's row is never read from the cache: its score and value
(``k_new``/``v_new``) are the online softmax's first state (maximum the
new row's score, sum 1, accumulator its value), which is what a
write-then-read sees. A slot of no cached row (a free slot) is that state
alone. Precisions are the dense path's: float32 scores scaled by
``head_dim ** -0.5`` (or the caller's ``scale``), a running float32
maximum and sum, probabilities cast to the values' type before the
product, float32 accumulation.

A group of ONE tensor (``kv_cache``'s ``latent`` kind: no ``v_cache``) is
the same algorithm adapted by shape: one block is fetched a pair and its
first ``v_width`` lanes are the values, so the compressed row is read once
for both products, by all the ``G`` query heads that share it.

Off the TPU the same kernel runs through the Pallas interpreter
(``interpret=True``), which the tests use; the served step takes the
dense path there (``kv_cache.attend_row``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_attention

#: rows of a block, of which a slot fetches half dead at the mean: the
#: smallest tried, as fast a byte as 512 at both served shapes, 8 and 13
#: stored rows a slot (PERF.md PR 31)
BLOCK = 128
#: queries a K/V row are padded to whole sublane tiles of this many
_SUBLANES = 8


def cached_rows(cache_len, rows):
    """Cached rows a slot's new token reads beside its own: ``cache_len``,
    and ``rows - 1`` for a stale or full slot, whose new row stands in
    the last one (``kv_cache.address``). Arrays of numpy or jax alike."""
    return cache_len.clip(0, rows - 1)


def _plan(cache_len, rows):
    """``cache_len`` (S,) -> what the kernel prefetches as scalars: the
    cached rows a slot, the number of (slot, block) pairs, and each
    pair's slot and block."""
    n = cached_rows(cache_len.astype(jnp.int32), rows)
    blocks = (n + BLOCK - 1) // BLOCK
    ends = jnp.cumsum(blocks)
    t = jnp.arange(cache_len.shape[0] * (rows // BLOCK), dtype=jnp.int32)
    slot = jnp.minimum((t[:, None] >= ends[None, :]).sum(axis=1),
                       cache_len.shape[0] - 1).astype(jnp.int32)
    return n, ends[-1:], slot, t - (ends - blocks)[slot]


def _kernel(layer_ref, total_ref, n_ref, slot_ref, block_ref,
            q_ref, kn_ref, vn_ref, *refs, scale, precision, v_width):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # K and V planes with their buffers, or the one plane read as both
    planes = len(refs) // 2 - 1
    o_ref, sem = refs[planes], refs[-1]
    fetched = list(zip(refs[:planes], refs[planes + 1:-1]))
    layer, total = layer_ref[0], total_ref[0]
    h, g = q_ref.shape[1:3]
    w = o_ref.shape[-1]

    def copies(t, buf):
        rows = pl.ds(pl.multiple_of(block_ref[t] * BLOCK, BLOCK), BLOCK)
        return [pltpu.make_async_copy(
            hbm.at[layer, slot_ref[t], :, rows, :], vmem.at[buf],
            sem.at[i, buf])
            for i, (hbm, vmem) in enumerate(fetched)]

    # a slot of no cached row attends its new row alone
    o_ref[...] = jnp.broadcast_to(vn_ref[...], o_ref.shape)

    @pl.when(total > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def body(t, carry):
        buf = t % 2
        slot, j = slot_ref[t], block_ref[t]
        n = n_ref[slot]

        @pl.when(t + 1 < total)
        def _():
            for c in copies(t + 1, 1 - buf):
                c.start()

        q = q_ref[slot]                                      # (H, G, W)
        # a slot's first block starts from the new row's state
        first = j == 0
        s_new = jnp.sum(q.astype(jnp.float32)
                        * kn_ref[slot].astype(jnp.float32),
                        axis=-1, keepdims=True) * scale
        m, l, acc = (jnp.where(first, a, b) for a, b in zip(
            (s_new, jnp.ones_like(s_new),
             jnp.broadcast_to(vn_ref[slot].astype(jnp.float32),
                              (h, g, w))), carry))
        for c in copies(t, buf):
            c.wait()
        k = fetched[0][1][buf]                              # (H, block, W)
        v = k[:, :, :v_width] if v_width else fetched[1][1][buf]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision) * scale
        at = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(at < n, s, -jnp.inf)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m2)
        p = jnp.exp(s - m2)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision)

        @pl.when(j == (n - 1) // BLOCK)
        def _():
            o_ref[slot] = (acc / l).astype(o_ref.dtype)

        return m2, l, acc

    zero = jnp.zeros((h, g, 1), jnp.float32)
    jax.lax.fori_loop(0, total, body,
                      (zero, zero, jnp.zeros((h, g, w), jnp.float32)))


def attend(q, k_cache, v_cache, layer, k_new, v_new, cache_len, head_dim,
           scale=None, v_width=None, interpret=None):
    """Layer ``layer``'s one-token attention: ``q`` (S, H, G, W), ``G``
    queries a stored K/V row; ``k_cache``/``v_cache`` the stacked
    (L, S, H, rows, W); ``k_new``/``v_new`` (S, H, 1, W) the new token's
    rows; ``cache_len`` (S,). Returns (S, H, G, W) in the values' type:
    softmax over the slot's cached rows and its new row. With no
    ``v_cache`` (and no ``v_new``) the values are the first ``v_width``
    lanes of the keys, and the result is that wide."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, h, g, w = q.shape
    rows = k_cache.shape[3]
    if v_cache is None:
        v_new, caches = k_new[..., :v_width], (k_cache,)
    else:
        v_width, caches = None, (k_cache, v_cache)
    w_out = v_new.shape[-1]
    if interpret is None:
        interpret = not pallas_attention.pallas_available()
    gp = -(-g // _SUBLANES) * _SUBLANES
    q = jnp.pad(q, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    n, total, slot_of, block_of = _plan(cache_len, rows)
    # the package's ambient ``highest`` is refused for 16-bit products
    precision = (jax.lax.Precision.DEFAULT if k_cache.dtype.itemsize < 4
                 else jax.lax.Precision.HIGHEST)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=head_dim ** -0.5 if scale is None else scale,
            precision=precision, v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(1,),
            in_specs=[whole, whole, whole]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in caches],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, h, BLOCK, w), c.dtype)
                            for c in caches]
            + [pltpu.SemaphoreType.DMA((len(caches), 2))]),
        out_shape=jax.ShapeDtypeStruct((s, h, gp, w_out), k_cache.dtype),
        # the blocks in flight (two a plane), and room for the rest
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=(16 << 20)
            + 4 * h * BLOCK * w * k_cache.dtype.itemsize),
        interpret=interpret, name="kv_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), total, n, slot_of,
      block_of, q, k_new, v_new, *caches)
    return out[:, :, :g]
