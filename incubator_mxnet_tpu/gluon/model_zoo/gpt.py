"""GPT-style autoregressive decoder — the sixth workload (ISSUE 12).

A pre-norm decoder-only transformer (GPT-2 convention: LayerNorm before
attention/FFN, learned position embeddings, untied LM head) built from
the same gluon blocks as the BERT encoder (``models/transformer.py``)
but wired for BOTH halves of the decoder-LLM story:

* **Training**: ``forward(tokens) -> logits`` is a plain causal
  full-sequence pass; attention routes through ``flash_attention``
  (size-dispatched: XLA dense below the measured Pallas crossover, the
  streaming Pallas kernels above it), so the same config trains under
  ``SPMDTrainer`` + SuperStep + the ZeRO ladder like every other
  workload.
* **Serving**: ``prefill`` additionally returns the per-layer K/V planes
  so a serving tier can seed a device-resident KV cache, and
  ``decode_step`` advances EVERY slot of the cache by one token and
  updates the cache WHERE IT LIES. The cache is kept in the STORED form
  ``[L, S, P, T, W]``: ``g = 128 // D`` heads lie side by side in one
  row of ``W = g * D`` lanes (``_kv_pack``; two heads of 64 for every
  GPT-2 width), ``P = ceil(H / g)`` such rows a position, the last one
  zero-padded where ``g`` does not divide ``H``. A row of whole
  128-lane tiles is what the TPU keeps minor, so a new row is ``P``
  tiles; a minor dimension of 64 it laid out ``T``-minor, ``4 * H``
  tiles a new row (PERF.md PR 29). Layer
  ``i``'s attention reads plane ``cache[i]`` (a static leading-axis
  slice) with the new token's K/V row selected in at
  ``cache_len[slot]`` — the values a write-then-read would see, bit
  for bit — over exactly ``[0, cache_len]`` (``_stored_attention``:
  the ``g`` heads of a stored row as ``g`` queries over one K/V head
  ``W`` wide, each zero outside its own lanes), and once the last
  layer's row exists all ``L`` rows of a slot are written straight
  into the stacked cache, one ``dynamic_update_slice`` per slot and
  tensor. No plane is sliced out and stacked back and the minor
  dimension is never reshaped, so in the donated decode executable the
  output caches alias the inputs and the only cache bytes a step
  writes are the ``S`` new rows per layer. Because every shape is
  static in ``max_len``/slot count, ONE compiled decode executable
  serves any mix of sequence ages with zero recompiles
  (serving/decode.py builds it).

All three entry points share the same sub-blocks (one parameter set),
so greedy decode through the cache is bit-exact against the
full-sequence forward oracle — the contract tests/test_decode.py pins.
"""

from __future__ import annotations

import numpy as np

from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, LayerNorm

__all__ = ["CausalSelfAttention", "GPTBlockCell", "GPTDecoder", "get_gpt"]


def _positions_like(tokens):
    """(B, T) int32 position ids 0..T-1 broadcast over the batch."""
    import jax.numpy as jnp

    from ...ndarray.ndarray import invoke

    return invoke(
        lambda x: jnp.broadcast_to(
            jnp.arange(x.shape[1], dtype=jnp.int32), x.shape),
        [tokens], name="positions", differentiable=False)


def _stack0(arrays):
    """Stack NDArrays along a new leading axis (per-layer cache planes)."""
    import jax.numpy as jnp

    from ...ndarray.ndarray import invoke

    return invoke(lambda *xs: jnp.stack(xs, axis=0), arrays,
                  name="stack_layers", differentiable=False)


#: lanes of a TPU tile: the stored K/V row is a whole number of them
_LANES = 128


def _kv_pack(head_dim):
    """Heads that lie side by side in one stored K/V row: as many as
    fill ``_LANES`` lanes (2 for GPT-2's 64, 8 for the tiny spec's 16),
    and 1 — a row is a head, the plain ``[.., H, T, D]`` — where a head
    is that wide already or does not divide it."""
    return _LANES // head_dim \
        if head_dim < _LANES and _LANES % head_dim == 0 else 1


def _store_rows(x, heads, pack):
    """K or V as the ``qkv`` product gives it, ``x`` (B, T, H*D), in the
    stored form (B, P, T, W): ``pack`` heads side by side in a row of
    ``W = pack * D``, ``P = ceil(H / pack)`` rows a position, what is
    left of the last row zero. One pad, one reshape of the minor
    ``H*D`` (the product's, not a cache's) and one transpose."""
    import jax.numpy as jnp

    from ...ndarray.ndarray import invoke

    def store(a):
        b, t, c = a.shape
        w = c // heads * pack
        rows = -(-heads // pack)
        a = jnp.pad(a, ((0, 0), (0, 0), (0, rows * w - c)))
        return a.reshape(b, t, rows, w).transpose(0, 2, 1, 3)

    return invoke(store, [x], name="kv_store_rows", differentiable=False)


def _stored_attention(q, k, v, total_lens, head_dim):
    """One-token attention over planes in the stored form: ``q``
    (S, P, 1, W) the query heads packed like a K/V row, ``k``/``v``
    (S, P, T, W), ``total_lens`` (S,) the valid length per slot. Returns
    the attended rows (S, P, 1, W), head ``p * g + j`` in lanes
    ``[j*D, (j+1)*D)`` of row ``p``.

    The ``g = W // D`` heads of a stored row are ``g`` queries over ONE
    K/V head ``W`` wide (the grouped-query form of
    ``decoder.py::serve_step``), query ``j`` zero outside its own ``D``
    lanes: the other lanes of a row hold another head's finite values
    or the pad's zeros, so they add exact zeros to a score, and of an
    output row each head keeps its own lanes. The products do ``g``
    times the useful work inside a fusion that waits on the plane's
    bytes; what they buy is that ``W`` is never split — reshaping
    ``W`` into ``(g, D)`` on a tiled plane is a relayout of the plane.
    Scale, mask and float32 softmax are
    ``ops/pallas_attention.py::_xla_reference``'s for one query at
    position ``total_lens - 1``; the scores are accumulated AND kept in
    float32."""
    import jax
    import jax.numpy as jnp

    from ...ndarray.ndarray import invoke

    def attend(q_, k_, v_, lens):
        w, t = k_.shape[-1], k_.shape[2]
        own = jnp.arange(w, dtype=jnp.int32)[None, :] // head_dim \
            == jnp.arange(w // head_dim, dtype=jnp.int32)[:, None]  # (g, W)
        sc = jnp.einsum("spgc,sptc->spgt", jnp.where(own, q_, 0), k_,
                        preferred_element_type=jnp.float32)
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] \
            < lens.astype(jnp.int32)[:, None]
        sc = jnp.where(valid[:, None, None, :], sc * (1.0 / head_dim ** 0.5),
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(v_.dtype)
        out = jnp.einsum("spgt,sptc->spgc", p, v_)
        return jnp.where(own, out, 0).sum(axis=2, keepdims=True)

    return invoke(attend, [q, k, v, total_lens], name="stored_attention",
                  differentiable=False)


def _kv_plane_with_row(cache, new, layer, total_lens):
    """Layer ``layer``'s (S, P, T, W) plane of the stacked cache with
    each slot's new row in place, WITHOUT writing it: ``cache``
    (L, S, P, T, W) in the stored form (``_kv_pack``), ``new``
    (S, P, 1, W), ``total_lens`` (S,) valid length per slot INCLUDING
    the new token. A select on the position over a static leading-axis
    slice — both fuse into the attention that reads the plane, which so
    sees exactly what it would read after the row was written at
    ``total_lens - 1``."""
    import jax.numpy as jnp

    from ...ndarray.ndarray import invoke

    def plane(c, u, lens):
        at = jnp.arange(c.shape[3], dtype=jnp.int32)[None, :] \
            == lens.astype(jnp.int32)[:, None] - 1
        return jnp.where(at[:, None, :, None], u, c[layer])

    return invoke(plane, [cache, new, total_lens], name="kv_plane_with_row",
                  differentiable=False)


def _kv_cache_write(cache, rows, total_lens):
    """Write every layer's new K/V rows into the stacked cache where it
    lies.

    ``cache`` (L, S, P, T, W) in the stored form; ``rows`` the ``L``
    per-layer (S, P, 1, W) rows; ``total_lens`` (S,) valid length per
    slot INCLUDING the new token — slot ``s``'s rows land at
    ``(:, s, :, total_lens[s] - 1, :)``. One ``dynamic_update_slice``
    per slot, chained on the whole cache: they are the cache's only
    writers in a step, so XLA updates the (donated) buffer in place and
    nothing of the cache's or a plane's shape is copied out or stacked
    back. (Measured on the v5e, PERF.md PR 26 and PR 29: an update's
    time goes by tiles touched, not by calls — one call per slot for
    all layers costs the device what one per slot and layer does, in a
    program that compiles and loads several times faster. A stored row
    of whole 128-lane tiles lies ``W``-minor and is ``P`` tiles; a
    ``[.., H, T, 64]`` cache lay ``T``-minor and a row was ``4 * H``.
    A scatter — which a vmapped ``dynamic_update_slice`` also lowers to
    — makes the TPU compiler relayout its whole operand around it.)
    The slot is static and the position is CLAMPED into ``[0, T)`` by
    ``dynamic_update_slice``, so a freed slot's stale ``cache_len`` of
    ``max_len`` neither faults nor lands outside that slot's own
    (freed) rows."""
    import jax.numpy as jnp
    from jax import lax

    from ...ndarray.ndarray import invoke

    def write(c, lens, *us):
        u = jnp.stack(us, axis=0)                     # (L, S, P, 1, W)
        pos = lens.astype(jnp.int32) - 1
        for s in range(c.shape[1]):
            c = lax.dynamic_update_slice(c, u[:, s:s + 1],
                                         (0, s, 0, pos[s], 0))
        return c

    return invoke(write, [cache, total_lens, *rows], name="kv_cache_write",
                  differentiable=False)


class CausalSelfAttention(HybridBlock):
    """Fused-QKV multi-head causal self-attention with a decode mode."""

    def __init__(self, units, num_heads, dropout=0.0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
        self._pack = _kv_pack(units // num_heads)
        with self.name_scope():
            self.qkv = Dense(3 * units, flatten=False, in_units=units)
            self.proj = Dense(units, flatten=False, in_units=units)
            self.drop = Dropout(dropout)

    def _split(self, x):
        b, t, _ = x.shape
        return x.reshape(b, t, self._heads,
                         self._units // self._heads).transpose((0, 2, 1, 3))

    def _qkv(self, x):
        """The fused product's three (B, T, C) slices."""
        c = self._units
        qkv = self.qkv(x)
        return (qkv.slice_axis(2, 0, c), qkv.slice_axis(2, c, 2 * c),
                qkv.slice_axis(2, 2 * c, 3 * c))

    def _stored(self, x):
        return _store_rows(x, self._heads, self._pack)

    def forward(self, x, *args):
        out, _, _ = self.forward_with_kv(x)
        return out

    def forward_with_kv(self, x, stored=False):
        """Full-sequence causal attention; also returns this layer's K/V
        planes for cache seeding (prefill): (B, H, T, D), or with
        ``stored`` the cache's own (B, P, T, W) (``_store_rows``, from
        the product's slices and not from the split heads)."""
        from ...ndarray.ndarray import invoke_op

        q, k, v = self._qkv(x)
        kh, vh = self._split(k), self._split(v)
        out = invoke_op("flash_attention", self._split(q), kh, vh,
                        causal=True)
        b, h, t, d = out.shape
        out = out.transpose((0, 2, 1, 3)).reshape(b, t, self._units)
        if stored:
            kh, vh = self._stored(k), self._stored(v)
        return self.drop(self.proj(out)), kh, vh

    def decode_step(self, x, k_cache, v_cache, total_lens, layer):
        """One-token decode of layer ``layer`` over the stacked cache.

        ``x`` (S, 1, C) — the new token's activations per slot;
        ``k_cache``/``v_cache`` (L, S, P, T, W), ALL layers in the
        stored form, read and not written here; ``total_lens`` (S,)
        valid length per slot including the new token; ``layer`` this
        layer's (static) index. Returns the attended activations and
        the new token's K/V rows (S, P, 1, W) for the caller to write:
        attention reads the layer's plane with those rows selected in
        at ``total_lens - 1`` over ``[0, total_lens)`` exactly
        (``_stored_attention``)."""
        q, k_new, v_new = (self._stored(a) for a in self._qkv(x))
        out = _stored_attention(
            q, _kv_plane_with_row(k_cache, k_new, layer, total_lens),
            _kv_plane_with_row(v_cache, v_new, layer, total_lens),
            total_lens, self._units // self._heads)
        s = out.shape[0]
        out = out.transpose((0, 2, 1, 3)).reshape(s, 1, -1) \
            .slice_axis(2, 0, self._units)
        return self.drop(self.proj(out)), k_new, v_new


class GPTBlockCell(HybridBlock):
    """Pre-norm decoder block: x + attn(ln1(x)); x + ffn(ln2(x))."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.1,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.ln1 = LayerNorm(in_channels=units)
            self.attn = CausalSelfAttention(units, num_heads,
                                            dropout=dropout)
            self.ln2 = LayerNorm(in_channels=units)
            self.ffn1 = Dense(hidden_size, flatten=False, in_units=units)
            self.ffn2 = Dense(units, flatten=False, in_units=hidden_size)
            self.ffn_drop = Dropout(dropout)

    def _ffn(self, x):
        from ... import ndarray as F

        return self.ffn_drop(self.ffn2(F.Activation(self.ffn1(x),
                                                    act_type="gelu")))

    def forward(self, x, *args):
        x = x + self.attn(self.ln1(x))
        return x + self._ffn(self.ln2(x))

    def forward_with_kv(self, x, stored=False):
        a, k, v = self.attn.forward_with_kv(self.ln1(x), stored=stored)
        x = x + a
        return x + self._ffn(self.ln2(x)), k, v

    def decode_step(self, x, k_cache, v_cache, total_lens, layer):
        a, k_new, v_new = self.attn.decode_step(
            self.ln1(x), k_cache, v_cache, total_lens, layer)
        x = x + a
        return x + self._ffn(self.ln2(x)), k_new, v_new


class GPTDecoder(HybridBlock):
    """GPT-style decoder LM: tokens (B, T) int32 -> logits (B, T, V).

    ``max_length`` bounds both the training sequence length and the
    serving KV-cache ``max_len`` (learned position table size)."""

    def __init__(self, vocab_size=50257, units=768, hidden_size=None,
                 num_layers=12, num_heads=12, max_length=1024, dropout=0.1,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._vocab = vocab_size
        self._units = units
        self._layers = num_layers
        self._heads = num_heads
        self._max_length = max_length
        hidden_size = 4 * units if hidden_size is None else hidden_size
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units)
            self.position_embed = Embedding(max_length, units)
            self.embed_dropout = Dropout(dropout)
            for i in range(num_layers):
                setattr(self, f"layer{i}",
                        GPTBlockCell(units, hidden_size, num_heads,
                                     dropout=dropout))
            self.ln_f = LayerNorm(in_channels=units)
            self.head = Dense(vocab_size, flatten=False, use_bias=False,
                              in_units=units)

    # serving/decode.py sizes the KV cache off these
    @property
    def num_layers(self):
        return self._layers

    @property
    def num_heads(self):
        return self._heads

    @property
    def head_dim(self):
        return self._units // self._heads

    @property
    def max_length(self):
        return self._max_length

    @property
    def vocab_size(self):
        return self._vocab

    #: integers a decode step returns beside the logits: none here
    step_counters = ()

    def cache_groups(self, max_len):
        """The K/V cache this block is served with (docs/SERVING.md "What
        a block declares"): one group, every layer ``max_len`` rows, in
        the STORED form — ``heads`` is the stored rows a position,
        ``ceil(H / g)``, and ``head_dim`` their width ``g * D``
        (``_kv_pack``: ``[48, S, 13, T, 128]`` for GPT-2 XL's 25 heads
        of 64)."""
        g = _kv_pack(self.head_dim)
        return [dict(layers=self._layers, heads=-(-self._heads // g),
                     rows=int(max_len), head_dim=g * self.head_dim,
                     kind="full")]

    def serve_prefill(self, tokens, n):
        """``prefill`` of one padded prompt ``tokens`` (T,) as the serving
        tier takes it: the logits at the last TRUE position ``n - 1``
        (``n`` a traced scalar) and the K/V planes in the stored form
        ``[L, P, T, W]`` that ``cache_groups`` declares."""
        import jax

        from ...ndarray.ndarray import invoke

        logits, k, v = self.prefill(tokens.reshape(1, -1), stored=True)
        return invoke(
            lambda lg, k_, v_, n_: (jax.lax.dynamic_index_in_dim(
                lg[0], n_ - 1, axis=0, keepdims=False), k_[:, 0], v_[:, 0]),
            [logits, k, v, n], name="serve_prefill", differentiable=False)

    def serve_step(self, tokens, cache_len, k_cache, v_cache):
        """``decode_step`` in the serving tier's argument order."""
        return self.decode_step(tokens, k_cache, v_cache, cache_len)

    def _embed(self, tokens, positions):
        return self.embed_dropout(self.word_embed(tokens)
                                  + self.position_embed(positions))

    def forward(self, tokens, *args):
        x = self._embed(tokens, _positions_like(tokens))
        for i in range(self._layers):
            x = getattr(self, f"layer{i}")(x)
        return self.head(self.ln_f(x))

    def prefill(self, tokens, stored=False):
        """Full causal forward that ALSO returns the per-layer K/V planes
        for cache seeding: ``logits`` (B, T, V), ``k``/``v``
        (L, B, H, T, D), or with ``stored`` the cache's (L, B, P, T, W).
        Positions beyond a prompt's true length carry garbage K/V —
        causality guarantees no valid position ever attended them, and
        the serving tier's per-slot ``cache_len`` keeps decode from
        reading them."""
        x = self._embed(tokens, _positions_like(tokens))
        ks, vs = [], []
        for i in range(self._layers):
            x, k, v = getattr(self, f"layer{i}").forward_with_kv(
                x, stored=stored)
            ks.append(k)
            vs.append(v)
        return self.head(self.ln_f(x)), _stack0(ks), _stack0(vs)

    def decode_step(self, tokens, k_cache, v_cache, cache_len):
        """Advance every slot one token: ``tokens`` (S,) int32 — the next
        input token per slot; ``k_cache``/``v_cache`` (L, S, P, T, W),
        the stored form ``cache_groups`` declares; ``cache_len`` (S,)
        tokens already cached per slot (the new token lands at that
        position). Returns ``logits`` (S, V) and the updated caches.

        The stacked caches are read by every layer (plane ``i`` with
        the new row selected in) and written once, after the last
        layer: ``S`` rows per layer and tensor, nothing of the cache's
        or a plane's shape is built beside them, so a donated
        executable updates the cache where it lies
        (``tests/test_decode.py`` pins the lowered program). Slots whose
        entries are stale (free slots) still compute — the scheduler
        ignores their rows; their writes land in their own freed rows,
        also when a stale ``cache_len`` is ``max_len``
        (``_kv_cache_write``: the position is clamped)."""
        s = tokens.shape[0]
        tok = tokens.reshape(s, 1)
        pos = cache_len.reshape(s, 1)
        x = self._embed(tok, pos)
        total = cache_len + 1
        new_k, new_v = [], []
        for i in range(self._layers):
            x, k_l, v_l = getattr(self, f"layer{i}").decode_step(
                x, k_cache, v_cache, total, i)
            new_k.append(k_l)
            new_v.append(v_l)
        logits = self.head(self.ln_f(x)).squeeze(1)
        return (logits, _kv_cache_write(k_cache, new_k, total),
                _kv_cache_write(v_cache, new_v, total))


#: GPT-2-family configs (117M/345M) plus a tiny config for tests/benches
_GPT_SPECS = {
    "gpt_decoder_tiny": dict(num_layers=2, units=64, num_heads=4),
    "gpt_decoder_117m": dict(num_layers=12, units=768, num_heads=12),
    "gpt_decoder_345m": dict(num_layers=24, units=1024, num_heads=16),
}


def get_gpt(model_name="gpt_decoder_117m", vocab_size=50257, dropout=0.1,
            max_length=1024, **kwargs):
    """GPT decoder factory (the ``get_bert`` analog for the decoder
    workload)."""
    if model_name not in _GPT_SPECS:
        raise ValueError(f"unknown gpt spec {model_name!r}; "
                         f"known {sorted(_GPT_SPECS)}")
    spec = dict(_GPT_SPECS[model_name])
    spec.update(kwargs)
    return GPTDecoder(vocab_size=vocab_size, dropout=dropout,
                      max_length=max_length, **spec)
