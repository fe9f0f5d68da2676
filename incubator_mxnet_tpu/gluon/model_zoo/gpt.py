"""GPT-style autoregressive decoder — the sixth workload (ISSUE 12).

A pre-norm decoder-only transformer (GPT-2 convention: LayerNorm before
attention/FFN, learned position embeddings, untied LM head) built from
the same gluon blocks as the BERT encoder (``models/transformer.py``)
but wired for BOTH halves of the decoder-LLM story:

* **Training**: ``forward(tokens) -> logits`` is a plain causal
  full-sequence pass; attention routes through ``flash_attention``
  (dispatched per direction and by size: XLA dense below the measured
  Pallas crossover, the streaming Pallas kernels above it; a
  differentiated call by the backward kernels' crossover, a served
  prefill by the forward kernel's own), so the same config trains under
  ``SPMDTrainer`` + SuperStep + the ZeRO ladder like every other
  workload.
* **Serving** (docs/SERVING.md "What a block declares"): ``prefill``
  additionally returns the per-layer K/V planes that seed a
  device-resident cache, and ``serve_step`` advances EVERY slot of the
  cache by one token. The cache's rows are ``ops/kv_cache.py``'s: this
  block declares one ``full`` group in the stored form ``[L, S, P, T,
  W]`` (``g = 128 // D`` heads side by side in a row of ``W = g * D``
  lanes, two heads of 64 for every GPT-2 width) and hands each layer's
  packed query and new K/V rows to that module's read, attend and
  write. Every shape is static in ``max_len``/slot count, so ONE
  compiled decode executable serves any mix of sequence ages with zero
  recompiles (serving/decode.py builds it).

All entry points share the same sub-blocks (one parameter set), so
greedy decode through the cache is bit-exact against the full-sequence
forward oracle — the contract tests/test_decode.py pins.
"""

from __future__ import annotations

import numpy as np

from ...ops import kv_cache
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


class CausalSelfAttention(HybridBlock):
    """Fused-QKV multi-head causal self-attention with a decode mode."""

    def __init__(self, units, num_heads, dropout=0.0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        assert units % num_heads == 0
        self._units = units
        self._heads = num_heads
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
        """A (B, T, C) slice of the product in the cache's stored form
        (B, P, T, W)."""
        from ...ndarray.ndarray import invoke

        d = self._units // self._heads
        return invoke(
            lambda a: kv_cache.store_rows(a, self._heads, kv_cache.pack(d)),
            [x], name="kv_store_rows", differentiable=False)

    def _attend(self, q, k, v):
        """Full-sequence causal attention of the product's slices."""
        from ...ndarray.ndarray import invoke_op

        out = invoke_op("flash_attention", self._split(q), self._split(k),
                        self._split(v), causal=True)
        b, h, t, d = out.shape
        out = out.transpose((0, 2, 1, 3)).reshape(b, t, self._units)
        return self.drop(self.proj(out))

    def forward(self, x, *args):
        return self._attend(*self._qkv(x))

    def forward_with_kv(self, x):
        """``forward`` and this layer's K/V planes for cache seeding
        (prefill), in the stored form (B, P, T, W)."""
        q, k, v = self._qkv(x)
        return self._attend(q, k, v), self._stored(k), self._stored(v)

    def decode_step(self, x, k_cache, v_cache, cache_len, at, layer):
        """One-token decode of layer ``layer`` over the stacked cache.

        ``x`` (S, 1, C) — the new token's activations per slot;
        ``k_cache``/``v_cache`` (L, S, P, T, W), ALL layers, read and not
        written here; ``at`` the step's ``kv_cache.address`` of
        ``cache_len``; ``layer`` this layer's (static) index. Returns
        the attended activations and the new token's K/V rows
        (S, P, 1, W) for the caller to write."""
        from ...ndarray.ndarray import invoke

        q, k_new, v_new = (self._stored(a) for a in self._qkv(x))
        d = self._units // self._heads
        out = invoke(
            lambda q_, kc, vc, kn, vn, n, *at_: kv_cache.attend_row(
                q_, kc, vc, layer, kn, vn, n, at_, "full", d),
            [q, k_cache, v_cache, k_new, v_new, cache_len, *at],
            name="stored_attention", differentiable=False)
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

    def forward_with_kv(self, x):
        a, k, v = self.attn.forward_with_kv(self.ln1(x))
        x = x + a
        return x + self._ffn(self.ln2(x)), k, v

    def decode_step(self, x, k_cache, v_cache, cache_len, at, layer):
        a, k_new, v_new = self.attn.decode_step(
            self.ln1(x), k_cache, v_cache, cache_len, at, layer)
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

    #: parameters read by rows (a gather): a served session holds them
    #: row-major where the device's default layout is not (GPT-2 XL's
    #: 1600-wide tables on a TPU)
    row_tables = ("word_embed.weight", "position_embed.weight")

    def cache_groups(self, max_len):
        """The K/V cache this block is served with (docs/SERVING.md "What
        a block declares"): one group, every layer ``max_len`` rows, in
        the STORED form — ``heads`` is the stored rows a position,
        ``ceil(H / g)``, and ``head_dim`` their width ``g * D``
        (``kv_cache.pack``: ``[48, S, 13, T, 128]`` for GPT-2 XL's 25
        heads of 64)."""
        g = kv_cache.pack(self.head_dim)
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

        logits, k, v = self.prefill(tokens.reshape(1, -1))
        return invoke(
            lambda lg, k_, v_, n_: (jax.lax.dynamic_index_in_dim(
                lg[0], n_ - 1, axis=0, keepdims=False), k_[:, 0], v_[:, 0]),
            [logits, k, v, n], name="serve_prefill", differentiable=False)

    def serve_step(self, tokens, cache_len, k_cache, v_cache):
        """Advance every slot one token: ``tokens`` (S,) int32 — the next
        input token per slot; ``cache_len`` (S,) tokens already cached
        per slot (the new token lands at that position);
        ``k_cache``/``v_cache`` (L, S, P, T, W), the stored form
        ``cache_groups`` declares. Returns ``logits`` (S, V) and the
        updated caches.

        Every layer reads the stacked caches (plane ``i`` with the new
        row selected in) and they are written once, after the last
        layer, so a donated executable updates them where they lie
        (``tests/test_decode.py`` pins the lowered program). Slots whose
        entries are stale (free slots) still compute — the scheduler
        ignores their rows, and their writes land in their own freed
        rows (``kv_cache.address``)."""
        from ...ndarray.ndarray import invoke

        s = tokens.shape[0]
        x = self._embed(tokens.reshape(s, 1), cache_len.reshape(s, 1))
        at = invoke(
            lambda n: kv_cache.address(n, k_cache.shape[3], "full"),
            [cache_len], name="kv_address", differentiable=False)
        new_k, new_v = [], []
        for i in range(self._layers):
            x, k_l, v_l = getattr(self, f"layer{i}").decode_step(
                x, k_cache, v_cache, cache_len, at, i)
            new_k.append(k_l)
            new_v.append(v_l)
        logits = self.head(self.ln_f(x)).squeeze(1)

        def write(cache, new):
            return invoke(lambda c, row, *us: kv_cache.write(c, us, row),
                          [cache, at[0], *new], name="kv_cache_write",
                          differentiable=False)

        return logits, write(k_cache, new_k), write(v_cache, new_v)

    def _embed(self, tokens, positions):
        return self.embed_dropout(self.word_embed(tokens)
                                  + self.position_embed(positions))

    def forward(self, tokens, *args):
        x = self._embed(tokens, _positions_like(tokens))
        for i in range(self._layers):
            x = getattr(self, f"layer{i}")(x)
        return self.head(self.ln_f(x))

    def prefill(self, tokens):
        """Full causal forward that ALSO returns the per-layer K/V planes
        for cache seeding: ``logits`` (B, T, V), ``k``/``v``
        (L, B, P, T, W), the form ``cache_groups`` declares. Positions
        beyond a prompt's true length carry garbage K/V — causality
        guarantees no valid position ever attended them, and the serving
        tier's per-slot ``cache_len`` keeps decode from reading them."""
        x = self._embed(tokens, _positions_like(tokens))
        ks, vs = [], []
        for i in range(self._layers):
            x, k, v = getattr(self, f"layer{i}").forward_with_kv(x)
            ks.append(k)
            vs.append(v)
        return self.head(self.ln_f(x)), _stack0(ks), _stack0(vs)


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
