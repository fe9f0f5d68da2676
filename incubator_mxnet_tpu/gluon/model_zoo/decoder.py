"""A decoder-only language model built from data: per layer an attention
kind (``full`` or a sliding ``window``) and an FFN kind (``dense`` or
``sparse``), over one set of widths.

The layer is the one today's large open decoders share: grouped-query
attention (``num_heads`` query heads over ``num_kv_heads`` K/V heads of
``head_dim``), RMSNorm over each query and key head, rotary positions on
the window layers and none on the full ones, RMSNorm on each sub-layer's
OUTPUT (``h = h + norm(attn(h))``, ``h = h + norm(ffn(h))``), a
SiLU-gated FFN, and on ``sparse`` layers a router over ``num_experts``
experts of which the layer HOLDS ``experts_held`` (share ``expert_share``
of ``num_experts // experts_held``: what one chip of an expert-parallel
deployment holds) plus a shared expert. Routing drops nothing
(``ops.moe.moe_route`` / ``moe_held_ffn``); what the experts held
elsewhere would add is left out, as it is on that chip before the
exchange.

What the block declares to be served by ``serving.DecodeSession``
(docs/SERVING.md "What a block declares"):

* ``cache_groups(max_len)``: the K/V cache as groups of layers with
  their own row count: the full layers keep ``max_len`` rows, the window
  layers a ring of ``window`` rows;
* ``serve_prefill(tokens, n)``: one padded prompt -> the logits at its
  last TRUE position and each group's K/V planes ``[Lg, Hkv, T, D]``;
* ``serve_step(tokens, cache_len, *caches)``: every slot one token on,
  the caches updated where they lie. Where a row lies, what a slot may
  read, the one-token attention and the writes are ``ops/kv_cache.py``'s;
* ``step_counters``: the integers a step returns beside the logits.

The arithmetic is plain ``jax.numpy`` over the parameter arrays (one
``invoke`` per entry point); the matrix products take their operands'
type and sum in float32, the router, RoPE and every norm's statistics
are float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ndarray.ndarray import invoke
from ...ops import kv_cache
from ...ops.moe import moe_held_ffn, moe_route
from ..block import HybridBlock

__all__ = ["HybridDecoder", "get_decoder"]

#: the cache groups, in ``cache_groups`` order: full-attention layers keep
#: every position, window layers a ring of the window's rows
_FULL, _RING = 0, 1
_KINDS = ("full", "ring")

#: queries per block of the prefill attention: scores are built a block
#: at a time against the keys that block may see, so a 2048-token prompt
#: never holds a (T, T) score tensor per head
_Q_BLOCK = 512


def _mm(x, w):
    """``x @ w.T`` for a Dense-style (out, in) weight."""
    return jnp.einsum("...i,oi->...o", x, w)


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta):
    """Rotary embedding (the half-split convention) of ``x`` (..., T, D)
    at ``positions`` (..., T), angles in float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[..., None] * inv      # (..., T, D/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


def gated_ffn(x, w_gate, w_up, w_down):
    return _mm(jax.nn.silu(_mm(x, w_gate)) * _mm(x, w_up), w_down)


class HybridDecoder(HybridBlock):
    """tokens (B, T) int32 -> logits (B, T, V); see the module docstring.

    ``layer_types[i]`` is ``"full_attention"`` or ``"sliding_attention"``,
    ``mlp_layer_types[i]`` ``"dense"`` or ``"sparse"`` (the published
    configs' own words)."""

    step_counters = ("routed_here", "routed_all", "experts_hit",
                     "expert_load_max")

    def __init__(self, vocab_size, units, num_heads, num_kv_heads, head_dim,
                 layer_types, mlp_layer_types, hidden_size, window=128,
                 rope_theta=1e6, eps=1e-5, num_experts=0, experts_held=0,
                 expert_share=0, experts_per_token=0, expert_hidden=0,
                 routed_scale=1.0, max_length=4096, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if len(layer_types) != len(mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        sparse = "sparse" in mlp_layer_types
        if sparse and not (0 < experts_held <= num_experts
                           and (expert_share + 1) * experts_held
                           <= num_experts
                           and 0 < experts_per_token <= num_experts):
            raise ValueError(
                f"share {expert_share} of {experts_held} experts does not "
                f"lie in {num_experts}, or top-{experts_per_token} does not")
        self._vocab, self._units = int(vocab_size), int(units)
        self._heads, self._kv_heads = int(num_heads), int(num_kv_heads)
        self._head_dim = int(head_dim)
        self._kinds = tuple(zip(layer_types, mlp_layer_types))
        self._window, self._theta = int(window), float(rope_theta)
        self._eps = float(eps)
        self._experts, self._held = int(num_experts), int(experts_held)
        self._first_expert = int(expert_share) * int(experts_held)
        self._top_k, self._scale = int(experts_per_token), float(routed_scale)
        self._max_length = int(max_length)
        # per layer (cache group, index in the group); layers per group
        self._group_counts, self._group_of = [0, 0], []
        for attn, _ in self._kinds:
            g = _FULL if attn == "full_attention" else _RING
            self._group_of.append((g, self._group_counts[g]))
            self._group_counts[g] += 1
        c, d = self._units, self._head_dim
        hq, hkv = self._heads * d, self._kv_heads * d
        f, fe, e = int(hidden_size), int(expert_hidden), self._held
        get = self.params.get
        with self.name_scope():
            self.embed = get("embed", shape=(self._vocab, c))
            self.final_norm = get("final_norm", shape=(c,), init="ones")
            self.head = get("head", shape=(self._vocab, c))
            for i, (_, ffn) in enumerate(self._kinds):
                shapes = {"q": (hq, c), "k": (hkv, c), "v": (hkv, c),
                          "o": (c, hq), "q_norm": (d,), "k_norm": (d,),
                          "attn_norm": (c,), "ffn_norm": (c,)}
                if ffn == "dense":
                    shapes.update(gate=(f, c), up=(f, c), down=(c, f))
                else:
                    shapes.update(
                        router=(self._experts, c),
                        router_bias=(self._experts,),
                        experts_gate=(e, c, fe), experts_up=(e, c, fe),
                        experts_down=(e, fe, c), shared_gate=(fe, c),
                        shared_up=(fe, c), shared_down=(c, fe))
                for name, shape in shapes.items():
                    init = "ones" if name.endswith("norm") else (
                        "zeros" if name == "router_bias" else None)
                    setattr(self, f"layer{i}_{name}",
                            get(f"layer{i}_{name}", shape=shape, init=init))

    # -- what serving sizes the cache off ------------------------------------
    @property
    def max_length(self):
        return self._max_length

    def cache_groups(self, max_len):
        """The K/V cache this block is served with, a dict per group of
        layers: ``layers``, ``heads`` (K/V heads), ``rows``, ``head_dim``
        and ``kind`` (``ops/kv_cache.py``). Groups a model has no layer
        of are left out of the cache but keep their place in the
        order."""
        rows = (int(max_len), min(self._window, int(max_len)))
        return [dict(layers=n, heads=self._kv_heads, rows=r,
                     head_dim=self._head_dim, kind=kind)
                for n, r, kind in zip(self._group_counts, rows, _KINDS)]

    # -- the arithmetic, over plain arrays -------------------------------------
    def _arrays(self):
        """Parameter NDArrays in a fixed order, and how to name them."""
        names = sorted(self._reg_params)
        return names, [self._reg_params[n].data() for n in names]

    def _layer(self, p, i):
        pre = f"layer{i}_"
        return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}

    def _qkv(self, lp, x, positions, window):
        """``x`` (B, T, C) -> q (B, Hkv, G, T, D), k and v (B, Hkv, T, D),
        normed per head; rotated on window layers."""
        b, t, _ = x.shape
        d, hkv = self._head_dim, self._kv_heads
        heads = lambda a, n: a.reshape(b, t, n, d).transpose(0, 2, 1, 3)
        q = rms_norm(heads(_mm(x, lp["q"]), self._heads), lp["q_norm"],
                     self._eps)
        k = rms_norm(heads(_mm(x, lp["k"]), hkv), lp["k_norm"], self._eps)
        v = heads(_mm(x, lp["v"]), hkv)
        if window:
            q = rope(q, positions[:, None, :], self._theta)
            k = rope(k, positions[:, None, :], self._theta)
        return q.reshape(b, hkv, self._heads // hkv, t, d), k, v

    def _merge(self, lp, out):
        """(B, Hkv, G, T, D) attended heads -> (B, T, C)."""
        b, hkv, g, t, d = out.shape
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, t, hkv * g * d)
        return _mm(out, lp["o"])

    def _attend_sequence(self, q, k, v, window):
        """Causal attention of whole sequences, a block of queries at a
        time over the keys that block may see: all before it, or on a
        window layer those less than ``window`` behind."""
        t = q.shape[3]
        scale = 1.0 / math.sqrt(self._head_dim)
        outs = []
        for q0 in range(0, t, _Q_BLOCK):
            q1 = min(t, q0 + _Q_BLOCK)
            k0 = max(0, q0 - window + 1) if window else 0
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q[:, :, :, q0:q1],
                           k[:, :, k0:q1],
                           preferred_element_type=jnp.float32) * scale
            qi = jnp.arange(q0, q1)[:, None]
            kj = jnp.arange(k0, q1)[None, :]
            see = kj <= qi
            if window:
                see &= qi - kj < window
            w = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(v.dtype),
                                   v[:, :, k0:q1]))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=3)

    def _ffn(self, lp, x, live=None):
        """``x`` (N, C) -> the FFN's output and the routing's counts
        (None on a dense layer)."""
        if "gate" in lp:
            return gated_ffn(x, lp["gate"], lp["up"], lp["down"]), None
        with jax.named_scope("router"):
            logits = jnp.einsum("ni,ei->ne", x, lp["router"],
                                preferred_element_type=jnp.float32)
            idx, w = moe_route(logits, self._top_k, bias=lp["router_bias"],
                               scale=self._scale)
        with jax.named_scope("grouped_product"):
            y, counts = moe_held_ffn(
                x, idx, w, lp["experts_gate"], lp["experts_up"],
                lp["experts_down"], first_expert=self._first_expert,
                live=live)
        with jax.named_scope("shared_expert"):
            y = y + gated_ffn(x, lp["shared_gate"], lp["shared_up"],
                              lp["shared_down"]).astype(jnp.float32)
        return y.astype(x.dtype), counts

    def _sequence(self, p, tokens):
        """``tokens`` (B, T) -> the last layer's output (B, T, C) and
        per layer the K/V planes (B, Hkv, T, D)."""
        b, t = tokens.shape
        x = jnp.take(p["embed"], tokens, axis=0)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))
        planes = []
        for i, (attn, _) in enumerate(self._kinds):
            lp = self._layer(p, i)
            window = self._window if attn == "sliding_attention" else 0
            with jax.named_scope("attention"):
                q, k, v = self._qkv(lp, x, positions, window)
                a = self._merge(lp, self._attend_sequence(q, k, v, window))
            planes.append((k, v))
            x = x + rms_norm(a, lp["attn_norm"], self._eps)
            y, _ = self._ffn(lp, x.reshape(b * t, -1))
            x = x + rms_norm(y.reshape(b, t, -1), lp["ffn_norm"], self._eps)
        return x, planes

    def _logits(self, p, x):
        return _mm(rms_norm(x, p["final_norm"], self._eps), p["head"])

    def _run(self, fn, inputs, name):
        names, arrays = self._arrays()
        n = len(inputs)
        return invoke(
            lambda *a: fn(dict(zip(names, a[n:])), *a[:n]),
            list(inputs) + arrays, name=name, differentiable=False)

    # -- entry points ----------------------------------------------------------
    def forward(self, tokens, *args):
        return self._run(
            lambda p, tok: self._logits(p, self._sequence(p, tok)[0]),
            [tokens], "hybrid_decoder_forward")

    def serve_prefill(self, tokens, n):
        """One padded prompt ``tokens`` (T,) of true length ``n`` (a
        traced scalar): the logits (V,) at position ``n - 1`` (the head
        is applied there and nowhere else) and, per cache group, the K
        and V planes ``[Lg, Hkv, T, D]`` of the whole bucket (positions
        from ``n`` on hold garbage that no true position attended)."""
        group_of, counts = self._group_of, self._group_counts

        def fn(p, tok, n_true):
            x, planes = self._sequence(p, tok[None])
            last = jax.lax.dynamic_index_in_dim(x[0], n_true - 1, axis=0,
                                                keepdims=False)
            out = [self._logits(p, last)]
            for g, count in enumerate(counts):
                if count:
                    mine = [planes[i] for i, (gi, _) in enumerate(group_of)
                            if gi == g]
                    out.append(jnp.stack([k[0] for k, _ in mine]))
                    out.append(jnp.stack([v[0] for _, v in mine]))
            return tuple(out)

        return self._run(fn, [tokens, n], "hybrid_decoder_prefill")

    def serve_step(self, tokens, cache_len, *caches):
        """Every slot one token on. ``tokens``/``cache_len`` (S,);
        ``caches`` the K and V array ``[Lg, S, Hkv, rows, D]`` of each
        cache group (K then V, groups in ``cache_groups`` order, groups
        of no layer left out). Returns the logits (S, V), the
        ``step_counters`` as one int32 vector (over the slots whose
        ``cache_len`` is not 0: a free slot's is), and the caches with
        each slot's new row written (``kv_cache.address``)."""
        group_of = self._group_of
        present = [g for g, c in enumerate(self._group_counts) if c]

        def fn(p, tok, lens, *cs):
            lens = lens.astype(jnp.int32)
            kv = {g: (cs[2 * j], cs[2 * j + 1])
                  for j, g in enumerate(present)}
            at = {g: kv_cache.address(lens, kv[g][0].shape[3], _KINDS[g])
                  for g in present}
            x = jnp.take(p["embed"], tok, axis=0)[:, None]     # (S, 1, C)
            live = lens > 0
            new = {g: ([], []) for g in present}
            totals = dict.fromkeys(("routed_here", "experts_hit"), 0)
            load_max, sparse = 0, 0
            for i, (attn, _) in enumerate(self._kinds):
                lp = self._layer(p, i)
                g, j = group_of[i]
                with jax.named_scope("attention"):
                    q, k_new, v_new = self._qkv(
                        lp, x, lens[:, None], self._window * (g == _RING))
                    a = kv_cache.attend_row(
                        q[:, :, :, 0], kv[g][0], kv[g][1], j, k_new, v_new,
                        lens, at[g], _KINDS[g], self._head_dim)
                    a = self._merge(lp, a[:, :, :, None])
                new[g][0].append(k_new)
                new[g][1].append(v_new)
                x = x + rms_norm(a, lp["attn_norm"], self._eps)
                y, c = self._ffn(lp, x[:, 0], live=live)
                if c is not None:
                    sparse += 1
                    for name in totals:
                        totals[name] = totals[name] + c[name]
                    load_max = jnp.maximum(load_max, c["load_max"])
                x = x + rms_norm(y[:, None], lp["ffn_norm"], self._eps)
            logits = self._logits(p, x[:, 0])
            counters = jnp.stack([jnp.asarray(v, jnp.int32) for v in (
                totals["routed_here"],
                live.sum() * self._top_k * sparse,
                totals["experts_hit"], load_max)])
            out = [logits, counters]
            out += [kv_cache.write(cache, rows_new, at[g][0])
                    for g in present
                    for cache, rows_new in zip(kv[g], new[g])]
            return tuple(out)

        return self._run(fn, [tokens, cache_len, *caches],
                         "hybrid_decoder_step")


#: layer data of the published configs this decoder is built from; the
#: callers' keyword arguments override any of it (depth, the experts
#: held, the vocabulary slice: chipbench/configs/*.json say which)
_SPECS = {
    # LGAI-EXAONE/K-EXAONE-236B-A23B config.json: 48 layers ``LLLG``,
    # layer 0 dense, 128 experts top-8 + 1 shared, window 128
    "exaone_moe": dict(
        vocab_size=153600, units=6144, num_heads=64, num_kv_heads=8,
        head_dim=128, hidden_size=18432, window=128, rope_theta=1e6,
        eps=1e-5, num_experts=128, experts_held=128, experts_per_token=8,
        expert_hidden=2048, routed_scale=2.5, num_layers=48,
        pattern="LLLG", dense_layers=1),
    "exaone_moe_tiny": dict(
        vocab_size=97, units=64, num_heads=8, num_kv_heads=2, head_dim=16,
        hidden_size=96, window=8, rope_theta=1e6, eps=1e-5, num_experts=8,
        experts_held=4, experts_per_token=2, expert_hidden=32,
        routed_scale=2.5, num_layers=3, pattern="LG", dense_layers=1,
        max_length=128),
}


def get_decoder(model_name="exaone_moe", **kwargs):
    """Decoder factory (``get_gpt``'s analog for the data-built decoder).
    ``num_layers``, ``pattern`` (``L`` a window layer, ``G`` a full one,
    repeated) and ``dense_layers`` (the leading layers whose FFN is
    dense) expand to the per-layer kinds unless ``layer_types`` /
    ``mlp_layer_types`` are given."""
    if model_name not in _SPECS:
        raise ValueError(f"unknown decoder spec {model_name!r}; "
                         f"known {sorted(_SPECS)}")
    spec = dict(_SPECS[model_name])
    spec.update(kwargs)
    n = int(spec.pop("num_layers"))
    pattern, dense = spec.pop("pattern"), int(spec.pop("dense_layers"))
    spec.setdefault("layer_types", [
        "full_attention" if pattern[i % len(pattern)] == "G"
        else "sliding_attention" for i in range(n)])
    spec.setdefault("mlp_layer_types", [
        "dense" if i < dense else "sparse" for i in range(n)])
    return HybridDecoder(**spec)
