"""A decoder-only language model built from data: per layer a sequence
mixer (attention that is ``full``, a sliding ``window`` or ``latent``, or
a selective state-space layer, ``mamba``) and an FFN kind (``dense`` or
``sparse``), or one of the two alone, over one set of widths, under one
of two residual rules.

The layers are those today's large open decoders are built from:

* grouped-query attention (``num_heads`` query heads over
  ``num_kv_heads`` K/V heads of ``head_dim``), RMSNorm over each query and
  key head (``qk_norm``), rotary positions on the window layers and, only
  where ``rope_full`` says so, on the full ones;
* a selective state-space mixer (Jamba's, arXiv:2403.19887; ``mamba`` gives
  ``expand``, ``d_state``, ``dt_rank``, ``d_conv``): ``[x, z] = W_in u``, a
  causal depthwise convolution over the last ``d_conv`` inputs, ``[d, B,
  C] = W_x x`` each RMS-normed, ``dt = softplus(W_dt d + b_dt)``, the
  selective scan over a state of ``d_state x E`` a sequence
  (``ops/state_space.py``), the output gated by ``silu(z)``. It keeps no
  row a position: what a sequence carries is that state and the
  convolution's last inputs, the same size at every length. ``mamba``
  ``version`` 2 is Mamba-2's mixer (arXiv:2405.21060, as Nemotron-H has
  it; ``heads``, ``head_dim``, ``n_groups``, ``d_state``, ``d_conv``,
  ``chunk``): ``[z, xBC, dt] = W_in u``, the convolution over all of
  ``xBC``, ONE ``dt`` and ``A`` a head, ``B`` and ``C`` a group of heads,
  ``y * silu(z)`` RMS-normed a group at a time; the scan by the
  state-space dual form in chunks over whole sequences;
* latent attention (DeepSeek-V2's, arXiv:2405.04434; ``latent`` gives its
  ranks and head parts): queries through a normed ``q_rank`` bottleneck,
  keys and values through ONE normed ``kv_rank`` latent a position beside
  a rotated ``rope_dim`` key that every head shares, YaRN's frequencies.
  What is cached is that one row ``[c | k_r]``. It has two formulations
  of one arithmetic: whole sequences EXPAND the latent to every head's
  key and value (``_latent_expanded``); the decode step ABSORBS the
  expansion into the query and the output and attends over the cached
  rows themselves (``_latent_absorbed``);
* a SiLU-gated FFN (or, ``ffn_act`` ``relu2``, ``down(relu(up x) ** 2)``
  with no gate), and on ``sparse`` layers a router over
  ``num_experts`` experts of which the layer HOLDS ``experts_held`` (share
  ``expert_share`` of ``num_experts // experts_held``: what one chip of an
  expert-parallel deployment holds) plus a shared expert. Routing drops
  nothing (``ops.moe.moe_route`` / ``moe_held_ffn``); what the experts
  held elsewhere would add is left out, as it is on that chip before the
  exchange. With ``moe_latent`` the routed experts work in a latent of
  that width (Nemotron-H's LatentMoE): the router reads ``x``, the
  experts ``W_lat_down x``, their sum returns through ``W_lat_up``; the
  shared expert (``shared_hidden`` wide) reads ``x``;
* the residual rule (``_sub``): one stream, ``h = h + g(h)``, or
  ``streams`` of them under manifold-constrained hyper-connections
  (``ops/hyper_connection.py``: per token and sub-layer a read, a write
  and a doubly stochastic mix of the streams); and where the norm stands:
  on each sub-layer's OUTPUT (``g = norm . f``), ``pre_norm`` on its
  input (``g = f . norm``), or ``sandwich`` on both, each with a gain of
  its own (``g = norm_out . f . norm_in``);
* a loop (Ouro's LoopLM, arXiv:2510.25741): ``passes`` over 1 runs the
  whole stack that many times a token over the same weights, the final
  norm at the end of every pass and its output the next pass's input; the
  head reads the last pass's normed state. Every (pass, layer) attends
  over keys of its own, so a layer's cache rows are kept per pass. The
  passes are a loop in the program (``jax.lax.scan``, scope
  ``loop_pass``), not ``passes`` copies of the stack.

What the block declares to be served by ``serving.DecodeSession``
(docs/SERVING.md "What a block declares"):

* ``cache_groups(max_len)``: the cache as groups of layers with their own
  row count and kind (of a looped block, ``passes`` x its layers: pass
  ``r`` of the group's layer ``j`` at index ``r * Lg + j``): the full
  layers keep ``max_len`` rows of K and V,
  the window layers a ring of ``window`` rows, the latent layers
  ``max_len`` rows of one tensor, the state-space layers a ``state``
  group (no rows: two tensors a layer that a step replaces);
* ``serve_prefill(tokens, n)``: one padded prompt -> the logits at its
  last TRUE position and each group's planes ``[Lg, H, T, W]`` (K then V;
  a latent group its one; a state group the state AFTER position ``n -
  1``, ``[Lg, d_state, E]`` and ``[Lg, d_conv - 1, E]``);
* ``serve_step(tokens, cache_len, *caches)``: every slot one token on,
  the caches updated where they lie. Where a row lies, what a slot may
  read, the one-token attention, the writes and how a state advances are
  ``ops/kv_cache.py``'s;
* ``step_counters``: the integers a step returns beside the logits, and
  ``held_expert_bytes``: what a held expert's weights hold;
* ``row_tables``: the parameters read by rows, the embedding table;
* ``passes`` and ``step_weight_bytes(slots)``: how many times a step runs
  the stack, and the parameter bytes one step reads.

The arithmetic is plain ``jax.numpy`` over the parameter arrays (one
``invoke`` per entry point); the matrix products take their operands'
type and sum in float32, the router, RoPE, every norm's statistics and
every hyper-connection coefficient are float32, as are the state-space
layer's ``dt``, recurrence and state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...ndarray.ndarray import invoke
from ...ops import hyper_connection, kv_cache, state_space
from ...ops.moe import moe_held_ffn, moe_route
from ..block import HybridBlock

__all__ = ["HybridDecoder", "get_decoder"]

#: the cache groups, in ``cache_groups`` order: full-attention layers keep
#: every position, window layers a ring of the window's rows, latent
#: layers every position in one tensor, state-space layers a state
_FULL, _RING, _LATENT, _STATE = 0, 1, 2, 3
_KINDS = ("full", "ring", "latent", "state")
_GROUP_OF = {"full_attention": _FULL, "sliding_attention": _RING,
             "latent_attention": _LATENT, "mamba": _STATE}

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


def yarn_inv_freq(dim, theta, factor, original_max_position_embeddings,
                  beta_fast, beta_slow, **_):
    """YaRN's inverse frequencies of a ``dim``-wide rotary part
    (arXiv:2309.00071): ``theta ** (-2i / dim)`` where a frequency turns
    more than ``beta_fast`` times over the original positions, that over
    ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    ramp between."""
    def turns_at(n):
        return dim * math.log(original_max_position_embeddings
                              / (n * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    keep = 1.0 - jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv = theta ** (-2.0 * i / dim)
    return inv / factor * (1.0 - keep) + inv * keep


def rope(x, positions, theta, inv=None):
    """Rotary embedding (the half-split convention) of ``x`` (..., T, D)
    at ``positions`` (..., T), angles in float32; ``inv`` (D/2,) where
    the inverse frequencies are not ``theta``'s plain ones."""
    d = x.shape[-1]
    if inv is None:
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


def relu2(x):
    """The squared ReLU of Nemotron-H's FFNs and experts."""
    return jnp.square(jax.nn.relu(x))


#: an FFN's activation: ``silu`` gates (``down(silu(gate x) * up x)``),
#: ``relu2`` has no gate (``down(relu(up x) ** 2)``)
_ACTS = {"silu": jax.nn.silu, "relu2": relu2}


def group_rms_norm(x, g, groups, eps):
    """RMSNorm over each of ``groups`` equal parts of the last axis (the
    gated norm of a Mamba-2 mixer), statistics in float32."""
    shape = x.shape
    xf = x.astype(jnp.float32).reshape(shape[:-1] + (groups, -1))
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y.reshape(shape) * g.astype(jnp.float32)).astype(x.dtype)


class HybridDecoder(HybridBlock):
    """tokens (B, T) int32 -> logits (B, T, V); see the module docstring.

    ``layer_types[i]`` is ``"full_attention"``, ``"sliding_attention"``,
    ``"latent_attention"`` or ``"mamba"``, ``mlp_layer_types[i]``
    ``"dense"`` or ``"sparse"`` (the published configs' own words); either
    may be None where the layer is the other alone (each sub-layer that
    is there has its own norm). ``mamba``, where a layer is one, is a
    dict: ``expand``, ``d_state``, ``dt_rank``, ``d_conv``, or with
    ``version`` 2 ``heads``, ``head_dim``, ``n_groups``, ``d_state``,
    ``d_conv`` and ``chunk``. ``ffn_act`` is ``silu`` (gated) or ``relu2``
    (no gate), for the dense FFNs and every expert; ``moe_latent`` the
    width the routed experts work in (0: the stream's), ``shared_hidden``
    the shared expert's (``expert_hidden`` where None). ``qk_norm`` off
    leaves the attention heads un-normed, ``tie_embeddings`` reads the
    logits off the embedding table (no ``head``). ``latent``, where a
    layer is latent, is a dict: ``q_rank``, ``kv_rank``, ``nope_dim``,
    ``rope_dim``, ``v_dim`` and ``rope_scaling`` (YaRN's keys); a cached
    row ``[c | k_r]`` is stored at ``kv_cache.whole_tiles`` of ``kv_rank +
    rope_dim`` lanes, the rest zero. ``streams`` over 1 puts the hyper-connections'
    ``streams`` streams in the one's place, with ``hc`` their ``iters``,
    ``eps`` and ``clamp``. ``sandwich`` norms each sub-layer's input and
    its output (``attn_out_norm``, ``ffn_out_norm``), ``rope_full`` rotates
    the full-attention layers' queries and keys too, ``passes`` runs the
    stack that many times a token; ``exit_threshold`` is the early-exit
    gate's, and only 1 (every token runs every pass: the gate selects
    nothing and is not computed) is served."""

    step_counters = ("routed_here", "routed_all", "experts_hit",
                     "expert_load_max")
    row_tables = ("embed",)

    def __init__(self, vocab_size, units, num_heads, num_kv_heads, head_dim,
                 layer_types, mlp_layer_types, hidden_size, window=128,
                 rope_theta=1e6, eps=1e-5, num_experts=0, experts_held=0,
                 expert_share=0, experts_per_token=0, expert_hidden=0,
                 routed_scale=1.0, max_length=4096, latent=None, streams=1,
                 hc=None, pre_norm=False, mamba=None, qk_norm=True,
                 tie_embeddings=False, sandwich=False, rope_full=False,
                 passes=1, exit_threshold=1.0, ffn_act="silu",
                 shared_hidden=None, moe_latent=0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        if len(layer_types) != len(mlp_layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in "
                             "length")
        if sandwich and pre_norm:
            raise ValueError("a sub-layer's norm is sandwich or pre_norm, "
                             "not both")
        if exit_threshold < 1:
            raise ValueError(
                f"exit_threshold {exit_threshold}: an early exit before the "
                f"last pass is not served (every token runs every pass)")
        if int(passes) > 1 and (streams > 1 or "sparse" in mlp_layer_types
                                or "mamba" in layer_types):
            raise ValueError("passes over 1 run a stack of attention layers "
                             "with dense FFNs on one stream only")
        if num_heads % num_kv_heads:
            raise ValueError("num_heads must be a multiple of num_kv_heads")
        if any(a is None and f is None
               for a, f in zip(layer_types, mlp_layer_types)):
            raise ValueError("a layer has a mixer, an FFN or both")
        if ffn_act not in _ACTS:
            raise ValueError(f"ffn_act {ffn_act!r}: one of {sorted(_ACTS)}")
        sparse = "sparse" in mlp_layer_types
        if sparse and not (0 < experts_held <= num_experts
                           and (expert_share + 1) * experts_held
                           <= num_experts
                           and 0 < experts_per_token <= num_experts):
            raise ValueError(
                f"share {expert_share} of {experts_held} experts does not "
                f"lie in {num_experts}, or top-{experts_per_token} does not")
        if "latent_attention" in layer_types and not latent:
            raise ValueError("a latent_attention layer needs ``latent``")
        if "mamba" in layer_types and not mamba:
            raise ValueError("a mamba layer needs ``mamba``")
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
        self._latent = dict(latent or {})
        if self._latent:
            self._latent["row"] = kv_cache.whole_tiles(
                self._latent["kv_rank"] + self._latent["rope_dim"])
        self._streams, self._pre_norm = int(streams), bool(pre_norm)
        self._hc = dict(hc or {})
        self._ssm = dict(mamba, version=mamba.get("version", 1)) \
            if mamba else {}
        self._gated, self._act = ffn_act == "silu", _ACTS[ffn_act]
        self._moe_latent = int(moe_latent)
        self._qk_norm, self._tied = bool(qk_norm), bool(tie_embeddings)
        self._sandwich, self._rope_full = bool(sandwich), bool(rope_full)
        self.passes = int(passes)
        # per layer (cache group, index in the group); layers per group
        self._group_counts, self._group_of = [0] * len(_KINDS), []
        for attn, _ in self._kinds:
            if attn is None:
                self._group_of.append((None, None))
                continue
            g = _GROUP_OF[attn]
            self._group_of.append((g, self._group_counts[g]))
            self._group_counts[g] += 1
        c, d = self._units, self._head_dim
        hq, hkv = self._heads * d, self._kv_heads * d
        f, fe, e = int(hidden_size), int(expert_hidden), self._held
        fs = fe if shared_hidden is None else int(shared_hidden)
        # the experts' own width: the stream's, or the latent's they work in
        ce = self._moe_latent or c
        # an FFN's projections into its hidden width: gate and up, or up
        ins = ("gate", "up") if self._gated else ("up",)
        n = self._streams
        get = self.params.get
        with self.name_scope():
            self.embed = get("embed", shape=(self._vocab, c))
            self.final_norm = get("final_norm", shape=(c,), init="ones")
            if not self._tied:
                self.head = get("head", shape=(self._vocab, c))
            for i, (attn, ffn) in enumerate(self._kinds):
                subs = [sub for sub, kind in (("attn", attn), ("ffn", ffn))
                        if kind is not None]
                shapes = {f"{sub}_norm": (c,) for sub in subs}
                if self._sandwich:
                    shapes.update(attn_out_norm=(c,), ffn_out_norm=(c,))
                if attn == "latent_attention":
                    la, h = self._latent, self._heads
                    shapes.update(
                        q_a=(la["q_rank"], c), q_a_norm=(la["q_rank"],),
                        q_b=(h * (la["nope_dim"] + la["rope_dim"]),
                             la["q_rank"]),
                        kv_a=(la["kv_rank"] + la["rope_dim"], c),
                        kv_norm=(la["kv_rank"],),
                        kv_b=(h * (la["nope_dim"] + la["v_dim"]),
                              la["kv_rank"]),
                        o=(c, h * la["v_dim"]))
                elif attn == "mamba" and self._ssm["version"] == 2:
                    ma = self._ssm
                    ex, conv = self._mamba2_widths()
                    shapes.update(
                        in_proj=(ex + conv + ma["heads"], c),
                        conv_w=(ma["d_conv"], conv), conv_bias=(conv,),
                        dt_bias=(ma["heads"],), a_log=(ma["heads"],),
                        d_skip=(ma["heads"],), mamba_norm=(ex,),
                        out_proj=(c, ex))
                elif attn == "mamba":
                    ma = self._ssm
                    ex, ns = ma["expand"] * c, ma["d_state"]
                    shapes.update(
                        in_proj=(2 * ex, c), conv_w=(ma["d_conv"], ex),
                        conv_bias=(ex,), x_proj=(ma["dt_rank"] + 2 * ns, ex),
                        dt_norm=(ma["dt_rank"],), b_norm=(ns,), c_norm=(ns,),
                        dt_proj=(ex, ma["dt_rank"]), dt_bias=(ex,),
                        a_log=(ns, ex), d_skip=(ex,), out_proj=(c, ex))
                elif attn is not None:
                    shapes.update(q=(hq, c), k=(hkv, c), v=(hkv, c),
                                  o=(c, hq))
                    if self._qk_norm:
                        shapes.update(q_norm=(d,), k_norm=(d,))
                if n > 1:
                    for sub in subs:
                        shapes.update({
                            f"hc_{sub}_w": (2 * n + n * n, n * c),
                            f"hc_{sub}_scale": (3,),
                            f"hc_{sub}_bias": (2 * n + n * n,)})
                if ffn == "dense":
                    shapes.update({k: (f, c) for k in ins}, down=(c, f))
                elif ffn == "sparse":
                    shapes.update(
                        router=(self._experts, c),
                        router_bias=(self._experts,),
                        **{f"experts_{k}": (e, ce, fe) for k in ins},
                        experts_down=(e, fe, ce),
                        **{f"shared_{k}": (fs, c) for k in ins},
                        shared_down=(c, fs))
                    if self._moe_latent:
                        shapes.update(latent_down=(ce, c), latent_up=(c, ce))
                for name, shape in shapes.items():
                    init = "ones" if name.endswith(("norm", "_scale",
                                                     "d_skip")) \
                        else ("zeros" if name.endswith(("bias", "a_log"))
                              else None)
                    setattr(self, f"layer{i}_{name}",
                            get(f"layer{i}_{name}", shape=shape, init=init))

    # -- what serving sizes the cache off ------------------------------------
    @property
    def max_length(self):
        return self._max_length

    def cache_groups(self, max_len):
        """The cache this block is served with, a dict per group of
        layers: ``layers``, ``heads`` (K/V heads), ``rows``, ``head_dim``
        and ``kind`` (``ops/kv_cache.py``; a ``latent`` group is one
        tensor of one ``row``-wide head). A ``state`` group declares the
        scan's state a layer as ``state`` rows of ``width`` (Mamba's
        ``d_state`` rows of its channels, Mamba-2's ``d_state x n_groups``
        rows of a group's channels), the convolution's ``taps`` of
        ``taps_width`` (the channels', or Mamba-2's ``x``, ``B`` and ``C``
        together) and the state's ``dtype``: float32 whatever the model is
        served in, because the scan sums into it at every step. Groups a
        model has no layer of are left out but
        keep their place in the order. A looped block's group holds
        ``passes`` x its layers, pass by pass."""
        rows = (int(max_len), min(self._window, int(max_len)), int(max_len))
        heads = (self._kv_heads, self._kv_heads, 1)
        widths = (self._head_dim, self._head_dim, self._latent.get("row"))
        # the three kinds of rows (``zip`` stops before the state group)
        groups = [dict(layers=n * self.passes, heads=h, rows=r, head_dim=w,
                       kind=kind)
                  for n, h, r, w, kind in zip(self._group_counts, heads,
                                              rows, widths, _KINDS)]
        ma = self._ssm
        if ma:
            state, width = self._state_layout()
            taps = self._mamba2_widths()[1] if ma["version"] == 2 else width
            groups.append(dict(
                layers=self._group_counts[_STATE], kind=_KINDS[_STATE],
                width=width, state=state, taps=ma["d_conv"] - 1,
                taps_width=taps, dtype="float32"))
        return [g for g in groups if g["layers"]]

    def step_weight_bytes(self, slots):
        """Parameter bytes a decode step of ``slots`` slots reads, as they
        are held: every layer's and the final norm's once a pass, the head
        once and one table row a slot. None where a step reads a share of
        them that routing decides (a ``sparse`` layer)."""
        if any(ffn == "sparse" for _, ffn in self._kinds):
            return None
        nbytes = {n: int(math.prod(p.shape)) * jnp.dtype(p.dtype).itemsize
                  for n, p in self._reg_params.items()}
        stack = sum(b for n, b in nbytes.items()
                    if n.startswith("layer") or n == "final_norm")
        table = nbytes["embed"]
        return self.passes * stack + nbytes.get("head", table) \
            + int(slots) * table // self._vocab

    @property
    def held_expert_bytes(self):
        """Bytes of one held expert's weights as they are held (every
        sparse layer's are alike), or None where no layer is sparse: what
        a step reads of a layer for each held expert it hits."""
        names = ("experts_gate", "experts_up", "experts_down")
        for i, (_, ffn) in enumerate(self._kinds):
            if ffn == "sparse":
                ps = [self._reg_params[f"layer{i}_{n}"] for n in names
                      if f"layer{i}_{n}" in self._reg_params]
                return sum(int(math.prod(p.shape[1:]))
                           * jnp.dtype(p.dtype).itemsize for p in ps)
        return None

    # -- the arithmetic, over plain arrays -------------------------------------
    def _arrays(self):
        """Parameter NDArrays in a fixed order, and how to name them."""
        names = sorted(self._reg_params)
        return names, [self._reg_params[n].data() for n in names]

    def _layer(self, p, i):
        pre = f"layer{i}_"
        return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}

    def _sub(self, lp, x, name, f):
        """Sub-layer ``f`` (``attn`` or ``ffn``) under the block's
        residual rule: ``x`` is the stream, or the ``streams`` streams
        (n, ..., C)."""
        norm = lambda a: rms_norm(a, lp[name + "_norm"], self._eps)
        if self._sandwich:
            g = lambda u: rms_norm(f(norm(u)), lp[name + "_out_norm"],
                                   self._eps)
        else:
            g = (lambda u: f(norm(u))) if self._pre_norm \
                else (lambda u: norm(f(u)))
        if self._streams == 1:
            return x + g(x)
        with jax.named_scope("hyper_connection"):
            pre, post, res = hyper_connection.coefficients(
                x, lp[f"hc_{name}_w"], lp[f"hc_{name}_scale"],
                lp[f"hc_{name}_bias"], **self._hc)
            u = hyper_connection.read(x, pre)
        y = g(u)
        with jax.named_scope("hyper_connection"):
            return hyper_connection.write(x, res, post, y)

    def _embed(self, p, tokens):
        """The stream a token enters on: its row, in every stream."""
        x = jnp.take(p["embed"], tokens, axis=0)
        return x if self._streams == 1 \
            else jnp.broadcast_to(x, (self._streams,) + x.shape)

    def _qkv(self, lp, x, positions, window):
        """``x`` (B, T, C) -> q (B, Hkv, G, T, D), k and v (B, Hkv, T, D),
        normed per head where the block norms them; rotated on window
        layers, and on full ones where ``rope_full`` says so."""
        b, t, _ = x.shape
        d, hkv = self._head_dim, self._kv_heads
        heads = lambda a, n: a.reshape(b, t, n, d).transpose(0, 2, 1, 3)
        q, k = heads(_mm(x, lp["q"]), self._heads), heads(_mm(x, lp["k"]),
                                                          hkv)
        if self._qk_norm:
            q = rms_norm(q, lp["q_norm"], self._eps)
            k = rms_norm(k, lp["k_norm"], self._eps)
        v = heads(_mm(x, lp["v"]), hkv)
        if window or self._rope_full:
            q = rope(q, positions[:, None, :], self._theta)
            k = rope(k, positions[:, None, :], self._theta)
        return q.reshape(b, hkv, self._heads // hkv, t, d), k, v

    def _merge(self, lp, out):
        """(B, Hkv, G, T, D) attended heads -> (B, T, C)."""
        b, hkv, g, t, d = out.shape
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, t, hkv * g * d)
        return _mm(out, lp["o"])

    def _attend_sequence(self, q, k, v, window, scale):
        """Causal attention of whole sequences, a block of queries at a
        time over the keys that block may see: all before it, or on a
        window layer those less than ``window`` behind."""
        t = q.shape[3]
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

    # -- latent attention ------------------------------------------------------
    def _latent_scale(self):
        """The softmax scale: ``(nope + rope) ** -0.5`` times the square of
        YaRN's ``0.1 mscale_all_dim ln(factor) + 1``."""
        la = self._latent
        ys = la["rope_scaling"]
        m = 0.1 * ys.get("mscale_all_dim", 0) * math.log(ys["factor"]) + 1.0
        return (la["nope_dim"] + la["rope_dim"]) ** -0.5 * m * m

    def _latent_rows(self, lp, x, positions):
        """``x`` (B, T, C), already normed -> the queries' two parts
        ``q_nope`` (B, T, H, nope) and ``q_rope`` (B, T, H, rope), rotated,
        and what is cached of a position, ``row`` (B, T, row) = ``[c |
        k_r | 0]``: the normed latent and the rotated shared key."""
        la, h = self._latent, self._heads
        b, t, _ = x.shape
        inv = yarn_inv_freq(la["rope_dim"], self._theta, **la["rope_scaling"])
        c_q = rms_norm(_mm(x, lp["q_a"]), lp["q_a_norm"], self._eps)
        q = _mm(c_q, lp["q_b"]).reshape(b, t, h, -1)
        q_nope, q_rope = q[..., :la["nope_dim"]], q[..., la["nope_dim"]:]
        q_rope = rope(q_rope.transpose(0, 2, 1, 3), positions[:, None, :],
                      self._theta, inv).transpose(0, 2, 1, 3)
        ckr = _mm(x, lp["kv_a"])
        c = rms_norm(ckr[..., :la["kv_rank"]], lp["kv_norm"], self._eps)
        k_r = rope(ckr[..., la["kv_rank"]:], positions, self._theta, inv)
        row = jnp.concatenate([c, k_r], axis=-1)
        return q_nope, q_rope, jnp.pad(
            row, ((0, 0), (0, 0), (0, la["row"] - row.shape[-1])))

    def _latent_expanded(self, lp, x, positions):
        """Whole sequences: the latent expanded to every head's key
        ``[k_nope | k_r]`` and value, causal attention over them. Returns
        the attention's output (B, T, C) and the cached rows
        (B, 1, T, row)."""
        la, h = self._latent, self._heads
        b, t, _ = x.shape
        q_nope, q_rope, row = self._latent_rows(lp, x, positions)
        with jax.named_scope("expand"):
            kv = _mm(row[..., :la["kv_rank"]], lp["kv_b"]).reshape(
                b, t, h, -1)
            k_r = row[..., la["kv_rank"]:la["kv_rank"] + la["rope_dim"]]
            k = jnp.concatenate(
                [kv[..., :la["nope_dim"]],
                 jnp.broadcast_to(k_r[:, :, None], (b, t, h, la["rope_dim"]))],
                axis=-1).transpose(0, 2, 1, 3)
            v = kv[..., la["nope_dim"]:].transpose(0, 2, 1, 3)
            q = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(
                0, 2, 1, 3)[:, :, None]
        with jax.named_scope("attend"):
            a = self._attend_sequence(q, k, v, 0, self._latent_scale())
        return self._merge(lp, a), row[:, None]

    def _latent_absorbed(self, lp, x, lens, cache, layer, at):
        """The decode step: ``x`` (S, 1, C), every slot's new token. The
        expansion's key half goes into the query and its value half into
        the output, and the ``H`` heads attend as ``H`` queries over the
        cached rows themselves (``kv_cache``'s ``latent`` kind). Returns
        the attention's output (S, 1, C) and the new rows (S, 1, 1, row)."""
        la, h = self._latent, self._heads
        q_nope, q_rope, row = self._latent_rows(lp, x, lens[:, None])
        w_kv = lp["kv_b"].reshape(h, la["nope_dim"] + la["v_dim"],
                                  la["kv_rank"])
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("sthd,hdr->sthr", q_nope,
                               w_kv[:, :la["nope_dim"]])
            q = jnp.concatenate([q_lat, q_rope], axis=-1)
            q = jnp.pad(q, ((0, 0), (0, 0), (0, 0),
                            (0, la["row"] - q.shape[-1])))
        with jax.named_scope("attend"):
            o_lat = kv_cache.attend_row(
                q, cache, None, layer, row[:, None], None, lens, at,
                "latent", la["row"], scale=self._latent_scale(),
                v_width=la["kv_rank"])
        with jax.named_scope("expand"):
            out = jnp.einsum("sthr,hdr->sthd", o_lat,
                             w_kv[:, la["nope_dim"]:])
        return _mm(out.reshape(out.shape[0], 1, -1), lp["o"]), row[:, None]

    # -- the state-space mixer -----------------------------------------------
    def _mamba_gates(self, lp, x):
        """The convolution's output ``x`` (..., E) -> what selects the
        scan there: ``dt`` (..., E) float32 and ``B``, ``C`` (..., N), each
        of ``W_x x``'s three parts RMS-normed with its own gain."""
        ma = self._ssm
        r, ns = ma["dt_rank"], ma["d_state"]
        dbc = _mm(x, lp["x_proj"])
        d = rms_norm(dbc[..., :r], lp["dt_norm"], self._eps)
        b = rms_norm(dbc[..., r:r + ns], lp["b_norm"], self._eps)
        c = rms_norm(dbc[..., r + ns:], lp["c_norm"], self._eps)
        dt = jax.nn.softplus(
            jnp.einsum("...i,oi->...o", d, lp["dt_proj"],
                       preferred_element_type=jnp.float32)
            + lp["dt_bias"].astype(jnp.float32))
        return dt, b, c

    def _mamba(self, lp, u, state=None, n=None):
        """The mixer over ``u`` (B, T, C), already normed. Whole sequences
        (``state`` None) of true length ``n`` (traced; ``T`` where None),
        or with ``state`` = (``h`` (S, N, E), ``taps`` (S, K - 1, E)) every
        slot's one token ``u`` (S, 1, C). Returns the mixer's output and
        the state after it: ``h``, ``taps``."""
        if self._ssm["version"] == 2:
            return self._mamba2(lp, u, state, n)
        xz = _mm(u, lp["in_proj"])
        x, z = jnp.split(xz, 2, axis=-1)
        a = -jnp.exp(lp["a_log"].astype(jnp.float32))
        if state is None:
            x, taps = state_space.conv_sequence(x, lp["conv_w"],
                                                lp["conv_bias"], n)
            dt, b, c = self._mamba_gates(lp, x)
            y, h = state_space.scan_sequence(x, dt, a, b, c, lp["d_skip"], n)
        else:
            h, taps = state
            x, taps = state_space.conv_step(x[:, 0], taps, lp["conv_w"],
                                            lp["conv_bias"])
            dt, b, c = self._mamba_gates(lp, x)
            y, h = state_space.scan_step(h, x, dt, a, b, c, lp["d_skip"])
            y = y[:, None]
        return _mm(y * jax.nn.silu(z), lp["out_proj"]), h, taps

    def _mamba2_widths(self):
        """A Mamba-2 mixer's channels ``E = heads x head_dim`` and what its
        convolution runs over, ``x``, ``B`` and ``C``: ``E + 2 G N``."""
        ma = self._ssm
        ex = ma["heads"] * ma["head_dim"]
        return ex, ex + 2 * ma["n_groups"] * ma["d_state"]

    def _state_layout(self):
        """The scan's state as a ``state`` group keeps it a layer and slot,
        ``(state, width)``: Mamba's ``(N, E)``; Mamba-2's ``(N G, E / G)``,
        ``ops/state_space.py``'s ``(N, G, Q)`` with its two outer axes
        merged (a reshape that moves no byte)."""
        ma = self._ssm
        if ma["version"] == 2:
            ex, _ = self._mamba2_widths()
            return ma["n_groups"] * ma["d_state"], ex // ma["n_groups"]
        return ma["d_state"], ma["expand"] * self._units

    def _mamba2(self, lp, u, state=None, n=None):
        """``_mamba`` of a Mamba-2 mixer (``ops/state_space.py``'s ``ssd_*``
        forms): ``[z, xBC, dt] = W_in u``, the causal convolution over all
        of ``xBC``, one ``dt`` and ``A`` a head, ``B`` and ``C`` a group of
        heads, the output gated by ``silu(z)`` and RMS-normed a group at
        a time."""
        ma = self._ssm
        ex, conv = self._mamba2_widths()
        g, ns = ma["n_groups"], ma["d_state"]
        zxd = _mm(u, lp["in_proj"])
        z, xbc, dt = jnp.split(zxd, [ex, ex + conv], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["a_log"].astype(jnp.float32))
        groups = lambda v: v.reshape(v.shape[:-1] + (g, ns))
        if state is None:
            xbc, taps = state_space.conv_sequence(xbc, lp["conv_w"],
                                                  lp["conv_bias"], n)
            x, b, c = jnp.split(xbc, [ex, ex + g * ns], axis=-1)
            y, h = state_space.ssd_sequence(x, dt, a, groups(b), groups(c),
                                            lp["d_skip"], n,
                                            chunk=ma["chunk"])
        else:
            h, taps = state
            xbc, taps = state_space.conv_step(xbc[:, 0], taps, lp["conv_w"],
                                              lp["conv_bias"])
            x, b, c = jnp.split(xbc, [ex, ex + g * ns], axis=-1)
            y, h = state_space.ssd_step(
                h.reshape(h.shape[0], ns, g, -1), x, dt[:, 0], a, groups(b),
                groups(c), lp["d_skip"])
            y = y[:, None]
        h = h.reshape(h.shape[0], ns * g, -1)
        y = group_rms_norm(y.astype(jnp.float32) * jax.nn.silu(
            z.astype(jnp.float32)), lp["mamba_norm"], g, self._eps)
        return _mm(y.astype(u.dtype), lp["out_proj"]), h, taps

    def _dense(self, lp, x, name=""):
        """The FFN ``name`` (``""`` a dense layer's, ``"shared_"`` the
        shared expert) over ``x`` (N, C): gated or not, as ``ffn_act``
        says."""
        if self._gated:
            return gated_ffn(x, lp[name + "gate"], lp[name + "up"],
                             lp[name + "down"])
        return _mm(self._act(_mm(x, lp[name + "up"])), lp[name + "down"])

    def _ffn(self, lp, x, live=None):
        """``x`` (N, C) -> the FFN's output and the routing's counts
        (None on a dense layer). Of a ``moe_latent`` block the experts
        work in the latent: the router reads ``x``, the chosen experts
        ``W_lat_down x``, and their sum goes back through ``W_lat_up``;
        the shared expert works at full width."""
        if "router" not in lp:
            return self._dense(lp, x), None
        with jax.named_scope("router"):
            logits = jnp.einsum("ni,ei->ne", x, lp["router"],
                                preferred_element_type=jnp.float32)
            idx, w = moe_route(logits, self._top_k, bias=lp["router_bias"],
                               scale=self._scale)
        u = x
        if self._moe_latent:
            with jax.named_scope("latent_down"):
                u = _mm(x, lp["latent_down"])
        with jax.named_scope("grouped_product"):
            y, counts = moe_held_ffn(
                u, idx, w, lp.get("experts_gate"), lp["experts_up"],
                lp["experts_down"], first_expert=self._first_expert,
                live=live, act=self._act, width=logits.shape[-1])
        if self._moe_latent:
            with jax.named_scope("latent_up"):
                y = _mm(y.astype(x.dtype), lp["latent_up"]).astype(
                    jnp.float32)
        with jax.named_scope("shared_expert"):
            y = y + self._dense(lp, x, "shared_").astype(jnp.float32)
        return y.astype(x.dtype), counts

    def _sequence(self, p, tokens, n=None, entry="forward"):
        """``tokens`` (B, T) -> the last layer's output (B, T, C) and
        per layer its cache planes: K and V (B, Hkv, T, D), the one
        (B, 1, T, row) of a latent layer, or a state-space layer's state
        (B, N, E) and taps (B, K - 1, E) after position ``n - 1`` (the
        true length of padded sequences, traced; ``T`` where None). Of a
        looped block (``_passes``) the output is the last pass's, normed,
        and a layer's planes are stacked over the passes, (R, B, ...)."""
        b, t = tokens.shape
        x = self._embed(p, tokens)
        positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

        def stack(x, _):
            planes = []
            for i, (attn, ffn) in enumerate(self._kinds):
                lp = self._layer(p, i)

                def attention(u):
                    if attn == "latent_attention":
                        with jax.named_scope("latent_attention"):
                            a, row = self._latent_expanded(lp, u, positions)
                        planes.append((row,))
                        return a
                    if attn == "mamba":
                        with jax.named_scope("state_space"):
                            a, *state = self._mamba(lp, u, n=n)
                        planes.append(tuple(state))
                        return a
                    window = self._window if attn == "sliding_attention" \
                        else 0
                    with jax.named_scope("attention"):
                        q, k, v = self._qkv(lp, u, positions, window)
                        a = self._merge(lp, self._attend_sequence(
                            q, k, v, window, 1.0 / math.sqrt(self._head_dim)))
                    planes.append((k, v))
                    return a

                if attn is None:
                    planes.append(())
                else:
                    x = self._sub(lp, x, "attn", attention)
                if ffn is not None:
                    x = self._sub(lp, x, "ffn", lambda u: self._ffn(
                        lp, u.reshape(b * t, -1))[0].reshape(b, t, -1))
            return x, planes

        x, planes = self._passes(p, stack, x, entry)
        return self._out(x), planes

    def _passes(self, p, stack, x, entry):
        """``stack(x, r)`` -> ``(x, out)``, the layers once with ``r`` the
        pass: once (``r`` 0), or of a looped block ``passes`` times in a
        program loop over the same weights, ``r`` traced, the final norm
        at the end of every pass. Returns the last ``x`` and ``out``, of a
        loop each of its arrays stacked over the passes."""
        if self.passes == 1:
            return stack(x, 0)
        from ... import telemetry

        telemetry.counter("mxtpu_loop_passes_total",
                          "pass loops traced, by the entry point that "
                          "traced them", entry=entry).inc()

        def one(x, r):
            with jax.named_scope("loop_pass"):
                x, out = stack(x, r)
                return rms_norm(x, p["final_norm"], self._eps), out

        return jax.lax.scan(one, x, jnp.arange(self.passes, dtype=jnp.int32))

    def _by_instance(self, arrays):
        """One group's per-layer arrays, each stacked over the passes
        (R, ...), as the cache holds them: (R x Lg, ...), pass by pass."""
        a = jnp.stack(arrays, axis=1)
        return a.reshape((-1,) + a.shape[2:])

    def _out(self, x):
        """The streams summed into the one the head reads."""
        return x if self._streams == 1 \
            else x.astype(jnp.float32).sum(axis=0).astype(x.dtype)

    def _logits(self, p, x):
        """The head over the last layer's output; a looped block's output
        is normed at the end of its last pass already."""
        if self.passes == 1:
            x = rms_norm(x, p["final_norm"], self._eps)
        return _mm(x, p["embed" if self._tied else "head"])

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
        is applied there and nowhere else) and, per cache group, its
        planes ``[Lg, H, T, W]`` of the whole bucket: K then V, or a
        latent group's one (positions from ``n`` on hold garbage that no
        true position attended), of a looped block every pass's planes in
        pass order; a state group's ``[Lg, N, E]`` and ``[Lg, K - 1, E]``,
        the state after position ``n - 1``."""
        group_of, counts = self._group_of, self._group_counts

        def fn(p, tok, n_true):
            x, planes = self._sequence(p, tok[None], n_true, "prefill")
            last = jax.lax.dynamic_index_in_dim(x[0], n_true - 1, axis=0,
                                                keepdims=False)
            out = [self._logits(p, last)]
            for g, count in enumerate(counts):
                if count:
                    mine = [planes[i] for i, (gi, _) in enumerate(group_of)
                            if gi == g]
                    out += [jnp.stack([a[0] for a in tensor])
                            if self.passes == 1 else
                            self._by_instance([a[:, 0] for a in tensor])
                            for tensor in zip(*mine)]
            return tuple(out)

        return self._run(fn, [tokens, n], "hybrid_decoder_prefill")

    def serve_step(self, tokens, cache_len, *caches):
        """Every slot one token on. ``tokens``/``cache_len`` (S,);
        ``caches`` the arrays ``[Lg, S, H, rows, W]`` of each cache group
        (K then V, or a latent group's one; a state group's ``[Lg, S, N,
        E]`` and ``[Lg, S, K - 1, E]``; groups in ``cache_groups`` order).
        Returns the logits (S, V), the ``step_counters`` as one int32
        vector (over the slots whose ``cache_len`` is not 0: a free
        slot's is), and the caches with each slot's new row written
        (``kv_cache.address``), a state group's with every layer's new
        state in the old one's place (``kv_cache.advance``). A looped
        block's passes read the caches at their own indices inside the
        loop and write nothing there: every pass's rows go in after it,
        in one ``kv_cache.write`` a tensor."""
        group_of = self._group_of
        present = [g for g, c in enumerate(self._group_counts) if c]

        def fn(p, tok, lens, *cs):
            lens = lens.astype(jnp.int32)
            cs = iter(cs)
            kv = {g: [next(cs) for _ in range(kv_cache.tensors(_KINDS[g]))]
                  for g in present}
            rowed = [g for g in present if g != _STATE]
            at = {g: kv_cache.address(lens, kv[g][0].shape[3], _KINDS[g])
                  for g in rowed}
            x = self._embed(p, tok)[..., None, :]          # (S, 1, C)
            live = lens > 0
            # the routing's counts over the sparse layers (a loop has none)
            totals = dict.fromkeys(("routed_here", "experts_hit",
                                    "load_max", "sparse"), 0)

            def stack(x, r):
                """The layers once, pass ``r``: each reads the cache at its
                own index of its group in that pass and hands out its new
                rows, which nothing writes before the last pass."""
                new = {g: tuple([] for _ in kv[g]) for g in rowed}
                for i, (attn, ffn) in enumerate(self._kinds):
                    lp = self._layer(p, i)
                    g, j = group_of[i]
                    at_layer = None if attn is None \
                        else j + r * self._group_counts[g]

                    def attention(u):
                        if g == _STATE:
                            # the layer reads its own state of the arrays
                            # the layer before it handed on, and replaces
                            # it there
                            with jax.named_scope("state_space"):
                                a, *state = self._mamba(
                                    lp, u, state=[c[j] for c in kv[g]])
                                kv[g] = [kv_cache.advance(c, j, s)
                                         for c, s in zip(kv[g], state)]
                            return a
                        if g == _LATENT:
                            with jax.named_scope("latent_attention"):
                                a, *rows = self._latent_absorbed(
                                    lp, u, lens, kv[g][0], at_layer, at[g])
                        else:
                            with jax.named_scope("attention"):
                                q, *rows = self._qkv(
                                    lp, u, lens[:, None],
                                    self._window * (g == _RING))
                                a = kv_cache.attend_row(
                                    q[:, :, :, 0], *kv[g], at_layer, *rows,
                                    lens, at[g], _KINDS[g], self._head_dim)
                                a = self._merge(lp, a[:, :, :, None])
                        for rows_new, row in zip(new[g], rows):
                            rows_new.append(row)
                        return a

                    counts = []

                    def ffn_step(u):
                        y, c = self._ffn(lp, u[:, 0], live=live)
                        counts.append(c)
                        return y[:, None]

                    if attn is not None:
                        x = self._sub(lp, x, "attn", attention)
                    if ffn is not None:
                        x = self._sub(lp, x, "ffn", ffn_step)
                    if counts and counts[0] is not None:
                        totals["sparse"] += 1
                        for name in ("routed_here", "experts_hit"):
                            totals[name] = totals[name] + counts[0][name]
                        totals["load_max"] = jnp.maximum(
                            totals["load_max"], counts[0]["load_max"])
                return x, new

            x, new = self._passes(p, stack, x, "step")
            logits = self._logits(p, self._out(x)[:, 0])
            counters = jnp.stack([jnp.asarray(v, jnp.int32) for v in (
                totals["routed_here"],
                live.sum() * self._top_k * totals["sparse"],
                totals["experts_hit"], totals["load_max"])])
            out = [logits, counters]
            for g in present:
                out += kv[g] if g == _STATE else [
                    kv_cache.write(cache, rows_new if self.passes == 1
                                   else self._by_instance(rows_new),
                                   at[g][0])
                    for cache, rows_new in zip(kv[g], new[g])]
            return tuple(out)

        return self._run(fn, [tokens, cache_len, *caches],
                         "hybrid_decoder_step")


#: layer data of the published configs this decoder is built from; the
#: callers' keyword arguments override any of it (depth, the experts
#: held, the vocabulary slice: chipbench/configs/*.json say which)
_SPECS = {
    # https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/
    # config.json: 48 layers ``LLLG``, layer 0 dense, 128 experts top-8
    # + 1 shared, window 128
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
    # https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/
    # config.json (``xing4_0``): 40 latent-attention layers (q rank 768,
    # one 512 + 64 row a position, 32 heads of 128 + 64 / 128, YaRN x64
    # over 4096), layers 0-1 dense, 64 experts top-4 + 1 shared, four
    # hyper-connection streams of 20 Sinkhorn rounds, pre-norm
    "xing4_29b": dict(
        vocab_size=131072, units=3584, num_heads=32, num_kv_heads=32,
        head_dim=192, hidden_size=9216, rope_theta=1e4, eps=1e-6,
        num_experts=64, experts_held=64, experts_per_token=4,
        expert_hidden=1024, routed_scale=2.0, num_layers=40, pattern="M",
        dense_layers=2, pre_norm=True, streams=4,
        hc=dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0)),
        latent=dict(q_rank=768, kv_rank=512, nope_dim=128, rope_dim=64,
                    v_dim=128, rope_scaling=dict(
                        factor=64, original_max_position_embeddings=4096,
                        beta_fast=32, beta_slow=1, mscale_all_dim=1))),
    "xing4_tiny": dict(
        vocab_size=97, units=64, num_heads=4, num_kv_heads=4, head_dim=24,
        hidden_size=96, rope_theta=1e4, eps=1e-6, num_experts=8,
        experts_held=4, experts_per_token=2, expert_hidden=32,
        routed_scale=2.0, num_layers=3, pattern="M", dense_layers=1,
        pre_norm=True, streams=4,
        hc=dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0)),
        latent=dict(q_rank=24, kv_rank=32, nope_dim=16, rope_dim=8,
                    v_dim=16, rope_scaling=dict(
                        factor=4, original_max_position_embeddings=16,
                        beta_fast=32, beta_slow=1, mscale_all_dim=1)),
        max_length=128),
    # https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json
    # (``jamba``): 28 pre-norm layers, attention (20 query heads over ONE
    # K/V head of 128, no positions, no per-head norm) where ``i % 14 ==
    # 7``, a selective state-space mixer (5120 channels, state 16, dt rank
    # 160, convolution over 4) everywhere else, every FFN dense 8192
    # (``num_experts`` 1), the logits off the tied embedding table
    "jamba2_3b": dict(
        vocab_size=65536, units=2560, num_heads=20, num_kv_heads=1,
        head_dim=128, hidden_size=8192, eps=1e-6, num_layers=28,
        attn_layer_period=14, attn_layer_offset=7, dense_layers=28,
        pre_norm=True, qk_norm=False, tie_embeddings=True,
        mamba=dict(expand=2, d_state=16, dt_rank=160, d_conv=4)),
    "jamba_tiny": dict(
        vocab_size=97, units=64, num_heads=4, num_kv_heads=1, head_dim=16,
        hidden_size=96, eps=1e-6, num_layers=4, attn_layer_period=4,
        attn_layer_offset=1, dense_layers=4, pre_norm=True, qk_norm=False,
        tie_embeddings=True,
        mamba=dict(expand=2, d_state=8, dt_rank=4, d_conv=4),
        max_length=128),
    # https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json
    # (``ouro``): 48 full-attention layers of 16 heads of 128 (as many K/V
    # heads), rotary positions at theta 1e6 on every layer, no per-head
    # norm, sandwich norms, every FFN dense 5632, an untied head; the
    # stack run ``total_ut_steps`` 4 times a token
    "ouro_2p6b": dict(
        vocab_size=49152, units=2048, num_heads=16, num_kv_heads=16,
        head_dim=128, hidden_size=5632, rope_theta=1e6, eps=1e-6,
        num_layers=48, pattern="G", dense_layers=48, qk_norm=False,
        sandwich=True, rope_full=True, passes=4, max_length=65536),
    "ouro_tiny": dict(
        vocab_size=97, units=64, num_heads=4, num_kv_heads=4, head_dim=16,
        hidden_size=96, rope_theta=1e6, eps=1e-6, num_layers=3,
        pattern="G", dense_layers=3, qk_norm=False, sandwich=True,
        rope_full=True, passes=4, max_length=128),
    # https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/
    # blob/main/config.json (``nemotron_h``): 88 pre-norm layers that are
    # each ONE of a Mamba-2 mixer (``M``: 128 heads of 64, 8 groups of
    # state 128, convolution over 4), a latent expert layer (``E``: 512
    # experts top-22 of relu2 2688 in a 1024 latent, a relu2 shared expert
    # 5376 at full width, scaling 5) or attention (``*``: 32 query heads
    # over 2 K/V heads of 128, no positions, no per-head norm); an untied
    # head
    "nemotron3_super": dict(
        vocab_size=131072, units=4096, num_heads=32, num_kv_heads=2,
        head_dim=128, hidden_size=2688, eps=1e-5, num_experts=512,
        experts_held=512, experts_per_token=22, expert_hidden=2688,
        shared_hidden=5376, moe_latent=1024, routed_scale=5.0,
        hybrid_override_pattern=(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        pre_norm=True, qk_norm=False, ffn_act="relu2",
        mamba=dict(version=2, heads=128, head_dim=64, n_groups=8,
                   d_state=128, d_conv=4, chunk=128),
        max_length=262144),
    "nemotron_h_tiny": dict(
        vocab_size=97, units=64, num_heads=4, num_kv_heads=2, head_dim=16,
        hidden_size=96, eps=1e-5, num_experts=16, experts_held=8,
        experts_per_token=4, expert_hidden=32, shared_hidden=48,
        moe_latent=24, routed_scale=5.0, hybrid_override_pattern="MEM*EME",
        pre_norm=True, qk_norm=False, ffn_act="relu2",
        mamba=dict(version=2, heads=8, head_dim=8, n_groups=2, d_state=16,
                   d_conv=4, chunk=8),
        max_length=128),
}
_PATTERN = {"G": "full_attention", "L": "sliding_attention",
            "M": "latent_attention"}
#: Nemotron-H's ``hybrid_override_pattern``: a layer is ONE of a Mamba
#: mixer, a sparse FFN or attention
_HYBRID = {"M": ("mamba", None), "E": (None, "sparse"),
           "*": ("full_attention", None)}


def get_decoder(model_name="exaone_moe", **kwargs):
    """Decoder factory (``get_gpt``'s analog for the data-built decoder).
    ``num_layers``, ``pattern`` (``L`` a window layer, ``G`` a full one,
    ``M`` a latent one, repeated) or ``attn_layer_period`` /
    ``attn_layer_offset`` (a full-attention layer where ``i % period ==
    offset``, a ``mamba`` layer everywhere else: Jamba's rule), and
    ``dense_layers`` (the leading layers whose FFN is dense) expand to the
    per-layer kinds unless ``layer_types`` / ``mlp_layer_types`` are
    given. A ``hybrid_override_pattern`` (Nemotron-H's: a character a
    layer, ``M`` a mixer alone, ``E`` a sparse FFN alone, ``*`` attention
    alone) is the whole stack, its depth too."""
    if model_name not in _SPECS:
        raise ValueError(f"unknown decoder spec {model_name!r}; "
                         f"known {sorted(_SPECS)}")
    spec = dict(_SPECS[model_name])
    spec.update(kwargs)
    if "hybrid_override_pattern" in spec:
        kinds = [_HYBRID[k] for k in spec.pop("hybrid_override_pattern")]
        spec.setdefault("layer_types", [a for a, _ in kinds])
        spec.setdefault("mlp_layer_types", [f for _, f in kinds])
        return HybridDecoder(**spec)
    n = int(spec.pop("num_layers"))
    dense = int(spec.pop("dense_layers"))
    if "attn_layer_period" in spec:
        period = int(spec.pop("attn_layer_period"))
        offset = int(spec.pop("attn_layer_offset"))
        kinds = ["full_attention" if i % period == offset else "mamba"
                 for i in range(n)]
    else:
        pattern = spec.pop("pattern")
        kinds = [_PATTERN[pattern[i % len(pattern)]] for i in range(n)]
    spec.setdefault("layer_types", kinds)
    spec.setdefault("mlp_layer_types", [
        "dense" if i < dense else "sparse" for i in range(n)])
    return HybridDecoder(**spec)
