"""The selective state-space layer's two recurrences (Mamba, arXiv:2312.00752,
as Jamba's mixer has it, arXiv:2403.19887): a causal depthwise convolution
over the last ``K`` inputs of a channel, and the selective scan

    h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t        (N x E)
    y_t = C_t . h_t + D * x_t                                      (E)

each in a whole-sequence form (prefill, ``forward``) and a one-token form
(the decode step), which take and return the state a sequence carries from
one position to the next: the scan's ``h`` and the convolution's last
``K - 1`` inputs (its ``taps``). Plain ``jax.numpy`` over arrays. Mamba-2's
scan, a scalar decay a head and ``B``/``C`` a group of heads, has its own
two forms below (``ssd_step``, ``ssd_sequence``: the state-space dual form
by chunks); the convolution is shared.

Layout: the ``E`` channels are the MINOR axis everywhere (``h`` is
``(..., N, E)``, the taps ``(..., K - 1, E)``, ``A`` ``(N, E)``, the
convolution's weight ``(K, E)``): ``E`` is whole 128-lane tiles on the TPU,
where a minor axis of 16 or 3 would be padded to 128.

Precision: ``dt``, ``exp(dt A)``, the recurrence, ``h`` and ``y`` are
float32 whatever the activations' type; the convolution multiplies and sums
in float32; ``y`` and the convolution's output are rounded to the
activations' type where they are handed on.

Padding: the whole-sequence forms take the TRUE length ``n`` of a sequence
padded to ``T`` and return the state after position ``n - 1``. A position
at or past ``n`` is given ``dt = 0``, so ``exp(0) = 1`` keeps ``h`` and the
input term is 0; the taps are the inputs at ``n - K + 1 .. n - 1``, zeros
before position 0. (Outputs at such positions are garbage no true position
depends on.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions of a chunk of the whole-sequence scan: the discretised
#: ``(chunk, N, E)`` float32 tensors live a chunk at a time (21 MB each at
#: 64 x 16 x 5120), the state is carried from chunk to chunk
CHUNK = 64


def conv_sequence(x, w, b, n=None):
    """``x`` (B, T, E), ``w`` (K, E), ``b`` (E,): ``silu(b + sum_k w[k] *
    x[t - K + 1 + k])`` (B, T, E) with zeros before the sequence, and the
    taps (B, K - 1, E) a sequence of true length ``n`` (traced; ``T``
    where None) leaves behind."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    y = b.astype(jnp.float32) + sum(
        wf[j] * padded[:, j:j + t].astype(jnp.float32) for j in range(k))
    # position p lies at p + K - 1 of ``padded``: the K - 1 before ``n``
    # start at ``n``
    taps = padded[:, t:] if n is None else jax.lax.dynamic_slice_in_dim(
        padded, n, k - 1, axis=1)
    return jax.nn.silu(y).astype(x.dtype), taps


def conv_step(x, taps, w, b):
    """One token: ``x`` (S, E) and the taps (S, K - 1, E) before it ->
    the convolution's output (S, E) and the taps after it."""
    window = jnp.concatenate([taps, x[:, None].astype(taps.dtype)], axis=1)
    y = b.astype(jnp.float32) + jnp.einsum(
        "ske,ke->se", window.astype(jnp.float32), w.astype(jnp.float32))
    return jax.nn.silu(y).astype(x.dtype), window[:, 1:]


def _discretise(x, dt, a, b):
    """``exp(dt (x) A)`` and ``(dt * x) (x) B``, both (..., N, E) float32,
    of ``x``/``dt`` (..., E) and ``b`` (..., N)."""
    xf, bf = x.astype(jnp.float32), b.astype(jnp.float32)
    return (jnp.exp(dt[..., None, :] * a),
            (dt * xf)[..., None, :] * bf[..., :, None])


def _combine(left, right):
    """Two stretches of the recurrence ``h -> a h + s`` in one."""
    (a1, s1), (a2, s2) = left, right
    return a1 * a2, a2 * s1 + s2


def scan_sequence(x, dt, a, b, c, d, n=None, h0=None, chunk=CHUNK):
    """The selective scan over whole sequences: ``x`` (B, T, E) the
    convolution's output, ``dt`` (B, T, E) float32 (after its softplus),
    ``a`` (N, E) float32 (negative), ``b``/``c`` (B, T, N), ``d`` (E,).
    Returns ``y`` (B, T, E) in ``x``'s type and the state ``h`` (B, N, E)
    float32 after position ``n - 1`` (``T - 1`` where ``n`` is None),
    starting from ``h0`` (zeros where None).

    By chunks of ``chunk`` positions: inside a chunk the recurrence is an
    associative scan over the discretised ``(chunk, N, E)`` tensors, and
    ``h`` is carried from chunk to chunk."""
    bsz, t, e = x.shape
    if n is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None] < n, dt, 0.0)
    pad = -t % chunk
    if pad:     # dt = 0 there: the state stays as position T - 1 left it
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))
    chunks = lambda v: v.reshape(bsz, -1, chunk, v.shape[-1]).swapaxes(0, 1)
    h = jnp.zeros((bsz, a.shape[0], e), jnp.float32) if h0 is None else h0

    def one_chunk(h, inputs):
        xq, dtq, bq, cq = inputs                        # (B, Q, E | N)
        decay, s = jax.lax.associative_scan(
            _combine, _discretise(xq, dtq, a, bq), axis=1)
        hq = decay * h[:, None] + s                     # (B, Q, N, E)
        y = jnp.einsum("bqne,bqn->bqe", hq, cq.astype(jnp.float32))
        return hq[:, -1], y

    h, y = jax.lax.scan(one_chunk, h, tuple(map(chunks, (x, dt, b, c))))
    y = y.swapaxes(0, 1).reshape(bsz, t + pad, e)[:, :t]
    return (y + d.astype(jnp.float32) * x[:, :t].astype(jnp.float32)
            ).astype(x.dtype), h


def scan_step(h, x, dt, a, b, c, d):
    """One token: the state ``h`` (S, N, E) float32 before it, ``x``/``dt``
    (S, E), ``b``/``c`` (S, N) -> ``y`` (S, E) in ``x``'s type and the
    state after it."""
    decay, s = _discretise(x, dt, a, b)
    h = decay * h + s
    y = jnp.einsum("sne,sn->se", h, c.astype(jnp.float32))
    return (y + d.astype(jnp.float32) * x.astype(jnp.float32)
            ).astype(x.dtype), h


# -- Mamba-2: a scalar decay a head, B and C shared by a group of heads -----
#
# The state-space duality's layer (Mamba-2, arXiv:2405.21060, as
# Nemotron-H's mixer has it): ``H`` heads of ``P`` channels (``E = H P``,
# head-major), each head with ONE ``dt`` and ONE ``A`` a position, and
# ``B``/``C`` of ``N`` in ``G`` groups, group ``g`` read by the ``H / G``
# heads of its ``Q = E / G`` channels:
#
#     h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t                  (N x P)
#     y_t = C_t . h_t + D x_t                                       (P)
#
# The state is kept ``(..., N, G, Q)``: a group's channels minor, the
# groups next (one tile of rows at ``G`` = 8), ``N`` outside both. A
# step's update is then the state times what broadcasts along ``N`` (a
# channel's decay and input, laid ``(G, Q)`` as the state's last two axes
# are) and along ``Q`` (the group's ``B`` and ``C``), and the compiler
# fuses it whole: nothing of the state's size is built beside it. (Laid
# ``(N, E)`` as Mamba's is, ``B`` and ``C`` are spread over ``E`` first;
# laid ``(G, N, Q)``, the decay and the input are broadcast across the
# rows of a tile: either way two more state-sized tensors a layer, as
# the TPU compiler lays them out.)


def _per(v, width):
    """``v`` (..., k) -> (..., width): each entry repeated ``width // k``
    times, as head ``i``'s scalar covers its ``P`` channels."""
    return jnp.repeat(v, width // v.shape[-1], axis=-1)


def ssd_step(h, x, dt, a, b, c, d):
    """One token: the state ``h`` (S, N, G, Q) float32 before it, ``x``
    (S, E), ``dt`` (S, H) float32 (after its softplus), ``a`` (H,) float32
    (negative), ``b``/``c`` (S, G, N), ``d`` (H,) -> ``y`` (S, E) in
    ``x``'s type and the state after it. Elementwise over the state and a
    sum over ``N``: every product is float32."""
    s, _, g, q = h.shape
    e = g * q
    chan = lambda v: _per(v, e).reshape(-1, 1, g, q)        # a channel's
    lanes = lambda v: v.astype(jnp.float32).swapaxes(1, 2)[..., None]
    xf = x.astype(jnp.float32).reshape(s, 1, g, q)
    h = jnp.exp(chan(dt * a)) * h + (chan(dt) * xf) * lanes(b)
    y = (h * lanes(c)).sum(axis=1, keepdims=True) \
        + chan(d.astype(jnp.float32)[None]) * xf
    return y.reshape(s, e).astype(x.dtype), h


def ssd_sequence(x, dt, a, b, c, d, n=None, h0=None, chunk=128):
    """The scan over whole sequences by the state-space dual form: ``x``
    (B, T, E), ``dt`` (B, T, H) float32, ``a`` (H,), ``b``/``c`` (B, T,
    G, N), ``d`` (H,). Returns ``y`` (B, T, E) in ``x``'s type and the
    state ``h`` (B, N, G, Q) float32 after position ``n - 1`` (``T - 1``
    where ``n`` is None), starting from ``h0`` (zeros where None).

    By chunks of ``chunk`` positions, a ``lax.scan`` carrying the state:
    inside a chunk ``y_i = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j)
    dt_j x_j`` (a masked-decay ``C B^T`` product a head, ``cum`` the running
    sum of ``dt A``), plus what the state before the chunk gives,
    ``exp(cum_i) C_i . h``; the state after it is ``exp(cum_Q) h + sum_j
    exp(cum_Q - cum_j) dt_j x_j (x) B_j``. What a chunk holds is ``(H, Q,
    Q)`` and the state, never ``(Q, N, E)``. Products of float32 operands
    are summed at ``HIGHEST``."""
    bsz, t, e = x.shape
    hh, (g, ns) = dt.shape[-1], b.shape[-2:]
    p, r = e // hh, hh // g
    if n is not None:
        dt = jnp.where(jnp.arange(t)[None, :, None] < n, dt, 0.0)
    pad = -t % chunk
    if pad:     # dt = 0 there: the state stays as position T - 1 left it
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (x, dt, b, c))
    nq = (t + pad) // chunk
    chunks = lambda v: v.reshape((bsz, nq, chunk) + v.shape[2:]).swapaxes(
        0, 1)
    h = jnp.zeros((bsz, ns, g, e // g), jnp.float32) if h0 is None else h0
    ein = lambda spec, *ops: jnp.einsum(
        spec, *ops, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(h, inputs):
        xq, dtq, bq, cq = inputs           # (B, Q, E) (B, Q, H) (B, Q, G, N)
        xq = xq.astype(jnp.float32).reshape(bsz, chunk, g, r, p)
        bq, cq = bq.astype(jnp.float32), cq.astype(jnp.float32)
        dth = dtq.swapaxes(1, 2).reshape(bsz, g, r, chunk)    # positions minor
        cum = jnp.cumsum(dth * a.reshape(g, r, 1), axis=-1)
        decay = jnp.exp(jnp.where(causal, cum[..., :, None]
                                  - cum[..., None, :], -jnp.inf))
        cb = ein("bign,bjgn->bgij", cq, bq)            # (B, G, Qi, Qj)
        y = ein("bgrij,bjgrp->bigrp",
                decay * cb[:, :, None] * dth[..., None, :], xq)
        hg = h.reshape(bsz, ns, g, r, p)
        y = y + jnp.moveaxis(jnp.exp(cum), -1, 1)[..., None] * ein(
            "bign,bngrp->bigrp", cq, hg)
        last = cum[..., -1]                            # (B, G, R)
        into = jnp.exp(last[..., None] - cum) * dth    # (B, G, R, Q)
        hg = jnp.exp(last)[:, None, :, :, None] * hg + ein(
            "bjgn,bjgrp->bngrp", bq, jnp.moveaxis(into, -1, 1)[..., None]
            * xq)
        return hg.reshape(h.shape), y.reshape(bsz, chunk, e)

    h, y = jax.lax.scan(one_chunk, h, tuple(map(chunks, (x, dt, b, c))))
    y = y.swapaxes(0, 1).reshape(bsz, t + pad, e)[:, :t]
    return (y + _per(d.astype(jnp.float32), e) * x[:, :t].astype(
        jnp.float32)).astype(x.dtype), h
