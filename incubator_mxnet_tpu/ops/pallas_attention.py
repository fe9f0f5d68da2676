"""Pallas flash attention — the hand-tuned custom-kernel layer.

Capability parity with the reference's RTC/custom-kernel tier
(``src/common/rtc.cc`` runtime-compiled CUDA + ``src/operator/fusion/``
NVRTC pointwise fusion): where the reference lets users and the framework
drop to hand-written CUDA, this framework drops to Pallas TPU kernels
(SURVEY.md §7 step 10 "Pallas blockwise attention").

The forward kernel streams K/V blocks through VMEM with an online-softmax
accumulator, so the (T_q, T_k) score matrix is never materialised in HBM —
the flash-attention recipe block-tiled for the MXU (q·kᵀ and p·v per
(bq, bk) tile) with fp32 accumulators on the VPU. Per-sample key lengths
(BERT ``valid_length``) are supported natively via an SMEM scalar, and the
causal mask uses the bottom-right alignment of the XLA reference
(``tril(k=tk-tq)``) so decode-style tq != tk calls agree.

Backward (round 4) is a pair of streaming Pallas kernels — dQ over KV
blocks, dK/dV over Q blocks — that recompute the probabilities per block
from the saved log-sum-exp statistic, so no (T_q, T_k) score matrix is
ever materialised in either direction: O(T) memory end to end, the
FlashAttention-2 backward recipe. The same kernels serve as the per-
rotation block engine of the differentiable Pallas ring
(``parallel/ring_attention.ring_attention_pallas``).

On non-TPU backends the same kernel runs through the Pallas interpreter
(``interpret=True``) so correctness tests run on the CPU mesh.

Measured on v5e-1 (bf16, causal, D=64; see PROFILE.md). Forward: 1.7x
over the XLA chain at T=2048, ~60x at T=8192 (XLA spills), 2.6x at
T=16384 where the XLA path OOMs without remat. Backward: 1.8x at T=2048,
4.7x at T=4096 over the XLA backward. Which of the kernels and the dense
chain a call takes: ``_kernel_pays``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register


def pallas_available() -> bool:
    """True if the default backend is a TPU (compiled Pallas path). A
    backend that fails to initialise raises — it is never read as "no
    TPU", which would send a kernel to the interpreter in silence."""
    return jax.default_backend() == "tpu"


def _flash_fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref=None,
                      *, bq, bk, t_k, t_valid, tq_valid, scale, causal,
                      n_heads, cache_offset=False):
    from jax import lax

    qi = q_ref[0]                                # native dtype: bf16 stays
    d = qi.shape[-1]                             # on the fast MXU path
    i = _pl().program_id(1)
    # whole lengths vector lives in SMEM (Mosaic rejects rank-1 sub-
    # blocking); index the batch entry for this (batch*head) program
    klen = len_ref[_pl().program_id(0) // n_heads]
    # dtype-aware matmul precision: bf16 inputs take the native MXU pass
    # (DEFAULT); f32 inputs need HIGHEST or Mosaic truncates the
    # multiplies to bf16 (~1e-2 abs error vs the XLA reference)
    prec = (jax.lax.Precision.DEFAULT
            if qi.dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)

    m0 = jnp.full((bq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    nblocks = t_k // bk
    # bottom-right causal alignment, matching the XLA reference
    # tril(k = tk - tq): col <= row + (tk - tq). The cache-offset path
    # (KV-cache decode: K/V are a [0, klen) prefix of a max_len buffer)
    # aligns the diagonal to the PER-SAMPLE valid length instead of the
    # static buffer end: query row i sits at absolute position
    # klen - tq + i and attends keys [0, klen - tq + i] exactly.
    diag_off = (klen - tq_valid) if cache_offset else (t_valid - tq_valid)

    def body(j, carry):
        m, l, acc = carry
        pl = _pl()
        k = k_ref[0, pl.ds(j * bk, bk), :]                   # (bk, d)
        v = v_ref[0, pl.ds(j * bk, bk), :]
        # qk in the input dtype with fp32 accumulation (MXU-native);
        # explicit precision because the package-global 'highest' default
        # is rejected by Mosaic for bf16 contractions
        s = jax.lax.dot_general(
            qi, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec) * scale                          # (bq, bk)
        cols = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = cols < jnp.minimum(t_valid, klen)
        if causal:
            rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (cols <= rows + diag_off)
        s = jnp.where(valid, s, -jnp.inf)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # rows with no valid key yet keep m2 == -inf; guard the exps
        m2s = jnp.where(jnp.isfinite(m2), m2, 0.0)
        p = jnp.exp(s - m2s)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m2s), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=prec)
        return m2, l, acc

    if causal:
        # only blocks up to and including the diagonal contribute
        hi = lax.min((i + 1) * bq + diag_off + bk - 1, t_k) // bk
        hi = lax.max(hi, 0)
        m, l, acc = lax.fori_loop(0, hi, body, (m0, l0, acc0))
    else:
        m, l, acc = lax.fori_loop(0, nblocks, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-37)
    o_ref[0] = out.astype(o_ref.dtype)
    if lse_ref is not None:
        # log-sum-exp per query row (flash-decoding merge statistic);
        # fully-masked rows get -inf so partial merges ignore them.
        # Stored row-broadcast over a 128-lane minor dim — Mosaic rejects
        # (1, bq) blocks (sublane dim 1 is not tileable); same layout as
        # jax's reference TPU flash kernel's l/m buffers.
        lse = jnp.where(l[:, 0] > 0,
                        jnp.where(jnp.isfinite(m[:, 0]), m[:, 0], 0.0)
                        + jnp.log(jnp.maximum(l[:, 0], 1e-37)),
                        -jnp.inf)
        lse_ref[0] = jnp.broadcast_to(
            lse.astype(jnp.float32)[:, None], lse_ref.shape[1:])


def _pl():
    from jax.experimental import pallas as pl

    return pl


def _flash_fwd(q, k, v, lengths, scale, causal, interpret, bq=256, bk=512,
               return_lse=False, cache_offset=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    # block sizes: capped by (16-aligned) sequence length to satisfy the
    # TPU sublane tiling constraint for bf16
    bq = min(bq, ((tq + 15) // 16) * 16)
    bk = min(bk, ((tk + 15) // 16) * 16)

    pad_q = (-tq) % bq
    pad_k = (-tk) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    tqp, tkp = tq + pad_q, tk + pad_k

    qf = qp.reshape(b * h, tqp, d)
    kf = kp.reshape(b * h, tkp, d)
    vf = vp.reshape(b * h, tkp, d)
    lens = (jnp.full((b,), tk, jnp.int32) if lengths is None
            else lengths.astype(jnp.int32))

    kernel = functools.partial(
        _flash_fwd_kernel, bq=bq, bk=bk, t_k=tkp, t_valid=tk, tq_valid=tq,
        scale=scale, causal=causal, n_heads=h, cache_offset=cache_offset)
    in_specs = [
        pl.BlockSpec((b,), lambda bi, i: (0,),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1, bq, d), lambda bi, i: (bi, i, 0)),
        pl.BlockSpec((1, tkp, d), lambda bi, i: (bi, 0, 0)),
        pl.BlockSpec((1, tkp, d), lambda bi, i: (bi, 0, 0)),
    ]
    o_spec = pl.BlockSpec((1, bq, d), lambda bi, i: (bi, i, 0))
    o_shape = jax.ShapeDtypeStruct((b * h, tqp, d), q.dtype)
    if return_lse:
        out, lse = pl.pallas_call(
            kernel,
            grid=(b * h, tqp // bq),
            in_specs=in_specs,
            out_specs=[o_spec,
                       pl.BlockSpec((1, bq, 128),
                                    lambda bi, i: (bi, i, 0))],
            out_shape=[o_shape,
                       jax.ShapeDtypeStruct((b * h, tqp, 128),
                                            jnp.float32)],
            interpret=interpret,
        )(lens, qf, kf, vf)
        return (out.reshape(b, h, tqp, d)[:, :, :tq, :],
                lse[:, :, 0].reshape(b, h, tqp)[:, :, :tq])
    out = pl.pallas_call(
        kernel,
        grid=(b * h, tqp // bq),
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=o_shape,
        interpret=interpret,
    )(lens, qf, kf, vf)
    return out.reshape(b, h, tqp, d)[:, :, :tq, :]


def _flash_bwd_dq_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                         delta_ref, dq_ref, *, bq, bk, t_k, t_valid,
                         tq_valid, scale, causal, n_heads,
                         cache_offset=False):
    """dQ = sum_j dS_j @ K_j, streaming KV blocks through VMEM.

    P is recomputed per block from the saved row log-sum-exp (no score
    matrix in HBM): p = exp(s - lse); ds = p * (dp - delta) * scale with
    dp = g @ v^T and delta = rowsum(g * out) precomputed outside.
    """
    from jax import lax

    pl = _pl()
    qi = q_ref[0]                                 # (bq, d)
    gi = g_ref[0]
    lse = lse_ref[0, :, 0].astype(jnp.float32)    # (bq,) from lane 0
    delta = delta_ref[0, :, 0].astype(jnp.float32)
    d = qi.shape[-1]
    i = pl.program_id(1)
    klen = len_ref[pl.program_id(0) // n_heads]
    prec = (jax.lax.Precision.DEFAULT
            if qi.dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)
    diag_off = (klen - tq_valid) if cache_offset else (t_valid - tq_valid)
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    finite = jnp.isfinite(lse)[:, None]
    lse_safe = jnp.where(finite, lse[:, None], 0.0)
    delta_col = delta[:, None]

    def body(j, acc):
        k = k_ref[0, pl.ds(j * bk, bk), :]
        v = v_ref[0, pl.ds(j * bk, bk), :]
        s = lax.dot_general(qi, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=prec) * scale
        cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = cols < jnp.minimum(t_valid, klen)
        if causal:
            valid = valid & (cols <= rows + diag_off)
        p = jnp.where(valid & finite, jnp.exp(s - lse_safe), 0.0)
        dp = lax.dot_general(gi, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec)
        ds = p * (dp - delta_col) * scale
        return acc + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        hi = lax.min((i + 1) * bq + diag_off + bk - 1, t_k) // bk
        hi = lax.max(hi, 0)
        acc = lax.fori_loop(0, hi, body, acc0)
    else:
        acc = lax.fori_loop(0, t_k // bk, body, acc0)
    dq_ref[0] = acc.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(len_ref, k_ref, v_ref, q_ref, g_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, *, bq, bk, t_valid,
                          tq_valid, scale, causal, n_heads,
                          cache_offset=False):
    """dK = sum_i dS_i^T @ Q_i and dV = sum_i P_i^T @ dO_i.

    3-D grid (bh, kv block j, q block i) with i innermost: each program
    handles ONE (q, kv) tile and accumulates into the f32 dk/dv output
    block (constant index over i — the TPU revisiting pattern). Nothing
    full-sequence ever sits in VMEM, so the backward scales to long T
    (the r4 first cut held full q/g/lse/delta per program and ran out of
    VMEM at T=8192)."""
    from jax import lax

    pl = _pl()
    kj = k_ref[0]                                 # (bk, d)
    vj = v_ref[0]
    j = pl.program_id(1)
    i = pl.program_id(2)
    klen = len_ref[pl.program_id(0) // n_heads]
    prec = (jax.lax.Precision.DEFAULT
            if kj.dtype in (jnp.bfloat16, jnp.float16)
            else jax.lax.Precision.HIGHEST)
    diag_off = (klen - tq_valid) if cache_offset else (t_valid - tq_valid)
    cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = cols < jnp.minimum(t_valid, klen)

    @pl.when(i == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    # causal: a tile whose every (row, col) violates col <= row + diag_off
    # contributes only zeros — skip its MXU work entirely (the dq kernel
    # skips via its fori_loop bound; this is the grid-form equivalent)
    if causal:
        contributes = (i + 1) * bq - 1 + diag_off >= j * bk
    else:
        contributes = True

    @pl.when(contributes)
    def _compute():
        q = q_ref[0]
        g = g_ref[0]
        lse = lse_ref[0, :, 0].astype(jnp.float32)
        delta = delta_ref[0, :, 0].astype(jnp.float32)
        s = lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=prec) * scale
        rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ok = valid & (rows < tq_valid)            # mask padded q rows
        if causal:
            ok = ok & (cols <= rows + diag_off)
        finite = jnp.isfinite(lse)[:, None]
        p = jnp.where(ok & finite,
                      jnp.exp(s - jnp.where(finite, lse[:, None], 0.0)),
                      0.0)
        dv = lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dp = lax.dot_general(g, vj, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec)
        ds = p * (dp - delta[:, None]) * scale
        dk = lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
        dk_ref[0] += dk
        dv_ref[0] += dv


def _flash_bwd(q, k, v, lens, lse, delta, g, scale, causal, interpret,
               bq=256, bk=256, cache_offset=False):
    """Streaming flash backward: returns (dq, dk, dv) in the input dtypes.

    ``lse``/``delta`` are (B, H, Tq) fp32 row statistics from the forward
    (delta = rowsum(g * out)). Memory is O(T) — neither kernel ever holds
    more than a (bq, bk) probability tile.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk = k.shape[2]
    bq = min(bq, ((tq + 15) // 16) * 16)
    bk = min(bk, ((tk + 15) // 16) * 16)
    pad_q = (-tq) % bq
    pad_k = (-tk) % bk
    qf = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    gf = jnp.pad(g, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    # +inf pad => finite-mask kills padded q rows inside the kernels
    lsef = jnp.pad(lse.astype(jnp.float32), ((0, 0), (0, 0), (0, pad_q)),
                   constant_values=np.inf)
    deltaf = jnp.pad(delta.astype(jnp.float32), ((0, 0), (0, 0),
                                                 (0, pad_q)))
    tqp, tkp = tq + pad_q, tk + pad_k
    qf = qf.reshape(b * h, tqp, d)
    gf = gf.reshape(b * h, tqp, d)
    kf = kf.reshape(b * h, tkp, d)
    vf = vf.reshape(b * h, tkp, d)
    # row stats ride a 128-lane minor dim (Mosaic can't tile (1, bq)
    # blocks; jax's reference flash kernel uses the same layout)
    lsef = jnp.broadcast_to(lsef.reshape(b * h, tqp)[:, :, None],
                            (b * h, tqp, 128))
    deltaf = jnp.broadcast_to(deltaf.reshape(b * h, tqp)[:, :, None],
                              (b * h, tqp, 128))
    lens_arr = (jnp.full((b,), tk, jnp.int32) if lens is None
                else lens.astype(jnp.int32))

    common = dict(bq=bq, bk=bk, t_valid=tk, tq_valid=tq, scale=scale,
                  causal=causal, n_heads=h, cache_offset=cache_offset)
    len_spec = pl.BlockSpec((b,), lambda bi, i: (0,),
                            memory_space=pltpu.SMEM)
    q_blk = pl.BlockSpec((1, bq, d), lambda bi, i: (bi, i, 0))
    q_full = pl.BlockSpec((1, tqp, d), lambda bi, i: (bi, 0, 0))
    k_blk = pl.BlockSpec((1, bk, d), lambda bi, i: (bi, i, 0))
    k_full = pl.BlockSpec((1, tkp, d), lambda bi, i: (bi, 0, 0))
    row_blk = pl.BlockSpec((1, bq, 128), lambda bi, i: (bi, i, 0))
    row_full = pl.BlockSpec((1, tqp, 128), lambda bi, i: (bi, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, t_k=tkp, **common),
        grid=(b * h, tqp // bq),
        in_specs=[len_spec, q_blk, k_full, k_full, q_blk, row_blk,
                  row_blk],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((b * h, tqp, d), q.dtype),
        interpret=interpret,
    )(lens_arr, qf, kf, vf, gf, lsef, deltaf)

    # 3-D grid: (bh, kv block, q block); q-dim innermost so dk/dv output
    # blocks (constant index over it) accumulate in fp32
    kv_blk3 = pl.BlockSpec((1, bk, d), lambda bi, j, i: (bi, j, 0))
    q_blk3 = pl.BlockSpec((1, bq, d), lambda bi, j, i: (bi, i, 0))
    row_blk3 = pl.BlockSpec((1, bq, 128), lambda bi, j, i: (bi, i, 0))
    len_spec3 = pl.BlockSpec((b,), lambda bi, j, i: (0,),
                             memory_space=pltpu.SMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(b * h, tkp // bk, tqp // bq),
        in_specs=[len_spec3, kv_blk3, kv_blk3, q_blk3, q_blk3, row_blk3,
                  row_blk3],
        out_specs=[kv_blk3, kv_blk3],
        out_shape=[jax.ShapeDtypeStruct((b * h, tkp, d), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, tkp, d), jnp.float32)],
        interpret=interpret,
    )(lens_arr, kf, vf, qf, gf, lsef, deltaf)
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)

    dq = dq.reshape(b, h, tqp, d)[:, :, :tq, :]
    dk = dk.reshape(b, h, tkp, d)[:, :, :tk, :]
    dv = dv.reshape(b, h, tkp, d)[:, :, :tk, :]
    return dq, dk, dv


def _xla_reference(q, k, v, lengths, scale, causal, cache_offset=False):
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    tq, tk = scores.shape[-2], scores.shape[-1]
    if causal and cache_offset:
        # diagonal aligned to the per-sample valid length (KV-cache
        # decode): query row i is at absolute position l_b - tq + i and
        # attends keys [0, l_b - tq + i]; the lengths mask below bounds
        # the buffer tail
        rows = jnp.arange(tq)[None, :, None]
        cols = jnp.arange(tk)[None, None, :]
        off = (lengths.astype(jnp.int32) - tq)[:, None, None]
        cm = cols <= rows + off                        # (B, Tq, Tk)
        scores = jnp.where(cm[:, None, :, :], scores, -jnp.inf)
    elif causal:
        cm = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        scores = jnp.where(cm, scores, -jnp.inf)
    if lengths is not None:
        cols = jnp.arange(tk)
        lm = cols[None, :] < lengths.astype(jnp.int32)[:, None]  # (B, Tk)
        scores = jnp.where(lm[:, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_core(q, k, v, lens, scale, causal, interpret,
                cache_offset=False):
    return _flash_fwd(q, k, v, lens, scale, causal, interpret,
                      cache_offset=cache_offset)


def _flash_core_fwd(q, k, v, lens, scale, causal, interpret,
                    cache_offset=False):
    out, lse = _flash_fwd(q, k, v, lens, scale, causal, interpret,
                          return_lse=True, cache_offset=cache_offset)
    return out, (q, k, v, lens, out, lse)


def _flash_core_bwd(scale, causal, interpret, cache_offset, res, g):
    q, k, v, lens, out, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dq, dk, dv = _flash_bwd(q, k, v, lens, lse, delta, g.astype(q.dtype),
                            scale, causal, interpret,
                            cache_offset=cache_offset)
    lens_ct = None if lens is None else \
        np.zeros(lens.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, lens_ct


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _kernel_pays(direction, bh, tq, tk):
    """Whether the Pallas kernels beat the dense chain for this call: per
    direction, from the call's shapes (v5e, bf16; PERF.md section 6
    "PR 37" holds the tables, chiprun_out/flash_sweep_pr37_a.json,
    flash_sweep_pr37_b.json and prefill_bench_pr37.json the runs).

    A call that is being differentiated runs the forward AND the backward
    kernels, and the backward pair decides: 0.47x the XLA backward at
    T = 1024, 1.8x at 2048, 4.7x at 4096 (PROFILE.md, round 4).

    A call that is not runs the forward kernel alone: ~0.65 us a program
    (B*H x Tq / 256 of them) and 3-5 us a million scores (B*H x Tq x Tk),
    so 5.2 us a million at T = 1024, 8.2 at 512, 19 at 256, 43 at 128.
    The dense chain takes 4.2-5.4 us a million while the compiler keeps
    its score tensor on the chip and 7.3-24 once it goes through HBM,
    which it does between 17.8M scores ((1, 17, 1024, 1024): dense 81 us,
    kernel 95) and 21.0M ((1, 20, ..): 154 against 108; (1, 25, ..): 198
    against 139, and inside a served GPT-2 XL prefill, where other
    buffers want the same memory, 0.39 ms a layer against 0.16). Over
    that size the kernel wins from 512 positions ((16, 16, 512, 512):
    1259 us against 587) and not under them ((64, 16, 256, 256): 1385
    against 1264; (256, 12, 128, 128): 1181 against 2193).

    Whatever the speed, a dense fp32 score tensor over 1 GiB takes the
    kernels: their memory is O(T), and a huge-B*H job must never run out
    of memory because of a speed heuristic."""
    scores = bh * tq * tk
    if scores * 4 > (1 << 30):
        return True
    if direction == "differentiated":
        return max(tq, tk) >= 2048
    return scores >= 19 << 20 and min(tq, tk) >= 512


def _implementation(direction, q, k, lens, scale, causal, interpret,
                    cache_offset):
    """The function of (q, k, v) this call runs, counted where it is
    chosen: while the program is traced. An explicit ``interpret=`` pins
    the kernels (tests exercise them at tiny shapes that way)."""
    from .. import telemetry

    b, h, tq, _ = q.shape
    kernel = interpret is not None or _kernel_pays(
        direction, b * h, tq, k.shape[2])
    telemetry.counter(
        "mxtpu_flash_dispatch_total",
        "flash_attention calls traced, by the implementation chosen",
        path="kernel" if kernel else "dense", direction=direction).inc()
    if not kernel:
        return lambda q, k, v: _xla_reference(
            q, k, v, lens, scale, causal, cache_offset=cache_offset)

    def kernels(interpret):
        return lambda q, k, v: _flash_core(
            q, k, v, lens, scale, causal, interpret, cache_offset)

    if interpret is not None:
        return kernels(bool(interpret))
    # compiled where the program is lowered for a TPU (a compile for a
    # described chip included), the Pallas interpreter elsewhere
    return lambda q, k, v: jax.lax.platform_dependent(
        q, k, v, tpu=kernels(False), default=kernels(True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _attention(q, k, v, lens, *static):
    """What JAX runs when nothing differentiates the call."""
    return _implementation("forward", q, k, lens, *static)(q, k, v)


def _attention_fwd(q, k, v, lens, *static):
    """What JAX runs in the call's place when it is differentiated."""
    out, pull = jax.vjp(
        _implementation("differentiated", q, k, lens, *static), q, k, v)
    return out, (pull, lens)


def _attention_bwd(*static_res_g):
    (pull, lens), g = static_res_g[-2:]
    lens_ct = None if lens is None else \
        np.zeros(lens.shape, dtype=jax.dtypes.float0)
    return (*pull(g), lens_ct)


_attention.defvjp(_attention_fwd, _attention_bwd)


@register("flash_attention")
def flash_attention(q, k, v, lengths=None, scale=None, causal=False,
                    interpret=None, cache_offset=False):
    """Block-tiled flash attention. q, k, v: (B, H, T, D); ``lengths``
    (B,) optional per-sample valid key length. The TPU analog of a
    hand-written fused attention CUDA kernel; see module docstring.

    ``cache_offset=True`` is the KV-cache decode alignment (ISSUE 12):
    K/V are the ``[0, lengths_b)`` prefix of a fixed ``max_len`` buffer
    and the Tq query tokens are the LAST tq of that prefix — query row i
    sits at absolute position ``lengths_b - tq + i`` and attends keys
    ``[0, lengths_b - tq + i]`` exactly (decode step t attends [0, t]).
    Requires ``lengths`` with every entry >= Tq; implies ``causal``.

    Dispatch: the Pallas kernels where they beat the mathematically
    identical XLA dense path, which runs elsewhere — same contract, chosen
    per direction (is JAX differentiating this call?) and from the call's
    shapes (``_kernel_pays``), the way the reference's cuDNN autotune
    registry picks an algo per shape."""
    d = q.shape[-1]
    s = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if cache_offset:
        if lengths is None:
            raise ValueError("cache_offset=True requires per-sample "
                             "lengths (the cache fill per slot)")
        causal = True
    return _attention(q, k, v, lengths, s, bool(causal), interpret,
                      bool(cache_offset))
