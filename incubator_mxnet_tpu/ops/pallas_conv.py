"""Pallas fused Conv2D + BatchNorm epilogue/prologue — the cuDNN
``ConvolutionBiasActivationForward`` / BN-genstats analog for TPU.

Why this exists (PROFILE.md, rounds 2-5): in ResNet training ~30% of the
step is BatchNorm statistics passes that XLA cannot fuse into the adjacent
convolutions — every BN re-reads the conv output from HBM to reduce
per-channel mean/var, and the normalize-apply is another full read+write.
The round-5 decision record quantifies the prize at **15.3 ms/step**
(2,454 -> ~3,360 img/s at batch 128 if the stat passes disappear). The
reference solves the same problem with cuDNN fused kernels
(``src/operator/nn/cudnn/`` — SURVEY.md §2.1 operator-library row); the
TPU-native solve is a Pallas conv kernel that

* applies the PREVIOUS layer's BN (scale/shift) + ReLU to the input tile
  while it sits in VMEM (prologue — the normalized activation is never
  materialised in HBM), and
* accumulates per-channel ``sum`` / ``sum-of-squares`` of its own raw
  output while the tile is still in VMEM (stats epilogue — the separate
  stat pass disappears).

v2 kernel structure (PROFILE.md named the three levers after the
per-shape fit table showed 128ch@28² and 512ch@7² losing 2.7-3.6x):

* **output-channel blocking**: the grid is ``(co/bc, n/nb)`` so each
  program contracts into a ``bc``-wide output block. Shrinking the weight
  block frees VMEM for more images per program, which is what feeds the
  MXU's M dimension at small spatial extents (512ch@7² went from nb=8 /
  392 matmul rows to nb-limited-by-batch with bc=128).
* **weight-stationary accumulation**: the batch dimension is the INNER
  grid dimension, so the weight block (and the stats accumulators) stay
  resident in VMEM across the whole batch sweep; only x/y blocks stream.
* **DMA pipelining**: streaming x/y blocks over the inner grid dimension
  is exactly what the Pallas pipeline emitter double-buffers — the next
  batch block's HBM->VMEM copy overlaps the current block's MXU work,
  and the ky/kx taps slice from the VMEM-resident x block (no HBM
  traffic per tap).

**v3 (this round) — the residual-epilogue fusion + stride-2 layouts:**

* **fused residual epilogue**: the prologue generalises to the WHOLE
  inter-bottleneck boundary — ``x_pro = relu(a·x + b + ar·r + br)`` with
  the residual ``r`` streamed as a third operand (``ar``/``br`` fold the
  downsample-branch BN; identity shortcuts pass ``ar=1, br=0``). The
  conv+BN+ReLU+residual-add of a ResNet bottleneck junction is then ONE
  kernel: the previous conv's raw output, its BN coefficients and the
  shortcut meet in VMEM and the joined activation feeds the MXU without
  an intervening XLA elementwise op (a Pallas call is an opaque custom
  call — XLA cannot fuse across it, so the v2 model paid one extra
  activation read + write per bottleneck at the join). ``emit_act=True``
  additionally writes the joined activation out once (the shortcut /
  downsample consumer of the SAME value), which costs one write instead
  of the separate join op's read+read+write.
* **matching backward**: the dx kernel folds the dReLU mask and the
  residual cotangent into its epilogue — ``dr = dlin·ar`` streams out
  next to ``dx = dlin·a`` with the per-channel ``dar = Σ dlin·r`` sum
  accumulated alongside ``da``/``db`` (``dbr ≡ db``); an emitted
  activation's incoming cotangent is added to the transpose-conv
  accumulator before masking. The dW kernel's prologue recomputes the
  joined ``x_pro`` in VMEM. ``MXTPU_CONV_EPILOGUE`` gates the model-level
  wiring (gluon/model_zoo/vision/fused_resnet.py).
* **stride-2 layout variants** (``MXTPU_CONV_STRIDE2``): the v2 per-image
  unrolled phase decomposition caps nb at 8 to bound kernel code size,
  which starves the MXU at small spatial extents (l3/l4's strided
  shapes want nb 10-41 at the 2048-row target). The new ``prephase``
  variant pads the prologue-applied input to an exact phase multiple and
  phase-decomposes it in XLA — ``(N, Hq, Wq, s²·Ci)`` phase-major
  channels — so every in-kernel tap is a PLAIN batched slice (lane-dim
  offset at Ci multiples; Ci >= 128 on every ResNet-50 strided conv),
  nb is uncapped and the kernel body is stride-1-shaped. Trade-off: the
  prologue materialises host-side for those convs (7 of ResNet-50's 53).
  ``auto`` picks prephase exactly when the unroll cap binds
  (row-target/(ho·wo) > 8), else keeps the in-kernel unroll.

**Backward (v2)**: two Pallas kernels replace the XLA NHWC
transpose-conv backward that kept ``fused_resnet50_v1`` 1.8x behind the
zoo model end-to-end:

* ``dx`` — a transpose-conv kernel whose PROLOGUE folds the BN-statistics
  cotangents into the output cotangent in VMEM (``dy_t = dy + ds +
  2*y*dss`` — the BN-backward; dy_t is never materialised in HBM) and
  whose EPILOGUE emits the per-channel prologue-parameter sums
  (``da = Σ dxp*relu'*x``, ``db = Σ dxp*relu'``) while the tile is
  resident — the backward analog of the forward stats epilogue.
* ``dW`` — the weight-gradient contraction (per-tap ``xsᵀ @ dy_t`` into a
  VMEM-resident fp32 ``dW`` accumulator) with the same BN-backward
  prologue recomputing ``x_pro`` and ``dy_t`` in VMEM.

``MXTPU_CONV_BWD`` selects the implementation: ``auto`` (default) runs
the Pallas kernels for the stride-1 shapes (51 of ResNet-50's 53 convs)
and keeps the XLA formulation for strided convs until the phase-stack
pattern is proven on the TPU tier; ``pallas`` forces every shape through
the kernels; ``xla`` restores the round-4 path (vjp over
:func:`_conv_part_ref`).

Kernel shape contract (ResNet family): NHWC, square kernels 1x1/3x3
(arbitrary odd sizes accepted), stride 1 or 2, symmetric padding, no
groups/dilation. The 7x7 stem (C_in=3 wastes the MXU lane dim) stays in
XLA; the residual joins now fuse (v3) when the epilogue knob engages.

On non-TPU backends the kernels run through the Pallas interpreter so the
correctness suite covers every variant on the CPU mesh
(tests/test_pallas_conv.py — forward, dx, dW, da/db, the v3 residual
operands and both stride-2 layouts, each oracle-proven against the XLA
formulation).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..config import config
from .registry import register


def _prec(dtype):
    return (lax.Precision.DEFAULT if dtype in (jnp.bfloat16, jnp.float16)
            else lax.Precision.HIGHEST)


def _low_prec(dtype):
    return dtype in (jnp.bfloat16, jnp.float16)


def _esz(dtype):
    return 2 if _low_prec(dtype) else 4


def _out_size(h, pad, k, stride):
    return (h + 2 * pad - k) // stride + 1


# ---------------------------------------------------------------------------
# shared in-kernel helpers
# ---------------------------------------------------------------------------

def _pad_input(x, pad, stride):
    """Symmetric padding; extra (stride-1) bottom/right padding keeps the
    strided slice-reshape uniform for odd sizes (those rows are never
    selected)."""
    if pad or stride > 1:
        return jnp.pad(x, ((0, 0), (pad, pad + stride - 1),
                           (pad, pad + stride - 1), (0, 0)))
    return x


def _make_tap(x, stride, ho, wo, nb, ci, phase=0):
    """Return ``tap(ky, kx) -> (nb*ho*wo, ci)`` slicing the VMEM block.

    stride>1, ``phase == 0`` (the v2 ``unroll`` variant): per-image phase
    decomposition — one reshape into stride-phases per image, then every
    tap is a PLAIN slice (offset strided slices at tap offsets and the
    batched 6-D strided reshape are both rejected by the Mosaic compiler
    — the unroll is per-image, which is why the caller caps nb at 8).

    ``phase == s`` (the v3 ``prephase`` variant): the block arrived
    already phase-decomposed by the host — ``(nb, Hq, Wq, s²·ci)`` with
    phase-major channels — so every tap is a plain BATCHED slice (the
    channel offset selects the (ry, rx) phase) and nb is uncapped."""
    if phase:
        s = phase

        def tap(ky, kx):
            qy, ry = divmod(ky, s)
            qx, rx = divmod(kx, s)
            c0 = (ry * s + rx) * ci
            return x[:, qy:qy + ho, qx:qx + wo, c0:c0 + ci].reshape(
                nb * ho * wo, ci)
        return tap

    if stride == 1:
        def tap(ky, kx):
            return x[:, ky:ky + ho, kx:kx + wo, :].reshape(nb * ho * wo, ci)
        return tap

    s = stride
    hp, wp = x.shape[1], x.shape[2]
    hp -= hp % s
    wp -= wp % s
    xphs = [x[img, :hp, :wp, :].reshape(hp // s, s, wp // s, s, ci)
            for img in range(nb)]

    def tap(ky, kx):
        qy, ry = divmod(ky, s)
        qx, rx = divmod(kx, s)
        parts = [xph[qy:qy + ho, ry, qx:qx + wo, rx, :].reshape(ho * wo, ci)
                 for xph in xphs]
        return parts[0] if nb == 1 else jnp.concatenate(parts, axis=0)
    return tap


def _prologue(x, a_row, b_row, relu, r=None, ar_row=None, br_row=None):
    """BN scale/shift (+residual affine, +ReLU) of the previous layer, in
    fp32, cast back — the v3 form of the inter-layer boundary:
    ``relu(a·x + b + ar·r + br)`` (identity shortcuts: ar=1, br=0)."""
    xf = x.astype(jnp.float32) * a_row[None, None, None, :] \
        + b_row[None, None, None, :]
    if r is not None:
        xf = xf + r.astype(jnp.float32) * ar_row[None, None, None, :] \
            + br_row[None, None, None, :]
    if relu:
        xf = jnp.maximum(xf, 0.0)
    return xf.astype(x.dtype)


def _fold_bn_cotangents(dy, y, ds_row, dss_row):
    """BN-backward prologue: fold the stats cotangents into the output
    cotangent — ``d(sum)/dy = 1`` and ``d(sumsq)/dy = 2y`` with the SAVED
    kernel output. fp32, cast to the compute dtype by the caller."""
    return (dy.astype(jnp.float32) + ds_row[None, None, None, :]
            + 2.0 * y.astype(jnp.float32) * dss_row[None, None, None, :])


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fused_conv_kernel(*refs, stride, pad, relu, kh, kw, ho, wo, has_pro,
                       has_res, emit, phase, nb, im2col):
    """One ``(co-block, batch-block)`` grid program: prologue (+residual
    join) -> pad -> conv as MXU matmuls (fp32 accumulation) -> stats
    epilogue (+ the joined activation written once when ``emit``).

    Grid order is (co-block OUTER, batch-block INNER): the weight block
    and the stats accumulators stay VMEM-resident across the inner batch
    sweep (weight-stationary) while x/y blocks stream double-buffered.

    Two matmul strategies: ``im2col`` gathers the kh*kw shifted views into
    one (nb*ho*wo, kh*kw*ci) patch matrix in VMEM for a single deep-
    contraction matmul (best when ci < 128 lanes); otherwise one matmul
    per (ky, kx) tap against the resident weight block.

    ``phase == s`` marks the prephase variant: the x block arrived
    phase-decomposed with the prologue already applied host-side, so the
    in-kernel prologue/pad are skipped and taps are plain batched slices.
    """
    from jax.experimental import pallas as pl

    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    a_ref = next(it)
    b_ref = next(it)
    r_ref = next(it) if has_res else None
    ar_ref = next(it) if has_res else None
    br_ref = next(it) if has_res else None
    y_ref = next(it)
    s_ref = next(it)
    ss_ref = next(it)
    xp_ref = next(it) if emit else None

    x = x_ref[...]                                 # (nb, H, W, Ci)
    ci = w_ref.shape[2]
    bc = w_ref.shape[-1]
    prec = _prec(x.dtype)
    if (has_pro or has_res) and not phase:
        x = _prologue(x, a_ref[0], b_ref[0], relu,
                      r_ref[...] if has_res else None,
                      ar_ref[0] if has_res else None,
                      br_ref[0] if has_res else None)
    if emit:
        # the joined activation for the shortcut-path consumer. The
        # block is revisited (and rewritten with identical bytes) once
        # per outer co-block — the caller keeps co//bc == 1 for the
        # model's junction convs (1x1 weight blocks fit the budget
        # whole) and declares the co dimension "arbitrary" under emit so
        # Megacore never splits the revisits across cores. A
        # pl.when(j == 0) guard would be WRONG: later j visits would
        # write back an unstored VMEM buffer.
        xp_ref[...] = x
    if not phase:
        x = _pad_input(x, pad, stride)
    tap = _make_tap(x, stride, ho, wo, nb, ci, phase=phase)

    if im2col and (kh, kw) != (1, 1):
        patches = jnp.concatenate(
            [tap(ky, kx) for ky in range(kh) for kx in range(kw)], axis=-1)
        acc = lax.dot_general(
            patches, w_ref[...].reshape(kh * kw * ci, bc),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)
    else:
        acc = jnp.zeros((nb * ho * wo, bc), jnp.float32)
        for ky in range(kh):
            for kx in range(kw):
                acc = acc + lax.dot_general(
                    tap(ky, kx), w_ref[ky, kx],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32, precision=prec)

    y_ref[...] = acc.reshape(nb, ho, wo, bc).astype(y_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        ss_ref[...] = jnp.zeros_like(ss_ref)

    s_ref[0] += jnp.sum(acc, axis=0)
    ss_ref[0] += jnp.sum(acc * acc, axis=0)


# ---------------------------------------------------------------------------
# block-size heuristics (shared by fwd and bwd)
# ---------------------------------------------------------------------------

#: Mosaic's scoped-VMEM limit for these kernels. The compiler's default
#: (16 MiB on v5e, of 128 MiB physical) is below what the block-size
#: heuristics' working sets come to once Mosaic pads 7- and 14-wide
#: tiles to the (8, 128) tiling — the v5e compiler refused the layer-3
#: backward and every layer-4 shape at the default ("exceeded scoped
#: vmem limit").
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _vmem_budget():
    return int(config.get("MXTPU_CONV_VMEM_MB")) * 1024 * 1024


def _pick_oc_block(co, weight_bytes_per_co):
    """Output-channel block: the largest divisor of ``co`` from
    {co, 256, 128} whose weight block fits the per-block weight budget
    (~2 MiB). Shrinking the resident weight block is what frees VMEM for
    more images per program at the 512ch@7² class of shapes."""
    knob = int(config.get("MXTPU_CONV_OC_BLOCK") or 0)
    if knob and co % knob == 0 and knob <= co:
        return knob
    budget = 2 * 1024 * 1024
    for cand in (co, 256, 128):
        if cand <= co and co % cand == 0 \
                and cand * weight_bytes_per_co <= budget:
            return cand
    return 128 if co % 128 == 0 else co


def _pick_nb(n, ho, wo, *, per_image_bytes=0, fixed_bytes=0, stride=1):
    """Images per grid program: aim for the knob's matmul-row target
    (default 2048) so the MXU's M dimension is well fed even at 7x7
    spatial sizes, capped so the per-program working set stays under the
    VMEM budget (v5e has ~16 MB; nb=32 at the layer-4 shapes crashes the
    Mosaic compile helper). Strided convs on the ``unroll`` variant
    unroll per image, so their nb is additionally capped at 8 to bound
    kernel code size (the ``prephase`` variant passes stride=1 here —
    its taps are batched, nb uncapped)."""
    target = int(config.get("MXTPU_CONV_ROW_TARGET"))
    nb = max(1, target // max(ho * wo, 1))
    if stride > 1:
        nb = min(nb, 8)
    budget = _vmem_budget()
    if per_image_bytes:
        nb = min(nb, max(1, (budget - fixed_bytes) // per_image_bytes))
    nb = min(nb, n)
    while n % nb:
        nb -= 1
    return nb


def _compiler_params(interpret, semantics):
    if interpret:
        return {}
    from jax.experimental.pallas import tpu as pltpu

    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_VMEM_LIMIT_BYTES)}


def _use_im2col(ci, kh, kw):
    return (bool(config.get("MXTPU_CONV_IM2COL"))
            and ci < 128 and (kh, kw) != (1, 1))


def _stride2_variant(stride, ho, wo):
    """Which strided-conv layout the forward kernel uses
    (``MXTPU_CONV_STRIDE2``): ``unroll`` is the v2 per-image in-kernel
    phase decomposition (prologue stays in VMEM; nb capped at 8),
    ``prephase`` phase-decomposes the prologue-applied input host-side
    so the kernel body is stride-1-shaped (nb uncapped, taps batched;
    the prologue materialises once in XLA for these convs). ``auto``
    picks prephase exactly where the unroll cap binds — the small-
    spatial shapes whose row target wants more than 8 images per
    program (l3/l4's strided convs; PROFILE.md "conv v3")."""
    if stride <= 1:
        return "none"
    mode = str(config.get("MXTPU_CONV_STRIDE2")).strip().lower()
    if mode in ("unroll", "prephase"):
        return mode
    target = int(config.get("MXTPU_CONV_ROW_TARGET"))
    return "prephase" if target // max(ho * wo, 1) > 8 else "unroll"


# ---------------------------------------------------------------------------
# forward pallas_call
# ---------------------------------------------------------------------------

def _fused_conv_pallas(x, w, a, b, stride, pad, relu, interpret,
                       r=None, ar=None, br=None, emit=False):
    from jax.experimental import pallas as pl

    n, h, wdt, ci = x.shape
    kh, kw, wci, co = w.shape
    assert wci == ci, f"channel mismatch {wci} != {ci}"
    ho = _out_size(h, pad, kh, stride)
    wo = _out_size(wdt, pad, kw, stride)
    if _stride2_variant(stride, ho, wo) == "prephase":
        return _fused_conv_prephase(x, w, a, b, stride, pad, relu,
                                    interpret, r=r, ar=ar, br=br,
                                    emit=emit)
    has_pro = a is not None
    has_res = r is not None
    if not has_pro:  # dummy operands keep one kernel signature
        a = jnp.ones((ci,), jnp.float32)
        b = jnp.zeros((ci,), jnp.float32)
    esz = _esz(x.dtype)
    bc = _pick_oc_block(co, kh * kw * ci * esz)
    # double-buffered x and y blocks + the fp32 accumulator, per image;
    # the residual stream and the emitted activation add an x-sized
    # block each
    per_img = 2 * ((h + 2 * pad) * (wdt + 2 * pad) * ci
                   + ho * wo * bc) * esz + ho * wo * bc * 4
    per_img += 2 * h * wdt * ci * esz * (int(has_res) + int(emit))
    nb = _pick_nb(n, ho, wo, per_image_bytes=per_img,
                  fixed_bytes=kh * kw * ci * bc * esz, stride=stride)
    # deep-contraction im2col pays off when the per-tap contraction is
    # shallower than the MXU's 128 lanes — but the VMEM concatenate
    # currently trips a Mosaic layout bug ("result/input offset mismatch
    # on non-concat dimension") for some channel counts, so it is opt-in
    im2col = _use_im2col(ci, kh, kw)

    kernel = functools.partial(
        _fused_conv_kernel, stride=stride, pad=pad, relu=relu, kh=kh,
        kw=kw, ho=ho, wo=wo, has_pro=has_pro, has_res=has_res, emit=emit,
        phase=0, nb=nb, im2col=im2col)
    in_specs = [
        pl.BlockSpec((nb, h, wdt, ci), lambda j, i: (i, 0, 0, 0)),
        pl.BlockSpec((kh, kw, ci, bc), lambda j, i: (0, 0, 0, j)),
        pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
        pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
    ]
    operands = [x, w, a.astype(jnp.float32).reshape(1, ci),
                b.astype(jnp.float32).reshape(1, ci)]
    if has_res:
        in_specs += [
            pl.BlockSpec((nb, h, wdt, ci), lambda j, i: (i, 0, 0, 0)),
            pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
            pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
        ]
        operands += [r, jnp.asarray(ar, jnp.float32).reshape(1, ci),
                     jnp.asarray(br, jnp.float32).reshape(1, ci)]
    out_specs = [
        pl.BlockSpec((nb, ho, wo, bc), lambda j, i: (i, 0, 0, j)),
        pl.BlockSpec((1, bc), lambda j, i: (0, j)),
        pl.BlockSpec((1, bc), lambda j, i: (0, j)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
        jax.ShapeDtypeStruct((1, co), jnp.float32),
        jax.ShapeDtypeStruct((1, co), jnp.float32),
    ]
    if emit:
        out_specs.append(
            pl.BlockSpec((nb, h, wdt, ci), lambda j, i: (i, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, h, wdt, ci), x.dtype))
    # emit revisits the xp block along the co grid dimension; declaring
    # it "arbitrary" serializes those revisits (no Megacore aliased
    # write). Free for the model's junction convs, whose co//bc == 1.
    semantics = ("arbitrary" if emit else "parallel", "arbitrary")
    outs = pl.pallas_call(
        kernel,
        grid=(co // bc, n // nb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **_compiler_params(interpret, semantics),
    )(*operands)
    if emit:
        y, s, ss, xp = outs
        return y, s[0], ss[0], xp
    y, s, ss = outs
    return y, s[0], ss[0]


def _fused_conv_prephase(x, w, a, b, stride, pad, relu, interpret,
                        r=None, ar=None, br=None, emit=False):
    """The v3 ``prephase`` strided layout: apply the prologue (+residual
    join) in XLA, pad to an exact phase multiple, and phase-decompose to
    ``(N, Hq, Wq, s²·Ci)`` phase-major channels so the kernel's taps are
    plain batched slices — the stride-1 kernel body with nb uncapped.
    The strided reshape/transpose runs in XLA (where it is legal and
    fuses with the prologue); Mosaic still rejects it in-kernel."""
    from jax.experimental import pallas as pl

    n, h, wdt, ci = x.shape
    kh, kw, _, co = w.shape
    s = stride
    ho = _out_size(h, pad, kh, s)
    wo = _out_size(wdt, pad, kw, s)
    xp = _apply_prologue_host(x, a, b, r=r, ar=ar, br=br, relu=relu) \
        if (a is not None or r is not None) else x
    # exact padded extent: every tap must stay in range and the extent
    # must be a phase multiple (extra rows are never selected)
    hp = s * (ho - 1) + kh
    hp += (-hp) % s
    wp = s * (wo - 1) + kw
    wp += (-wp) % s
    xpad = jnp.pad(xp, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    xpad = xpad[:, :hp, :wp, :] if (hp <= h + 2 * pad
                                    and wp <= wdt + 2 * pad) else \
        jnp.pad(xpad, ((0, 0), (0, max(0, hp - h - 2 * pad)),
                       (0, max(0, wp - wdt - 2 * pad)), (0, 0))
                )[:, :hp, :wp, :]
    hq, wq = hp // s, wp // s
    xph = xpad.reshape(n, hq, s, wq, s, ci).transpose(
        0, 1, 3, 2, 4, 5).reshape(n, hq, wq, s * s * ci)

    esz = _esz(x.dtype)
    bc = _pick_oc_block(co, kh * kw * ci * esz)
    per_img = 2 * (hq * wq * s * s * ci + ho * wo * bc) * esz \
        + ho * wo * bc * 4
    nb = _pick_nb(n, ho, wo, per_image_bytes=per_img,
                  fixed_bytes=kh * kw * ci * bc * esz, stride=1)
    dummy = jnp.ones((1, ci), jnp.float32)
    kernel = functools.partial(
        _fused_conv_kernel, stride=s, pad=0, relu=relu, kh=kh, kw=kw,
        ho=ho, wo=wo, has_pro=False, has_res=False, emit=False, phase=s,
        nb=nb, im2col=False)
    y, sm, ssm = pl.pallas_call(
        kernel,
        grid=(co // bc, n // nb),
        in_specs=[
            pl.BlockSpec((nb, hq, wq, s * s * ci),
                         lambda j, i: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw, ci, bc), lambda j, i: (0, 0, 0, j)),
            pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
            pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((nb, ho, wo, bc), lambda j, i: (i, 0, 0, j)),
            pl.BlockSpec((1, bc), lambda j, i: (0, j)),
            pl.BlockSpec((1, bc), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
            jax.ShapeDtypeStruct((1, co), jnp.float32),
        ],
        interpret=interpret,
        **_compiler_params(interpret, ("parallel", "arbitrary")),
    )(xph, w, dummy, jnp.zeros((1, ci), jnp.float32))
    if emit:
        return y, sm[0], ssm[0], xp
    return y, sm[0], ssm[0]


# ---------------------------------------------------------------------------
# backward: dx (transpose conv, BN-backward prologue, da/db epilogue)
# ---------------------------------------------------------------------------

def _conv_bwd_dx_kernel(*refs, stride, pad, relu, kh, kw, h, wsp, ho, wo,
                        has_pro, has_res, has_emit, nb):
    """dx = transpose-conv(dy_t, w) * prologue-backward.

    Prologue: fold the stats cotangents into dy in VMEM (dy_t never
    touches HBM). Body: stride-1 is the classic flipped-tap correlation
    over a (k-1-pad)-padded dy_t; stride>1 decomposes dx into stride²
    phases, each a plain-slice tap subset sum, re-interleaved by one
    reshape. Epilogue: per-channel da/db sums of the prologue backward
    accumulate across the inner batch grid dimension — the backward
    analog of the forward stats epilogue. v3 residual extension: the
    emitted-activation cotangent ``g`` joins the accumulator before the
    dReLU mask; ``dr = dlin·ar`` streams out next to dx and
    ``dar = Σ dlin·r`` accumulates next to da/db (``dbr ≡ db``)."""
    from jax.experimental import pallas as pl

    it = iter(refs)
    dy_ref = next(it)
    y_ref = next(it)
    x_ref = next(it)
    w_ref = next(it)
    a_ref = next(it)
    b_ref = next(it)
    ds_ref = next(it)
    dss_ref = next(it)
    r_ref = next(it) if has_res else None
    ar_ref = next(it) if has_res else None
    br_ref = next(it) if has_res else None
    g_ref = next(it) if has_emit else None
    dx_ref = next(it)
    da_ref = next(it)
    db_ref = next(it)
    dr_ref = next(it) if has_res else None
    dar_ref = next(it) if has_res else None

    dy = dy_ref[...]                      # (nb, ho, wo, Co)
    y = y_ref[...]
    co = dy.shape[-1]
    cb = w_ref.shape[2]                   # ci block
    cdt = y.dtype
    prec = _prec(cdt)
    dyt = _fold_bn_cotangents(dy, y, ds_ref[0], dss_ref[0]).astype(cdt)

    def tap_dot(rows):
        # contract over Co: (M, Co) x (cb, Co) -> (M, cb)
        return lax.dot_general(
            rows, w_tap, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=prec)

    if stride == 1:
        py, px = kh - 1 - pad, kw - 1 - pad
        dyp = jnp.pad(dyt, ((0, 0), (py, py), (px, px), (0, 0)))
        acc = jnp.zeros((nb * h * wsp, cb), jnp.float32)
        for ky in range(kh):
            for kx in range(kw):
                w_tap = w_ref[ky, kx]
                acc = acc + tap_dot(
                    dyp[:, kh - 1 - ky:kh - 1 - ky + h,
                        kw - 1 - kx:kw - 1 - kx + wsp, :].reshape(
                            nb * h * wsp, co))
        dxp = acc.reshape(nb, h, wsp, cb)
    else:
        s = stride
        kp = max(kh, kw)
        dyp = jnp.pad(dyt, ((0, 0), (kp, kp), (kp, kp), (0, 0)))
        hq = -(-h // s)
        wq = -(-wsp // s)

        def rows_at(oy, ox):
            return dyp[:, kp + oy:kp + oy + hq,
                       kp + ox:kp + ox + wq, :].reshape(nb * hq * wq, co)

        col_phases = []
        for ri in range(s):
            row_phases = []
            for rj in range(s):
                acc = jnp.zeros((nb * hq * wq, cb), jnp.float32)
                for ky in range(kh):
                    if (pad + ri - ky) % s:
                        continue
                    oy = (pad + ri - ky) // s
                    for kx in range(kw):
                        if (pad + rj - kx) % s:
                            continue
                        ox = (pad + rj - kx) // s
                        acc = acc + lax.dot_general(
                            rows_at(oy, ox), w_ref[ky, kx],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=prec)
                row_phases.append(acc.reshape(nb, hq, wq, cb))
            # (nb, hq, wq, s, cb): interleave the column phases
            col_phases.append(jnp.stack(row_phases, axis=3))
        # (nb, hq, s, wq, s, cb) -> (nb, hq*s, wq*s, cb) -> crop
        ph = jnp.stack(col_phases, axis=2)
        dxp = ph.reshape(nb, hq * s, wq * s, cb)[:, :h, :wsp, :]

    if has_emit:
        # the emitted joined activation's cotangent joins the transpose-
        # conv accumulator BEFORE the mask (both flow through the same
        # prologue backward)
        dxp = dxp + g_ref[...].astype(jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        da_ref[...] = jnp.zeros_like(da_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        if has_res:
            dar_ref[...] = jnp.zeros_like(dar_ref)

    if has_pro or has_res:
        x32 = x_ref[...].astype(jnp.float32)
        lin = x32 * a_ref[0][None, None, None, :] \
            + b_ref[0][None, None, None, :]
        if has_res:
            r32 = r_ref[...].astype(jnp.float32)
            lin = lin + r32 * ar_ref[0][None, None, None, :] \
                + br_ref[0][None, None, None, :]
        mask = (lin > 0.0).astype(jnp.float32) if relu \
            else jnp.ones_like(lin)
        dxf = dxp * mask
        dx_ref[...] = (dxf * a_ref[0][None, None, None, :]).astype(
            dx_ref.dtype)
        da_ref[0] += jnp.sum(dxf * x32, axis=(0, 1, 2))
        db_ref[0] += jnp.sum(dxf, axis=(0, 1, 2))
        if has_res:
            dr_ref[...] = (dxf * ar_ref[0][None, None, None, :]).astype(
                dr_ref.dtype)
            dar_ref[0] += jnp.sum(dxf * r32, axis=(0, 1, 2))
    else:
        dx_ref[...] = dxp.astype(dx_ref.dtype)
        # da/db stay at their init zeros (no prologue to differentiate)


def _conv_bwd_dx_pallas(x, w, a, b, y, dy, ds, dss, stride, pad, relu,
                        interpret, *, r=None, ar=None, br=None, g=None):
    from jax.experimental import pallas as pl

    n, h, wsp, ci = x.shape
    kh, kw, _, co = w.shape
    ho, wo = y.shape[1], y.shape[2]
    has_pro = a is not None
    has_res = r is not None
    has_emit = g is not None
    if not has_pro:
        a = jnp.ones((ci,), jnp.float32)
        b = jnp.zeros((ci,), jnp.float32)
    esz = _esz(x.dtype)
    cb = _pick_oc_block(ci, kh * kw * co * esz)
    per_img = 2 * (ho * wo * co * 2 + h * wsp * ci + h * wsp * cb) * esz \
        + h * wsp * cb * 4
    per_img += 2 * h * wsp * cb * esz * (2 * int(has_res) + int(has_emit))
    nb = _pick_nb(n, h, wsp, per_image_bytes=per_img,
                  fixed_bytes=kh * kw * ci * co * esz, stride=stride)
    kernel = functools.partial(
        _conv_bwd_dx_kernel, stride=stride, pad=pad, relu=relu, kh=kh,
        kw=kw, h=h, wsp=wsp, ho=ho, wo=wo, has_pro=has_pro,
        has_res=has_res, has_emit=has_emit, nb=nb)
    in_specs = [
        pl.BlockSpec((nb, ho, wo, co), lambda j, i: (i, 0, 0, 0)),
        pl.BlockSpec((nb, ho, wo, co), lambda j, i: (i, 0, 0, 0)),
        pl.BlockSpec((nb, h, wsp, cb), lambda j, i: (i, 0, 0, j)),
        pl.BlockSpec((kh, kw, cb, co), lambda j, i: (0, 0, j, 0)),
        pl.BlockSpec((1, cb), lambda j, i: (0, j)),
        pl.BlockSpec((1, cb), lambda j, i: (0, j)),
        pl.BlockSpec((1, co), lambda j, i: (0, 0)),
        pl.BlockSpec((1, co), lambda j, i: (0, 0)),
    ]
    operands = [dy, y, x, w,
                a.astype(jnp.float32).reshape(1, ci),
                b.astype(jnp.float32).reshape(1, ci),
                jnp.asarray(ds, jnp.float32).reshape(1, co),
                jnp.asarray(dss, jnp.float32).reshape(1, co)]
    if has_res:
        in_specs += [
            pl.BlockSpec((nb, h, wsp, cb), lambda j, i: (i, 0, 0, j)),
            pl.BlockSpec((1, cb), lambda j, i: (0, j)),
            pl.BlockSpec((1, cb), lambda j, i: (0, j)),
        ]
        operands += [r, jnp.asarray(ar, jnp.float32).reshape(1, ci),
                     jnp.asarray(br, jnp.float32).reshape(1, ci)]
    if has_emit:
        in_specs.append(
            pl.BlockSpec((nb, h, wsp, cb), lambda j, i: (i, 0, 0, j)))
        operands.append(g)
    out_specs = [
        pl.BlockSpec((nb, h, wsp, cb), lambda j, i: (i, 0, 0, j)),
        pl.BlockSpec((1, cb), lambda j, i: (0, j)),
        pl.BlockSpec((1, cb), lambda j, i: (0, j)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n, h, wsp, ci), x.dtype),
        jax.ShapeDtypeStruct((1, ci), jnp.float32),
        jax.ShapeDtypeStruct((1, ci), jnp.float32),
    ]
    if has_res:
        out_specs += [
            pl.BlockSpec((nb, h, wsp, cb), lambda j, i: (i, 0, 0, j)),
            pl.BlockSpec((1, cb), lambda j, i: (0, j)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((n, h, wsp, ci), x.dtype),
            jax.ShapeDtypeStruct((1, ci), jnp.float32),
        ]
    outs = pl.pallas_call(
        kernel,
        grid=(ci // cb, n // nb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **_compiler_params(interpret, ("parallel", "arbitrary")),
    )(*operands)
    if has_res:
        dx, da, db, dr, dar = outs
        return dx, (da[0] if has_pro else None), \
            (db[0] if has_pro else None), dr, dar[0]
    dx, da, db = outs
    if not has_pro:
        return dx, None, None
    return dx, da[0], db[0]


# ---------------------------------------------------------------------------
# backward: dW (per-tap contraction, BN-backward prologue)
# ---------------------------------------------------------------------------

def _conv_bwd_dw_kernel(*refs, stride, pad, relu, kh, kw, ho, wo, has_pro,
                        has_res, nb):
    """dW[ky,kx] += x_proᵀ(tap ky,kx) @ dy_t, accumulated fp32 in the
    VMEM-resident dW block across the inner batch grid dimension.

    Prologues recompute ``x_pro`` (forward BN+ReLU — and, v3, the
    residual join — of the input tile) and fold the stats cotangents
    into ``dy_t`` in VMEM — neither is ever materialised in HBM (the XLA
    backward materialises both)."""
    from jax.experimental import pallas as pl

    it = iter(refs)
    x_ref = next(it)
    dy_ref = next(it)
    y_ref = next(it)
    a_ref = next(it)
    b_ref = next(it)
    ds_ref = next(it)
    dss_ref = next(it)
    r_ref = next(it) if has_res else None
    ar_ref = next(it) if has_res else None
    br_ref = next(it) if has_res else None
    dw_ref = next(it)

    x = x_ref[...]
    ci = x.shape[-1]
    bc = dy_ref.shape[-1]
    cdt = y_ref.dtype
    prec = _prec(cdt)
    if has_pro or has_res:
        x = _prologue(x, a_ref[0], b_ref[0], relu,
                      r_ref[...] if has_res else None,
                      ar_ref[0] if has_res else None,
                      br_ref[0] if has_res else None)
    x = _pad_input(x, pad, stride)
    tap = _make_tap(x, stride, ho, wo, nb, ci)

    dyt = _fold_bn_cotangents(dy_ref[...], y_ref[...], ds_ref[0],
                              dss_ref[0]).astype(cdt)
    dyr = dyt.reshape(nb * ho * wo, bc)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    for ky in range(kh):
        for kx in range(kw):
            dw_ref[ky, kx] += lax.dot_general(
                tap(ky, kx), dyr, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec)


def _conv_bwd_dw_pallas(x, w, a, b, y, dy, ds, dss, stride, pad, relu,
                        interpret, *, r=None, ar=None, br=None):
    from jax.experimental import pallas as pl

    n, h, wsp, ci = x.shape
    kh, kw, _, co = w.shape
    ho, wo = y.shape[1], y.shape[2]
    has_pro = a is not None
    has_res = r is not None
    if not has_pro:
        a = jnp.ones((ci,), jnp.float32)
        b = jnp.zeros((ci,), jnp.float32)
    esz = _esz(x.dtype)
    bc = _pick_oc_block(co, kh * kw * ci * 4)   # fp32 dW accumulator
    per_img = 2 * ((h + 2 * pad) * (wsp + 2 * pad) * ci
                   + 2 * ho * wo * bc) * esz
    per_img += 2 * h * wsp * ci * esz * int(has_res)
    nb = _pick_nb(n, ho, wo, per_image_bytes=per_img,
                  fixed_bytes=kh * kw * ci * bc * 4, stride=stride)
    kernel = functools.partial(
        _conv_bwd_dw_kernel, stride=stride, pad=pad, relu=relu, kh=kh,
        kw=kw, ho=ho, wo=wo, has_pro=has_pro, has_res=has_res, nb=nb)
    in_specs = [
        pl.BlockSpec((nb, h, wsp, ci), lambda j, i: (i, 0, 0, 0)),
        pl.BlockSpec((nb, ho, wo, bc), lambda j, i: (i, 0, 0, j)),
        pl.BlockSpec((nb, ho, wo, bc), lambda j, i: (i, 0, 0, j)),
        pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
        pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
        pl.BlockSpec((1, bc), lambda j, i: (0, j)),
        pl.BlockSpec((1, bc), lambda j, i: (0, j)),
    ]
    operands = [x, dy, y,
                a.astype(jnp.float32).reshape(1, ci),
                b.astype(jnp.float32).reshape(1, ci),
                jnp.asarray(ds, jnp.float32).reshape(1, co),
                jnp.asarray(dss, jnp.float32).reshape(1, co)]
    if has_res:
        in_specs += [
            pl.BlockSpec((nb, h, wsp, ci), lambda j, i: (i, 0, 0, 0)),
            pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
            pl.BlockSpec((1, ci), lambda j, i: (0, 0)),
        ]
        operands += [r, jnp.asarray(ar, jnp.float32).reshape(1, ci),
                     jnp.asarray(br, jnp.float32).reshape(1, ci)]
    dw = pl.pallas_call(
        kernel,
        grid=(co // bc, n // nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((kh, kw, ci, bc),
                               lambda j, i: (0, 0, 0, j)),
        out_shape=jax.ShapeDtypeStruct((kh, kw, ci, co), jnp.float32),
        interpret=interpret,
        **_compiler_params(interpret, ("parallel", "arbitrary")),
    )(*operands)
    return dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# XLA reference formulation (oracle + fallback backward)
# ---------------------------------------------------------------------------

def _apply_prologue_host(x, a, b, r=None, ar=None, br=None, relu=True):
    """The inter-layer boundary in XLA — prologue BN + residual affine +
    ReLU, fp32 math, cast back. THE reference math of the kernels'
    prologue (oracle, fallback backward, and the prephase variant's
    host-side half). The activation is ``where(lin > 0, lin, 0)`` so its
    vjp is the same strict ``lin > 0`` dReLU mask the Pallas kernels use
    (``jnp.maximum`` splits the cotangent 0.5/0.5 at exact zeros)."""
    if a is None and r is None:
        return x
    xf = x.astype(jnp.float32)
    if a is not None:
        xf = xf * a + b
    if r is not None:
        rf = r.astype(jnp.float32)
        xf = xf + (rf if ar is None else rf * ar) \
            + (0.0 if br is None else br)
    if relu:
        xf = jnp.where(xf > 0.0, xf, 0.0)
    return xf.astype(x.dtype)


def _conv_raw(x, w, stride, pad):
    """The bare NHWC/HWIO conv of the reference formulation.

    For bf16/f16 inputs the conv runs NATIVELY in the input dtype (the
    MXU still accumulates fp32 internally; only the output rounds) —
    ``preferred_element_type=f32`` would make the conv's transpose rule
    mix f32 cotangents with bf16 operands, which lax.conv rejects, and
    would silently make every backward conv f32 (2-8x slower)."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    low_prec = _low_prec(x.dtype)
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)], dimension_numbers=dn,
        preferred_element_type=None if low_prec else jnp.float32,
        precision=_prec(x.dtype))


def _conv_part_ref(x, w, a, b, stride, pad, relu, r=None, ar=None,
                   br=None):
    """Prologue (+residual join) + conv only (no stats) — the single XLA
    body shared by the test oracle (_fused_conv_ref) and the fallback
    backward linearization."""
    return _conv_raw(_apply_prologue_host(x, a, b, r=r, ar=ar, br=br,
                                          relu=relu), w, stride, pad)


def _fused_conv_ref(x, w, a, b, stride, pad, relu, r=None, ar=None,
                    br=None):
    """XLA formulation with matching math (prologue in fp32, fp32-
    accumulated conv, stats in fp32). Oracle for tests; the backward
    linearizes through :func:`_conv_part_ref` (the same body minus the
    stats)."""
    y = _conv_part_ref(x, w, a, b, stride, pad, relu, r=r, ar=ar, br=br)
    y32 = y.astype(jnp.float32)
    s = jnp.sum(y32, axis=(0, 1, 2))
    ss = jnp.sum(y32 * y32, axis=(0, 1, 2))
    return y32.astype(x.dtype), s, ss


# ---------------------------------------------------------------------------
# custom vjp (v2 path — no residual operand)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_conv(x, w, a, b, stride, pad, relu, interpret):
    return _fused_conv_pallas(x, w, a, b, stride, pad, relu, interpret)


def _fused_conv_fwd(x, w, a, b, stride, pad, relu, interpret):
    out = _fused_conv_pallas(x, w, a, b, stride, pad, relu, interpret)
    y = out[0]
    return out, (x, w, a, b, y)


def _bwd_wants_pallas(stride):
    """Backward-implementation dispatch (``MXTPU_CONV_BWD``): returns
    (dx_pallas, dw_pallas). ``auto`` runs both Pallas kernels at stride 1
    (51/53 ResNet-50 convs) and the Pallas dW everywhere, keeping the XLA
    dx for strided convs until the phase-stack pattern is proven on the
    TPU tier; ``pallas`` forces both; ``xla`` restores the r4 path."""
    mode = str(config.get("MXTPU_CONV_BWD")).lower()
    if mode == "xla":
        return False, False
    if mode == "pallas":
        return True, True
    return stride == 1, True


def _fused_conv_bwd(stride, pad, relu, interpret, res, cts):
    """Backward. Pallas path (default, see :func:`_bwd_wants_pallas`):
    the dx transpose-conv kernel with the BN-backward prologue + da/db
    epilogue and the dW contraction kernel — the stats cotangents are
    folded in VMEM with the SAVED kernel output, and dy_t / x_pro are
    never materialised in HBM.

    XLA fallback: fold the stats cotangents by hand (``d(sum)/dy = 1``,
    ``d(sumsq)/dy = 2y``) then transpose only prologue+conv via jax.vjp.
    Differentiating the ref's stats directly would make XLA recompute the
    whole forward conv in the backward (ss's vjp needs y), which measured
    ~2x on ResNet-50."""
    x, w, a, b, y = res
    dy, ds, dss = cts
    dx_pallas, dw_pallas = _bwd_wants_pallas(stride)

    dw = None
    if dw_pallas:
        dw = _conv_bwd_dw_pallas(x, w, a, b, y, dy, ds, dss, stride, pad,
                                 relu, interpret)
    if dx_pallas:
        # _bwd_wants_pallas never yields pallas-dx without pallas-dW
        dx, da, db = _conv_bwd_dx_pallas(x, w, a, b, y, dy, ds, dss,
                                         stride, pad, relu, interpret)
        if a is None:
            return dx, dw, None, None
        return dx, dw, da, db

    # XLA dx (and dw unless the Pallas dW already ran) — same fold as the
    # kernels' prologue, materialised since XLA owns the transpose conv
    dy_t = _fold_bn_cotangents(dy, y, ds, dss).astype(y.dtype)
    if a is None:
        if dw is not None:
            _, vjp = jax.vjp(
                lambda x_: _conv_part_ref(x_, w, None, None, stride, pad,
                                          relu), x)
            (dx,) = vjp(dy_t)
            return dx, dw, None, None
        _, vjp = jax.vjp(
            lambda x_, w_: _conv_part_ref(x_, w_, None, None, stride, pad,
                                          relu), x, w)
        dx, dwx = vjp(dy_t)
        return dx, dwx, None, None
    if dw is not None:
        _, vjp = jax.vjp(
            lambda x_, a_, b_: _conv_part_ref(x_, w, a_, b_, stride, pad,
                                              relu), x, a, b)
        dx, da, db = vjp(dy_t)
        return dx, dw, da, db
    _, vjp = jax.vjp(
        lambda x_, w_, a_, b_: _conv_part_ref(x_, w_, a_, b_, stride, pad,
                                              relu), x, w, a, b)
    return vjp(dy_t)


_fused_conv.defvjp(_fused_conv_fwd, _fused_conv_bwd)


# ---------------------------------------------------------------------------
# custom vjp (v3 path — residual operand, optional emitted activation)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _fused_conv_epi(x, w, a, b, r, ar, br, stride, pad, relu, emit,
                    interpret):
    return _fused_conv_pallas(x, w, a, b, stride, pad, relu, interpret,
                              r=r, ar=ar, br=br, emit=emit)


def _fused_conv_epi_fwd(x, w, a, b, r, ar, br, stride, pad, relu, emit,
                        interpret):
    out = _fused_conv_pallas(x, w, a, b, stride, pad, relu, interpret,
                             r=r, ar=ar, br=br, emit=emit)
    y = out[0]
    return out, (x, w, a, b, r, ar, br, y)


def _fused_conv_epi_bwd(stride, pad, relu, emit, interpret, res, cts):
    """Backward of the residual-epilogue kernel. Pallas path: the dx
    kernel streams ``dr = dlin·ar`` out next to dx, accumulates
    ``dar = Σ dlin·r`` next to da/db (``dbr ≡ db`` — the shift enters
    the same linear term), and folds the emitted activation's cotangent
    into the transpose-conv accumulator before the dReLU mask; the dW
    kernel recomputes the joined ``x_pro`` in VMEM. XLA fallback: one
    jax.vjp over the (prologue+join+conv, x_pro) pair."""
    x, w, a, b, r, ar, br, y = res
    if emit:
        dy, ds, dss, g = cts
    else:
        dy, ds, dss = cts
        g = None
    dx_pallas, dw_pallas = _bwd_wants_pallas(stride)

    dw = None
    if dw_pallas:
        dw = _conv_bwd_dw_pallas(x, w, a, b, y, dy, ds, dss, stride, pad,
                                 relu, interpret, r=r, ar=ar, br=br)
    if dx_pallas:
        dx, da, db, dr, dar = _conv_bwd_dx_pallas(
            x, w, a, b, y, dy, ds, dss, stride, pad, relu, interpret,
            r=r, ar=ar, br=br, g=g)
        return dx, dw, da, db, dr, dar, db

    dy_t = _fold_bn_cotangents(dy, y, ds, dss).astype(y.dtype)
    g0 = jnp.zeros_like(x) if g is None else g

    def f(x_, a_, b_, r_, ar_, br_, w_):
        xp = _apply_prologue_host(x_, a_, b_, r=r_, ar=ar_, br=br_,
                                  relu=relu)
        return _conv_raw(xp, w_, stride, pad), xp

    if dw is not None:
        _, vjp = jax.vjp(
            lambda x_, a_, b_, r_, ar_, br_: f(x_, a_, b_, r_, ar_, br_,
                                               w), x, a, b, r, ar, br)
        dx, da, db, dr, dar, dbr = vjp((dy_t, g0))
        return dx, dw, da, db, dr, dar, dbr
    _, vjp = jax.vjp(
        lambda x_, w_, a_, b_, r_, ar_, br_: f(x_, a_, b_, r_, ar_, br_,
                                               w_), x, w, a, b, r, ar, br)
    dx, dwx, da, db, dr, dar, dbr = vjp((dy_t, g0))
    return dx, dwx, da, db, dr, dar, dbr


_fused_conv_epi.defvjp(_fused_conv_epi_fwd, _fused_conv_epi_bwd)


from .pallas_attention import pallas_available as pallas_conv_available


@register("fused_conv_bn")
def fused_conv_bn(x, w, a=None, b=None, stride=1, pad=0, relu=True,
                  resid=None, resid_scale=None, resid_shift=None,
                  emit_act=False, interpret=None):
    """Fused (prologue-BN+ReLU [+residual join]) -> Conv2D -> (stats
    epilogue).

    x: (N, H, W, Ci) NHWC; w: (kh, kw, Ci, Co) HWIO; a/b: optional (Ci,)
    fp32 scale/shift applied to x first (the PREVIOUS BatchNorm folded to
    ``a = gamma/sqrt(var+eps)``, ``b = beta - mean*a``); ``relu`` gates the
    prologue activation. Returns ``(y_raw, sum, sumsq)`` where the fp32
    per-channel stats are taken over the raw conv output — feed them to
    :func:`bn_scale_shift` to fold THIS layer's BN into the next call.

    v3 residual epilogue: ``resid`` (x-shaped) streams as a third operand
    and the prologue becomes the whole bottleneck junction
    ``relu(a·x + b + resid_scale·resid + resid_shift)`` — identity
    shortcuts default ``resid_scale/shift`` to 1/0; a downsample branch
    passes its folded BN coefficients. With ``emit_act=True`` the joined
    activation is additionally returned (4th output) for the shortcut-
    path consumer — one extra write instead of a separate XLA join op's
    two reads + write.

    Differentiable: the custom vjp runs the v2/v3 Pallas backward kernels
    (dx transpose-conv with BN-backward prologue + da/db/dar epilogue and
    residual-cotangent stream-out; dW contraction) — see
    ``MXTPU_CONV_BWD`` for the dispatch contract and
    ``MXTPU_CONV_STRIDE2`` for the strided-layout variant.
    """
    if interpret is None:
        interpret = not pallas_conv_available()
    if resid is None:
        if emit_act:
            raise ValueError(
                "emit_act requires a resid operand (the emitted "
                "activation is the joined shortcut input; without a "
                "residual the caller already holds x)")
        return _fused_conv(x, w, a, b, int(stride), int(pad), bool(relu),
                           bool(interpret))
    ci = x.shape[-1]
    if a is None:
        # dummy identity prologue keeps one kernel/vjp signature; the
        # da/db cotangents fall out as constants the caller never sees
        a = jnp.ones((ci,), jnp.float32)
        b = jnp.zeros((ci,), jnp.float32)
    ar = jnp.ones((ci,), jnp.float32) if resid_scale is None \
        else resid_scale
    br = jnp.zeros((ci,), jnp.float32) if resid_shift is None \
        else resid_shift
    return _fused_conv_epi(x, w, a, b, resid, ar, br, int(stride),
                           int(pad), bool(relu), bool(emit_act),
                           bool(interpret))


def bn_scale_shift(s, ss, count, gamma, beta, eps=1e-5):
    """Fold batch statistics + BN parameters into per-channel (a, b) for
    the next kernel's prologue. Returns (a, b, mean, var) — mean/var for
    the running-stat update (gluon BatchNorm semantics)."""
    count = jnp.asarray(count, jnp.float32)
    mean = s / count
    var = jnp.maximum(ss / count - mean * mean, 0.0)
    inv = lax.rsqrt(var + eps)
    a = gamma.astype(jnp.float32) * inv
    b = beta.astype(jnp.float32) - mean * a
    return a, b, mean, var
