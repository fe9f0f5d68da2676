#!/usr/bin/env python
"""Fused Pallas conv+BN kernel vs the XLA conv->BN chain, per ResNet-50
conv shape, on the real chip — forward AND backward rows.

Three measurements per shape (training BN semantics):
  conv  — lax.conv alone (the per-shape roofline reference)
  xla   — lax.conv (bf16, fp32 acc) -> per-channel mean/var stat pass ->
          normalize+relu apply pass (what the zoo model does today)
  fused — Pallas fused_conv_bn (prologue BN+relu of the PREVIOUS layer +
          conv + stats epilogue) — one HBM round-trip
plus, with ``--bwd``, the gradient of a scalarized head through each
formulation (the v2 Pallas dx/dW kernels vs XLA's transpose-conv
autodiff; ``MXTPU_CONV_BWD`` governs the fused dispatch), and, with
``--epilogue``, the v3 residual-junction rows: the xla column becomes
join-materialise-then-conv (``relu(a*x+b+r)`` in XLA, then the conv —
what the v2 model does at every bottleneck boundary) and the fused
column streams the residual as a third kernel operand so the whole
conv+BN+ReLU+residual-add junction is ONE kernel.

Timing: fence-cancelling repeated two-point fits over on-device
lax.fori_loop windows (bench._fit_windows — median of K fits with
recorded spread; see PROFILE.md).

Usage: python benchmark/fused_conv_bench.py [--iters 20] [--batch 64]
           [--bwd] [--shapes l2.3x3,l4.3x3]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# (name, H, Ci, Co, k, stride) — ResNet-50 body shapes (NHWC)
SHAPES = [
    ("l1.1x1a", 56, 64, 64, 1, 1),
    ("l1.3x3", 56, 64, 64, 3, 1),
    ("l1.1x1b", 56, 64, 256, 1, 1),
    ("l2.3x3", 28, 128, 128, 3, 1),
    ("l2.1x1b", 28, 128, 512, 1, 1),
    ("l2.down", 56, 256, 512, 1, 2),
    ("l2.3x3s", 56, 128, 128, 3, 2),
    # the prephase-selected strided shapes (MXTPU_CONV_STRIDE2 auto:
    # out extents 14^2/7^2 want >8 images/program — PROFILE.md conv v3)
    ("l3.3x3s", 28, 256, 256, 3, 2),
    ("l3.down", 28, 512, 1024, 1, 2),
    ("l4.3x3s", 14, 512, 512, 3, 2),
    ("l4.down", 14, 1024, 2048, 1, 2),
    ("l3.3x3", 14, 256, 256, 3, 1),
    ("l3.1x1b", 14, 256, 1024, 1, 1),
    ("l4.3x3", 7, 512, 512, 3, 1),
    ("l4.1x1b", 7, 512, 2048, 1, 1),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--shapes", type=str, default="")
    ap.add_argument("--bwd", action="store_true",
                    help="also measure the backward of each formulation")
    ap.add_argument("--epilogue", action="store_true",
                    help="measure the v3 residual-junction rows (the "
                         "residual streams into the fused kernel; the "
                         "xla column materialises the join first)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmark.resnet_decision_bench import fit_time
    from incubator_mxnet_tpu.ops.pallas_conv import fused_conv_bn

    n = args.batch
    iters = args.iters
    rs = np.random.RandomState(0)
    only = set(args.shapes.split(",")) if args.shapes else None

    print(f"batch={n} iters={iters}/{4 * iters} (fit windows) "
          f"dev={jax.devices()[0].device_kind}")
    hdr = f"{'shape':10s} {'dir':3s} {'conv ms':>8s} {'xla ms':>8s} " \
          f"{'fused ms':>9s} {'speedup':>8s} {'TF/s fus':>9s}"
    print(hdr)
    for name, h, ci, co, k, stride in SHAPES:
        if only and name not in only:
            continue
        pad = k // 2
        x = jnp.asarray(rs.randn(n, h, h, ci), jnp.bfloat16)
        w = jnp.asarray(rs.randn(k, k, ci, co) * 0.05, jnp.bfloat16)
        g = jnp.ones((co,), jnp.float32)
        b = jnp.zeros((co,), jnp.float32)
        a_pro = jnp.ones((ci,), jnp.float32)
        b_pro = jnp.zeros((ci,), jnp.float32)
        ho = h // stride
        flops = 2 * n * ho * ho * ci * co * k * k

        def conv_only(c, wc):
            dn = lax.conv_dimension_numbers(c.shape, wc.shape,
                                            ("NHWC", "HWIO", "NHWC"))
            # bf16 runs natively (f32 preferred_element_type would mix
            # dtypes in the conv transpose — same constraint as
            # _conv_part_ref; the MXU still accumulates fp32 internally)
            low = c.dtype in (jnp.bfloat16, jnp.float16)
            y = lax.conv_general_dilated(
                c, wc, (stride, stride), [(pad, pad)] * 2,
                dimension_numbers=dn,
                preferred_element_type=None if low else jnp.float32)
            return y.astype(c.dtype), None, None

        def xla_chain(c, wc):
            y, _, _ = conv_only(c, wc)
            y32 = y.astype(jnp.float32)
            mu = jnp.mean(y32, axis=(0, 1, 2))
            var = jnp.maximum(jnp.mean(y32 * y32, axis=(0, 1, 2))
                              - mu * mu, 0.0)
            out = ((y32 - mu) * lax.rsqrt(var + 1e-5) * g + b)
            return jnp.maximum(out, 0.0).astype(c.dtype), mu, var

        def fused(c, wc):
            return fused_conv_bn(c, wc, a_pro, b_pro, stride=stride,
                                 pad=pad, relu=True)

        # --epilogue: the v3 residual-junction formulations. The xla
        # column is what the v2 model executes at a bottleneck boundary
        # (join materialised by a separate elementwise op, then the
        # conv); the fused column is the ONE-kernel junction. The
        # residual operand itself is built only when the mode engages
        # (below) — no dead H2D on default runs.

        def conv_only_res(c, res, wc):
            return conv_only(c, wc)

        def xla_chain_res(c, res, wc):
            xn = jnp.maximum(
                c.astype(jnp.float32) * a_pro + b_pro
                + res.astype(jnp.float32), 0.0).astype(c.dtype)
            y, _, _ = conv_only(xn, wc)
            y32 = y.astype(jnp.float32)
            s = jnp.sum(y32, axis=(0, 1, 2))
            ss = jnp.sum(y32 * y32, axis=(0, 1, 2))
            return y, s, ss

        def fused_res(c, res, wc):
            return fused_conv_bn(c, wc, a_pro, b_pro, stride=stride,
                                 pad=pad, relu=True, resid=res)

        def fwd_loop(step):
            # serialize iterations through the (small) WEIGHT operand —
            # a whole-x carried dependency costs an extra HBM pass over
            # the activation that pollutes the measurement; the operand
            # tuple rides in as an argument (a captured constant would
            # be const-folded); the dep is a direct scalar index
            # (reshape(-1)[0] forces a relayout)
            def body_of(xx):
                def body(i, wc):
                    out, s1, s2 = step(*xx, wc)
                    dep = out[(0,) * out.ndim].astype(jnp.float32)
                    if s1 is not None:
                        dep = dep + (s1[0] + s2[0]) * 1e-20
                    return wc * (1.0 + 0.0 * dep).astype(wc.dtype)
                return body
            return jax.jit(lambda kk, xx: jnp.sum(
                lax.fori_loop(0, kk, body_of(xx), w)[(0,) * w.ndim]
                .astype(jnp.float32)), static_argnums=0)

        def bwd_loop(step):
            def loss(ops, wc):
                out, s1, s2 = step(*ops, wc)
                head = jnp.sum(out.astype(jnp.float32)) * 1e-6
                if s1 is not None:
                    head = head + jnp.sum(s1) * 1e-8 + jnp.sum(s2) * 1e-10
                return head

            grad = jax.grad(loss, argnums=(0, 1))

            def body_of(xx):
                def body(i, wc):
                    dops, dw = grad(xx, wc)
                    # scalar deps keep EVERY grad instruction live (XLA
                    # DCEs whole instructions, not elements) without an
                    # extra HBM pass over the activation-sized dx/dr
                    dep = dw[(0,) * dw.ndim].astype(jnp.float32)
                    for d in dops:
                        dep = dep + d[(0,) * d.ndim].astype(jnp.float32)
                    return wc * (1.0 + 0.0 * dep).astype(wc.dtype)
                return body
            return jax.jit(lambda kk, xx: jnp.sum(
                lax.fori_loop(0, kk, body_of(xx), w)[(0,) * w.ndim]
                .astype(jnp.float32)), static_argnums=0)

        if args.epilogue:
            triples = (("conv", conv_only_res), ("xla", xla_chain_res),
                       ("fused", fused_res))
            xs = (x, jnp.asarray(rs.randn(n, h, h, ci) * 0.1, x.dtype))
        else:
            triples = (("conv", conv_only), ("xla", xla_chain),
                       ("fused", fused))
            xs = (x,)
        rows = [("fwd", fwd_loop, flops)]
        if args.bwd:
            # the grad row executes fwd + dx + dW (forward recompute is
            # not DCE-able: the stats cotangent needs y) ~ 3x fwd FLOPs
            rows.append(("f+b", bwd_loop, 3 * flops))
        for tag, mk, fl in rows:
            res = {}
            for label, step in triples:
                try:
                    run = mk(step)
                    per, _ = fit_time(
                        lambda kk: jax.device_get(run(kk, xs)), iters,
                        4 * iters)
                    res[label] = per
                except Exception as e:
                    print(f"{name:10s} {tag} {label} FAILED: "
                          f"{str(e)[:110]}")
                    res[label] = float("nan")
            if all(np.isfinite(v) for v in res.values()):
                tag_out = tag if not args.epilogue else f"{tag}+r"
                print(f"{name:10s} {tag_out:5s} {res['conv']*1e3:8.3f} "
                      f"{res['xla']*1e3:8.3f} {res['fused']*1e3:9.3f} "
                      f"{res['xla']/res['fused']:8.2f} "
                      f"{fl/res['fused']/1e12:9.1f}", flush=True)


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
