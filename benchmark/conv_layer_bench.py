#!/usr/bin/env python
"""Per-layer conv benchmark at the REAL ResNet-50 shape table, with
on-device ``lax.fori_loop`` chained timing (each iteration consumes the
previous output, so nothing is dead-code-eliminated and the per-call
dispatch latency is amortised over the whole loop — the r4
per-layer microbench dispatched per call and was overhead-dominated;
PROFILE.md header).

Variants per shape:
  xla_nchw  — lax.conv NCHW (what the zoo model runs)
  xla_nhwc  — lax.conv NHWC
  pallas    — ops.pallas_conv fused kernel (prologue+stats included)

Usage: python benchmark/conv_layer_bench.py [--batch 128] [--iters 20]
       [--only l4] [--variants xla_nchw,xla_nhwc,pallas]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# (name, H, Cin, Cout, k, stride) — every distinct conv shape in
# ResNet-50 v1 (stem excluded: C_in=3 stays in XLA per the kernel
# contract). H is the INPUT spatial size at batch-major NHWC.
SHAPES = [
    ("l1.proj",   56,   64,  256, 1, 1),
    ("l1.c1",     56,   64,   64, 1, 1),
    ("l1.c2",     56,   64,   64, 3, 1),
    ("l1.c3",     56,   64,  256, 1, 1),
    ("l1.c1b",    56,  256,   64, 1, 1),
    ("l2.proj",   56,  256,  512, 1, 2),
    ("l2.c1",     56,  256,  128, 1, 2),
    ("l2.c2",     28,  128,  128, 3, 1),
    ("l2.c3",     28,  128,  512, 1, 1),
    ("l2.c1b",    28,  512,  128, 1, 1),
    ("l3.proj",   28,  512, 1024, 1, 2),
    ("l3.c1",     28,  512,  256, 1, 2),
    ("l3.c2",     14,  256,  256, 3, 1),
    ("l3.c3",     14,  256, 1024, 1, 1),
    ("l3.c1b",    14, 1024,  256, 1, 1),
    ("l4.proj",   14, 1024, 2048, 1, 2),
    ("l4.c1",     14, 1024,  512, 1, 2),
    ("l4.c2",      7,  512,  512, 3, 1),
    ("l4.c3",      7,  512, 2048, 1, 1),
    ("l4.c1b",     7, 2048,  512, 1, 1),
]


def build_variant(variant, batch, h, ci, co, k, stride, dtype):
    import jax
    import jax.numpy as jnp
    from jax import lax

    pad = (k - 1) // 2
    rs = np.random.RandomState(0)
    gamma = jnp.asarray(rs.rand(ci).astype(np.float32) + 0.5)
    beta = jnp.asarray(rs.rand(ci).astype(np.float32))

    if variant == "pallas":
        from incubator_mxnet_tpu.ops.pallas_conv import fused_conv_bn

        x = jnp.asarray(rs.rand(batch, h, h, ci), dtype)
        w = jnp.asarray(rs.rand(k, k, ci, co) * 0.1, dtype)

        def body(i, carry):
            x_, s_ = carry
            y, s, ss = fused_conv_bn(x_, w, gamma, beta, stride=stride,
                                     pad=pad, relu=True, interpret=False)
            # feed a scalar of y back so iterations chain (same H needs
            # stride 1; strided shapes chain through the stats only)
            bump = (s[0] * 1e-20).astype(dtype)
            if stride == 1 and ci == co:
                return x_ + y * 1e-20, s_ + s[0]
            return x_ + bump, s_ + s[0]

        def run(iters):
            xf, sf = lax.fori_loop(0, iters, body,
                                   (x, jnp.zeros((), jnp.float32)))
            return sf

    else:
        nchw = variant == "xla_nchw"
        if nchw:
            x = jnp.asarray(rs.rand(batch, ci, h, h), dtype)
            w = jnp.asarray(rs.rand(co, ci, k, k) * 0.1, dtype)
            dn = lax.conv_dimension_numbers(
                x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
            bshape = (1, ci, 1, 1)
        else:
            x = jnp.asarray(rs.rand(batch, h, h, ci), dtype)
            w = jnp.asarray(rs.rand(k, k, ci, co) * 0.1, dtype)
            dn = lax.conv_dimension_numbers(
                x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
            bshape = (1, 1, 1, ci)

        def body(i, carry):
            x_, s_ = carry
            # same math as the fused kernel: BN scale/shift + relu on the
            # input, conv, then the output stat reductions
            xn = jnp.maximum(
                x_.astype(jnp.float32) * gamma.reshape(bshape)
                + beta.reshape(bshape), 0.0).astype(dtype)
            y = lax.conv_general_dilated(
                xn, w, (stride, stride), [(pad, pad), (pad, pad)],
                dimension_numbers=dn)
            y32 = y.astype(jnp.float32)
            ax = (0, 2, 3) if nchw else (0, 1, 2)
            s = jnp.sum(y32, axis=ax)
            ss = jnp.sum(y32 * y32, axis=ax)
            bump = ((s[0] + ss[0]) * 1e-20).astype(dtype)
            if stride == 1 and ci == co:
                return x_ + y * 1e-20, s_ + s[0]
            return x_ + bump, s_ + s[0]

        def run(iters):
            xf, sf = lax.fori_loop(0, iters, body,
                                   (x, jnp.zeros((), jnp.float32)))
            return sf

    return jax.jit(run, static_argnums=0)


def main():
    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--variants", default="xla_nchw,pallas")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32

    print(f"{'shape':9s} " + "".join(
        f"{v:>12s}" for v in args.variants.split(",")) + "   TF/s(best)")
    for name, h, ci, co, k, stride in SHAPES:
        if args.only and args.only not in name:
            continue
        ho = h // stride
        flops = 2 * args.batch * ho * ho * ci * co * k * k
        row, times = f"{name:9s} ", {}
        for variant in args.variants.split(","):
            try:
                run = build_variant(variant, args.batch, h, ci, co, k,
                                    stride, dtype)
                # warm with the SAME static iters value — static_argnums
                # caches per value, so run(2) would leave the timed call
                # to retrace+compile inside the measurement
                float(jax.device_get(run(args.iters)))
                t0 = time.perf_counter()
                float(jax.device_get(run(args.iters)))
                dt = (time.perf_counter() - t0) / args.iters
                times[variant] = dt
                row += f"{dt * 1e3:10.3f}ms"
            except Exception as e:
                row += f"  FAIL:{str(e)[:40]:>40s}"
        if times:
            best = min(times.values())
            row += f"   {flops / best / 1e12:7.1f}"
        print(row, flush=True)


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
