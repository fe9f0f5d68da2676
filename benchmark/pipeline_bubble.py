#!/usr/bin/env python
"""Pipeline bubble measurement: step time vs microbatch count for the
GPipe and 1F1B schedules on the virtual 8-device CPU mesh (VERDICT r4
item 6 'done' criterion — writes the docs/PIPELINE.md table numbers).

Analytic bubble fraction (per direction): (S-1) / (M + S - 1) for GPipe;
1F1B interleaves both directions in M + 2(S-1) combined ticks — same
bubble fraction, but activation stash bounded by 2S-1 instead of M+S-1.

Usage:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmark/pipeline_bubble.py [--stages 4] [--width 256]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--width", type=int, default=256)
    ap.add_argument("--mb-size", type=int, default=32)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax

    # schedule arithmetic on the CPU: pin it before the first backend
    # query, so this script never takes the chip
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from incubator_mxnet_tpu import parallel

    S, D = args.stages, args.width
    rs = np.random.RandomState(0)
    mesh = parallel.make_mesh({"pipe": S},
                              devices=jax.devices()[:S])
    stacked = {"w": jnp.asarray(
        rs.randn(S, D, D).astype(np.float32) * 0.1)}

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    def per_mb_loss(h, y):
        return jnp.mean((h - y) ** 2)

    print(f"S={S} D={D} mb_size={args.mb_size} "
          f"(fixed microbatch size; batch grows with M; gpipe2/inter = "
          f"the SAME 2S-layer model, 2-layer stages vs V=2 interleaved)")
    print(f"{'M':>4} {'gpipe ms':>9} {'1f1b ms':>9} {'gpipe2 ms':>9} "
          f"{'inter ms':>9} {'bubble%':>8} {'i-bubble%':>9}")
    for M in (S, 2 * S, 4 * S, 8 * S):
        B = args.mb_size * M
        x = jnp.asarray(rs.randn(B, D).astype(np.float32))
        y = jnp.asarray(rs.randn(B, D).astype(np.float32))

        def loss_gpipe(params):
            out = parallel.pipeline_apply(stage_fn, params, x, mesh=mesh,
                                          num_microbatches=M)
            return jnp.mean((out - y) ** 2)

        g_gpipe = jax.jit(jax.value_and_grad(loss_gpipe))
        f_1f1b = jax.jit(lambda p: parallel.pipeline_apply_1f1b(
            stage_fn, p, x, y, per_mb_loss, mesh=mesh,
            num_microbatches=M))

        # interleaved vs 2-layer-per-stage GPipe: SAME 2S-layer model on
        # the same S devices — GPipe fuses 2 layers per tick, the
        # interleaved schedule runs V=2 single-layer chunks per device
        # (bubble (S-1)/(MV+S-1), half of GPipe's relative bubble)
        stacked_v = {"w": jnp.asarray(
            rs.randn(2 * S, D, D).astype(np.float32) * 0.1)}
        stacked_2 = {"w": stacked_v["w"].reshape(S, 2, D, D)}

        def stage2_fn(p, h):
            return jnp.tanh(jnp.tanh(h @ p["w"][0]) @ p["w"][1])

        def loss_gpipe2(params):
            out = parallel.pipeline_apply(stage2_fn, params, x, mesh=mesh,
                                          num_microbatches=M)
            return jnp.mean((out - y) ** 2)

        def loss_inter(params):
            out = parallel.pipeline_apply_interleaved(
                stage_fn, params, x, mesh=mesh, num_microbatches=M)
            return jnp.mean((out - y) ** 2)

        g_gpipe2 = jax.jit(jax.value_and_grad(loss_gpipe2))
        g_inter = jax.jit(jax.value_and_grad(loss_inter))

        res = {}
        for name, fn in (("gpipe", g_gpipe), ("1f1b", f_1f1b),
                         ("gpipe2", lambda _: g_gpipe2(stacked_2)),
                         ("inter", lambda _: g_inter(stacked_v))):
            out = fn(stacked)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(stacked)
            jax.block_until_ready(out)
            res[name] = (time.perf_counter() - t0) / args.iters * 1e3
        bubble = 100.0 * (S - 1) / (M + S - 1)
        ibubble = 100.0 * (S - 1) / (M * 2 + S - 1)
        print(f"{M:4d} {res['gpipe']:9.2f} {res['1f1b']:9.2f} "
              f"{res['gpipe2']:9.2f} {res['inter']:9.2f} "
              f"{bubble:8.1f} {ibubble:9.1f}", flush=True)


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
