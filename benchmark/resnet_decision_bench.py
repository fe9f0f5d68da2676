#!/usr/bin/env python
"""ResNet decision measurements, all with fence-cancelling repeated
two-point-fit timing (PROFILE.md round-5 correction + round-6
median-of-K reproducibility layer via bench._fit_windows):

  a. v2 Pallas fused-conv rate per shape vs XLA NCHW — now with a
     BACKWARD row per shape (the v2 Pallas dx/dW kernels vs XLA's
     transpose-conv autodiff), covering the four key 3x3 shapes PLUS the
     strided and 1x1 projection kernels
  b. whole-model train step at batch 128 vs 256 (r3's "flat batch
     scaling" was fence-biased)
  c. BN use_global_stats ablation (re-validate the ~15.3 ms stat cost)
  d. whole-model fused_resnet50_v1 vs zoo resnet50_v1 train step — the
     row that decides whether the 15.3 ms BN-stat prize is claimed
     (fused >= zoo - 5% flips the BENCH headline to the fused model)

Runs unchanged on the next TPU tier pass:
    python benchmark/resnet_decision_bench.py [--which a,b,c,d]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def fit_time(run, n1, n2, reps=2):
    """Warm both window sizes, then delegate the slope fit to bench.py's
    shared `_fit_windows` (one implementation of the fence-cancelling
    methodology). Returns (per-iter seconds, fence intercept)."""
    import jax

    from bench import _fit_windows

    jax.block_until_ready(run(n1))
    jax.block_until_ready(run(n2))

    times = {}

    def window(n):
        best = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(run(n))
            best = min(best, time.perf_counter() - t0)
        times[n] = best
        return best

    per = _fit_windows(window, n1, n2)
    return per, times[n1] - per * n1


# (ci, co, hw, k, stride, name) — the four key 3x3 shapes, the strided
# 3x3 + 1x1 downsample projections (incl. the l3/l4 strided shapes the
# MXTPU_CONV_STRIDE2 auto heuristic routes to the prephase layout —
# PROFILE.md "conv v3"), and two 1x1 body projections
SHAPES_A = [
    (64, 64, 56, 3, 1, "l1.c2"), (128, 128, 28, 3, 1, "l2.c2"),
    (256, 256, 14, 3, 1, "l3.c2"), (512, 512, 7, 3, 1, "l4.c2"),
    (128, 128, 56, 3, 2, "l2.c2s"), (256, 512, 56, 1, 2, "l2.ds"),
    (256, 256, 28, 3, 2, "l3.c2s"), (512, 512, 14, 3, 2, "l4.c2s"),
    (256, 64, 56, 1, 1, "l1.c1b"), (1024, 256, 14, 1, 1, "l3.c1b"),
]


def part_a(batch=128):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from incubator_mxnet_tpu.ops.pallas_conv import fused_conv_bn

    rs = np.random.RandomState(0)
    with jax.default_matmul_precision("default"):
        for ci, co, hw, k, stride, name in SHAPES_A:
            pad = (k - 1) // 2
            xh = jnp.asarray(rs.rand(batch, hw, hw, ci), jnp.bfloat16)
            wh = jnp.asarray(rs.rand(k, k, ci, co) * 0.1, jnp.bfloat16)
            g = jnp.asarray(rs.rand(ci).astype(np.float32) + 0.5)
            b = jnp.asarray(rs.rand(ci).astype(np.float32))

            def pfwd(c, w_):
                return fused_conv_bn(c, w_, g, b, stride=stride, pad=pad,
                                     relu=True, interpret=False)

            def pbody(i, c):
                y, s, ss = pfwd(c, wh)
                # keep stats alive in the chain (DCE guard) either way
                upd = ((s[0] + ss[0]) * 1e-20).astype(c.dtype)
                if ci == co and stride == 1:
                    return c * 0.9 + y * 1e-6 + upd
                return c * 0.9 + upd

            prun = jax.jit(
                lambda kk: lax.fori_loop(0, kk, pbody, xh),
                static_argnums=0)

            # backward: grad of a scalarized head through the fused
            # kernel == one dx + one dW Pallas kernel + the folded BN
            # cotangents (MXTPU_CONV_BWD governs dispatch)
            def ploss(c, w_):
                y, s, ss = pfwd(c, w_)
                return (jnp.sum(y.astype(jnp.float32)) * 1e-6
                        + jnp.sum(s) * 1e-8 + jnp.sum(ss) * 1e-10)

            pgrad = jax.grad(ploss, argnums=(0, 1))

            def pbwd_body(i, c):
                dx, dw = pgrad(c, wh)
                # fold dw into the carry too — an unused dW contraction
                # would be DCE'd and the row would time only dx
                dwdep = (jnp.sum(dw.astype(jnp.float32)) * 1e-20
                         ).astype(c.dtype)
                return c * 0.9 + dx.astype(c.dtype) * 1e-6 + dwdep

            pbrun = jax.jit(
                lambda kk: lax.fori_loop(0, kk, pbwd_body, xh),
                static_argnums=0)

            xc = jnp.asarray(rs.rand(batch, ci, hw, hw), jnp.bfloat16)
            wc = jnp.asarray(rs.rand(co, ci, k, k) * 0.1, jnp.bfloat16)
            dn = lax.conv_dimension_numbers(
                xc.shape, wc.shape, ("NCHW", "OIHW", "NCHW"))
            gc = g.reshape(1, ci, 1, 1)
            bc = b.reshape(1, ci, 1, 1)

            def xfwd(c, w_):
                xn = jnp.maximum(c.astype(jnp.float32) * gc + bc, 0.0
                                 ).astype(c.dtype)
                y = lax.conv_general_dilated(
                    xn, w_, (stride, stride), [(pad, pad), (pad, pad)],
                    dimension_numbers=dn)
                y32 = y.astype(jnp.float32)
                s = jnp.sum(y32, axis=(0, 2, 3))
                ss = jnp.sum(y32 * y32, axis=(0, 2, 3))
                return y, s, ss

            def xbody(i, c):
                y, s, ss = xfwd(c, wc)
                # fold the stats into the carry so XLA cannot DCE the
                # two reduction passes (review r5: ci==co shapes were
                # silently dropping them, biasing the comparison)
                upd = ((s[0] + ss[0]) * 1e-20).astype(c.dtype)
                if ci == co and stride == 1:
                    return c * 0.9 + y * 1e-6 + upd
                return c * 0.9 + upd

            xrun = jax.jit(
                lambda kk: lax.fori_loop(0, kk, xbody, xc),
                static_argnums=0)

            def xloss(c, w_):
                y, s, ss = xfwd(c, w_)
                return (jnp.sum(y.astype(jnp.float32)) * 1e-6
                        + jnp.sum(s) * 1e-8 + jnp.sum(ss) * 1e-10)

            xgrad = jax.grad(xloss, argnums=(0, 1))

            def xbwd_body(i, c):
                dx, dw = xgrad(c, wc)
                dwdep = (jnp.sum(dw.astype(jnp.float32)) * 1e-20
                         ).astype(c.dtype)
                return c * 0.9 + dx.astype(c.dtype) * 1e-6 + dwdep

            xbrun = jax.jit(
                lambda kk: lax.fori_loop(0, kk, xbwd_body, xc),
                static_argnums=0)

            fl = 2 * batch * (hw // stride) ** 2 * ci * co * k * k
            rows = [("fwd", prun, xrun, fl),
                    # the grad row executes fwd + dx + dW (the loss
                    # depends on sum(ss) whose cotangent needs y, so the
                    # forward cannot be DCE'd; the fused custom_vjp runs
                    # its forward for residuals either way) ~ 3x fl
                    ("f+b", pbrun, xbrun, 3 * fl)]
            for tag, pr, xr, fl_ in rows:
                try:
                    pp, _ = fit_time(pr, 10, 40)
                    pal = f"{pp * 1e3:7.3f} ms {fl_ / pp / 1e12:6.1f} TF/s"
                except Exception as e:
                    pal = f"FAIL {str(e)[:60]}"
                try:
                    xp, _ = fit_time(xr, 10, 40)
                    xla = f"{xp * 1e3:7.3f} ms {fl_ / xp / 1e12:6.1f} TF/s"
                except Exception as e:
                    xla = f"FAIL {str(e)[:60]}"
                print(f"{name:7s} {tag} pallas {pal} | xla+bn {xla}",
                      flush=True)


def _trainer(batch_per_chip, use_global_stats=False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    # per-chip convention matching bench.py (batch scales with devices,
    # throughput reported /chip) so the numbers stay citable next to
    # BENCH_r0x on any device count
    batch = batch_per_chip * len(jax.devices())
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init="xavier")
    net.cast("bfloat16")
    net(mx.nd.zeros((2, 3, 224, 224), dtype="bfloat16"))
    if use_global_stats:
        def freeze(b):
            if b.__class__.__name__ == "BatchNorm":
                b._use_global_stats = True
        net.apply(freeze)
    mesh = parallel.make_mesh({"data": -1})
    tr = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    sh = NamedSharding(mesh, PartitionSpec("data"))
    rs = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(rs.rand(batch, 3, 224, 224),
                                   jnp.bfloat16), sh)
    y = jax.device_put(jnp.asarray(rs.randint(0, 1000, (batch,)),
                                   np.float32), sh)
    return tr, x, y


def _steps_fit(tr, x, y, n1=5, n2=20):
    import jax

    per, _ = fit_time(
        lambda n: jax.device_get(tr.run_steps(n, x, y)), n1, n2)
    return per


def part_b():
    import jax

    n_dev = len(jax.devices())
    for batch in (128, 256):
        tr, x, y = _trainer(batch)
        per = _steps_fit(tr, x, y)
        print(f"batch {batch}/chip: {per * 1e3:.1f} ms/step "
              f"{batch / per:.0f} img/s/chip", flush=True)
        del tr, x, y


def part_c():
    tr, x, y = _trainer(128, use_global_stats=True)
    per = _steps_fit(tr, x, y)
    print(f"batch 128/chip global-stats: {per * 1e3:.1f} ms/step "
          f"{128 / per:.0f} img/s/chip", flush=True)


def part_d():
    """Whole-model fused_resnet50_v1 vs zoo resnet50_v1 train step (the
    prize row): fused >= zoo - 5% means the BN-stat savings survived the
    kernel swap end-to-end and the BENCH headline flips to the fused
    model (VERDICT r5 item 2's 'done' bar).

    ISSUE 11: the flip decision is recorded as a ``kind:"decision"``
    JSONL record through the PR 4 sink (ratio, winner, the conv knob
    states, and both models' per-step/MFU numbers) so BENCH rounds carry
    the provenance of which kernel configuration produced the headline;
    online-vs-offline MFU prints for the fused model the same way it
    does for the zoo model (both loops share the code path below)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from incubator_mxnet_tpu.gluon.model_zoo.vision import fused_resnet

    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.config import config

    # the acceptance row for the ONLINE MFU gauge: force FLOP accounting
    # on so the run_steps meter publishes mxtpu_mfu_percent, then print
    # it next to the offline two-point-fit MFU — the two must agree
    # within 15% (ISSUE 4) since they share the canonical formula
    config.set("MXTPU_TELEMETRY_MFU", "1")
    batch = 128 * len(jax.devices())
    rs = np.random.RandomState(0)
    results = {}
    for label, ctor in (("zoo", vision.resnet50_v1),
                        ("fused", fused_resnet.fused_resnet50_v1)):
        net = ctor(classes=1000)
        net.initialize(init="xavier")
        net.cast("bfloat16")
        net(mx.nd.zeros((2, 3, 224, 224), dtype="bfloat16"))
        mesh = parallel.make_mesh({"data": -1})
        tr = parallel.SPMDTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
        sh = NamedSharding(mesh, PartitionSpec("data"))
        x = jax.device_put(jnp.asarray(rs.rand(batch, 3, 224, 224),
                                       jnp.bfloat16), sh)
        y = jax.device_put(jnp.asarray(rs.randint(0, 1000, (batch,)),
                                       np.float32), sh)
        per = _steps_fit(tr, x, y)
        flops = tr.step_cost_analysis(x, y)
        offline_mfu = telemetry.mfu_percent(flops / per) if flops else None
        gauge = telemetry.get_registry().find("mxtpu_mfu_percent",
                                              site="spmd.run_steps")
        online_mfu = gauge.value if gauge is not None and gauge.value \
            else None
        results[label] = {"per": per, "offline_mfu": offline_mfu,
                          "online_mfu": online_mfu}
        mfu_txt = ""
        if offline_mfu is not None:
            mfu_txt = f"  offline MFU {offline_mfu:.1f}%"
            if online_mfu is not None:
                rel = abs(online_mfu - offline_mfu) / offline_mfu * 100
                mfu_txt += (f"  online gauge {online_mfu:.1f}% "
                            f"(|delta| {rel:.0f}%)")
        print(f"{label:5s} train step: {per * 1e3:.1f} ms/step "
              f"{batch / per:.0f} img/s{mfu_txt}", flush=True)
        del tr, x, y, net
    ratio = results["zoo"]["per"] / results["fused"]["per"]
    verdict = "PRIZE CLAIMED" if ratio >= 0.95 else "still behind"
    record = {
        "kind": "decision", "metric": "resnet_decision_part_d",
        "ratio": round(ratio, 4), "threshold": 0.95,
        "winner": "fused" if ratio >= 0.95 else "zoo",
        "epilogue": str(config.get("MXTPU_CONV_EPILOGUE")),
        "conv_bwd": str(config.get("MXTPU_CONV_BWD")),
        "stride2": str(config.get("MXTPU_CONV_STRIDE2")),
        "batch_per_chip": 128,
    }
    for label, res in results.items():
        record[f"{label}_ms_per_step"] = round(res["per"] * 1e3, 3)
        for k in ("offline_mfu", "online_mfu"):
            if res[k] is not None:
                record[f"{label}_{k}_pct"] = round(res[k], 2)
    try:
        telemetry.jsonl_emit(record)
    except Exception:
        pass  # observability can never break the decision row
    print(f"fused/zoo speed ratio {ratio:.3f} (>=0.95 flips the BENCH "
          f"headline) -> {verdict} "
          f"[epilogue={record['epilogue']} bwd={record['conv_bwd']} "
          f"stride2={record['stride2']}]", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", default="a,b,c,d")
    args = ap.parse_args()
    for part in args.which.split(","):
        {"a": part_a, "b": part_b, "c": part_c, "d": part_d}[part]()


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
