#!/usr/bin/env python
"""BatchNorm-strategy ablation on the real chip (PROFILE.md follow-up).

Times the ResNet-50 fused train step under different batch_norm
implementations:
  baseline  — jnp.mean + jnp.var (two stat passes, XLA autodiff backward)
  onepass   — E[x], E[x^2] in one fused pass, XLA autodiff backward
  customvjp — onepass forward + hand-written backward (two fused
              reductions over dy instead of autodiff's transpose chain)

Usage: python benchmark/bn_experiment.py [--variants a,b,c] [--iters 10]
"""

from __future__ import annotations

import argparse
import sys
import os
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def make_variants():
    import jax
    import jax.numpy as jnp
    from functools import partial

    def bn_onepass_stats(x, axis):
        red = tuple(i for i in range(x.ndim) if i != axis)
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=red)
        m2 = jnp.mean(jnp.square(xf), axis=red)
        return m, jnp.maximum(m2 - jnp.square(m), 0.0)

    def batch_norm_onepass(x, gamma, beta, moving_mean, moving_var,
                           eps=1e-5, momentum=0.9, fix_gamma=False,
                           use_global_stats=False, output_mean_var=False,
                           axis=1, training=False):
        bshape = [1] * x.ndim
        bshape[axis] = x.shape[axis]
        if fix_gamma:
            gamma = jnp.ones_like(gamma)
        if training and not use_global_stats:
            mean, var = bn_onepass_stats(x, axis)
        else:
            mean, var = moving_mean, moving_var
        scale = (gamma.astype(jnp.float32)
                 * jax.lax.rsqrt(var.astype(jnp.float32) + eps))
        out = ((x.astype(jnp.float32) - mean.reshape(bshape))
               * scale.reshape(bshape)
               + beta.astype(jnp.float32).reshape(bshape)).astype(x.dtype)
        if training and not use_global_stats:
            return out, mean.astype(x.dtype), var.astype(x.dtype)
        return out

    def _bn_fwd(x, gamma, beta, eps, axis):
        red = tuple(i for i in range(x.ndim) if i != axis)
        bshape = [1] * x.ndim
        bshape[axis] = x.shape[axis]
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=red)
        m2 = jnp.mean(jnp.square(xf), axis=red)
        var = jnp.maximum(m2 - jnp.square(m), 0.0)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (xf - m.reshape(bshape)) * rstd.reshape(bshape)
        out = (xhat * gamma.astype(jnp.float32).reshape(bshape)
               + beta.astype(jnp.float32).reshape(bshape)).astype(x.dtype)
        return (out, m, var), (xhat, rstd, gamma)

    def _bn_cv_fwd(x, gamma, beta, eps, axis):
        (out, m, var), res = _bn_fwd(x, gamma, beta, eps, axis)
        return (out, m, var), res

    def _bn_cv_bwd(eps, axis, res, cts):
        dy, _, _ = cts
        xhat, rstd, gamma = res
        xdtype = dy.dtype
        red = tuple(i for i in range(dy.ndim) if i != axis)
        bshape = [1] * dy.ndim
        bshape[axis] = dy.shape[axis]
        dyf = dy.astype(jnp.float32)
        n = 1
        for i in red:
            n *= dy.shape[i]
        sum_dy = jnp.sum(dyf, axis=red)
        sum_dy_xhat = jnp.sum(dyf * xhat, axis=red)
        dx = (gamma.astype(jnp.float32) * rstd).reshape(bshape) * (
            dyf - (sum_dy / n).reshape(bshape)
            - xhat * (sum_dy_xhat / n).reshape(bshape))
        return (dx.astype(xdtype), sum_dy_xhat.astype(gamma.dtype),
                sum_dy.astype(gamma.dtype))

    @partial(jax.custom_vjp, nondiff_argnums=(3, 4))
    def bn_train_customvjp(x, gamma, beta, eps, axis):
        return _bn_fwd(x, gamma, beta, eps, axis)[0]

    bn_train_customvjp.defvjp(_bn_cv_fwd, _bn_cv_bwd)

    def batch_norm_customvjp(x, gamma, beta, moving_mean, moving_var,
                             eps=1e-5, momentum=0.9, fix_gamma=False,
                             use_global_stats=False, output_mean_var=False,
                             axis=1, training=False):
        if fix_gamma:
            gamma = jnp.ones_like(gamma)
        if training and not use_global_stats:
            out, m, var = bn_train_customvjp(x, gamma, beta, eps, axis)
            return out, m.astype(x.dtype), var.astype(x.dtype)
        bshape = [1] * x.ndim
        bshape[axis] = x.shape[axis]
        out = ((x - moving_mean.reshape(bshape)) * jax.lax.rsqrt(
            moving_var.reshape(bshape) + eps) * gamma.reshape(bshape)
            + beta.reshape(bshape))
        return out

    return {"onepass": batch_norm_onepass,
            "customvjp": batch_norm_customvjp}


def time_resnet_step(iters, warmup=3):
    import jax
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    from jax.sharding import NamedSharding, PartitionSpec

    n_dev = len(jax.devices())
    batch = 128 * n_dev
    net = vision.resnet50_v1(classes=1000)
    net.initialize(init="xavier")
    net.cast("bfloat16")
    net(mx.nd.zeros((2, 3, 224, 224), dtype="bfloat16"))
    mesh = parallel.make_mesh({"data": -1})
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    x = jax.device_put(
        jnp.asarray(np.random.rand(batch, 3, 224, 224), jnp.bfloat16),
        NamedSharding(mesh, PartitionSpec("data")))
    y = jax.device_put(
        jnp.asarray(np.random.randint(0, 1000, (batch,)), jnp.float32),
        NamedSharding(mesh, PartitionSpec("data")))
    loss = trainer.step(x, y)
    float(jax.device_get(loss))
    for _ in range(warmup - 1):
        loss = trainer.step(x, y)
    float(jax.device_get(loss))
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = trainer.step(x, y)
    lv = float(jax.device_get(loss))
    dt = time.perf_counter() - t0
    return batch * iters / dt / n_dev, lv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--variants", default="baseline,onepass,customvjp")
    args = ap.parse_args()

    from incubator_mxnet_tpu.ops import nn as ops_nn
    from incubator_mxnet_tpu.ops import registry

    variants = make_variants()
    baseline_fn = registry.get("BatchNorm").fn
    for name in args.variants.split(","):
        if name == "baseline":
            fn = baseline_fn
        else:
            fn = variants[name]
        registry.get("BatchNorm").fn = fn
        try:
            ips, loss = time_resnet_step(args.iters)
            print(f"{name:10s} {ips:9.1f} img/s/chip   loss={loss:.4f}",
                  flush=True)
        except Exception as e:  # keep sweeping
            print(f"{name:10s} FAILED: {e}", flush=True)
    registry.get("BatchNorm").fn = baseline_fn


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
