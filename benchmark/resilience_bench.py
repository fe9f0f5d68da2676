"""Async-checkpoint overhead per training step (the bench.py
``resilience`` row).

Measures the same SPMD training loop twice — bare, and with a
``resilience.CheckpointManager`` saving asynchronously every
``ckpt_every`` steps — and reports the per-step overhead percentage.
The acceptance budget (ISSUE 6) is **< 5%**: the async path only pays
the on-device snapshot copy + state capture on the step thread; the
host transfer, file IO, fsync and atomic rename all happen on the
writer thread, overlapped with subsequent steps.

The model is sized so a step is real work (a few ms on CPU) rather than
dispatch noise, and both loops run the K-repeat two-point-fit timing
methodology from ``bench.py`` (fence-term cancellation + median-of-K).

Standalone::

    JAX_PLATFORMS=cpu python benchmark/resilience_bench.py
"""

from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_trainer():
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn

    n_dev = len(jax.devices())
    batch = 1024 * n_dev
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(512, in_units=256, activation="relu"),
            nn.Dense(512, in_units=512, activation="relu"),
            nn.Dense(64, in_units=512))
    net.initialize(init="xavier")
    mesh = parallel.make_mesh({"data": -1})
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    from jax.sharding import NamedSharding, PartitionSpec
    import jax.numpy as jnp

    sharding = NamedSharding(mesh, PartitionSpec("data"))
    x = jax.device_put(jnp.asarray(
        np.random.rand(batch, 256).astype(np.float32)), sharding)
    y = jax.device_put(jnp.asarray(
        np.random.randint(0, 64, (batch,)).astype(np.float32)), sharding)
    return trainer, (x, y)


def compare_checkpoint_overhead(ckpt_every: int = 10, root: str = None):
    """Returns ``(per_bare_s, per_ckpt_s, overhead_pct)``: per-step
    seconds without checkpointing, with async checkpointing every
    ``ckpt_every`` steps, and the overhead percentage."""
    import jax

    from bench import _fit_windows
    from incubator_mxnet_tpu import resilience

    trainer, args = _build_trainer()

    def window_bare(n):
        import time

        t0 = time.perf_counter()
        for _ in range(n):
            loss = trainer.step(*args)
        float(jax.device_get(loss))
        return time.perf_counter() - t0

    # warmup (compile)
    float(jax.device_get(trainer.step(*args)))
    float(jax.device_get(trainer.step(*args)))
    per_bare = _fit_windows(window_bare)

    own_tmp = root is None
    if own_tmp:
        root = tempfile.mkdtemp(prefix="mxtpu-resilience-bench-")
    mgr = resilience.CheckpointManager(root, keep_last_k=2)
    counter = {"n": 0}

    def window_ckpt(n):
        import time

        t0 = time.perf_counter()
        for _ in range(n):
            loss = trainer.step(*args)
            counter["n"] += 1
            if counter["n"] % ckpt_every == 0:
                mgr.save(counter["n"], trainer)     # async
        float(jax.device_get(loss))
        return time.perf_counter() - t0

    per_ckpt = _fit_windows(window_ckpt)
    mgr.wait()
    if own_tmp:
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    overhead_pct = 100.0 * (per_ckpt - per_bare) / per_bare \
        if per_bare > 0 else float("nan")
    return per_bare, per_ckpt, overhead_pct


def main():
    import json

    bare, ckpt, pct = compare_checkpoint_overhead()
    print(json.dumps({
        "metric": "resilience_async_ckpt_overhead",
        "bare_ms_per_step": round(bare * 1e3, 4),
        "ckpt_ms_per_step": round(ckpt * 1e3, 4),
        "overhead_pct": round(pct, 2),
        "budget_pct": 5.0,
    }))


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
