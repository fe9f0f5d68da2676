"""Span-tracing overhead per training step (the bench.py ``trace``
row).

Measures the same SPMD training loop under three sampling rates of the
``mxtpu.telemetry.trace`` spine — off (``MXTPU_TRACE_SAMPLE=0``, the
default), 1%, and 100% — and reports the per-step overhead of each
versus the off run. The tentpole contract is that **off is free**: an
unsampled step's only trace cost is one config read and the shared
``NULL_SPAN``, so the off-vs-off re-measure (the noise floor) and the
1% number should both sit inside run-to-run noise; even 100% pays only
span bookkeeping + one JSONL line per step, with a 5% budget like the
async-checkpoint row.

Both loops run the two-point-fit timing methodology from ``bench.py``
(fence-term cancellation). Standalone::

    JAX_PLATFORMS=cpu python benchmark/trace_bench.py
"""

from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_trainer():
    import jax

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


def _median(vals):
    s = sorted(vals)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def compare_trace_overhead(repeats: int = 5):
    """Returns ``(per_off_s, results)`` where ``results`` maps each
    measured configuration (``"off2"``, ``"1pct"``, ``"100pct"``) to
    ``(per_step_s, overhead_pct_vs_off)``. Sampled spans are emitted
    through the JSONL sink (a real file, so the 100% number pays the
    actual serialization + write cost, not a no-op sink).

    The configurations are measured **interleaved and paired**: each
    sweep round runs one two-point fit per configuration back-to-back
    and the overhead is computed per round against that round's own
    off fit, with the median over rounds reported — host-load drift on
    a shared box moves both sides of a pair together, where four
    sequential ``_fit_windows`` blocks would alias it into fake
    overhead."""
    import jax

    from bench import ITERS, ITERS2, _fit_once
    from incubator_mxnet_tpu import telemetry
    from incubator_mxnet_tpu.config import config

    trainer, args = _build_trainer()

    def window(n):
        import time

        t0 = time.perf_counter()
        for _ in range(n):
            loss = trainer.step(*args)
        float(jax.device_get(loss))
        return time.perf_counter() - t0

    # warmup (compile)
    float(jax.device_get(trainer.step(*args)))
    float(jax.device_get(trainer.step(*args)))

    sink = tempfile.NamedTemporaryFile(
        suffix=".jsonl", prefix="mxtpu-trace-bench-", delete=False)
    sink.close()
    prev_sample = config.get("MXTPU_TRACE_SAMPLE")
    configs = (("off", 0.0), ("off2", 0.0), ("1pct", 0.01),
               ("100pct", 1.0))
    samples = {key: [] for key, _ in configs}
    try:
        telemetry.set_jsonl(sink.name)
        for _ in range(max(1, repeats)):
            for key, rate in configs:
                config.set("MXTPU_TRACE_SAMPLE", rate)
                samples[key].append(_fit_once(window, ITERS, ITERS2))
    finally:
        config.set("MXTPU_TRACE_SAMPLE", prev_sample)
        telemetry.set_jsonl(None)
        os.unlink(sink.name)
    per_off = _median(samples["off"])
    results = {}
    for key, _rate in configs[1:]:
        pcts = [100.0 * (s - o) / o
                for s, o in zip(samples[key], samples["off"]) if o > 0]
        results[key] = (_median(samples[key]),
                        _median(pcts) if pcts else float("nan"))
    return per_off, results


def main():
    import json

    per_off, results = compare_trace_overhead()
    print(json.dumps({
        "metric": "trace_sampling_overhead",
        "off_ms_per_step": round(per_off * 1e3, 4),
        "noise_floor_pct": round(results["off2"][1], 2),
        "overhead_1pct_pct": round(results["1pct"][1], 2),
        "overhead_100pct_pct": round(results["100pct"][1], 2),
        "budget_pct": 5.0,
    }))


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
