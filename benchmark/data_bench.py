#!/usr/bin/env python
"""Input-pipeline overlap bench: synchronous feed vs DevicePrefetcher.

The number this subsystem exists to move (docs/DATA.md): with a host
source that takes ``--item-ms`` per batch of ETL, a synchronous loop
pays ``etl + h2d + step`` per step, while a ``DevicePrefetcher``-fed
loop pays ``max(etl, step)`` — the overlap the TF paper's prefetched
input pipeline buys (arXiv:1605.08695 §4.2). Emits one JSON line per
feed mode plus a ``data_pipeline_speedup`` line, all mirrored through
the PR-4 telemetry JSONL sink when ``MXTPU_TELEMETRY_JSONL`` is set
(``tools/telemetry_report.py --compare`` then diffs rounds); the
``data_pipeline`` row of ``bench.py`` drives :func:`compare_feeds`.

    python benchmark/data_bench.py [--steps 30] [--item-ms 5] [--batch 256]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _emit(record):
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit({"kind": "bench", **record})
    except Exception:
        pass
    print(json.dumps(record), flush=True)


def make_trainer(batch: int, dim: int = 256):
    """A small SPMD MLP trainer — enough device work per step that
    overlap is visible, small enough for the CPU tier."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(dim, activation="relu"),
            nn.Dense(dim, activation="relu"), nn.Dense(10))
    net.initialize(init="xavier")
    net(mx.nd.zeros((2, dim)))
    mesh = parallel.make_mesh({"data": -1})
    return parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh)


def slow_source(n_batches: int, batch: int, dim: int, item_ms: float,
                workers: int = 0):
    """A seeded mxtpu.data pipeline whose map stage sleeps ``item_ms``
    per batch — the tunable synthetic-slow host ETL. ``workers`` > 0
    runs the ETL on the bounded pool (the pipeline's parallel-host-ETL
    half); 0 keeps it inline (the naive feed)."""
    from incubator_mxnet_tpu import data

    rng = np.random.RandomState(0)
    xs = rng.rand(n_batches * batch, dim).astype(np.float32)
    ys = rng.randint(0, 10, (n_batches * batch,)).astype(np.float32)

    def etl(item):
        time.sleep(item_ms / 1e3)
        return item

    return data.from_ndarray(xs, ys).batch(batch).map(
        etl, num_workers=workers)


def run_feed(trainer, source, steps: int, prefetch: bool,
             depth: int = 2):
    """Wall-seconds per step over ``steps`` trainer steps fed either
    synchronously or through the trainer's DevicePrefetcher. The loop
    fetches the loss every step — the realistic training-loop shape
    (metrics/logging fence each step): that fence is exactly what
    serializes host ETL with device compute in the synchronous feed,
    and what the background producer hides. Returns
    ``(per_step_s, min_queue_depth_seen_after_warmup)``."""
    import jax

    feed = trainer.device_prefetcher(source, depth=depth) if prefetch \
        else None
    it = iter(feed) if prefetch else iter(source)
    # warmup: compile the step outside the timed window
    x, y = next(it)
    float(jax.device_get(trainer.step(x, y)))
    depths = []
    t0 = time.perf_counter()
    done = 0
    for x, y in it:
        loss = trainer.step(x, y)
        float(jax.device_get(loss))          # per-step metrics fence
        if prefetch:
            depths.append(feed.queue_depth())
        done += 1
        if done >= steps:
            break
    dt = (time.perf_counter() - t0) / max(1, done)
    if prefetch:
        feed.close()
    else:
        close = getattr(source, "close", None)
        if close:
            close()
    return dt, (min(depths[1:]) if len(depths) > 1 else 0)


def compare_feeds(steps: int = 30, item_ms: float = 20.0,
                  batch: int = 256, dim: int = 256, depth: int = 2,
                  workers: int = 4):
    """(sync_per_step_s, prefetch_per_step_s, min_queue_depth).

    The synchronous side is the naive feed (inline ETL, then step); the
    prefetched side is the whole subsystem — the same ETL on ``workers``
    pool threads behind a DevicePrefetcher — so the ratio measures what
    the pipeline buys end to end."""
    trainer = make_trainer(batch, dim)
    n = steps + 4
    sync_per, _ = run_feed(
        trainer, slow_source(n, batch, dim, item_ms, workers=0),
        steps, prefetch=False)
    pre_per, min_depth = run_feed(
        trainer, slow_source(n, batch, dim, item_ms, workers=workers),
        steps, prefetch=True, depth=depth)
    return sync_per, pre_per, min_depth


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--item-ms", type=float, default=20.0)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args(argv)

    sync_per, pre_per, min_depth = compare_feeds(
        args.steps, args.item_ms, args.batch, args.dim, args.depth,
        args.workers)
    _emit({"metric": "data_feed_sync_step_ms",
           "value": round(sync_per * 1e3, 3), "unit": "ms/step"})
    _emit({"metric": "data_feed_prefetch_step_ms",
           "value": round(pre_per * 1e3, 3), "unit": "ms/step",
           "min_queue_depth": min_depth})
    _emit({"metric": "data_pipeline_speedup",
           "value": round(sync_per / pre_per, 3) if pre_per else 0,
           "unit": "x", "item_ms": args.item_ms,
           "steps": args.steps})
    return 0


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    sys.exit(main())
