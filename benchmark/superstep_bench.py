#!/usr/bin/env python
"""Superstep sweep: K-steps-per-dispatch throughput vs K (ISSUE 9).

The dispatch-bound configs (BENCH_r05: MLP 7.1% / LSTM 7.2% MFU) pay a
fixed host round-trip per step; ``run_superstep`` amortizes it over K
distinct batches per dispatch. This sweep measures per-step wall time
for K in {1, 8, 32} on MLP- and LSTM-shaped models driven through the
whole engine — window stacking, device staging and the compiled K-step
loop — so the win AND its knee are visible per round. One JSON line per
(model, K) point plus a ``superstep_speedup`` line per model, all
mirrored through the PR-4 telemetry JSONL sink; the ``superstep`` row
of ``bench.py`` drives :func:`sweep`.

    python benchmark/superstep_bench.py [--windows 6] [--ks 1,8,32]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

KS = (1, 8, 32)


def _emit(record):
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit({"kind": "bench", **record})
    except Exception:
        pass
    print(json.dumps(record), flush=True)


def make_mlp(batch: int = 1024, dim: int = 256):
    """The MLP-shaped dispatch-bound config, sized for the CPU tier."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(dim, activation="relu"),
            nn.Dense(dim, activation="relu"), nn.Dense(10))
    net.initialize(init="xavier")
    net(mx.nd.zeros((2, dim)))
    mesh = parallel.make_mesh({"data": -1})
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1}, mesh=mesh)

    def make_batch(i):
        rs = np.random.RandomState(1000 + i)
        return (rs.rand(batch, dim).astype(np.float32),
                rs.randint(0, 10, (batch,)).astype(np.float32))

    return trainer, make_batch, batch


def make_lstm(batch: int = 16, seq: int = 16, hidden: int = 64,
              vocab: int = 500):
    """The LSTM-shaped (scan-heavy, tiny per-step FLOPs) config."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn, rnn

    net = nn.HybridSequential()
    net.add(nn.Embedding(vocab, hidden),
            rnn.LSTM(hidden, num_layers=1, layout="NTC",
                     input_size=hidden),
            nn.Dense(vocab, flatten=False, in_units=hidden))
    net.initialize(init="xavier")
    net(mx.nd.zeros((2, seq), dtype="int32"))
    mesh = parallel.make_mesh({"data": -1})
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 1.0, "clip_gradient": 0.25}, mesh=mesh)

    def make_batch(i):
        rs = np.random.RandomState(2000 + i)
        d = rs.randint(0, vocab, (batch, seq + 1))
        return (d[:, :-1].astype(np.int32), d[:, 1:].astype(np.float32))

    return trainer, make_batch, batch


MODELS = {"mlp": make_mlp, "lstm": make_lstm}


def time_k(trainer, make_batch, k: int, windows: int = 6):
    """Per-step wall seconds at window size ``k``: warm one window, then
    time ``windows`` supersteps over DISTINCT pre-stacked batches with
    one fence at the end (the loss array IS the per-step stream, so no
    per-step fence is needed — exactly the dispatch pattern the engine
    ships)."""
    import jax

    from incubator_mxnet_tpu.parallel.superstep import stack_window

    wins = [stack_window([make_batch(w * k + i) for i in range(k)])
            for w in range(windows + 1)]
    # warmup compiles the K-loop
    jax.device_get(trainer.run_superstep(wins[0][0], wins[0][1]))
    t0 = time.perf_counter()
    losses = None
    for w in range(1, windows + 1):
        losses = trainer.run_superstep(wins[w][0], wins[w][1])
    jax.device_get(losses)
    return (time.perf_counter() - t0) / (windows * k)


def sweep(ks=KS, models=("mlp", "lstm"), windows: int = 6):
    """{model: {k: per_step_s}} plus per-model K-max-vs-K=1 speedups."""
    out = {}
    for name in models:
        trainer, make_batch, batch = MODELS[name]()
        per = {}
        for k in ks:
            per[k] = time_k(trainer, make_batch, int(k), windows=windows)
            _emit({"metric": "superstep_sweep", "model": name,
                   "k": int(k), "value": round(per[k] * 1e3, 4),
                   "unit": "ms/step", "batch": batch,
                   "dispatches_per_step": round(1.0 / int(k), 4)})
        out[name] = per
        kmax = max(ks)
        _emit({"metric": "superstep_speedup", "model": name,
               "value": round(per[min(ks)] / per[kmax], 3)
               if per[kmax] > 0 else 0,
               "unit": f"x_k{kmax}_vs_k{min(ks)}"})
    return out


def geomean_speedup(per_model, ks=KS) -> float:
    """Geometric mean over models of per_step(K=min)/per_step(K=max)."""
    lo, hi = min(ks), max(ks)
    ratios = [per[lo] / per[hi] for per in per_model.values()
              if per.get(hi)]
    if not ratios:
        return 0.0
    return float(np.exp(np.mean(np.log(ratios))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--ks", default="1,8,32")
    ap.add_argument("--models", default="mlp,lstm")
    args = ap.parse_args(argv)
    ks = tuple(int(v) for v in args.ks.split(","))
    per_model = sweep(ks=ks, models=tuple(args.models.split(",")),
                      windows=args.windows)
    _emit({"metric": "superstep_speedup_geomean",
           "value": round(geomean_speedup(per_model, ks), 3),
           "unit": "x", "ks": list(ks)})
    return 0


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    sys.exit(main())
