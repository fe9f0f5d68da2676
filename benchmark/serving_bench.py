#!/usr/bin/env python
"""Serving latency/throughput bench (docs/SERVING.md; BENCH row
`serving`): concurrent clients through ModelServer's dynamic batcher vs
the same traffic served unbatched, one forward per request.

Reports requests/sec and p50/p99 request latency for both paths plus
the measured batch occupancy — the number dynamic batching exists to
raise. Runs on whatever backend jax selects (CPU fallback included):

    python benchmark/serving_bench.py [--requests 512] [--clients 16] \
        [--in-dim 256] [--hidden 512] [--wait-ms 2.0]

Open-loop sustained-traffic mode (ISSUE 12): a Poisson arrival process
at each offered rate — arrivals do NOT wait for completions, so queueing
delay is measured honestly (closed-loop clients self-throttle and hide
it). One p99-latency-vs-offered-load point per rate, emitted as
``kind:"serving"`` JSONL rows; :func:`open_loop` is the load harness
``decode_bench.py`` shares::

    python benchmark/serving_bench.py --open-loop --rates 50,100,200 \
        --duration 5
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def open_loop(fire, rate_rps: float, duration_s: float, seed: int = 0,
              join_timeout: float = 120.0) -> dict:
    """Open-loop (Poisson) load generator — the harness shared by batch
    serving and decode serving.

    ``fire(i)`` must START request ``i`` and return its resolver: any
    object with Future-style ``add_done_callback(fn)`` +
    ``exception(timeout)`` — a ``concurrent.futures.Future``
    (ModelServer) or a ``serving.DecodeHandle``. Completion latency is
    recorded from the resolver's own done-callback, NOT from a
    per-request waiter thread: at 200 req/s x 5 s a thread per request
    is ~1000 GIL-contending Python threads whose scheduler thrash would
    inflate exactly the p99 this harness exists to measure.
    Backpressure rejections must raise from ``fire`` itself
    (``QueueFullError``); deadline sheds may surface from either side
    (``DeadlineExceededError``). Returns offered/completed counts,
    rejected/shed/error counts and the completed-request latency list.
    """
    from incubator_mxnet_tpu.serving import (DeadlineExceededError,
                                             QueueFullError)

    rs = np.random.RandomState(seed)
    cv = threading.Condition()
    lats, counts = [], {"rejected": 0, "shed": 0, "errors": 0}
    outstanding = [0]

    def record(obj, ts):
        dt = time.perf_counter() - ts
        try:
            exc = obj.exception(0)         # done: never blocks
        except Exception:                  # noqa: BLE001 — cancelled etc.
            exc = RuntimeError("unresolved")
        with cv:
            if exc is None:
                lats.append(dt)
            elif isinstance(exc, DeadlineExceededError):
                counts["shed"] += 1
            else:
                counts["errors"] += 1
            outstanding[0] -= 1
            cv.notify_all()

    offered = 0
    t0 = time.perf_counter()
    next_t = rs.exponential(1.0 / rate_rps)
    while True:
        now = time.perf_counter() - t0
        if now >= duration_s:
            break
        if now < next_t:
            time.sleep(min(next_t - now, 0.005))
            continue
        next_t += rs.exponential(1.0 / rate_rps)
        offered += 1
        t_sub = time.perf_counter()
        try:
            obj = fire(offered - 1)
        except QueueFullError:
            counts["rejected"] += 1
            continue
        except DeadlineExceededError:
            counts["shed"] += 1
            continue
        with cv:
            outstanding[0] += 1
        obj.add_done_callback(lambda o, ts=t_sub: record(o, ts))
    deadline = time.perf_counter() + join_timeout
    with cv:
        while outstanding[0] > 0 and time.perf_counter() < deadline:
            cv.wait(timeout=0.1)
    wall = time.perf_counter() - t0
    return {"offered": offered, "completed": len(lats),
            "offered_rps": offered / duration_s,
            "achieved_rps": len(lats) / wall, "lats": lats,
            "duration_s": duration_s, **counts}


def open_loop_row(model: str, rate: float, res: dict) -> dict:
    """One ``kind:"serving"`` JSONL row per offered-rate point — shared
    by the batch and decode benches so the row schema (and the --compare
    key parity between the two curves) cannot drift. ``rate`` is the
    NOMINAL requested rate and is what compare keys point at: the
    measured Poisson ``offered_rps`` differs run to run, so exact-match
    keys built from it would never line up across rounds."""
    return {"kind": "serving", "mode": "open_loop", "model": model,
            "rate": float(rate),
            "offered_rps": round(res["offered_rps"], 2),
            "achieved_rps": round(res["achieved_rps"], 2),
            "p50_ms": round(pctl(res["lats"], 50) * 1e3, 3),
            "p99_ms": round(pctl(res["lats"], 99) * 1e3, 3),
            "completed": res["completed"], "rejected": res["rejected"],
            "shed": res["shed"], "errors": res["errors"]}


def emit_row(row: dict) -> None:
    """Mirror a row into the telemetry JSONL sink; never let
    observability break the benchmark."""
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit(row)
    except Exception:
        pass


def run_open_loop(net, xs, rates, duration, wait_ms, buckets,
                  deadline_ms):
    """One ModelServer per offered rate (clean queue state per point)."""
    from incubator_mxnet_tpu import serving

    rows = []
    for idx, rate in enumerate(rates):
        # one server (and one watchdog site) per rate point: a reused
        # site name would let point N+1's warmup compiles be judged
        # against point N's step ledger and flag false recompiles
        srv = serving.ModelServer(net, buckets=buckets, max_wait_ms=wait_ms,
                                  max_queue=4 * buckets[-1],
                                  name=f"bench-r{idx}",
                                  deadline_ms=deadline_ms or None)
        try:
            srv.warmup(xs.shape[1:], xs.dtype)

            def fire(i):
                return srv.submit(xs[i % len(xs)])

            res = open_loop(fire, rate, duration)
        finally:
            srv.drain(10)
            srv.close()
        row = open_loop_row("bench", rate, res)
        rows.append(row)
        emit_row(row)
    return rows


def build_net(in_dim: int, hidden: int, out_dim: int, seed: int = 0):
    import numpy as _np

    import incubator_mxnet_tpu as mx

    _np.random.seed(seed)
    net = mx.gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(hidden, activation="relu",
                                  in_units=in_dim))
        net.add(mx.gluon.nn.Dense(out_dim, in_units=hidden))
    net.initialize(mx.initializer.Xavier())
    return net


def run_cold_start(net, feature_shape, buckets, artifact_dir):
    """The ISSUE 14 cold-start row: warm a replica three ways — serial
    compile (the pre-artifact baseline), thread-pool compile (first
    boot of THIS PR), and artifact deserialization (every boot after) —
    and report the artifact speedup vs compile-from-scratch. The
    artifact-warmed cache must perform ZERO XLA compiles."""
    import shutil

    from incubator_mxnet_tpu.serving import BucketedExecutorCache

    own_dir = artifact_dir is None
    if own_dir:
        # a fixed path under the checkout (never a temp name, pid or
        # time), emptied first so the compile-and-persist leg is cold
        artifact_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".serving_artifacts", "serving_bench")
        shutil.rmtree(artifact_dir, ignore_errors=True)
    try:
        def fresh(store):
            return BucketedExecutorCache.from_block(
                net, buckets=buckets, artifact_dir=store, name="bench")

        c_serial = fresh("")                       # store disabled
        t0 = time.perf_counter()
        c_serial.warmup(feature_shape, "float32", threads=1)
        t_serial = time.perf_counter() - t0

        c_par = fresh(artifact_dir)                # compiles AND persists
        t0 = time.perf_counter()
        c_par.warmup(feature_shape, "float32")     # knob/auto threads
        t_par = time.perf_counter() - t0

        c_art = fresh(artifact_dir)                # deserializes
        t0 = time.perf_counter()
        c_art.warmup(feature_shape, "float32")
        t_art = time.perf_counter() - t0

        assert c_art.metrics.compiles == 0, (
            "artifact-warmed cache compiled "
            f"{c_art.metrics.compiles} executables")
        assert c_art.metrics.artifact_hits == len(buckets)
        row = {"kind": "serving", "mode": "cold_start", "model": "bench",
               "buckets": len(buckets),
               "compile_serial_s": round(t_serial, 4),
               "compile_parallel_s": round(t_par, 4),
               "artifact_s": round(t_art, 4),
               "speedup_vs_compile": round(t_par / max(t_art, 1e-9), 2),
               "speedup_vs_serial": round(t_serial / max(t_art, 1e-9), 2),
               "artifact_compiles": c_art.metrics.compiles,
               "artifact_hits": c_art.metrics.artifact_hits}
        emit_row(row)
        for metric, value, unit in (
                ("serving_cold_start_compile_s", t_par, "s"),
                ("serving_cold_start_serial_s", t_serial, "s"),
                ("serving_cold_start_artifact_s", t_art, "s"),
                ("serving_cold_start_speedup",
                 t_par / max(t_art, 1e-9), "x")):
            emit_row({"kind": "bench", "metric": metric,
                      "value": round(float(value), 4), "unit": unit})
        return row
    finally:
        if own_dir:
            shutil.rmtree(artifact_dir, ignore_errors=True)


def run_hot_swap(net, xs, rate, duration, wait_ms, buckets, hidden,
                 out_dim):
    """The ISSUE 14 hot-swap row: identical open-loop Poisson load on
    two servers — one steady, one with a live ``publish_weights`` flip
    mid-run — comparing p99 across the flip against steady state. The
    flip must drop nothing and compile nothing."""
    import numpy as _np

    from incubator_mxnet_tpu import serving, telemetry
    from incubator_mxnet_tpu.parallel.spmd import collect_params

    net_b = build_net(xs.shape[1], hidden, out_dim, seed=1)
    new_weights = {k: p.data().asnumpy()
                   for k, p in collect_params(net_b).items()}

    results = {}
    for phase in ("steady", "swap"):
        srv = serving.ModelServer(net, buckets=buckets,
                                  max_wait_ms=wait_ms,
                                  max_queue=4 * buckets[-1],
                                  name=f"hotswap-{phase}")
        swap_stats = {}
        try:
            srv.warmup(xs.shape[1:], xs.dtype)
            wd = telemetry.get_watchdog()
            c0 = wd.compile_count if wd else 0

            def fire(i, srv=srv):
                return srv.submit(xs[i % len(xs)])

            if phase == "swap":
                def flip():
                    time.sleep(duration / 2.0)
                    swap_stats.update(
                        srv.publish_weights(new_weights, version=2))

                t = threading.Thread(target=flip, daemon=True)
                t.start()
            res = open_loop(fire, rate, duration)
            if phase == "swap":
                t.join(10)
            res["compiles_during"] = \
                (wd.compile_count - c0) if wd else 0
        finally:
            srv.drain(10)
            srv.close()
        results[phase] = (res, swap_stats)

    steady, _ = results["steady"]
    swap, sstats = results["swap"]
    row = {"kind": "serving", "mode": "hot_swap", "model": "bench",
           "rate": float(rate),
           "p99_steady_ms": round(pctl(steady["lats"], 99) * 1e3, 3),
           "p99_swap_ms": round(pctl(swap["lats"], 99) * 1e3, 3),
           "p50_swap_ms": round(pctl(swap["lats"], 50) * 1e3, 3),
           "offered": swap["offered"], "completed": swap["completed"],
           "dropped": swap["errors"], "rejected": swap["rejected"],
           "shed": swap["shed"],
           "recompiles": int(swap.get("compiles_during", 0)),
           "swap_aliased": int(sstats.get("aliased", 0)),
           "swap_updated": int(sstats.get("updated", 0)),
           "swap_seconds": sstats.get("seconds", 0.0)}
    emit_row(row)
    return row


def pctl(vals, p):
    if not vals:
        return 0.0
    return sorted(vals)[min(len(vals) - 1, int(p / 100.0 * len(vals)))]


def run_unbatched(net, xs):
    """One compiled forward per request, sequential — the Predictor-loop
    baseline a client would run without a server."""
    import incubator_mxnet_tpu as mx

    net.hybridize()
    x0 = mx.nd.array(xs[0][None])
    net(x0).asnumpy()                      # compile outside the clock
    lats = []
    t0 = time.perf_counter()
    for x in xs:
        t1 = time.perf_counter()
        net(mx.nd.array(x[None])).asnumpy()
        lats.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    return wall, lats


def run_served(net, xs, clients, wait_ms, buckets):
    from incubator_mxnet_tpu import serving

    srv = serving.ModelServer(net, buckets=buckets, max_wait_ms=wait_ms,
                              max_queue=4 * buckets[-1], name="bench")
    try:
        srv.warmup(xs.shape[1:], xs.dtype)
        lats = []
        lock = threading.Lock()

        def client(rows):
            for x in rows:
                t1 = time.perf_counter()
                while True:
                    try:
                        fut = srv.submit(x)
                        break
                    except serving.QueueFullError as e:   # backpressure
                        time.sleep(e.retry_after)
                fut.result(timeout=60)
                with lock:
                    lats.append(time.perf_counter() - t1)

        shards = [xs[i::clients] for i in range(clients)]
        threads = [threading.Thread(target=client, args=(s,))
                   for s in shards if len(s)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return wall, lats, srv.stats()
    finally:
        srv.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--in-dim", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--out-dim", type=int, default=64)
    ap.add_argument("--wait-ms", type=float, default=2.0)
    ap.add_argument("--buckets", type=str, default="1,2,4,8,16,32")
    ap.add_argument("--open-loop", action="store_true",
                    help="sustained-traffic mode: Poisson arrivals at "
                         "each --rates point, p99 vs offered load")
    ap.add_argument("--rates", type=str, default="50,100,200",
                    help="offered request rates (req/s) for --open-loop")
    ap.add_argument("--duration", type=float, default=5.0,
                    help="seconds per offered-rate point in --open-loop")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request queue deadline in --open-loop "
                         "(0 = no shedding)")
    ap.add_argument("--cold-start", action="store_true",
                    help="ISSUE 14 row: artifact-warmed replica start "
                         "(deserialize) vs compile-from-scratch")
    ap.add_argument("--hot-swap", action="store_true",
                    help="ISSUE 14 row: open-loop p99 across a live "
                         "publish_weights flip vs steady state")
    ap.add_argument("--artifact-dir", type=str, default=None,
                    help="persist --cold-start artifacts here instead "
                         "of <checkout>/.serving_artifacts/serving_bench")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="offered rate (req/s) for --hot-swap")
    args = ap.parse_args()

    import jax

    buckets = tuple(int(b) for b in args.buckets.split(","))
    net = build_net(args.in_dim, args.hidden, args.out_dim)
    xs = np.random.RandomState(0).rand(
        args.requests, args.in_dim).astype(np.float32)

    if args.cold_start:
        row = run_cold_start(net, (args.in_dim,), buckets,
                             args.artifact_dir)
        print(f"serving bench (cold start) — backend="
              f"{jax.default_backend()} net={args.in_dim}x{args.hidden}"
              f"x{args.out_dim} buckets={len(buckets)}")
        print(f"  compile warmup (serial)   : "
              f"{row['compile_serial_s'] * 1e3:9.1f} ms")
        print(f"  compile warmup (parallel) : "
              f"{row['compile_parallel_s'] * 1e3:9.1f} ms")
        print(f"  artifact warmup           : "
              f"{row['artifact_s'] * 1e3:9.1f} ms   "
              f"({row['artifact_compiles']} compiles, "
              f"{row['artifact_hits']} deserialized)")
        print(f"  speedup vs compile        : "
              f"{row['speedup_vs_compile']:9.2f}x   "
              f"(vs serial {row['speedup_vs_serial']:.2f}x)")
        return

    if args.hot_swap:
        row = run_hot_swap(net, xs, args.rate, args.duration,
                           args.wait_ms, buckets, args.hidden,
                           args.out_dim)
        print(f"serving bench (hot swap) — backend="
              f"{jax.default_backend()} rate={row['rate']:.0f} rps "
              f"duration={args.duration}s")
        print(f"  p99 steady : {row['p99_steady_ms']:9.2f} ms")
        print(f"  p99 w/flip : {row['p99_swap_ms']:9.2f} ms   "
              f"(aliased {row['swap_aliased']}, updated "
              f"{row['swap_updated']}, flip {row['swap_seconds']*1e3:.1f} ms)")
        print(f"  dropped {row['dropped']}  rejected {row['rejected']}  "
              f"shed {row['shed']}  recompiles {row['recompiles']}")
        return

    if args.open_loop:
        rates = [float(r) for r in args.rates.split(",")]
        rows = run_open_loop(net, xs, rates, args.duration, args.wait_ms,
                             buckets, args.deadline_ms)
        print(f"serving bench (open loop) — backend="
              f"{jax.default_backend()} net={args.in_dim}x{args.hidden}"
              f"x{args.out_dim} duration={args.duration}s "
              f"deadline={args.deadline_ms}ms")
        print(f"  {'offered rps':>12s} {'achieved rps':>13s} "
              f"{'p50 ms':>9s} {'p99 ms':>9s} {'rejected':>9s} "
              f"{'shed':>6s} {'errors':>7s}")
        for r in rows:
            print(f"  {r['offered_rps']:12.1f} {r['achieved_rps']:13.1f} "
                  f"{r['p50_ms']:9.2f} {r['p99_ms']:9.2f} "
                  f"{r['rejected']:9d} {r['shed']:6d} {r['errors']:7d}")
        return

    uw, ul = run_unbatched(net, xs)
    sw, sl, stats = run_served(net, xs, args.clients, args.wait_ms, buckets)

    n = args.requests
    print(f"serving bench — backend={jax.default_backend()} "
          f"requests={n} clients={args.clients} "
          f"net={args.in_dim}x{args.hidden}x{args.out_dim} "
          f"buckets={buckets} wait={args.wait_ms}ms")
    print(f"  unbatched : {n / uw:9.1f} req/s   "
          f"p50 {pctl(ul, 50) * 1e3:7.2f} ms   "
          f"p99 {pctl(ul, 99) * 1e3:7.2f} ms")
    print(f"  batched   : {n / sw:9.1f} req/s   "
          f"p50 {pctl(sl, 50) * 1e3:7.2f} ms   "
          f"p99 {pctl(sl, 99) * 1e3:7.2f} ms   "
          f"occupancy {stats['batch_occupancy']:.1f}   "
          f"compiles {stats['executor_cache']['compiles']}")

    # mirror the run into the telemetry JSONL sink (MXTPU_TELEMETRY_JSONL)
    # so tools/telemetry_report.py --compare can diff serving rounds;
    # never let observability break the benchmark
    try:
        from incubator_mxnet_tpu import telemetry

        for metric, value, unit in (
                ("serving_unbatched_rps", n / uw, "req/s"),
                ("serving_batched_rps", n / sw, "req/s"),
                ("serving_batched_p50_ms", pctl(sl, 50) * 1e3, "ms"),
                ("serving_batched_p99_ms", pctl(sl, 99) * 1e3, "ms"),
                ("serving_batch_occupancy", stats["batch_occupancy"],
                 "req"),
                ("serving_compiles", stats["executor_cache"]["compiles"],
                 "count")):
            telemetry.jsonl_emit({"kind": "bench", "metric": metric,
                                  "value": round(float(value), 3),
                                  "unit": unit})
    except Exception:
        pass


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
