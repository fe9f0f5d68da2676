#!/usr/bin/env python
"""Decode serving bench (ISSUE 12): KV-cache continuous batching vs
naive re-prefill batching, identical greedy token streams.

Two servings of the same mixed-length request set through the same
decoder parameters:

* **continuous** — ``serving.DecodeSession``: prefill once per prompt
  into a slot of the device-resident KV cache, then ONE donated decode
  executable advances every live slot per step; sequences join/leave at
  step boundaries.
* **naive** — re-prefill batching, the baseline a server without a KV
  cache runs: requests are served in static waves of ``--slots``
  sequences; EVERY token re-runs the full causal forward over each
  sequence-so-far (padded to a shared length bucket), and a wave holds
  its stragglers until every member finishes.

Both paths must produce bit-identical greedy streams (asserted), so the
speedup is pure serving architecture. Reports tokens/s for both, the
ratio (ISSUE 12 acceptance: >= 2x at mixed lengths), the
prefill-vs-decode wall split and cost-analysis MFU for both phases —
all mirrored as JSONL rows through the PR 4 sink
(``MXTPU_TELEMETRY_JSONL``) for ``tools/telemetry_report.py --compare``.

    python benchmark/decode_bench.py [--requests 24] [--slots 8] \
        [--layers 4] [--units 128] [--max-len 192] [--open-loop ...]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_model(args):
    from incubator_mxnet_tpu.gluon.model_zoo.gpt import GPTDecoder

    net = GPTDecoder(vocab_size=args.vocab, units=args.units,
                     num_layers=args.layers, num_heads=args.heads,
                     max_length=args.max_len, dropout=0.0)
    net.initialize(init="xavier")
    return net


def make_requests(args):
    """Mixed prompt lengths and generation budgets (the ragged traffic
    continuous batching exists for)."""
    rs = np.random.RandomState(args.seed)
    reqs = []
    for _ in range(args.requests):
        n = int(rs.randint(args.min_prompt, args.max_prompt + 1))
        new = int(rs.randint(args.min_new, args.max_new + 1))
        reqs.append((rs.randint(1, args.vocab, (n,)).astype(np.int32), new))
    return reqs


def run_continuous(net, reqs, args):
    from incubator_mxnet_tpu import serving

    sess = serving.DecodeSession(
        net, max_slots=args.slots, max_len=args.max_len,
        prefill_buckets=tuple(int(b) for b in args.buckets.split(",")),
        max_queue=max(64, 2 * len(reqs)), name="decode_bench")
    sess.warmup()                      # compiles outside the clock
    t0 = time.perf_counter()
    handles = [sess.submit(p, max_new_tokens=n) for p, n in reqs]
    outs = [h.result(600) for h in handles]
    wall = time.perf_counter() - t0
    stats = sess.stats()
    # phase MFU from XLA's own cost model over the measured wall split
    dec_flops = sess.decode_cost_analysis()
    pre_flops = 0.0
    try:
        for p, _ in reqs:
            b = sess._prefill.bucket_for(len(p))
            pre_flops += sess.prefill_cost_analysis(b) or 0.0
    except Exception:
        pre_flops = 0.0
    sess.drain(30)
    sess.close()
    return wall, outs, stats, dec_flops, pre_flops


def run_naive(net, reqs, args):
    """Re-prefill waves: full forward per token, stragglers hold the
    wave. Length-bucketed executables so the baseline pays for its
    architecture, not for recompiles."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.serving.executor_cache import \
        pure_method_runner

    run, params = pure_method_runner(net)
    buckets = sorted({int(b) for b in args.buckets.split(",")}
                     | {args.max_len})

    def bucket_for(n):
        for b in buckets:
            if n <= b:
                return b
        return args.max_len

    execs = {}

    def step(pvals, toks, lens):
        logits = run(net.forward, pvals, toks)[0]          # (B, Lb, V)
        last = jnp.take_along_axis(
            logits, (lens.astype(jnp.int32) - 1)[:, None, None], axis=1)
        return jnp.argmax(last[:, 0, :], axis=-1).astype(jnp.int32)

    def next_tokens(seqs):
        bsz = len(seqs)
        lens = np.array([len(s) for s in seqs], np.int32)
        lb = bucket_for(int(lens.max()))
        toks = np.zeros((bsz, lb), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s
        key = (bsz, lb)
        if key not in execs:
            execs[key] = jax.jit(step)
        return np.asarray(execs[key](params, jnp.asarray(toks),
                                     jnp.asarray(lens)))

    # compile every (wave size, bucket) signature outside the clock —
    # the baseline is naive in ARCHITECTURE, not unwarmed. A wave's
    # bucket walks from bucket_for(longest prompt) up to
    # bucket_for(longest final sequence).
    waves = [reqs[i:i + args.slots] for i in range(0, len(reqs),
                                                   args.slots)]
    for wave in waves:
        lo = bucket_for(max(len(p) for p, _ in wave))
        hi = bucket_for(min(args.max_len,
                            max(len(p) + n for p, n in wave)))
        for b in buckets:
            if lo <= b <= hi:
                next_tokens([np.zeros((b,), np.int32) for _ in wave])
    t0 = time.perf_counter()
    outs = []
    for wave in waves:
        seqs = [list(p) for p, _ in wave]
        gen = [[] for _ in wave]
        live = [True] * len(wave)
        while any(live):
            nxt = next_tokens(seqs)
            for i, (p, budget) in enumerate(wave):
                if not live[i]:
                    continue
                t = int(nxt[i])
                gen[i].append(t)
                seqs[i].append(t)
                if (len(gen[i]) >= budget
                        or len(seqs[i]) >= args.max_len):
                    live[i] = False
        outs.extend(gen)
    wall = time.perf_counter() - t0
    return wall, outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--units", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--max-len", type=int, default=192)
    ap.add_argument("--buckets", type=str, default="16,32,64,128")
    ap.add_argument("--min-prompt", type=int, default=8)
    ap.add_argument("--max-prompt", type=int, default=96)
    ap.add_argument("--min-new", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--open-loop", action="store_true",
                    help="also run the shared Poisson load harness "
                         "against the decode session")
    ap.add_argument("--rates", type=str, default="2,4",
                    help="offered request rates (req/s) for --open-loop")
    ap.add_argument("--duration", type=float, default=5.0)
    args = ap.parse_args()
    if args.max_prompt + args.max_new > args.max_len:
        ap.error("size --max-len above --max-prompt + --max-new so "
                 "budgets, not cache capacity, end sequences (the two "
                 "paths count the capacity-edge token differently)")

    import jax

    net = build_model(args)
    reqs = make_requests(args)
    total_prompt = sum(len(p) for p, _ in reqs)

    cw, couts, stats, dec_flops, pre_flops = run_continuous(net, reqs, args)
    nw, nouts = run_naive(net, reqs, args)

    # identical greedy streams or the comparison is meaningless
    mismatch = sum(1 for a, b in zip(couts, nouts) if a != b)
    assert mismatch == 0, f"{mismatch} of {len(reqs)} streams diverged"

    toks = sum(len(o) for o in couts)
    cont_tps, naive_tps = toks / cw, toks / nw
    ratio = cont_tps / naive_tps

    from incubator_mxnet_tpu.telemetry import mfu_percent

    dec_mfu = pre_mfu = None
    if dec_flops and stats["decode_seconds"]:
        dec_mfu = mfu_percent(dec_flops * stats["steps"]
                              / stats["decode_seconds"])
    if pre_flops and stats["prefill_seconds"]:
        pre_mfu = mfu_percent(pre_flops / stats["prefill_seconds"])

    print(f"decode bench — backend={jax.default_backend()} "
          f"model={args.layers}x{args.units}x{args.heads} "
          f"vocab={args.vocab} requests={len(reqs)} slots={args.slots} "
          f"prompt_tokens={total_prompt} new_tokens={toks}")
    print(f"  continuous : {cont_tps:9.1f} tok/s   wall {cw:6.2f}s   "
          f"occupancy {stats['mean_step_occupancy']:.2f}   "
          f"prefill_frac {stats['prefill_frac']:.2f}"
          + (f"   decode MFU {dec_mfu:.1f}%" if dec_mfu else ""))
    print(f"  naive      : {naive_tps:9.1f} tok/s   wall {nw:6.2f}s   "
          f"(re-prefill waves of {args.slots})")
    print(f"  speedup    : {ratio:9.2f}x  (acceptance >= 2x)")

    try:
        from incubator_mxnet_tpu import telemetry

        rows = [
            ("decode_tokens_per_s", cont_tps, "tokens/s",
             {"mfu_pct": round(dec_mfu, 2) if dec_mfu else None,
              "prefill_frac": round(stats["prefill_frac"], 4),
              "occupancy": round(stats["mean_step_occupancy"], 3)}),
            ("decode_naive_tokens_per_s", naive_tps, "tokens/s", {}),
            ("decode_speedup_vs_reprefill", ratio, "x", {}),
        ]
        if pre_mfu is not None:
            rows.append(("decode_prefill_mfu", pre_mfu, "percent", {}))
        for metric, value, unit, extra in rows:
            rec = {"kind": "bench", "metric": metric,
                   "value": round(float(value), 3), "unit": unit}
            rec.update({k: v for k, v in extra.items() if v is not None})
            telemetry.jsonl_emit(rec)
    except Exception:
        pass

    if args.open_loop:
        from benchmark.serving_bench import (emit_row, open_loop,
                                             open_loop_row)
        from incubator_mxnet_tpu import serving

        for idx, rate in enumerate(float(r) for r in args.rates.split(",")):
            sess = serving.DecodeSession(
                net, max_slots=args.slots, max_len=args.max_len,
                prefill_buckets=tuple(int(b)
                                      for b in args.buckets.split(",")),
                name=f"decode_bench-r{idx}")
            sess.warmup()

            def fire(i, _s=sess):
                return _s.submit(reqs[i % len(reqs)][0],
                                 max_new_tokens=reqs[i % len(reqs)][1])

            res = open_loop(fire, rate, args.duration)
            sess.drain(30)
            sess.close()

            row = open_loop_row("decode_bench", rate, res)
            print(f"  open-loop  : offered {row['offered_rps']:6.1f} rq/s "
                  f"achieved {row['achieved_rps']:6.1f}  "
                  f"p50 {row['p50_ms']:8.1f} ms  "
                  f"p99 {row['p99_ms']:8.1f} ms  "
                  f"rejected {row['rejected']}")
            emit_row(row)


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
