#!/usr/bin/env python
"""Summarize / diff mxtpu.telemetry JSONL runs (docs/OBSERVABILITY.md).

Summary mode — per site: step count, p50/p95 step wall time, MFU trend
(first→last EMA window), recompiles flagged, device-memory high-water;
plus any bench rows the file carries::

    python tools/telemetry_report.py run.jsonl

Compare mode — per-metric deltas between two runs (the BENCH_r* diff
tool: point it at the JSONL sinks of two bench.py / serving_bench.py
invocations)::

    python tools/telemetry_report.py --compare a.jsonl b.jsonl

Only stdlib + the sibling package's reader are used, so this runs on a
box without jax installed (the JSONL file is plain JSON objects).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _read(path: str) -> List[Dict]:
    try:
        from incubator_mxnet_tpu.telemetry import read_jsonl

        return read_jsonl(path)
    except ImportError:          # jax-less box: inline the tolerant reader
        out = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
        return out


def _select_run(records: List[Dict], merge: bool = False):
    """The sink writes a ``run_start`` boundary record each time it
    opens, and the file is append-mode — a reused path holds several
    runs. Default to the newest run that has records (mixing runs
    silently doubles step counts and skews percentiles); ``--all``
    merges. Returns ``(records, n_skipped_runs)``."""
    if merge:
        return [r for r in records if r.get("kind") != "run_start"], 0
    runs: List[List[Dict]] = [[]]
    for r in records:
        if r.get("kind") == "run_start":
            runs.append([])
        else:
            runs[-1].append(r)
    runs = [seg for seg in runs if seg]
    if not runs:
        return [], 0
    return runs[-1], len(runs) - 1


def _pctl(vals: List[float], p: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    return s[min(len(s) - 1, max(0, int(round(p / 100.0 * len(s))) - 1))]


def _group_steps(records: List[Dict]) -> Dict[str, List[Dict]]:
    sites: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "step":
            sites.setdefault(r.get("site", "?"), []).append(r)
    return sites


def _step_walls(steps: List[Dict]) -> List[float]:
    """Per-STEP wall samples for percentile math. A superstep record
    (``fused_steps: k``) already carries the per-step amortized
    ``wall_ms`` but stands for k steps — weight it k times so the
    percentiles of a K=32 run compare apples-to-apples against a
    pre-superstep per-dispatch run. Compile-dominated steps stay
    excluded (the meter keeps them out of EMA/MFU for the same
    reason)."""
    walls: List[float] = []
    for r in steps:
        if "wall_ms" in r and not r.get("compiled"):
            walls.extend([r["wall_ms"]]
                         * max(1, int(r.get("fused_steps", 1))))
    return walls


def _steps_of(records: List[Dict]) -> int:
    return sum(max(1, int(r.get("fused_steps", 1))) for r in records)


def _mfu_trend(steps: List[Dict]) -> Optional[str]:
    mfus = [r["mfu_pct"] for r in steps if "mfu_pct" in r]
    if not mfus:
        return None
    k = max(1, len(mfus) // 5)
    first = sum(mfus[:k]) / k
    last = sum(mfus[-k:]) / k
    arrow = "->"
    return f"{first:.1f}% {arrow} {last:.1f}%"


def summarize(path: str, merge: bool = False) -> str:
    records, skipped = _select_run(_read(path), merge=merge)
    head = f"telemetry report — {path} ({len(records)} records"
    if skipped:
        head += f"; newest of {skipped + 1} runs, --all merges"
    lines = [head + ")"]
    sites = _group_steps(records)
    recompiles: Dict[str, int] = {}
    for r in records:
        if r.get("kind") == "recompile":
            recompiles[r.get("site", "?")] = \
                recompiles.get(r.get("site", "?"), 0) + 1
    if sites:
        lines.append("")
        lines.append(f"{'site':24s} {'steps':>7s} {'p50 ms':>9s} "
                     f"{'p95 ms':>9s} {'disp/step':>10s} "
                     f"{'MFU trend':>16s} {'recompiles':>11s}")
        for site in sorted(sites):
            steps = sites[site]
            # per-step, superstep-normalized, compile-excluded samples
            walls = _step_walls(steps)
            n_steps = _steps_of(steps)
            disp = sum(int(r.get("dispatches", 1)) for r in steps) \
                / max(1, n_steps)
            trend = _mfu_trend(steps) or "-"
            lines.append(
                f"{site:24s} {n_steps:7d} "
                f"{_pctl(walls, 50):9.3f} {_pctl(walls, 95):9.3f} "
                f"{disp:10.3f} "
                f"{trend:>16s} {recompiles.get(site, 0):11d}")
    for site in sorted(sites):
        # decode steps say whether they were dispatched before the fetch
        # of the step before them (docs/SERVING.md "A second step in
        # flight") and what they computed for a stream already ended
        ahead = [r["ahead"] for r in sites[site] if "ahead" in r]
        if ahead:
            lines.append(
                f"{site}: {sum(ahead)} of {len(ahead)} steps launched "
                f"ahead, "
                f"{sum(r.get('dropped', 0) for r in sites[site])} "
                "slot-tokens dropped")
    for site, n in sorted(recompiles.items()):
        if site not in sites:
            lines.append(f"recompiles at un-stepped site {site}: {n}")
    peaks = [r["mem_peak_bytes"] for r in records
             if r.get("mem_peak_bytes") is not None]
    live = [r["mem_bytes_in_use"] for r in records
            if r.get("mem_bytes_in_use") is not None]
    if peaks or live:
        lines.append("")
        if peaks:
            lines.append(f"device memory high-water: "
                         f"{max(peaks) / 2**20:.1f} MiB (peak)")
        if live:
            lines.append(f"device memory max live:   "
                         f"{max(live) / 2**20:.1f} MiB")
    data = {}
    for r in records:
        if r.get("kind") == "data":
            data.setdefault(r.get("site", "?"), []).append(r)
    if data:
        lines.append("")
        lines.append(f"{'input pipeline':24s} {'batches':>8s} "
                     f"{'input-bound%':>13s} {'epochs':>7s}")
        for site in sorted(data):
            recs = data[site]
            bounds = [r["input_bound_pct"] for r in recs
                      if "input_bound_pct" in r]
            # superstep feeds deliver stacked windows: 'batches' counts
            # items delivered; 'batches_exact' (tail windows counted by
            # their actual length) or the nominal 'superstep' factor
            # converts to the per-batch granularity pre-superstep runs
            # report
            n_batches = max(
                int(r.get("batches_exact",
                          int(r.get("batches", 0))
                          * int(r.get("superstep", 1))))
                for r in recs)
            lines.append(
                f"{site:24s} {n_batches:8d} "
                f"{(f'{bounds[-1]:.1f}' if bounds else '-'):>13s} "
                f"{sum(1 for r in recs if r.get('epoch_end')):7d}")
    decs: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "decode":
            decs.setdefault(r.get("model", "?"), []).append(r)
    if decs:
        # continuous-batching decode (ISSUE 12): one record per finished
        # request; the per-step wall/MFU numbers ride the decode.<model>
        # step site above
        lines.append("")
        lines.append(f"{'decode (per request)':24s} {'requests':>9s} "
                     f"{'tokens':>8s} {'tok/req':>8s} {'occupancy':>10s} "
                     f"{'wait p95 ms':>12s} {'wall p95 ms':>12s}")
        for model in sorted(decs):
            recs = decs[model]
            toks = sum(int(r.get("new_tokens", 0)) for r in recs)
            waits = [r["queue_wait_ms"] for r in recs
                     if "queue_wait_ms" in r]
            walls = [r["wall_ms"] for r in recs if "wall_ms" in r]
            occ = [r["slots_active"] for r in recs
                   if "slots_active" in r]
            lines.append(
                f"{model:24s} {len(recs):9d} {toks:8d} "
                f"{toks / max(1, len(recs)):8.1f} "
                f"{(sum(occ) / len(occ)) if occ else 0.0:10.2f} "
                f"{_pctl(waits, 95):12.2f} {_pctl(walls, 95):12.2f}")
    regs: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "registry":
            regs.setdefault(r.get("model", "?"), []).append(r)
    if regs:
        # serving registry / persistent-artifact lifecycle (ISSUE 14):
        # warmup rows carry the compile-vs-deserialize cold-start
        # split; admit/evict/swap rows the residency churn
        lines.append("")
        lines.append(f"{'registry':24s} {'warmups':>8s} {'last s':>8s} "
                     f"{'compiles':>9s} {'deser':>6s} {'admits':>7s} "
                     f"{'evicts':>7s} {'swaps':>6s}")
        for model in sorted(regs):
            recs = regs[model]
            warm = [r for r in recs if r.get("event") == "warmup"]
            lines.append(
                f"{model:24s} {len(warm):8d} "
                f"{(warm[-1].get('seconds', 0.0) if warm else 0.0):8.3f} "
                f"{sum(int(r.get('compiles', 0)) for r in warm):9d} "
                f"{sum(int(r.get('deserialized', 0)) for r in warm):6d} "
                f"{sum(1 for r in recs if r.get('event') == 'admit'):7d} "
                f"{sum(1 for r in recs if r.get('event') == 'evict'):7d} "
                f"{sum(1 for r in recs if r.get('event') == 'swap'):6d}")
    res = [r for r in records if r.get("kind") == "resilience"]
    if res:
        counts: Dict[str, int] = {}
        for r in res:
            ev = r.get("event", "?")
            counts[ev] = counts.get(ev, 0) + 1
        ck_ms = sorted(r["ms"] for r in res
                       if r.get("event") == "checkpoint" and "ms" in r)
        lines.append("")
        lines.append("resilience: " + ", ".join(
            f"{ev}={n}" for ev, n in sorted(counts.items())))
        if ck_ms:
            last_step = max(r.get("step", 0) for r in res
                            if r.get("event") == "checkpoint")
            lines.append(
                f"  checkpoint latency p50 {_pctl(ck_ms, 50):.1f} ms / "
                f"p95 {_pctl(ck_ms, 95):.1f} ms "
                f"({len(ck_ms)} committed, last good step {last_step})")
        bad = counts.get("checkpoint_failed", 0)
        if bad:
            lines.append(f"  !! {bad} checkpoint write(s) failed before "
                         "commit (torn writes are never visible; see "
                         "docs/RESILIENCE.md)")
    migs: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "migrate":
            migs.setdefault(r.get("site", "?"), []).append(r)
    if migs:
        # in-ICI live resharding (ISSUE 15): one record per device->
        # device layout flip; wire bytes are the planned schedule's
        # exact accounting, host bytes are zero by construction
        lines.append("")
        lines.append(f"{'migrate (live reshard)':24s} {'flips':>6s} "
                     f"{'tensors':>8s} {'moved':>6s} {'wire MiB':>9s} "
                     f"{'quant':>6s} {'mode':>11s} {'last ms':>8s}")
        for site in sorted(migs):
            recs = migs[site]
            last = recs[-1]
            lines.append(
                f"{site:24s} {len(recs):6d} "
                f"{int(last.get('tensors', 0)):8d} "
                f"{int(last.get('moved', 0)):6d} "
                f"{sum(r.get('wire_bytes', 0) for r in recs) / 2**20:9.2f} "
                f"{str(last.get('quant', 'none')):>6s} "
                f"{str(last.get('mode', '?')):>11s} "
                f"{last.get('ms', 0.0):8.1f}")
    coll: Dict[str, Dict] = {}
    for r in records:
        if r.get("kind") == "collective":
            coll[r.get("site", "?")] = r      # last record per site wins
    if coll:
        lines.append("")
        lines.append(f"{'collectives':24s} {'stage':>6s} {'quant':>6s} "
                     f"{'wire/step':>12s} {'quant frac':>11s} "
                     f"{'param B/chip':>13s} {'opt B/chip':>11s}")
        for site in sorted(coll):
            r = coll[site]
            lines.append(
                f"{site:24s} {int(r.get('stage', 0)):6d} "
                f"{str(r.get('quant', 'none')):>6s} "
                f"{r.get('wire_bytes_per_step', 0) / 2**20:10.2f}Mi "
                f"{r.get('quant_fraction', 1.0):11.3f} "
                f"{int(r.get('param_bytes_per_chip', 0)):13d} "
                f"{int(r.get('opt_bytes_per_chip', 0)):11d}")
    ovl: Dict[str, Dict] = {}
    for r in records:
        if r.get("kind") == "zero_overlap":
            ovl[r.get("site", "?")] = r       # last record per site wins
    if ovl:
        lines.append("")
        lines.append(f"{'zero-3 overlap':24s} {'mode':>6s} {'eng':>4s} "
                     f"{'layers':>6s} {'hidden':>7s} {'AG/step':>12s} "
                     f"reason")
        for site in sorted(ovl):
            r = ovl[site]
            lines.append(
                f"{site:24s} {str(r.get('mode', '?')):>6s} "
                f"{'y' if r.get('engaged') else 'n':>4s} "
                f"{int(r.get('layers', 0)):6d} "
                f"{r.get('overlap_fraction', 0.0):7.3f} "
                f"{r.get('run_ag_bytes_per_step', 0) / 2**20:10.2f}Mi "
                f"{r.get('reason') or '-'}")
    bench = [r for r in records if r.get("kind") == "bench"]
    if bench:
        lines.append("")
        lines.append(f"{'bench metric':44s} {'value':>12s} {'unit':>18s} "
                     f"{'disp/step':>10s}")
        for r in bench:
            dps = r.get("dispatches_per_step")
            lines.append(f"{str(r.get('metric', '?')):44s} "
                         f"{r.get('value', 0):12.2f} "
                         f"{str(r.get('unit', '')):>18s} "
                         f"{(f'{dps:.3f}' if isinstance(dps, (int, float)) else '-'):>10s}")
    for r in records:
        if r.get("kind") == "decision":
            lines.append("")
            lines.append(
                f"decision {r.get('metric', '?')}: winner="
                f"{r.get('winner', '?')} ratio={r.get('ratio', 0):.3f} "
                f"(threshold {r.get('threshold', 0):.2f}) "
                f"epilogue={r.get('epilogue', '?')} "
                f"bwd={r.get('conv_bwd', '?')} "
                f"stride2={r.get('stride2', '?')}")
    return "\n".join(lines)


def _comparable_metrics(records: List[Dict]) -> Dict[str, float]:
    """Flatten a run into {metric_key: value} for diffing: bench rows by
    metric name, per-site step p50/p95 and final MFU, recompile counts."""
    out: Dict[str, float] = {}
    for r in records:
        if r.get("kind") == "bench" and "metric" in r \
                and isinstance(r.get("value"), (int, float)):
            out[f"bench/{r['metric']}"] = float(r["value"])
            if isinstance(r.get("mfu_pct"), (int, float)):
                out[f"bench/{r['metric']}/mfu_pct"] = float(r["mfu_pct"])
            # per-workload dispatch regression key (ISSUE 11): compare()
            # flags any workload whose disp/step GREW vs the baseline
            # run — the superstep wiring silently falling back to eager
            # looks exactly like 1/K -> 1.0 here
            if isinstance(r.get("dispatches_per_step"), (int, float)):
                out[f"bench/{r['metric']}/dispatches_per_step"] = \
                    float(r["dispatches_per_step"])
        if r.get("kind") == "decision" and "metric" in r \
                and isinstance(r.get("ratio"), (int, float)):
            out[f"decision/{r['metric']}/ratio"] = float(r["ratio"])
    for site, steps in _group_steps(records).items():
        # superstep-normalized per-step samples (see _step_walls): a
        # --compare of a K>1 run against a pre-superstep run diffs
        # per-step percentiles, not per-dispatch ones
        walls = _step_walls(steps)
        if walls:
            out[f"step/{site}/p50_ms"] = _pctl(walls, 50)
            out[f"step/{site}/p95_ms"] = _pctl(walls, 95)
        n_steps = _steps_of(steps)
        if n_steps:
            out[f"step/{site}/dispatches_per_step"] = \
                sum(int(r.get("dispatches", 1)) for r in steps) / n_steps
        mfus = [r["mfu_pct"] for r in steps if "mfu_pct" in r]
        if mfus:
            out[f"step/{site}/mfu_pct"] = mfus[-1]
    # serving open-loop rows (serving_bench --open-loop / decode_bench):
    # the p99-vs-offered-load curve, diffable per rate point. Keys use
    # the NOMINAL requested rate ("rate"), not the measured Poisson
    # offered_rps — the measured value differs between runs, so keys
    # built from it would never match across rounds
    for r in records:
        if r.get("kind") == "serving" and r.get("mode") == "open_loop":
            rate = r.get("rate", r.get("offered_rps", "?"))
            if isinstance(rate, float) and rate.is_integer():
                rate = int(rate)
            base = f"serving/{r.get('model', '?')}/rate{rate}"
            for key in ("achieved_rps", "p50_ms", "p99_ms", "shed"):
                if isinstance(r.get(key), (int, float)):
                    out[f"{base}/{key}"] = float(r[key])
    # per-request decode records aggregate into per-model compare keys
    dec_by_model: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "decode":
            dec_by_model.setdefault(r.get("model", "?"), []).append(r)
    for model, recs in dec_by_model.items():
        toks = sum(int(r.get("new_tokens", 0)) for r in recs)
        out[f"decode/{model}/requests"] = float(len(recs))
        out[f"decode/{model}/tokens"] = float(toks)
        waits = [r["queue_wait_ms"] for r in recs if "queue_wait_ms" in r]
        if waits:
            out[f"decode/{model}/queue_wait_p95_ms"] = _pctl(waits, 95)
        occ = [r["slots_active"] for r in recs if "slots_active" in r]
        if occ:
            out[f"decode/{model}/occupancy"] = sum(occ) / len(occ)
    # registry lifecycle records aggregate into per-model compare keys:
    # warmup seconds + the compile-vs-deserialize split (the cold-start
    # diff between a compile round and an artifact-warmed round), plus
    # residency churn counts
    reg_by_model: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "registry":
            reg_by_model.setdefault(r.get("model", "?"), []).append(r)
    for model, recs in reg_by_model.items():
        base = f"registry/{model}"
        warm = [r for r in recs if r.get("event") == "warmup"]
        if warm:
            out[f"{base}/warmup_s"] = float(warm[-1].get("seconds", 0.0))
            out[f"{base}/warmup_compiles"] = float(
                sum(int(r.get("compiles", 0)) for r in warm))
            out[f"{base}/warmup_deserialized"] = float(
                sum(int(r.get("deserialized", 0)) for r in warm))
        for ev, key in (("admit", "admissions"), ("evict", "evictions"),
                        ("swap", "swaps")):
            n = sum(1 for r in recs if r.get("event") == ev)
            if n:
                out[f"{base}/{key}"] = float(n)
    n_rec: Dict[str, int] = {}
    for r in records:
        if r.get("kind") == "recompile":
            site = r.get("site", "?")
            n_rec[site] = n_rec.get(site, 0) + 1
    for site, n in n_rec.items():
        out[f"recompiles/{site}"] = float(n)
    for r in records:
        # last data record per site wins: the EMA's final value
        if r.get("kind") == "data" and "input_bound_pct" in r:
            out[f"data/{r.get('site', '?')}/input_bound_pct"] = \
                float(r["input_bound_pct"])
    res_counts: Dict[str, int] = {}
    ck_ms: List[float] = []
    for r in records:
        if r.get("kind") == "resilience":
            ev = r.get("event", "?")
            res_counts[ev] = res_counts.get(ev, 0) + 1
            if ev == "checkpoint" and "ms" in r:
                ck_ms.append(float(r["ms"]))
    for ev, n in res_counts.items():
        out[f"resilience/{ev}"] = float(n)
    if ck_ms:
        out["resilience/checkpoint_p50_ms"] = _pctl(sorted(ck_ms), 50)
    # migrate records aggregate per site: flip count + total wire bytes
    # + the last flip's plan size (the diffable footprint of the
    # device->device reshard path; a wire_bytes delta between rounds is
    # a layout-schedule change, a migrations delta is a consumer change)
    mig_by_site: Dict[str, List[Dict]] = {}
    for r in records:
        if r.get("kind") == "migrate":
            mig_by_site.setdefault(r.get("site", "?"), []).append(r)
    for site, recs in mig_by_site.items():
        base = f"migrate/{site}"
        out[f"{base}/migrations"] = float(len(recs))
        out[f"{base}/wire_bytes"] = float(
            sum(r.get("wire_bytes", 0) for r in recs))
        out[f"{base}/plan_ops"] = float(recs[-1].get("plan_ops", 0))
        out[f"{base}/peak_host_bytes"] = float(
            max(r.get("peak_host_bytes", 0) for r in recs))
    for r in records:
        # last collective record per site wins (trainer rebuilds emit one
        # each); the diffable ZeRO/quantization footprint of a run
        if r.get("kind") == "collective":
            site = r.get("site", "?")
            for key in ("wire_bytes_per_step", "quant_fraction",
                        "param_bytes_per_chip", "opt_bytes_per_chip",
                        "grad_bytes_per_chip"):
                if isinstance(r.get(key), (int, float)):
                    out[f"collective/{site}/{key}"] = float(r[key])
            out[f"collective/{site}/stage"] = float(r.get("stage", 0))
        # last zero_overlap record per site wins: the latency-hiding
        # scan's engagement + schedule-exact hidden fraction (ISSUE 18)
        # — a --compare where engaged flips 1 -> 0 is the overlap
        # silently falling back to the unrolled body
        if r.get("kind") == "zero_overlap":
            site = r.get("site", "?")
            out[f"zero/{site}/overlap_fraction"] = float(
                r.get("overlap_fraction", 0.0))
            out[f"zero/{site}/overlap_engaged"] = \
                1.0 if r.get("engaged") else 0.0
            out[f"zero/{site}/overlap_ag_bytes_per_step"] = float(
                r.get("run_ag_bytes_per_step", 0.0))
    return out


def compare(path_a: str, path_b: str, merge: bool = False) -> str:
    a = _comparable_metrics(_select_run(_read(path_a), merge=merge)[0])
    b = _comparable_metrics(_select_run(_read(path_b), merge=merge)[0])
    keys = sorted(set(a) | set(b))
    lines = [f"telemetry compare — A={path_a}  B={path_b}",
             "",
             f"{'metric':44s} {'A':>12s} {'B':>12s} {'delta':>9s}"]
    disp_regressions = []
    for k in keys:
        va, vb = a.get(k), b.get(k)
        if va is None or vb is None:
            lines.append(f"{k:44s} "
                         f"{'-' if va is None else format(va, '12.3f'):>12s} "
                         f"{'-' if vb is None else format(vb, '12.3f'):>12s} "
                         f"{'only ' + ('B' if va is None else 'A'):>9s}")
            continue
        if va:
            delta = f"{100.0 * (vb - va) / abs(va):+8.1f}%"
        else:
            delta = "   n/a" if vb == 0 else "   new"
        flag = ""
        if "dispatches_per_step" in k and vb > va * 1.05 + 1e-9:
            flag = "  !!"
            disp_regressions.append((k, va, vb))
        lines.append(f"{k:44s} {va:12.3f} {vb:12.3f} {delta:>9s}{flag}")
    if disp_regressions:
        # the superstep-wiring guard (ISSUE 11): a workload whose
        # dispatches/step GREW between rounds means the K-steps-per-
        # dispatch engine silently fell back to per-step eager dispatch
        # (knob off, engine fallback, or a bench row regression)
        lines.append("")
        lines.append(f"!! dispatches_per_step grew on "
                     f"{len(disp_regressions)} metric(s) — superstep "
                     f"fell back to eager dispatch?")
        for k, va, vb in disp_regressions:
            lines.append(f"!!   {k}: {va:.3f} -> {vb:.3f}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize or diff mxtpu telemetry JSONL runs")
    ap.add_argument("paths", nargs="*", help="one JSONL file to summarize")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"),
                    help="diff two JSONL runs per metric")
    ap.add_argument("--all", action="store_true",
                    help="merge every run in the file instead of only "
                         "the newest (files are append-mode; each sink "
                         "open writes a run_start boundary)")
    args = ap.parse_args(argv)
    if args.compare:
        print(compare(*args.compare, merge=args.all))
        return 0
    if len(args.paths) != 1:
        ap.error("pass exactly one JSONL path, or --compare A B")
    print(summarize(args.paths[0], merge=args.all))
    return 0


if __name__ == "__main__":
    sys.exit(main())
