"""Arithmetic that several metric readers share: from a run's record
(client stamps, the program's counters, the reduced trace) to the
quantities the readers divide."""

from __future__ import annotations

from . import stats


def counter_delta(record: dict, key: str):
    c = record.get("counters")
    if not c or key not in c["start"]:
        return None
    return c["end"][key] - c["start"][key]


def idle_pct(record: dict):
    tr = record.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def module_times(record: dict, role: str):
    """Device durations of every traced launch of the executable that the
    configuration's ``trace_modules`` names for ``role``."""
    tr = record.get("trace") or {}
    names = {}
    for block in ("serving", "training"):
        names.update(record["config"].get(block, {}).get("trace_modules", {}))
    want = names.get(role)
    return (tr.get("modules") or {}).get(want) or None


def itemsize(record: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[
        record["config"]["dtype"]]


def decode_work(record: dict, t0: float, t1: float):
    """Decode steps' useful work between ``t0`` and ``t1``: the tokens
    (past each request's first) delivered there and the cached positions
    they saw between them."""
    tokens = ctx_sum = 0
    for r in record["requests"]:
        for j, t in enumerate(r["stamps"]):
            if j and stats.in_window(t, t0, t1):
                tokens += 1
                ctx_sum += r["prompt_len"] + j
    return tokens, ctx_sum


def decode_flops(record: dict, tokens: int, ctx_sum: int) -> float:
    """Model FLOPs of ``tokens`` generated tokens that saw ``ctx_sum``
    keys between them."""
    fl, model = record["flops"], record["model"]
    return 2.0 * fl.matmul_params(model) * tokens \
        + fl.attn_flops(model, ctx_sum)


def prefills_in(record: dict, t0: float, t1: float):
    """Prompt lengths of the requests whose first token came in [t0, t1)."""
    return [r["prompt_len"] for r in record["requests"]
            if r["stamps"] and stats.in_window(r["stamps"][0], t0, t1)]


def serve_flops(record: dict) -> float:
    """Model FLOPs of every token the window processed: prompts at their
    true length, generated tokens at the context each saw."""
    fl, model = record["flops"], record["model"]
    t0, t1 = record["t0"], record["t1"]
    total = decode_flops(record, *decode_work(record, t0, t1))
    return total + sum(fl.prefill_flops(model, n)
                       for n in prefills_in(record, t0, t1))
