"""The program's turn ledger, read in this process after the window.

``incubator_mxnet_tpu.telemetry.trace`` keeps one always-on ring of
records, one per unit of work of a scheduler or trainer thread, each with
``site``, ``kind``, ``t0`` (``perf_counter``, the clock the runners stamp
``t0``/``t1`` with), ``dur_s`` and ``phases`` (seconds by phase name). The
readers under ``metrics/`` take the records of the cell's site whose ``t0``
lies in the window. They read nothing (``None``) where the program stamps
no ``t0`` (a program older than the ledger), where telemetry is off (the
ring is empty), and where the ring is full and its oldest record is
younger than the window's start: the start was overwritten, and a
percentile of the rest would be a percentile of something else.
"""

from __future__ import annotations

from typing import List, Optional

from . import stats


def read_ring() -> dict:
    from incubator_mxnet_tpu.telemetry import trace

    steps = trace.ring()["steps"]
    capacity = getattr(trace, "ring_capacity", lambda: len(steps) + 1)()
    return {"steps": steps, "capacity": capacity}


def site_of(record: dict) -> str:
    """The ledger site the cell's product path writes under."""
    if record["kind"] == "serve":
        return "decode." + record["config"]["name"]
    return "spmd.step"


def turns(record: dict, kind: str) -> Optional[List[dict]]:
    """The window's ledger records of ``kind`` (``step`` | ``prefill``)
    at the cell's site, oldest first; None where the ledger cannot show
    the whole window. ``record["ledger"]`` holds what was read from the
    program (read once, on first use)."""
    led = record.get("ledger")
    if led is None:
        led = record["ledger"] = read_ring()
    steps = led["steps"]
    if not steps or "t0" not in steps[0]:
        return None
    t0, t1 = record["t0"], record["t1"]
    if len(steps) >= led["capacity"] and steps[0]["t0"] > t0:
        return None
    site = site_of(record)
    return [r for r in steps
            if r.get("site") == site and r.get("kind") == kind
            and "phases" in r and stats.in_window(r["t0"], t0, t1)]


def durations(record: dict, kind: str) -> List[float]:
    return [r["dur_s"] for r in turns(record, kind) or []]


def phase_sums(record: dict, kind: str, *names: str) -> List[float]:
    """Per record, the seconds spent in the phases ``names``."""
    return [sum(r["phases"][n] for n in names)
            for r in turns(record, kind) or []]


def mean(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else 1e3 * seconds
