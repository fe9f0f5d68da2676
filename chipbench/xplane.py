"""Reduction of a profiler trace (``*.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU the trace
has one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds
one event per operation that ran on the device and whose line
``XLA Modules`` holds one event per executable launched; host threads are
lines of ``/host:CPU``. From that:

- ``busy_s``: the union of the device-op intervals, averaged over chips;
- ``window_s``: first device-op start to last device-op end;
- ``modules``: per executable name, the device duration of every launch;
- ``device_ops``: the ten operations that took most device time;
- ``idle_gaps``: the ten longest gaps between device ops, each named by
  the host event that covers most of it.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def module_name(event_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``: the id changes per compile."""
    return _SUFFIX.sub("", event_name)


_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_op(name: str, width: int = 96) -> str:
    """An HLO instruction's text cut to what names it: ``fusion.12
    fusion(bf16[48,16,25,1024,64] %k.1), kind=kLoop`` (layouts and the
    tuple of result shapes dropped)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):            # a tuple of result shapes
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return (head.lstrip("%") + " " + rest.strip())[:width]


def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by (start, end) pairs."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps_of(intervals: List[Tuple[float, float]]):
    """(start, end) of every stretch no interval covers, between the
    first start and the last end."""
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def _events(line):
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


def reduce(profile) -> dict:
    """``profile`` is a ``ProfileData``. Returns the dict described at the
    top of this file, or ``{}`` when no device plane holds an operation."""
    busy, windows = [], []
    modules: Dict[str, List[float]] = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    first_ops = None
    host_events = []
    for plane in profile.planes:
        if _DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = _events(line)
                    if not evs:
                        continue
                    iv = [(s, e) for _, s, e in evs]
                    busy.append(union_s(iv))
                    windows.append(max(e for _, e in iv)
                                   - min(s for s, _ in iv))
                    if first_ops is None:
                        first_ops = iv
                    for name, s, e in evs:
                        op_time[name] += e - s
                elif line.name == MODULES_LINE:
                    for name, s, e in _events(line):
                        modules[module_name(name)].append(e - s)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_events.extend(_events(line))
    if not busy:
        return {}
    chips = len(busy)
    gaps = sorted(gaps_of(first_ops), key=lambda g: g[0] - g[1])[:10]
    return {
        "chips": chips,
        "busy_s": sum(busy) / chips,
        "window_s": sum(windows) / chips,
        "modules": dict(modules),
        "device_ops": [[short_op(n), t / chips] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_covering(host_events, s, e), e - s]
                      for s, e in gaps],
    }


def _covering(host_events, s: float, e: float) -> str:
    """Name of the host event that overlaps [s, e) most; the shortest of
    those that cover it equally, so a leaf and not ``main``."""
    best, best_key = "nothing traced on the host", (0.0, 0.0)
    for name, hs, he in host_events:
        ov = min(e, he) - max(s, hs)
        if ov <= 0:
            continue
        key = (ov, -(he - hs))
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_dir(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    return reduce(load(path)) if path else {}
