"""``mxtpu.telemetry.trace`` — end-to-end span tracing, the flight
recorder, and trigger-driven profiler capture (docs/OBSERVABILITY.md
"Tracing & flight recorder").

The aggregate layer (registry + meters) answers "how is the system
doing"; this module answers "where did THIS request / THIS step spend
its time". Three services on one spine:

* **Spans** — ``span(name, **attrs)`` context managers building
  per-trace trees. Context is thread-local and *explicitly carried*
  across the runtime's thread hops (the batcher queue, the
  DecodeSession scheduler, the async checkpoint writer, the
  DevicePrefetcher producer) via :func:`use`; work that happens on a
  worker thread still lands in the submitting request's trace. Trace
  IDs are minted at the serving front door under **head-based
  sampling** (``MXTPU_TRACE_SAMPLE``, default 0): an unsampled request
  carries no context and every ``span()`` on its path returns the
  shared no-op ``NULL_SPAN`` — the same zero-cost-when-off contract as
  the NULL instruments. Finished spans flow to two sinks: the JSONL
  sink (``kind:"trace"`` records, next to steps/recompiles/bench rows)
  and — while a profiling run is active — the chrome-trace stream
  (host scopes of ``profiler.py``; not the XPlane's clock).

* **Phases and the turn ledger** — a :class:`Turn` is the open ledger
  record of one unit of a thread's work (a decode step, a prefill, a
  trainer step); ``turn.phase(name)`` times one part of it. A phase is a
  ``jax.profiler.TraceAnnotation("mxtpu/<site>/<phase>")``, so while a
  ``jax.profiler`` capture is live (whoever started it) the program's
  phases sit on the thread's line of the XPlane's ``/host:CPU`` plane,
  on the clock of the device ops; and its ``perf_counter`` duration is
  added to the turn's record. No sampling decision, no lock, no
  ``Span``. ``turn.close`` writes ``t0``, ``dur_s`` and ``phases``
  into the always-on step ring below.

* **Flight recorder** — a fixed-size ring of the last N finished spans
  plus the last N turn-ledger records (every ``StepMeter`` commit calls
  :func:`flight_step`; one deque append, always on). :func:`dump`
  writes the rings atomically (tmp + fsync + rename — the checkpoint
  commit idiom, so a torn dump never corrupts an earlier one) to
  ``MXTPU_TRACE_DUMP_DIR``; the Supervisor calls :func:`incident_dump`
  on fatal / hung-step / SIGTERM-preempt, so every chaos or elastic
  incident ships its own black box.

* **Trigger engine** — :func:`trigger` captures one bounded
  ``jax.profiler`` trace when something breaches: a queue-wait/TTFT SLO
  (:func:`note_latency`, threshold ``MXTPU_TRACE_SLO_MS``) or a
  post-warmup recompile flagged by the watchdog. Debounced
  (``MXTPU_TRACE_TRIGGER_DEBOUNCE_S``), one capture at a time, off by
  default (``MXTPU_TRACE_TRIGGER``); every capture is cross-linked from
  the trace JSONL (``event:"trigger"`` with the profile directory).

Render trace files with ``tools/trace_report.py`` (per-request
critical-path breakdowns, TTFT decomposition, ``--compare``).
"""

from __future__ import annotations

import json
import os
import random as _random_mod
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

__all__ = [
    "NULL_SPAN", "Span", "SpanContext", "active_spans", "ctx", "dump",
    "Turn", "flight_step", "incident_dump", "note_latency", "record",
    "reset", "ring", "ring_capacity", "span", "start", "trigger", "use",
]

_lock = threading.Lock()
_tls = threading.local()

#: open (started, not yet finished) sampled spans, span_id -> record —
#: the "what was in flight when it died" half of an incident dump.
#: Bounded: a span leaked by a crashed worker must not grow this
#: forever, so past the cap the oldest entry is evicted.
_ACTIVE_CAP = 4096
_active: "OrderedDict[str, Dict]" = OrderedDict()

_ring_spans: Optional[deque] = None
_ring_steps: Optional[deque] = None
_dump_seq = 0
_insts = None

# trigger-engine state: last capture time (monotonic) + in-flight flag
_trigger_last: Optional[float] = None
_trigger_busy = False


def _cfg(name: str):
    from ..config import config

    return config.get(name)


def _telemetry_enabled() -> bool:
    from . import enabled

    return enabled()


def _instruments():
    global _insts
    if _insts is None:
        from . import counter

        _insts = {
            "spans": counter("mxtpu_trace_spans_total",
                             "finished sampled trace spans"),
            "dumps": counter("mxtpu_trace_dumps_total",
                             "flight-recorder dumps written"),
            "triggers": counter("mxtpu_trace_triggers_total",
                                "trigger-driven profiler captures"),
        }
    return _insts


def _new_id() -> str:
    return f"{_random_mod.getrandbits(64):016x}"


# -- context ----------------------------------------------------------------
class SpanContext:
    """Immutable (trace_id, span_id) pair — the thing that crosses a
    thread hop (on a batcher queue tuple, a ``_Request`` slot, a
    checkpoint-writer job). Adopt it on the other side with
    :func:`use`."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self):
        return f"SpanContext({self.trace_id}/{self.span_id})"


def _stack() -> list:
    stack = getattr(_tls, "trace_stack", None)
    if stack is None:
        stack = _tls.trace_stack = []
    return stack


def ctx() -> Optional[SpanContext]:
    """The ambient span context of this thread, or None (unsampled /
    outside any span). Snapshot it before handing work to another
    thread; the worker re-enters it with :func:`use`."""
    stack = getattr(_tls, "trace_stack", None)
    return stack[-1] if stack else None


class use:
    """Adopt a foreign :class:`SpanContext` (or a live :class:`Span`)
    on the current thread: spans opened inside become its children.
    ``use(None)`` is a no-op, so call sites can pass the carried
    context unconditionally."""

    __slots__ = ("_ctx", "_pushed")

    def __init__(self, context):
        if isinstance(context, Span):
            context = context.context
        self._ctx = context
        self._pushed = False

    def __enter__(self):
        if self._ctx is not None:
            _stack().append(self._ctx)
            self._pushed = True
        return self._ctx

    def __exit__(self, *exc):
        if self._pushed:
            _stack().pop()
        return False


# -- spans ------------------------------------------------------------------
class _NullSpan:
    """Shared no-op span: what every unsampled path gets. Like the NULL
    instrument — one process-wide instance, no per-call allocation."""

    __slots__ = ()
    trace_id = None
    span_id = None
    context = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self, **attrs):
        pass

    def annotate(self, **attrs):
        pass


NULL_SPAN = _NullSpan()


class Span:
    """One live sampled span. Use as a context manager for same-thread
    scopes, or keep it detached (:func:`start`) and call :meth:`end`
    from wherever the work actually finishes — the serving root spans
    end on the worker thread that resolves the request."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "t0", "_ended", "_pushed")

    def __init__(self, trace_id: str, parent_id: Optional[str],
                 name: str, attrs: Dict[str, Any]):
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.name = name
        self.attrs = attrs
        self.t0 = time.perf_counter()
        self._ended = False
        self._pushed = False
        with _lock:
            _active[self.span_id] = {
                "trace": trace_id, "span": self.span_id,
                "parent": parent_id, "name": name, "t0": self.t0}
            while len(_active) > _ACTIVE_CAP:
                _active.popitem(last=False)

    @property
    def context(self) -> SpanContext:
        return SpanContext(self.trace_id, self.span_id)

    def parent_context(self) -> Optional[SpanContext]:
        if self.parent_id is None:
            return None
        return SpanContext(self.trace_id, self.parent_id)

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self):
        _stack().append(self.context)
        self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._pushed:
            _stack().pop()
            self._pushed = False
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.end()
        return False

    def end(self, **attrs) -> None:
        """Finish the span (idempotent) and emit it to the sinks."""
        if self._ended:
            return
        self._ended = True
        if attrs:
            self.attrs.update(attrs)
        t1 = time.perf_counter()
        with _lock:
            _active.pop(self.span_id, None)
        _finish(self, self.t0, t1)


def span(name: str, **attrs):
    """A span under the ambient context; at the top of a thread with
    sampling on, a fresh root (head-sampled). Returns ``NULL_SPAN``
    when the path is unsampled — the common, zero-cost case."""
    stack = getattr(_tls, "trace_stack", None)
    if stack:
        parent = stack[-1]
        return Span(parent.trace_id, parent.span_id, name, attrs)
    if not _should_sample():
        return NULL_SPAN
    return Span(_new_id(), None, name, attrs)


def start(name: str, **attrs) -> Optional[Span]:
    """Mint a *detached* span (not pushed on this thread's stack): the
    front-door primitive. Under an ambient context it is a child;
    otherwise a head-sampling decision is made and ``None`` comes back
    for the unsampled case, so callers can skip carrying context
    entirely."""
    parent = ctx()
    if parent is not None:
        return Span(parent.trace_id, parent.span_id, name, attrs)
    if not _should_sample():
        return None
    return Span(_new_id(), None, name, attrs)


def record(parent, name: str, t0: float, t1: float,
           **attrs) -> Optional[SpanContext]:
    """Emit an already-measured span (explicit ``perf_counter``
    endpoints) under ``parent`` (a :class:`SpanContext`, a
    :class:`Span`, or None = no-op). The batch-shaped hot paths use
    this: one dispatch covers many requests, so each carried context
    gets the shared interval recorded as its own child after the
    fact — no context juggling inside the dispatch."""
    if parent is None:
        return None
    if isinstance(parent, Span):
        parent = parent.context
    sid = _new_id()
    rec = {"kind": "trace", "trace": parent.trace_id, "span": sid,
           "parent": parent.span_id, "name": name,
           "t0": t0, "dur_ms": round((t1 - t0) * 1e3, 4),
           "tid": threading.get_ident()}
    if attrs:
        rec.update(attrs)
    _emit(rec, t0, t1 - t0, name)
    return SpanContext(parent.trace_id, sid)


def _should_sample() -> bool:
    if not _telemetry_enabled():
        return False
    try:
        rate = float(_cfg("MXTPU_TRACE_SAMPLE"))
    except (TypeError, ValueError):
        return False
    if rate <= 0.0:
        return False
    return rate >= 1.0 or _random_mod.random() < rate


def _finish(sp: Span, t0: float, t1: float) -> None:
    rec = {"kind": "trace", "trace": sp.trace_id, "span": sp.span_id,
           "parent": sp.parent_id, "name": sp.name,
           "t0": t0, "dur_ms": round((t1 - t0) * 1e3, 4),
           "tid": threading.get_ident()}
    if sp.attrs:
        rec.update(sp.attrs)
    _emit(rec, t0, t1 - t0, sp.name)


def _emit(rec: Dict, t0: float, dur: float, name: str) -> None:
    from . import jsonl_emit

    _spans_ring().append(rec)
    _instruments()["spans"].inc()
    jsonl_emit(rec)
    from .. import profiler

    if profiler.is_running():
        profiler._record(f"trace::{name}", "trace", "X", ts=t0, dur=dur,
                         args={k: v for k, v in rec.items()
                               if k not in ("kind", "t0", "tid")})


# -- flight recorder --------------------------------------------------------
def _ring_len() -> int:
    try:
        return max(16, int(_cfg("MXTPU_TRACE_RING")))
    except (TypeError, ValueError):
        return 12288


def _spans_ring() -> deque:
    global _ring_spans
    if _ring_spans is None:
        with _lock:
            if _ring_spans is None:
                _ring_spans = deque(maxlen=_ring_len())
    return _ring_spans


def _steps_ring() -> deque:
    global _ring_steps
    if _ring_steps is None:
        with _lock:
            if _ring_steps is None:
                _ring_steps = deque(maxlen=_ring_len())
    return _ring_steps


def flight_step(rec: Dict) -> None:
    """Append one turn-ledger record (a ``StepMeter`` commit dict, or a
    :class:`Turn` with no meter of its own) to the always-on ring. One
    deque append — cheap enough for every step even with sampling off,
    which is what makes the black box useful in the default
    configuration."""
    _steps_ring().append(rec)


def ring() -> Dict[str, List[Dict]]:
    """The flight recorder's current contents (copies)."""
    return {"spans": list(_spans_ring()), "steps": list(_steps_ring())}


def ring_capacity() -> int:
    """Records each ring holds before it overwrites its oldest."""
    return _steps_ring().maxlen


def active_spans() -> List[Dict]:
    """Sampled spans currently open (started, not finished)."""
    with _lock:
        return [dict(v) for v in _active.values()]


def _chrome_events(spans: List[Dict]) -> List[Dict]:
    pid = os.getpid()
    out = []
    for rec in spans:
        out.append({
            "name": rec.get("name", "?"), "cat": "trace", "ph": "X",
            "ts": float(rec.get("t0", 0.0)) * 1e6,
            "dur": float(rec.get("dur_ms", 0.0)) * 1e3,
            "pid": pid, "tid": rec.get("tid", 0),
            "args": {k: v for k, v in rec.items()
                     if k not in ("kind", "t0", "dur_ms", "tid", "name")},
        })
    return out


def dump(reason: str = "manual",
         dir: Optional[str] = None) -> Optional[str]:
    """Write the flight recorder to ``MXTPU_TRACE_DUMP_DIR`` (or
    ``dir``) and return the path; None when no directory is configured.

    The payload holds the span ring, the step-ledger ring, the open
    spans, and a ready-to-load ``traceEvents`` rendering (open the file
    in Perfetto directly); when a profiling run started an XPlane
    trace, its directory rides along for correlation. The write is the
    checkpoint commit idiom — tmp file, fsync, ``os.replace`` — and
    every dump gets a fresh sequence-numbered name, so a dump torn by
    the very crash it documents can never corrupt an earlier one."""
    global _dump_seq
    if dir is None:
        dir = str(_cfg("MXTPU_TRACE_DUMP_DIR") or "").strip()
    if not dir:
        return None
    os.makedirs(dir, exist_ok=True)
    with _lock:
        _dump_seq += 1
        seq = _dump_seq
    spans = list(_spans_ring())
    payload = {
        "reason": reason, "ts": time.time(), "pid": os.getpid(),
        "seq": seq,
        "spans": spans,
        "steps": list(_steps_ring()),
        "active": active_spans(),
        "traceEvents": _chrome_events(spans),
        "displayTimeUnit": "ms",
    }
    from .. import profiler

    xplane = profiler._state.get("jax_trace_dir")
    if xplane:
        payload["otherData"] = {"xplane_dir": xplane}
    path = os.path.join(dir, f"flight-{os.getpid()}-{seq:04d}-{reason}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    _instruments()["dumps"].inc()
    from . import jsonl_emit

    jsonl_emit({"kind": "trace", "event": "dump", "reason": reason,
                "path": path})
    return path


def incident_dump(reason: str) -> Optional[str]:
    """Best-effort :func:`dump` for crash paths (Supervisor fatal,
    hung step, SIGTERM preempt): never raises — forensics must not
    mask the incident it documents."""
    try:
        return dump(reason)
    except Exception:
        return None


# -- phases and the turn ledger ---------------------------------------------
_annotation = None


def _trace_annotation(name: str):
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(name)


class _Phase:
    __slots__ = ("_phases", "_name", "_ann", "_t0")

    def __init__(self, phases: Dict[str, float], name: str, label: str):
        self._phases = phases
        self._name = name
        self._ann = _trace_annotation(label)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._phases[self._name] += dt
        return False


class Turn:
    """The open ledger record of one unit of a thread's work at
    ``site``: a decode step, a prefill, a trainer step. ``phases``
    names every phase the record will carry (each starts at 0, so a
    turn in which a phase never ran still reports it). Owned by the one
    thread that runs the turn: no lock, no sampling, no ``Span``.

    A turn whose work runs under ``StepMeter.step(turn=...)`` shares
    the meter's record: the commit is timed as phase ``meter`` and
    leaves its record in ``rec``, and :meth:`close` adds the turn's
    fields to it in place. A turn with no meter appends a record of
    its own."""

    __slots__ = ("site", "phases", "rec", "_prefix")

    def __init__(self, site: str, phases):
        self.site = site
        self.phases = dict.fromkeys(phases, 0.0)
        self.rec: Optional[Dict] = None
        self._prefix = f"mxtpu/{site}/"

    def phase(self, name: str) -> _Phase:
        """Context manager around one phase of the turn: a
        ``TraceAnnotation`` (recorded only while a ``jax.profiler``
        capture is live, whoever started it) whose ``perf_counter``
        duration is added to ``phases[name]``."""
        return _Phase(self.phases, name, self._prefix + name)

    def add(self, name: str, seconds: float) -> None:
        """Time the caller measured itself (between turns, by
        difference of stamps it already takes)."""
        self.phases[name] += seconds

    def close(self, t0: float, dur_s: float, **fields) -> None:
        """Write the turn into the ledger: ``t0`` and ``dur_s`` on the
        ``perf_counter`` clock, ``phases``, and ``fields``. With
        telemetry off nothing is written."""
        if self.rec is not None:
            self.rec.update(fields, t0=t0, dur_s=dur_s, phases=self.phases)
        elif _telemetry_enabled():
            flight_step(dict(fields, site=self.site, t0=t0, dur_s=dur_s,
                             phases=self.phases))


# -- trigger engine ---------------------------------------------------------
def _trigger_enabled() -> bool:
    val = str(_cfg("MXTPU_TRACE_TRIGGER")).strip().lower()
    return val in ("1", "on", "true", "yes", "auto")


def note_latency(site: str, seconds: float) -> None:
    """SLO gate for the trigger engine: hot paths report per-request
    queue-wait/TTFT here; a value past ``MXTPU_TRACE_SLO_MS`` (0 = no
    SLO) fires one debounced profiler capture. Cheap no-op while the
    trigger knob is off."""
    if not _trigger_enabled() or not _telemetry_enabled():
        return
    try:
        slo_ms = float(_cfg("MXTPU_TRACE_SLO_MS"))
    except (TypeError, ValueError):
        return
    if slo_ms <= 0 or seconds * 1e3 <= slo_ms:
        return
    trigger("slo", site=site, detail=f"{seconds * 1e3:.1f}ms>"
                                     f"{slo_ms:.0f}ms")


def trigger(reason: str, site: str = "", detail: str = "") -> bool:
    """Request one bounded ``jax.profiler`` capture (async, on its own
    daemon thread). Debounced and single-flight: at most one capture
    per ``MXTPU_TRACE_TRIGGER_DEBOUNCE_S``, never two at once, never
    while an explicit profiling run is active. Returns whether a
    capture was actually started."""
    global _trigger_last, _trigger_busy
    if not _telemetry_enabled() or not _trigger_enabled():
        return False
    dump_dir = str(_cfg("MXTPU_TRACE_DUMP_DIR") or "").strip()
    if not dump_dir:
        return False
    from .. import profiler

    if profiler.is_running():
        return False            # an explicit run already captures
    try:
        debounce = float(_cfg("MXTPU_TRACE_TRIGGER_DEBOUNCE_S"))
    except (TypeError, ValueError):
        debounce = 300.0
    now = time.monotonic()
    with _lock:
        if _trigger_busy:
            return False
        if _trigger_last is not None and now - _trigger_last < debounce:
            return False
        _trigger_busy = True
        _trigger_last = now
        global _dump_seq
        _dump_seq += 1
        seq = _dump_seq
    profile_dir = os.path.join(
        dump_dir, f"profile-{os.getpid()}-{seq:04d}-{reason}")
    t = threading.Thread(target=_capture,
                         args=(reason, site, detail, profile_dir),
                         name="mxtpu-trace-trigger", daemon=True)
    t.start()
    return True


def _capture(reason: str, site: str, detail: str,
             profile_dir: str) -> None:
    global _trigger_busy
    try:
        try:
            ms = float(_cfg("MXTPU_TRACE_TRIGGER_CAPTURE_MS"))
        except (TypeError, ValueError):
            ms = 500.0
        ok = False
        try:
            import jax

            jax.profiler.start_trace(profile_dir)
            ok = True
            time.sleep(max(0.0, ms) / 1e3)
        finally:
            if ok:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    ok = False
        _instruments()["triggers"].inc()
        from . import jsonl_emit

        jsonl_emit({"kind": "trace", "event": "trigger",
                    "reason": reason, "site": site, "detail": detail,
                    "profile_dir": profile_dir if ok else None,
                    "captured": ok})
    except Exception:
        pass
    finally:
        with _lock:
            _trigger_busy = False


# -- test hygiene -----------------------------------------------------------
def reset() -> None:
    """Clear rings, open-span set, lazies, and trigger state (tests).
    Thread-local stacks of other threads are theirs to unwind."""
    global _ring_spans, _ring_steps, _insts, _dump_seq, _trigger_last, \
        _trigger_busy
    with _lock:
        _active.clear()
        _ring_spans = None
        _ring_steps = None
        _insts = None
        _dump_seq = 0
        _trigger_last = None
        _trigger_busy = False
    stack = getattr(_tls, "trace_stack", None)
    if stack:
        del stack[:]
