"""Built-in meters: step telemetry, device memory, online MFU.

``StepMeter`` is the one instrument every hot path wraps around its
step: ``gluon.trainer.Trainer.step`` (FusedStep or per-param),
``parallel.spmd.SPMDTrainer.step``/``run_steps``,
``parallel.pipeline.PipelineTrainer.step``, and
``serving.server.ModelServer``'s batch dispatch. Per step it records:

* wall time (histogram + EMA gauge) and dispatch count,
* host→device transfer bytes (the caller passes what it moved),
* device memory stats (live/peak bytes via ``Device.memory_stats()``),
* an **online MFU gauge** — XLA cost-analysis FLOPs over the step-time
  EMA against the measured MXU ceiling, the same canonical formula
  ``bench.py`` documents (``mfu_pct = 100 * (flops/per_step)/ceiling``),
* recompile-watchdog bookkeeping (``note_step`` + attribution scope),
* a JSONL record and, when the profiler runs, a chrome-trace event so
  telemetry, host scopes and the XPlane trace share one timeline.

Steps during which a compile fired are excluded from the EMA/MFU (the
wall time would be compile-dominated); they are still counted and their
JSONL record carries ``"compiled": true``.

FLOP counting is **lazy and observer-gated**: ``flops_fn`` is only
invoked when MFU accounting is on (``MXTPU_TELEMETRY_MFU``; ``auto`` =
only while a JSONL sink or /metrics server is live), because deriving
FLOPs needs an extra AOT lower+compile per executable signature.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, Optional


#: published dense bf16 peak of one chip in TF/s, keyed by the
#: ``device_kind`` jax reports ("TPU v5 lite" is the v5e). Source: Google
#: Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
PEAK_BF16_TFS = {"TPU v5 lite": 197.0}


def ceiling_tfs() -> float:
    """The MFU denominator in TF/s: the published peak of the device
    this process runs on, looked up by ``device_kind``. SOURCE OF TRUTH
    for the number — bench.py resolves it from here (lazily, so its
    driver loop stays package-import-free), so the online
    ``mxtpu_mfu_percent`` gauge and the offline bench MFU share one
    table and one override (``MXTPU_BENCH_CEILING_TFS``). A device that
    is not in the table — the CPU included — raises ``LookupError``:
    an MFU against an assumed peak is never emitted."""
    env = os.environ.get("MXTPU_BENCH_CEILING_TFS")
    if env:
        return float(env)
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_TFS:
        raise LookupError(
            f"no published peak for device kind {kind!r}; MFU is not "
            "emitted (set MXTPU_BENCH_CEILING_TFS to supply one)")
    return PEAK_BF16_TFS[kind]


def mfu_percent(flops_per_second: float) -> float:
    """The canonical MFU formula (one implementation — the online
    ``mxtpu_mfu_percent`` gauge, ``bench.py`` rows, and the
    ``resnet_decision_bench`` part_d offline fit all call this):
    ``100 * achieved_flops_per_second / (ceiling_tfs() * 1e12)``."""
    return 100.0 * flops_per_second / (ceiling_tfs() * 1e12)


def flops_of_compiled(compiled) -> Optional[float]:
    """Per-device FLOPs from an XLA compiled executable's own cost
    model, or None where the backend doesn't expose cost analysis."""
    if compiled is None:
        return None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):      # one dict per device
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0)) if cost else 0.0
        return flops or None
    except Exception:
        return None


def aot_flops(jitted, args) -> Optional[float]:
    """Cost-analysis FLOPs for ``jitted(*args)`` via an AOT
    lower+compile (the executable jax compiles on call is not
    introspectable from the outside). One extra compile per signature —
    call only under ``mfu_enabled()`` and cache the result.

    The probe compile runs inside ``probe_scope``: it keeps the ambient
    attribution — a meter whose step contains it still marks the step
    compile-dominated and keeps it out of the EMA/MFU — but the
    watchdog never flags it as drift."""
    from .watchdog import probe_scope

    try:
        with probe_scope():
            return flops_of_compiled(jitted.lower(*args).compile())
    except Exception:
        return None


#: memory-stats capability probe: None = unknown, False = backend has
#: none (CPU) — probed once so hot paths don't re-ask a dead API per step
_mem_device = None
_mem_supported: Optional[bool] = None


def device_memory_stats() -> Optional[Dict[str, int]]:
    """(bytes_in_use, peak_bytes_in_use, bytes_limit) of device 0, or
    None where the PJRT plugin doesn't expose memory stats (CPU). The
    capability is probed once per process; unsupported backends pay no
    per-step query."""
    global _mem_device, _mem_supported
    if _mem_supported is False:
        return None
    try:
        if _mem_device is None:
            import jax

            _mem_device = jax.local_devices()[0]
        stats = _mem_device.memory_stats()
    except Exception:
        _mem_supported = False
        return None
    if not stats:
        _mem_supported = False
        return None
    _mem_supported = True
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False

    def commit(self, t0, t1, **fields):
        return None


_NULL_CTX = _NullCtx()

_EMA_ALPHA = 0.3


def _meter_id_counter():
    import itertools

    return itertools.count(1)


#: process-wide meter numbering: the ``meter`` gauge label that keeps
#: two meters on one site from overwriting each other's gauges
_meter_ids = _meter_id_counter()


class _StepScope:
    """The live per-step context: measures wall time, attributes
    compiles, commits instruments on exit."""

    __slots__ = ("meter", "h2d_bytes", "dispatches", "count", "flops_fn",
                 "detail", "turn", "defer", "_t0", "_attr", "_compiles0",
                 "record")

    def __init__(self, meter, h2d_bytes, dispatches, count, flops_fn,
                 detail, turn, defer):
        self.meter = meter
        self.h2d_bytes = h2d_bytes
        self.dispatches = dispatches
        self.count = count
        self.flops_fn = flops_fn
        self.detail = detail
        self.turn = turn
        self.defer = defer
        self.record: Dict = {}

    def __enter__(self):
        from .watchdog import attribute

        m = self.meter
        wd = m._watchdog()
        if wd is not None and m._last_step == 0:
            # a fresh meter (new trainer/server instance) gets its own
            # warmup budget even when the site name was used before
            wd.begin_site(m.site)
        # step counts tick at COMMIT (after the body): a compile during
        # the first occurrence of a new signature is judged against the
        # steps *completed* so far, so warming a second window size /
        # bucket right at the warmup boundary is not a false positive.
        # The compile snapshot is SITE-scoped: a compile on another
        # thread (serving bucket miss next to a train loop) must not
        # mark this step compile-dominated
        self._compiles0 = wd.site_compiles(m.site) if wd is not None \
            else None
        self._attr = attribute(m.site, self.detail)
        self._attr.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        self._attr.__exit__(exc_type, exc, tb)
        if exc_type is None and not self.defer:
            self.commit(self._t0, t1)
        return False

    def commit(self, t0: float, t1: float, **fields) -> None:
        """Commit the step as running from ``t0`` to ``t1``, ``fields``
        in its record: at the scope's exit, or, for a scope made with
        ``defer``, when the caller says (a decode step is launched
        inside its scope and fetched later, maybe after the step behind
        it was launched; compiles are attributed inside the scope)."""
        self._t0 = t0
        self.record = fields
        turn = self.turn
        if turn is None:
            self.meter._commit(self, t1 - t0, self._compiles0)
        else:
            # the commit is a phase of the caller's turn, and its
            # record the turn's: close() adds t0/dur_s/phases to it
            with turn.phase("meter"):
                self.meter._commit(self, t1 - t0, self._compiles0)
            turn.rec = self.record


class StepMeter:
    """Per-site step telemetry. One instance per trainer/server; cheap
    to construct; every ``step(...)`` context is a no-op returning a
    shared null context when telemetry is disabled.

    Two live meters sharing one site name (two Trainers stepping
    concurrently — a GAN's generator and discriminator) keep their
    *gauges* apart: the per-step gauges (EMA, MFU, FLOPs) carry a
    ``meter`` label next to ``site``, so concurrent meters stop
    overwriting each other's values. Counters and histograms stay
    site-keyed — aggregating steps/seconds across the meters of one
    site is the useful reading there."""

    def __init__(self, site: str):
        self.site = site
        self.meter_id = f"m{next(_meter_ids)}"
        self._last_step = 0
        self._ema_s: Optional[float] = None
        self._insts = None

    # -- lazies -------------------------------------------------------------
    def _watchdog(self):
        from . import get_watchdog

        return get_watchdog()

    def _instruments(self):
        if self._insts is None:
            from . import counter, gauge, histogram

            s = {"site": self.site}
            # gauges are keyed by (site, meter): a gauge holds "the
            # latest value", and two meters on one site would otherwise
            # overwrite each other's EMA/MFU (the old documented
            # cross-talk caveat). Counters/histograms aggregate, so
            # they stay site-keyed.
            g = {"site": self.site, "meter": self.meter_id}
            self._insts = {
                "steps": counter("mxtpu_step_total",
                                 "steps executed", **s),
                "seconds": histogram("mxtpu_step_seconds",
                                     "step wall time", **s),
                "ema": gauge("mxtpu_step_time_ema_seconds",
                             "EMA of step wall time", **g),
                "dispatches": counter("mxtpu_step_dispatches_total",
                                      "executable dispatches", **s),
                "h2d": counter("mxtpu_h2d_bytes_total",
                               "host-to-device bytes moved by steps",
                               **s),
                "mfu": gauge("mxtpu_mfu_percent",
                             "online MFU: cost-analysis FLOPs over the "
                             "step-time EMA vs the measured ceiling",
                             **g),
                "flops": gauge("mxtpu_step_flops",
                               "XLA cost-analysis FLOPs per step", **g),
                # unlabelled process-wide gauges, cached here so the hot
                # path never re-resolves them through the registry lock
                "mem": gauge("mxtpu_device_bytes_in_use",
                             "live device bytes (device 0)"),
                "mem_peak": gauge("mxtpu_device_peak_bytes_in_use",
                                  "peak device bytes (device 0)"),
            }
        return self._insts

    # -- the hot-path API ---------------------------------------------------
    def step(self, h2d_bytes: int = 0, dispatches: int = 1,
             count: int = 1, flops_fn: Optional[Callable] = None,
             detail: str = "", turn=None, defer: bool = False):
        """Context manager around one step (or ``count`` fused steps —
        ``run_steps`` drives N device-side steps in one dispatch).
        ``flops_fn`` is a zero-arg callable returning per-step FLOPs (or
        None); it is only called when MFU accounting is observed.
        ``turn`` is the caller's open ``trace.Turn`` (one with a phase
        ``meter``): the commit is then timed as that phase and its
        ledger record becomes the turn's. With ``defer`` the scope's
        exit commits nothing: the caller calls the scope's
        ``commit(t0, t1, **fields)`` when the step's end is known."""
        from . import enabled

        if not enabled():
            return _NULL_CTX
        return _StepScope(self, int(h2d_bytes), int(dispatches),
                          max(1, int(count)), flops_fn, detail, turn,
                          bool(defer))

    # -- commit -------------------------------------------------------------
    def _commit(self, scope: _StepScope, dt: float,
                compiles0: Optional[int]) -> None:
        from . import jsonl_emit, mfu_enabled

        insts = self._instruments()
        per = dt / scope.count
        wd = self._watchdog()
        if wd is not None:
            self._last_step = wd.note_steps(self.site, scope.count)
        else:
            self._last_step += scope.count
        compiled = (compiles0 is not None and wd is not None
                    and wd.site_compiles(self.site) != compiles0)
        insts["steps"].inc(scope.count)
        # one superstep = count per-step observations of the amortized
        # per-step time: percentiles stay step-weighted, so a K=32 run
        # compares apples-to-apples with a per-dispatch run
        insts["seconds"].observe(per, n=scope.count)
        insts["dispatches"].inc(scope.dispatches)
        if scope.h2d_bytes:
            insts["h2d"].inc(scope.h2d_bytes)
        mfu_pct = None
        flops = None
        if not compiled:
            self._ema_s = per if self._ema_s is None else \
                (1 - _EMA_ALPHA) * self._ema_s + _EMA_ALPHA * per
            insts["ema"].set(self._ema_s)
            if scope.flops_fn is not None and mfu_enabled():
                try:
                    flops = scope.flops_fn()
                except Exception:
                    flops = None
                if flops:
                    insts["flops"].set(flops)
                    try:
                        mfu_pct = mfu_percent(flops / self._ema_s)
                    except (LookupError, ValueError):
                        # a device with no published peak, or a bad
                        # MXTPU_BENCH_CEILING_TFS: no MFU, never a guess
                        mfu_pct = None
                    else:
                        insts["mfu"].set(mfu_pct)
        mem = device_memory_stats()
        if mem is not None:
            insts["mem"].set(mem.get("bytes_in_use", 0))
            if "peak_bytes_in_use" in mem:
                insts["mem_peak"].set(mem["peak_bytes_in_use"])
        rec = dict(scope.record, kind="step", site=self.site,
                   step=self._last_step, t0=scope._t0,
                   wall_ms=round(per * 1e3, 4),
                   dispatches=scope.dispatches, h2d_bytes=scope.h2d_bytes)
        if scope.count > 1:
            rec["fused_steps"] = scope.count
        if compiled:
            rec["compiled"] = True
        if self._ema_s is not None:
            rec["ema_ms"] = round(self._ema_s * 1e3, 4)
        if flops:
            rec["flops"] = flops
        if mfu_pct is not None:
            rec["mfu_pct"] = round(mfu_pct, 2)
        if mem is not None:
            rec["mem_bytes_in_use"] = mem.get("bytes_in_use")
            if "peak_bytes_in_use" in mem:
                rec["mem_peak_bytes"] = mem["peak_bytes_in_use"]
        if scope.detail:
            rec["detail"] = scope.detail
        scope.record = rec
        jsonl_emit(rec)
        # flight recorder: every step commit lands in the always-on
        # ring (one deque append), so an incident dump carries the
        # recent turn ledger even with span sampling off
        from .trace import flight_step

        flight_step(rec)
        self._correlate(scope, dt, rec)

    def _correlate(self, scope: _StepScope, dt: float, rec: Dict) -> None:
        """Mirror the step into the running profiler's chrome-trace
        stream (an X event on this thread + counter tracks) so host
        scopes, telemetry and the XPlane trace line up."""
        from .. import profiler

        if not profiler.is_running():
            return
        args = {k: v for k, v in rec.items()
                if k in ("step", "wall_ms", "ema_ms", "mfu_pct",
                         "dispatches", "h2d_bytes", "compiled",
                         "mem_bytes_in_use")}
        profiler._record(f"telemetry::{self.site}::step", "telemetry",
                         "X", ts=scope._t0, dur=dt, args=args)
        if "mfu_pct" in rec:
            profiler._record(f"{self.site}/mfu_pct", "counter", "C",
                             args={"value": rec["mfu_pct"]})
        if rec.get("mem_bytes_in_use") is not None:
            profiler._record("device/bytes_in_use", "counter", "C",
                             args={"value": rec["mem_bytes_in_use"]})

    # -- reads --------------------------------------------------------------
    @property
    def ema_seconds(self) -> Optional[float]:
        return self._ema_s

    @property
    def steps_seen(self) -> int:
        return self._last_step
