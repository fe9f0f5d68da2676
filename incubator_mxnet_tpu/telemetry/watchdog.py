"""Recompile watchdog: flag XLA compiles that happen after warmup.

The silent killer of every hot path in this repo is an unnoticed
per-step recompile — a drifting hyper-key in ``FusedStep``, a ragged
batch shape reaching ``SPMDTrainer``, an unbucketed signature hitting
the serving executor cache. Offline, ``bench.py`` catches these as a
throughput collapse a round later; this watchdog catches them **online,
at the step that triggered them**.

Mechanism: ``jax.monitoring`` fires a duration event for every backend
compile (``/jax/core/compile/backend_compile_duration`` — present since
jax 0.4.x; we subscribe through the public listener API). Each
instrumented hot path (Trainer step, SPMD step, pipeline step, serving
batch) wraps its work in :func:`attribute`, so a compile event can be
attributed to the exact site — and each path reports step counts via
:meth:`RecompileWatchdog.note_step`. A compile observed while a site is
past its warmup budget (``MXTPU_RECOMPILE_WARMUP_STEPS``) is *flagged*:
recorded, counted in ``mxtpu_recompiles_flagged_total{site=...}``, sent
to the JSONL sink, and logged. Compiles during warmup (or outside any
attributed scope — model building, AOT warmup) only tick
``mxtpu_compiles_total``.

Fallback: on a runtime without ``jax.monitoring`` the watchdog degrades
to jit cache-miss counting — :meth:`note_cache_miss` lets engines that
manage their own executable caches (``FusedStep``, the serving executor
cache) report misses directly through the same flagging path.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger("mxtpu.telemetry")

#: event names that mean "XLA compiled an executable". jax times
#: ``compile_or_get_cached`` under this name, so with the persistent
#: compilation cache on the event fires for a cache hit as well: a
#: post-warmup executable request is seen whether the cache is warm or
#: cold (checked on jax 0.9.0, ISSUE 21)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)

_tls = threading.local()


def _attribution_stack() -> list:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


class attribute:
    """Context manager marking work as belonging to ``site`` (e.g.
    ``trainer.step``, ``serving.resnet``) with an optional free-form
    ``detail`` (e.g. ``bucket=8``). Compiles observed inside the scope
    are attributed to the innermost site. Thread-local, so serving
    worker threads and the training loop never cross-attribute."""

    __slots__ = ("site", "detail")

    def __init__(self, site: str, detail: str = ""):
        self.site = site
        self.detail = detail

    def __enter__(self):
        _attribution_stack().append((self.site, self.detail))
        return self

    def __exit__(self, *exc):
        _attribution_stack().pop()
        return False


def current_attribution() -> Tuple[Optional[str], str]:
    stack = _attribution_stack()
    return stack[-1] if stack else (None, "")


class probe_scope:
    """Marks deliberate telemetry-internal compiles (the MFU FLOP
    probe). A compile inside this scope keeps its ambient attribution —
    so a meter still sees the step as compile-dominated and excludes it
    from the EMA/MFU — but is never *flagged* as drift."""

    __slots__ = ()

    def __enter__(self):
        _tls.probe = getattr(_tls, "probe", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.probe -= 1
        return False


def _in_probe() -> bool:
    return getattr(_tls, "probe", 0) > 0


@dataclasses.dataclass
class RecompileEvent:
    """One flagged post-warmup compile."""

    site: str
    detail: str
    step: int           # the site's step count when the compile fired
    event: str          # jax event name (or "cache_miss" fallback)
    duration_s: float
    ts: float           # wall clock (time.time())


class RecompileWatchdog:
    """Listener + per-site step ledger + flag log.

    One process-global instance is armed lazily by the package front
    door whenever telemetry is enabled; tests build private instances
    with explicit ``start``/``stop``.
    """

    def __init__(self, warmup_steps: Optional[int] = None,
                 max_events: int = 256):
        self._warmup_override = None if warmup_steps is None \
            else int(warmup_steps)
        self._lock = threading.Lock()
        self._steps: Dict[str, int] = {}
        self._warmup_base: Dict[str, int] = {}
        self._site_compiles: Dict[str, int] = {}
        self._flagged: deque = deque(maxlen=max_events)
        self.compile_count = 0       # every observed compile, any phase
        self.flag_count = 0
        self._installed = False
        # registration succeeding does not prove the event name still
        # exists (jax.monitoring keys are not a stability-guaranteed
        # surface): stay in cache-miss fallback until a matching event
        # is actually observed, else a renamed event leaves the
        # watchdog blind with both paths disabled
        self._listener_live = False
        self._dead = False           # stop() tombstone: see below

    @property
    def warmup_steps(self) -> int:
        """Explicit constructor value, else the live config knob — like
        every other telemetry knob, ``config.set(
        'MXTPU_RECOMPILE_WARMUP_STEPS', n)`` takes effect immediately
        on the already-armed watchdog (compiles are rare; one registry
        read per observed compile)."""
        if self._warmup_override is not None:
            return self._warmup_override
        from ..config import config

        return int(config.get("MXTPU_RECOMPILE_WARMUP_STEPS"))

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "RecompileWatchdog":
        """Register the jax.monitoring listener (idempotent)."""
        self._dead = False
        if self._installed:
            return self
        try:
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(self._on_event)
            self._installed = True
        except Exception:           # no jax.monitoring: cache-miss mode
            self._installed = False
        return self

    def stop(self) -> None:
        # unregistration goes through a private jax API (the public
        # surface has no per-listener remove); the tombstone guarantees
        # a dead watchdog stays silent even if that API is ever gone
        # and the listener leaks
        self._dead = True
        if not self._installed:
            return
        try:
            from jax._src import monitoring as _m

            _m._unregister_event_duration_listener_by_callback(
                self._on_event)
        except Exception:
            pass
        self._installed = False

    # -- hot-path hooks -----------------------------------------------------
    def note_step(self, site: str) -> int:
        """Record one step for ``site``; returns the new count."""
        return self.note_steps(site, 1)

    def note_steps(self, site: str, n: int) -> int:
        """Bulk step increment (one lock round-trip — ``run_steps(n)``
        commits n steps at once); returns the new count."""
        with self._lock:
            total = self._steps.get(site, 0) + int(n)
            self._steps[site] = total
            return total

    def begin_site(self, site: str) -> None:
        """Restart ``site``'s warmup budget. Called when a NEW meter
        takes over a site (a second trainer in the same process): its
        own first compiles are legitimate warmup, not drift of the
        previous trainer's executables. The step ledger itself is NOT
        reset — an older meter sharing the site keeps monotonic step
        numbers; only the warmup window reopens (for warmup_steps
        steps, drift at the shared site goes unflagged — compiles at a
        site cannot be attributed to one meter or the other)."""
        with self._lock:
            self._warmup_base[site] = self._steps.get(site, 0)

    def steps(self, site: str) -> int:
        with self._lock:
            return self._steps.get(site, 0)

    def site_compiles(self, site: str) -> int:
        """Compiles attributed to ``site`` (meters diff this around a
        step so a compile in another thread/site never marks an
        unrelated step compile-dominated)."""
        with self._lock:
            return self._site_compiles.get(site, 0)

    def note_cache_miss(self, site: str, detail: str = "") -> None:
        """Fallback path: an executable-cache miss reported by an engine
        that manages its own cache (used when jax.monitoring is absent
        or its compile event never fires; the first compile of a process
        may be seen by both paths — a harmless duplicate tick of
        ``mxtpu_compiles_total`` during warmup)."""
        if self._installed and self._listener_live:
            return                  # the event listener sees compiles
        self._observe("cache_miss", 0.0, site_override=(site, detail))

    # -- the listener -------------------------------------------------------
    def _on_event(self, event: str, duration_secs: float = 0.0,
                  **kwargs) -> None:
        if self._dead or event not in COMPILE_EVENTS:
            return
        self._listener_live = True
        self._observe(event, float(duration_secs))

    def _observe(self, event: str, duration_s: float,
                 site_override: Optional[Tuple[str, str]] = None) -> None:
        site, detail = site_override if site_override is not None \
            else current_attribution()
        in_probe = site_override is None and _in_probe()
        from . import _instruments_for_compile  # lazy: avoid cycle

        # a probe compile outside any step scope (SPMD/pipeline MFU
        # probes run at commit time) still counts, but under its own
        # label so the exporter doesn't show phantom unattributed work
        compiles, flagged_ctr = _instruments_for_compile(
            site if site is not None else
            ("(mfu-probe)" if in_probe else None))
        with self._lock:
            self.compile_count += 1
            if site is not None:
                self._site_compiles[site] = \
                    self._site_compiles.get(site, 0) + 1
            past_warmup = (site is not None
                           and not in_probe
                           and self._steps.get(site, 0)
                           - self._warmup_base.get(site, 0)
                           > self.warmup_steps)
            step = self._steps.get(site, 0) if site else 0
        compiles.inc()
        if not past_warmup:
            return
        ev = RecompileEvent(site=site, detail=detail, step=step,
                            event=event, duration_s=duration_s,
                            ts=time.time())
        with self._lock:
            self._flagged.append(ev)
            self.flag_count += 1
        flagged_ctr.inc()
        logger.warning(
            "recompile after warmup: site=%s%s step=%d event=%s "
            "(%.1f ms) — a post-warmup compile means a cache key is "
            "drifting (shape, hyper, or bucket)", site,
            f" [{detail}]" if detail else "", step, event,
            duration_s * 1e3)
        from . import jsonl_emit    # lazy: avoid cycle

        jsonl_emit({"kind": "recompile", "site": site, "detail": detail,
                    "step": step, "event": event,
                    "duration_ms": round(duration_s * 1e3, 3),
                    "ts": ev.ts})
        # a flagged post-warmup recompile is a trigger-engine event:
        # capture one bounded profiler trace of the drift (debounced,
        # no-op unless MXTPU_TRACE_TRIGGER is on)
        from .trace import trigger    # lazy: avoid cycle

        trigger("recompile", site=site, detail=detail)

    # -- reads --------------------------------------------------------------
    def flagged(self, site: Optional[str] = None) -> List[RecompileEvent]:
        with self._lock:
            evs = list(self._flagged)
        if site is None:
            return evs
        return [e for e in evs if e.site == site]

    def reset(self) -> None:
        with self._lock:
            self._steps.clear()
            self._warmup_base.clear()
            self._site_compiles.clear()
            self._flagged.clear()
            self.compile_count = 0
            self.flag_count = 0
