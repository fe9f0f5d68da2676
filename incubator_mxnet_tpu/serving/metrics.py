"""Per-model serving metrics.

One ``ServingMetrics`` instance is shared by a model's executor cache,
batcher, and server so every layer reports into the same ledger:
request latency percentiles (sliding window), queue depth, batch
occupancy (requests per executed batch — the number dynamic batching
exists to raise), and executor-cache hit/miss/compile counters.

Every observation is mirrored into the shared ``mxtpu.telemetry``
registry (``mxtpu_serving_*`` metric families, labelled by model), so
serving and training counters live in ONE namespace behind ONE set of
exporters (Prometheus /metrics, JSONL — docs/OBSERVABILITY.md) instead
of the pre-telemetry split-brain of serving-local dicts vs profiler
counters. The local ints stay authoritative for ``snapshot()`` — they
are functional server state (backpressure, occupancy) and must work
with telemetry disabled.

The live gauges are also published through ``profiler.counter`` so a
profiling run (``profiler.set_state('run')``) shows queue depth and
batch size as counter tracks in the chrome trace, next to the
``serving::<model>::*`` execution scopes the server emits.
"""

from __future__ import annotations

import math
import threading
from collections import deque
from typing import Dict, List, Optional

from .. import profiler
from .. import telemetry

#: occupancy bucket bounds: requests per executed batch
_OCCUPANCY_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)


def _percentile(sorted_vals, p: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   math.ceil(p / 100.0 * len(sorted_vals)) - 1))
    return sorted_vals[k]


class ServingMetrics:
    """Thread-safe counters + sliding-window latency reservoir."""

    def __init__(self, model: str = "model", window: int = 2048):
        self.model = model
        self._lock = threading.Lock()
        self._latencies = deque(maxlen=window)     # seconds per request
        self._batch_sizes = deque(maxlen=window)   # requests per batch
        self.requests = 0
        self.rejected = 0
        self.shed = 0
        self.forced_closes = 0
        self.batches = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self.artifact_hits = 0       # executables deserialized from disk
        self.artifact_misses = 0     # no (usable) artifact: compiled
        self.artifact_refused = 0    # artifact present but guard-mismatched
        self.deserialize_seconds = 0.0
        self.warmup_seconds = 0.0    # last warmup() wall time
        self.swaps = 0               # weight versions published
        self.queue_depth = 0
        self._c_depth = profiler.counter(f"serving/{model}/queue_depth")
        self._c_batch = profiler.counter(f"serving/{model}/batch_size")
        # shared-registry mirrors (no-op NULL instruments when telemetry
        # is disabled)
        lbl = {"model": model}
        self._t_requests = telemetry.counter(
            "mxtpu_serving_requests_total", "requests answered", **lbl)
        self._t_rejected = telemetry.counter(
            "mxtpu_serving_rejected_total",
            "requests rejected by backpressure", **lbl)
        self._t_shed = telemetry.counter(
            "mxtpu_serving_deadline_shed_total",
            "queued requests shed past their per-request deadline", **lbl)
        self._t_forced = telemetry.counter(
            "mxtpu_serving_forced_close_total",
            "drains force-closed after their timeout expired", **lbl)
        self._t_batches = telemetry.counter(
            "mxtpu_serving_batches_total", "batches executed", **lbl)
        self._t_queue = telemetry.gauge(
            "mxtpu_serving_queue_depth", "requests waiting", **lbl)
        self._t_occupancy = telemetry.histogram(
            "mxtpu_serving_batch_occupancy",
            "requests per executed batch",
            buckets=_OCCUPANCY_BUCKETS, **lbl)
        self._t_latency = telemetry.histogram(
            "mxtpu_serving_request_latency_seconds",
            "submit-to-result request latency", **lbl)
        self._t_hits = telemetry.counter(
            "mxtpu_serving_cache_hits_total",
            "executor-cache hits", **lbl)
        self._t_misses = telemetry.counter(
            "mxtpu_serving_cache_misses_total",
            "executor-cache misses", **lbl)
        self._t_compiles = telemetry.counter(
            "mxtpu_serving_compiles_total",
            "executor compiles", **lbl)
        self._t_compile_s = telemetry.counter(
            "mxtpu_serving_compile_seconds_total",
            "time spent compiling executors", **lbl)
        # persistent-artifact cache (ISSUE 14): the cold-start split —
        # every warmed executable either deserialized (artifact hit) or
        # compiled (artifact miss; 'refused' = present but stale)
        self._t_art_hits = telemetry.counter(
            "mxtpu_serving_artifact_hits_total",
            "executables deserialized from the persistent artifact "
            "store instead of compiled", **lbl)
        self._t_art_misses = telemetry.counter(
            "mxtpu_serving_artifact_misses_total",
            "executor-cache misses with no usable artifact (compiled)",
            **lbl)
        self._t_art_refused = telemetry.counter(
            "mxtpu_serving_artifact_refused_total",
            "artifacts refused on a guard-fingerprint mismatch (wrong "
            "jaxlib/backend/topology/model fingerprint)", **lbl)
        self._t_deser_s = telemetry.counter(
            "mxtpu_serving_deserialize_seconds_total",
            "time spent deserializing artifact executables", **lbl)
        self._t_warmup_s = telemetry.gauge(
            "mxtpu_serving_warmup_seconds",
            "wall time of the last warmup() — the cold-start cost "
            "(compare against compile_seconds/deserialize_seconds for "
            "the compile-vs-artifact split)", **lbl)
        self._t_swaps = telemetry.counter(
            "mxtpu_serving_weight_swaps_total",
            "weight versions published into the live server "
            "(hot swaps, no drain)", **lbl)

    # -- batcher-side observations -------------------------------------------
    def observe_queue_depth(self, depth: int) -> None:
        with self._lock:
            self.queue_depth = depth
        self._c_depth.set_value(depth)
        self._t_queue.set(depth)

    def observe_reject(self) -> None:
        with self._lock:
            self.rejected += 1
        self._t_rejected.inc()

    def observe_shed(self) -> None:
        """A queued request aged past the per-request deadline and was
        failed with ``DeadlineExceededError`` instead of served late."""
        with self._lock:
            self.shed += 1
        self._t_shed.inc()

    def observe_forced_close(self) -> None:
        """A graceful drain hit its timeout and was force-closed with
        requests still in flight (docs/SERVING.md shutdown contract)."""
        with self._lock:
            self.forced_closes += 1
        self._t_forced.inc()

    def observe_batch(self, batch_size: int) -> None:
        with self._lock:
            self.batches += 1
            self._batch_sizes.append(batch_size)
        self._c_batch.set_value(batch_size)
        self._t_batches.inc()
        self._t_occupancy.observe(batch_size)

    def observe_latency(self, seconds: float) -> None:
        with self._lock:
            self.requests += 1
            self._latencies.append(seconds)
        self._t_requests.inc()
        self._t_latency.observe(seconds)

    # -- executor-cache-side observations ------------------------------------
    def cache_hit(self) -> None:
        with self._lock:
            self.cache_hits += 1
        self._t_hits.inc()

    def cache_miss(self) -> None:
        with self._lock:
            self.cache_misses += 1
        self._t_misses.inc()

    def observe_compile(self, seconds: float) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_seconds += seconds
        self._t_compiles.inc()
        self._t_compile_s.inc(seconds)

    def observe_deserialize(self, seconds: float) -> None:
        """An executable came off the persistent artifact store (no
        XLA compile happened)."""
        with self._lock:
            self.artifact_hits += 1
            self.deserialize_seconds += seconds
        self._t_art_hits.inc()
        self._t_deser_s.inc(seconds)

    def artifact_miss(self, refused: bool = False) -> None:
        """No usable artifact for a missed signature: the cache fell
        back to compile (and will repersist). ``refused`` marks the
        stale-fingerprint case — an artifact existed but its guard
        (jaxlib/backend/topology/model fingerprint) mismatched."""
        with self._lock:
            self.artifact_misses += 1
            if refused:
                self.artifact_refused += 1
        self._t_art_misses.inc()
        if refused:
            self._t_art_refused.inc()

    def observe_warmup(self, seconds: float) -> None:
        with self._lock:
            self.warmup_seconds = seconds
        self._t_warmup_s.set(seconds)

    def observe_swap(self) -> None:
        """A new weight version was published into the live server."""
        with self._lock:
            self.swaps += 1
        self._t_swaps.inc()

    # -- reads ----------------------------------------------------------------
    def latency_ms(self, p: float) -> float:
        """Latency percentile in milliseconds over the sliding window."""
        with self._lock:
            vals = sorted(self._latencies)
        return _percentile(vals, p) * 1e3

    def mean_batch_occupancy(self) -> float:
        """Mean requests per executed batch (> 1 means batching works)."""
        with self._lock:
            sizes = list(self._batch_sizes)
        return sum(sizes) / len(sizes) if sizes else 0.0

    def snapshot(self) -> Dict[str, object]:
        occ = self.mean_batch_occupancy()
        with self._lock:
            vals = sorted(self._latencies)   # one sort for all percentiles
        return {
            "model": self.model,
            "requests": self.requests,
            "rejected": self.rejected,
            "shed": self.shed,
            "forced_closes": self.forced_closes,
            "batches": self.batches,
            "queue_depth": self.queue_depth,
            "batch_occupancy": occ,
            "latency_ms": {f"p{p}": _percentile(vals, p) * 1e3
                           for p in (50, 90, 99)},
            "warmup_seconds": self.warmup_seconds,
            "swaps": self.swaps,
            "executor_cache": {"hits": self.cache_hits,
                               "misses": self.cache_misses,
                               "compiles": self.compiles,
                               "compile_seconds": self.compile_seconds,
                               "artifact_hits": self.artifact_hits,
                               "artifact_misses": self.artifact_misses,
                               "artifact_refused": self.artifact_refused,
                               "deserialize_seconds":
                                   self.deserialize_seconds},
        }


#: decode-step occupancy bucket bounds: active slots per step
_SLOT_BUCKETS = (1, 2, 4, 8, 16, 32, 64)


class DecodeMetrics:
    """Per-session continuous-batching decode metrics (ISSUE 12).

    The ``mxtpu_decode_*`` telemetry family: slot occupancy, token
    throughput, the prefill-vs-decode wall-time split, KV-cache bytes,
    and the queue-wait histogram — mirrored into the shared registry
    exactly like :class:`ServingMetrics` so decode serving shows up in
    the same /metrics + JSONL exporters as everything else. Local ints
    stay authoritative for ``snapshot()`` (work with telemetry off)."""

    def __init__(self, model: str = "model", window: int = 2048):
        self.model = model
        self._lock = threading.Lock()
        self._queue_waits = deque(maxlen=window)    # seconds, per request
        self._ttfts = deque(maxlen=window)          # submit -> first token
        self._active_hist = deque(maxlen=window)    # slots active per step
        self.requests = 0
        self.rejected = 0
        self.shed = 0
        self.finished = 0
        self.tokens = 0
        self.prefills = 0
        self.steps = 0
        self.steps_ahead = 0       # dispatched before the fetch before them
        self.tokens_dropped = 0    # computed for a stream found ended
        self.prefill_seconds = 0.0
        self.decode_seconds = 0.0
        self.slots_active = 0
        self.cache_bytes = 0
        lbl = {"model": model}
        self._t_requests = telemetry.counter(
            "mxtpu_decode_requests_total", "decode requests admitted to "
            "the queue", **lbl)
        self._t_rejected = telemetry.counter(
            "mxtpu_decode_rejected_total",
            "decode requests rejected by backpressure", **lbl)
        self._t_shed = telemetry.counter(
            "mxtpu_decode_shed_total",
            "queued decode requests shed past their deadline", **lbl)
        self._t_finished = telemetry.counter(
            "mxtpu_decode_finished_total", "decode requests completed",
            **lbl)
        self._t_tokens = telemetry.counter(
            "mxtpu_decode_tokens_total", "tokens generated", **lbl)
        self._t_steps = telemetry.counter(
            "mxtpu_decode_steps_total", "decode steps executed", **lbl)
        self._t_steps_ahead = telemetry.counter(
            "mxtpu_decode_steps_ahead_total",
            "decode steps dispatched while the step before them was "
            "still unfetched", **lbl)
        self._t_dropped = telemetry.counter(
            "mxtpu_decode_tokens_dropped_total",
            "slot-tokens a step launched ahead computed for a stream "
            "that its eos_id had ended", **lbl)
        self._t_prefills = telemetry.counter(
            "mxtpu_decode_prefills_total", "prefills executed", **lbl)
        self._t_prefill_s = telemetry.counter(
            "mxtpu_decode_prefill_seconds_total",
            "wall time in prefill+join dispatches (the prefill half of "
            "the prefill/decode split)", **lbl)
        self._t_decode_s = telemetry.counter(
            "mxtpu_decode_seconds_total",
            "time the decode steps added to every stream: from the later "
            "of a step's dispatch and the fetch before it to its own "
            "fetch (the decode half of the prefill/decode split)", **lbl)
        self._t_slots = telemetry.gauge(
            "mxtpu_decode_slots_active",
            "KV-cache slots occupied by live sequences", **lbl)
        self._t_slots_total = telemetry.gauge(
            "mxtpu_decode_slots_total", "KV-cache slot capacity", **lbl)
        self._t_cache_bytes = telemetry.gauge(
            "mxtpu_decode_cache_bytes",
            "device bytes held by the resident KV cache", **lbl)
        self._t_occupancy = telemetry.histogram(
            "mxtpu_decode_step_occupancy",
            "active slots per decode step", buckets=_SLOT_BUCKETS, **lbl)
        self._t_queue_wait = telemetry.histogram(
            "mxtpu_decode_queue_wait_seconds",
            "submit-to-slot-admission wait", **lbl)
        self._t_step_s = telemetry.histogram(
            "mxtpu_decode_step_seconds", "decode step wall time", **lbl)
        self._t_prefill_hist = telemetry.histogram(
            "mxtpu_decode_prefill_latency_seconds",
            "per-prompt prefill+join wall time", **lbl)

    def set_capacity(self, slots: int, cache_bytes: int) -> None:
        with self._lock:
            self.cache_bytes = int(cache_bytes)
        self._t_slots_total.set(slots)
        self._t_cache_bytes.set(cache_bytes)

    def observe_submit(self) -> None:
        with self._lock:
            self.requests += 1
        self._t_requests.inc()

    def observe_reject(self) -> None:
        with self._lock:
            self.rejected += 1
        self._t_rejected.inc()

    def observe_shed(self) -> None:
        with self._lock:
            self.shed += 1
        self._t_shed.inc()

    def observe_admit(self, queue_wait_s: float, prefill_s: float) -> None:
        with self._lock:
            self.prefills += 1
            self.prefill_seconds += prefill_s
            self._queue_waits.append(queue_wait_s)
        self._t_prefills.inc()
        self._t_prefill_s.inc(prefill_s)
        self._t_queue_wait.observe(queue_wait_s)
        self._t_prefill_hist.observe(prefill_s)

    def queue_waits(self) -> List[float]:
        """Submit -> admission waits (seconds) of the last ``window``
        admitted requests, oldest first (a copy)."""
        with self._lock:
            return list(self._queue_waits)

    def observe_first_token(self, ttft_s: float) -> None:
        with self._lock:
            self._ttfts.append(ttft_s)

    def observe_step(self, active: int, seconds: float, new_tokens: int,
                     ahead: bool = False, dropped: int = 0) -> None:
        with self._lock:
            self.steps += 1
            self.steps_ahead += ahead
            self.tokens_dropped += dropped
            self.decode_seconds += seconds
            self.tokens += new_tokens
            self._active_hist.append(active)
        self._t_steps.inc()
        if ahead:
            self._t_steps_ahead.inc()
        if dropped:
            self._t_dropped.inc(dropped)
        self._t_decode_s.inc(seconds)
        self._t_tokens.inc(new_tokens)
        self._t_occupancy.observe(active)
        self._t_step_s.observe(seconds)

    def observe_prefill_token(self, n: int = 1) -> None:
        """Prefill emits the first generated token of a sequence."""
        with self._lock:
            self.tokens += n
        self._t_tokens.inc(n)

    def observe_slots(self, active: int) -> None:
        with self._lock:
            self.slots_active = active
        self._t_slots.set(active)

    def observe_finish(self) -> None:
        with self._lock:
            self.finished += 1
        self._t_finished.inc()

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            waits = sorted(self._queue_waits)
            ttfts = sorted(self._ttfts)
            act = list(self._active_hist)
            total = self.prefill_seconds + self.decode_seconds
            return {
                "model": self.model,
                "requests": self.requests,
                "rejected": self.rejected,
                "shed": self.shed,
                "finished": self.finished,
                "tokens": self.tokens,
                "steps": self.steps,
                "steps_ahead": self.steps_ahead,
                "tokens_dropped": self.tokens_dropped,
                "prefills": self.prefills,
                "slots_active": self.slots_active,
                "cache_bytes": self.cache_bytes,
                "mean_step_occupancy":
                    (sum(act) / len(act)) if act else 0.0,
                "queue_wait_ms": {f"p{p}": _percentile(waits, p) * 1e3
                                  for p in (50, 90, 99)},
                "ttft_ms": {f"p{p}": _percentile(ttfts, p) * 1e3
                            for p in (50, 90, 99)},
                "prefill_seconds": self.prefill_seconds,
                "decode_seconds": self.decode_seconds,
                "prefill_frac":
                    (self.prefill_seconds / total) if total else 0.0,
            }


class RegistryMetrics:
    """Registry-level serving metrics (ISSUE 14): the ``mxtpu_registry_*``
    family — resident-model and budget gauges plus per-model admission /
    eviction / SLO-rejection / weight-swap counters, mirrored into the
    shared telemetry registry like every other serving family. Local
    ints stay authoritative for ``snapshot()`` (work with telemetry
    disabled); per-model telemetry counters are created lazily on first
    observation (the shared registry dedupes by (name, labels))."""

    def __init__(self, registry: str = "registry"):
        self.registry = registry
        self._lock = threading.Lock()
        self.admissions = 0
        self.cold_admissions = 0     # built by compile (no warm artifacts)
        self.evictions = 0
        self.slo_rejections = 0
        self.swaps = 0
        self.resident = 0
        self.resident_bytes = 0
        self.budget_bytes = 0
        self.per_model: Dict[str, Dict[str, int]] = {}
        lbl = {"registry": registry}
        self._g_resident = telemetry.gauge(
            "mxtpu_registry_models_resident",
            "models currently holding device memory in this registry",
            **lbl)
        self._g_bytes = telemetry.gauge(
            "mxtpu_registry_resident_bytes",
            "device bytes attributed to resident models "
            "(params + KV caches)", **lbl)
        self._g_budget = telemetry.gauge(
            "mxtpu_registry_budget_bytes",
            "configured device-memory budget (0 = unlimited)", **lbl)

    def _bump(self, model: str, key: str) -> None:
        with self._lock:
            slot = self.per_model.setdefault(
                model, {"admissions": 0, "evictions": 0,
                        "slo_rejections": 0, "swaps": 0})
            slot[key] += 1

    def _counter(self, name: str, help: str, model: str):
        return telemetry.counter(name, help, registry=self.registry,
                                 model=model)

    def observe_admit(self, model: str, cold: bool) -> None:
        with self._lock:
            self.admissions += 1
            if cold:
                self.cold_admissions += 1
        self._bump(model, "admissions")
        self._counter("mxtpu_registry_admissions_total",
                      "models admitted (built/rebuilt) into the registry",
                      model).inc()

    def observe_evict(self, model: str) -> None:
        with self._lock:
            self.evictions += 1
        self._bump(model, "evictions")
        self._counter("mxtpu_registry_evictions_total",
                      "idle models evicted to fit the memory budget",
                      model).inc()

    def observe_slo_rejection(self, model: str) -> None:
        with self._lock:
            self.slo_rejections += 1
        self._bump(model, "slo_rejections")
        self._counter("mxtpu_registry_slo_rejections_total",
                      "requests rejected at admission because the "
                      "model's backlog already exceeded its deadline",
                      model).inc()

    def observe_swap(self, model: str) -> None:
        with self._lock:
            self.swaps += 1
        self._bump(model, "swaps")
        self._counter("mxtpu_registry_weight_swaps_total",
                      "weight versions hot-swapped through the registry",
                      model).inc()

    def set_residency(self, resident: int, resident_bytes: int) -> None:
        with self._lock:
            self.resident = int(resident)
            self.resident_bytes = int(resident_bytes)
        self._g_resident.set(resident)
        self._g_bytes.set(resident_bytes)

    def set_budget(self, budget_bytes: int) -> None:
        with self._lock:
            self.budget_bytes = int(budget_bytes)
        self._g_budget.set(budget_bytes)

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {
                "registry": self.registry,
                "admissions": self.admissions,
                "cold_admissions": self.cold_admissions,
                "evictions": self.evictions,
                "slo_rejections": self.slo_rejections,
                "swaps": self.swaps,
                "resident": self.resident,
                "resident_bytes": self.resident_bytes,
                "budget_bytes": self.budget_bytes,
                "per_model": {m: dict(v)
                              for m, v in self.per_model.items()},
            }
