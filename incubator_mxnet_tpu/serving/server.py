"""ModelServer — the serving front door over batcher + executor cache.

Owns one model end to end: load (from a live gluon ``Block``, a native
``.params`` checkpoint through the C ABI, or ``export_for_serving``
artifacts), warm up the bucketed executables, dispatch traffic through
the dynamic batcher on a worker thread, and wind down cleanly (graceful
drain vs immediate shutdown). The MXNet Model Server / ``Module
.predict`` capability, rebuilt TPU-native on AOT-compiled XLA
executables with device-resident weights.

Usage::

    import incubator_mxnet_tpu as mx

    net = mx.gluon.nn.Dense(10, in_units=784)
    net.initialize()
    with mx.serving.ModelServer(net, max_wait_ms=2.0) as srv:
        srv.warmup((784,), "float32")
        fut = srv.submit(example)          # one example, no batch axis
        probs = fut.result()
        print(srv.stats()["latency_ms"]["p99"])
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Optional, Sequence, Tuple

import numpy as np

from .. import profiler
from .. import telemetry
from .batcher import (DeadlineExceededError, DynamicBatcher, QueueFullError,
                      ServerClosedError)
from .executor_cache import DEFAULT_BUCKETS, BucketedExecutorCache
from .metrics import ServingMetrics

__all__ = ["DeadlineExceededError", "ModelServer", "QueueFullError",
           "ServerClosedError", "load_block_checkpoint",
           "load_weight_arrays"]


def _sharded_prefix(params_path: str) -> Optional[str]:
    """The sharded-checkpoint prefix when ``params_path`` names one (the
    ``{prefix}.manifest.json`` itself or the bare prefix), else None."""
    suffix = ".manifest.json"
    if params_path.endswith(suffix) and os.path.exists(params_path):
        return params_path[:-len(suffix)]
    if os.path.exists(params_path + suffix):
        return params_path
    return None


def load_block_checkpoint(block, params_path: str, ctx=None,
                          use_native: Optional[bool] = None):
    """Load ``params_path`` into ``block`` — the loader shared by every
    serving front door (``ModelServer.from_checkpoint`` and
    ``DecodeSession.from_checkpoint``).

    ``params_path`` may be a native ``.params`` checkpoint (read through
    the C ABI ``mxio_params_*`` when the library is available — the same
    reader non-Python consumers use — else ``nd.load``;
    ``use_native=True`` makes a missing native library an error instead
    of a silent fallback) **or a sharded training checkpoint
    prefix/manifest** written by ``parallel.save_sharded`` on any mesh:
    the ``param/`` + ``frozen/`` tensors are assembled at M=1 through the
    slice-planning reshard reader (``parallel/reshard.py``) — a
    multi-chip training checkpoint feeds the 1-chip serving tier
    directly, no export step, optimizer state never touched
    (docs/SERVING.md "Serving a training checkpoint")."""
    from .. import native
    from ..ndarray import ndarray as _ndimpl

    sharded_prefix = _sharded_prefix(params_path)
    if sharded_prefix is not None:
        from ..parallel.reshard import load_dense_arrays

        arrays = load_dense_arrays(sharded_prefix)
        loaded = {k: _ndimpl.array(v, ctx=ctx, dtype=v.dtype.name)
                  for k, v in arrays.items()}
        block._load_parameters_dict(loaded, params_path, ctx=ctx)
        return block
    if use_native is None:
        use_native = native.lib() is not None
    if use_native:
        arrays = native.native_params_load(params_path)
        loaded = {k: _ndimpl.array(v, ctx=ctx, dtype=v.dtype.name)
                  for k, v in arrays.items()}
        block._load_parameters_dict(loaded, params_path, ctx=ctx)
    else:
        block.load_parameters(params_path, ctx=ctx)
    return block


def load_weight_arrays(source, names=None) -> dict:
    """Resolve a weight *source* to ``{structural_name: np.ndarray}`` —
    the block-less loader behind live weight hot-swap
    (:meth:`ModelServer.publish_weights`). ``source`` may be

    * a dict of arrays (returned as-is, keys assumed structural),
    * a positional list/tuple of arrays (returned as-is — for caches
      built without structural names),
    * a sharded training-checkpoint prefix/manifest from ANY mesh —
      the ``param/`` + ``frozen/`` tensors stream through the PR 7
      slice-planning reader one at a time (``names`` restricts the
      read to the parameters the model actually serves), or
    * a native ``.params`` checkpoint (C ABI reader when available,
      else ``nd.load``), with ``arg:``/``aux:`` prefixes stripped.
    """
    if isinstance(source, dict):
        return {k: np.asarray(v) for k, v in source.items()}
    if isinstance(source, (list, tuple)):
        return [np.asarray(v) for v in source]
    path = str(source)
    sharded_prefix = _sharded_prefix(path)
    if sharded_prefix is not None:
        from ..parallel.reshard import load_dense_arrays

        return load_dense_arrays(sharded_prefix, names=names)
    from .. import native

    if native.lib() is not None:
        arrays = native.native_params_load(path)
    else:
        from ..ndarray import ndarray as _ndimpl

        arrays = {k: v.asnumpy()
                  for k, v in _ndimpl.load(path).items()}
    out = {}
    for k, v in arrays.items():
        if k.startswith(("arg:", "aux:")):
            k = k.split(":", 1)[1]
        out[k] = np.asarray(v)
    return out


def _stage_publish(params, digests, param_names, source,
                   allow_partial: bool, model: str, formats=None):
    """The shared first half of every weight publish: resolve the
    source to arrays, drop checkpoint tensors the serving graph does
    not consume (an explicit dict publish keeps unknown keys so staging
    rejects typos loudly), and stage the swap — all off the hot path,
    each changed parameter placed in its live one's format."""
    from .executor_cache import stage_weight_swap

    names = set(param_names or []) or None
    arrays = load_weight_arrays(source, names=names)
    if names is not None and isinstance(arrays, dict) \
            and not isinstance(source, dict):
        arrays = {k: v for k, v in arrays.items() if k in names}
        if not arrays:
            # a checkpoint whose tensor names match NOTHING served is a
            # wrong-model/typo'd path, not a weight update — committing
            # it would bump the version while the old weights keep
            # serving, silently
            raise ValueError(
                f"checkpoint {source!r} contains no tensors matching "
                f"{model}'s served parameter names "
                f"(e.g. {sorted(names)[:3]}); wrong checkpoint?")
    return stage_weight_swap(params, digests, param_names, arrays,
                             allow_partial=allow_partial, model=model,
                             formats=formats)


def _resolve_version(base, version):
    """Explicit version tag, else autobump an integer lineage."""
    if version is not None:
        return version
    return (base + 1) if isinstance(base, int) else 1


def _emit_swap_record(model: str, stats: dict) -> None:
    telemetry.jsonl_emit({"kind": "registry", "event": "swap",
                          "model": model, **stats})


class ModelServer:
    """Serve one model with dynamic batching and bucketed AOT executors.

    ``model`` is a gluon ``Block`` (parameters initialized) or an
    already-built ``BucketedExecutorCache``. ``max_batch_size`` defaults
    to the largest bucket; it may not exceed it (a flushed batch must
    fit the biggest executable).

    ``artifact_dir`` (default: the ``MXTPU_SERVING_ARTIFACT_DIR`` knob)
    points the executor cache at a persistent artifact store: warmup
    deserializes previously-compiled executables instead of compiling
    (docs/SERVING.md "Model registry & persistent artifacts").
    """

    def __init__(self, model, buckets: Optional[Sequence[int]] = None,
                 max_batch_size: Optional[int] = None,
                 max_wait_ms: float = 5.0, max_queue: int = 64,
                 name: Optional[str] = None,
                 donate: Optional[bool] = None,
                 deadline_ms: Optional[float] = None,
                 artifact_dir: Optional[str] = None,
                 model_version: str = ""):
        if isinstance(model, BucketedExecutorCache):
            if buckets is not None or donate is not None \
                    or artifact_dir is not None:
                raise ValueError(
                    "buckets/donate/artifact_dir are fixed by the "
                    "prebuilt BucketedExecutorCache; configure them "
                    "there")
            self._cache = model
            name = name or model.name
        else:
            name = name or (getattr(model, "name", "") or "model")
            self._cache = BucketedExecutorCache.from_block(
                model,
                buckets=DEFAULT_BUCKETS if buckets is None else buckets,
                donate=donate, name=name, metrics=ServingMetrics(name),
                artifact_dir=artifact_dir, model_version=model_version)
        self.name = name
        self.metrics: ServingMetrics = self._cache.metrics
        if max_batch_size is None:
            max_batch_size = self._cache.max_batch_size
        if max_batch_size > self._cache.max_batch_size:
            raise ValueError(
                f"max_batch_size={max_batch_size} exceeds the largest "
                f"bucket {self._cache.max_batch_size}")
        if deadline_ms is None:
            from ..config import config

            deadline_ms = float(config.get("MXTPU_SERVING_DEADLINE_MS"))
        self._batcher = DynamicBatcher(
            self._run_batch, max_batch_size=max_batch_size,
            max_wait_ms=max_wait_ms, max_queue=max_queue,
            metrics=self.metrics, name=name, deadline_ms=deadline_ms)
        self._meter = telemetry.StepMeter(f"serving.{name}")
        self._maintenance = 0          # healthz unready while > 0
        self._maintenance_lock = threading.Lock()
        self._weights_version: object = 0   # bumped by publish_weights
        self._swap_lock = threading.Lock()  # serializes publishers only
        telemetry.maybe_start_http()
        # the exporter's /healthz aggregates every live server: a fleet
        # front door probes one port per process (docs/OBSERVABILITY.md)
        telemetry.register_health(f"serving.{self.name}", self.healthz)

    # -- construction from artifacts -----------------------------------------
    @classmethod
    def from_checkpoint(cls, block, params_path: str, ctx=None,
                        use_native: Optional[bool] = None,
                        **kwargs) -> "ModelServer":
        """Load ``params_path`` into ``block`` and serve it.

        ``params_path`` may be a native ``.params`` checkpoint (read
        through the C ABI ``mxio_params_*`` when the library is
        available — the same reader non-Python consumers use — else
        ``nd.load``; ``use_native=True`` makes a missing native library
        an error instead of a silent fallback) **or a sharded training
        checkpoint prefix/manifest** written by ``parallel.save_sharded``
        on any mesh: the ``param/`` + ``frozen/`` tensors are assembled
        at M=1 through the slice-planning reshard reader
        (``parallel/reshard.py``) — a multi-chip training checkpoint
        feeds the 1-chip serving tier directly, no export step,
        optimizer state never touched (docs/SERVING.md
        "Serving a training checkpoint"). The loaders are shared with
        :class:`~.decode.DecodeSession` via
        :func:`load_block_checkpoint`."""
        load_block_checkpoint(block, params_path, ctx=ctx,
                              use_native=use_native)
        return cls(block, **kwargs)

    @staticmethod
    def _sharded_prefix(params_path: str) -> Optional[str]:
        """Back-compat alias of the module-level :func:`_sharded_prefix`."""
        return _sharded_prefix(params_path)

    @classmethod
    def from_exported(cls, path: str, ctx=None, **kwargs) -> "ModelServer":
        """Serve ``HybridBlock.export_for_serving`` artifacts: rebuilds
        the graph as a ``SymbolBlock``, loads the checkpoint, applies the
        recorded buckets, and warms up every bucket for the recorded
        input signature."""
        from ..gluon.block import SymbolBlock

        with open(f"{path}-serving.json") as f:
            spec = json.load(f)
        if spec.get("version") != 1:
            raise ValueError(f"unsupported serving spec {path}-serving.json")
        if len(spec["inputs"]) != 1:
            raise NotImplementedError(
                "serving currently batches single-input models")
        base = os.path.dirname(os.path.abspath(path))
        block = SymbolBlock.imports(
            os.path.join(base, spec["symbol"]),
            [io["name"] for io in spec["inputs"]],
            os.path.join(base, spec["params"]), ctx=ctx)
        kwargs.setdefault("buckets", spec["buckets"])
        kwargs.setdefault("name", os.path.basename(path))
        srv = cls(block, **kwargs)
        io0 = spec["inputs"][0]
        srv.warmup(tuple(io0["features"]), io0["dtype"])
        return srv

    # -- dispatch -------------------------------------------------------------
    def _run_batch(self, batch: np.ndarray):
        # one telemetry step per executed batch: wall time, request
        # bytes moved H2D, recompile attribution to this model's site
        with self._meter.step(h2d_bytes=int(batch.nbytes),
                              detail=f"batch={batch.shape[0]}"):
            with profiler.scope(f"serving::{self.name}::batch"):
                out = self._cache(batch)
        if isinstance(out, tuple):
            return tuple(np.asarray(o) for o in out)
        return np.asarray(out)

    def submit(self, example) -> Future:
        """Enqueue one example (feature shape, no batch axis); resolves to
        the model output row (or tuple of rows for multi-output nets).
        Raises ``QueueFullError`` (backpressure) / ``ServerClosedError``.

        The serving front door for traces: a head-sampled request gets
        a root ``serving.request`` span here whose tree (queue →
        dispatch → depad) follows the request across the batcher's
        worker thread; the trace id rides the returned future as
        ``fut.trace_id``."""
        root = telemetry.trace.start("serving.request", model=self.name)
        if root is None:
            return self._batcher.submit(example)
        try:
            with telemetry.trace.use(root):
                fut = self._batcher.submit(example)
        except BaseException as exc:
            root.end(error=type(exc).__name__)
            raise
        fut.trace_id = root.trace_id
        fut.add_done_callback(
            lambda f: root.end(ok=f.exception() is None))
        return fut

    def predict(self, example, timeout: Optional[float] = 60.0):
        """Synchronous ``submit`` — one request through the batcher."""
        return self.submit(example).result(timeout=timeout)

    # -- lifecycle ------------------------------------------------------------
    def warmup(self, feature_shape: Tuple[int, ...], dtype="float32",
               buckets: Optional[Sequence[int]] = None,
               threads: Optional[int] = None) -> None:
        """Build every bucket for the given request signature before
        traffic arrives (cold-start compiles otherwise land on the first
        unlucky requests), and pin the accepted signature. Warm
        artifacts deserialize; cold buckets compile across a thread
        pool (``MXTPU_SERVING_WARMUP_THREADS``)."""
        self._cache.warmup(tuple(feature_shape), dtype, buckets,
                           threads=threads)
        self._batcher.expect_features(tuple(feature_shape), dtype)

    # -- persistent artifacts & weight hot-swap (ISSUE 14) --------------------
    def save_artifacts(self, directory: Optional[str] = None) -> int:
        """Persist every compiled executable so the next replica (or
        elastic-restart incarnation) warms by deserializing — see
        :meth:`BucketedExecutorCache.save_artifacts`."""
        return self._cache.save_artifacts(directory)

    def load_artifacts(self, directory: Optional[str] = None) -> int:
        """Eagerly load every guard-matching artifact of this model."""
        return self._cache.load_artifacts(directory)

    @property
    def weights_version(self):
        """The version tag of the live weights (0 until the first
        :meth:`publish_weights`)."""
        return self._weights_version

    def publish_weights(self, source, version=None,
                        allow_partial: bool = True) -> dict:
        """Publish a new weight version into the LIVE server — no drain,
        no recompile, zero dropped requests (the TF-Serving version-flip
        lifecycle, arXiv:1605.08695).

        ``source`` is a ``{structural_name: array}`` dict, a sharded
        training-checkpoint prefix from ANY mesh (streamed through the
        PR 7 slice reader, optimizer state never read), or a native
        ``.params`` path. The heavy work — reading the checkpoint,
        digesting, device_put of CHANGED params (unchanged ones alias
        the resident buffers zero-copy) — happens here, off the hot
        path, while traffic keeps flowing and ``healthz()`` stays
        ready. Only the final pointer flip runs inside a (microseconds-
        long) :meth:`maintenance` window, between batches: a batch in
        flight keeps the version it read, the next batch sees the new
        version whole — old-or-new, never a mix.

        Returns the swap stats (``aliased``/``updated`` param counts,
        ``seconds``, ``version``)."""
        with self._swap_lock:
            t0 = time.perf_counter()
            staged = _stage_publish(self._cache._params,
                                    self._cache._digests,
                                    self._cache.param_names, source,
                                    allow_partial, self.name)
            with self.maintenance():
                stats = self._cache.commit_params(staged)
                version = _resolve_version(self._weights_version,
                                           version)
                self._weights_version = version
            dt = time.perf_counter() - t0
        stats["version"] = version
        stats["seconds"] = round(dt, 4)
        _emit_swap_record(self.name, stats)
        return stats

    def resident_bytes(self) -> int:
        """Device bytes this server pins (params) — the registry's
        budget accounting."""
        return self._cache.param_bytes()

    def estimated_wait_s(self) -> float:
        """Current queue-wait estimate for a NEW request (0 when the
        backlog fits one batch) — what the registry's SLO admission
        control compares against the model's deadline."""
        return self._batcher.estimated_wait_s()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful: refuse new requests, answer everything queued —
        but never hang shutdown forever: after ``timeout`` seconds
        (default ``MXTPU_SERVING_DRAIN_TIMEOUT_S``) a wedged in-flight
        batch is force-closed with a warning, queued requests fail with
        ``ServerClosedError``, and the event is counted in
        ``mxtpu_serving_forced_close_total``. Returns True on a clean
        drain, False when it had to force-close."""
        if timeout is None:
            from ..config import config

            timeout = float(config.get("MXTPU_SERVING_DRAIN_TIMEOUT_S"))
        if self._batcher.drain(timeout):
            return True
        logging.getLogger("mxtpu.serving").warning(
            "drain of %s did not finish within %.1fs (queue_depth=%d); "
            "force-closing", self.name, timeout, self.queue_depth)
        self.metrics.observe_forced_close()
        self._batcher.close(join_timeout=0.5)
        return False

    def close(self) -> None:
        """Immediate: fail queued requests, stop the worker."""
        telemetry.unregister_health(f"serving.{self.name}")
        self._batcher.close()

    def maintenance(self):
        """Context manager flipping :meth:`healthz` unready for the
        duration (hot-restore / weight-swap window: the load balancer
        stops routing new traffic here while in-flight requests keep
        being served)."""
        server = self

        class _Maintenance:
            def __enter__(self):
                with server._maintenance_lock:
                    server._maintenance += 1
                return server

            def __exit__(self, *exc):
                with server._maintenance_lock:
                    server._maintenance -= 1
                return False

        return _Maintenance()

    def healthz(self) -> dict:
        """Readiness probe (the k8s-style health endpoint contract):
        ``ready`` is True only while the server is accepting and
        serving traffic — it flips False during drain/close and inside
        a :meth:`maintenance` window (hot-restore), so a front door can
        stop routing before requests start failing."""
        state = self._batcher._state
        with self._maintenance_lock:
            in_maintenance = self._maintenance > 0
        return {
            "ready": state == "running" and not in_maintenance,
            "state": state,
            "maintenance": in_maintenance,
            "model": self.name,
            "queue_depth": self.queue_depth,
            "compiled_buckets": len(self.compiled_signatures()),
            "weights_version": self._weights_version,
        }

    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, *exc) -> None:
        if exc and exc[0] is None:
            self.drain(timeout=30.0)
        self.close()

    # -- introspection --------------------------------------------------------
    @property
    def queue_depth(self) -> int:
        return self._batcher.queue_depth

    @property
    def buckets(self) -> Tuple[int, ...]:
        return self._cache.buckets

    def compiled_signatures(self):
        """(bucket, feature_shape, dtype) keys with a live executable."""
        return self._cache.compiled_signatures()

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["buckets"] = list(self.buckets)
        snap["compiled"] = [list(k) for k in self.compiled_signatures()]
        snap["weights_version"] = self._weights_version
        return snap
