"""Bucketed-shape executor cache — the serving-side analog of CachedOp.

Online traffic arrives with ragged batch sizes; compiling one XLA
executable per observed size would thrash the compile cache exactly when
the system is busiest. Instead, incoming batches are padded up to a
small set of batch-size buckets and ONE ahead-of-time-compiled
executable is kept per (model, bucket, feature signature):
``jax.jit(...).lower(...).compile()`` — AOT full-graph compilation in
the arXiv:1810.09868 style, done at warmup or on first miss, never
re-traced on the hot path.

Parameters are placed on device once at construction and stay resident;
every call moves only the request bytes (the Python twin of the C++
``Predictor`` residency fix, and TF-Serving's loaded-servable design,
arXiv:1605.08695). On non-CPU backends the padded input buffer is
donated to the executable so steady-state serving does not hold two
copies of the batch in HBM.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .. import telemetry
from .artifacts import (ArtifactStore, environment_fingerprint,
                        params_fingerprint)
from .metrics import ServingMetrics

logger = logging.getLogger("mxtpu.serving")

# powers of two up to a modest ceiling: small buckets keep padding waste
# low for singleton traffic, the 2x spacing keeps the executable count
# (and warmup compile time) logarithmic in max batch size
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32)


def warmup_thread_count(threads: Optional[int], n_tasks: int) -> int:
    """Resolve the warmup pool size: explicit ``threads``, else the
    ``MXTPU_SERVING_WARMUP_THREADS`` knob, with 0 meaning auto (one per
    core — XLA compilation releases the GIL, so first-boot warmup
    scales with cores), always clipped to the task count."""
    import os

    if threads is None:
        from ..config import config

        threads = int(config.get("MXTPU_SERVING_WARMUP_THREADS"))
    if threads <= 0:
        threads = os.cpu_count() or 1
    return max(1, min(int(threads), int(n_tasks)))


def _digest(arr: np.ndarray) -> str:
    """Content digest of one parameter value (the zero-copy aliasing
    test for weight hot-swap: equal digest => reuse the resident device
    buffer)."""
    return hashlib.sha1(np.ascontiguousarray(arr).tobytes()).hexdigest()


class _StagedSwap:
    """A fully-staged weight version: every changed parameter already
    on device, unchanged ones aliased to the live buffers. Built off
    the hot path by :meth:`BucketedExecutorCache.stage_params`;
    :meth:`~BucketedExecutorCache.commit_params` flips it in atomically
    (one attribute assignment — an in-flight batch keeps the list it
    already read, the next batch sees the new version whole)."""

    __slots__ = ("params", "digests", "stats")

    def __init__(self, params: List[Any], digests: List[str],
                 stats: Dict[str, int]):
        self.params = params
        self.digests = digests
        self.stats = stats


def stage_weight_swap(params: List[Any], digests: Optional[List[str]],
                      param_names: Optional[List[str]], new,
                      allow_partial: bool = True,
                      model: str = "model") -> _StagedSwap:
    """Stage a new weight version against a live parameter list — the
    aliasing core shared by :class:`BucketedExecutorCache` and the
    decode session. ``new`` is a ``{structural_name: array}`` dict
    (needs ``param_names``) or a full positional sequence; shapes and
    dtypes must match (the AOT executables are signature-frozen).
    Unchanged values (by content digest) alias the RESIDENT device
    buffer — zero-copy across versions; changed ones are device_put
    here, off the hot path, so the commit is a pure pointer flip."""
    if isinstance(new, dict):
        if param_names is None:
            raise ValueError(
                "named weight publish needs recorded structural param "
                "names (build the cache via from_block); pass a "
                "positional sequence instead")
        index = {n: i for i, n in enumerate(param_names)}
        unknown = sorted(k for k in new if k not in index)
        if unknown:
            raise ValueError(
                f"unknown parameter(s) {unknown[:5]} for model "
                f"{model}; served names: {param_names[:5]}...")
        if not allow_partial and len(new) != len(param_names):
            missing = sorted(set(param_names) - set(new))
            raise ValueError(
                f"partial weight publish refused; missing {missing[:5]}")
        items = [(index[k], v) for k, v in new.items()]
    else:
        seq = list(new)
        if len(seq) != len(params):
            raise ValueError(
                f"positional publish must cover all {len(params)} "
                f"params, got {len(seq)}")
        items = list(enumerate(seq))
    cur = list(params)
    if digests is None:
        # first swap: digest the live version once (D2H off the hot
        # path); afterwards digests update incrementally
        digests = [_digest(np.asarray(p)) for p in cur]
    digests = list(digests)
    aliased = updated = 0
    for i, v in items:
        arr = np.asarray(v)
        old = cur[i]
        if tuple(arr.shape) != tuple(old.shape) \
                or np.dtype(arr.dtype) != np.dtype(old.dtype):
            name = param_names[i] if param_names else f"#{i}"
            raise ValueError(
                f"param {name}: published {arr.dtype}{arr.shape} vs "
                f"served {old.dtype}{tuple(old.shape)} — AOT "
                f"executables are signature-frozen; an architecture "
                f"change needs a new server, not a weight swap")
        d = _digest(arr)
        if d == digests[i]:
            aliased += 1              # zero-copy: keep the device buffer
            continue
        cur[i] = jax.device_put(jnp.asarray(arr))
        digests[i] = d
        updated += 1
    stats = {"params": len(cur), "aliased": aliased, "updated": updated,
             "carried": len(cur) - aliased - updated}
    return _StagedSwap(cur, digests, stats)


def pure_method_runner(block) -> Tuple[Callable, List[Any]]:
    """``(run, params)`` — pure functional application of any Block
    method over injected parameter values via the ``_Trace`` mechanism
    (same tuple order as :func:`block_apply_fn`: callable first).

    ``run(method, pvals, *arrays)`` unwraps the NDArray outputs to a
    tuple of jax arrays; every call runs in inference mode
    (``training=False``: dropout off, BatchNorm uses running stats;
    aux-state writes are dropped, not replayed) with the matmul
    precision the parameter dtypes imply, and with ``next_key()`` routed
    to ``random.inference_key_provider`` — ``needs_rng`` ops draw-and-
    drop keys even in inference, and the default provider's trace-time
    ``fold_in`` would hoist the RNG root key into the lowered
    computation as a phantom const input. Shared by the whole serving
    tier: :func:`block_apply_fn` (batch forward) and the decode tier's
    prefill/decode appliers (``decode.py``)."""
    from .. import autograd
    from .. import random as _random
    from ..config import matmul_precision_for
    from ..gluon.block import _Trace
    from ..gluon.parameter import _trace
    from ..ndarray import NDArray
    from ..parallel.spmd import collect_params

    objs = collect_params(block)
    plist = list(objs.values())
    precision = matmul_precision_for(p.dtype for p in plist)
    nullkeys = _random.inference_key_provider()
    param_names = list(objs)   # exported on `run` below: named weight
    # hot-swap maps checkpoint tensors onto param POSITIONS, so the
    # names must come from the SAME collect_params walk the values were
    # zipped from — never a second traversal that could order differently

    def run(method, pvals, *arrays):
        param_map = {id(p): NDArray(v) for p, v in zip(plist, pvals)}
        trace = _Trace(param_map)
        _trace.stack.append(trace)
        try:
            with nullkeys, \
                    autograd._RecordingStateScope(False, False), \
                    jax.default_matmul_precision(precision):
                out = method(*[NDArray(a) for a in arrays])
        finally:
            _trace.stack.pop()
        leaves = jax.tree_util.tree_leaves(
            out, is_leaf=lambda o: isinstance(o, NDArray))
        return tuple(l._data if isinstance(l, NDArray) else jnp.asarray(l)
                     for l in leaves)

    run.param_names = param_names
    params = [p.data()._data for p in plist]
    return run, params


def block_apply_fn(block) -> Tuple[Callable, List[Any]]:
    """Build a pure ``apply_fn(param_values, x) -> outputs`` over a gluon
    ``Block`` plus the initial parameter values (jax arrays, structural-
    name order) — the single-forward special case of
    :func:`pure_method_runner`; the jitted graph is pure and the cache —
    not the Block — owns the device-resident copies."""
    run, params = pure_method_runner(block)

    def apply_fn(pvals, x):
        data = run(block.forward, pvals, x)
        return data[0] if len(data) == 1 else data

    apply_fn.param_names = run.param_names
    return apply_fn, params


class BucketedExecutorCache:
    """AOT-compiled executables keyed by (bucket, feature signature).

    ``apply_fn(params, x)`` must be pure, take the full parameter list as
    its first argument and a batch-leading array as its second, and
    return arrays whose leading axis is the batch axis (single array or
    tuple — de-padding slices every output to the true batch size).

    Two decode-tier extensions (ISSUE 12 — the prefill path buckets on
    SEQUENCE LENGTH with the token axis leading instead of on batch
    size, through this same cache):

    * ``pass_count=True`` — ``apply_fn(params, x, n)`` additionally
      receives the true un-padded leading count as a traced int32
      scalar (so e.g. prefill can read the last VALID position's
      logits without a per-length recompile).
    * ``depad=False`` — outputs are returned exactly as the executable
      produced them (bucket-padded); callers that consume whole padded
      planes (a KV-cache block write) or non-batch-leading outputs
      slice for themselves.
    """

    def __init__(self, apply_fn: Callable, params: Sequence[Any],
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 donate: Optional[bool] = None,
                 metrics: Optional[ServingMetrics] = None,
                 name: str = "model", pass_count: bool = False,
                 depad: bool = True,
                 artifact_dir: Optional[str] = None,
                 model_version: str = ""):
        self.name = name
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets}")
        self._apply = apply_fn
        # residency: one device_put at construction; executions reference
        # these arrays, no per-call host-to-device parameter traffic
        self._params = [jax.device_put(jnp.asarray(p)) for p in params]
        if donate is None:
            # XLA ignores donation on CPU (and warns); only donate where
            # the runtime can actually alias the buffer
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)
        self._pass_count = bool(pass_count)
        self._depad = bool(depad)
        self._execs = {}
        self._building: Dict[Tuple, threading.Event] = {}
        self._lock = threading.Lock()
        self.metrics = metrics if metrics is not None \
            else ServingMetrics(name)
        # weight hot-swap state: structural names (set by from_block) map
        # published checkpoints onto param positions; digests are lazy —
        # computed at the first stage_params (off the hot path), then
        # maintained incrementally
        self.param_names: Optional[List[str]] = None
        self._digests: Optional[List[str]] = None
        # the persistent artifact store (ISSUE 14): None when disabled
        # (no dir configured or explicit ""); the guard fingerprint is
        # what a stored artifact must match field-for-field before
        # deserialization
        if artifact_dir is None:
            from ..config import config

            artifact_dir = str(
                config.get("MXTPU_SERVING_ARTIFACT_DIR") or "")
        self._store = ArtifactStore(artifact_dir) if artifact_dir else None
        self._guard = dict(
            environment_fingerprint(), model=str(name),
            fingerprint=params_fingerprint(self._params),
            version=str(model_version), donate=self._donate,
            pass_count=self._pass_count)

    @classmethod
    def from_block(cls, block, **kwargs) -> "BucketedExecutorCache":
        kwargs.setdefault("name", getattr(block, "name", "model") or "model")
        apply_fn, params = block_apply_fn(block)
        cache = cls(apply_fn, params, **kwargs)
        # the names ride the runner (same collect_params walk the
        # param values were zipped from — the hot-swap ordering
        # invariant), not a second block traversal
        cache.param_names = list(apply_fn.param_names)
        return cache

    # -- bucket policy --------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket that holds ``n`` requests."""
        if n < 1:
            raise ValueError(f"batch size must be >= 1, got {n}")
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(
            f"batch of {n} exceeds the largest bucket {self.buckets[-1]}; "
            "raise buckets= or split the batch")

    @property
    def max_batch_size(self) -> int:
        return self.buckets[-1]

    def compiled_signatures(self) -> List[Tuple]:
        with self._lock:
            return sorted(self._execs)

    # -- compilation ----------------------------------------------------------
    def executable(self, bucket: int, feature_shape: Tuple[int, ...],
                   dtype) -> Any:
        """The AOT executable for one bucketed signature. On miss, the
        persistent artifact store is consulted first (deserialize — no
        XLA compile) and only then the compiler (with the result
        repersisted). Concurrent callers of the same signature build it
        once: one thread compiles, the rest wait — what lets
        :meth:`warmup` fan buckets across a thread pool."""
        if bucket not in self.buckets:
            raise ValueError(f"{bucket} is not one of {self.buckets}")
        dtype = jnp.dtype(dtype)
        key = (bucket, tuple(int(d) for d in feature_shape), dtype.name)
        while True:
            with self._lock:
                ex = self._execs.get(key)
                if ex is not None:
                    self.metrics.cache_hit()
                    return ex
                ev = self._building.get(key)
                if ev is None:
                    ev = self._building[key] = threading.Event()
                    break
            # another thread is building this signature: wait for it
            # (outside the lock), then re-check — its failure leaves the
            # key unbuilt and this thread takes over
            ev.wait()
        try:
            ex = self._build(key)
            with self._lock:
                self._execs[key] = ex
            return ex
        finally:
            with self._lock:
                self._building.pop(key, None)
            ev.set()

    def _logical_key(self, key: Tuple) -> Dict[str, Any]:
        bucket, feat, dtype_name = key
        return {"component": "bucket", "bucket": int(bucket),
                "features": tuple(feat), "dtype": dtype_name}

    def _build(self, key: Tuple) -> Any:
        """Artifact-or-compile for one missed signature (exactly one
        thread per key runs this)."""
        bucket, feat, dtype_name = key
        self.metrics.cache_miss()
        if self._store is not None:
            t0 = time.perf_counter()
            ex, reason = self._store.load(self.name,
                                          self._logical_key(key),
                                          self._guard)
            if ex is not None:
                self.metrics.observe_deserialize(time.perf_counter() - t0)
                return ex
            self.metrics.artifact_miss(
                refused=reason.startswith("refused"))
        telemetry.note_cache_miss(f"serving.{self.name}",
                                  detail=f"bucket={bucket}")
        t0 = time.perf_counter()
        with telemetry.attribute(f"serving.{self.name}",
                                 detail=f"bucket={bucket}"), \
                profiler.scope(f"serving::{self.name}::compile"):
            jitted = jax.jit(
                self._apply,
                donate_argnums=(1,) if self._donate else ())
            p_specs = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                       for p in self._params]
            x_spec = jax.ShapeDtypeStruct((bucket,) + key[1],
                                          jnp.dtype(dtype_name))
            if self._pass_count:
                n_spec = jax.ShapeDtypeStruct((), jnp.int32)
                ex = jitted.lower(p_specs, x_spec, n_spec).compile()
            else:
                ex = jitted.lower(p_specs, x_spec).compile()
        self.metrics.observe_compile(time.perf_counter() - t0)
        if self._store is not None:
            try:
                self._store.save(self.name, self._logical_key(key),
                                 self._guard, ex)
            except Exception as e:   # noqa: BLE001 — persistence is an
                # optimization; a full disk must not break serving
                logger.warning("artifact persist failed for %s %s: %s",
                               self.name, key, e)
        return ex

    def warmup(self, feature_shape: Tuple[int, ...], dtype="float32",
               buckets: Optional[Sequence[int]] = None,
               threads: Optional[int] = None) -> None:
        """Build every bucket for one input signature ahead of traffic —
        from the artifact store where warm, else compiled across a small
        thread pool (XLA compilation releases the GIL, so first-boot
        warmup scales with cores; ``MXTPU_SERVING_WARMUP_THREADS``)."""
        bs = tuple(buckets if buckets is not None else self.buckets)
        feat = tuple(feature_shape)
        c0, a0 = self.metrics.compiles, self.metrics.artifact_hits
        t0 = time.perf_counter()
        n = warmup_thread_count(threads, len(bs))
        if n <= 1:
            for b in bs:
                self.executable(b, feat, dtype)
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=n) as pool:
                futs = [pool.submit(self.executable, b, feat, dtype)
                        for b in bs]
                for f in futs:
                    f.result()          # re-raise the first failure
        dt = time.perf_counter() - t0
        self.metrics.observe_warmup(dt)
        telemetry.jsonl_emit({
            "kind": "registry", "event": "warmup", "model": self.name,
            "seconds": round(dt, 4), "buckets": len(bs),
            "compiles": self.metrics.compiles - c0,
            "deserialized": self.metrics.artifact_hits - a0,
            "threads": n})

    # -- persistent artifacts (ISSUE 14) --------------------------------------
    def save_artifacts(self, directory: Optional[str] = None) -> int:
        """Serialize every compiled executable into the artifact store
        (``directory`` overrides the configured one); returns the count
        written. A replica pointed at the same directory then warms by
        deserialization — seconds, not minutes, and zero XLA compiles
        under the armed recompile watchdog."""
        store = self._resolve_store(directory)
        with self._lock:
            snap = dict(self._execs)
        for key, ex in snap.items():
            store.save(self.name, self._logical_key(key), self._guard, ex)
        return len(snap)

    def load_artifacts(self, directory: Optional[str] = None) -> int:
        """Eagerly deserialize every stored artifact of this model whose
        guard fingerprint matches (no feature signature needed up
        front); returns the count loaded. Mismatched artifacts are
        skipped — the next :meth:`warmup` compiles and repersists."""
        store = self._resolve_store(directory)
        loaded = 0
        t_last = time.perf_counter()
        for logical, ex in store.load_all(self.name, self._guard):
            now = time.perf_counter()
            if logical.get("component") != "bucket":
                t_last = now
                continue
            bucket = int(logical.get("bucket", 0))
            if bucket not in self.buckets:
                t_last = now
                continue
            key = (bucket, tuple(logical.get("features", ())),
                   str(logical.get("dtype")))
            with self._lock:
                fresh = key not in self._execs
                if fresh:
                    self._execs[key] = ex
            if fresh:
                loaded += 1
                self.metrics.observe_deserialize(now - t_last)
            t_last = now
        return loaded

    def _resolve_store(self, directory: Optional[str]) -> ArtifactStore:
        if directory is not None:
            return ArtifactStore(directory)
        if self._store is None:
            raise RuntimeError(
                "no artifact store configured: pass artifact_dir= (or "
                "set MXTPU_SERVING_ARTIFACT_DIR), or pass an explicit "
                "directory")
        return self._store

    # -- live weight hot-swap (ISSUE 14) --------------------------------------
    def stage_params(self, new, allow_partial: bool = True) -> _StagedSwap:
        """Stage a new weight version OFF the hot path: ``new`` is a
        ``{structural_name: array}`` dict (requires :meth:`from_block`
        construction, which records the names) or a full positional
        sequence. Shapes and dtypes must match the live parameters —
        the AOT executables are signature-frozen, so a mismatch is a
        model-architecture change, not a weight update. Unchanged
        values (by content digest) alias the RESIDENT device buffer —
        zero-copy across versions; changed ones are device_put here,
        so :meth:`commit_params` is a pure pointer flip. (The staging
        core is :func:`stage_weight_swap`, shared with the decode
        session.)"""
        return stage_weight_swap(self._params, self._digests,
                                 self.param_names, new,
                                 allow_partial=allow_partial,
                                 model=self.name)

    def commit_params(self, staged: _StagedSwap) -> Dict[str, int]:
        """Flip the staged version live: one atomic assignment. A batch
        already dispatched keeps the parameter list it read; the next
        ``__call__`` sees the new version whole — old-or-new, never a
        mix. No executable is touched (same signatures), so the flip
        costs nothing and the recompile watchdog stays silent."""
        self._params = staged.params
        self._digests = staged.digests
        self.metrics.observe_swap()
        return dict(staged.stats)

    def swap_params(self, new, allow_partial: bool = True) -> Dict[str, int]:
        """``commit_params(stage_params(new))`` — the one-call form."""
        return self.commit_params(self.stage_params(new, allow_partial))

    def param_bytes(self) -> int:
        """Device bytes held by the resident parameters (the registry's
        budget accounting)."""
        return sum(int(p.nbytes) for p in self._params)

    def release(self) -> None:
        """Drop the resident parameters and every loaded executable: the
        owner is closed and nothing will be dispatched again. Without it
        they live until the cycle collector finds the owner (the cache
        holds its owner's bound ``apply_fn``)."""
        with self._lock:
            self._params = []
            self._execs = {}

    # -- execution ------------------------------------------------------------
    def __call__(self, x) -> Any:
        """Pad ``x`` up to its bucket, execute, slice outputs back down."""
        arr = np.asarray(x)
        if arr.ndim < 1:
            raise ValueError("input must have a leading batch axis")
        n = arr.shape[0]
        bucket = self.bucket_for(n)
        if n < bucket:
            pad = np.zeros((bucket - n,) + arr.shape[1:], arr.dtype)
            arr = np.concatenate([arr, pad], axis=0)
        ex = self.executable(bucket, arr.shape[1:], arr.dtype)
        with profiler.scope(f"serving::{self.name}::execute"):
            # fresh device array per call: required for donation, and the
            # only per-call H2D traffic (params are already resident)
            if self._pass_count:
                out = ex(self._params, jnp.asarray(arr),
                         jnp.asarray(n, jnp.int32))
            else:
                out = ex(self._params, jnp.asarray(arr))
        if not self._depad:
            return out
        # de-pad on the HOST: slicing the jax array (out[:n]) would
        # dispatch a jit-compiled slice per distinct (bucket, n) pair —
        # a slow drip of post-warmup compiles the recompile watchdog
        # rightly flags under ragged traffic. Callers consume numpy
        # rows anyway (the batcher fans results out per request).
        if isinstance(out, tuple):
            return tuple(np.asarray(o)[:n] for o in out)
        return np.asarray(out)[:n]
