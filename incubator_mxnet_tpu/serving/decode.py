"""KV-cache-resident autoregressive decode with continuous batching.

The serving half of the decoder-LLM workload (ISSUE 12): a
**prefill/decode split** over a slot-based, device-resident KV cache
(one ``[layers, slots, heads, rows, head_dim]`` array pair per group of
layers the block declares: ``max_len`` rows, or a ring of a window's
rows; a ``latent`` group one array, not a pair), in the full-AOT stance of
arXiv:1810.09868 / arXiv:1605.08695 — a small FIXED set of pre-compiled
executables with ALL dynamism carried as device-resident state or tiny
per-step host vectors, never as recompilation:

* **Prefill** compiles once per prompt-LENGTH bucket through the PR 1
  ``BucketedExecutorCache`` (token axis leading, ``pass_count`` so the
  true prompt length reaches the graph as a traced scalar): one causal
  forward over the padded prompt returning the greedy first token and
  the per-layer K/V planes.
* **Join** (one tiny executable per bucket) writes a prefilled plane
  into a slot's cache range at a TRACED slot index — any free slot, no
  recompile — donating the cache so the write aliases in place.
* **Decode** is ONE donated executable over the whole cache: every
  step advances EVERY slot one token; per-slot ``cache_len`` (a host
  int32 vector, H2D per step) makes the single program serve any mix
  of sequence ages.

How a row of the cache is addressed, read, attended, written and joined
(full groups and rings) is ``ops/kv_cache.py``'s; the block's
``serve_step`` and the join here call it.

**Continuous batching**: new sequences join the running batch at step
boundaries (the scheduler assigns free slots and prefills between decode
steps), finished sequences free their slot without disturbing
neighbours. The scheduler mirrors ``cache_len``/active state on the
host — it is fully determined by its own actions, so the only per-step
device→host traffic is the ``[slots]`` next-token vector the clients
need anyway. While every slot stays busy a second step is launched
before the first one's tokens are fetched (its ``tokens`` are the first
one's device output), so the device does not wait a host round trip per
token (docs/SERVING.md "A second step in flight").

Front-door semantics mirror :class:`~.server.ModelServer`: bounded-queue
backpressure (``QueueFullError.retry_after``), per-request
``deadline_ms`` shedding while queued, ``drain``/``close``/``healthz``;
tokens stream out per step through :class:`DecodeHandle`.
"""

from __future__ import annotations

import logging
import queue as _queue
import threading
import time
from collections import deque
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import profiler
from .. import telemetry
from ..ops import kv_cache
from .artifacts import (ArtifactStore, environment_fingerprint,
                        params_fingerprint)
from .batcher import (DeadlineExceededError, QueueFullError,
                      ServerClosedError)
from .executor_cache import (BucketedExecutorCache,
                             pure_method_runner)
from .metrics import DecodeMetrics, ServingMetrics

__all__ = ["DecodeHandle", "DecodeSession", "KVCache"]

logger = logging.getLogger("mxtpu.serving")

#: phases of the scheduler's turn-ledger records (docs/OBSERVABILITY.md
#: "Phases and the turn ledger"); ``sched`` and ``idle`` are the time
#: between two records, the rest lie inside the step or the prefill
_STEP_PHASES = ("sched", "idle", "h2d", "dispatch", "fence", "meter",
                "deliver", "finish")
_PREFILL_PHASES = ("dispatch", "join", "fence")

#: revision of the PROGRAM the join and decode executables are lowered
#: from, part of their artifact guard: the guard's other fields name the
#: compiler, the device and the shapes, and an artifact persisted by an
#: older program agrees with all of them. Bump it with any change to
#: what ``_decode_apply``/``join`` compute or take, so that such an
#: artifact is ``refused:program`` and recompiled, never deserialized
#: (1, unwritten: the step that re-stacked the cache; 2: in place; 3:
#: cache groups, the join takes ``[slot, true length]``; 4: GPT's cache
#: in the stored form, several heads side by side in a 128-lane row; 5:
#: both blocks and the join through ``ops/kv_cache.py``; 6: a full
#: group's attention by blocks of live rows on the TPU; 7: a group of
#: one tensor, the block kernel's operands by planes; 8: the step returns
#: its tokens once more, as the vector a step launched ahead takes; 9: a
#: GPT prefill's whole-prompt attention by the forward flash kernel
#: where ``ops/pallas_attention.py``'s rule sends it).
_PROGRAM_REVISION = 9


def default_prefill_buckets(max_len: int) -> Tuple[int, ...]:
    """Prompt-length buckets from ``MXTPU_DECODE_BUCKETS`` clipped to the
    cache capacity (a bucket longer than ``max_len`` could never be
    joined into a slot)."""
    from ..config import config

    raw = str(config.get("MXTPU_DECODE_BUCKETS"))
    buckets = tuple(sorted({int(b) for b in raw.split(",") if b.strip()}))
    clipped = tuple(b for b in buckets if b <= max_len)
    if not clipped:
        clipped = (max_len,)
    return clipped


class KVCache:
    """Device-resident per-slot cache planes, stacked arrays
    ``[Lg, S, H, rows, D]`` per GROUP of layers: a K and V pair, or the
    one tensor of a ``latent`` group (``kv_cache.tensors``).

    ``groups`` is what the served block declares (``cache_groups``): per
    group ``layers``, ``heads``, ``rows``, ``head_dim`` and ``kind``
    (``"full"``, ``"ring"`` or ``"latent"``: ``ops/kv_cache.py`` holds the
    rules). ``heads`` and ``head_dim`` are the STORED form, which is the
    block's to choose and nothing here looks inside: a K/V head a row
    (the data-built decoder's 8 heads of 128), several heads side by
    side in one row of 128 lanes (``gpt.py``: GPT-2 XL's 25 heads of 64
    are ``heads`` 13, ``head_dim`` 128), or latent attention's one
    compressed row a position that every head reads (``heads`` 1,
    ``head_dim`` the row's stored width). The block's ``serve_prefill``
    returns its planes, and its ``serve_step`` reads and writes the
    cache, in that same form. A group of no layer holds nothing.
    ``arrays`` is the flat list the executables take and return: group
    by group, k then v or the one; ``kinds`` and ``shapes`` name each
    group, ``array_kinds`` and ``specs()`` each array. A ``state`` group
    (a recurrent layer's state, the same size at every length) declares
    ``layers``, ``width``, ``state``, ``taps`` and its own ``dtype``
    instead: two arrays ``[Lg, S, n, E]`` that a step replaces where it
    lies (``kv_cache.layout``, ``advance``); it has no rows, so ``rows``,
    ``live_rows``, ``read`` and ``max_len`` leave it out, ``nbytes``
    counts it and ``state_bytes`` is its own.

    Owned by a :class:`DecodeSession`; rebound on every donated
    join/decode dispatch. Both executables only ever update the stacked
    arrays in place (the join one slot's prompt range, the decode step
    one row per slot and layer after every layer has read its plane:
    ``kv_cache.join``, ``kv_cache.write``), so with donation on the TPU
    their outputs alias their inputs and the cache is updated where it
    lies; without donation (the CPU default) each dispatch copies it
    once. Freed slots are not zeroed — their ranges are
    overwritten by the next prefill and never read in between
    (``cache_len`` guards every attention read)."""

    def __init__(self, groups, slots: int, dtype="float32"):
        self.groups = [dict(g) for g in groups if int(g["layers"])]
        self.dtype = jnp.dtype(dtype)
        self._slots = int(slots)
        self.kinds = [g["kind"] for g in self.groups]
        layouts = [kv_cache.layout(g, self._slots, self.dtype)
                   for g in self.groups]
        #: per group the shape of its arrays (a ``state`` group's first)
        self.shapes = [arrays[0][0] for arrays in layouts]
        #: per array of ``arrays``: its group's kind
        self.array_kinds = [kind for kind, arrays in zip(self.kinds, layouts)
                            for _ in arrays]
        self._specs = [jax.ShapeDtypeStruct(shape, dt)
                       for arrays in layouts for shape, dt in arrays]
        self.arrays = [jax.device_put(jnp.zeros(s.shape, s.dtype))
                       for s in self._specs]
        # which groups a step attends by blocks of live rows: the rule's
        # answer for the platform the arrays lie on, asked once
        on_tpu = {d.platform for a in self.arrays for d in a.devices()} \
            == {"tpu"}
        # the groups of ROWS (every kind but ``state``): layers, rows,
        # whether read by blocks, and the bytes a position of a layer
        # holds as stored, every tensor
        self._row_groups = [
            (l, r, on_tpu and kv_cache.blocked(r, kind),
             h * d * kv_cache.tensors(kind) * self.dtype.itemsize)
            for kind, (l, _, h, r, d) in (
                pair for pair in zip(self.kinds, self.shapes)
                if pair[0] != "state")]
        # bytes of recurrent state a slot holds, every layer and tensor
        self._slot_state_bytes = sum(
            int(np.prod(s.shape)) // self._slots * s.dtype.itemsize
            for kind, s in zip(self.array_kinds, self._specs)
            if kind == "state")

    # a cache of one K/V group (GPT-2's) reads as the one array pair it is
    @property
    def shape(self) -> Tuple[int, ...]:
        (shape,) = self.shapes
        return shape

    @property
    def k(self):
        k, _ = self.arrays
        return k

    @property
    def v(self):
        _, v = self.arrays
        return v

    def specs(self) -> list:
        """``jax.ShapeDtypeStruct`` of every array, in ``arrays`` order."""
        return list(self._specs)

    @property
    def slots(self) -> int:
        return self._slots

    @property
    def max_len(self) -> int:
        """The most positions a slot's rows hold (a ``state`` group holds
        a sequence of any length)."""
        return max((r for _, r, _, _ in self._row_groups), default=0)

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in self._specs)

    @property
    def rows(self) -> int:
        """Rows the cache holds in all: layers x slots x rows, summed
        over the groups of rows (a row is a position of a layer, whatever
        it stores there)."""
        return sum(l * self._slots * r for l, r, _, _ in self._row_groups)

    def live_rows(self, lens) -> int:
        """Of those, the rows that hold a position of a sequence whose
        cached length is in ``lens`` (one entry per active slot)."""
        lens = np.asarray(lens, np.int64)
        return int(sum(l * np.minimum(lens, r).sum()
                       for l, r, _, _ in self._row_groups))

    def read(self, cache_len) -> Tuple[int, int]:
        """The rows a decode step's attention READS for slots whose
        cached lengths (before the step's token) are ``cache_len``, and
        the bytes they hold as stored. Rows: whole blocks up to each
        length and the new row where a group goes by blocks, every row
        of the plane where it is read whole (``kv_cache.fetched_rows``),
        summed over the groups of rows; never under ``live_rows`` of
        ``cache_len + 1``. Bytes: a row's ``heads x head_dim`` values in
        every tensor of its group (K and V, or a latent group's one)."""
        cache_len = np.asarray(cache_len, np.int64)
        rows = [int(l * kv_cache.fetched_rows(cache_len, r, by_blocks).sum())
                for l, r, by_blocks, _ in self._row_groups]
        return sum(rows), sum(n * b for n, (_, _, _, b)
                              in zip(rows, self._row_groups))

    def state_bytes(self, active: int) -> int:
        """Bytes of recurrent state a decode step reads AND writes for
        ``active`` slots, as stored: each one's state of every ``state``
        group once in, once out (0 without such a group)."""
        return 2 * int(active) * self._slot_state_bytes


_DONE = object()


class DecodeHandle:
    """Streaming result of one decode request.

    Iterate to receive generated token ids as the session emits them
    (one per decode step; the first arrives with prefill)::

        for tok in handle:           # blocks per token
            ...
        toks = handle.result(30.0)   # or wait for the full list

    Errors (shed deadline, closed server, failed step) surface from both
    the iterator and ``result``."""

    def __init__(self):
        self._q: _queue.Queue = _queue.Queue()
        self._done = threading.Event()
        self._tokens: List[int] = []
        self._exc: Optional[BaseException] = None
        self._cb_lock = threading.Lock()
        self._callbacks: List = []
        #: trace id of the request's sampled root span (None unsampled)
        self.trace_id: Optional[str] = None

    # -- session side -------------------------------------------------------
    def _put(self, tok: int) -> None:
        if self._done.is_set():
            return
        self._tokens.append(int(tok))
        self._q.put(int(tok))

    def _finish(self) -> None:
        if not self._done.is_set():
            self._done.set()
            self._q.put(_DONE)
            self._fire_callbacks()

    def _fail(self, exc: BaseException) -> None:
        if not self._done.is_set():
            self._exc = exc
            self._done.set()
            self._q.put(_DONE)
            self._fire_callbacks()

    def _fire_callbacks(self) -> None:
        with self._cb_lock:
            cbs, self._callbacks = self._callbacks, []
        for fn in cbs:
            try:
                fn(self)
            except Exception:              # noqa: BLE001 — callbacks
                pass                       # never break the scheduler

    # -- client side --------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self) -> int:
        item = self._q.get()
        if item is _DONE:
            self._q.put(_DONE)       # keep the stream terminal
            if self._exc is not None:
                raise self._exc
            raise StopIteration
        return item

    def done(self) -> bool:
        return self._done.is_set()

    def add_done_callback(self, fn) -> None:
        """Future-style completion hook: ``fn(handle)`` runs when the
        sequence finishes or fails (immediately if already done). Keep
        callbacks tiny — they run on the scheduler thread. Gives
        ``DecodeHandle`` the same completion surface as the batch tier's
        ``concurrent.futures.Future``, so the open-loop load harness
        drives both without per-request waiter threads."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        fn(self)

    def exception(self, timeout: Optional[float] = None):
        """Future-style: block until done; the failure (or None)."""
        if not self._done.wait(timeout):
            raise TimeoutError("decode request not finished in time")
        return self._exc

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block until the sequence finishes; the full generated-token
        list (prompt not included)."""
        if not self._done.wait(timeout):
            raise TimeoutError("decode request not finished in time")
        if self._exc is not None:
            raise self._exc
        return list(self._tokens)

    @property
    def tokens(self) -> List[int]:
        """Tokens generated so far (live view; grows per step)."""
        return list(self._tokens)


class _Request:
    # ``trace`` is the request's root span (or None when unsampled) and
    # ``t_submit_p`` its perf_counter twin of t_submit: the trace
    # context crosses the scheduler thread hop ON the request object
    __slots__ = ("prompt", "max_new", "eos_id", "t_submit", "t_submit_p",
                 "handle", "trace")

    def __init__(self, prompt: np.ndarray, max_new: int,
                 eos_id: Optional[int]):
        self.prompt = prompt
        self.max_new = max_new
        self.eos_id = eos_id
        self.t_submit = time.monotonic()
        self.t_submit_p = time.perf_counter()
        self.handle = DecodeHandle()
        self.trace = None

    def _end_trace(self, **attrs) -> None:
        if self.trace is not None:
            self.trace.end(**attrs)


class _Active:
    __slots__ = ("req", "generated", "t_admitted", "t0_steps")

    def __init__(self, req: _Request):
        self.req = req
        self.generated = 0
        self.t_admitted = time.monotonic()
        self.t0_steps: Optional[float] = None   # first decode-step start


class _Flight:
    """A decode step on the device whose tokens are not yet on the host:
    the ``(slot, _Active)`` pairs it was launched for (a slot may change
    hands before it lands), their cached lengths before it, its open
    turn and meter scope, its outputs (``out``: tokens and counters, the
    one fetch; ``fed``: the tokens as the next step takes them), and
    whether it was dispatched while the step before it was unfetched."""

    __slots__ = ("turn", "scope", "t_launch", "pairs", "lens", "out",
                 "fed", "ahead", "first_steps")

    def __init__(self, turn, scope, t_launch, pairs, lens, ahead,
                 first_steps):
        self.turn, self.scope, self.t_launch = turn, scope, t_launch
        self.pairs, self.lens, self.ahead = pairs, lens, ahead
        self.first_steps = first_steps
        self.out = self.fed = None


class DecodeSession:
    """Continuous-batching autoregressive serving over one decoder block.

    ``block`` is a gluon block that declares what it is served with
    (``cache_groups``/``serve_prefill``/``serve_step``/``step_counters``:
    docs/SERVING.md "What a block declares";
    :class:`~..gluon.model_zoo.gpt.GPTDecoder` and
    :class:`~..gluon.model_zoo.decoder.HybridDecoder` do), parameters
    initialized. Greedy
    decoding (argmax) — the contract that makes the output stream
    bit-exact against the full-sequence forward oracle.

    Usage::

        sess = mx.serving.DecodeSession(net, max_slots=8, max_len=256)
        sess.warmup()                      # compile the fixed executable set
        h = sess.submit(prompt_ids, max_new_tokens=64, eos_id=0)
        for tok in h:                      # streams one token per step
            ...
        sess.drain(); sess.close()
    """

    def __init__(self, block, max_slots: Optional[int] = None,
                 max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_queue: int = 64, name: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 donate: Optional[bool] = None,
                 max_new_tokens: Optional[int] = None,
                 artifact_dir: Optional[str] = None,
                 model_version: str = ""):
        from ..config import config

        self.name = name or (getattr(block, "name", "") or "gpt")
        if max_slots is None:
            max_slots = int(config.get("MXTPU_DECODE_SLOTS"))
        if max_len is None:
            max_len = int(config.get("MXTPU_DECODE_MAX_LEN"))
        block_max = int(getattr(block, "max_length", max_len))
        if max_len > block_max:
            max_len = block_max     # position table bounds the cache
        if max_slots < 1 or max_len < 2:
            raise ValueError(f"need max_slots >= 1 and max_len >= 2, got "
                             f"{max_slots}/{max_len}")
        if max_new_tokens is None:
            max_new_tokens = int(config.get("MXTPU_DECODE_MAX_NEW_TOKENS"))
        if deadline_ms is None:
            deadline_ms = float(config.get("MXTPU_SERVING_DEADLINE_MS"))
        self.max_len = int(max_len)
        self.max_slots = int(max_slots)
        self.max_queue = int(max_queue)
        self.default_max_new = int(max_new_tokens)
        self.deadline_ms = None if deadline_ms <= 0 else float(deadline_ms)
        if donate is None:
            donate = jax.default_backend() != "cpu"
        self._donate = bool(donate)

        self._run, self._params = pure_method_runner(block)
        self._block = block
        buckets = tuple(prefill_buckets) if prefill_buckets is not None \
            else default_prefill_buckets(self.max_len)
        bad = [b for b in buckets if b > self.max_len]
        if bad:
            raise ValueError(f"prefill buckets {bad} exceed max_len="
                             f"{self.max_len}")
        self._prefill = BucketedExecutorCache(
            self._prefill_apply, self._params, buckets=buckets,
            donate=donate, name=f"{self.name}.prefill",
            metrics=ServingMetrics(f"{self.name}.prefill"),
            pass_count=True, depad=False, artifact_dir=artifact_dir,
            model_version=model_version)
        # same collect_params walk the param values were zipped from
        # (pure_method_runner exports it) — the hot-swap name→position
        # mapping must never come from a second traversal
        self._param_names = list(self._run.param_names)
        self._prefill.param_names = self._param_names

        dtype = self._params[0].dtype
        self._kv = KVCache(block.cache_groups(self.max_len), max_slots,
                           dtype=dtype)
        self._counters = tuple(block.step_counters)
        self.metrics = DecodeMetrics(self.name)
        self.metrics.set_capacity(max_slots, self._kv.nbytes)
        self._site = f"decode.{self.name}"
        self._meter = telemetry.StepMeter(self._site)
        self._flops: Optional[float] = None

        self._joins: dict = {}
        self._dec_ex = None
        self._compile_lock = threading.Lock()
        # persistent artifacts for the join + decode executables (the
        # prefill cache manages its own); the engine metrics carry
        # their compile-vs-deserialize split under <name>.engine
        if artifact_dir is None:
            artifact_dir = str(
                config.get("MXTPU_SERVING_ARTIFACT_DIR") or "")
        self._store = ArtifactStore(artifact_dir) if artifact_dir else None
        self._guard = dict(
            environment_fingerprint(), model=self.name,
            fingerprint=params_fingerprint(self._params),
            version=str(model_version), donate=self._donate,
            program=_PROGRAM_REVISION, kv_shape=tuple(self._kv.shapes),
            kv_dtype="/".join(sorted({s.dtype.name
                                      for s in self._kv.specs()})))
        self.engine_metrics = ServingMetrics(f"{self.name}.engine")
        # live weight hot-swap: publishers stage off the hot path; the
        # scheduler flips the staged version in BETWEEN steps
        self._pending_swap: Optional[dict] = None
        self._param_digests: Optional[List[str]] = None
        self._weights_version: object = 0
        self._swap_lock = threading.Lock()

        # host mirrors of the device cache state — fully determined by
        # scheduler actions, so they are inputs each step, never fetched;
        # ``_cache_len`` (and ``_Active.generated``) count a step from
        # its dispatch, ``_tokens`` holds what the last fetch brought
        self._cache_len = np.zeros((max_slots,), np.int32)
        self._tokens = np.zeros((max_slots,), np.int32)
        self._slots: List[Optional[_Active]] = [None] * max_slots
        self._free = deque(range(max_slots))
        self._pending: deque = deque()
        self._cv = threading.Condition()
        self._state = "running"
        # the scheduler's turn ledger: the next step's record is open
        # from the moment the last record closed (``_t_mark``); what the
        # scheduler does in between is that step's ``sched``, what it
        # waits with nothing to run its ``idle``
        self._turn = telemetry.trace.Turn(self._site, _STEP_PHASES)
        self._t_mark = time.perf_counter()
        # the last step's tokens and record while they wait for the next
        # step to be on the device (``_hand_on``); the scheduler's alone
        self._held: Optional[tuple] = None
        # the step launched ahead of the last fetch, and when that fetch
        # returned (a step's time starts there, or at its own dispatch)
        self._flying: Optional[_Flight] = None
        self._t_fetch = 0.0
        self._worker = threading.Thread(
            target=self._loop, name=f"mxtpu-decode-{self.name}",
            daemon=True)
        self._worker.start()
        telemetry.maybe_start_http()
        telemetry.register_health(f"decode.{self.name}", self.healthz)

    # -- construction from artifacts -----------------------------------------
    @classmethod
    def from_checkpoint(cls, block, params_path: str, ctx=None,
                        use_native: Optional[bool] = None,
                        **kwargs) -> "DecodeSession":
        """Load ``params_path`` into ``block`` and serve decode from it.
        Accepts everything :meth:`ModelServer.from_checkpoint` accepts —
        native ``.params`` checkpoints and sharded training-checkpoint
        manifests from ANY mesh (train multi-chip, decode single-chip,
        no export step): the loaders are shared
        (``server.load_block_checkpoint``)."""
        from .server import load_block_checkpoint

        load_block_checkpoint(block, params_path, ctx=ctx,
                              use_native=use_native)
        return cls(block, **kwargs)

    # -- the compiled executable set -----------------------------------------
    def _prefill_apply(self, pvals, tokens, n):
        """(first greedy token, each cache group's k/v planes
        ``[Lg, H, Lb, D]``) of one padded prompt; ``n`` is the TRUE
        prompt length (traced), so the greedy read indexes the last
        valid position without a per-length executable."""
        last, *planes = self._run(self._block.serve_prefill, pvals, tokens,
                                  n)
        return (jnp.argmax(last, axis=-1).astype(jnp.int32), *planes)

    def _decode_apply(self, pvals, *args):
        """``(pvals, *caches, cache_len, tokens)`` -> ``(out, *caches,
        next_tokens)``: ``out`` holds the greedy next token of every
        slot and, behind them, the block's ``step_counters`` (one fetch
        brings both); ``next_tokens`` (S,) int32 is those tokens alone,
        never fetched and never donated: the ``tokens`` of a step
        launched before this one's ``out`` is on the host."""
        *caches, cache_len, tokens = args
        logits, *rest = self._run(self._block.serve_step, pvals, tokens,
                                  cache_len, *caches)
        out = nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if self._counters:
            out = jnp.concatenate([nxt, rest.pop(0).astype(jnp.int32)])
        return (out, *rest, nxt)

    def _load_or_compile(self, logical: dict, compile_fn):
        """Artifact-or-compile for one engine executable (caller holds
        ``_compile_lock``): a guard-matching artifact deserializes (no
        XLA compile — the cold-start win), anything else compiles and
        repersists. Accounting lands in ``engine_metrics``."""
        self.engine_metrics.cache_miss()
        if self._store is not None:
            t0 = time.perf_counter()
            ex, reason = self._store.load(self.name, logical, self._guard)
            if ex is not None:
                self.engine_metrics.observe_deserialize(
                    time.perf_counter() - t0)
                return ex
            self.engine_metrics.artifact_miss(
                refused=reason.startswith("refused"))
        telemetry.note_cache_miss(f"decode.{self.name}",
                                  detail=str(logical.get("component")))
        t0 = time.perf_counter()
        with profiler.scope(f"decode::{self.name}::compile"):
            ex = compile_fn()
        self.engine_metrics.observe_compile(time.perf_counter() - t0)
        if self._store is not None:
            try:
                self._store.save(self.name, logical, self._guard, ex)
            except Exception as e:   # noqa: BLE001 — persistence only
                logger.warning("artifact persist failed for %s %s: %s",
                               self.name, logical, e)
        return ex

    def _join_exec(self, bucket: int):
        """The per-bucket cache-join executable: ``kv_cache.join`` of
        each group's prefilled ``[Lg, H, Lb, D]`` plane into slot
        ``slot``'s cache range (a TRACED slot index: one executable
        serves every slot) for a prompt of TRUE length ``n`` (a ``state``
        group's operand is one slot's state, with no bucket axis, and
        replaces the slot's). ``at`` is ``[slot, n]``. Cache operands are
        donated."""
        ex = self._joins.get(bucket)
        if ex is not None:
            return ex
        with self._compile_lock:
            ex = self._joins.get(bucket)
            if ex is not None:
                return ex
            kinds = self._kv.array_kinds
            n_arrays = len(kinds)

            def compile_join():
                def join(*args):
                    caches, planes = args[:n_arrays], args[n_arrays:-1]
                    slot, n = args[-1][0], args[-1][1]
                    return tuple(
                        kv_cache.join(cache, plane, slot, n, kind)
                        for cache, plane, kind in zip(caches, planes, kinds))

                planes = [jax.ShapeDtypeStruct(
                    kv_cache.plane_shape(spec.shape, kind, bucket),
                    spec.dtype) for spec, kind in zip(self._kv.specs(),
                                                      kinds)]
                at = jax.ShapeDtypeStruct((2,), jnp.int32)
                jitted = jax.jit(join, donate_argnums=tuple(range(n_arrays))
                                 if self._donate else ())
                return jitted.lower(*self._kv.specs(), *planes,
                                    at).compile()

            ex = self._load_or_compile(
                {"component": "join", "bucket": int(bucket)},
                compile_join)
            self._joins[bucket] = ex
            return ex

    def _lower_decode(self):
        """The decode step lowered for this session's shapes: every
        cache array donated (where the session donates), so the compiled
        program's output caches alias its inputs."""
        caches = self._kv.specs()
        vec = jax.ShapeDtypeStruct((self.max_slots,), jnp.int32)
        jitted = jax.jit(self._decode_apply,
                         donate_argnums=tuple(range(1, 1 + len(caches)))
                         if self._donate else ())
        p_specs = [jax.ShapeDtypeStruct(p.shape, p.dtype)
                   for p in self._params]
        return jitted.lower(p_specs, *caches, vec, vec)

    def _decode_exec(self):
        """THE decode executable — built once (deserialized where a
        warm artifact exists); serves every mix of sequence ages and
        slot occupancies with zero recompiles."""
        if self._dec_ex is not None:
            return self._dec_ex
        with self._compile_lock:
            if self._dec_ex is not None:
                return self._dec_ex
            self._dec_ex = self._load_or_compile(
                {"component": "decode"},
                lambda: self._lower_decode().compile())
            return self._dec_ex

    def _decode_flops(self) -> Optional[float]:
        """Cost-analysis FLOPs of the decode step (free — the executable
        is already compiled) for the online MFU gauge."""
        if self._flops is None:
            self._flops = telemetry.flops_of_compiled(self._dec_ex) or 0.0
        return self._flops or None

    def decode_cost_analysis(self) -> Optional[float]:
        """XLA cost-analysis FLOPs of ONE decode step (whole-cache; all
        slots) — compiles the decode executable if needed. None where
        the backend exposes no cost model."""
        self._decode_exec()
        return self._decode_flops()

    def prefill_cost_analysis(self, bucket: int) -> Optional[float]:
        """Cost-analysis FLOPs of one prefill at ``bucket`` tokens."""
        return telemetry.flops_of_compiled(
            self._prefill.executable(bucket, (), "int32"))

    def warmup(self) -> None:
        """Build the ENTIRE executable set ahead of traffic: every
        prefill bucket, every join, and the decode program —
        deserialized from the artifact store where warm, compiled (and
        persisted) where not. After this, steady-state serving performs
        zero compiles — the recompile contract tests/test_decode.py
        pins under the armed watchdog."""
        t0 = time.perf_counter()
        c0 = (self._prefill.metrics.compiles
              + self.engine_metrics.compiles)
        a0 = (self._prefill.metrics.artifact_hits
              + self.engine_metrics.artifact_hits)
        self._prefill.warmup((), "int32")
        for b in self._prefill.buckets:
            self._join_exec(b)
        self._decode_exec()
        dt = time.perf_counter() - t0
        self.engine_metrics.observe_warmup(dt)
        telemetry.jsonl_emit({
            "kind": "registry", "event": "warmup", "model": self.name,
            "seconds": round(dt, 4),
            "buckets": len(self._prefill.buckets),
            "compiles": (self._prefill.metrics.compiles
                         + self.engine_metrics.compiles) - c0,
            "deserialized": (self._prefill.metrics.artifact_hits
                             + self.engine_metrics.artifact_hits) - a0})

    def save_artifacts(self, directory: Optional[str] = None) -> int:
        """Persist the full executable set (prefill buckets, joins, the
        decode program) so the next replica warms by deserializing;
        returns the artifact count written."""
        if directory is None and self._store is None:
            raise RuntimeError(
                "no artifact store configured: pass artifact_dir= (or "
                "set MXTPU_SERVING_ARTIFACT_DIR), or pass an explicit "
                "directory")
        store = self._store if directory is None \
            else ArtifactStore(directory)
        # the prefill cache shares the same artifact_dir, so its store
        # is configured exactly when ours is
        n = self._prefill.save_artifacts(directory)
        with self._compile_lock:
            joins = dict(self._joins)
            dec = self._dec_ex
        for bucket, ex in joins.items():
            store.save(self.name, {"component": "join",
                                   "bucket": int(bucket)},
                       self._guard, ex)
            n += 1
        if dec is not None:
            store.save(self.name, {"component": "decode"},
                       self._guard, dec)
            n += 1
        return n

    # -- live weight hot-swap (ISSUE 14) --------------------------------------
    @property
    def weights_version(self):
        """Version tag of the live weights (0 until the first
        :meth:`publish_weights`)."""
        return self._weights_version

    def publish_weights(self, source, version=None,
                        allow_partial: bool = True,
                        timeout: Optional[float] = 30.0) -> dict:
        """Publish a new weight version into the LIVE session — no
        drain, no recompile, nothing dropped. The checkpoint read
        (dict / sharded prefix through the PR 7 slice reader / native
        ``.params``), content digesting, and device_put of changed
        params all happen HERE, on the publisher's thread, while
        decoding continues; the staged version is then flipped in by
        the scheduler BETWEEN decode steps — every prefill and every
        step runs under exactly one version. In-flight sequences keep
        their KV cache (computed under the old weights) and continue
        under the new ones from the next step; sequences finished
        before the flip are pure old-version streams, sequences
        admitted after it pure new-version streams.

        Blocks until the scheduler applies the swap (``timeout``);
        returns the swap stats. On timeout the staged swap is WITHDRAWN
        (a publish reported failed can never flip in later)."""
        from .server import (_emit_swap_record, _resolve_version,
                             _stage_publish)

        with self._swap_lock:
            t0 = time.perf_counter()
            staged = _stage_publish(self._params, self._param_digests,
                                    self._param_names, source,
                                    allow_partial, self.name)
            version = _resolve_version(self._weights_version, version)
            applied = threading.Event()
            swap = {"staged": staged, "version": version,
                    "applied": applied}
            with self._cv:
                if self._state != "running":
                    raise ServerClosedError(
                        f"decode session is {self._state}; not "
                        "accepting a weight publish")
                self._pending_swap = swap
                self._cv.notify_all()
            if not applied.wait(timeout):
                with self._cv:
                    if self._pending_swap is swap:
                        # withdraw: the scheduler never saw it, and a
                        # failed publish must not flip in later
                        self._pending_swap = None
                        raise TimeoutError(
                            "weight swap staged but not applied in "
                            "time (is the scheduler thread alive?)")
                # lost the race: the scheduler applied it after the
                # wait expired — the publish DID land; fall through
            with self._cv:
                if self._state == "closed" \
                        and self._weights_version != version:
                    raise ServerClosedError(
                        "decode session closed before the staged swap "
                        "was applied")
            dt = time.perf_counter() - t0
        stats = dict(staged.stats)
        stats["version"] = version
        stats["seconds"] = round(dt, 4)
        self.engine_metrics.observe_swap()
        _emit_swap_record(self.name, stats)
        return stats

    def _apply_pending_swap_locked(self) -> None:
        """Flip a staged weight version live (scheduler thread, under
        ``_cv``, between decode steps — the step-boundary atomicity
        contract)."""
        swap = self._pending_swap
        if swap is None:
            return
        self._pending_swap = None
        self._params = swap["staged"].params
        self._param_digests = swap["staged"].digests
        # the prefill cache holds its own parameter list (it is a
        # standalone BucketedExecutorCache): flip it at the SAME step
        # boundary so a prefill and the decode steps that follow it can
        # never run under different versions
        self._prefill._params = swap["staged"].params
        self._prefill._digests = swap["staged"].digests
        self._weights_version = swap["version"]
        swap["applied"].set()

    def resident_bytes(self) -> int:
        """Device bytes this session pins (params + the KV cache) —
        the registry's budget accounting."""
        return (sum(int(p.nbytes) for p in self._params)
                + int(self._kv.nbytes))

    def estimated_wait_s(self) -> float:
        """Queue-wait estimate for a NEW request (0 while a slot is
        free and nothing queues) — the registry's SLO admission
        signal."""
        with self._cv:
            if self._free and not self._pending:
                return 0.0
            return self._retry_after_locked()

    # -- client side ---------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None) -> DecodeHandle:
        """Enqueue one prompt (1-D int token ids). The sequence joins the
        running batch at the next step boundary with a free slot; tokens
        stream out through the returned handle (greedy; generation stops
        at ``eos_id`` (delivered), ``max_new_tokens``, or cache
        capacity). Raises ``QueueFullError`` (backpressure) /
        ``ServerClosedError``."""
        arr = np.asarray(prompt, np.int32).reshape(-1)
        n = arr.shape[0]
        if n < 1:
            raise ValueError("empty prompt")
        if n > self._prefill.max_batch_size:
            raise ValueError(
                f"prompt of {n} tokens exceeds the largest prefill bucket "
                f"{self._prefill.max_batch_size}; raise prefill_buckets=")
        if n >= self.max_len:
            raise ValueError(f"prompt of {n} tokens leaves no cache room "
                             f"(max_len={self.max_len})")
        max_new = self.default_max_new if max_new_tokens is None \
            else int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        req = _Request(arr, max_new, eos_id)
        with self._cv:
            if self._state != "running":
                raise ServerClosedError(
                    f"decode session is {self._state}; not accepting")
            if len(self._pending) >= self.max_queue:
                self.metrics.observe_reject()
                raise QueueFullError(
                    f"decode queue full ({self.max_queue} waiting)",
                    retry_after=self._retry_after_locked())
            self._pending.append(req)
            self._cv.notify_all()
        self.metrics.observe_submit()
        # request root span minted at the front door (caller thread);
        # the context rides the _Request across the scheduler hop
        req.trace = telemetry.trace.start("decode.request",
                                          model=self.name, prompt_len=n)
        if req.trace is not None:
            req.handle.trace_id = req.trace.trace_id
        return req.handle

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 timeout: Optional[float] = 300.0) -> List[int]:
        """Synchronous :meth:`submit` — the full generated-token list."""
        return self.submit(prompt, max_new_tokens, eos_id).result(timeout)

    def _retry_after_locked(self) -> float:
        # a slot frees after ~max_new steps; estimate from the step EMA
        ema = self._meter.ema_seconds or 0.01
        waves = (len(self._pending) + self.max_slots - 1) \
            // max(1, self.max_slots)
        return max(0.01, waves * ema * max(1, self.default_max_new) * 0.25)

    # -- scheduler ------------------------------------------------------------
    @property
    def active_slots(self) -> int:
        with self._cv:
            return sum(1 for s in self._slots if s is not None)

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._pending)

    def _loop(self) -> None:
        while True:
            admits, shed = self._wait_for_work()
            if admits is None:
                self._flying = None     # its streams were failed by close()
                self._hand_on()
                return
            for req in shed:
                self.metrics.observe_shed()
                with self._cv:
                    retry_after = self._retry_after_locked()
                req._end_trace(error="DeadlineExceededError", shed=True)
                req.handle._fail(DeadlineExceededError(
                    f"request exceeded its {self.deadline_ms:.1f} ms "
                    "deadline while queued", retry_after=retry_after))
            for slot, req in admits:
                try:
                    self._prefill_into(slot, req)
                except Exception as exc:   # noqa: BLE001 — fail the caller
                    req._end_trace(error=type(exc).__name__)
                    req.handle._fail(exc)
                    with self._cv:
                        # idempotent recovery: close() may have already
                        # nulled the slot AND refilled _free underneath
                        # the in-flight prefill — only free what is
                        # still ours
                        if self._slots[slot] is not None:
                            self._slots[slot] = None
                            self._free.append(slot)
            if self.active_slots or self._flying is not None:
                try:
                    self._step()
                except Exception as exc:   # noqa: BLE001 — worker survives
                    logger.exception("decode step failed; failing the "
                                     "active sequences")
                    self._flying = None     # the step behind it: discarded
                    self._hand_on()     # what the step before it gave
                    with self._cv:
                        active = [(i, s) for i, s in enumerate(self._slots)
                                  if s is not None]
                        for i, s in active:
                            self._slots[i] = None
                            self._free.append(i)
                    for _, s in active:
                        s.req._end_trace(error=type(exc).__name__)
                        s.req.handle._fail(exc)

    def _wait_for_work(self):
        """Block until there is something to do. Returns
        ``(admissions, shed)`` — admissions is None when the worker
        should exit (closed, or drained dry). A staged weight swap is
        applied here, on the scheduler thread between decode steps —
        the step-boundary atomicity the hot-swap contract needs (every
        prefill and every decode step runs under exactly one weight
        version; the KV cache carries over, so an in-flight sequence
        continues under the new weights next step)."""
        with self._cv:
            while True:
                self._apply_pending_swap_locked()
                if self._state == "closed":
                    return None, []
                n_active = sum(1 for s in self._slots if s is not None)
                if n_active or self._flying is not None \
                        or (self._pending and self._free):
                    break
                if self._state == "draining" and not self._pending:
                    return None, []
                t_wait = time.perf_counter()
                self._turn.add("sched", t_wait - self._t_mark)
                with self._turn.phase("idle"):
                    self._cv.wait(timeout=0.25)
                self._t_mark = time.perf_counter()
            shed: List[_Request] = []
            admits: List[Tuple[int, _Request]] = []
            now = time.monotonic()
            if self.deadline_ms is not None:
                # sweep expired requests EVERY wakeup, not only when a
                # slot is free: while every slot is busy with long
                # generations, expired entries must still fail fast AND
                # stop counting against max_queue (the batch tier's
                # batcher sheds each flush cycle the same way). The
                # queue is FIFO over submit times, so only the front
                # can be expired.
                cutoff = now - self.deadline_ms / 1e3
                while self._pending and self._pending[0].t_submit < cutoff:
                    shed.append(self._pending.popleft())
            # (a step still in flight lands first: only an ``eos_id``
            # ending, found one step late, frees a slot underneath one)
            while self._pending and self._free and self._flying is None:
                req = self._pending.popleft()
                slot = self._free.popleft()
                self._slots[slot] = _Active(req)
                admits.append((slot, req))
            return admits, shed

    def _prefill_into(self, slot: int, req: _Request) -> None:
        """Admit one sequence at a step boundary: prefill its prompt
        through the length-bucketed cache, join the K/V planes into the
        slot's cache range, emit the first greedy token."""
        n = int(req.prompt.shape[0])
        bucket = self._prefill.bucket_for(n)
        root = req.trace
        turn = telemetry.trace.Turn(self._site, _PREFILL_PHASES)
        t0 = time.perf_counter()
        self._turn.add("sched", t0 - self._t_mark)
        with telemetry.attribute(self._site, detail=f"prefill len={n}"):
            with turn.phase("dispatch"):
                first, *planes = self._prefill(req.prompt)
            t_pf1 = time.perf_counter()
            with turn.phase("join"):
                join = self._join_exec(bucket)
                # a numpy vector: the executable takes it as it is, and
                # building a jax array from a list would compile
                self._kv.arrays = list(join(
                    *self._kv.arrays, *planes,
                    np.asarray([slot, n], np.int32)))
            with turn.phase("fence"):
                # the step before's tokens, under the prefill and not
                # behind it: as late after their fence as a step hands on
                self._hand_on()
                first_tok = int(first)                # the D2H fence
        t_fence = time.perf_counter()
        self._t_mark = t_fence
        dt = t_fence - t0
        now = time.monotonic()
        if root is not None:
            # contiguous perf-clock segments of the TTFT critical path:
            # queue (submit -> admission), prefill (dispatch -> device
            # done for the bucketed prompt pass), join (K/V splice +
            # the D2H fence that makes the first token host-visible)
            telemetry.trace.record(root, "queue", req.t_submit_p, t0,
                                   slot=slot)
            telemetry.trace.record(root, "prefill", t0, t_pf1,
                                   bucket=bucket)
            telemetry.trace.record(root, "join", t_pf1, t_fence)
        with self._cv:
            st = self._slots[slot]
            if st is None:                 # closed underneath the prefill
                return
            self._cache_len[slot] = n
            self._tokens[slot] = first_tok
        st.generated = 1
        queue_wait = st.t_admitted - req.t_submit
        self.metrics.observe_admit(queue_wait, dt)
        turn.close(t0, dt, kind="prefill", bucket=bucket, prompt_len=n,
                   queue_wait_s=queue_wait)
        self.metrics.observe_first_token(now - req.t_submit)
        if root is not None:
            # the measured TTFT on the SAME perf clock the segments use
            root.annotate(ttft_ms=round((t_fence - req.t_submit_p) * 1e3,
                                        3))
        telemetry.trace.note_latency(f"decode.{self.name}",
                                     now - req.t_submit)
        self.metrics.observe_prefill_token()
        req.handle._put(first_tok)
        # capacity cannot end a sequence here: submit() rejects prompts
        # with n >= max_len, so there is always room for one decode step
        done = first_tok == req.eos_id or st.generated >= req.max_new
        if done:
            self._finish_slot(slot)
        self.metrics.observe_slots(self.active_slots)

    def _step(self) -> None:
        """Land one decode step: the one launched ahead of the last
        fetch, or one dispatched here. Every step advances every occupied
        slot (free slots compute too — their rows are ignored and their
        writes land in freed space) through the ONLY hot-path executable:
        no shape in it depends on which slots are live or how old their
        sequences are.

        While :meth:`_stays_full` holds the NEXT step is dispatched
        before this one's tokens are fetched, its ``tokens`` this one's
        device output: the device goes from step to step and the host's
        round trip lies under its time. Otherwise the device waits for
        this thread between a fetch and the next dispatch, so only what
        that dispatch needs is done there (:meth:`_land`) and the tokens
        go to their callers under the next step, or an admission's
        prefill (:meth:`_hand_on`)."""
        flight, self._flying = self._flying, None
        if flight is None:
            flight = self._launch()
        if self._stays_full(flight):
            self._flying = self._launch(flight)
        self._land(flight)

    def _stays_full(self, flight: _Flight) -> bool:
        """Whether the step after ``flight`` may be launched ahead of
        ``flight``'s fetch, from what the scheduler can see: every slot
        is busy with the stream ``flight`` was launched for and none of
        them ends with it by ``max_new_tokens`` or cache capacity (both
        counted from the dispatch), the session is running, no weight
        swap is staged. Then no admission and no swap could happen at
        the boundary that is skipped: launching ahead delays neither. A
        stream that ends by its ``eos_id`` is found when the token
        arrives, one step late (:meth:`_land` drops what the step behind
        computed for it)."""
        with self._cv:
            return (self._state == "running" and self._pending_swap is None
                    and len(flight.pairs) == self.max_slots
                    and all(self._slots[i] is st
                            and st.generated < st.req.max_new
                            and self._cache_len[i] < self.max_len
                            for i, st in flight.pairs))

    def _launch(self, before: Optional[_Flight] = None) -> _Flight:
        """Dispatch one decode step and start its tokens' copy to the
        host. ``before`` is the step still in flight that this one is
        launched ahead of: its device token vector is this one's
        ``tokens`` (it was launched for the same streams, so nothing has
        to be mixed in); without it the host's. The mirrors count the
        step from here: +1 row and +1 token for each slot it is launched
        for."""
        # this step takes the open turn; the next one opens here, so a
        # step that fails leaves nothing of itself in a later record
        turn, self._turn = self._turn, telemetry.trace.Turn(
            self._site, _STEP_PHASES)
        t0 = time.perf_counter()
        turn.add("sched", t0 - self._t_mark)
        first_steps: List[_Request] = []
        with self._cv:
            pairs = [(i, s) for i, s in enumerate(self._slots)
                     if s is not None]
            cache_len = self._cache_len.copy()
            tokens = self._tokens.copy() if before is None else before.fed
            for i, st in pairs:
                self._cache_len[i] += 1
                st.generated += 1
                if st.t0_steps is None:
                    st.t0_steps = t0
                    if st.req.trace is not None:
                        first_steps.append(st.req)
        h2d_bytes = cache_len.nbytes + (tokens.nbytes if before is None else 0)
        flight = _Flight(
            turn, self._meter.step(
                h2d_bytes=h2d_bytes, detail=f"active={len(pairs)}",
                flops_fn=self._decode_flops, turn=turn, defer=True),
            t0, pairs, cache_len[[i for i, _ in pairs]],
            int(before is not None), first_steps)
        with flight.scope:
            ex = self._decode_exec()
            with turn.phase("h2d"):
                cache_len = jnp.asarray(cache_len)
                if before is None:
                    tokens = jnp.asarray(tokens)
            with turn.phase("dispatch"):
                flight.out, *self._kv.arrays, flight.fed = ex(
                    self._params, *self._kv.arrays, cache_len, tokens)
                flight.out.copy_to_host_async()
        self._t_mark = time.perf_counter()
        return flight

    def _land(self, flight: _Flight) -> None:
        """Fetch ``flight``'s tokens and do what the next dispatch waits
        for: the host's token mirror, the slots that finished. A step's
        time runs from the later of its dispatch and the fetch before it
        to its own fetch: what it added to every stream.

        A token goes only where the slot still holds the stream the step
        was launched for. With a step in flight behind this one nothing
        ends here by length (:meth:`_stays_full`), the tokens are handed
        on at once (the device is busy) and a stream whose ``eos_id``
        arrived is un-counted the token that step computes for it."""
        turn, behind = flight.turn, self._flying
        with turn.phase("fence"):
            self._hand_on()     # what the step before it left held
            toks = np.asarray(flight.out).tolist()    # the D2H fence
        t1 = time.perf_counter()
        t0, self._t_fetch = max(flight.t_launch, self._t_fetch), t1
        live: List[Tuple[_Active, int]] = []
        finished: List[int] = []
        with turn.phase("deliver"), self._cv:
            for i, st in flight.pairs:
                if self._slots[i] is not st:
                    continue    # ended a step ago, or closed underneath us
                tok = toks[i]
                self._tokens[i] = tok
                live.append((st, tok))
                if tok == st.req.eos_id:
                    if behind is not None:
                        st.generated -= 1
                    finished.append(i)
                elif behind is None and (
                        st.generated >= st.req.max_new
                        or self._cache_len[i] >= self.max_len):
                    finished.append(i)
        dropped = len(flight.pairs) - len(live)
        flight.scope.commit(t0, t1, ahead=flight.ahead, dropped=dropped)
        self.metrics.observe_step(len(flight.pairs), t1 - t0, len(live),
                                  flight.ahead, dropped)
        self._held = (flight, t0, t1, toks, live)
        if behind is not None:
            with behind.turn.phase("fence"):    # under ITS device time
                self._hand_on()
        with turn.phase("finish"):
            if finished:
                self._hand_on()     # a last token before its stream ends
            for i in finished:
                self._finish_slot(i)
        self._t_mark = time.perf_counter()
        self.metrics.observe_slots(self.active_slots)

    def _hand_on(self) -> None:
        """Hand the held step's tokens to their callers and write its
        ledger record. The block's own per-step integers ride behind the
        tokens; the cache's live rows are the scheduler's to know (the
        rows the step read: each slot's length with its new token; the
        rows its attention fetched for them: whole blocks, or planes)."""
        held, self._held = self._held, None
        if held is None:
            return
        flight, t0, t1, toks, live = held
        for st, tok in live:
            st.req.handle._put(tok)
        active, lens = len(flight.pairs), flight.lens
        for req in flight.first_steps:
            telemetry.trace.record(req.trace, "first_step", t0, t1,
                                   active=active)
        read_rows, read_bytes = self._kv.read(lens)
        state = {"state_bytes": self._kv.state_bytes(active)} \
            if "state" in self._kv.kinds else {}
        flight.turn.close(t0, t1 - t0, active=active, **state,
                          kv_live_rows=self._kv.live_rows(lens + 1),
                          kv_read_rows=read_rows, kv_read_bytes=read_bytes,
                          kv_rows=self._kv.rows,
                          **dict(zip(self._counters, toks[self.max_slots:])))

    def _finish_slot(self, slot: int) -> None:
        """Retire a finished sequence: resolve its handle, free the slot
        (neighbouring slots keep decoding untouched), emit the
        per-request JSONL record."""
        with self._cv:
            st = self._slots[slot]
            if st is None:
                return
            # occupancy INCLUDING this request: the record describes the
            # load the request ran under, not the state it left behind
            n_active = sum(1 for s in self._slots if s is not None)
            self._slots[slot] = None
            self._free.append(slot)
            # reset the mirrors: a capacity-finished slot would otherwise
            # keep cache_len == max_len and feed an out-of-table position
            # index into every later step (harmless only via XLA's clamp
            # semantics — don't rely on it)
            self._cache_len[slot] = 0
            self._tokens[slot] = 0
            self._cv.notify_all()
        st.req.handle._finish()
        if st.req.trace is not None:
            if st.t0_steps is not None:
                telemetry.trace.record(st.req.trace, "steps",
                                       st.t0_steps, time.perf_counter(),
                                       tokens=st.generated)
            st.req._end_trace(new_tokens=st.generated,
                              slots_active=n_active)
        self.metrics.observe_finish()
        now = time.monotonic()
        telemetry.jsonl_emit({
            "kind": "decode", "model": self.name,
            "prompt_len": int(st.req.prompt.shape[0]),
            "new_tokens": st.generated,
            "queue_wait_ms": round(
                (st.t_admitted - st.req.t_submit) * 1e3, 3),
            "wall_ms": round((now - st.req.t_submit) * 1e3, 3),
            "slots_active": n_active,
        })

    # -- lifecycle ------------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful: refuse new requests, finish every queued and active
        sequence; after ``timeout`` (default
        ``MXTPU_SERVING_DRAIN_TIMEOUT_S``) force-close. True on a clean
        drain."""
        if timeout is None:
            from ..config import config

            timeout = float(config.get("MXTPU_SERVING_DRAIN_TIMEOUT_S"))
        with self._cv:
            if self._state == "running":
                self._state = "draining"
            self._cv.notify_all()
        self._worker.join(timeout)
        if not self._worker.is_alive():
            return True
        logger.warning(
            "drain of decode session %s did not finish within %.1fs "
            "(queue_depth=%d active=%d); force-closing", self.name,
            timeout, self.queue_depth, self.active_slots)
        self.close(join_timeout=0.5)
        return False

    def close(self, join_timeout: float = 5.0) -> None:
        """Immediate: fail queued and active requests, stop the worker."""
        telemetry.unregister_health(f"decode.{self.name}")
        with self._cv:
            self._state = "closed"
            pending = list(self._pending)
            self._pending.clear()
            active = [s for s in self._slots if s is not None]
            self._slots = [None] * self.max_slots
            self._free = deque(range(self.max_slots))
            swap, self._pending_swap = self._pending_swap, None
            if swap is not None:
                swap["applied"].set()   # waiting publisher fails fast
            self._cv.notify_all()
        for req in pending:
            req._end_trace(error="ServerClosedError")
            req.handle._fail(ServerClosedError("decode session closed"))
        for st in active:
            st.req._end_trace(error="ServerClosedError")
            st.req.handle._fail(ServerClosedError("decode session closed"))
        self._worker.join(timeout=join_timeout)
        if not self._worker.is_alive():
            # a closed session pins no device memory: the cache, the
            # parameter references and the loaded executables go now, not
            # when the cycle collector finds the session (the prefill
            # cache holds a bound method of it); whoever still holds the
            # block's parameters holds the weights
            with self._compile_lock:
                self._joins, self._dec_ex = {}, None
            self._kv.arrays = []
            self._params = []
            self._prefill.release()

    def __enter__(self) -> "DecodeSession":
        return self

    def __exit__(self, *exc) -> None:
        if exc and exc[0] is None:
            self.drain(timeout=30.0)
        self.close()

    # -- introspection --------------------------------------------------------
    def healthz(self) -> dict:
        """Readiness probe with the ModelServer contract: ``ready`` only
        while accepting traffic."""
        with self._cv:
            state = self._state
            depth = len(self._pending)
            active = sum(1 for s in self._slots if s is not None)
        return {
            "ready": state == "running",
            "state": state,
            "model": self.name,
            "queue_depth": depth,
            "slots": {"active": active, "total": self.max_slots},
            "compiled": {
                "prefill_buckets": len(self._prefill.compiled_signatures()),
                "joins": len(self._joins),
                "decode": self._dec_ex is not None,
            },
        }

    @property
    def prefill_buckets(self) -> Tuple[int, ...]:
        return self._prefill.buckets

    def stats(self) -> dict:
        snap = self.metrics.snapshot()
        snap["prefill_buckets"] = list(self._prefill.buckets)
        snap["prefill_cache"] = self._prefill.metrics.snapshot()[
            "executor_cache"]
        snap["engine_cache"] = self.engine_metrics.snapshot()[
            "executor_cache"]
        snap["warmup_seconds"] = self.engine_metrics.warmup_seconds
        snap["weights_version"] = self._weights_version
        snap["max_len"] = self.max_len
        snap["kv_shapes"] = list(self._kv.shapes)
        if self._meter.ema_seconds is not None:
            snap["step_ema_ms"] = self._meter.ema_seconds * 1e3
        return snap
