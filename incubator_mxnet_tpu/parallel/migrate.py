"""In-ICI device→device live resharding (``mxtpu.migrate``).

PR 7's reshard engine solved the *file→device* half of arXiv:2112.01075
("Memory-efficient array redistribution through portable collective
communication"): any checkpoint restores onto any mesh through planned
byte-range reads. This module is the *device→device* half: live arrays
flip between two shardings — a different mesh shape over the same
chips, a ZeRO-3 training layout to a replicated serving layout, a
regrown pod after an elastic shrink — WITHOUT the host-gather +
restore round-trip those flips used to pay.

Three layers, mirroring ``reshard.py`` but over live device buffers:

* **plan** — per tensor, intersect the source sharding's per-device
  shard boxes with the destination's (the same slice-plan math the
  reshard engine runs over manifest boxes): every (dest device, piece)
  whose holder set excludes the destination device is bytes-on-wire,
  every piece is one slice/concat step. The schedule is static, so the
  accounting is exact the way ``zero_bench``'s is — this box cannot
  measure ICI, the plan can.
* **execute** — all leaves that share one device assignment lower into
  ONE donated jitted executable (identity bodies with the destination
  as ``out_shardings``; XLA's SPMD partitioner emits the
  ``collective-permute`` / ``all-to-all`` / slice+concat schedule the
  plan describes, inside ICI). The executable is cached per
  (src-layout, dst-layout, topology, quant) — repeated identical flips
  are compile-free — and persisted through the serving artifact store
  when ``MXTPU_SERVING_ARTIFACT_DIR`` is configured, so even a fresh
  process deserializes instead of compiling. Arrays whose source and
  destination span *different* device sets (an elastic grow/shrink)
  take a per-leaf ``jax.device_put`` — still direct device-to-device
  transfers, zero host bytes, just not one program.
* **quantize** (``MXTPU_MIGRATE_QUANT=int8``) — eligible floating
  tensors ship as per-block int8 codes + f32 scales (the
  ``collectives._quantize_rows`` wire format, EQuARX-style,
  arXiv:2506.17615): the resharding collective moves 1 byte/value
  instead of 4, at a bounded per-block error (``max|block| / 254``).
  The default ``none`` path is bit-exact.

Peak host bytes of a migration is **zero** by construction — no numpy
buffer is ever materialized; ``stats["peak_host_bytes"]`` records the
invariant.

Telemetry (``mxtpu_migrate_*``): migrations, planned ops, wire bytes
(and the fp32 bytes the unquantized schedule would move), wall time;
one ``kind: "migrate"`` JSONL record per call
(``tools/telemetry_report.py`` prints the section and diffs the keys).

Consumers: ``SPMDTrainer.apply_zero_placement`` (restore-time ZeRO
re-placement), ``resilience.elastic.ElasticRunner`` (rebuild without a
checkpoint round-trip), and the serving flip
(:func:`serving_weights` → ``ModelServer``/``ModelRegistry``/
``DecodeSession.publish_weights``). docs/SCALING.md "Live resharding"
and docs/RESILIENCE.md "Elastic grow-back" describe the end-to-end
behavior.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from .collectives import _dequantize_rows, _quantize_rows
from .reshard import _intersect, _normalize_index

__all__ = ["MigrateError", "last_stats", "migrate_arrays",
           "migrate_trainer_state", "plan_arrays", "serving_weights"]

_log = logging.getLogger("mxtpu.migrate")

MIGRATE_QUANTS = ("none", "int8")


class MigrateError(ValueError):
    """A device→device migration cannot be planned or executed (host
    arrays, shape/structure mismatch, deleted/donated source buffers).
    Callers with a slower correct path — the checkpoint restore, a
    per-tensor ``device_put`` — fall back on this."""


def _cfg(name: str):
    from ..config import config

    return config.get(name)


def resolve_quant(explicit: Optional[str]) -> str:
    quant = str(_cfg("MXTPU_MIGRATE_QUANT") or "none") \
        if explicit is None else str(explicit)
    quant = quant.strip().lower() or "none"
    if quant not in MIGRATE_QUANTS:
        raise ValueError(
            f"migrate quant {quant!r} not in {MIGRATE_QUANTS}")
    return quant


# ---------------------------------------------------------------------------
# layout fingerprints + the slice plan
# ---------------------------------------------------------------------------
def _device_ids(sh) -> Tuple[int, ...]:
    """The sharding's device assignment as a flat id tuple (execution
    order — two shardings compose into one executable only when these
    match exactly)."""
    mesh = getattr(sh, "mesh", None)
    if mesh is not None and hasattr(mesh, "devices"):
        return tuple(int(d.id) for d in mesh.devices.flat)
    da = getattr(sh, "_device_assignment", None)
    if da is not None:
        return tuple(int(d.id) for d in da)
    return tuple(sorted(int(d.id) for d in sh.device_set))


def _sharding_fp(sh) -> Tuple:
    """Structural fingerprint of one sharding — the layout half of the
    executable cache key."""
    mesh = getattr(sh, "mesh", None)
    mesh_fp = tuple((str(a), int(s)) for a, s in mesh.shape.items()) \
        if mesh is not None and hasattr(mesh, "shape") else ()
    return (type(sh).__name__, _device_ids(sh), mesh_fp,
            str(getattr(sh, "spec", sh)))


def _leaf_boxes(sh, shape) -> "OrderedDict[Any, Tuple]":
    """device -> absolute shard box for one sharding (the live-array
    analog of a manifest entry's shard listings)."""
    idx = sh.devices_indices_map(tuple(shape))
    return OrderedDict(
        (dev, _normalize_index(index, shape)) for dev, index in idx.items())


def _plan_leaf(shape, src_sh, dst_sh) -> Dict[str, Any]:
    """The slice plan of one tensor: per destination device, how many
    elements arrive from non-local source shards (``remote_elems``) and
    how many slice/concat steps the schedule needs (``ops`` — local
    pieces included: they are slice+concat work even without wire
    traffic). Reuses ``reshard._intersect`` over the live shardings'
    boxes instead of manifest boxes."""
    src_map = _leaf_boxes(src_sh, shape)
    dst_map = _leaf_boxes(dst_sh, shape)
    holders: "OrderedDict[Tuple, set]" = OrderedDict()
    for dev, box in src_map.items():
        holders.setdefault(box, set()).add(int(dev.id))
    ops = 0
    remote_elems: Dict[int, int] = {}
    for dev, bd in dst_map.items():
        did = int(dev.id)
        for sb, hs in holders.items():
            inter = _intersect(sb, bd) if bd else ()
            if inter is None:
                continue
            elems = 1
            for lo, hi in inter:
                elems *= hi - lo
            ops += 1
            if did not in hs:
                remote_elems[did] = remote_elems.get(did, 0) + elems
    return {"ops": ops, "remote_elems": remote_elems,
            "dest_shards": len(dst_map)}


def _name_of(path) -> str:
    parts = []
    for entry in path:
        key = getattr(entry, "key", getattr(entry, "idx", None))
        if isinstance(key, tuple):
            parts.extend(str(k) for k in key)
        else:
            parts.append(str(key))
    return "/".join(parts) if parts else "<leaf>"


def _leaf_names(flat) -> List[str]:
    """One stable, unique stats name per leaf (shared by the planner
    and the executor so their per-tensor entries line up)."""
    names: List[str] = []
    seen = set()
    for i, (path, _leaf) in enumerate(flat):
        name = _name_of(path)
        if name in seen:
            name = f"{name}#{i}"
        seen.add(name)
        names.append(name)
    return names


# ---------------------------------------------------------------------------
# the executable cache (per src-layout x dst-layout x topology x quant)
# ---------------------------------------------------------------------------
_EXEC_CACHE: Dict[Tuple, Any] = {}
_EXEC_LOCK = threading.Lock()


def _artifact_store():
    """The persistent serving artifact store when configured — a
    migrate executable is one more AOT artifact, so a fresh process
    repeats a known flip by DESERIALIZING (ISSUE 14 machinery)."""
    from ..serving.artifacts import ArtifactStore

    root = str(_cfg("MXTPU_SERVING_ARTIFACT_DIR") or "")
    return ArtifactStore(root) if root else None


def _compile_group(key: Tuple, leaf_specs: List[Tuple], dst_shs: List,
                   qflags: List[bool], block: int, donate: bool,
                   site: str) -> Tuple[Any, bool]:
    """The donated executable moving one group of leaves (all sharing
    one device assignment): identity bodies with the destination
    ``out_shardings`` — XLA lowers exactly the planned collective
    schedule — and the int8 quantize→exchange→dequantize pipeline for
    flagged leaves. Returns ``(executable, compiled_now)``."""
    from .. import telemetry

    with _EXEC_LOCK:
        ex = _EXEC_CACHE.get(key)
    if ex is not None:
        return ex, False

    logical = {"component": "migrate",
               "sig": hashlib.sha1(repr(key).encode()).hexdigest()}
    store = _artifact_store()
    guard = None
    if store is not None:
        try:
            from ..serving.artifacts import environment_fingerprint

            guard = dict(environment_fingerprint(), donate=bool(donate),
                         block=int(block))
            loaded, _reason = store.load("__migrate__", logical, guard)
            if loaded is not None:
                with _EXEC_LOCK:
                    _EXEC_CACHE[key] = loaded
                return loaded, False
        except Exception:
            store = None

    def fn(xs):
        outs = []
        for x, dst, qf in zip(xs, dst_shs, qflags):
            if qf:
                rows = x.size // block
                c2 = x.astype(jnp.float32).reshape(rows, block)
                payload, scales, _deq = _quantize_rows(c2, "int8", block)
                # the codes — 1 byte/value — are what crosses the wire;
                # the per-block scales replicate (rows * 4 bytes)
                codes = jax.lax.with_sharding_constraint(
                    payload.reshape(x.shape), dst)
                scales = jax.lax.with_sharding_constraint(
                    scales, NamedSharding(dst.mesh, PartitionSpec()))
                deq = _dequantize_rows(codes.reshape(rows, block),
                                       scales, "int8", block, block)
                outs.append(deq.reshape(x.shape).astype(x.dtype))
            else:
                outs.append(x)
        return outs

    jitted = jax.jit(fn, out_shardings=list(dst_shs),
                     donate_argnums=(0,) if donate else ())
    structs = [jax.ShapeDtypeStruct(shape, dtype, sharding=src)
               for shape, dtype, src in leaf_specs]
    with telemetry.attribute(f"migrate.{site}", detail=f"{len(structs)}"
                             " leaves"):
        ex = jitted.lower(structs).compile()
    with _EXEC_LOCK:
        _EXEC_CACHE[key] = ex
    if store is not None and guard is not None:
        try:
            store.save("__migrate__", logical, guard, ex)
        except Exception as e:   # persistence is an optimization only
            _log.debug("migrate artifact persist failed: %s", e)
    return ex, True


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------
_LAST_STATS: Optional[Dict[str, Any]] = None


def last_stats() -> Optional[Dict[str, Any]]:
    """Stats of the most recent :func:`migrate_arrays` call in this
    process (tests and benchmarks read these; telemetry carries the
    same numbers as ``mxtpu_migrate_*``)."""
    return _LAST_STATS


def _publish(stats: Dict[str, Any]) -> None:
    global _LAST_STATS
    _LAST_STATS = stats
    try:
        from .. import telemetry

        site = stats["site"]
        telemetry.counter(
            "mxtpu_migrate_migrations_total",
            "device-to-device live reshardings executed",
            site=site).inc()
        telemetry.counter(
            "mxtpu_migrate_plan_ops_total",
            "slice/concat steps in migrate schedules", site=site).inc(
                stats["plan_ops"])
        telemetry.counter(
            "mxtpu_migrate_wire_bytes_total",
            "per-plan bytes-on-wire moved by migrations (static "
            "schedule)", site=site).inc(stats["wire_bytes"])
        telemetry.gauge(
            "mxtpu_migrate_last_wire_bytes",
            "bytes-on-wire of the last migration at this site",
            site=site).set(float(stats["wire_bytes"]))
        telemetry.gauge(
            "mxtpu_migrate_peak_host_bytes",
            "host bytes materialized by the device path (zero by "
            "construction)", site=site).set(
                float(stats["peak_host_bytes"]))
        telemetry.gauge(
            "mxtpu_migrate_quant_fraction",
            "wire bytes over the fp32 schedule's bytes (1.0 "
            "unquantized)", site=site).set(stats["quant_fraction"])
        telemetry.histogram(
            "mxtpu_migrate_seconds",
            "wall time of one device-to-device migration",
            site=site).observe(stats["wall_s"])
        telemetry.jsonl_emit({
            "kind": "migrate", "site": site,
            "tensors": stats["tensors_total"],
            "moved": stats["moved"], "aliased": stats["aliased"],
            "plan_ops": stats["plan_ops"],
            "wire_bytes": stats["wire_bytes"],
            "fp_wire_bytes": stats["fp_wire_bytes"],
            "quant": stats["quant"], "mode": stats["mode"],
            "compiled": stats["compiled"],
            "peak_host_bytes": stats["peak_host_bytes"],
            "ms": round(stats["wall_s"] * 1e3, 3),
        })
    except Exception:               # observability never breaks a flip
        pass
    _log.info(
        "migrated %d tensor(s) (%d aliased) at %s: %d plan ops, "
        "%.2f MiB on wire (fp32 schedule %.2f MiB), mode=%s, %.0f ms",
        stats["moved"], stats["aliased"], stats["site"],
        stats["plan_ops"], stats["wire_bytes"] / 2**20,
        stats["fp_wire_bytes"] / 2**20, stats["mode"],
        stats["wall_s"] * 1e3)


# ---------------------------------------------------------------------------
# the public entry points
# ---------------------------------------------------------------------------
def _dest_shardings(tree, dest, treedef):
    if isinstance(dest, jax.sharding.Sharding):
        return [dest] * treedef.num_leaves
    d_leaves, d_def = jax.tree_util.tree_flatten(
        dest, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))
    if d_def != treedef:
        raise MigrateError(
            f"destination structure {d_def} does not match the array "
            f"tree {treedef}")
    out = []
    for d in d_leaves:
        if isinstance(d, jax.sharding.Sharding):
            out.append(d)
        elif hasattr(d, "sharding"):
            out.append(d.sharding)
        else:
            raise MigrateError(
                f"destination leaf {type(d).__name__} is neither a "
                "Sharding nor an array with one")
    return out


def plan_arrays(tree, dest, *, quant: Optional[str] = None,
                block: Optional[int] = None) -> Dict[str, Any]:
    """The static schedule of :func:`migrate_arrays` WITHOUT executing
    it: per-tensor plan ops / wire bytes / per-device remote bytes.
    What the tests of the multi-process contract ("each process only
    exchanges its destination ranges") and the bench assert against."""
    quant = resolve_quant(quant)
    if block is None:
        block = int(_cfg("MXTPU_COLLECTIVE_QUANT_BLOCK"))
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    dst_shs = _dest_shardings(tree, dest, treedef)
    names = _leaf_names(flat)
    tensors: "OrderedDict[str, Dict]" = OrderedDict()
    totals = {"plan_ops": 0, "wire_bytes": 0, "fp_wire_bytes": 0,
              "moved": 0, "aliased": 0}
    recv: Dict[int, int] = {}
    for (path, leaf), dst_sh, name in zip(flat, dst_shs, names):
        shape = tuple(getattr(leaf, "shape", ()))
        src_sh = getattr(leaf, "sharding", None)
        if src_sh is None:
            raise MigrateError(
                f"leaf {name} is not a device array (host arrays "
                "restore through parallel.restore_sharded / device_put)")
        itemsize = jnp.dtype(leaf.dtype).itemsize
        size = int(np.prod(shape)) if shape else 1
        aliased = src_sh == dst_sh
        entry: Dict[str, Any] = {"aliased": aliased, "ops": 0,
                                 "wire_bytes": 0, "fp_wire_bytes": 0,
                                 "quantized": False}
        if not aliased:
            plan = _plan_leaf(shape, src_sh, dst_sh)
            fp_remote = sum(plan["remote_elems"].values()) * itemsize
            quantized = (
                quant == "int8" and fp_remote > 0
                and jnp.issubdtype(jnp.dtype(leaf.dtype), jnp.floating)
                and size % block == 0
                and isinstance(dst_sh, NamedSharding)
                # quantize→exchange→dequantize lives inside the ONE
                # executable; a device-set-changing leaf transfers via
                # device_put and must stay full-precision (exact)
                and _device_ids(src_sh) == _device_ids(dst_sh))
            wire = 0
            for did, elems in plan["remote_elems"].items():
                b = elems * (1 if quantized else itemsize)
                if quantized:
                    b += (size // block) * 4      # replicated scales
                wire += b
                recv[did] = recv.get(did, 0) + b
            entry.update(ops=plan["ops"], wire_bytes=wire,
                         fp_wire_bytes=fp_remote, quantized=quantized,
                         dest_shards=plan["dest_shards"])
            totals["plan_ops"] += plan["ops"]
            totals["wire_bytes"] += wire
            totals["fp_wire_bytes"] += fp_remote
            totals["moved"] += 1
        else:
            totals["aliased"] += 1
        tensors[name] = entry
    frac = (totals["wire_bytes"] / totals["fp_wire_bytes"]
            if quant != "none" and totals["fp_wire_bytes"] else 1.0)
    return {"tensors": tensors, "tensors_total": len(flat),
            "quant": quant, "block": int(block),
            "quant_fraction": frac, "recv_bytes_by_device": recv,
            **totals}


def migrate_arrays(tree, dest, *, quant: Optional[str] = None,
                   block: Optional[int] = None,
                   donate: Optional[bool] = None,
                   site: str = "migrate"):
    """Reshard a pytree of live device arrays to ``dest`` — a matching
    pytree of shardings (or arrays, whose shardings are used) or one
    sharding broadcast to every leaf — entirely device-to-device:
    zero host gather, peak host bytes 0, one donated executable per
    device-assignment group (cached: repeated identical flips never
    recompile), values bit-identical on the default fp path.

    ``donate`` (default: on everywhere but CPU, where XLA ignores
    donation) hands the SOURCE buffers to the executable — live-
    reshard semantics: the old layout is consumed. Arrays whose source
    and destination device sets differ (elastic grow/shrink) transfer
    per-leaf via ``jax.device_put`` instead — still direct D2D.

    Returns the migrated tree committed to the destination shardings;
    :func:`last_stats` carries the executed plan's accounting."""
    quant = resolve_quant(quant)
    if block is None:
        block = int(_cfg("MXTPU_COLLECTIVE_QUANT_BLOCK"))
    if donate is None:
        donate = jax.default_backend() != "cpu"
    t0 = time.perf_counter()
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [leaf for _p, leaf in flat]
    dst_shs = _dest_shardings(tree, dest, treedef)
    names = _leaf_names(flat)
    for (path, leaf), dst_sh in zip(flat, dst_shs):
        if getattr(leaf, "sharding", None) is None:
            raise MigrateError(
                f"leaf {_name_of(path)} is not a device array")
        if callable(getattr(leaf, "is_deleted", None)) \
                and leaf.is_deleted():
            raise MigrateError(
                f"leaf {_name_of(path)} was deleted (donated by an "
                "earlier executable) — nothing to migrate")
    stats = plan_arrays(tree, dest, quant=quant, block=block)

    # routing: leaves grouped by shared device assignment -> ONE
    # executable each; mismatched assignments (grow/shrink) -> d2d
    # device_put; src == dst sharding -> untouched alias
    groups: "OrderedDict[Tuple, List[int]]" = OrderedDict()
    dput: List[int] = []
    out: List[Any] = list(leaves)
    for i, leaf in enumerate(leaves):
        entry = stats["tensors"][names[i]]
        if entry["aliased"]:
            continue
        src_ids = _device_ids(leaf.sharding)
        dst_ids = _device_ids(dst_shs[i])
        if src_ids == dst_ids:
            groups.setdefault(src_ids, []).append(i)
        else:
            dput.append(i)
    compiled = False
    from ..telemetry import trace

    sp = trace.span("migrate.flip", site=site)
    try:
        for ids, idxs in groups.items():
            leaf_specs = [(tuple(leaves[i].shape),
                           jnp.dtype(leaves[i].dtype),
                           leaves[i].sharding) for i in idxs]
            qflags = [bool(stats["tensors"][names[i]]["quantized"])
                      for i in idxs]
            key = (ids,
                   tuple((s[0], str(s[1]), _sharding_fp(s[2]),
                          _sharding_fp(dst_shs[i]), qf)
                         for s, i, qf in zip(leaf_specs, idxs, qflags)),
                   quant, int(block), bool(donate))
            ex, c = _compile_group(key, leaf_specs,
                                   [dst_shs[i] for i in idxs], qflags,
                                   block, donate, site)
            compiled = compiled or c
            moved = ex([leaves[i] for i in idxs])
            for i, arr in zip(idxs, moved):
                out[i] = arr
        for i in dput:
            out[i] = jax.device_put(leaves[i], dst_shs[i])
    except MigrateError:
        sp.end(error="MigrateError")
        raise
    except Exception as e:
        sp.end(error=type(e).__name__)
        raise MigrateError(f"migration failed to lower/execute: {e}") \
            from e
    moved_leaves = [out[i] for g in groups.values() for i in g] \
        + [out[i] for i in dput]
    if moved_leaves:
        jax.block_until_ready(moved_leaves)
    if not groups:
        mode = "device_put" if dput else "alias"
    else:
        mode = "mixed" if dput else "executable"
    stats.update(site=site, mode=mode, compiled=compiled,
                 peak_host_bytes=0,
                 wall_s=time.perf_counter() - t0)
    sp.end(mode=mode, compiled=compiled,
           wire_bytes=stats["wire_bytes"])
    _publish(stats)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# trainer-level migration (the elastic / cross-layout consumer)
# ---------------------------------------------------------------------------
def migrate_trainer_state(src, dst, *, quant: Optional[str] = None,
                          donate: Optional[bool] = None,
                          site: str = "elastic") -> Dict[str, Any]:
    """Move a live trainer's state (params + frozen + optimizer) onto
    ``dst``'s mesh and at-rest layouts — the in-memory alternative to
    ``save_sharded``/``restore_sharded`` when both trainers are alive
    in this process group (an elastic rebuild, a stage flip, a serving
    handoff). One :func:`migrate_arrays` call over the whole state;
    ``dst`` ends up exactly as a host-path restore would leave it
    (bit-identical on the default fp path), with zero host bytes.

    Raises :class:`MigrateError` when the states are not migratable
    (different parameter sets/shapes, different optimizer structure,
    deleted source buffers) — callers keep the checkpoint path as
    fallback. Error-feedback residuals whose device dimension does not
    match the destination plan reset to zero exactly like the restore
    path (``zero.check_residuals``)."""
    from . import zero as zero_mod

    if set(src.params) != set(dst.params):
        raise MigrateError(
            "parameter sets differ between source and destination "
            "trainers")
    if set(src.frozen) != set(dst.frozen):
        raise MigrateError("frozen (aux) sets differ")
    moves: Dict[Tuple, Any] = {}
    wants: Dict[Tuple, Any] = {}

    def add(kind, key, arr, want_leaf):
        if tuple(arr.shape) != tuple(want_leaf.shape) \
                or jnp.dtype(arr.dtype) != jnp.dtype(want_leaf.dtype):
            raise MigrateError(
                f"{kind} {key}: source {arr.dtype}{tuple(arr.shape)} vs "
                f"destination {want_leaf.dtype}{tuple(want_leaf.shape)}")
        moves[(kind, key)] = arr
        wants[(kind, key)] = want_leaf.sharding

    for n, arr in src.params.items():
        add("param", n, arr, dst.params[n])
    for n, arr in src.frozen.items():
        add("frozen", n, arr, dst.frozen[n])
    s_inner, s_res = zero_mod.split_opt_state(src.opt_state)
    d_inner, d_res = zero_mod.split_opt_state(dst.opt_state)
    s_leaves, s_def = jax.tree_util.tree_flatten(s_inner)
    d_leaves, d_def = jax.tree_util.tree_flatten(d_inner)
    if s_def != d_def:
        raise MigrateError(
            f"optimizer state structure differs ({s_def} vs {d_def})")
    for i, (sl, dl) in enumerate(zip(s_leaves, d_leaves)):
        if hasattr(sl, "shape") and hasattr(dl, "shape"):
            add("opt", i, sl, dl)
    if d_res is not None and s_res is not None:
        for name, dr in d_res.items():
            sr = s_res.get(name)
            if sr is not None and tuple(sr.shape) == tuple(dr.shape):
                add("resid", name, sr, dr)

    migrated = migrate_arrays(moves, wants, quant=quant, donate=donate,
                              site=site)
    dst.params = {n: migrated[("param", n)] for n in src.params}
    dst.frozen = {n: migrated[("frozen", n)] for n in src.frozen}
    new_leaves = [migrated.get(("opt", i), sl if not hasattr(dl, "shape")
                               else dl)
                  for i, (sl, dl) in enumerate(zip(s_leaves, d_leaves))]
    inner = jax.tree_util.tree_unflatten(d_def, new_leaves)
    if d_res is not None:
        res = {name: migrated.get(("resid", name), dr)
               for name, dr in d_res.items()}
        if dst.zero_plan is not None:
            # a topology-changing migration leaves per-OLD-device
            # residual rows behind: same reset rule as the restore path
            res = zero_mod.check_residuals(dst.zero_plan, res)
        dst.opt_state = zero_mod.wrap_opt_state(inner, res)
    else:
        dst.opt_state = inner
    if dst.zero_plan is not None and dst.zero_last_stats is not None:
        dst.zero_last_stats = dst.zero_plan.publish(
            "spmd.step", dst.params, dst.opt_state, dst.frozen)
    return last_stats()


def serving_weights(trainer, names=None, *,
                    donate: bool = False,
                    quant: Optional[str] = None,
                    site: str = "serving") -> Dict[str, Any]:
    """Flip a trained layout (ZeRO-3 sharded, DP, TP — whatever the
    trainer holds) to the replicated SERVING layout in ICI and return
    ``{structural_name: array}`` ready for
    ``ModelServer.publish_weights`` / ``ModelRegistry.publish_weights``
    / ``DecodeSession.publish_weights`` (their artifact guard already
    keys on topology, so a warm server takes the flip with zero
    recompiles). ``names`` restricts the flip to the tensors the
    serving graph consumes. ``donate=False`` by default — the trainer
    usually stays live; donate on the final flip to free the training
    layout."""
    tree: Dict[str, Any] = {}
    for n, arr in list(trainer.params.items()) \
            + list(trainer.frozen.items()):
        if names is not None and n not in names:
            continue
        tree[n] = arr
    dest = {n: NamedSharding(trainer.mesh, PartitionSpec())
            for n in tree}
    return migrate_arrays(tree, dest, quant=quant, donate=donate,
                          site=site)
