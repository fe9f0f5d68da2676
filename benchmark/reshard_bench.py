"""Topology-portable restore cost (the bench.py ``reshard`` row).

Saves a sharded checkpoint of an MLP trainer whose optimizer state is
ZeRO-1-sharded over the data axis, then restores it two ways onto a
mesh of a DIFFERENT shape:

* **gather** — the legacy path (``MXTPU_RESHARD_MODE=never``): every
  tensor is materialized as the FULL global array on host before
  ``device_put``;
* **planned** — the PR 7 reshard engine (``always``): one host buffer
  per unique destination shard, filled by slice-plan byte-range reads.

Reported: wall time of each restore, bytes read, the engine's peak host
buffer, and the **peak-host reduction factor** — for the largest tensor
that is actually *sharded* at the destination (the ZeRO-1 optimizer
state here), its full size over the engine's largest host buffer for
it. That ratio is what decides whether a restore fits in host RAM when
a big sharded model comes back on different hardware; tensors that are
replicated at the destination restore at full size on every path. On
one host every byte must still be read (all destination shards are
local); the byte-read savings appear with multiple processes, the
memory bound appears everywhere.

``--device`` (ISSUE 15) compares the PR 7 HOST path (checkpoint
round-trip) against the in-ICI DEVICE path
(``parallel.migrate.migrate_trainer_state``) for a live layout flip
over the same chips: wall time, wire bytes from the planned schedule,
and ``peak_host_bytes`` — asserted ZERO on the device path.
``--quant int8`` ships the migration payloads block-quantized.

Standalone::

    JAX_PLATFORMS=cpu python benchmark/reshard_bench.py [--device]
"""

from __future__ import annotations

import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_trainer(n_dev, *, seed=0, hidden=512, axes=None):
    import jax

    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn

    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, in_units=256, activation="relu"),
            nn.Dense(hidden, in_units=hidden, activation="relu"),
            nn.Dense(64, in_units=hidden))
    net.initialize(init="xavier")
    mesh = parallel.make_mesh(dict(axes) if axes else {"data": n_dev},
                              devices=jax.devices()[:n_dev])
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
        donate=False, shard_weight_update=True)
    return trainer


def compare_restore(hidden: int = 512, root: str = None):
    """Returns a dict with gather/planned wall ms, planned bytes read,
    planned peak host bytes, the largest full-tensor bytes, and the
    peak reduction factor."""
    import jax

    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.config import config
    from incubator_mxnet_tpu.parallel import reshard as reshard_mod

    n_dev = len(jax.devices())
    if n_dev < 2:
        # nothing to reshard between: reporting 1.0x here would read as
        # "no better than gathering" — a false regression. bench.py's
        # reshard row arranges the 8-device virtual CPU mesh; standalone
        # runs need XLA_FLAGS=--xla_force_host_platform_device_count=N.
        raise RuntimeError(
            "reshard bench needs >= 2 devices (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 on a 1-chip host)")
    save_dev = max(1, n_dev // 2)
    own_tmp = root is None
    if own_tmp:
        root = tempfile.mkdtemp(prefix="mxtpu-reshard-bench-")
    prefix = os.path.join(root, "ckpt")

    src = _build_trainer(save_dev, hidden=hidden)
    x = np.random.rand(64 * save_dev, 256).astype(np.float32)
    y = np.random.randint(0, 64, (64 * save_dev,)).astype(np.float32)
    src.step(x, y)                       # momentum state nonzero
    parallel.save_sharded(prefix, src)

    biggest = max(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in src.params.values())

    results = {}
    for mode in ("never", "always"):
        dst = _build_trainer(n_dev, seed=7, hidden=hidden)
        config.set("MXTPU_RESHARD_MODE", mode)
        try:
            t0 = time.perf_counter()
            parallel.restore_sharded(prefix, dst)
            jax.block_until_ready(jax.tree_util.tree_leaves(dst.params))
            results[mode] = time.perf_counter() - t0
        finally:
            config.unset("MXTPU_RESHARD_MODE")
    stats = reshard_mod.last_stats()

    if own_tmp:
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    peak = int(stats["peak_host_bytes"])
    # the reduction that matters: among tensors actually SHARDED at the
    # destination (here the ZeRO-1 optimizer state), the largest one's
    # full size vs. the engine's largest host buffer for it. Replicated
    # tensors restore at full size on every path — docs/SCALING.md
    # "Restore memory" shows both bounds.
    sharded = [(t["full_bytes"], t["peak_host_bytes"], n)
               for n, t in stats["tensors"].items()
               if t["unique_boxes"] > 1]
    if sharded:
        s_full, s_peak, s_name = max(sharded)
        sharded_reduction = s_full / s_peak if s_peak else float("nan")
    else:
        s_full = s_peak = 0
        s_name = None
        sharded_reduction = 1.0
    return {
        "gather_ms": results["never"] * 1e3,
        "planned_ms": results["always"] * 1e3,
        "bytes_read": int(stats["bytes_read"]),
        "full_gather_bytes": int(stats["full_gather_bytes"]),
        "plan_ops": int(stats["plan_ops"]),
        "peak_host_bytes": peak,
        "biggest_tensor_bytes": biggest,
        "sharded_tensor": s_name,
        "sharded_tensor_bytes": int(s_full),
        "sharded_tensor_peak_bytes": int(s_peak),
        "peak_reduction_x": sharded_reduction,
        "save_devices": save_dev,
        "restore_devices": n_dev,
    }


def compare_device(hidden: int = 512, root: str = None,
                   quant: str = None):
    """``--device`` mode (ISSUE 15): the PR 7 HOST path (save_sharded +
    slice-planned restore_sharded) vs the in-ICI DEVICE path
    (``parallel.migrate.migrate_trainer_state``) for the same layout
    flip — a ZeRO-1 trainer's state flipping between two mesh shapes
    over the SAME chips (``(N,)`` -> ``(N/2, 2)``), so the device path
    runs as the one donated executable, not per-leaf transfers.
    Reports wall time, bytes (host path: bytes read from disk; device
    path: planned bytes-on-wire), and the peak host bytes of each —
    asserted ZERO on the device path — plus a bit-exactness
    cross-check of the two destinations. Rows ride the PR 4 JSONL sink
    (``kind: "bench"``) so ``tools/telemetry_report.py --compare``
    diffs them across rounds."""
    import jax

    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel import migrate as migrate_mod
    from incubator_mxnet_tpu.parallel import reshard as reshard_mod

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            "reshard bench needs >= 2 devices (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 on a 1-chip host)")
    dst_axes = {"data": max(1, n_dev // 2), "model": 2} if n_dev >= 4 \
        else {"data": 1, "model": n_dev}
    own_tmp = root is None
    if own_tmp:
        root = tempfile.mkdtemp(prefix="mxtpu-reshard-bench-")
    prefix = os.path.join(root, "ckpt")

    src = _build_trainer(n_dev, hidden=hidden)
    x = np.random.rand(64 * n_dev, 256).astype(np.float32)
    y = np.random.randint(0, 64, (64 * n_dev,)).astype(np.float32)
    src.step(x, y)

    # HOST path: checkpoint round-trip through the PR 7 planner
    t0 = time.perf_counter()
    parallel.save_sharded(prefix, src)
    dst_host = _build_trainer(n_dev, seed=7, hidden=hidden,
                              axes=dst_axes)
    parallel.restore_sharded(prefix, dst_host, reshard="always")
    import jax as _jax

    _jax.block_until_ready(_jax.tree_util.tree_leaves(dst_host.params))
    host_s = time.perf_counter() - t0
    host_stats = reshard_mod.last_stats()

    # DEVICE path: the live state flips in ICI, no file, no host buffer
    dst_dev = _build_trainer(n_dev, seed=8, hidden=hidden,
                             axes=dst_axes)
    t0 = time.perf_counter()
    migrate_mod.migrate_trainer_state(src, dst_dev, quant=quant,
                                      donate=False, site="bench")
    _jax.block_until_ready(_jax.tree_util.tree_leaves(dst_dev.params))
    dev_s = time.perf_counter() - t0
    dev_stats = migrate_mod.last_stats()
    assert dev_stats["peak_host_bytes"] == 0, \
        "device path materialized host bytes"

    # cross-check: the two destinations agree bit-for-bit (fp path)
    if (quant or "none") == "none":
        for n in dst_host.params:
            np.testing.assert_array_equal(
                np.asarray(dst_host.params[n]),
                np.asarray(dst_dev.params[n]), n)

    if own_tmp:
        import shutil

        shutil.rmtree(root, ignore_errors=True)
    rows = {
        "host_ms": host_s * 1e3,
        "device_ms": dev_s * 1e3,
        "speedup_x": host_s / dev_s if dev_s else float("nan"),
        "host_bytes_read": int(host_stats["bytes_read"]),
        "host_peak_host_bytes": int(host_stats["peak_host_bytes"]),
        "device_wire_bytes": int(dev_stats["wire_bytes"]),
        "device_fp_wire_bytes": int(dev_stats["fp_wire_bytes"]),
        "device_peak_host_bytes": int(dev_stats["peak_host_bytes"]),
        "device_plan_ops": int(dev_stats["plan_ops"]),
        "device_mode": dev_stats["mode"],
        "quant": dev_stats["quant"],
        "devices": n_dev,
        "src_mesh": {"data": n_dev},
        "dst_mesh": dst_axes,
    }
    _emit({"kind": "bench", "metric": "reshard_device_ms",
           "value": rows["device_ms"], "unit": "ms",
           "wire_bytes": rows["device_wire_bytes"],
           "peak_host_bytes": 0, "quant": rows["quant"]})
    _emit({"kind": "bench", "metric": "reshard_host_ms",
           "value": rows["host_ms"], "unit": "ms",
           "bytes_read": rows["host_bytes_read"],
           "peak_host_bytes": rows["host_peak_host_bytes"]})
    return rows


def _emit(record):
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit(record)
    except Exception:
        pass


def main(argv=None):
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument("--device", action="store_true",
                    help="device-path (in-ICI migrate) vs host-path "
                         "(checkpoint round-trip) comparison")
    ap.add_argument("--quant", default=None,
                    help="--device only: migrate payload quantization "
                         "(none/int8)")
    args = ap.parse_args(argv)
    if args.device:
        out = compare_device(quant=args.quant)
        out["metric"] = "reshard_device"
        out["host_ms"] = round(out["host_ms"], 3)
        out["device_ms"] = round(out["device_ms"], 3)
        out["speedup_x"] = round(out["speedup_x"], 2)
        print(json.dumps(out))
        return
    out = compare_restore()
    out["metric"] = "reshard_restore"
    out["gather_ms"] = round(out["gather_ms"], 3)
    out["planned_ms"] = round(out["planned_ms"], 3)
    out["peak_reduction_x"] = round(out["peak_reduction_x"], 2)
    print(json.dumps(out))


if __name__ == "__main__":
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    main()
