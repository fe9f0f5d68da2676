"""ZeRO ladder cost table (the bench.py ``zero`` row; docs/SCALING.md).

Sweeps ``zero_stage in {0, 1, 2, 3}`` x ``MXTPU_COLLECTIVE_QUANT in
{none, int8, 2bit}`` (quantization requires stage >= 2 — invalid cells
are skipped) over MLP- and BERT-shaped dense models on the 8-device
virtual CPU mesh, reporting per configuration:

* **measured** per-chip at-rest bytes: parameters, optimizer state,
  error-feedback residual (``zero.bytes_per_chip`` over the live
  arrays' shard shapes) and the gradient bytes materialized at the
  update point;
* **bytes-on-wire per step** from the static collective schedule
  (``ZeroPlan.wire_stats`` — ring reduce-scatter/all-gather legs,
  quantized payloads counted by their code + scale bytes; this box
  cannot measure ICI, the schedule is exact);
* the loss stream of a few steps and its max delta vs the stage-0
  unquantized baseline (the measured accuracy cost of quantization).

Every row rides the PR 4 JSONL sink (``kind: "bench"``, metric
``zero_detail``). The headline value is the geomean over both models of
``(param+opt bytes/chip, stage 0) / (param+opt bytes/chip, stage 3)``
— the ZeRO-3 memory reduction (acceptance: >= 4x on 8 devices).

``--overlap`` runs the ISSUE 18 latency-hiding matrix instead: overlap
{on, off} x stage {2, 3} x quant {none, int8} over a deep homogeneous
tower, reporting engagement, the schedule-exact hidden-gather fraction
and warm-up bytes, wall/step, and asserting the overlapped loss stream
bitwise equal to the non-overlapped one (metric
``zero_overlap_detail`` on the JSONL sink).

Standalone::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmark/zero_bench.py [--overlap]
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGES = (0, 1, 2, 3)
QUANTS = ("none", "int8", "2bit")
STEPS = 4


def _models():
    """Two dense shapes: 'mlp' (small, dispatch-bound bench row shape)
    and 'bert' (hidden/FFN ratio of a transformer block — the
    BERT-shaped memory row). Dims divide 8 so the whole ladder engages."""
    return {
        "mlp": dict(in_units=256, hidden=512, out=64, batch=128),
        "bert": dict(in_units=512, hidden=2048, out=512, batch=64),
    }


def _build(name, cfg, stage, quant):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(7)
    np.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(cfg["hidden"], in_units=cfg["in_units"],
                     activation="relu"),
            nn.Dense(cfg["hidden"], in_units=cfg["hidden"],
                     activation="relu"),
            nn.Dense(cfg["out"], in_units=cfg["hidden"]))
    net.initialize(init="xavier")
    mesh = parallel.make_mesh({"data": -1})
    return parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=mesh, donate=False,
        zero_stage=stage, collective_quant=quant)


def _batch(cfg):
    rs = np.random.RandomState(0)
    x = rs.rand(cfg["batch"], cfg["in_units"]).astype(np.float32)
    y = rs.randint(0, cfg["out"], (cfg["batch"],)).astype(np.float32)
    return x, y


def _jsonl_emit(record):
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit(record)
    except Exception:
        pass


def sweep(steps: int = STEPS):
    """Returns {model: {(stage, quant): row_dict}} and emits JSONL rows."""
    import time

    import jax

    from incubator_mxnet_tpu.parallel import zero as zero_mod

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "zero bench needs >= 2 devices (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 on a 1-chip host)")
    out = {}
    for model, cfg in _models().items():
        rows = {}
        x, y = _batch(cfg)
        baseline_losses = None
        for stage in STAGES:
            for quant in QUANTS:
                if quant != "none" and stage < 2:
                    continue        # the ladder: quant needs stage >= 2
                tr = _build(model, cfg, stage, quant)
                t0 = time.perf_counter()
                losses = [float(tr.step(x, y)) for _ in range(steps)]
                wall_s = time.perf_counter() - t0
                stats = tr.zero_last_stats or {
                    "param_bytes_per_chip":
                        zero_mod.bytes_per_chip(tr.params),
                    "opt_bytes_per_chip":
                        zero_mod.bytes_per_chip(tr.opt_state),
                    "residual_bytes_per_chip": 0,
                    "grad_bytes_per_chip":
                        zero_mod.bytes_per_chip(tr.params),
                    # stage 0: one fused allreduce of every grad
                    "wire_bytes_per_step": sum(
                        2 * a.nbytes * (len(jax.devices()) - 1)
                        / len(jax.devices())
                        for a in tr.params.values()),
                    "rs_wire_bytes_per_step": 0.0,
                    "rs_fp32_wire_bytes_per_step": 0.0,
                    "quant_fraction": 1.0,
                }
                if baseline_losses is None:
                    baseline_losses = losses
                row = {
                    "model": model, "stage": stage, "quant": quant,
                    "losses": losses,
                    "loss_delta_vs_stage0": float(max(
                        abs(a - b)
                        for a, b in zip(losses, baseline_losses))),
                    "wall_s_per_step": wall_s / steps,
                    **{k: stats[k] for k in (
                        "param_bytes_per_chip", "opt_bytes_per_chip",
                        "residual_bytes_per_chip", "grad_bytes_per_chip",
                        "wire_bytes_per_step", "rs_wire_bytes_per_step",
                        "rs_fp32_wire_bytes_per_step", "quant_fraction")},
                }
                rows[(stage, quant)] = row
                _jsonl_emit({"kind": "bench", "metric": "zero_detail",
                             **{k: v for k, v in row.items()
                                if k != "losses"}})
        out[model] = rows
    return out


def _build_deep(cfg, stage, quant, overlap, optimizer="sgd"):
    """A HOMOGENEOUS tower (head + L identical hidden blocks + tail) —
    the shape ``zero.layer_plan`` can group; the main sweep's 3-distinct-
    width models are deliberately NOT groupable and document the
    fallback."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu.config import config
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(7)
    np.random.seed(7)
    config.set("MXTPU_ZERO_OVERLAP", overlap)
    net = nn.HybridSequential()
    net.add(nn.Dense(cfg["hidden"], in_units=cfg["in_units"],
                     activation="tanh"))
    for _ in range(cfg["layers"]):
        net.add(nn.Dense(cfg["hidden"], in_units=cfg["hidden"],
                         activation="tanh"))
    net.add(nn.Dense(cfg["out"], in_units=cfg["hidden"]))
    net.initialize(init="xavier")
    mesh = parallel.make_mesh({"data": -1})
    return parallel.SPMDTrainer(
        net, gluon.loss.L2Loss(), optimizer, {"learning_rate": 1e-2},
        mesh=mesh, donate=False, zero_stage=stage,
        collective_quant=quant)


OVERLAP_CFG = dict(in_units=256, hidden=512, out=64, batch=128, layers=6)


def overlap_sweep(steps: int = STEPS):
    """The ISSUE 18 matrix: overlap {on, off} x stage {2, 3} x quant
    {none, int8} over the deep homogeneous tower. Per cell: wall/step,
    engagement + recorded fallback reason, and the static-schedule comm
    accounting (run all-gather bytes, warm-up overhead, the fraction of
    gather latency the double buffer hides — exact from the schedule;
    this box cannot time ICI). Rows ride the PR 4 JSONL sink
    (``kind: "bench"``, metric ``zero_overlap_detail``); the bit-exact
    loss check vs the non-overlapped body rides every stage-3 pair."""
    import time

    import jax

    from incubator_mxnet_tpu.config import config

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "overlap bench needs >= 2 devices (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 on a 1-chip host)")
    cfg = OVERLAP_CFG
    rs = np.random.RandomState(0)
    x = rs.rand(cfg["batch"], cfg["in_units"]).astype(np.float32)
    y = rs.rand(cfg["batch"], cfg["out"]).astype(np.float32)
    rows = {}
    try:
        for stage in (2, 3):
            for quant in ("none", "int8"):
                cell_losses = {}
                for overlap in ("off", "on"):
                    tr = _build_deep(cfg, stage, quant, overlap)
                    t0 = time.perf_counter()
                    losses = [float(tr.step(x, y)) for _ in range(steps)]
                    wall_s = time.perf_counter() - t0
                    cell_losses[overlap] = losses
                    info = tr.zero_overlap or {}
                    row = {
                        "model": "tower", "stage": stage, "quant": quant,
                        "overlap": overlap, "losses": losses,
                        "wall_s_per_step": wall_s / steps,
                        "engaged": bool(info.get("engaged")),
                        "reason": info.get("reason"),
                        "layers": info.get("layers", 0),
                        "gather": info.get("gather"),
                        "overlap_fraction":
                            float(info.get("overlap_fraction", 0.0)),
                        "run_ag_bytes_per_step":
                            float(info.get("run_ag_bytes_per_step", 0.0)),
                        "overlap_extra_ag_bytes_per_step": float(
                            info.get("overlap_extra_ag_bytes_per_step",
                                     0.0)),
                    }
                    rows[(stage, quant, overlap)] = row
                    _jsonl_emit({"kind": "bench",
                                 "metric": "zero_overlap_detail",
                                 **{k: v for k, v in row.items()
                                    if k != "losses"}})
                # the numerics contract, asserted in the bench itself:
                # overlapped losses == non-overlapped losses, bitwise
                bit = all(
                    np.float32(a).tobytes() == np.float32(b).tobytes()
                    for a, b in zip(cell_losses["on"], cell_losses["off"]))
                rows[(stage, quant, "on")]["losses_bit_exact_vs_off"] = bit
                if not bit:
                    raise RuntimeError(
                        f"overlap loss stream diverged at stage {stage} "
                        f"quant {quant}: {cell_losses}")
    finally:
        config.unset("MXTPU_ZERO_OVERLAP")
    return rows


def overlap_hidden_fraction(rows) -> float:
    """Mean over ENGAGED cells of the schedule's hidden-gather fraction
    ((L-1)/(L+1) of the run's all-gather latency issued under compute)."""
    fr = [r["overlap_fraction"] for r in rows.values() if r["engaged"]]
    return float(np.mean(fr)) if fr else 0.0


def memory_reduction(rows_by_model) -> float:
    """Geomean over models of (param+opt)/chip at stage 0 over stage 3."""
    factors = []
    for rows in rows_by_model.values():
        base = rows[(0, "none")]
        z3 = rows[(3, "none")]
        b = base["param_bytes_per_chip"] + base["opt_bytes_per_chip"]
        z = z3["param_bytes_per_chip"] + z3["opt_bytes_per_chip"]
        factors.append(b / max(1, z))
    return float(np.exp(np.mean(np.log(factors))))


def rs_wire_reduction(rows_by_model, quant: str = "int8") -> float:
    """Geomean over models of the gradient reduce-scatter leg's fp32
    bytes over its quantized bytes (stage 2)."""
    factors = []
    for rows in rows_by_model.values():
        r = rows[(2, quant)]
        if r["rs_wire_bytes_per_step"] > 0:
            factors.append(r["rs_fp32_wire_bytes_per_step"]
                           / r["rs_wire_bytes_per_step"])
    return float(np.exp(np.mean(np.log(factors)))) if factors else 0.0


def main_overlap() -> int:
    rows = overlap_sweep()
    print(f"{'stage':>5s} {'quant':>5s} {'ovl':>3s} {'eng':>3s} "
          f"{'L':>2s} {'gather':>17s} {'hidden':>6s} {'AG/step':>11s} "
          f"{'warmup/step':>11s} {'wall/step':>10s}  reason")
    for (stage, quant, overlap), r in sorted(
            rows.items(), key=lambda kv: (kv[0][0], kv[0][1], kv[0][2])):
        print(f"{stage:5d} {quant:>5s} {overlap:>3s} "
              f"{'y' if r['engaged'] else 'n':>3s} {r['layers']:2d} "
              f"{str(r['gather']):>17s} {r['overlap_fraction']:6.2f} "
              f"{int(r['run_ag_bytes_per_step']):11,d} "
              f"{int(r['overlap_extra_ag_bytes_per_step']):11,d} "
              f"{r['wall_s_per_step'] * 1e3:9.2f}m  "
              f"{r['reason'] or '-'}")
    print(f"\nhidden gather fraction (engaged cells, schedule-exact): "
          f"{overlap_hidden_fraction(rows):.3f}")
    return 0


def main() -> int:
    rows_by_model = sweep()
    print(f"{'model':6s} {'stage':>5s} {'quant':>5s} "
          f"{'param/chip':>11s} {'opt/chip':>10s} {'grad/chip':>10s} "
          f"{'resid/chip':>11s} {'wire/step':>11s} {'rsQ/rsFP':>9s} "
          f"{'dLoss':>10s}")
    for model, rows in rows_by_model.items():
        for (stage, quant), r in sorted(rows.items()):
            print(f"{model:6s} {stage:5d} {quant:>5s} "
                  f"{r['param_bytes_per_chip']:11,d} "
                  f"{r['opt_bytes_per_chip']:10,d} "
                  f"{r['grad_bytes_per_chip']:10,d} "
                  f"{r['residual_bytes_per_chip']:11,d} "
                  f"{int(r['wire_bytes_per_step']):11,d} "
                  f"{r['quant_fraction']:9.3f} "
                  f"{r['loss_delta_vs_stage0']:10.2e}")
    print(f"\nZeRO-3 param+opt per-chip reduction (geomean): "
          f"{memory_reduction(rows_by_model):.2f}x")
    print(f"int8 reduce-scatter wire reduction (geomean):  "
          f"{rs_wire_reduction(rows_by_model, 'int8'):.2f}x")
    print(f"2bit reduce-scatter wire reduction (geomean):  "
          f"{rs_wire_reduction(rows_by_model, '2bit'):.2f}x")
    return 0


if __name__ == "__main__":
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()
    sys.exit(main_overlap() if "--overlap" in sys.argv else main())
