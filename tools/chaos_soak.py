#!/usr/bin/env python
"""Chaos soak: N supervised training steps under a seeded fault schedule.

Runs the deterministic CPU config (small SPMD MLP + a shuffle/shard/
batch ``mxtpu.data`` pipeline) twice:

1. **reference** — uninterrupted, chaos off: the ground-truth loss
   stream;
2. **soak** — the same seeds under a :class:`resilience.Supervisor` +
   :class:`CheckpointManager` with the fault plan active (default: a
   transient step fault, a fatal step fault, a slow step, a torn
   checkpoint write, and a data-worker death — every chaos site in the
   catalog fires at least once).

The soak must (a) complete all N steps and (b) reproduce the reference
loss stream **exactly** — restarts rewind model, optimizer, input
position and RNG together, so any drift is a recovery bug. Exits
nonzero on any non-recovered failure or loss mismatch; emits a
``kind: "resilience"`` JSONL summary through the PR 4 sink
(``--jsonl`` / ``MXTPU_TELEMETRY_JSONL``), so
``tools/telemetry_report.py`` shows the soak next to its retry/restart/
checkpoint records.

``--elastic`` (PR 7) runs the topology-loss scenario instead: the run
starts on a 2-device mesh fed by 2 simulated input ranks, a fatal
fault kills the incarnation mid-run (past the first checkpoint), and
:class:`resilience.ElasticRunner` rebuilds on ONE device with ONE
input rank — ``restore_sharded`` reshards the tensors onto the
surviving mesh and the data sidecars re-partition the global sample
position (a mid-restore ``checkpoint.restore`` fault is also injected
and survived). The merged loss stream must STILL equal the
uninterrupted 2-device reference bit-exactly.

Usage::

    JAX_PLATFORMS=cpu python tools/chaos_soak.py --steps 60 \
        --ckpt-every 10 --jsonl soak.jsonl
    JAX_PLATFORMS=cpu python tools/chaos_soak.py --elastic --steps 40
    python tools/telemetry_report.py soak.jsonl

A custom plan rides ``--plan`` (JSON) or the ``MXTPU_CHAOS`` knob.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_PLAN = {
    # transient step fault: retried in place
    "step": {"at_calls": [4], "transient": True},
    # slow step: trips the (enforcing) hung-step watchdog, then retried.
    # fires once (max_fires) so the retry itself is clean
    "step.slow": {"at_calls": [9], "action": "sleep", "sleep_s": 3.0,
                  "max_fires": 1},
    # torn checkpoint write: the save fails, training continues, and the
    # NEXT save commits — a later restart restores that one
    "checkpoint.commit": {"at_calls": [2]},
    # data worker death: surfaces at next(feed), retried without
    # consuming a sample
    "data.worker": {"at_calls": [30]},
}
#: a fatal step fault is scheduled relative to --steps (after the first
#: checkpoint) in main(), so the restart path always runs

#: --elastic plan: a transient step fault, a FATAL step fault that kills
#: incarnation 0 (max_restarts=0, so it escalates to the ElasticRunner),
#: and a mid-reshard restore fault the rebuilt incarnation must survive.
#: The fatal call lands after the first checkpoint commits (set in
#: main() relative to --ckpt-every).
ELASTIC_PLAN = {
    "step": {"at_calls": [4], "transient": True},
    "checkpoint.restore": {"at_calls": [1]},
}


def build(seed: int):
    """Deterministic trainer + pipeline (fresh instances per run)."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu import data as mxdata
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16, activation="relu"),
            nn.Dense(8, in_units=32))
    net.initialize(init="xavier")
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh({"data": -1}))
    rs = np.random.RandomState(seed + 1)
    x = rs.rand(256, 16).astype(np.float32)
    y = rs.randint(0, 8, (256,)).astype(np.float32)
    pipe = (mxdata.from_ndarray(x, y)
            .shuffle(64, seed=seed)
            .shard(0, 1)
            .batch(16)
            .prefetch(2))
    return trainer, pipe


def reference_run(steps: int, seed: int):
    trainer, pipe = build(seed)
    losses, it = [], iter(pipe)
    for _ in range(steps):
        try:
            batch = next(it)
        except StopIteration:
            it = iter(pipe)
            batch = next(it)
        losses.append(float(trainer.step(*batch)))
    pipe.close()
    return losses


def soak_run(steps: int, seed: int, ckpt_every: int, root: str,
             plan: dict, plan_seed: int):
    from incubator_mxnet_tpu import resilience

    trainer, pipe = build(seed)
    mgr = resilience.CheckpointManager(root, keep_last_k=3)
    sup = resilience.Supervisor(trainer, mgr, checkpoint_every=ckpt_every,
                                enforce_deadline=True, min_deadline_s=0.5,
                                backoff_base_s=0.01, seed=plan_seed)
    resilience.chaos.configure(plan, seed=plan_seed)
    try:
        losses = sup.run(pipe, steps=steps, start_step=0)
    finally:
        events = resilience.chaos.events()   # before disable clears them
        resilience.chaos.disable()
        pipe.close()
    return losses, sup, events


class SimShardedFeed:
    """Simulates an N-process input fleet in one process: one pipeline
    per simulated rank, each global batch the rank batches concatenated
    in rank order. With ``shard`` ABOVE ``batch`` (``.batch(B)
    .shard(r, N)``), rank ``r``'s ``t``-th batch is post-shuffle batch
    ``t*N + r`` — so the concatenation is the natural contiguous global
    batch and the global stream is IDENTICAL for every simulated rank
    count. ``load_state_dict`` with a different saved rank count
    re-partitions the global sample position via
    ``data.state.reshard_iterator_states``."""

    def __init__(self, pipes):
        self.pipes = pipes

    def __iter__(self):
        import numpy as np

        its = [iter(p) for p in self.pipes]
        while True:
            parts = []
            for it in its:
                try:
                    parts.append(next(it))
                except StopIteration:
                    if parts:
                        raise RuntimeError(
                            "simulated ranks exhausted unevenly — the "
                            "sample count does not split over the rank "
                            "count")
                    # epoch boundary: drive every sibling to ITS epoch
                    # end too, so all pipes reset together on re-iter
                    # (a rank with samples left means a ragged split)
                    for other in its:
                        if other is it:
                            continue
                        try:
                            next(other)
                        except StopIteration:
                            continue
                        else:
                            raise RuntimeError(
                                "simulated ranks exhausted unevenly — "
                                "the sample count does not split over "
                                "the rank count")
                    return
            yield tuple(np.concatenate([p[i] for p in parts])
                        for i in range(len(parts[0])))

    def state_dict(self):
        return {"sim_ranks": len(self.pipes),
                "ranks": [p.state_dict() for p in self.pipes]}

    def load_state_dict(self, sd):
        from incubator_mxnet_tpu.data import state as dstate

        states = sd["ranks"]
        if len(states) == len(self.pipes):
            for p, s in zip(self.pipes, states):
                p.load_state_dict(s)
        else:
            dstate.reshard_iterator_states(states, self.pipes)

    def close(self):
        for p in self.pipes:
            p.close()


def build_elastic(seed: int, sim_ranks: int, n_devices: int,
                  global_batch: int = 16):
    """Deterministic trainer on the first ``n_devices`` devices + a
    ``sim_ranks``-way simulated sharded input fleet. The GLOBAL batch
    (and therefore the loss stream) is invariant across both knobs."""
    import jax
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, parallel
    from incubator_mxnet_tpu import data as mxdata
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, in_units=16, activation="relu"),
            nn.Dense(8, in_units=32))
    net.initialize(init="xavier")
    mesh = parallel.make_mesh({"data": n_devices},
                              devices=jax.devices()[:n_devices])
    trainer = parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh)
    rs = np.random.RandomState(seed + 1)
    x = rs.rand(256, 16).astype(np.float32)
    y = rs.randint(0, 8, (256,)).astype(np.float32)
    if global_batch % sim_ranks:
        raise ValueError("global batch must divide over sim ranks")
    per_rank = global_batch // sim_ranks
    pipes = [(mxdata.from_ndarray(x, y)
              .shuffle(64, seed=seed)
              .batch(per_rank)
              .shard(r, sim_ranks)
              .prefetch(2))
             for r in range(sim_ranks)]
    return trainer, SimShardedFeed(pipes)


def elastic_reference_run(steps: int, seed: int):
    trainer, feed = build_elastic(seed, sim_ranks=2, n_devices=2)
    losses, it = [], iter(feed)
    for _ in range(steps):
        try:
            batch = next(it)
        except StopIteration:
            it = iter(feed)
            batch = next(it)
        losses.append(float(trainer.step(*batch)))
    feed.close()
    return losses


def elastic_soak_run(steps: int, seed: int, ckpt_every: int, root: str,
                     plan: dict, plan_seed: int, topo0, topo1,
                     migrate: bool = False):
    """Incarnation 0 on topology ``topo0 = (sim_ranks, n_devices)``
    dies to a fatal fault (no in-place restarts: max_restarts=0); the
    ElasticRunner rebuilds on ``topo1``. With ``migrate=False`` the
    rebuild goes through the reshard-restore (surviving a mid-restore
    fault on the way — the PR 7 contract this soak exists to prove);
    ``migrate=True`` exercises the ISSUE 15 in-memory short-circuit
    instead (no checkpoint round-trip at all)."""
    from incubator_mxnet_tpu import resilience

    def build_fn(incarnation):
        return build_elastic(seed, *(topo0 if incarnation == 0
                                     else topo1))

    runner = resilience.ElasticRunner(
        build_fn, root, max_incarnations=4,
        manager_kwargs={"keep_last_k": 3}, migrate=migrate,
        checkpoint_every=ckpt_every, backoff_base_s=0.01,
        max_restarts=0, seed=plan_seed)
    resilience.chaos.configure(plan, seed=plan_seed)
    try:
        losses = runner.run(steps)
    finally:
        events = resilience.chaos.events()
        resilience.chaos.disable()
    return losses, runner, events


def elastic_main(args, plan: dict, root: str) -> int:
    """The ``--elastic`` scenarios (docs/RESILIENCE.md "Elastic
    restart"). One uninterrupted 2-input-rank/2-device reference, then:

    * **input-host loss** — incarnation 1 rebuilds with ONE input rank
      on the SAME mesh, with the reshard planner forced on
      (``MXTPU_RESHARD_MODE=always``): the merged loss stream must be
      **bit-exact** — planner tensor restore and N->M sidecar
      re-partitioning are both provably lossless;
    * **chip loss** — incarnation 1 rebuilds on ONE device (and one
      input rank): tensors restore bit-identically (the reshard matrix
      tests prove that), but the loss stream is compared within float
      tolerance — partitioning the batch over a different device count
      changes XLA's reduction association order by design, so the last
      ulp of a mean is not preserved across a mesh-size change;
    * **migrate grow-back** (ISSUE 15) — same input-host loss, but the
      rebuild short-circuits through ``parallel.migrate``: surviving
      device state reshards in ICI, the run resumes at the EXACT
      failure step with NO checkpoint restore (asserted: at least one
      migrated rebuild, zero ``checkpoint.restore`` fault firings),
      and the merged loss stream is still bit-exact.

    The first two scenarios pin ``migrate=False`` so the checkpoint
    path — and its mid-restore fault survival — keeps being proven.
    """
    import numpy as np

    from incubator_mxnet_tpu.config import config

    print(f"[chaos_soak] elastic reference run (2 input ranks, "
          f"2 devices): {args.steps} steps", flush=True)
    ref = elastic_reference_run(args.steps, args.seed)
    scenarios = [
        # (name, topo0, topo1, atol, migrate)
        ("input_host_loss", (2, 2), (1, 2), 0.0, False),
        ("chip_loss", (2, 2), (1, 1), 1e-5, False),
        ("migrate_grow_back", (2, 2), (1, 2), 0.0, True),
    ]
    results = []
    failure = None
    for name, topo0, topo1, atol, migrate in scenarios:
        # the migrate scenario never restores, so its planted
        # mid-restore fault would sit unfired and trip chaos
        # accounting expectations — drop it from that plan
        splan = {k: v for k, v in plan.items()
                 if not (migrate and k == "checkpoint.restore")}
        print(f"[chaos_soak] elastic scenario {name}: "
              f"{topo0[0]} ranks/{topo0[1]} devices -> "
              f"{topo1[0]} ranks/{topo1[1]} devices under plan "
              f"{json.dumps(splan)}"
              + (" (in-memory migrate)" if migrate else ""),
              flush=True)
        sroot = os.path.join(root, name)
        if topo0[1] == topo1[1]:
            config.set("MXTPU_RESHARD_MODE", "always")
        try:
            losses, runner, events = elastic_soak_run(
                args.steps, args.seed, args.ckpt_every, sroot, splan,
                plan_seed=args.seed, topo0=topo0, topo1=topo1,
                migrate=migrate)
        except BaseException as e:  # noqa: BLE001 — report, don't crash
            failure = (f"{name}: soak did not complete: "
                       f"{type(e).__name__}: {e}")
            break
        finally:
            config.unset("MXTPU_RESHARD_MODE")
        nans = sum(1 for v in losses if v != v)
        if len(losses) != len(ref) or nans:
            failure = (f"{name}: produced {len(losses)} losses "
                       f"({nans} NaN), expected {len(ref)}")
            break
        # a run short enough that the fatal (or the mid-restore fault)
        # never fired would pass the loss checks trivially — when the
        # plan schedules those faults, refuse to claim the elastic
        # path was exercised unless they actually fired
        expects_fatal = bool(splan.get("step", {}).get("fatal_calls"))
        expects_restore = "checkpoint.restore" in splan
        restore_faults = sum(1 for e in events
                             if e["site"] == "checkpoint.restore")
        if (expects_fatal and runner.incarnation < 1) or \
                (expects_restore and restore_faults < 1):
            failure = (f"{name}: elastic path not exercised "
                       f"(incarnations={runner.incarnation + 1}, "
                       f"mid-restore faults={restore_faults}) — the "
                       "fatal lands at step ckpt_every+3; increase "
                       "--steps")
            break
        if migrate:
            # the short-circuit contract: EVERY rebuild resumed from
            # migrated in-memory state — none fell back to a
            # checkpoint restore. (Counted on the runner itself: the
            # chaos event log only records sites the plan schedules,
            # so it cannot witness an unexpected restore.)
            if runner.migrated_rebuilds < 1 \
                    or runner.migrated_rebuilds != runner.incarnation:
                failure = (f"{name}: {runner.migrated_rebuilds} of "
                           f"{runner.incarnation} rebuild(s) migrated "
                           "— the rest fell back to the checkpoint "
                           "path")
                break
        if atol == 0.0:
            bad = sum(1 for a, b in zip(ref, losses) if a != b)
            if bad:
                failure = (f"{name}: {bad}/{len(ref)} losses differ "
                           "bit-wise from the uninterrupted reference")
                break
        else:
            worst = max(abs(a - b) for a, b in zip(ref, losses))
            if worst > atol:
                failure = (f"{name}: max loss deviation {worst:.3e} "
                           f"exceeds {atol:.0e}")
                break
            bad = int(np.sum([a != b for a, b in zip(ref, losses)]))
        results.append({
            "scenario": name, "from": list(topo0), "to": list(topo1),
            "incarnations": runner.incarnation + 1,
            "migrated_rebuilds": runner.migrated_rebuilds,
            "faults_injected": len(events),
            "fault_log": events, "exact": atol == 0.0,
            "loss_mismatches": bad,
        })
    summary = {
        "kind": "resilience", "event": "soak_summary", "elastic": True,
        "steps": args.steps, "ok": failure is None,
        "scenarios": results,
    }
    if failure:
        summary["failure"] = failure
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit(summary)
    except Exception:
        pass
    print(json.dumps(summary))
    if failure:
        print(f"[chaos_soak] FAIL: {failure}", file=sys.stderr)
        return 1
    print(f"[chaos_soak] OK: {args.steps} steps x "
          f"{len(results)} elastic scenarios "
          "(input-host loss bit-exact; chip loss within float "
          "tolerance; migrate grow-back bit-exact with zero restores), "
          "reshard-restore survived a mid-restore fault")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--elastic", action="store_true",
                    help="topology-loss scenario: kill the 2-device/"
                         "2-input-rank incarnation mid-run, rebuild on "
                         "1 device/1 rank via reshard-restore, assert "
                         "the merged loss stream still matches the "
                         "uninterrupted reference")
    ap.add_argument("--plan", type=str, default=None,
                    help="JSON chaos plan (default: the built-in "
                         "all-sites schedule; MXTPU_CHAOS also accepted)")
    ap.add_argument("--root", type=str, default=None,
                    help="checkpoint root (default: a fresh tmp dir)")
    ap.add_argument("--jsonl", type=str, default=None,
                    help="telemetry JSONL sink path")
    args = ap.parse_args(argv)

    if args.elastic and "jax" not in sys.modules:
        # the elastic scenario needs >= 2 CPU devices; arrange the XLA
        # flag BEFORE jax initializes (re-exec once if the operator
        # didn't set it)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags \
                and not os.environ.get("MXTPU_SOAK_REEXEC"):
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
            os.environ["MXTPU_SOAK_REEXEC"] = "1"
            os.execv(sys.executable, [sys.executable] + sys.argv)

    # after the re-exec decision above, which must see jax unimported
    from incubator_mxnet_tpu import runtime

    runtime.enable_compile_cache()

    if args.jsonl:
        os.environ["MXTPU_TELEMETRY_JSONL"] = args.jsonl
    if args.plan:
        plan = json.loads(args.plan)
    elif os.environ.get("MXTPU_CHAOS", "").strip():
        data = json.loads(os.environ["MXTPU_CHAOS"])
        plan = data.get("sites", data)
    elif args.elastic:
        plan = {k: dict(v) for k, v in ELASTIC_PLAN.items()}
        # the incarnation-killing fatal lands after the first
        # checkpoint commits, so the rebuilt topology has something to
        # reshard-restore from
        plan["step"]["fatal_calls"] = [max(args.ckpt_every + 3, 6)]
    else:
        plan = {k: dict(v) for k, v in DEFAULT_PLAN.items()}
        # a fatal step fault lands after the first checkpoint commits,
        # so the soak always exercises a real restore-from-checkpoint
        # (the call at 4 stays transient: before any checkpoint exists
        # a fatal would end the run)
        plan["step"]["fatal_calls"] = [max(args.ckpt_every + 3, 6)]

    root = args.root or tempfile.mkdtemp(prefix="mxtpu-chaos-soak-")
    own_root = args.root is None

    if args.elastic:
        rc = elastic_main(args, plan, root)
        if own_root:
            shutil.rmtree(root, ignore_errors=True)
        return rc

    print(f"[chaos_soak] reference run: {args.steps} steps", flush=True)
    ref = reference_run(args.steps, args.seed)
    print(f"[chaos_soak] soak run under plan: {json.dumps(plan)}",
          flush=True)
    failure = None
    losses = sup = events = None
    try:
        losses, sup, events = soak_run(args.steps, args.seed,
                                       args.ckpt_every, root, plan,
                                       plan_seed=args.seed)
    except BaseException as e:      # noqa: BLE001 — report, don't crash
        failure = f"soak did not complete: {type(e).__name__}: {e}"

    mismatches = 0
    if failure is None:
        mismatches = sum(1 for a, b in zip(ref, losses) if a != b)
        if len(losses) != len(ref):
            failure = (f"soak produced {len(losses)} losses, "
                       f"expected {len(ref)}")
        elif mismatches:
            failure = (f"{mismatches}/{len(ref)} losses differ from the "
                       "uninterrupted reference (recovery is not "
                       "bit-exact)")

    summary = {
        "kind": "resilience", "event": "soak_summary",
        "steps": args.steps, "ok": failure is None,
        "faults_injected": len(events or []),
        "fault_log": events or [],
        "retries": getattr(sup, "retries", None),
        "restarts": getattr(sup, "restarts", None),
        "hung_steps": getattr(sup, "hung_steps", None),
        "loss_mismatches": mismatches,
    }
    if failure:
        summary["failure"] = failure
    try:
        from incubator_mxnet_tpu import telemetry

        telemetry.jsonl_emit(summary)
    except Exception:
        pass
    print(json.dumps(summary))
    if own_root:
        shutil.rmtree(root, ignore_errors=True)
    if failure:
        print(f"[chaos_soak] FAIL: {failure}", file=sys.stderr)
        return 1
    print(f"[chaos_soak] OK: {args.steps} steps, "
          f"{summary['faults_injected']} faults injected, "
          f"{summary['retries']} retries, {summary['restarts']} "
          "restarts, loss stream bit-exact")
    return 0


if __name__ == "__main__":
    sys.exit(main())
