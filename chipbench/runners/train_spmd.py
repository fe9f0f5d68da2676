"""Runner of the training path: ``parallel.SPMDTrainer.step`` on a fresh
host batch every step.

``build`` makes ONE trainer (the compiled step with its state), drives it
from the seed through its first steps on rows that all differ, through the
same call and feed as the window, and keeps what the comparison needs: each
step's loss, the first gradient's norms as the optimizer got it (from
AdamW's first moment after one step), the norms of the parameters' change
after the checked steps. ``measure`` hands that same trainer to the window.
``check`` follows those steps in the float32 reference.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from ..harness import (Context, build_zoo_model, leaf_targets,
                       sleep_through_window)
from ..loadgen import train_pool


class State:
    pass


def _first_moment(opt_state):
    """AdamW's ``mu`` inside an optax state, wherever the chain put it."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            mu = _first_moment(s)
            if mu is not None:
                return mu
    return None


def build(ctx: Context) -> State:
    import jax
    from incubator_mxnet_tpu import amp, gluon, parallel

    st = State()
    st.ctx = ctx
    tr = ctx.config["training"]
    ref = ctx.family("references")
    if tr.get("amp"):
        amp.init(tr["amp"])
    net = build_zoo_model(ctx, trainable=True)
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    st.trainer = parallel.SPMDTrainer(
        net, lambda logits, labels: ce(logits, labels).mean(),
        tr["optimizer"], dict(tr["optimizer_params"]),
        mesh=parallel.make_mesh({"data": -1}))
    # the fused step never reads the gradient buffers that the gluon
    # parameters carry for the imperative path: 1.6 GB the step needs
    net.collect_params().setattr("grad_req", "null")
    ctx.mark("trainer")
    st.batch, st.seq = int(tr["batch"]), int(tr["seq"])
    st.pool = train_pool(ctx.traffic, ctx.seed, st.batch, st.seq,
                         int(ctx.model["vocab_size"]))
    names = {v: k for k, v in leaf_targets(ctx.config).items()}
    rename = lambda tree: {names[n]: a for n, a in tree.items()}
    b1 = float(tr["optimizer_params"]["beta1"])
    n_check = int(ctx.config["check"]["train"]["steps"])
    st.seen = {"losses": []}
    for i in range(n_check):
        x, y = st.pool[i]
        st.seen["losses"].append(float(st.trainer.step(x, y)))
        if i == 0:
            ctx.mark("first_step")
            mu = _first_moment(st.trainer.opt_state)
            st.seen["grad_norms"] = {
                n: v / (1.0 - b1)
                for n, v in ref.leaf_norms(rename(mu)).items()}
    # the parameters' change: what the trainer holds now, less the seeded
    # weights drawn again (the program was handed exactly these)
    g, layers = ref.draw_all(ctx.model, ctx.seed, ctx.config["dtype"])
    start = ref.flatten_leaves(g, layers)
    now = rename(st.trainer.params)
    diff = jax.jit(lambda a, b: {n: a[n] - b[n] for n in a})(now, start)
    st.seen["change_norms"] = ref.leaf_norms(diff)
    del g, layers, start, now, diff
    st.next = n_check
    for _ in range(int(tr["warm_steps"])):
        _step(st)
    ctx.mark("warm")
    return st


def _step(st: State):
    x, y = st.pool[st.next % len(st.pool)]
    st.next += 1
    return st.trainer.step(x, y)


def measure(st: State, seconds: float) -> dict:
    import jax

    ctx, mix = st.ctx, st.ctx.traffic
    jax.block_until_ready(st.trainer.params)
    in_flight = int(mix["steps_in_flight"])
    pending: deque = deque()
    host_step_s, loop_s, traced = [], [], []
    c0 = ctx.compiles.snapshot()
    t0 = time.perf_counter()
    t1 = t0 + seconds
    tracer = None
    if ctx.trace_dir:
        # the profiler is started and stopped from a thread of its own, so
        # the loop that feeds the steps never waits for it
        tracer = threading.Thread(
            target=lambda: traced.append(sleep_through_window(
                ctx.trace_dir, t0, t1, float(mix["trace_after_s"]),
                float(mix["trace_s"]))), daemon=True)
        tracer.start()
    steps = 0
    while time.perf_counter() < t1:
        a = time.perf_counter()
        pending.append(_step(st))
        host_step_s.append(time.perf_counter() - a)
        steps += 1
        # a loop that reads its loss a step or two late: the device never
        # waits for the host, and the host never runs far ahead
        if len(pending) > in_flight:
            jax.block_until_ready(pending.popleft())
        loop_s.append(time.perf_counter() - a)
    last = float(pending[-1])          # closes the window: all steps done
    t_end = time.perf_counter()
    if tracer:
        tracer.join()
    c1 = ctx.compiles.snapshot()
    return {"kind": "train", "t0": t0, "t1": t_end,
            "window_s": t_end - t0, "steps": steps, "attempted": steps,
            "failed": 0 if np.isfinite(last) else steps,
            "last_loss": last, "host_step_s": host_step_s,
            "loop_s": loop_s,
            "traced": traced[0] if traced else None, "batch": st.batch, "seq": st.seq,
            "compile_requests_in_window": c1["requests"] - c0["requests"],
            "seen": st.seen}


def release(st: State) -> None:
    from incubator_mxnet_tpu import amp

    for p in st.trainer.net._collect_params_with_prefix().values():
        p._data = None
    st.trainer.params = st.trainer.opt_state = st.trainer.frozen = None
    st.trainer._step_cache.clear()
    del st.trainer, st.pool
    amp.deinit()


def check(ctx: Context, record: dict, control=None) -> list:
    """The numbers compared, each ``{"name", "value", "limit"}``. With a
    ``control`` (a name from the configuration's ``controls``) the
    reference a step lower (``int8``: every product, forward and backward)
    or with a fault planted (``half_batch``) stands in the program's
    place: the run then has to come out not correct."""
    ref = ctx.family("references")
    tr, lim = ctx.config["training"], ctx.config["check"]["train"]
    opt = dict(tr["optimizer_params"])
    pool = train_pool(ctx.traffic, ctx.seed, int(tr["batch"]),
                      int(tr["seq"]), int(ctx.model["vocab_size"]))
    seen = record["seen"]
    n_check = int(lim["steps"])

    def follow(**kw):
        r = ref.TrainReference(ctx.model, ctx.seed, ctx.config["dtype"],
                               opt, rows_per_block=int(lim["rows_per_block"]),
                               **kw)
        losses, grad_norms = [], None
        for i in range(n_check):
            loss, grads = r.step(*pool[i])
            losses.append(loss)
            if i == 0:
                grad_norms = ref.leaf_norms(ref.flatten_leaves(*grads))
            del grads
        change = ref.leaf_norms(ref.flatten_leaves(*r.change()))
        return losses, grad_norms, change

    def numbers(got_losses, got_grads, got_change, want):
        losses, grad_norms, change = want
        med = float(np.median(list(grad_norms.values())))
        # a leaf whose gradient is nought to rounding in the reference (a
        # key's bias under softmax) moves under Adam by round-off alone
        dead = [n for n, v in grad_norms.items() if v < 1e-3 * med]
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(got_losses, losses))
        g_gap, g_at = ref.worst_leaf_gap(got_grads, grad_norms)
        c_gap, c_at = ref.worst_leaf_gap(got_change, change, skip=dead)
        return [
            {"name": "loss_gap_max", "value": float(loss_gap),
             "limit": float(lim["loss_gap_limit"])},
            {"name": "grad_norm_gap_worst_leaf", "value": float(g_gap),
             "limit": float(lim["grad_gap_limit"]), "leaf": g_at},
            {"name": "change_norm_gap_worst_leaf", "value": float(c_gap),
             "limit": float(lim["change_gap_limit"]), "leaf": c_at},
        ], dead

    want = follow()
    out, dead = numbers(seen["losses"], seen["grad_norms"],
                        seen["change_norms"], want)
    record["notes"] = {"losses": seen["losses"], "ref_losses": want[0],
                       "leaves_left_out_of_change": dead}
    if record.get("loop_s"):
        # a host stall shows as one long turn of the feeding loop
        record["notes"]["loop_turn_ms"] = {
            "p50": 1e3 * float(np.median(record["loop_s"])),
            "max": 1e3 * max(record["loop_s"]),
            "host_call_max": 1e3 * max(record["host_step_s"])}
    if control:
        planted = {"int8": {"control": True},
                   "half_batch": {"rows": int(tr["batch"]) // 2}}
        if control not in lim["controls"]:
            raise SystemExit(f"chipbench: {ctx.config['name']} has no "
                             f"control {control!r}, only {lim['controls']}")
        record["notes"]["program"] = {n["name"]: n["value"] for n in out}
        out, _ = numbers(*follow(**planted[control]), want)
    return out
