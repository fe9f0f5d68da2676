"""Runner of the serving path: ``serving.DecodeSession`` under a closed or
an open (``poisson``) loop of clients that stamp every token themselves.

``build`` makes the model with seeded weights, opens the session and warms
the buckets the mix can hit; ``measure`` runs the loop, opens one plain
window of ``seconds`` once the ramp is over, and drains what is in flight
after the close; ``check`` holds a sample of what the window itself served
against the float32 reference.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import stats
from ..harness import Context, build_zoo_model, sleep_through_window
from ..loadgen import RequestMix, arrivals


class State:
    pass


def build(ctx: Context) -> State:
    from incubator_mxnet_tpu import serving

    st = State()
    st.ctx = ctx
    dep = ctx.config["serving"]
    net = build_zoo_model(ctx, trainable=False)
    st.session = serving.DecodeSession(
        net, max_slots=dep["max_slots"], max_len=dep["max_len"],
        prefill_buckets=tuple(dep["prefill_buckets"]),
        max_queue=dep["max_queue"], name=ctx.config["name"],
        artifact_dir=None if ctx.rehearse
        else ctx.cache_dir + "/artifacts")
    ctx.mark("session")
    st.mix = RequestMix(ctx.traffic, ctx.seed, ctx.model["vocab_size"])
    # warm the executables this mix can hit and no others: one request in
    # each prefill bucket (prefill, join), two tokens (the decode step)
    buckets = sorted(dep["prefill_buckets"])
    hit = {min(b for b in buckets if b >= n): n for n, _ in st.mix.pairs}
    rng = np.random.default_rng(0)
    for n in hit.values():
        prompt = rng.integers(0, ctx.model["vocab_size"], n).astype(np.int32)
        st.session.submit(prompt, max_new_tokens=2).result(1200.0)
    ctx.mark("warm")
    return st


def _consume(session, prompt, max_new, rec) -> None:
    """Send one request and stamp each token as the client receives it."""
    try:
        handle = session.submit(prompt, max_new_tokens=max_new)
        for tok in handle:
            rec["stamps"].append(time.perf_counter())
            rec["tokens"].append(tok)
    except Exception as e:      # noqa: BLE001 — a failed request is counted
        rec["failed"] = True
        rec["error"] = repr(e)
    rec["done"] = True


def _new_record(k, prompt, max_new, due=None) -> dict:
    return {"k": k, "prompt": prompt, "prompt_len": int(len(prompt)),
            "max_new": int(max_new), "due": due, "submit": None,
            "stamps": [], "tokens": [], "failed": False, "done": False}


def _closed_loop(st: State, records: list, stop: threading.Event):
    mix, lock, nxt = st.mix, threading.Lock(), [0]
    clients = int(st.ctx.traffic["clients"])
    think_s = float(st.ctx.traffic["think_ms"]) * 1e-3
    ramp = st.ctx.traffic["ramp_tokens"]

    def client(i: int) -> None:
        first = True
        while not stop.is_set():
            with lock:
                k = nxt[0]
                nxt[0] += 1
            prompt, m = mix.request(k)
            if first:
                # the ramp: first requests are short and end at staggered
                # steps, so the callers do not march in step afterwards
                m = int(ramp[0]) + int(ramp[1]) * i
                first = False
            elif think_s:
                # a caller's own time between an answer and its next
                # request: the request then never races the scheduler's
                # step boundary, it always waits for the step in flight
                time.sleep(think_s)
            rec = _new_record(k, prompt, m)
            records.append(rec)
            rec["submit"] = time.perf_counter()
            _consume(st.session, prompt, m, rec)

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"chipbench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    return threads


def _open_loop(st: State, records: list, stop: threading.Event,
               horizon: float):
    """Arrivals on the seeded schedule; a request's clock starts when it
    was DUE, so a stalled generator shows as latency and as lateness."""
    due = arrivals(st.ctx.traffic, horizon)
    workers: list = []

    def dispatcher() -> None:
        t_base = time.perf_counter()
        for k, d in enumerate(due):
            while not stop.is_set():
                wait = t_base + d - time.perf_counter()
                if wait <= 0:
                    break
                time.sleep(min(wait, 0.05))
            if stop.is_set():
                return
            prompt, m = st.mix.request(k)
            rec = _new_record(k, prompt, m, due=t_base + d)
            rec["sent"] = time.perf_counter()
            rec["submit"] = rec["due"]
            records.append(rec)
            w = threading.Thread(target=_consume, daemon=True,
                                 args=(st.session, prompt, m, rec))
            w.start()
            workers.append(w)

    t = threading.Thread(target=dispatcher, daemon=True,
                         name="chipbench-dispatcher")
    t.start()
    return [t], workers


def _counters(session) -> dict:
    m = session.metrics
    return {"steps": m.steps, "tokens": m.tokens, "prefills": m.prefills,
            "decode_seconds": m.decode_seconds,
            "prefill_seconds": m.prefill_seconds,
            "requests": m.requests, "finished": m.finished}


def measure(st: State, seconds: float) -> dict:
    ctx, mix = st.ctx, st.ctx.traffic
    records: list = []
    stop = threading.Event()
    ramp = float(mix["ramp_s"])
    late_workers: list = []
    if mix["kind"] == "closed":
        threads = _closed_loop(st, records, stop)
    elif mix["kind"] == "poisson":
        threads, late_workers = _open_loop(st, records, stop,
                                           ramp + seconds)
    else:
        raise ValueError(f"the serving runner cannot drive a "
                         f"{mix['kind']!r} mix")
    time.sleep(ramp)
    c0, n0 = ctx.compiles.snapshot(), _counters(st.session)
    t0 = time.perf_counter()
    t1 = t0 + seconds
    traced = sleep_through_window(
        ctx.trace_dir, t0, t1, float(mix["trace_after_s"]),
        float(mix["trace_s"]))
    n1, c1 = _counters(st.session), ctx.compiles.snapshot()
    stop.set()
    # what is in flight is drained after the close, so its stamps are
    # whole; an answer that comes late is late, one that never comes fails
    deadline = time.perf_counter() + 90.0
    for t in threads + late_workers:
        t.join(max(0.0, deadline - time.perf_counter()))
    for r in records:
        if not r["done"]:
            r["failed"] = True
            r["error"] = "no answer 90 s after the window closed"
    waits = list(getattr(st.session.metrics, "_queue_waits", []))
    window = stats.serve_window(records, t0, t1)
    return {
        "kind": "serve", "t0": t0, "t1": t1, "requests": records,
        "window": window, "traced": traced,
        "attempted": window["attempted"], "failed": window["failed"],
        "counters": {"start": n0, "end": n1},
        "queue_waits_s": waits[-max(1, n1["prefills"] - n0["prefills"]):],
        "compile_requests_in_window": c1["requests"] - c0["requests"],
        "max_slots": int(ctx.config["serving"]["max_slots"]),
    }


def release(st: State) -> None:
    """Free the program's state on the device before the reference runs."""
    st.session.drain(timeout=30.0)
    st.session.close()
    for p in st.session._block._collect_params_with_prefix().values():
        p._data = None
    del st.session


def check_sample(ctx: Context, record: dict) -> list:
    """The requests the reference reads: whole ones that were submitted in
    the window or had a token delivered in it; all of them, or where they
    are more than the mix's ``check_sample``, the longest and a draw from
    the seed."""
    t0, t1 = record["t0"], record["t1"]
    ok = [r for r in record["requests"]
          if not r["failed"] and r["tokens"]
          and (stats.in_window(r["submit"], t0, t1)
               or any(stats.in_window(t, t0, t1) for t in r["stamps"]))]
    n = int(ctx.traffic["check_sample"])
    if len(ok) <= n:
        return ok
    longest = max(ok, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([ctx.seed & 0xFFFFFFFF, ctx.seed >> 32, 5])
    picks = rng.permutation(len(rest))[:n - 1]
    return [longest] + [rest[i] for i in picks]


def _spread(x) -> dict:
    x = np.asarray(x, np.float64)
    return {"n": int(x.size), "mean": float(x.mean()),
            "over_0": int((x > 0).sum()), "over_0.01": int((x > 0.01).sum()),
            "max": float(x.max())}


def check(ctx: Context, record: dict, control=None) -> list:
    """The numbers compared, each ``{"name", "value", "limit"}``. With a
    ``control`` (a name from the configuration's ``controls``) the tokens
    that control puts first, at the same prompts and tokens, stand in the
    served tokens' place: the run then has to come out not correct."""
    limits = ctx.config["check"]["serve"]
    if control and control not in limits["controls"]:
        raise SystemExit(f"chipbench: {ctx.config['name']} has no control "
                         f"{control!r}, only {limits['controls']}")
    mine = [r for r in record["requests"]
            if stats.in_window(r["submit"], record["t0"], record["t1"])]
    short = sum(1 for r in mine
                if r["failed"] or len(r["tokens"]) != r["max_new"])
    mean = {"name": "served_gap_mean", "value": float("inf"),
            "limit": float(limits["gap_mean_limit"])}
    noise = {"name": "served_logit_noise", "value": float("inf"),
             "limit": float(limits["logit_noise_limit"])}
    out = [{"name": "requests_not_whole", "value": float(short),
            "limit": 0.0}, mean, noise]
    # beside the numbers compared, in untraced runs too: the session's own
    # mean step and prefill times, to tell a slow device from a slow host
    c0, c1 = record["counters"]["start"], record["counters"]["end"]
    notes = record.setdefault("notes", {})
    notes["session_mean_ms"] = {
        k: 1e3 * (c1[s] - c0[s]) / max(1, c1[n] - c0[n])
        for k, s, n in (("decode_step", "decode_seconds", "steps"),
                        ("prefill", "prefill_seconds", "prefills"))}
    sample = check_sample(ctx, record)
    if not sample:
        return out
    pad_to = -(-RequestMix(ctx.traffic, ctx.seed,
                           ctx.model["vocab_size"]).longest() // 64) * 64
    gaps = ctx.family("references").served_gaps(
        ctx.model, ctx.seed, ctx.config["dtype"],
        [(r["prompt"], r["tokens"]) for r in sample], pad_to,
        modes=(control,) if control else (),
        rows_per_block=int(limits["rows_per_block"]))
    got = gaps[control or "served"]
    mean["value"] = float(np.mean(got))
    noise["value"] = stats.noise_scale(gaps["margin"], got > 0)
    record["checked_tokens"] = int(got.size)
    notes["checked_requests"] = len(sample)
    notes["gaps"] = _spread(got)
    return out
