"""End-to-end span tracing tests (ISSUE 19): the ``mxtpu.telemetry.
trace`` spine, the crash-safe flight recorder, and the trigger engine.

Contracts pinned here: sampling off (the default) is a shared no-op —
``span()`` hands back the one ``NULL_SPAN`` and ``start()`` returns
None; a sampled serving request and a sampled decode request each come
out as ONE connected trace across every thread hop, with the decode
TTFT decomposition (queue + prefill + join) summing to the measured
TTFT within 5%; the flight recorder dumps on a chaos-induced fatal AND
on SIGTERM preemption, and a dump torn by a SIGKILL mid-write can never
corrupt an earlier dump; the trigger engine debounces to one capture;
and tracing at 100% sampling performs zero post-warmup recompiles under
the armed watchdog.
"""

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import data as mxdata
from incubator_mxnet_tpu import gluon, parallel, resilience, serving, telemetry
from incubator_mxnet_tpu.config import config
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.model_zoo import get_gpt
from incubator_mxnet_tpu.parallel.superstep import stack_window
from incubator_mxnet_tpu.resilience import chaos
from incubator_mxnet_tpu.telemetry import trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 61


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    chaos.disable()
    telemetry.set_jsonl(None)
    for k in ("MXTPU_TRACE_SAMPLE", "MXTPU_TRACE_DUMP_DIR",
              "MXTPU_TRACE_RING", "MXTPU_TRACE_TRIGGER",
              "MXTPU_TRACE_SLO_MS", "MXTPU_TRACE_TRIGGER_DEBOUNCE_S",
              "MXTPU_TRACE_TRIGGER_CAPTURE_MS",
              "MXTPU_RECOMPILE_WARMUP_STEPS", "MXTPU_TELEMETRY_JSONL",
              "MXTPU_TELEMETRY"):
        config.unset(k)
    telemetry.reset()


def _dense(out=3, inp=4, seed=0):
    np.random.seed(seed)
    net = mx.gluon.nn.Dense(out, in_units=inp)
    net.initialize(mx.initializer.Xavier(rnd_type="gaussian"))
    return net


def _tiny_gpt(seed=0):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = get_gpt("gpt_decoder_tiny", vocab_size=VOCAB, units=32,
                  num_layers=2, max_length=48, dropout=0.1)
    net.initialize(init="xavier")
    return net


def _prompts(ns, seed=7):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (int(n),)).astype(np.int32) for n in ns]


def _trainer(seed=0):
    np.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, in_units=8, activation="relu"),
            nn.Dense(4, in_units=16))
    net.initialize(init="xavier")
    return parallel.SPMDTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=parallel.make_mesh({"data": -1}))


def _pipe(n=64, batch=8, seed=5):
    x = np.random.RandomState(1).rand(n, 8).astype(np.float32)
    y = np.random.RandomState(2).randint(0, 4, (n,)).astype(np.float32)
    return (mxdata.from_ndarray(x, y).shuffle(16, seed=seed)
            .shard(0, 1).batch(batch).prefetch(2))


def _spans(path):
    return [r for r in telemetry.read_jsonl(path)
            if r.get("kind") == "trace" and "span" in r]


def _load_trace_report():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the zero-cost contract: sampling off is a shared no-op
# ---------------------------------------------------------------------------
def test_sampling_off_is_shared_noop(tmp_path):
    path = str(tmp_path / "t.jsonl")
    telemetry.set_jsonl(path)
    assert float(config.get("MXTPU_TRACE_SAMPLE")) == 0.0
    sp = trace.span("unit.work", k=1)
    assert sp is trace.NULL_SPAN, \
        "unsampled span() must hand back the shared NULL_SPAN"
    with sp:
        assert trace.ctx() is None          # NULL spans push nothing
        assert trace.span("unit.child") is trace.NULL_SPAN
    sp.end(extra=1)                          # all no-ops
    assert trace.start("unit.root") is None
    assert trace.record(None, "x", 0.0, 1.0) is None
    assert trace.ring()["spans"] == []
    telemetry.set_jsonl(None)
    assert _spans(path) == []


def test_sampled_span_tree_is_one_trace(tmp_path):
    path = str(tmp_path / "t.jsonl")
    telemetry.set_jsonl(path)
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    with trace.span("root", site="unit") as r:
        with trace.span("child") as c:
            assert c.trace_id == r.trace_id
            with trace.span("grandchild"):
                pass
    telemetry.set_jsonl(None)
    recs = _spans(path)
    assert [x["name"] for x in recs] == ["grandchild", "child", "root"]
    by_name = {x["name"]: x for x in recs}
    assert len({x["trace"] for x in recs}) == 1
    assert by_name["root"]["parent"] is None
    assert by_name["child"]["parent"] == by_name["root"]["span"]
    assert by_name["grandchild"]["parent"] == by_name["child"]["span"]
    assert by_name["root"]["site"] == "unit"
    assert all(x["dur_ms"] >= 0 for x in recs)
    # the flight recorder ring saw the same three spans
    assert [x["name"] for x in trace.ring()["spans"]] \
        == ["grandchild", "child", "root"]


def test_error_spans_carry_the_exception_name():
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("nope")
    rec = trace.ring()["spans"][-1]
    assert rec["name"] == "boom" and rec["error"] == "ValueError"


def test_context_crosses_a_thread_hop_via_use():
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    root = trace.start("front.door")
    carried = trace.ctx() or root.context   # what a queue tuple carries
    got = {}

    def worker():
        assert trace.ctx() is None           # fresh thread, no ambient
        with trace.use(carried):
            with trace.span("hop.work") as w:
                got["trace"] = w.trace_id
                got["parent"] = w.parent_id
        assert trace.ctx() is None           # use() unwound cleanly

    t = threading.Thread(target=worker)
    t.start()
    t.join(10)
    root.end()
    assert got["trace"] == root.trace_id
    assert got["parent"] == root.span_id
    # record() (the batch-shaped hot path) joins the same trace too
    sc = trace.record(root, "post.hoc", 1.0, 2.0)
    assert sc.trace_id == root.trace_id
    # and use(None) is the unsampled no-op
    with trace.use(None):
        assert trace.span("x") is trace.NULL_SPAN \
            or trace.ctx() is None


def test_step_ledger_is_always_on_spans_are_not():
    """The black box records StepMeter commits with sampling OFF —
    that is what makes a crash dump useful in the default config."""
    assert float(config.get("MXTPU_TRACE_SAMPLE")) == 0.0
    meter = telemetry.StepMeter("unit.ledger")
    for _ in range(3):
        with meter.step():
            pass
    ring = trace.ring()
    assert ring["spans"] == []
    ledger = [r for r in ring["steps"] if r.get("site") == "unit.ledger"]
    assert len(ledger) == 3
    assert all("wall_ms" in r or "dur_ms" in r or "wall_s" in r
               or "step" in r for r in ledger)


# ---------------------------------------------------------------------------
# one connected trace per serving request (across the batcher hop)
# ---------------------------------------------------------------------------
def test_serving_request_is_one_connected_trace(tmp_path):
    path = str(tmp_path / "serve.jsonl")
    telemetry.set_jsonl(path)
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    srv = serving.ModelServer(_dense(), buckets=(4,), max_wait_ms=1.0,
                              name="traced")
    try:
        futs = [srv.submit(np.random.rand(4).astype(np.float32))
                for _ in range(3)]
        rows = [f.result(timeout=30) for f in futs]
        assert all(np.asarray(r).shape == (3,) for r in rows)
        tids = [f.trace_id for f in futs]
        assert all(tids), "sampled futures must carry fut.trace_id"
        assert len(set(tids)) == 3, "per-request trace ids"
    finally:
        srv.close()
    telemetry.set_jsonl(None)
    recs = _spans(path)
    for tid in tids:
        tr = [r for r in recs if r["trace"] == tid]
        names = {r["name"] for r in tr}
        assert {"serving.request", "queue", "dispatch", "depad"} <= names
        # connectivity: every span's parent is another span of the SAME
        # trace (or the root) — the hop onto the worker lost nothing
        ids = {r["span"] for r in tr}
        roots = [r for r in tr if r["parent"] is None]
        assert len(roots) == 1 and roots[0]["name"] == "serving.request"
        for r in tr:
            assert r["parent"] is None or r["parent"] in ids
        assert roots[0].get("ok") is True


# ---------------------------------------------------------------------------
# decode: one connected trace + the TTFT decomposition
# ---------------------------------------------------------------------------
def test_decode_trace_connected_and_ttft_decomposes(tmp_path):
    path = str(tmp_path / "decode.jsonl")
    telemetry.set_jsonl(path)
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    net = _tiny_gpt()
    handles = []
    with serving.DecodeSession(net, max_slots=3, max_len=48,
                               prefill_buckets=(8, 16),
                               name="traced") as sess:
        sess.warmup()
        for p, n in zip(_prompts([5, 12, 7], seed=3), (6, 4, 8)):
            handles.append(sess.submit(p, max_new_tokens=n))
        for h in handles:
            h.result(120)
    telemetry.set_jsonl(None)
    assert all(h.trace_id for h in handles)
    recs = _spans(path)
    for h in handles:
        tr = [r for r in recs if r["trace"] == h.trace_id]
        by_name = {r["name"]: r for r in tr}
        assert {"decode.request", "queue", "prefill", "join",
                "first_step", "steps"} <= set(by_name)
        root = by_name["decode.request"]
        assert root["parent"] is None
        ids = {r["span"] for r in tr}
        for r in tr:
            assert r["parent"] is None or r["parent"] in ids
        # the TTFT decomposition: contiguous perf_counter segments must
        # sum to the measured TTFT within 5%
        ttft = float(root["ttft_ms"])
        segs = sum(float(by_name[k]["dur_ms"])
                   for k in ("queue", "prefill", "join"))
        assert ttft > 0
        assert abs(segs - ttft) <= 0.05 * ttft + 0.05, \
            f"queue+prefill+join={segs:.3f}ms vs ttft={ttft:.3f}ms"
        assert by_name["steps"]["tokens"] == root["new_tokens"]

    # the report tool agrees: decomposition residual ~0 at the median
    rep = _load_trace_report()
    trs = [t for t in rep.assemble(recs).values()
           if t["root"] is not None
           and t["root"]["name"] == "decode.request"]
    d = rep.ttft_decomposition(trs)
    assert d is not None and d["n"] == 3
    assert d["residual"]["p50"] <= 0.05 * d["ttft_ms"]["p50"] + 0.05
    out = rep.summarize(path)
    assert "decode.request" in out and "prefill" in out


def test_trace_report_summary_and_compare(tmp_path):
    """trace_report renders per-root breakdowns from a JSONL run and
    --compare diffs two runs without crashing on partial overlap."""
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    for path, scale in ((a, 1), (b, 3)):
        telemetry.set_jsonl(path)
        for _ in range(4):
            with trace.span("unit.request"):
                with trace.span("work"):
                    time.sleep(0.001 * scale)
        telemetry.set_jsonl(None)
    rep = _load_trace_report()
    out = rep.summarize(a)
    assert "unit.request" in out and "work" in out
    assert rep.main([a]) == 0
    assert rep.main(["--compare", a, b]) == 0
    cmp_out = rep.compare(b, a)
    assert "unit.request" in cmp_out


# ---------------------------------------------------------------------------
# flight recorder: dump on fatal, dump on preempt, torn dumps harmless
# ---------------------------------------------------------------------------
def test_flight_dump_on_chaos_fatal(tmp_path):
    config.set("MXTPU_TRACE_DUMP_DIR", str(tmp_path / "flight"))
    mx.random.seed(42)
    tr = _trainer()
    pipe = _pipe()
    mgr = resilience.CheckpointManager(str(tmp_path / "ckpt"))
    sup = resilience.Supervisor(tr, mgr, checkpoint_every=5,
                                final_checkpoint=False,
                                backoff_base_s=0.001)
    sup.max_restarts = 0
    chaos.configure({"step": {"at_calls": [8], "transient": False}})
    with pytest.raises(resilience.InjectedFault):
        sup.run(pipe, steps=10)
    chaos.disable()
    pipe.close()
    dumps = glob.glob(str(tmp_path / "flight" / "flight-*-fatal.json"))
    assert len(dumps) == 1, "one flight dump for the fatal"
    with open(dumps[0]) as f:
        payload = json.load(f)
    assert payload["reason"] == "fatal"
    # the always-on step ledger captured the steps leading to the crash
    sites = {r.get("site") for r in payload["steps"]}
    assert "spmd.step" in sites
    assert isinstance(payload["traceEvents"], list)


def test_flight_dump_on_sigterm_preempt(tmp_path):
    config.set("MXTPU_TRACE_DUMP_DIR", str(tmp_path / "flight"))
    mx.random.seed(42)
    tr = _trainer()
    pipe = _pipe()
    mgr = resilience.CheckpointManager(str(tmp_path / "ckpt"))
    sup = resilience.Supervisor(tr, mgr)
    sup.install_preemption_handler()
    try:
        orig_step = tr.step

        def stepper(*args):
            if sup.step_num == 3:      # the cloud preemption notice
                os.kill(os.getpid(), signal.SIGTERM)
            return orig_step(*args)

        sup._step_fn = stepper
        with pytest.raises(resilience.Preempted):
            sup.run(pipe, steps=50)
    finally:
        sup.uninstall_preemption_handler()
        pipe.close()
    dumps = glob.glob(str(tmp_path / "flight" / "flight-*-preempt.json"))
    assert len(dumps) == 1
    with open(dumps[0]) as f:
        payload = json.load(f)
    assert payload["reason"] == "preempt"
    assert payload["steps"], "step ledger must ride the preempt dump"
    # the final synchronous checkpoint still landed (dump didn't break it)
    assert mgr.newest_valid() is not None


def test_dump_files_are_sequence_numbered_never_overwritten(tmp_path):
    config.set("MXTPU_TRACE_DUMP_DIR", str(tmp_path))
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    with trace.span("unit.a"):
        pass
    p1 = trace.dump("manual")
    with trace.span("unit.b"):
        pass
    p2 = trace.dump("manual")
    assert p1 != p2 and os.path.exists(p1) and os.path.exists(p2)
    with open(p1) as f:
        first = json.load(f)
    assert [s["name"] for s in first["spans"]] == ["unit.a"], \
        "a later dump must not rewrite an earlier one"


def test_kill_during_dump_never_corrupts_earlier_dumps(tmp_path):
    """SIGKILL a process that dumps in a tight loop: whatever survives
    on disk, every visible ``flight-*.json`` parses — the torn write
    only ever lands in the ``.tmp`` staging name."""
    dump_dir = str(tmp_path / "flight")
    script = tmp_path / "dumper.py"
    script.write_text(
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from incubator_mxnet_tpu.config import config\n"
        "from incubator_mxnet_tpu.telemetry import trace\n"
        f"config.set('MXTPU_TRACE_DUMP_DIR', {dump_dir!r})\n"
        "config.set('MXTPU_TRACE_SAMPLE', 1.0)\n"
        "for i in range(400):\n"
        "    trace.span('pad.%d' % i, payload='x' * 256).end()\n"
        "    trace.flight_step({'site': 's', 'step': i})\n"
        "while True:\n"
        "    trace.dump('loop')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if len(glob.glob(os.path.join(dump_dir, "flight-*.json"))) >= 3:
                break
            time.sleep(0.02)
        else:
            pytest.fail("dumper produced no dumps before the deadline")
        proc.kill()                    # SIGKILL mid-write, eventually
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(30)
    paths = sorted(glob.glob(os.path.join(dump_dir, "flight-*.json")))
    assert len(paths) >= 3
    for p in paths:                    # every published dump is whole
        with open(p) as f:
            payload = json.load(f)
        assert payload["reason"] == "loop"
        assert len(payload["steps"]) > 0


# ---------------------------------------------------------------------------
# trigger engine
# ---------------------------------------------------------------------------
def test_slo_breach_fires_one_debounced_capture(tmp_path):
    path = str(tmp_path / "trig.jsonl")
    telemetry.set_jsonl(path)
    config.set("MXTPU_TRACE_DUMP_DIR", str(tmp_path / "flight"))
    config.set("MXTPU_TRACE_TRIGGER", "1")
    config.set("MXTPU_TRACE_SLO_MS", 10.0)
    config.set("MXTPU_TRACE_TRIGGER_DEBOUNCE_S", 600.0)
    config.set("MXTPU_TRACE_TRIGGER_CAPTURE_MS", 20.0)
    trace.note_latency("serving.unit", 0.005)    # under SLO: no fire
    assert trace.trigger("recompile", site="unit") is True
    # debounced + single-flight: the second ask is refused
    assert trace.trigger("recompile", site="unit") is False
    trace.note_latency("serving.unit", 0.5)      # breach, but debounced
    deadline = time.monotonic() + 60
    rec = None
    while time.monotonic() < deadline and rec is None:
        time.sleep(0.05)
        recs = [r for r in telemetry.read_jsonl(path)
                if r.get("event") == "trigger"]
        rec = recs[0] if recs else None
    telemetry.set_jsonl(None)
    assert rec is not None, "capture thread never completed"
    assert rec["reason"] == "recompile" and rec["captured"] is True
    assert os.path.isdir(rec["profile_dir"]), \
        "profiler capture directory must exist"
    assert len([r for r in telemetry.read_jsonl(path)
                if r.get("event") == "trigger"]) == 1


def test_trigger_off_and_no_dump_dir_are_noops(tmp_path):
    assert trace.trigger("slo") is False          # knob off (default)
    config.set("MXTPU_TRACE_TRIGGER", "1")
    assert trace.trigger("slo") is False          # no dump dir
    trace.note_latency("serving.unit", 99.0)      # must not raise


# ---------------------------------------------------------------------------
# the recompile contract: tracing at 100% adds zero compiles
# ---------------------------------------------------------------------------
def test_traced_serving_and_superstep_zero_postwarmup_recompiles():
    config.set("MXTPU_RECOMPILE_WARMUP_STEPS", 2)
    telemetry.reset()                  # re-arm with the short warmup
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    wd = telemetry.get_watchdog()
    assert wd is not None

    # traced serving: warmup waves, then steady state must not compile
    srv = serving.ModelServer(_dense(), buckets=(4,), max_wait_ms=1.0,
                              name="wdog")
    try:
        for _ in range(4):             # past the warmup budget
            srv.predict(np.random.rand(4).astype(np.float32), timeout=30)
        before = wd.compile_count
        futs = [srv.submit(np.random.rand(4).astype(np.float32))
                for _ in range(6)]
        for f in futs:
            f.result(timeout=30)
        assert wd.compile_count == before, \
            "traced steady-state serving compiled something"
    finally:
        srv.close()

    # traced superstep: same executable across post-warmup windows
    mx.random.seed(42)
    tr = _trainer()
    rs = np.random.RandomState(0)

    def window():
        bs = [(rs.rand(8, 8).astype(np.float32),
               rs.randint(0, 4, (8,)).astype(np.float32))
              for _ in range(3)]
        win = stack_window(bs)
        return [win[0]], [win[1]]
    for _ in range(3):                 # warmup supersteps
        tr.run_superstep(*window())
    before = wd.compile_count
    for _ in range(3):
        tr.run_superstep(*window())
    assert wd.compile_count == before, \
        "traced steady-state superstep compiled something"
    assert not wd.flagged(), [e.__dict__ for e in wd.flagged()]


# ---------------------------------------------------------------------------
# /healthz endpoint (satellite: 200 / 503 / 404)
# ---------------------------------------------------------------------------
def test_healthz_endpoint_aggregates_and_404s():
    from urllib.error import HTTPError
    from urllib.request import urlopen

    srv = telemetry.MetricsHTTPServer(port=0, host="127.0.0.1").start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        # no providers: the process is up and exporting => ready
        with urlopen(f"{base}/healthz", timeout=10) as resp:
            assert resp.status == 200
            body = json.loads(resp.read())
        assert body["status"] == "ok"

        telemetry.register_health("m.ok", lambda: {"ready": True,
                                                   "state": "serving"})
        telemetry.register_health("m.bad", lambda: {"ready": False})
        with pytest.raises(HTTPError) as ei:
            urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "unready"
        assert body["providers"]["m.ok"]["ready"] is True
        assert body["providers"]["m.bad"]["ready"] is False

        # a provider that raises reports unready, never breaks the probe
        def _boom():
            raise RuntimeError("probe exploded")

        telemetry.register_health("m.bad", _boom)
        with pytest.raises(HTTPError) as ei:
            urlopen(f"{base}/healthz", timeout=10)
        assert ei.value.code == 503
        assert "RuntimeError" in json.loads(
            ei.value.read())["providers"]["m.bad"]["error"]

        telemetry.unregister_health("m.bad")
        with urlopen(f"{base}/healthz", timeout=10) as resp:
            assert resp.status == 200

        with pytest.raises(HTTPError) as ei:
            urlopen(f"{base}/nope", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


def test_decode_session_registers_health_provider():
    net = _tiny_gpt()
    with serving.DecodeSession(net, max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="hz") as sess:
        ready, payload = telemetry.healthz_status()
        assert "decode.hz" in payload["providers"]
    ready, payload = telemetry.healthz_status()
    assert "decode.hz" not in payload["providers"], \
        "close() must unregister the probe"


# ---------------------------------------------------------------------------
# (site, meter) gauge keying (satellite)
# ---------------------------------------------------------------------------
def test_two_meters_on_one_site_keep_separate_gauges():
    m1 = telemetry.StepMeter("unit.shared")
    m2 = telemetry.StepMeter("unit.shared")
    with m1.step():
        time.sleep(0.002)
    with m2.step():
        pass
    reg = telemetry.get_registry()
    fams = {name: insts for name, _kind, _help, insts in reg.collect()}
    gauges = [i for i in fams.get("mxtpu_step_time_ema_seconds", [])
              if dict(i.labels).get("site") == "unit.shared"]
    assert len(gauges) == 2, \
        "each meter must own its (site, meter)-keyed EMA gauge"
    meters = {dict(i.labels).get("meter") for i in gauges}
    assert len(meters) == 2 and None not in meters
    # the shared-site histogram still aggregates both meters' steps
    h = reg.find("mxtpu_step_seconds", site="unit.shared")
    assert h is not None and h.count == 2


# ---------------------------------------------------------------------------
# phases and the turn ledger (ISSUE 25)
# ---------------------------------------------------------------------------
STEP_PHASES = {"sched", "idle", "h2d", "dispatch", "fence", "meter",
               "deliver", "finish"}


def _ledger(site):
    return [r for r in trace.ring()["steps"] if r.get("site") == site]


def test_phases_land_on_one_host_line_of_the_xplane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    with serving.DecodeSession(_tiny_gpt(), max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="xp") as sess:
        sess.submit(_prompts([5])[0], max_new_tokens=2).result(120)
        jax.profiler.start_trace(str(tmp_path))
        try:
            s0 = sess.metrics.steps
            sess.submit(_prompts([6])[0], max_new_tokens=5).result(120)
            steps = sess.metrics.steps - s0
        finally:
            jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert len(found) == 1
    lines = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            names = [ev.name for ev in line.events
                     if ev.name.startswith("mxtpu/decode.xp/")]
            if names:
                lines.append(names)
    assert len(lines) == 1, "every phase on the scheduler thread's line"
    assert steps >= 4
    for phase in ("h2d", "dispatch", "fence", "deliver", "finish"):
        assert lines[0].count(f"mxtpu/decode.xp/{phase}") >= steps
    assert "mxtpu/decode.xp/join" in lines[0]


def test_ledger_holds_every_turn_with_sampling_off():
    assert float(config.get("MXTPU_TRACE_SAMPLE")) == 0.0
    t_open = time.perf_counter()
    with serving.DecodeSession(_tiny_gpt(), max_slots=2, max_len=48,
                               prefill_buckets=(8, 16), name="led") as sess:
        sess.warmup()
        for p, n in zip(_prompts([5, 12, 7], seed=3), (4, 3, 5)):
            sess.submit(p, max_new_tokens=n).result(120)
    elapsed = time.perf_counter() - t_open
    recs = _ledger("decode.led")
    steps = [r for r in recs if r["kind"] == "step"]
    prefills = [r for r in recs if r["kind"] == "prefill"]
    assert trace.ring()["spans"] == []
    assert len(steps) == sess.metrics.steps > 0
    assert len(prefills) == sess.metrics.prefills == 3
    for r in steps:
        assert set(r["phases"]) == STEP_PHASES and r["active"] >= 1
        # one stream at a time in two slots: every step is serial, from
        # its dispatch to its fetch (the meter's commit comes after it)
        inside = sum(r["phases"][k] for k in ("h2d", "dispatch", "fence"))
        assert r["ahead"] == 0 and 0 < inside <= r["dur_s"]
    for r in prefills:
        assert set(r["phases"]) == {"dispatch", "join", "fence"}
        assert 0 < sum(r["phases"].values()) <= r["dur_s"]
        assert r["bucket"] in (8, 16) and r["prompt_len"] <= r["bucket"]
        assert r["queue_wait_s"] >= 0
    # the records are stamped on one clock, in order, and the phases of
    # one thread never overlap: together they fit into its lifetime
    t0s = [r["t0"] for r in recs]
    assert t0s == sorted(t0s)
    assert sum(sum(r["phases"].values()) for r in recs) <= elapsed
    # dur_s is the very interval the session's own counters sum
    assert sum(r["dur_s"] for r in steps) == pytest.approx(
        sess.metrics.decode_seconds, abs=1e-6)
    assert sum(r["dur_s"] for r in prefills) == pytest.approx(
        sess.metrics.prefill_seconds, abs=1e-6)
    assert sess.metrics.queue_waits() == pytest.approx(
        [r["queue_wait_s"] for r in prefills])


def test_phases_construct_no_span(monkeypatch):
    made = []
    init = trace.Span.__init__

    def counting(self, *a, **kw):
        made.append(a)
        init(self, *a, **kw)

    monkeypatch.setattr(trace.Span, "__init__", counting)
    with serving.DecodeSession(_tiny_gpt(), max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="nospan") as sess:
        sess.submit(_prompts([5])[0], max_new_tokens=3).result(120)
    tr = _trainer()
    tr.step(np.random.rand(8, 8).astype(np.float32),
            np.random.randint(0, 4, (8,)).astype(np.float32))
    assert _ledger("decode.nospan") and _ledger("spmd.step")
    assert made == []


def test_an_empty_session_is_idle_not_scheduling():
    with serving.DecodeSession(_tiny_gpt(), max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="idle") as sess:
        sess.submit(_prompts([5])[0], max_new_tokens=2).result(120)
        time.sleep(0.3)
        sess.submit(_prompts([6])[0], max_new_tokens=2).result(120)
    steps = [r for r in _ledger("decode.idle") if r["kind"] == "step"]
    assert len(steps) == 2
    waited = steps[1]["phases"]
    assert waited["idle"] >= 0.25
    assert waited["sched"] < 0.05


def test_telemetry_off_leaves_the_ledger_empty():
    config.set("MXTPU_TELEMETRY", False)
    telemetry.reset()
    with serving.DecodeSession(_tiny_gpt(), max_slots=2, max_len=48,
                               prefill_buckets=(8,), name="off") as sess:
        toks = sess.submit(_prompts([5])[0], max_new_tokens=3).result(120)
    assert len(toks) == 3
    assert trace.ring()["steps"] == []


def test_spmd_step_writes_its_four_phases():
    tr = _trainer()
    rs = np.random.RandomState(0)
    for _ in range(3):
        tr.step(rs.rand(8, 8).astype(np.float32),
                rs.randint(0, 4, (8,)).astype(np.float32))
    recs = _ledger("spmd.step")
    assert [r["step"] for r in recs] == [1, 2, 3]
    for r in recs:
        assert r["kind"] == "step" and r["t0"] > 0
        assert set(r["phases"]) == {"h2d", "rng", "dispatch", "meter"}
        assert all(v > 0 for v in r["phases"].values())
        assert sum(r["phases"].values()) <= r["dur_s"]
    # a meter with no turn still stamps its record
    meter = telemetry.StepMeter("unit.noturn")
    with meter.step():
        pass
    (rec,) = _ledger("unit.noturn")
    assert rec["t0"] > 0 and "phases" not in rec


def test_ring_default_keeps_two_minutes_of_turns():
    assert trace.ring_capacity() == int(config.get("MXTPU_TRACE_RING"))
    assert trace.ring_capacity() >= 12000
