"""mxtpu.telemetry tests (ISSUE 4): registry semantics and
thread-safety, Prometheus exposition round-trip, JSONL sink replay,
recompile watchdog (induced shape-change + FusedStep-loop attribution,
zero false positives over 50 steady steps), disabled-mode no-op
instruments, profiler counter/dump regressions, /metrics HTTP
exporter, and the telemetry_report CLI."""

import json
import os
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, profiler, telemetry
from incubator_mxnet_tpu.config import config
from incubator_mxnet_tpu.gluon import nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def clean_telemetry():
    """Fresh registry/watchdog/sinks before and after each test using
    this fixture (the package keeps process-global state by design)."""
    telemetry.reset()
    yield
    for k in ("MXTPU_TELEMETRY", "MXTPU_TELEMETRY_MFU",
              "MXTPU_RECOMPILE_WARMUP_STEPS", "MXTPU_TELEMETRY_JSONL"):
        config.unset(k)
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_identity_and_values(clean_telemetry):
    r = telemetry.get_registry()
    c = r.counter("t_ops_total", "ops", site="a")
    assert r.counter("t_ops_total", site="a") is c
    assert r.counter("t_ops_total", site="b") is not c
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("t_depth")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5
    # a name cannot change kind
    with pytest.raises(ValueError):
        r.gauge("t_ops_total", site="a")
    with pytest.raises(ValueError):
        r.counter("t_depth")


def test_registry_histogram_buckets_and_quantiles(clean_telemetry):
    h = telemetry.get_registry().histogram(
        "t_lat_seconds", buckets=(0.001, 0.01, 0.1, 1.0))
    for v in (0.0005, 0.002, 0.003, 0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count == 6
    assert abs(h.sum - 2.5555) < 1e-9
    cum = dict(h.cumulative())
    assert cum[0.001] == 1
    assert cum[0.01] == 3
    assert cum[0.1] == 4
    assert cum[1.0] == 5
    assert cum[float("inf")] == 6
    # p50 (target: 3rd of 6 observations) interpolates inside (0.001, 0.01]
    assert 0.001 <= h.quantile(50) <= 0.01
    # p99 lands in the +Inf bucket -> max observed
    assert h.quantile(99) == 2.0


def test_registry_thread_safety_under_concurrent_increments(
        clean_telemetry):
    r = telemetry.get_registry()
    c = r.counter("t_conc_total")
    h = r.histogram("t_conc_seconds", buckets=(0.5,))
    n_threads, n_iter = 8, 2000

    def worker():
        for _ in range(n_iter):
            c.inc()
            h.observe(0.25)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter
    assert dict(h.cumulative())[0.5] == n_threads * n_iter


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------
def _parse_prometheus(text):
    """Minimal text-format parser: {'name{labels}': value}; types in a
    second dict."""
    values, types = {}, {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        values[key] = float(val)
    return values, types


def test_prometheus_exposition_round_trips(clean_telemetry):
    r = telemetry.get_registry()
    r.counter("t_req_total", "requests", model="m").inc(41)
    r.gauge("t_depth").set(3)
    h = r.histogram("t_lat_seconds", buckets=(0.01, 0.1))
    h.observe(0.005)
    h.observe(0.05)
    h.observe(5.0)
    vals, types = _parse_prometheus(telemetry.prometheus_text())
    assert vals['t_req_total{model="m"}'] == 41
    assert types["t_req_total"] == "counter"
    assert vals["t_depth"] == 3
    assert types["t_lat_seconds"] == "histogram"
    assert vals['t_lat_seconds_bucket{le="0.01"}'] == 1
    assert vals['t_lat_seconds_bucket{le="0.1"}'] == 2
    assert vals['t_lat_seconds_bucket{le="+Inf"}'] == 3
    assert vals["t_lat_seconds_count"] == 3
    assert abs(vals["t_lat_seconds_sum"] - 5.055) < 1e-9


def test_prometheus_sanitizes_profiler_counter_names(clean_telemetry):
    c = profiler.counter("serving/modelx/queue_depth")
    c.set_value(9)
    vals, _ = _parse_prometheus(telemetry.prometheus_text())
    assert vals["serving_modelx_queue_depth"] == 9


def test_metrics_http_server_serves_exposition(clean_telemetry):
    from urllib.request import urlopen

    telemetry.get_registry().counter("t_http_total").inc(5)
    srv = telemetry.MetricsHTTPServer(port=0, host="127.0.0.1").start()
    try:
        body = urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read()
        vals, _ = _parse_prometheus(body.decode())
        assert vals["t_http_total"] == 5
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------
def test_jsonl_sink_replay(tmp_path, clean_telemetry):
    path = str(tmp_path / "run.jsonl")
    telemetry.set_jsonl(path)
    telemetry.jsonl_emit({"kind": "step", "site": "s", "wall_ms": 1.5})
    telemetry.jsonl_emit({"kind": "bench", "metric": "m", "value": 2})
    telemetry.set_jsonl(None)
    with open(path, "a") as f:        # torn final line must be tolerated
        f.write('{"kind": "ste')
    recs = telemetry.read_jsonl(path)
    assert recs[0]["kind"] == "run_start" and "pid" in recs[0]
    recs = [r for r in recs if r["kind"] != "run_start"]
    assert len(recs) == 2
    assert recs[0]["site"] == "s" and "ts" in recs[0]
    assert recs[1]["metric"] == "m"


def test_step_meter_emits_jsonl_and_instruments(tmp_path, clean_telemetry):
    path = str(tmp_path / "steps.jsonl")
    telemetry.set_jsonl(path)
    meter = telemetry.StepMeter("unit.meter")
    for _ in range(4):
        with meter.step(h2d_bytes=100, dispatches=2):
            time.sleep(0.001)
    telemetry.set_jsonl(None)
    recs = [r for r in telemetry.read_jsonl(path) if r["kind"] == "step"]
    assert len(recs) == 4
    assert recs[-1]["step"] == 4
    assert recs[-1]["wall_ms"] >= 0.5
    assert "ema_ms" in recs[-1]
    r = telemetry.get_registry()
    assert r.find("mxtpu_step_total", site="unit.meter").value == 4
    assert r.find("mxtpu_h2d_bytes_total", site="unit.meter").value == 400
    assert r.find("mxtpu_step_dispatches_total",
                  site="unit.meter").value == 8
    assert meter.ema_seconds is not None and meter.ema_seconds > 0


# ---------------------------------------------------------------------------
# recompile watchdog
# ---------------------------------------------------------------------------
def test_watchdog_flags_induced_shape_change_and_stays_silent(
        clean_telemetry):
    import jax
    import jax.numpy as jnp

    wd = telemetry.RecompileWatchdog(warmup_steps=3).start()
    try:
        f = jax.jit(lambda x: x * 2.0 + 1.0)
        for _ in range(50):
            with telemetry.attribute("unit.loop"):
                f(jnp.ones(16)).block_until_ready()
            wd.note_step("unit.loop")
        # 50 steady-state steps: the single warmup compile (step 0) must
        # not be flagged, and no other compile fired
        assert wd.flagged("unit.loop") == []
        assert wd.steps("unit.loop") == 50
        with telemetry.attribute("unit.loop", detail="shape=(32,)"):
            f(jnp.ones(32)).block_until_ready()      # induced recompile
        flagged = wd.flagged("unit.loop")
        assert len(flagged) >= 1
        ev = flagged[-1]
        assert ev.site == "unit.loop"
        assert ev.detail == "shape=(32,)"
        assert ev.step == 50
    finally:
        wd.stop()


def test_watchdog_attribution_is_innermost_scope(clean_telemetry):
    import jax
    import jax.numpy as jnp

    wd = telemetry.RecompileWatchdog(warmup_steps=0).start()
    try:
        for _ in range(2):
            wd.note_step("outer")
            wd.note_step("inner")
        with telemetry.attribute("outer"):
            with telemetry.attribute("inner"):
                jax.jit(lambda x: x + 3.0)(jnp.ones(7)).block_until_ready()
        assert wd.flagged("inner")
        assert not wd.flagged("outer")
    finally:
        wd.stop()


def test_watchdog_fused_step_loop_detects_hyper_drift(clean_telemetry):
    """The acceptance loop: a FusedStep trainer runs steady steps with
    zero flags, then a mid-training hyperparameter mutation (part of the
    fused executable's cache key) forces a recompile that is detected
    and attributed to trainer.step."""
    config.set("MXTPU_RECOMPILE_WARMUP_STEPS", 5)
    telemetry.reset()                 # watchdog re-arms with warmup=5

    net = nn.Dense(4, in_units=8)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.array(np.random.rand(2, 8).astype(np.float32))

    def one_step():
        with mx.autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(2)

    for _ in range(20):
        one_step()
    wd = telemetry.get_watchdog()
    assert wd is not None
    assert wd.steps("trainer.step") == 20
    assert wd.flagged("trainer.step") == [], \
        "steady-state steps must produce zero false positives"

    # induced drift: momentum is trace-time hyper-key material, so the
    # next step builds (and compiles) a NEW fused executable
    trainer._optimizer.momentum = 0.5
    one_step()
    flagged = wd.flagged("trainer.step")
    assert len(flagged) >= 1
    assert flagged[-1].site == "trainer.step"
    assert flagged[-1].step >= 20
    reg = telemetry.get_registry()
    ctr = reg.find("mxtpu_recompiles_flagged_total", site="trainer.step")
    assert ctr is not None and ctr.value >= 1


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------
def test_disabled_mode_instruments_are_shared_noops(clean_telemetry):
    config.set("MXTPU_TELEMETRY", False)
    c = telemetry.counter("t_off_total")
    g = telemetry.gauge("t_off_gauge")
    h = telemetry.histogram("t_off_hist")
    # one shared singleton, no per-call state, nothing registered
    assert c is telemetry.NULL and g is telemetry.NULL \
        and h is telemetry.NULL
    assert c.inc() is None and c.inc(5) is None
    assert g.set(3) is None and h.observe(1.0) is None
    assert c.value == 0 and h.quantile(99) == 0.0
    assert list(telemetry.get_registry().collect()) == []
    assert telemetry.get_watchdog() is None

    meter = telemetry.StepMeter("t.off")
    ctx1 = meter.step(h2d_bytes=10)
    ctx2 = meter.step()
    assert ctx1 is ctx2               # the shared null context, no alloc
    with ctx1 as rec:
        assert rec is None
    assert list(telemetry.get_registry().collect()) == []


def test_disabled_mode_trainer_step_still_works(clean_telemetry):
    config.set("MXTPU_TELEMETRY", False)
    net = nn.Dense(3, in_units=5)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = mx.nd.array(np.random.rand(2, 5).astype(np.float32))
    for _ in range(2):
        with mx.autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        trainer.step(2)
    assert list(telemetry.get_registry().collect()) == []


# ---------------------------------------------------------------------------
# serving metrics share the registry
# ---------------------------------------------------------------------------
def test_serving_metrics_mirror_into_shared_registry(clean_telemetry):
    from incubator_mxnet_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics("tmodel")
    m.observe_queue_depth(4)
    m.observe_batch(8)
    m.observe_latency(0.02)
    m.observe_latency(0.04)
    m.observe_reject()
    m.cache_miss()
    m.observe_compile(0.5)
    r = telemetry.get_registry()
    assert r.find("mxtpu_serving_queue_depth", model="tmodel").value == 4
    assert r.find("mxtpu_serving_batches_total", model="tmodel").value == 1
    assert r.find("mxtpu_serving_requests_total",
                  model="tmodel").value == 2
    assert r.find("mxtpu_serving_rejected_total",
                  model="tmodel").value == 1
    assert r.find("mxtpu_serving_compile_seconds_total",
                  model="tmodel").value == 0.5
    lat = r.find("mxtpu_serving_request_latency_seconds", model="tmodel")
    assert lat.count == 2
    # the local snapshot stays authoritative and agrees
    snap = m.snapshot()
    assert snap["requests"] == 2 and snap["queue_depth"] == 4


# ---------------------------------------------------------------------------
# profiler regressions (ISSUE 4 satellite)
# ---------------------------------------------------------------------------
def test_profiler_dumps_reset_clears_counters(clean_telemetry):
    c = profiler.counter("t_prof_reset")
    c.set_value(7)
    c.increment(3)
    table = profiler.dumps()
    assert "t_prof_reset" in table and "10" in table
    profiler.dumps(reset=True)
    assert c._value == 0, "reset=True must clear counters, not only records"
    assert profiler._state["records"] == []
    c.increment(2)                    # counter object stays usable
    assert c._value == 2


def test_profiler_dump_honors_filename_set_after_start(tmp_path,
                                                       clean_telemetry):
    profiler.set_config(filename=str(tmp_path / "before.json"))
    profiler.set_state("run")
    with profiler.scope("late_rename_scope"):
        pass
    # config change while ALREADY running must win at dump time
    profiler.set_config(filename=str(tmp_path / "after.json"))
    profiler.set_state("stop")
    out = profiler.dump()
    assert out == str(tmp_path / "after.json")
    assert os.path.exists(out)
    with open(out) as f:
        trace = json.load(f)
    assert "late_rename_scope" in {e["name"] for e in trace["traceEvents"]}


def test_step_meter_correlates_into_profiler_trace(tmp_path,
                                                   clean_telemetry):
    profiler.set_config(filename=str(tmp_path / "t.json"))
    profiler.set_state("run")
    meter = telemetry.StepMeter("unit.corr")
    with meter.step():
        time.sleep(0.001)
    profiler.set_state("stop")
    names = {e["name"] for e in profiler._state["records"]}
    assert "telemetry::unit.corr::step" in names


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------
def _load_report_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(REPO, "tools",
                                         "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_telemetry_report_summary_and_compare(tmp_path, clean_telemetry):
    rep = _load_report_mod()
    a = tmp_path / "a.jsonl"
    b = tmp_path / "b.jsonl"
    with open(a, "w") as f:
        for i in range(10):
            f.write(json.dumps({"kind": "step", "site": "spmd.step",
                                "step": i + 1, "wall_ms": 10.0 + i,
                                "mfu_pct": 40.0,
                                "mem_peak_bytes": 1 << 20}) + "\n")
        f.write(json.dumps({"kind": "recompile", "site": "spmd.step",
                            "step": 9, "event": "e"}) + "\n")
        f.write(json.dumps({"kind": "bench", "metric": "resnet50",
                            "value": 800.0, "unit": "img/s"}) + "\n")
    with open(b, "w") as f:
        for i in range(10):
            f.write(json.dumps({"kind": "step", "site": "spmd.step",
                                "step": i + 1, "wall_ms": 20.0 + i,
                                "mfu_pct": 20.0}) + "\n")
        f.write(json.dumps({"kind": "bench", "metric": "resnet50",
                            "value": 400.0, "unit": "img/s"}) + "\n")

    summary = rep.summarize(str(a))
    assert "spmd.step" in summary
    assert "1.0 MiB" in summary               # memory high-water
    assert "resnet50" in summary
    lines = [ln for ln in summary.splitlines() if "spmd.step" in ln]
    assert any("1" == ln.split()[-1] for ln in lines), \
        f"recompile count column missing: {lines}"

    diff = rep.compare(str(a), str(b))
    assert "bench/resnet50" in diff
    assert "-50.0%" in diff                   # 800 -> 400
    assert "step/spmd.step/p50_ms" in diff
    # CLI surface
    assert rep.main([str(a)]) == 0
    assert rep.main(["--compare", str(a), str(b)]) == 0


def test_telemetry_report_selects_newest_run(tmp_path, clean_telemetry):
    """The sink appends and writes a run_start boundary per open; the
    report must not merge a reused file's runs into one step count."""
    rep = _load_report_mod()
    path = tmp_path / "reused.jsonl"
    with open(path, "w") as f:
        for run in range(2):
            f.write(json.dumps({"kind": "run_start", "pid": 1}) + "\n")
            for i in range(12):
                f.write(json.dumps({"kind": "step", "site": "trainer.step",
                                    "step": i + 1,
                                    "wall_ms": 1.0 + run}) + "\n")
    recs, skipped = rep._select_run(rep._read(str(path)))
    assert len(recs) == 12 and skipped == 1
    assert all(r["wall_ms"] >= 2.0 for r in recs)     # the newest run
    summary = rep.summarize(str(path))
    assert "12" in summary and "newest of 2 runs" in summary
    merged, skipped = rep._select_run(rep._read(str(path)), merge=True)
    assert len(merged) == 24 and skipped == 0


def test_jsonl_sink_survives_write_failure(clean_telemetry):
    """A full disk must disable the sink, not crash the step."""
    if not os.path.exists("/dev/full"):
        pytest.skip("no /dev/full on this platform")
    telemetry.set_jsonl("/dev/full")
    telemetry.jsonl_emit({"kind": "step", "site": "s"})   # must not raise
    telemetry.jsonl_emit({"kind": "step", "site": "s"})   # sink now closed


def test_watchdog_warmup_knob_is_live(clean_telemetry):
    config.set("MXTPU_RECOMPILE_WARMUP_STEPS", 3)
    telemetry.reset()
    wd = telemetry.get_watchdog()
    assert wd.warmup_steps == 3
    config.set("MXTPU_RECOMPILE_WARMUP_STEPS", 50)
    assert wd.warmup_steps == 50, \
        "config.set must take effect on the armed watchdog"
    assert telemetry.RecompileWatchdog(warmup_steps=7).warmup_steps == 7


# ---------------------------------------------------------------------------
# concurrency: scrapes under writer load, JSONL interleaving (ISSUE 19)
# ---------------------------------------------------------------------------
def test_concurrent_scrapes_with_concurrent_writers(clean_telemetry):
    """The /metrics endpoint stays consistent while instruments mutate:
    every scrape parses, and the final total equals what was written."""
    import threading
    from urllib.request import urlopen

    srv = telemetry.MetricsHTTPServer(port=0, host="127.0.0.1").start()
    c = telemetry.get_registry().counter("t_scrape_total")
    errors = []

    def writer():
        for _ in range(500):
            c.inc()

    def scraper():
        try:
            for _ in range(15):
                body = urlopen(f"http://127.0.0.1:{srv.port}/metrics",
                               timeout=10).read().decode()
                vals, _ = _parse_prometheus(body)
                assert 0 <= vals["t_scrape_total"] <= 2000
        except Exception as e:          # noqa: BLE001 — surfaced below
            errors.append(e)

    try:
        threads = [threading.Thread(target=writer) for _ in range(4)] \
            + [threading.Thread(target=scraper) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert errors == []
        body = urlopen(f"http://127.0.0.1:{srv.port}/metrics",
                       timeout=10).read().decode()
        vals, _ = _parse_prometheus(body)
        assert vals["t_scrape_total"] == 2000
    finally:
        srv.stop()


def test_jsonl_interleaves_trace_records_under_concurrent_writers(
        tmp_path, clean_telemetry):
    """``kind:"trace"`` span records share the JSONL sink with step and
    custom records across threads: every line stays one valid JSON
    object and nothing is lost or torn."""
    import threading

    from incubator_mxnet_tpu.telemetry import trace

    path = str(tmp_path / "mixed.jsonl")
    telemetry.set_jsonl(path)
    config.set("MXTPU_TRACE_SAMPLE", 1.0)
    n_threads, per = 6, 40

    def worker(i):
        for j in range(per):
            with trace.span(f"unit.t{i}", j=j):
                pass
            telemetry.jsonl_emit({"kind": "unit", "thread": i, "j": j})

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    telemetry.set_jsonl(None)
    config.unset("MXTPU_TRACE_SAMPLE")
    recs = telemetry.read_jsonl(path)
    spans = [r for r in recs if r.get("kind") == "trace" and "span" in r]
    custom = [r for r in recs if r.get("kind") == "unit"]
    assert len(spans) == n_threads * per
    assert len(custom) == n_threads * per
    # per-thread counts survived the interleave exactly
    for i in range(n_threads):
        assert sum(1 for r in spans
                   if r["name"] == f"unit.t{i}") == per
    # spans carry distinct head-sampled trace ids (roots, no ambient)
    assert len({r["trace"] for r in spans}) == n_threads * per


def test_mfu_ceiling_is_the_published_peak_or_nothing(monkeypatch):
    """ISSUE 21: the MFU denominator is looked up by device_kind; a
    device with no published peak (the CPU mesh here) raises instead of
    assuming one, and the env override still supplies a denominator."""
    from incubator_mxnet_tpu.telemetry import meters

    monkeypatch.delenv("MXTPU_BENCH_CEILING_TFS", raising=False)
    with pytest.raises(LookupError):
        meters.ceiling_tfs()
    with pytest.raises(LookupError):
        meters.mfu_percent(1e12)
    assert meters.PEAK_BF16_TFS["TPU v5 lite"] == 197.0
    monkeypatch.setenv("MXTPU_BENCH_CEILING_TFS", "100")
    assert meters.ceiling_tfs() == 100.0
    assert meters.mfu_percent(50e12) == 50.0
