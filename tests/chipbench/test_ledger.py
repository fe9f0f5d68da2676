"""The readers of the program's turn ledger: each on a hand-made ledger
(percentiles and means by hand, ``idle`` left out, a wrapped ring, a
program with no ledger), and in a traced rehearsal of a serving cell and
of the training cell, where the program itself writes the records."""

import math

import pytest

from chipbench import ledger
from chipbench import manifest as mf

SERVE, TRAIN = "gpt2_xl.decode_closed16", "gpt2_medium.train_seq1024"
PREFILL = "gpt2_xl.prefill_closed16"
NEW = {"decode_step_p50_ms", "decode_step_p95_ms", "prefill_p50_ms",
       "decode_launch_ms", "decode_fence_excess_ms",
       "sched_host_ms_per_step", "train_dispatch_p50_ms"}


def _step(t0, dur, site="decode.m", **phases):
    base = {"sched": 0.0003, "idle": 5.0, "h2d": 0.0002, "dispatch": 0.0008,
            "fence": 0.0935, "meter": 0.0001, "deliver": 0.0002,
            "finish": 0.0001}
    return {"kind": "step", "site": site, "t0": t0, "dur_s": dur,
            "active": 16, "phases": dict(base, **phases)}


def _prefill(t0, dur):
    return {"kind": "prefill", "site": "decode.m", "t0": t0, "dur_s": dur,
            "bucket": 512, "prompt_len": 400, "queue_wait_s": 0.1,
            "phases": {"dispatch": 0.001, "join": 0.0005,
                       "fence": dur - 0.002}}


def _serve_record(steps=None, capacity=1000):
    """A serving run's record; without ``steps`` the readers go to the
    program's own ring."""
    record = {"kind": "serve", "t0": 10.0, "t1": 20.0,
              "config": {"name": "m", "serving": {"trace_modules": {
                  "decode": "jit__decode_apply"}}},
              # three whole launches, and the stumps of the two that the
              # capture's edges clipped
              "trace": {"modules": {"jit__decode_apply": [
                  0.0310, 0.0905, 0.0915, 0.0925, 0.0642]}}}
    if steps is not None:
        record["ledger"] = {"steps": steps, "capacity": capacity}
    return record


def _window():
    """Twenty steps of 90..109 ms and three prefills inside [10, 20);
    around them a warm-up step, a step after the close, and another
    site's record, all with times no reader may count."""
    inside = [_step(10.0 + 0.4 * i, 0.090 + 0.001 * i) for i in range(20)]
    inside += [_prefill(10.1, 0.030), _prefill(12.1, 0.050),
               _prefill(14.1, 0.040)]
    inside.sort(key=lambda r: r["t0"])
    return ([_step(9.5, 7.0, h2d=7.0, sched=7.0)] + inside
            + [_step(20.0, 9.0, fence=9.0),
               _step(15.0, 8.0, site="decode.other", dispatch=8.0)])


BY_HAND = {
    # 20 values 90..109: the median lies between the 10th and the 11th
    "decode_step_p50_ms": 99.5,
    # nearest rank: ceil(0.95 * 20) = 19th of 20
    "decode_step_p95_ms": 108.0,
    "prefill_p50_ms": 40.0,
    "decode_launch_ms": 0.2 + 0.8,
    # fence 93.5 less the median of the traced launches, stumps and all
    "decode_fence_excess_ms": 93.5 - 90.5,
    # sched + deliver + finish; the 5 s of idle are not host work
    "sched_host_ms_per_step": 0.3 + 0.2 + 0.1,
}


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_serving_reader_on_a_hand_made_ledger(metric):
    got = mf.reader(metric)(_serve_record(_window()))
    assert got == pytest.approx(BY_HAND[metric], rel=1e-9)


def test_the_training_reader_on_a_hand_made_ledger():
    steps = [{"kind": "step", "site": "spmd.step", "t0": 1.0 + i,
              "dur_s": 0.008, "phases": {"h2d": 0.001, "rng": 0.0005,
                                         "dispatch": 0.001 * d,
                                         "meter": 0.0002}}
             for i, d in enumerate((9, 3, 4, 5, 70))]
    record = {"kind": "train", "t0": 2.0, "t1": 5.5, "config": {"name": "m"},
              "ledger": {"steps": steps, "capacity": 1000}}
    # the records stamped at 2, 3, 4, 5: dispatch 3, 4, 5, 70 ms
    assert mf.reader("train_dispatch_p50_ms")(record) == pytest.approx(4.5)


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_wrapped_ring_reads_nothing(metric):
    steps = _window()[1:]               # the oldest left is at 10.0 ...
    steps[0]["t0"] = 10.2               # ... no: younger than the start
    full = _serve_record(steps, capacity=len(steps))
    assert mf.reader(metric)(full) is None
    # the same records in a ring with room left lost nothing
    roomy = _serve_record(steps, capacity=len(steps) + 1)
    assert mf.reader(metric)(roomy) is not None
    # and a full ring whose oldest record is older than the start is whole
    whole = _serve_record(_window(), capacity=len(_window()))
    assert mf.reader(metric)(whole) == pytest.approx(BY_HAND[metric])


@pytest.mark.parametrize("steps", [
    [],                                                  # telemetry off
    [{"kind": "step", "site": "decode.m", "step": i, "wall_ms": 96.0}
     for i in range(512)],                               # no t0: older
], ids=["empty", "unstamped"])
def test_a_program_without_the_ledger_reads_nothing(steps):
    record = _serve_record(steps, capacity=512)
    for metric in BY_HAND:
        assert mf.reader(metric)(record) is None
    assert ledger.turns(record, "step") is None


def test_fence_excess_needs_the_device_plane():
    record = _serve_record(_window())
    record["trace"] = {}
    assert mf.reader("decode_fence_excess_ms")(record) is None
    assert mf.reader("decode_launch_ms")(record) is not None


def test_the_ring_is_read_from_the_program_once():
    from incubator_mxnet_tpu.telemetry import trace

    turn = trace.Turn("decode.m", ("h2d", "dispatch"))
    turn.add("h2d", 0.25)
    turn.close(11.0, 0.5, kind="step")
    record = _serve_record()
    assert ledger.phase_sums(record, "step", "h2d", "dispatch") == [0.25]
    assert record["ledger"]["capacity"] == trace.ring_capacity()
    trace.reset()
    assert ledger.durations(record, "step") == [0.5]


@pytest.mark.parametrize("workload", [SERVE, PREFILL, TRAIN])
def test_a_traced_rehearsal_reports_the_cells_ledger_metrics(
        rehearse, workload):
    rc, line = rehearse(workload, trace=1, seed=2 ** 31 + 25)
    assert rc == 0 and line["correct"] is True
    names = {m["name"] for m in mf.metrics_of(mf.load_manifest(), workload,
                                              "per_layer")}
    # no device plane on a CPU, so nothing to take the fence's excess from
    want = (names & NEW) - {"decode_fence_excess_ms"}
    assert want and want <= set(line["metrics"])
    assert "decode_fence_excess_ms" not in line["metrics"]
    for name in want:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value > 0
    if workload == SERVE:
        m = {k: v["value"] for k, v in line["metrics"].items()}
        # the ledger's median and the counters' mean time the same steps
        assert m["decode_step_p50_ms"] < 2 * m["decode_step_mean_ms"]
        assert m["decode_step_p50_ms"] <= m["decode_step_p95_ms"]
        assert m["decode_launch_ms"] < m["decode_step_p95_ms"]
