"""The benchmark's own arithmetic: manifest, FLOP and byte counts,
percentiles and windows, the traffic generator, the trace reduction."""

import os
import re

import numpy as np
import pytest

from chipbench import manifest as mf
from chipbench import loadgen as traffic
from chipbench import stats, xplane
from chipbench.flops import gpt2 as flops

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

MEDIUM = {"n_layer": 24, "n_embd": 1024, "n_head": 16, "n_positions": 1024,
          "vocab_size": 50257, "n_inner": None}


def test_manifest_is_consistent():
    man = mf.load_manifest()
    cells = {w["name"] for w in man["workloads"]}
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in man[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        mf.reader(m["name"])                 # a reader of its own exists
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for m in man["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for w in man["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        cfg = mf.load_json(mf.config_file(man, w["config"]))
        assert cfg["name"] == w["config"]
        assert {"source", "reduced", "assumed", "model"} <= set(cfg)
        mix = mf.load_json(mf.traffic_file(w["traffic"]))
        assert os.path.exists(os.path.join(
            mf.HERE, "runners", mix["runner"] + ".py"))
        reported = [m["name"] for m in mf.metrics_of(man, w["name"],
                                                     "per_layer")]
        assert any("mfu" in re.split(r"[_.]", n) for n in reported)
        assert len(mf.metrics_of(man, w["name"], "end_to_end")) >= 2


def test_gpt2_medium_counts_by_hand():
    # 24 blocks of 12 C^2 matmul weights and a 50257 x 1024 head
    assert flops.layer_matmul_params(MEDIUM) == 12 * 1024 * 1024
    mm = 24 * 12 * 1024 ** 2 + 50257 * 1024
    assert flops.matmul_params(MEDIUM) == mm == 353_453_056
    # with embeddings, biases and LayerNorms: GPT-2 medium's 354.8M plus
    # the zoo's untied head (51.5M)
    assert flops.param_count(MEDIUM) == 354_823_168 + 50257 * 1024
    pairs = 1024 * 1025 // 2
    fwd = 2 * 1024 * mm + 4 * 1024 * 24 * pairs
    assert flops.forward_flops_sequence(MEDIUM, 1024, 1024) == fwd
    assert flops.train_step_flops(MEDIUM, 4, 1024) == 3 * 4 * fwd
    # prefill applies the head once; a decode token sees ctx keys
    assert flops.prefill_flops(MEDIUM, 1024) == fwd - 2 * 1023 * 50257 * 1024
    assert flops.decode_token_flops(MEDIUM, 100) == 2 * mm + 4 * 1024 * 24 * 100
    # bytes: weights once a step, the live cache once, a row per token
    kv = 2 * 24 * 1024 * 2
    assert flops.decode_steps_bytes(MEDIUM, 3, 1000, 48, 2) == \
        3 * mm * 2 + (1000 + 48) * kv
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.least_seconds(197e12, 1.0, peaks) == (1.0, "compute")
    assert flops.least_seconds(1.0, 819e9, peaks) == (1.0, "bandwidth")


def test_percentiles_and_window_with_a_stall():
    assert stats.percentile([], 95) is None
    assert stats.percentile(range(1, 101), 95) == 95
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.median([3, 1, 2, 10]) == 2.5
    # three requests; window [10, 20): r0 submitted before it, r2 stalls
    reqs = [
        {"submit": 9.0, "stamps": [9.5, 10.5, 11.5], "failed": False},
        {"submit": 10.0, "stamps": [10.2, 10.3, 10.4], "failed": False},
        {"submit": 12.0, "stamps": [12.1, 19.9, 25.0], "failed": False},
        {"submit": 13.0, "stamps": [], "failed": True},
    ]
    w = stats.serve_window(reqs, 10.0, 20.0)
    assert w["tokens_in_window"] == 2 + 3 + 2     # where they are delivered
    assert w["attempted"] == 3 and w["failed"] == 1
    assert w["ttft_s"] == pytest.approx([0.2, 0.1])
    # the stalled request's gaps are whole although it ended after the close
    assert sorted(w["gaps_s"])[-2:] == pytest.approx([5.1, 7.8])
    assert stats.percentile(w["gaps_s"], 95) == pytest.approx(7.8)


def test_traffic_is_the_same_work_under_every_seed():
    spec = mf.load_json(mf.traffic_file("decode_closed16"))
    a = traffic.RequestMix(spec, 1, 50257)
    b = traffic.RequestMix(spec, 2 ** 31 + 12345, 50257)
    assert a.pairs == b.pairs                 # sizes and order: the file's
    assert not np.array_equal(a.request(3)[0], b.request(3)[0])
    lens = [n for n, _ in a.pairs]
    assert min(lens) >= 32 and max(lens) <= 256 and len(set(lens)) > 40
    p1, m1 = a.request(70)
    p2, _ = traffic.RequestMix(spec, 1, 50257).request(70)
    assert (p1 == p2).all() and len(p1) == a.pairs[70 % 64][0]
    assert p1.max() < 50257 and m1 == a.pairs[70 % 64][1]
    log = traffic.grid({"dist": "loguniform", "lo": 32, "hi": 900}, 64)
    assert np.median(log) < (32 + 900) / 4
    # poisson arrivals: the stated rate, exponential gaps
    spec = mf.load_json(os.path.join(HERE, "data",
                                     "chat_poisson_rehearsal.json"))
    due = traffic.arrivals(spec, 8.0)
    assert len(due) == pytest.approx(8.0 * 40.0, rel=0.05)
    gaps = np.diff(due[:33])
    assert np.std(gaps) > 0.5 * np.mean(gaps)      # not a metronome
    rows = traffic.train_pool({"pool_batches": 3}, 4, 8, 16, 97)
    flat = np.concatenate([t for t, _ in rows])
    assert len({r.tobytes() for r in flat}) == 24  # rows that all differ
    assert (rows[0][0][:, 1:] == rows[0][1][:, :-1]).all()


def test_xplane_reduction_on_a_small_recorded_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "small_trace.textproto")) as f:
        red = xplane.reduce(ProfileData.from_text_proto(f.read()))
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(7e-3)
    assert red["window_s"] == pytest.approx(9e-3)
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(2 / 9)
    assert sorted(red["modules"]["jit_step"]) == pytest.approx([3e-3, 4e-3])
    assert stats.median(red["modules"]["jit_step"]) == pytest.approx(3.5e-3)
    assert red["device_ops"][0] == ["copy.2 copy(bf16[48,16] %k.1)",
                                    pytest.approx(5e-3)]
    assert red["idle_gaps"] == [["np.asarray(jax.Array)",
                                 pytest.approx(2e-3)]]
    assert xplane.reduce(ProfileData.from_text_proto("")) == {}


def test_peaks_table_refuses_an_unknown_device():
    from chipbench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_noise_scale_finds_the_noise_that_made_the_flips():
    """Margins with a known density of near-ties, flips drawn from a known
    noise: the scale comes back within a tenth, and does not move with
    how many near-ties there are; nothing flipped reads the least scale."""
    from chipbench import stats

    rng = np.random.default_rng(0)
    for n in (4000, 16000):
        margins = np.abs(rng.normal(0.0, 0.3, n))
        for s in (0.01, 0.02, 0.04):
            flipped = margins + rng.normal(0.0, s, n) < 0
            assert stats.noise_scale(margins, flipped) == pytest.approx(
                s, rel=0.1)
    assert stats.noise_scale(margins, np.zeros(n, bool)) == pytest.approx(
        1e-4, rel=0.01)
