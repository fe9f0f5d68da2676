"""The Ouro family in the benchmark: the new cell rehearsed end to end on the
CPU, its controls, the yardstick's arithmetic at the published sizes with
the passes folded in, against the program's own declarations, the
configuration file's bookkeeping, and the new reader."""

import json

import numpy as np
import pytest

from chipbench import manifest as mf

CELL, CONFIG = "ouro_2p6b.decode_closed8_512", "ouro_2p6b"
SERVING = {"weight_mb_per_step", "kv_live_pct", "kv_read_over_live",
           "kv_read_mb_per_step", "slot_occupancy_pct",
           "compiles_in_window.serve", "decode_step_mean_ms",
           "decode_step_p50_ms", "decode_launch_ms",
           "sched_host_ms_per_step", "decode_ahead_pct"}


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(mf.config_file(mf.load_manifest(), CONFIG), False)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_end_to_end(rehearse, trace):
    rc, line = rehearse(CELL, trace=trace, seed=2 ** 31 + 4040)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    got = set(line["metrics"])
    if trace:
        assert SERVING <= got and got.isdisjoint({"serve_tokens_per_s"})
        assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
        # 3 layers of 4 x (2 x 64 x 64 + 96 x 64 x 3 + 4 x 64) float32
        # and the final norm, 4 times; the head once; 4 table rows
        layer = 4 * 64 * 64 + 3 * 64 * 96 + 4 * 64
        want = 4 * (4 * (3 * layer + 64) + 97 * 64 + 4 * 64) / 1e6
        assert line["metrics"]["weight_mb_per_step"]["value"] == \
            pytest.approx(want)
        # every pass's plane of the 3 layers, at most four slots
        kv = line["metrics"]["kv_read_mb_per_step"]["value"]
        assert 0 < kv <= 4 * 4 * 3 * 128 * 2 * 64 * 4 / 1e6
    else:
        assert got == {"serve_tokens_per_s", "setup_s"}
    assert not any("mfu" in n or "roofline" in n for n in got)


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_a_control_of_the_new_cell_comes_out_not_correct(rehearse, control):
    rc, line = rehearse(CELL, "--control", control, seed=3_000_000_017)
    assert rc == 0 and line["control"] == control
    assert line["correct"] is False
    by = {n["name"]: n for n in line["compared"]}
    assert by["served_logit_noise"]["value"] > by["served_logit_noise"][
        "limit"]
    assert by["served_gap_mean"]["value"] > by["served_gap_mean"]["limit"]


def test_every_pairing_of_the_mix_fits_the_served_context(cfg):
    """Whatever ``shape_seed`` pairs: the longest prompt with the longest
    answer ends inside ``max_len``, and every prompt has a bucket."""
    mix = mf.load_json(mf.traffic_file("decode_closed8_512"))
    assert mix["clients"] == cfg["serving"]["max_slots"] == 8
    assert mix["prompt_len"]["hi"] + mix["max_new_tokens"]["hi"] \
        == 192 + 288 < cfg["serving"]["max_len"] == 512
    assert mix["prompt_len"]["hi"] <= max(cfg["serving"]["prefill_buckets"])
    assert (mix["prompt_len"]["lo"], mix["max_new_tokens"]["lo"]) \
        == (64, 192) and mix["think_ms"] == 2 and mix["pool"] == 64
    assert mix["check_sample"] == 32


def test_flops_at_the_published_sizes(cfg):
    """The issue's arithmetic, reckoned again from the shapes, and held
    against what the program declares (shapes only: nothing allocated)."""
    from chipbench.flops import ouro as fl
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    model = cfg["model"]
    assert fl.attention_params(model) == 4 * 2048 * 2048 == 16_777_216
    assert fl.ffn_params(model) == 3 * 2048 * 5632 == 34_603_008
    assert fl.layer_params(model) == 51_388_416
    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    declared = sum(int(np.prod(p.shape)) for p in
                   net._collect_params_with_prefix().values())
    assert fl.param_count(model) == declared == 2_667_972_608
    # K and V of one position: 4 passes x 48 layers x 2 x 2048 x 2 B
    assert fl.kv_bytes_per_token(model, 2) == 1_572_864
    kv = serving.KVCache(net.cache_groups(cfg["serving"]["max_len"]), 8,
                         dtype=cfg["dtype"])
    assert kv.nbytes == 8 * 512 * 1_572_864
    assert round(kv.nbytes / 1e9, 2) == 6.44
    # a step's weights: the stack and the final norm 4 times, the head once
    stack = 48 * 51_388_416
    assert fl.weight_bytes(model, 2) == 2 * (4 * (stack + 2048)
                                             + 49152 * 2048)
    assert round(fl.weight_bytes(model, 2) / 1e9, 2) == 19.93
    net.cast("bfloat16")
    assert net.step_weight_bytes(8) == fl.weight_bytes(model, 2) \
        + 8 * 2048 * 2
    assert fl.matmul_params(model) == 4 * 48 * (16_777_216 + 34_603_008) \
        + 49152 * 2048
    assert fl.attn_flops(model, 1000) == 4 * 48 * 4 * 2048 * 1000
    # a step of 8 live slots at a context of 300: weights + live rows
    step = fl.decode_steps_bytes(model, 1, 8 * 300, 8, 2)
    assert step == fl.weight_bytes(model, 2) + 8 * 301 * 1_572_864
    assert 23.7e9 < step < 23.8e9
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = fl.least_seconds(8 * fl.decode_token_flops(model, 300),
                                    step, peaks)
    assert bound == "bandwidth" and 0.0289 < least < 0.0291
    t = 256
    assert fl.prefill_flops(model, t) == 2.0 * t * 4 * 48 * (
        16_777_216 + 34_603_008) + 2 * 49152 * 2048 \
        + fl.attn_flops(model, t * (t + 1) // 2)
    assert fl.prefill_bytes(model, t, 2) == fl.weight_bytes(model, 2) \
        + t * 1_572_864
    least, bound = fl.least_seconds(fl.prefill_flops(model, t),
                                    fl.prefill_bytes(model, t, 2), peaks)
    assert bound == "compute" and 0.0258 < least < 0.0260


def test_the_configuration_file_keeps_its_books(cfg):
    """The catalog's keys at the top level and under ``model`` but for the
    one reduced key, which stands beside its published value; every
    width, the depth, the passes and the vocabulary as published; the
    program's shapes are the reference's."""
    man = mf.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == entry["reduced"] == ["max_position_embeddings"]
    assert cfg["source"] == entry["source"]
    assert cfg["published"] == {"max_position_embeddings": 65536}
    model = cfg["model"]
    own = {"n_layer"}                              # the harness's
    assert {k: v for k, v in model.items() if k not in own} \
        == {k: cfg[k] for k in model if k not in own}
    assert model["n_layer"] == model["num_hidden_layers"] == 48
    for key, want in dict(
            hidden_size=2048, intermediate_size=5632, vocab_size=49152,
            num_attention_heads=16, num_key_value_heads=16, head_dim=128,
            total_ut_steps=4, early_exit_threshold=1, rms_norm_eps=1e-6,
            rope_theta=1000000, tie_word_embeddings=False,
            max_position_embeddings=512).items():
        assert model[key] == want, key
    assert model["layer_types"] == ["full_attention"] * 48
    assert len(cfg["assumed"]) >= 6 and "whole" in cfg["deployment"]
    assert cfg["serving"]["max_slots"] == 8
    assert cfg["serving"]["prefill_buckets"] == [128, 256]
    assert cfg["check"]["serve"]["controls"] == ["int8", "fp8"]
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
    from chipbench.harness import leaf_targets
    from chipbench.references import ouro as ref

    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    assert net.passes == model["total_ut_steps"]
    params, targets = net._collect_params_with_prefix(), leaf_targets(cfg)
    shapes, want = ref.leaf_shapes(model), {}
    for name in ref.GLOBAL_LEAVES:
        want[targets[name]] = shapes[name]
    for i in range(48):
        for name in ref.LAYER_LEAVES:
            want[targets[f"h{i}.{name}"]] = shapes[name]
    assert {n: p.shape for n, p in params.items()} == want
    json.dumps(cfg)         # the file is plain data


def test_the_new_reader_reads_a_record_with_and_without_the_field():
    """A program whose step records lack ``weight_bytes`` (the parent
    commit, or a block that declares none): the reader returns None and
    does not raise."""
    steps = [{"site": "decode.x", "kind": "step", "t0": 0.5, "dur_s": 0.01,
              "phases": {}, "active": 2, "kv_read_rows": 10,
              "kv_read_bytes": 100, "kv_live_rows": 8, "kv_rows": 40}]
    record = {"kind": "serve", "t0": 0.0, "t1": 1.0,
              "config": {"name": "x"}, "model": {},
              "ledger": {"steps": steps, "capacity": 8}}
    read = mf.reader("weight_mb_per_step")
    assert read(record) is None
    assert read({"kind": "serve", "t0": 0.0, "t1": 1.0,
                 "config": {"name": "x"}}) is None
    steps[0]["weight_bytes"] = 19_933_000_000
    steps.append(dict(steps[0], t0=0.6))
    assert read(record) == 19_933.0
