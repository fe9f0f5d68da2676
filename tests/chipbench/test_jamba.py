"""The Jamba family in the benchmark: the new cell rehearsed end to end on
the CPU, its controls, the yardstick's arithmetic at the published sizes
against the program's own declarations, the configuration file's
bookkeeping, and the new reader."""

import json

import numpy as np
import pytest

from chipbench import manifest as mf

CELL, CONFIG = "jamba2_3b.decode_closed64_4k", "jamba2_3b"
SERVING = {"state_mb_per_step", "kv_live_pct", "kv_read_over_live",
           "kv_read_mb_per_step", "slot_occupancy_pct",
           "compiles_in_window.serve", "decode_step_mean_ms",
           "decode_step_p50_ms", "decode_launch_ms",
           "sched_host_ms_per_step", "decode_ahead_pct"}


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(mf.config_file(mf.load_manifest(), CONFIG), False)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_end_to_end(rehearse, trace):
    rc, line = rehearse(CELL, trace=trace, seed=2 ** 31 + 1234)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    got = set(line["metrics"])
    if trace:
        assert SERVING <= got and got.isdisjoint({"serve_tokens_per_s"})
        assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
        # three state-space layers of 128 channels, 8 + 3 float32 values
        # a channel, in and out, at most four slots
        mb = line["metrics"]["state_mb_per_step"]["value"]
        assert 0 < mb <= 4 * 2 * 3 * 128 * 11 * 4 / 1e6
        # the one attention layer's whole plane of every active slot
        kv = line["metrics"]["kv_read_mb_per_step"]["value"]
        assert 0 < kv <= 4 * 128 * 2 * 16 * 4 / 1e6
        assert 0 < line["metrics"]["kv_live_pct"]["value"] <= 100
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
    mix = mf.load_json(mf.traffic_file("decode_closed64_4k"))
    assert mix["clients"] == cfg["serving"]["max_slots"] == 64
    assert mix["prompt_len"]["hi"] + mix["max_new_tokens"]["hi"] \
        == 512 + 3072 < cfg["serving"]["max_len"] == 4096
    assert mix["prompt_len"]["hi"] <= max(cfg["serving"]["prefill_buckets"])
    assert (mix["prompt_len"]["lo"], mix["max_new_tokens"]["lo"]) \
        == (64, 1024) and mix["think_ms"] == 2 and mix["pool"] == 64
    assert mix["check_sample"] == 32 and mix["trace_s"] == 3


def test_flops_at_the_published_sizes(cfg):
    """The issue's arithmetic, reckoned again from the shapes, and held
    against what the program declares (shapes only: nothing allocated)."""
    from chipbench.flops import jamba as fl
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    model = cfg["model"]
    mix = 2560 * 10240 + 192 * 5120 + 5120 * 160 + 5120 * 2560
    assert fl.mixer_matmul_params(model) == mix == 41_123_840
    assert fl.mixer_params(model) == mix + 4 * 5120 + 5120 + 5120 \
        + 16 * 5120 + 5120 + 160 + 32                       # 41.2M
    assert fl.attention_params(model) == 2 * 2560 * 2560 + 2 * 128 * 2560
    assert fl.ffn_params(model) == 3 * 2560 * 8192 == 62_914_560
    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    declared = sum(int(np.prod(p.shape)) for p in
                   net._collect_params_with_prefix().values())
    assert fl.param_count(model) == declared
    assert round(declared / 1e9, 2) == 3.03
    assert round(fl.weight_bytes(model, 2) / 1e9, 2) == 6.06
    assert fl.matmul_params(model) == 26 * mix + 2 * fl.attention_params(
        model) + 28 * fl.ffn_params(model) + 65536 * 2560
    # K and V of one position: 2 layers x 128 x 2 tensors x 2 B
    assert fl.kv_bytes_per_token(model, 2) == 1_024
    # a slot's state as stored: float32 h, bfloat16 taps, 26 layers
    slot = 26 * 5120 * (16 * 4 + 3 * 2)
    assert fl.state_bytes_per_slot(model, 2) == slot == 9_318_400
    kv = serving.KVCache(net.cache_groups(cfg["serving"]["max_len"]), 2,
                         dtype=cfg["dtype"])
    assert kv.state_bytes(1) == 2 * slot
    assert kv.nbytes == 2 * slot + 2 * 4096 * 1_024
    assert fl.scan_flops_per_token(model) == 26 * 5120 * 16 * 6
    assert fl.attn_flops(model, 1000) == 2 * 4 * 20 * 128 * 1000
    assert fl.decode_token_flops(model, 1000) == 2 * fl.matmul_params(
        model) + 26 * 5120 * 16 * 6 + fl.attn_flops(model, 1000)
    # a step of 64 live slots at a context of 2000: weights + state in
    # and out + live rows
    step = fl.decode_steps_bytes(model, 1, 64 * 2000, 64, 2)
    assert step == fl.weight_bytes(model, 2) + 64 * 2 * slot \
        + 64 * 2001 * 1_024
    assert 7.3e9 < step < 7.5e9
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = fl.least_seconds(64 * fl.decode_token_flops(model, 2000),
                                    step, peaks)
    assert bound == "bandwidth" and 0.0089 < least < 0.0092
    t = 512
    assert fl.prefill_flops(model, t) == 2.0 * t * (
        fl.matmul_params(model) - 65536 * 2560) + 2 * 65536 * 2560 \
        + t * 26 * 5120 * 16 * 6 + fl.attn_flops(model, t * (t + 1) // 2)
    assert fl.prefill_bytes(model, t, 2) == fl.weight_bytes(model, 2) \
        + t * 1_024 + slot
    least, bound = fl.least_seconds(fl.prefill_flops(model, t),
                                    fl.prefill_bytes(model, t, 2), peaks)
    assert bound == "compute" and 0.014 < least < 0.016


def test_the_configuration_file_keeps_its_books(cfg):
    """The catalog's keys at the top level and under ``model`` but for the
    one reduced key, which stands beside its published value; every
    width, the depth and the vocabulary as published; the program's
    shapes are the reference's."""
    man = mf.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == entry["reduced"] == ["max_position_embeddings"]
    assert cfg["source"] == entry["source"]
    assert cfg["published"] == {"max_position_embeddings": 262144}
    model = cfg["model"]
    own = {"n_layer", "state_dtype"}            # the harness's, the cache's
    assert {k: v for k, v in model.items() if k not in own} \
        == {k: cfg[k] for k in model if k not in own}
    assert model["n_layer"] == model["num_hidden_layers"] == 28
    for key, want in dict(
            hidden_size=2560, intermediate_size=8192, vocab_size=65536,
            num_attention_heads=20, num_key_value_heads=1, mamba_expand=2,
            mamba_d_state=16, mamba_dt_rank=160, mamba_d_conv=4,
            mamba_conv_bias=True, mamba_proj_bias=False,
            attn_layer_period=14, attn_layer_offset=7, num_experts=1,
            num_experts_per_tok=1, rms_norm_eps=1e-6,
            tie_word_embeddings=True, max_position_embeddings=4096,
            state_dtype="float32").items():
        assert model[key] == want, key
    assert len(cfg["assumed"]) >= 8 and "whole" in cfg["deployment"]
    assert cfg["serving"]["max_slots"] == 64
    assert cfg["serving"]["prefill_buckets"] == [128, 256, 512]
    assert cfg["check"]["serve"]["controls"] == ["int8", "fp8"]
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
    from chipbench.harness import leaf_targets
    from chipbench.references import jamba as ref

    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    params, targets = net._collect_params_with_prefix(), leaf_targets(cfg)
    shapes, want = ref.leaf_shapes(model), {}
    for name in ref.GLOBAL_LEAVES:
        want[targets[name]] = shapes[name]
    for i in range(28):
        for name in ref.layer_leaves(model, i):
            want[targets[f"h{i}.{name}"]] = shapes[name]
    assert {n: p.shape for n, p in params.items()} == want
    assert ref.sizes(model)["kinds"].count("attention") == 2
    json.dumps(cfg)         # the file is plain data


def test_the_new_reader_reads_a_record_with_and_without_the_field():
    """A program whose step records lack ``state_bytes`` (the parent
    commit, or a block with no state group): the reader returns None and
    does not raise."""
    steps = [{"site": "decode.x", "kind": "step", "t0": 0.5, "dur_s": 0.01,
              "phases": {}, "active": 2, "kv_read_rows": 10,
              "kv_read_bytes": 100, "kv_live_rows": 8, "kv_rows": 40}]
    record = {"kind": "serve", "t0": 0.0, "t1": 1.0,
              "config": {"name": "x"}, "model": {},
              "ledger": {"steps": steps, "capacity": 8}}
    read = mf.reader("state_mb_per_step")
    assert read(record) is None
    assert read({"kind": "serve", "t0": 0.0, "t1": 1.0,
                 "config": {"name": "x"}}) is None
    steps[0]["state_bytes"] = 3_000_000
    steps.append(dict(steps[0], t0=0.6, state_bytes=1_000_000))
    assert read(record) == 2.0


def test_the_seeded_recurrence_does_work_at_the_published_widths(cfg):
    """One mixer's small leaves drawn at the published widths (no
    projection): ``A`` is ``-(1..16)`` a channel to 10%, ``D`` near 1, and
    ``softplus(b_dt)`` spreads log-uniformly over [1e-3, 1e-1], so a
    channel's slowest state forgets over 10 to 1,000 positions."""
    import jax.numpy as jnp

    from chipbench.references import jamba as ref

    model = cfg["model"]
    key = ref.root_key(2 ** 31 + 5)
    a = -np.exp(np.asarray(ref.draw_leaf(model, key, 3, "a_log", "float32")))
    assert a.shape == (16, 5120)
    assert np.abs(a / -np.arange(1, 17)[:, None] - 1).max() < 0.15
    d = np.asarray(ref.draw_leaf(model, key, 3, "d_skip", "float32"))
    assert abs(d.mean() - 1) < 0.01 and 0.01 < d.std() < 0.03
    b = ref.draw_leaf(model, key, 3, "dt_b", "float32")
    dt = np.asarray(jnp.log1p(jnp.exp(b)))
    assert 0.99e-3 < dt.min() < 1.2e-3 and 0.08 < dt.max() < 0.101
    lo, mid, hi = np.quantile(dt, [0.1, 0.5, 0.9])
    assert 1.3e-3 < lo < 2e-3 and 0.008 < mid < 0.0125 and 0.05 < hi < 0.08
