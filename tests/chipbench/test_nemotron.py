"""The Nemotron-H family in the benchmark: the new cell rehearsed end to end
on the CPU, its controls, the configuration file's keys against the
published ones, the yardstick's arithmetic at the published sizes against
the program's own declarations, and the new reader."""

import json

import numpy as np
import pytest

from chipbench import manifest as mf

CELL, CONFIG = "nemotron3_super_ep4.decode_closed64_4k", "nemotron3_super_ep4"
SERVING = {"expert_mb_per_step", "state_mb_per_step", "moe_routed_here_pct",
           "moe_experts_hit_pct", "moe_load_max_over_mean", "kv_live_pct",
           "kv_read_over_live", "kv_read_mb_per_step", "slot_occupancy_pct",
           "compiles_in_window.serve", "decode_step_mean_ms",
           "decode_step_p50_ms", "decode_launch_ms",
           "sched_host_ms_per_step", "decode_ahead_pct"}
#: the source's ``config.json`` (NVIDIA-Nemotron-3-Super-120B-A12B-BF16),
#: every key the configuration file carries
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
#: the configuration file's own keys beside the source's: the harness's
#: depth, the experts held and their layers for the readers, the share,
#: the cache's type of the state
OWN = {"n_layer", "num_experts", "mlp_layer_types", "expert_share",
       "state_dtype"}


@pytest.fixture(scope="module")
def cfg():
    return mf.load_config(mf.config_file(mf.load_manifest(), CONFIG), False)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_new_cell_rehearses_end_to_end(rehearse, trace):
    rc, line = rehearse(CELL, trace=trace, seed=2 ** 31 + 4242)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    got = set(line["metrics"])
    if trace:
        assert SERVING <= got and got.isdisjoint({"serve_tokens_per_s"})
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["compiles_in_window.serve"] == 0
        # 8 of the tiny router's 16 experts are held
        assert 30 < m["moe_routed_here_pct"] < 70
        assert 0 < m["moe_experts_hit_pct"] <= 100
        # three expert layers of 8 held experts of 2 x 24 x 32 float32,
        # at most all of them a step
        assert 0 < m["expert_mb_per_step"] <= 3 * 8 * 2 * 24 * 32 * 4 / 1e6
        # three mixers of 16 x 64 state and 3 x 128 taps float32 a slot,
        # in and out, at most four slots
        assert 0 < m["state_mb_per_step"] \
            <= 4 * 2 * 3 * (16 * 64 + 3 * 128) * 4 / 1e6
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


def test_the_configuration_file_keeps_its_books(cfg):
    """Every key of the source at the top level and under ``model`` is
    the published value but for the reduced ones, which stand beside
    their published values; the cut is layers 33-43 of the published
    pattern; the program's shapes are the reference's."""
    man = mf.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size", "num_nextn_predict_layers",
               "max_position_embeddings"]
    assert cfg["reduced"] == entry["reduced"] == reduced
    assert cfg["source"] == entry["source"]
    assert cfg["published"] == {k: PUBLISHED[k] for k in reduced}
    for key, want in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == want, key
    model = cfg["model"]
    assert {k: v for k, v in model.items() if k not in OWN} \
        == {k: cfg[k] for k in PUBLISHED}
    assert model["hybrid_override_pattern"] \
        == PUBLISHED["hybrid_override_pattern"][33:44] == "MEM*EMEMEME"
    assert model["num_hidden_layers"] == model["n_layer"] == 11
    assert model["n_routed_experts"] == model["num_experts"] == 128
    assert model["expert_share"] == {"index": 0, "of": 4}
    assert model["vocab_size"] == PUBLISHED["vocab_size"] // 4
    assert model["mlp_layer_types"] == [
        "sparse" if k == "E" else None for k in "MEM*EMEMEME"]
    assert len(cfg["assumed"]) >= 8 and "EP4" in cfg["deployment"]
    assert cfg["serving"]["max_slots"] == 64
    assert cfg["serving"]["prefill_buckets"] == [128, 256, 512]
    assert cfg["check"]["serve"]["controls"] == ["int8", "fp8"]
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
    from chipbench.harness import leaf_targets
    from chipbench.references import nemotron_h as ref

    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    params, targets = net._collect_params_with_prefix(), leaf_targets(cfg)
    shapes, want = ref.leaf_shapes(model), {}
    for name in ref.GLOBAL_LEAVES:
        want[targets[name]] = shapes[name]
    for i in range(11):
        for name in ref.layer_leaves(model, i):
            want[targets[f"h{i}.{name}"]] = shapes[name]
    assert {n: p.shape for n, p in params.items()} == want
    json.dumps(cfg)         # the file is plain data


def test_flops_at_the_published_sizes(cfg):
    """The cut's arithmetic, reckoned again from the shapes, and held
    against what the program declares (its parameters' shapes, and the
    cache of two slots)."""
    from chipbench.flops import nemotron_h as fl
    from incubator_mxnet_tpu import serving
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder

    model = cfg["model"]
    mix = 18560 * 4096 + 4096 * 8192
    assert fl.mixer_matmul_params(model) == mix
    assert fl.mixer_params(model) == mix + 5 * 10240 + 3 * 128 + 8192 + 4096
    assert round(fl.mixer_params(model) / 1e6, 1) == 109.6
    assert fl.attention_params(model) == 2 * 4096 * 4096 + 2 * 4096 * 256
    assert fl.expert_params(model) == 2 * 1024 * 2688          # 5.51M
    assert fl.expert_layer_dense_params(model) == 4096 * 512 \
        + 2 * 4096 * 1024 + 2 * 4096 * 5376 == 54_525_952
    assert fl.routed_here_per_token(model) == 22 * 128 / 512
    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    net.cast(cfg["dtype"])
    declared = sum(int(np.prod(p.shape)) for p in
                   net._collect_params_with_prefix().values())
    assert fl.param_count(model) == declared
    assert round(declared / 1e9, 2) == 4.65
    assert net.held_expert_bytes == 2 * fl.expert_params(model)
    # every step reads 0.99B parameters whole: 1.98 GB
    assert round(fl.always_read_params(model) * 2 / 1e9, 2) == 1.98
    # 64 tokens of 22 of 512 hit 94% of the held experts: 6.63 GB
    assert round(fl.experts_hit_share(model, 64), 3) == 0.940
    # a slot's state as stored: float32 S, bfloat16 taps of 10240, five
    # mixers: 21.3 MB
    slot = 5 * (128 * 8192 * 4 + 3 * 10240 * 2)
    assert fl.state_bytes_per_slot(model, 2) == slot == 21_278_720
    kv = serving.KVCache(net.cache_groups(cfg["serving"]["max_len"]), 2,
                         dtype=cfg["dtype"])
    assert kv.state_bytes(1) == 2 * slot
    assert fl.kv_bytes_per_token(model, 2) == 1_024
    assert kv.nbytes == 2 * slot + 2 * 4096 * 1_024
    # weights and a cache of 64 slots: 68% of the chip's 16 GB
    assert round((2 * declared + 32 * kv.nbytes) / 16e9, 2) == 0.68
    assert fl.scan_flops_per_token(model) == 5 * 8192 * 128 * 5
    assert fl.attn_flops(model, 1000) == 4 * 4096 * 1000
    # a step of 64 live slots at a context of 2000: 11.4 GB, 13.9 ms
    step = fl.decode_steps_bytes(model, 1, 64 * 2000, 64, 2)
    assert step == fl.weight_bytes(model, 2, 64) + 64 * 2 * slot \
        + 64 * 2001 * 1_024
    assert 11.3e9 < step < 11.5e9
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = fl.least_seconds(64 * fl.decode_token_flops(model, 2000),
                                    step, peaks)
    assert bound == "bandwidth" and 0.0138 < least < 0.0141
    t = 512
    assert fl.prefill_flops(model, t) == 2.0 * t * (
        fl.matmul_params(model) - 32768 * 4096) + 2 * 32768 * 4096 \
        + t * fl.scan_flops_per_token(model) \
        + fl.attn_flops(model, t * (t + 1) // 2)
    assert fl.prefill_bytes(model, t, 2) == fl.weight_bytes(model, 2, t) \
        + t * 1_024 + slot


def test_the_new_reader_reads_a_record_with_and_without_the_field():
    """A program whose step records lack ``expert_bytes`` (the parent
    commit, or a block with no expert layer): the reader returns None and
    does not raise."""
    steps = [{"site": "decode.x", "kind": "step", "t0": 0.5, "dur_s": 0.01,
              "phases": {}, "active": 2, "experts_hit": 3}]
    record = {"kind": "serve", "t0": 0.0, "t1": 1.0,
              "config": {"name": "x"}, "model": {},
              "ledger": {"steps": steps, "capacity": 8}}
    read = mf.reader("expert_mb_per_step")
    assert read(record) is None
    assert read({"kind": "serve", "t0": 0.0, "t1": 1.0,
                 "config": {"name": "x"}}) is None
    steps[0]["expert_bytes"] = 3_000_000
    steps.append(dict(steps[0], t0=0.6, expert_bytes=5_000_000))
    assert read(record) == 4.0


def test_the_seeded_mixer_draws_its_published_initialisation(cfg):
    """One mixer's small leaves at the published widths: ``A`` in [-16,
    -1] a head, ``softplus(b_dt)`` log-uniform over [1e-3, 1e-1], ``D``
    near 1; the router's logits of about unit spread."""
    import jax.numpy as jnp

    from chipbench.references import nemotron_h as ref

    model = cfg["model"]
    key = ref.root_key(2 ** 31 + 5)
    a = -np.exp(np.asarray(ref.draw_leaf(model, key, 0, "a_log", "float32")))
    assert a.shape == (128,) and -16 <= a.min() and a.max() <= -1
    assert a.min() < -12 and a.max() > -3
    dt = np.asarray(jnp.log1p(jnp.exp(
        ref.draw_leaf(model, key, 0, "dt_b", "float32"))))
    assert 0.99e-3 < dt.min() and dt.max() < 0.101
    assert 0.005 < np.median(dt) < 0.02
    d = np.asarray(ref.draw_leaf(model, key, 0, "d_skip", "float32"))
    assert abs(d.mean() - 1) < 0.01
    w = np.asarray(ref.draw_leaf(model, key, 1, "router_w", "float32"))
    assert w.shape == (512, 4096) and 0.95 < w.std() * 64 < 1.05
