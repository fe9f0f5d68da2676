"""The EXAONE-MoE family in the benchmark: the new cell rehearsed end to
end on the CPU, its controls, the yardstick's arithmetic at the published
sizes, the configuration file's bookkeeping, the new readers."""

import pytest

from chipbench import manifest as mf

CELL, CONFIG = "k_exaone_236b_ep8.decode_closed32", "k_exaone_236b_ep8"
NEW = {"moe_routed_here_pct", "moe_experts_hit_pct", "moe_load_max_over_mean",
       "kv_live_pct"}


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
        # half the tiny router's experts are held: 4 of 8
        assert NEW <= got and got.isdisjoint({"serve_tokens_per_s"})
        assert 35 < line["metrics"]["moe_routed_here_pct"]["value"] < 65
        assert 0 < line["metrics"]["moe_experts_hit_pct"]["value"] <= 100
        assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1
        assert 0 < line["metrics"]["kv_live_pct"]["value"] <= 100
        assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
    else:
        assert got == {"serve_tokens_per_s", "setup_s"}
    assert not any("mfu" in n or "roofline" in n for n in got)


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_a_control_of_the_new_cell_comes_out_not_correct(rehearse, control):
    rc, line = rehearse(CELL, "--control", control, seed=3_000_000_017)
    assert rc == 0 and line["control"] == control
    assert line["correct"] is False
    noise = {n["name"]: n for n in line["compared"]}["served_logit_noise"]
    assert noise["value"] > noise["limit"]


def test_flops_at_the_published_sizes(cfg):
    """The issue's table of the cut, reckoned again from the shapes."""
    from chipbench.flops import exaone_moe as fl

    model = cfg["model"]
    assert fl.attention_params(model) == 6144 * 8192 * 2 + 6144 * 1024 * 2
    assert round(fl.attention_params(model) / 1e6, 1) == 113.2
    assert fl.expert_params(model) == 3 * 6144 * 2048        # 37.7M
    assert fl.routed_here_per_token(model) == 1.0            # one of eight
    always = (8 * fl.attention_params(model) + 3 * 6144 * 18432
              + 7 * (6144 * 128 + fl.expert_params(model)) + 19200 * 6144)
    assert fl.always_read_params(model) == always
    assert fl.matmul_params(model) == always + 7 * fl.expert_params(model)
    # resident: layers 0-7 and the vocabulary slice, 5.98B = 11.96 GB
    assert round(fl.param_count(model) / 1e9, 2) == 5.98
    assert round(2 * fl.param_count(model) / 1e9, 2) == 11.96
    # a step of 32 tokens hits 87% of the held experts and moves ~10.6 GB
    assert round(fl.experts_hit_share(model, 32), 2) == 0.87
    assert 10.4e9 < fl.weight_bytes(model, 2, 32) < 10.8e9
    assert fl.kv_bytes_per_token(model, 2) == 8 * 4096
    # K/V a token reads at context 2000: 2 full layers x 2000 + 6 x 128
    step = fl.decode_steps_bytes(model, 1, 32 * 2000, 32, 2)
    live = 32 * (2 * 2000 + 6 * 128) * 4096
    assert step == fl.weight_bytes(model, 2, 32) + live + 32 * 8 * 4096
    # attention FLOPs: full layers over the context, window over 128
    per_pair = 4 * 8192
    assert fl.decode_token_flops(model, 2000) == 2 * fl.matmul_params(
        model) + per_pair * (2 * 2000 + 6 * 128)
    assert fl.attn_flops(model, 2000) == per_pair * 2 * 2000
    assert fl.window_pairs(300, 128) == 128 * 129 // 2 + 172 * 128
    t = 1000
    assert fl.prefill_flops(model, t) == 2.0 * t * (
        fl.matmul_params(model) - 19200 * 6144) + 2 * 19200 * 6144 \
        + per_pair * (2 * fl.causal_pairs(t) + 6 * fl.window_pairs(t, 128))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = fl.least_seconds(32 * fl.decode_token_flops(model, 2000),
                                    step, peaks)
    assert bound == "bandwidth" and 0.012 < least < 0.016


def test_the_configuration_file_keeps_its_books(cfg):
    """Every reduced key stands beside its published value; the source's
    keys at the file's top level are the ``model`` block's; no width, head
    count, window, router width or experts per token differs from the
    source; the program's shapes are the reference's."""
    man = mf.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == entry["reduced"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "num_nextn_predict_layers": 1, "max_position_embeddings": 262144}
    model = cfg["model"]
    own = {"n_layer", "expert_share"}       # the harness's, the cut's
    assert {k: v for k, v in model.items() if k not in own} \
        == {k: cfg[k] for k in model if k not in own}
    assert model["n_layer"] == model["num_hidden_layers"] == 8
    assert model["num_experts"] * model["expert_share"]["of"] == 128
    for key, want in dict(
            hidden_size=6144, num_attention_heads=64, num_key_value_heads=8,
            head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
            num_experts_per_tok=8, sliding_window=128, num_shared_experts=1,
            routed_scaling_factor=2.5, scoring_func="sigmoid",
            norm_topk_prob=True, n_group=1, topk_group=1).items():
        assert model[key] == want, key
    assert model["layer_types"][:8] == ["sliding_attention"] * 3 + [
        "full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
    assert model["mlp_layer_types"][:8] == ["dense"] + ["sparse"] * 7
    assert len(model["layer_types"]) == len(model["mlp_layer_types"]) == 48
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
    from chipbench.harness import leaf_targets
    from chipbench.references import exaone_moe as ref

    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    params, targets = net._collect_params_with_prefix(), leaf_targets(cfg)
    shapes, want = ref.leaf_shapes(model), {}
    for name in ref.GLOBAL_LEAVES:
        want[targets[name]] = shapes[name]
    for i in range(8):
        for name in ref.layer_leaves(model, i):
            want[targets[f"h{i}.{name}"]] = shapes[name]
    assert {n: p.shape for n, p in params.items()} == want
    assert net.cache_groups(4096) == [
        dict(layers=2, heads=8, rows=4096, head_dim=128, kind="full"),
        dict(layers=6, heads=8, rows=128, head_dim=128, kind="ring")]


def test_the_new_readers_read_nothing_from_an_older_program():
    """A program whose step records lack the fields (the parent commit):
    every new reader returns None and does not raise."""
    steps = [{"site": "decode.x", "kind": "step", "t0": 0.5, "dur_s": 0.01,
              "phases": {}, "active": 2}]
    record = {"kind": "serve", "t0": 0.0, "t1": 1.0,
              "config": {"name": "x"},
              "model": {"num_hidden_layers": 2, "num_experts": 4,
                        "mlp_layer_types": ["dense", "sparse"]},
              "ledger": {"steps": steps, "capacity": 8}}
    for name in sorted(NEW):
        assert mf.reader(name)(record) is None
    steps[0].update(routed_here=3, routed_all=8, experts_hit=2,
                    expert_load_max=2, kv_live_rows=10, kv_rows=40)
    assert mf.reader("moe_routed_here_pct")(record) == 37.5
    assert mf.reader("moe_experts_hit_pct")(record) == 50.0
    assert mf.reader("moe_load_max_over_mean")(record) == 2 * 4 / 3
    assert mf.reader("kv_live_pct")(record) == 25.0


def test_a_mode_can_take_the_references_routing():
    """``<mode>+f32route`` is the mode with every router's choice taken
    from the float32 stream. In float32 the plain block IS the reference,
    with either routing; in bfloat16 the routed-by-reference block stays
    at least as near to the reference as the one that routes itself."""
    import numpy as np

    from chipbench import stats
    from chipbench.references import exaone_moe as ref

    cfg = mf.load_config(mf.config_file(mf.load_manifest(), CONFIG), True)
    rng = np.random.default_rng(0)
    samples = [(rng.integers(0, 97, 8), list(rng.integers(0, 97, 40)))
               for _ in range(16)]
    modes = ("stated", "stated+f32route")
    same = ref.served_gaps(cfg["model"], 3, "float32", samples, 64, modes)
    assert all(float(same[m].max()) == 0.0 for m in modes)
    low = ref.served_gaps(cfg["model"], 3, "bfloat16", samples, 64, modes)
    noise = [stats.noise_scale(low["margin"], low[m] > 0) for m in modes]
    assert noise[1] <= noise[0]
    assert low["router_near_ties"]["of"] == 16 * 64 * 2
