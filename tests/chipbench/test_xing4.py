"""The Xing4.0 family in the benchmark: the new cell rehearsed end to end
on the CPU, its controls, the yardstick's arithmetic at the published
sizes, the configuration file's bookkeeping, the new reader, and that
every serving cell's requests fit the context it is served with."""

import json

import pytest

from chipbench import manifest as mf

CELL, CONFIG = "xing4_29b_ep8.decode_closed32_2k", "xing4_29b_ep8"
SERVING = {"moe_routed_here_pct", "moe_experts_hit_pct",
           "moe_load_max_over_mean", "kv_live_pct", "kv_read_over_live",
           "kv_read_mb_per_step", "slot_occupancy_pct",
           "compiles_in_window.serve", "decode_step_mean_ms",
           "decode_step_p50_ms", "decode_launch_ms",
           "sched_host_ms_per_step"}


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
        assert SERVING <= got and got.isdisjoint({"serve_tokens_per_s"})
        assert 35 < line["metrics"]["moe_routed_here_pct"]["value"] < 65
        assert 0 < line["metrics"]["moe_experts_hit_pct"]["value"] <= 100
        assert 0 < line["metrics"]["kv_live_pct"]["value"] <= 100
        assert line["metrics"]["compiles_in_window.serve"]["value"] == 0
        # off the TPU a step reads every active slot's whole plane: three
        # layers of 128 rows of 128 float32 lanes (32 + 8 values and
        # the tile's zeros), at most four slots
        mb = line["metrics"]["kv_read_mb_per_step"]["value"]
        assert 0 < mb <= 4 * 3 * 128 * 128 * 4 / 1e6
        ratio = line["metrics"]["kv_read_over_live"]["value"]
        assert ratio > 1
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


def _serving_cells():
    man = mf.load_manifest()
    return [w["name"] for w in man["workloads"]
            if mf.load_json(mf.traffic_file(w["traffic"]))["runner"]
            == "serve_decode"]


@pytest.mark.parametrize("cell", _serving_cells())
def test_a_serving_cells_requests_fit_the_served_context(cell):
    """Every (prompt, new tokens) pair a serving cell's mix sends ends
    inside the positions its configuration is served with: a request that
    does not ends short, and ``requests_not_whole`` has limit 0."""
    from chipbench.loadgen import RequestMix

    man = mf.load_manifest()
    w = mf.cell(man, cell)
    cfg = mf.load_config(mf.config_file(man, w["config"]), False)
    mix = mf.load_json(mf.traffic_file(w["traffic"]))
    assert RequestMix(mix, 1, cfg["model"]["vocab_size"]).longest() \
        <= cfg["serving"]["max_len"]


def test_the_readmes_chat_mix_does_not_fit_gpt2_xl():
    """Why ``gpt2_xl.chat_poisson`` is no cell yet (PERF.md section 7 #2):
    ``chipbench/README.md``'s worked example, ``shape_seed`` 4, pairs a
    790-token prompt with 240 new tokens, 1030 positions where GPT-2 XL
    has 1024; its pairing has to be re-specified before it can end
    ``correct``."""
    from chipbench.loadgen import RequestMix

    mix = {"prompt_len": {"dist": "loguniform", "lo": 32, "hi": 900},
           "max_new_tokens": {"dist": "loguniform", "lo": 16, "hi": 256},
           "pool": 64, "shape_seed": 4}
    cfg = mf.load_config(mf.config_file(mf.load_manifest(), "gpt2_xl"), False)
    chat = RequestMix(mix, 1, 50257)
    assert (790, 240) in chat.pairs
    assert chat.longest() == 1030 > cfg["serving"]["max_len"] == 1024
    assert CELL in _serving_cells() and "gpt2_xl.chat_poisson" \
        not in _serving_cells()


def test_flops_at_the_published_sizes(cfg):
    """The issue's table of the cut, reckoned again from the shapes."""
    from chipbench.flops import xing4 as fl

    model = cfg["model"]
    attn = 3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 \
        + 32 * 128 * 3584
    assert fl.attention_params(model) == attn == 28_409_856      # 28.41M
    assert fl.hyper_params(model) == 2 * 4 * 3584 * 24           # 0.69M
    assert fl.expert_params(model) == 3 * 3584 * 1024            # 11.01M
    assert fl.routed_here_per_token(model) == 0.5    # 4 of 64, 8 held
    always = (40 * (attn + 2 * 14336 * 24) + 2 * 3 * 3584 * 9216
              + 38 * (3584 * 64 + fl.expert_params(model)) + 16384 * 3584)
    assert fl.always_read_params(model) == always
    assert round(2 * always / 1e9, 2) == 3.70        # always read, GB
    assert fl.matmul_params(model) == always + 38 * 0.5 * 3 * 3584 * 1024
    # resident: all 40 layers and the vocabulary slice, 5.25B = 10.51 GB
    assert round(fl.param_count(model) / 1e9, 2) == 5.25
    assert round(2 * fl.param_count(model) / 1e9, 2) == 10.51
    # a step of 32 tokens hits 87% of the held experts
    assert round(fl.experts_hit_share(model, 32), 3) == 0.873
    assert 9.4e9 < fl.weight_bytes(model, 2, 32) < 9.7e9
    # what is cached of a position: 512 + 64 bfloat16 values, 40 layers
    # (the issue's 46,080 B); the kernel fetches the 640 lanes stored
    assert fl.kv_bytes_per_token(model, 2) == 40 * 576 * 2 == 46_080
    assert 32 * 2048 * fl.kv_bytes_per_token(model, 2) == 3_019_898_880
    step = fl.decode_steps_bytes(model, 1, 32 * 1150, 32, 2)
    assert step == fl.weight_bytes(model, 2, 32) + 32 * 1151 * 46_080
    assert fl.latent_attend_bytes(model, 32 * 1150, 2) == 32 * 1150 * 51_200
    # a decode pair absorbed: 32 heads over 576 and back over 512; a
    # prefill pair expanded: 192 and 128
    assert fl.absorbed_pair_flops(model) == 2 * 32 * (576 + 512)
    assert fl.expanded_pair_flops(model) == 2 * 32 * (192 + 128)
    assert fl.attn_flops(model, 1150) == 40 * 69_632 * 1150
    assert fl.decode_token_flops(model, 1150) == 2 * fl.matmul_params(
        model) + 40 * 69_632 * 1150
    t = 1000
    pairs = t * (t + 1) // 2
    assert fl.prefill_attn_flops(model, t) == pairs * 20_480     # expanded
    assert fl.prefill_attn_flops(model, 8) == 36 * 20_480
    assert fl.prefill_flops(model, t) == 2.0 * t * (
        fl.matmul_params(model) - 16384 * 3584) + 2 * 16384 * 3584 \
        + 40 * pairs * 20_480
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = fl.least_seconds(32 * fl.decode_token_flops(model, 1150),
                                    step, peaks)
    assert bound == "bandwidth" and 0.0135 < least < 0.0145
    least, bound = fl.least_seconds(fl.prefill_flops(model, 1024),
                                    fl.prefill_bytes(model, 1024, 2), peaks)
    assert bound == "compute" and 0.022 < least < 0.024


def test_the_configuration_file_keeps_its_books(cfg):
    """Every reduced key stands beside its published value; the source's
    keys at the file's top level are the catalog's but for the reduced
    ones, and the ``model`` block's; no width differs from the source;
    the program's shapes are the reference's."""
    man = mf.load_manifest()
    entry = next(c for c in man["configs"] if c["name"] == CONFIG)
    assert cfg["reduced"] == entry["reduced"] and cfg["source"] == entry[
        "source"]
    assert set(cfg["published"]) == set(cfg["reduced"])
    assert cfg["published"] == {
        "n_routed_experts": 64, "vocab_size": 131072,
        "num_nextn_predict_layers": 1, "max_position_embeddings": 262144}
    model = cfg["model"]
    own = {"n_layer", "expert_share", "num_experts",
           "mlp_layer_types"}               # the harness's, the cut's
    assert {k: v for k, v in model.items() if k not in own} \
        == {k: cfg[k] for k in model if k not in own}
    assert model["n_layer"] == model["num_hidden_layers"] == 40
    assert model["num_experts"] == model["n_routed_experts"] == 8
    assert model["n_routed_experts"] * model["expert_share"]["of"] == 64
    assert model["vocab_size"] * 8 == 131072
    for key, want in dict(
            hidden_size=3584, num_attention_heads=32, q_lora_rank=768,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, intermediate_size=9216,
            moe_intermediate_size=1024, num_experts_per_tok=4,
            n_shared_experts=1, routed_scaling_factor=2,
            scoring_func="sigmoid", topk_method="noaux_tc",
            norm_topk_prob=True, n_group=1, topk_group=1,
            first_k_dense_replace=2, hc_mult=4, hc_sinkhorn_iters=20,
            hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
            rms_norm_eps=1e-6, rope_theta=10000).items():
        assert model[key] == want, key
    assert model["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert model["mlp_layer_types"] == ["dense"] * 2 + ["sparse"] * 38
    assert len(cfg["assumed"]) >= 8 and "EP8" in cfg["deployment"]
    assert cfg["serving"]["max_slots"] == 32
    assert cfg["serving"]["prefill_buckets"] == [256, 512, 1024]
    from incubator_mxnet_tpu.gluon.model_zoo import get_decoder
    from chipbench.harness import leaf_targets
    from chipbench.references import xing4 as ref

    net = get_decoder(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    params, targets = net._collect_params_with_prefix(), leaf_targets(cfg)
    shapes, want = ref.leaf_shapes(model), {}
    for name in ref.GLOBAL_LEAVES:
        want[targets[name]] = shapes[name]
    for i in range(40):
        for name in ref.layer_leaves(model, i):
            want[targets[f"h{i}.{name}"]] = shapes[name]
    assert {n: p.shape for n, p in params.items()} == want
    assert net.cache_groups(2048) == [
        dict(layers=40, heads=1, rows=2048, head_dim=640, kind="latent")]


def test_the_new_reader_reads_nothing_from_an_older_program():
    """A program whose step records lack ``kv_read_bytes`` (the parent
    commit): the reader returns None and does not raise."""
    steps = [{"site": "decode.x", "kind": "step", "t0": 0.5, "dur_s": 0.01,
              "phases": {}, "active": 2, "kv_read_rows": 10,
              "kv_live_rows": 8, "kv_rows": 40}]
    record = {"kind": "serve", "t0": 0.0, "t1": 1.0,
              "config": {"name": "x"}, "model": {},
              "ledger": {"steps": steps, "capacity": 8}}
    read = mf.reader("kv_read_mb_per_step")
    assert read(record) is None
    assert read({"kind": "serve", "t0": 0.0, "t1": 1.0,
                 "config": {"name": "x"}}) is None
    steps[0]["kv_read_bytes"] = 3_000_000
    steps.append(dict(steps[0], t0=0.6, kv_read_bytes=1_000_000))
    assert read(record) == 2.0


def test_the_seeded_coefficients_do_work_at_the_published_widths(cfg):
    """The hyper-connection leaves drawn at the published widths (one
    sub-layer's, no other weight): over tokens whose streams differ,
    ``H_pre`` spreads over about 0.2-0.8 and ``H_res``'s entries over about
    0.05-0.6, doubly stochastic to 1e-4, unlike from token to token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.references import xing4 as ref

    model = cfg["model"]
    s = ref.sizes(model)
    key = ref.root_key(2 ** 31 + 5)
    p = {n: ref.draw_leaf(model, key, 3, n, "float32")
         for n in ("ha_w", "ha_a", "ha_b")}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 4, 3584),
                          jnp.float32)
    pre, _, res = (np.asarray(a) for a in ref.coefficients(p, "ha", x, s))
    assert np.abs(res.sum(-1) - 1).max() < 1e-4
    assert np.abs(res.sum(-2) - 1).max() < 1e-4
    lo, hi = np.quantile(pre, [0.1, 0.9])
    assert 0.1 < lo < 0.4 and 0.6 < hi < 0.9
    lo, hi = np.quantile(res, [0.1, 0.9])
    assert 0.02 < lo < 0.12 and 0.4 < hi < 0.75
    assert np.abs(res - res[:, :1]).max() > 0.2
    json.dumps(cfg)         # the file is plain data
