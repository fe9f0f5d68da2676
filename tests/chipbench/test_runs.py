"""Rehearsals of whole runs on the CPU at the files' tiny presets: the
last line's contract, the reference against the program, the control, and
the timed path broken underneath (``correct`` has to come out false)."""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SERVE, TRAIN = "gpt2_xl.decode_closed16", "gpt2_medium.train_seq1024"


def test_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", SERVE,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("workload,trace", [(SERVE, 0), (SERVE, 1),
                                            (TRAIN, 0)])
def test_rehearsal_ends_in_the_contracts_line(rehearse, workload, trace):
    rc, line = rehearse(workload, trace=trace, seed=2 ** 31 + 77)
    assert rc == 0 and KEYS <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert all(n["value"] <= n["limit"] for n in line["compared"])
    want = "per_layer" if trace else "end_to_end"
    from chipbench import manifest as mf

    names = {m["name"] for m in mf.metrics_of(mf.load_manifest(), workload,
                                              want)}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    # no share of a peak from a CPU run
    assert not any("mfu" in n or "roofline" in n for n in line["metrics"])


def test_poisson_mix_runs_as_data(rehearse):
    """The open loop comes as a data file: arrivals on the schedule,
    latency from the due time, lateness on record."""
    from chipbench import manifest as mf
    from chipbench.harness import CompileLog, Context
    from chipbench.runners import serve_decode
    from chipbench.loadgen import with_rehearsal
    import time

    man = mf.load_manifest()
    cfg = mf.load_config(mf.config_file(man, "gpt2_xl"), rehearse=True)
    mix = with_rehearsal(mf.load_json(os.path.join(
        os.path.dirname(__file__), "data", "chat_poisson_rehearsal.json")),
        True)
    ctx = Context(config=cfg, traffic=mix, seed=3, rehearse=True,
                  cache_dir=os.path.join(mf.ROOT, ".chipbench_cache"),
                  trace_dir=None, t_start=time.perf_counter(),
                  compiles=CompileLog(), peaks=None)
    st = serve_decode.build(ctx)
    rec = serve_decode.measure(st, 1.5)
    serve_decode.release(st)
    assert rec["attempted"] == pytest.approx(60, abs=6) and not rec["failed"]
    late = [r["sent"] - r["due"] for r in rec["requests"]]
    assert max(late) < 0.25 and min(late) >= 0
    nums = serve_decode.check(ctx, rec)
    assert all(n["value"] <= n["limit"] for n in nums)


def test_reference_agrees_with_the_zoo_in_float32():
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import get_gpt
    from chipbench import manifest as mf
    from chipbench.harness import leaf_targets
    from chipbench.references import gpt2 as ref

    cfg = mf.load_config(mf.config_file(mf.load_manifest(), "gpt2_xl"), True)
    model, targets = cfg["model"], leaf_targets(cfg)
    net = get_gpt(cfg["zoo"]["spec"], **cfg["zoo"]["args"])
    g, layers = ref.draw_all(model, 11, "float32")
    params = net._collect_params_with_prefix()
    for leaf, arr in ref.flatten_leaves(g, layers).items():
        params[targets[leaf]].set_data(mx.nd.NDArray(arr))
    toks = np.random.default_rng(0).integers(0, 97, (2, 24)).astype(np.int32)
    want = np.asarray(ref.forward(g, layers, jnp.asarray(toks), 4))
    got = net(mx.nd.array(toks, dtype="int32")).asnumpy()
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max() + 2e-5
    # the layer-by-layer path the chip uses gives the same logits
    again = np.asarray(ref.sequence_logits(model, 11, "float32", toks))
    assert np.abs(again - want).max() < 1e-5


@pytest.mark.parametrize("control", ["int8", "half_batch"])
def test_a_training_control_comes_out_not_correct(rehearse, control):
    """The reference a step lower (int8 products, forward and backward),
    or with half of the batch left out, put in the program's place: the
    harness's own comparison has to say not correct, by the gradient."""
    rc, line = rehearse(TRAIN, "--control", control)
    assert rc == 0 and line["control"] == control
    assert line["correct"] is False
    got = {n["name"]: n for n in line["compared"]}
    grad = got["grad_norm_gap_worst_leaf"]
    assert grad["value"] > grad["limit"]
    assert grad["value"] > 10 * line["notes"]["program"][grad["name"]]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("control,correct", [
    ("stated", True), ("int8", False), ("fp8", False)])
def test_a_serving_control_comes_out_not_correct(control, correct, seed):
    """The serving controls at a size a test can hold (4 x 128, vocab 4001,
    64 rows of 64): the tokens that the lower precision puts first stand
    in the served tokens' place and go through the runner's ``check`` and
    the harness's ``judge``. At this size the plain block in the stated
    type reads a logit noise of 0.0026-0.0031 and a mean gap of 3-4e-5,
    int8 0.0051-0.0056 and 1.1-1.3e-4, float8 0.017 and 1.1-1.3e-3 (four
    seeds), so the limits here are 0.004 and 5e-4: the stated type passes,
    int8 fails by the noise, float8 by both."""
    import time
    from chipbench import manifest as mf
    from chipbench.harness import CompileLog, Context
    from chipbench.run import judge
    from chipbench.runners import serve_decode

    man = mf.load_manifest()
    cfg = mf.load_config(mf.config_file(man, "gpt2_xl"), rehearse=False)
    cfg["model"] = dict(cfg["model"], n_layer=4, n_embd=128, n_head=4,
                        n_positions=64, vocab_size=4001)
    cfg["check"]["serve"].update(logit_noise_limit=0.004,
                                 gap_mean_limit=5e-4,
                                 controls=["stated", "int8", "fp8"])
    mix = {"pool": 1, "shape_seed": 0, "check_sample": 64,
           "prompt_len": {"dist": "fixed", "value": 8},
           "max_new_tokens": {"dist": "fixed", "value": 56}}
    ctx = Context(config=cfg, traffic=mix, seed=seed, rehearse=True,
                  cache_dir="", trace_dir=None, t_start=time.perf_counter(),
                  compiles=CompileLog(), peaks=None)
    rng = np.random.default_rng(seed)
    draw = lambda n: rng.integers(0, 4001, n).astype(np.int32)
    requests = [{"submit": 0.5, "failed": False, "prompt": draw(8),
                 "prompt_len": 8, "tokens": list(draw(56)), "max_new": 56,
                 "stamps": [0.6]} for _ in range(64)]
    zero = dict.fromkeys(("steps", "prefills", "decode_seconds",
                          "prefill_seconds"), 0)
    record = {"requests": requests, "t0": 0.0, "t1": 1.0,
              "counters": {"start": zero, "end": zero}}
    numbers = serve_decode.check(ctx, record, control=control)
    assert judge(numbers) is correct
    bad = [n["name"] for n in numbers if not n["value"] <= n["limit"]]
    assert bad == {"stated": [], "int8": ["served_logit_noise"],
                   "fp8": ["served_gap_mean", "served_logit_noise"]}[control]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        rehearse, monkeypatch):
    from incubator_mxnet_tpu.serving.decode import DecodeHandle

    put = DecodeHandle._put

    def wrong(self, tok):
        put(self, (tok + 1) % 97 if len(self._tokens) == 2 else tok)

    monkeypatch.setattr(DecodeHandle, "_put", wrong)
    rc, line = rehearse(SERVE)
    assert rc == 0 and line["correct"] is False
    bad = [n for n in line["compared"] if not n["value"] <= n["limit"]]
    assert [n["name"] for n in bad] == ["served_gap_mean",
                                        "served_logit_noise"]


def test_a_request_cut_short_is_not_correct(rehearse, monkeypatch):
    from incubator_mxnet_tpu.serving.decode import DecodeHandle

    put = DecodeHandle._put
    monkeypatch.setattr(
        DecodeHandle, "_put",
        lambda self, tok: None if len(self._tokens) == 3 else put(self, tok))
    rc, line = rehearse(SERVE)
    assert line["correct"] is False
    assert line["compared"][0]["name"] == "requests_not_whole"
    assert line["compared"][0]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(rehearse, monkeypatch, fault):
    from incubator_mxnet_tpu.parallel import SPMDTrainer

    step = SPMDTrainer.step

    def state_unchanged(self, data, labels):
        keep = (self.params, self.frozen, self.opt_state)
        saved, self._donate = self._donate, False
        self._step_cache.clear()
        loss = step(self, data, labels)
        self._donate = saved
        self.params, self.frozen, self.opt_state = keep
        return loss

    def half_batch(self, data, labels):
        n = data.shape[0] // 2
        return step(self, np.concatenate([data[:n], data[:n]]),
                    np.concatenate([labels[:n], labels[:n]]))

    monkeypatch.setattr(SPMDTrainer, "step", locals()[fault])
    rc, line = rehearse(TRAIN)
    assert rc == 0 and line["correct"] is False
