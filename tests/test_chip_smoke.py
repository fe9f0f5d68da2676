"""CPU-tier contract of ``chip_smoke.py`` (ISSUE 21).

Without a TPU the script must stop at the device phase, exit non-zero
and print no ``"ok": true`` line; with a tiny size override (used only
here) its phase functions must run on the CPU mesh, Pallas kernels
interpreted. Plus the compile-cache helper's contract: where
``JAX_COMPILATION_CACHE_DIR`` is set no directory is set in code; where
it is not, the fixed ``<checkout>/.jax_cache``."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    interpret=True,
    flash_bhtd=(1, 2, 64, 16), decode_slots=2, decode_heads=2,
    decode_max_len=64,
    conv_cases=(("s1", 2, 8, 8, 8, 8, 3, 1, 1, False, True),
                ("s2", 2, 8, 8, 8, 8, 3, 2, 1, False, False),
                ("resid", 2, 8, 8, 8, 8, 1, 1, 0, True, True)),
    resnet="resnet18_v1", classes=10, image=32, batch_per_chip=2,
    train_steps=8, superstep_k=2,
    gpt="gpt_decoder_tiny", vocab=97, max_len=64, max_slots=4,
    prefill_buckets=(8, 32), prompt_lens=(5, 11, 20, 7), new_tokens=6,
    bert="bert_12_768_12",
    bert_kwargs=(("units", 32), ("hidden_size", 64), ("num_layers", 2),
                 ("num_heads", 4)),
    bert_vocab=64, bert_seq=16, bert_batch=8, bert_steps=2)


def _lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


def _run_script(*args, env=None):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("XLA_FLAGS", None)
    full.update(env or {})
    return subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py"),
                           *args], capture_output=True, text=True,
                          timeout=300, env=full, cwd=REPO)


@pytest.mark.parametrize("args", [(), ("--four-chips",)])
def test_script_fails_at_device_phase_without_tpu(args, tmp_path):
    r = _run_script(*args, env={"JAX_COMPILATION_CACHE_DIR":
                                str(tmp_path / "cache")})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout, r.stdout
    assert "no TPU" in r.stderr


def test_kernels_phase_runs_interpreted_on_cpu(capsys):
    chip_smoke.phase_kernels(TINY, chip_smoke.CompileLog())
    (line,) = _lines(capsys)
    assert line["phase"] == "kernels" and line["interpret"] is True
    assert set(line["flash_rel_err"]) == {"out", "dq", "dk", "dv", "decode"}
    assert set(line["conv_rel_err"]) == {"s1", "s2", "resid"}
    assert "dw" in line["conv_rel_err"]["s1"]
    assert "dw" not in line["conv_rel_err"]["s2"]      # forward only


def test_train_phase_runs_on_cpu_mesh(capsys):
    chip_smoke.phase_train(TINY, chip_smoke.CompileLog())
    (line,) = _lines(capsys)
    assert line["phase"] == "train" and line["donate"] is True
    assert len(line["losses"]) == TINY.train_steps
    assert line["losses"][-1] < line["losses"][0]


def test_serve_phase_runs_on_cpu_and_second_session_deserializes(
        capsys, tmp_path):
    chip_smoke.phase_serve(TINY, chip_smoke.CompileLog(),
                           str(tmp_path / "artifacts"))
    (line,) = _lines(capsys)
    assert line["phase"] == "serve"
    assert line["requests"] == len(TINY.prompt_lens)
    assert line["warmup_compiles"] > 0
    assert line["second_warmup_compiles"] == 0
    assert line["second_deserialized"] == line["warmup_compiles"]


def test_four_chip_phase_runs_on_four_virtual_devices(capsys, monkeypatch):
    import jax

    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    chip_smoke.phase_four_chips(TINY, chip_smoke.CompileLog())
    (line,) = _lines(capsys)
    assert line["phase"] == "four_chips"
    for name in ("zero3_data4", "zero3_data2_model2"):
        assert line[name]["max_rel_loss_err"] <= chip_smoke.TOL_LOSS
        assert line[name]["collectives"]["all-gather"] > 0


def test_compile_cache_helper_leaves_env_dir_alone(monkeypatch, tmp_path):
    import jax

    from incubator_mxnet_tpu import runtime

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_helper_fixed_path_when_env_unset(monkeypatch):
    import jax

    from incubator_mxnet_tpu import runtime

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = runtime.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert runtime.enable_compile_cache() == got      # same every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
