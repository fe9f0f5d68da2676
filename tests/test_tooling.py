"""Tooling-tier tests: im2rec packer, opperf harness, bandwidth bench,
examples/ smoke (SURVEY.md §2.3)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=900):  # generous: examples compile XLA programs and
    # may share the box with a concurrent bench run
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


def test_env_vars_doc_in_sync():
    """docs/ENV_VARS.md is GENERATED from the config knob registry
    (VERDICT r5 item 8 — the reference env_var.md analog); this test
    fails whenever a knob is added/changed without regenerating:

        python -c "from incubator_mxnet_tpu.config import write_env_vars_md; write_env_vars_md()"
    """
    from incubator_mxnet_tpu.config import generate_env_vars_md

    path = os.path.join(REPO, "docs", "ENV_VARS.md")
    assert os.path.exists(path), "docs/ENV_VARS.md missing — regenerate"
    with open(path) as f:
        committed = f.read()
    assert committed == generate_env_vars_md(), (
        "docs/ENV_VARS.md is stale — regenerate from the registry")


def test_env_vars_doc_covers_new_kernel_knobs():
    """The v2 Pallas conv knobs must be registered (and therefore
    documented): the doc row exists and the knob resolves."""
    from incubator_mxnet_tpu.config import config, generate_env_vars_md

    md = generate_env_vars_md()
    for name in ("MXTPU_CONV_OC_BLOCK", "MXTPU_CONV_ROW_TARGET",
                 "MXTPU_CONV_VMEM_MB", "MXTPU_CONV_IM2COL",
                 "MXTPU_CONV_BWD"):
        assert f"| `{name}` |" in md, name
        assert name in config._knobs


def test_im2rec_list_and_pack_roundtrip(tmp_path):
    from PIL import Image

    root = tmp_path / "data"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = np.random.RandomState(i).randint(
                0, 255, (10, 12, 3), np.uint8)
            Image.fromarray(arr).save(root / cls / f"{i}.png")
    prefix = str(tmp_path / "ds")

    p = _run([os.path.join(REPO, "tools", "im2rec.py"), prefix, str(root),
              "--list", "--shuffle", "0"])
    assert p.returncode == 0, p.stderr
    lines = open(prefix + ".lst").read().strip().splitlines()
    assert len(lines) == 6
    labels = {int(float(l.split("\t")[1])) for l in lines}
    assert labels == {0, 1}

    p = _run([os.path.join(REPO, "tools", "im2rec.py"), prefix, str(root)])
    assert p.returncode == 0, p.stderr
    assert os.path.exists(prefix + ".rec")
    assert os.path.exists(prefix + ".idx")

    from incubator_mxnet_tpu import recordio

    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    hdr, img = recordio.unpack_img(rec.read_idx(0))
    assert img.shape == (10, 12, 3)
    assert hdr.label in (0.0, 1.0)


def test_opperf_subset_runs():
    p = _run([os.path.join(REPO, "benchmark", "opperf.py"),
              "--ops", "relu,FullyConnected,Convolution,sum,_mul_scalar",
              "--batch", "8", "--iters", "2", "--json"])
    assert p.returncode == 0, p.stderr
    out = json.loads(p.stdout)
    by_op = {r["op"]: r for r in out["results"]}
    assert set(by_op) == {"relu", "FullyConnected", "Convolution", "sum",
                          "_mul_scalar"}
    for r in by_op.values():
        assert "error" not in r, r
        assert r["fwd_ms"] > 0


def test_opperf_covers_majority_of_registry():
    """The harness's argspec table must cover most of the op surface —
    the opperf-analog completeness check."""
    from benchmark.opperf import ARGSPECS
    from incubator_mxnet_tpu.ops import registry

    ops = registry.list_ops()
    covered = [o for o in ops if o in ARGSPECS]
    assert len(covered) >= len(ops) * 0.55, (
        f"opperf covers {len(covered)}/{len(ops)}")


def test_bandwidth_bench_runs():
    p = _run([os.path.join(REPO, "tools", "bandwidth.py"),
              "--min-mb", "0.25", "--max-mb", "0.5", "--iters", "2"])
    assert p.returncode == 0, p.stderr
    assert "GB/s" in p.stdout


@pytest.mark.slow
def test_example_image_classification_runs():
    p = _run([os.path.join(REPO, "examples", "image_classification",
                           "train.py"), "--network", "resnet18_v1",
              "--image-size", "32", "--batch-size", "8",
              "--iters-per-epoch", "3", "--epochs", "1"])
    assert p.returncode == 0, p.stderr
    assert "img/s" in p.stdout


@pytest.mark.slow
def test_example_lstm_ptb_runs():
    p = _run([os.path.join(REPO, "examples", "rnn", "lstm_ptb.py"),
              "--vocab", "50", "--embed", "16", "--hidden", "16",
              "--seq-len", "8", "--batch-size", "4", "--iters", "3"])
    assert p.returncode == 0, p.stderr
    assert "perplexity" in p.stdout


def test_example_moe_runs():
    r = _run([os.path.join(REPO, "examples", "parallel", "train_moe.py")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "final loss" in r.stdout


def test_example_pipeline_runs():
    r = _run([os.path.join(REPO, "examples", "parallel",
                           "train_pipeline.py")])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "final loss" in r.stdout


def test_im2rec_native_packer_matches_python(tmp_path):
    """The C++ packer (reference tools/im2rec.cc analog) must produce a
    .rec/.idx readable by the same readers, with identical headers and
    equivalent pixels (jpeg re-encode at the same quality differs only by
    codec noise)."""
    from PIL import Image

    from incubator_mxnet_tpu import native, recordio

    if native.lib() is None:
        import pytest

        pytest.skip("native toolchain unavailable")

    root = tmp_path / "data"
    for cls in ("a", "b"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            arr = np.random.RandomState(10 + i).randint(
                0, 255, (48, 64, 3), np.uint8)
            Image.fromarray(arr).save(root / cls / f"{i}.jpg", quality=95)
    prefix_n = str(tmp_path / "nat")
    prefix_p = str(tmp_path / "py")

    p = _run([os.path.join(REPO, "tools", "im2rec.py"), prefix_n,
              str(root), "--list", "--shuffle", "0"])
    assert p.returncode == 0, p.stderr
    import shutil

    shutil.copy(prefix_n + ".lst", prefix_p + ".lst")

    # native (default) and forced-python, both with resize
    p = _run([os.path.join(REPO, "tools", "im2rec.py"), prefix_n,
              str(root), "--resize", "32", "--num-thread", "3"])
    assert p.returncode == 0, p.stderr
    assert "[native" in p.stdout, p.stdout
    p = _run([os.path.join(REPO, "tools", "im2rec.py"), prefix_p,
              str(root), "--resize", "32", "--no-native"])
    assert p.returncode == 0, p.stderr

    rn = recordio.MXIndexedRecordIO(prefix_n + ".idx", prefix_n + ".rec",
                                    "r")
    rp = recordio.MXIndexedRecordIO(prefix_p + ".idx", prefix_p + ".rec",
                                    "r")
    for idx in range(6):
        hn, imn = recordio.unpack_img(rn.read_idx(idx))
        hp, imp = recordio.unpack_img(rp.read_idx(idx))
        assert hn.label == hp.label
        assert hn.id == hp.id
        # shorter side resized to 32 by both packers
        assert min(imn.shape[:2]) == 32, imn.shape
        assert imn.shape == imp.shape, (imn.shape, imp.shape)
        # same image content modulo jpeg codec noise + resampler choice
        diff = np.abs(imn.astype(np.int32) - imp.astype(np.int32))
        assert diff.mean() < 30.0, diff.mean()


def test_cpp_consumer_demo_end_to_end(tmp_path):
    """A pure C++ program driving the C ABI (pack -> stream -> decode) —
    the cpp-package-analog evidence for SURVEY §1 row 7 (the C API's
    purpose is serving non-Python consumers)."""
    import subprocess

    from PIL import Image

    demo = os.path.join(REPO, "examples", "cpp", "mxtpu_io_demo")
    if not os.path.exists(demo):
        r = subprocess.run(["make", "-C",
                            os.path.join(REPO, "examples", "cpp")],
                           capture_output=True, text=True, timeout=240)
        if r.returncode != 0:
            import pytest

            pytest.skip(f"toolchain unavailable: {r.stderr[-200:]}")

    root = tmp_path / "imgs"
    root.mkdir()
    for i in range(4):
        arr = np.random.RandomState(i).randint(0, 255, (24, 32, 3),
                                               np.uint8)
        Image.fromarray(arr).save(root / f"{i}.jpg", quality=92)
    lst = tmp_path / "ds.lst"
    with open(lst, "w") as f:
        for i in range(4):
            f.write(f"{i}\t{float(i)}\t{i}.jpg\n")

    p = subprocess.run([demo, str(lst), str(root),
                        str(tmp_path / "out")],
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "packed 4 records" in p.stdout
    assert "read 4 records, decoded 4 jpegs" in p.stdout


def test_cpp_checkpoint_roundtrip_end_to_end(tmp_path):
    """Round 5 (VERDICT item 4): a pure C++ program loads a gluon
    checkpoint through the C ABI, applies an update to every fp32
    tensor, writes a new .params + a RecordIO stream; Python loads both
    back and verifies values — the MXNDArrayLoad/Save C-API slice."""
    import subprocess

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, recordio
    from incubator_mxnet_tpu import ndarray as nd
    from incubator_mxnet_tpu.gluon import nn

    demo = os.path.join(REPO, "examples", "cpp", "mxtpu_params_demo")
    if not os.path.exists(demo):
        r = subprocess.run(["make", "-C",
                            os.path.join(REPO, "examples", "cpp"),
                            "mxtpu_params_demo"],
                           capture_output=True, text=True, timeout=240)
        if r.returncode != 0:
            import pytest

            pytest.skip(f"toolchain unavailable: {r.stderr[-200:]}")

    # a real gluon checkpoint, not a synthetic dict
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=4, activation="relu"), nn.Dense(3))
    net.initialize(init="xavier")
    net(mx.nd.zeros((1, 4)))
    src = str(tmp_path / "net.params")
    net.save_parameters(src)
    before = {k: v.asnumpy() for k, v in nd.load(src).items()}

    out_p = str(tmp_path / "half.params")
    out_r = str(tmp_path / "names.rec")
    p = subprocess.run([demo, src, out_p, out_r],
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stdout + p.stderr
    assert f"{len(before)} tensors" in p.stdout

    after = nd.load(out_p)
    assert set(after) == set(before)
    for k, v in before.items():
        got = after[k].asnumpy()
        if v.dtype == np.float32:
            np.testing.assert_allclose(got, v * 0.5, rtol=1e-6,
                                       err_msg=k)
        else:
            np.testing.assert_array_equal(got, v, err_msg=k)

    # the C-written RecordIO stream reads back through the Python reader
    rr = recordio.MXRecordIO(out_r, "r")
    names = []
    while True:
        rec = rr.read()
        if rec is None:
            break
        names.append(rec.decode())
    rr.close()
    assert sorted(names) == sorted(before)


def _run_pjrt_demo(demo_name, tmp_path, in_units, hidden, classes,
                   batch):
    """Shared protocol for the PJRT C/C++ inference demos:
    build-if-missing, export a small net, run the binary, and verify
    the .params output against the Python forward.

    One process per chip: the demo binary is the process that takes the
    chip, so this parent must not hold it. The test therefore runs from
    the default CPU-tier pytest on a machine that has a chip, and skips
    both where there is no chip and under ``MXTPU_TEST_PLATFORM=tpu``
    (where pytest itself holds the chip and the child could only hang
    or fail)."""
    import glob
    import subprocess

    import pytest

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import onnx as monnx
    from incubator_mxnet_tpu import ndarray as nd
    from incubator_mxnet_tpu.gluon import nn

    if os.environ.get("MXTPU_TEST_PLATFORM", "cpu") != "cpu":
        pytest.skip("this process holds the chip; the C program needs it")
    if not glob.glob("/dev/accel*") and not glob.glob("/dev/vfio/[0-9]*"):
        pytest.skip("PJRT-from-C needs the real TPU")
    demo = os.path.join(REPO, "examples", "cpp", demo_name)
    if not os.path.exists(demo):
        r = subprocess.run(["make", "-C",
                            os.path.join(REPO, "examples", "cpp"),
                            demo_name],
                           capture_output=True, text=True, timeout=240)
        if r.returncode != 0:
            pytest.skip(f"toolchain/PJRT header unavailable: "
                        f"{r.stderr[-200:]}")

    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, in_units=in_units, activation="relu"),
            nn.Dense(classes))
    net.initialize(init="xavier")
    net(mx.nd.zeros((1, in_units)))
    prefix = str(tmp_path / "cnet")
    monnx.export_for_pjrt_c(net, mx.nd.zeros((batch, in_units)), prefix)
    x = np.random.RandomState(0).rand(batch, in_units).astype(np.float32)
    nd.save(str(tmp_path / "in.params"), {"0": nd.array(x)})
    golden = net(nd.array(x)).asnumpy()

    import libtpu

    env = dict(os.environ)
    env.setdefault("MXTPU_PJRT_SO", os.path.join(
        os.path.dirname(libtpu.__file__), "libtpu.so"))
    env.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    p = subprocess.run(
        [demo, prefix, str(tmp_path / "in.params"),
         str(tmp_path / "out.params")],
        capture_output=True, text=True, timeout=400, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "executed on TPU" in p.stdout
    out = nd.load(str(tmp_path / "out.params"))["0"].asnumpy()
    np.testing.assert_allclose(out, golden, rtol=2e-5, atol=2e-5)


def test_cpp_pjrt_inference_end_to_end(tmp_path):
    """Round 5 (VERDICT item 4 stretch): a pure C++ program compiles the
    exported StableHLO through the PJRT C API and executes inference ON
    THE TPU — checkpoint in via the C ABI, logits out as .params, bit-
    checked against the Python forward. Needs a chip this process does
    not hold (see ``_run_pjrt_demo``)."""
    _run_pjrt_demo("mxtpu_infer_demo", tmp_path, 8, 16, 5, 4)


def test_cpp_frontend_predictor_end_to_end(tmp_path):
    """Round 5: the header-only C++ frontend (include/mxtpu_cpp.hpp —
    the cpp-package analog) runs Checkpoint + RecordIO + PJRT Predictor
    end to end; logits match the Python forward. TPU tier only."""
    _run_pjrt_demo("mxtpu_cpp_demo", tmp_path, 6, 12, 4, 3)


def test_native_params_writer_matches_python_and_numpy(tmp_path):
    """The C .params writer's output is byte-level compatible with BOTH
    nd.load and raw numpy.load; the C reader opens Python-written files
    (including bf16 entries via ml_dtypes descr)."""
    import io

    import pytest

    from incubator_mxnet_tpu import native
    from incubator_mxnet_tpu import ndarray as nd

    if native.lib() is None:
        pytest.skip("native library unavailable")

    rs = np.random.RandomState(0)
    arrays = {
        "w": rs.rand(5, 3).astype(np.float32),
        "idx": np.arange(11, dtype=np.int32),
        "mask": (rs.rand(2, 2, 2) > 0.5).astype(np.uint8),
        "scalar": np.array(2.25, np.float64),
    }
    path = str(tmp_path / "c.params")
    native.native_params_save(path, arrays)

    via_nd = nd.load(path)
    for k, v in arrays.items():
        np.testing.assert_array_equal(via_nd[k].asnumpy(), v, err_msg=k)
    with open(path, "rb") as f:
        assert f.read(8) == b"MXTPU001"
        z = np.load(io.BytesIO(f.read()))
        for k, v in arrays.items():
            np.testing.assert_array_equal(z[k], v, err_msg=k)

    # C reader over a Python-written checkpoint incl. bfloat16
    import ml_dtypes

    py_path = str(tmp_path / "py.params")
    bf = rs.rand(4, 2).astype(ml_dtypes.bfloat16)
    nd.save(py_path, {"a": nd.array(arrays["w"]),
                      "b16": nd.array(bf, dtype="bfloat16")})
    got = native.native_params_load(py_path)
    np.testing.assert_array_equal(got["a"], arrays["w"])
    assert got["b16"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(
        got["b16"].astype(np.float32), bf.astype(np.float32))


def test_native_recordio_writer_interop(tmp_path):
    """NativeRecordWriter (C) <-> Python MXRecordIO and the C prefetch
    reader agree on the dmlc framing, including empty and odd-length
    records (padding path)."""
    import pytest

    from incubator_mxnet_tpu import native, recordio

    if native.lib() is None:
        pytest.skip("native library unavailable")

    recs = [b"", b"x", b"abc", b"0123456789" * 7, b"\x00\xff" * 33]
    path = str(tmp_path / "w.rec")
    w = native.NativeRecordWriter(path)
    for r in recs:
        w.write(r)
    w.close()

    rr = recordio.MXRecordIO(path, "r")
    got = []
    while True:
        rec = rr.read()
        if rec is None:
            break
        got.append(bytes(rec))
    rr.close()
    assert got == recs

    nr = native.NativeRecordReader(path)
    got_c = []
    while True:
        rec = nr.read()
        if rec is None:
            break
        got_c.append(rec)
    nr.close()
    assert got_c == recs


def test_env_vars_doc_covers_v3_conv_knobs():
    """The v3 epilogue/stride-2 knobs must be registered (and therefore
    documented)."""
    from incubator_mxnet_tpu.config import config, generate_env_vars_md

    md = generate_env_vars_md()
    for name in ("MXTPU_CONV_EPILOGUE", "MXTPU_CONV_STRIDE2"):
        assert f"| `{name}` |" in md, name
        assert name in config._knobs


def test_telemetry_report_flags_dispatch_regression(tmp_path):
    """ISSUE 11 guard: --compare must flag any workload whose bench-row
    dispatches_per_step GREW vs the previous round (the signature of the
    superstep wiring silently falling back to eager dispatch), and stay
    quiet when it shrank."""
    import tools.telemetry_report as rep

    def write(path, dps):
        with open(path, "w") as f:
            for metric, d in dps.items():
                f.write(json.dumps({
                    "kind": "bench", "metric": metric, "value": 100.0,
                    "unit": "images/sec/chip",
                    "dispatches_per_step": d}) + "\n")
        return str(path)

    a = write(tmp_path / "a.jsonl",
              {"resnet50_v1_train_throughput_per_chip": 0.04,
               "ssd300_train_throughput_per_chip": 0.04})
    b = write(tmp_path / "b.jsonl",
              {"resnet50_v1_train_throughput_per_chip": 1.0,   # regressed
               "ssd300_train_throughput_per_chip": 0.034})     # improved
    out = rep.compare(a, b)
    assert "dispatches_per_step grew on 1 metric(s)" in out
    assert "resnet50_v1_train_throughput_per_chip/dispatches_per_step" \
        in out.split("!!", 1)[1]
    # the improved workload is not flagged
    flagged = [l for l in out.splitlines() if l.startswith("!!   ")]
    assert len(flagged) == 1

    # no regression (identical runs) -> no flag block at all
    out_ok = rep.compare(a, a)
    assert "grew" not in out_ok


def test_telemetry_report_shows_decision_record(tmp_path):
    """part_d's kind:"decision" JSONL record surfaces in the summary and
    its ratio is a comparable metric."""
    import tools.telemetry_report as rep

    sink = tmp_path / "run.jsonl"
    with open(sink, "w") as f:
        f.write(json.dumps({
            "kind": "decision", "metric": "resnet_decision_part_d",
            "ratio": 0.97, "threshold": 0.95, "winner": "fused",
            "epilogue": "auto", "conv_bwd": "auto",
            "stride2": "auto"}) + "\n")
        f.write(json.dumps({
            "kind": "bench", "metric": "resnet50_v1_train_throughput",
            "value": 2490.7, "unit": "images/sec/chip",
            "dispatches_per_step": 0.04}) + "\n")
    out = rep.summarize(str(sink))
    assert "decision resnet_decision_part_d" in out
    assert "winner=fused" in out and "ratio=0.970" in out
    assert "0.040" in out  # bench disp/step column
    metrics = rep._comparable_metrics(rep._read(str(sink)))
    assert metrics["decision/resnet_decision_part_d/ratio"] == 0.97


def test_observability_doc_catalogs_every_metric_family():
    """Doc-sync for docs/OBSERVABILITY.md (the ENV_VARS.md discipline
    applied to metrics): every ``mxtpu_*`` metric family instantiated
    in the runtime — a ``counter(``/``gauge(``/``histogram(`` call with
    a literal name — must have a row in the catalog. A new instrument
    without documentation fails CI here."""
    import re

    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    # the catalog compresses sibling families with one-level brace
    # expansion (`mxtpu_serving_cache_{hits,misses}_total`) — expand it
    documented = set()
    for tok in re.findall(r"mxtpu_[a-z0-9_]*(?:\{[a-z0-9_,]+\})?"
                          r"[a-z0-9_]*", doc):
        m = re.match(r"(.*)\{([^}]+)\}(.*)", tok)
        if m:
            documented.update(m.group(1) + alt + m.group(3)
                              for alt in m.group(2).split(","))
        else:
            documented.add(tok)
    pat = re.compile(
        r"""(?:counter|gauge|histogram)\(\s*["'](mxtpu_[a-z0-9_]+)["']""")
    families = set()
    pkg = os.path.join(REPO, "incubator_mxnet_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn)) as f:
                families.update(pat.findall(f.read()))
    assert families, "metric-family scan found nothing — pattern broken?"
    missing = sorted(families - documented)
    assert not missing, (
        f"metric families missing from docs/OBSERVABILITY.md: {missing} "
        "— add catalog rows for them")
