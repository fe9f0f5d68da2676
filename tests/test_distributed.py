"""Multi-process distributed tests: launcher + dist kvstore + cross-process
SPMD (SURVEY.md §4 'Distributed' tier — multi-process on one box; reference
tools/launch.py + tests/nightly/dist_sync_kvstore.py)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAUNCHER = os.path.join(REPO, "tools", "launch.py")
PAYLOAD = os.path.join(REPO, "tests", "dist_worker_payload.py")


def _clean_env():
    env = dict(os.environ)
    # the workers must form their own coordination service
    for k in list(env):
        if k.startswith(("DMLC_", "MXTPU_COORDINATOR", "MXTPU_NUM_WORKERS",
                         "MXTPU_WORKER_RANK")):
            del env[k]
    # CPU-only by explicit environment: a worker never asks for a chip
    # the parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    # workers import the package from the repo; existing entries stay
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


@pytest.mark.parametrize("n", [2])
def test_launcher_runs_dist_kvstore_workers(n):
    """launch.py spawns N workers; each drives KVStoreDist push/pull/
    pushpull and a jitted cross-process AllReduce. Exit 0 everywhere."""
    proc = subprocess.run(
        [sys.executable, LAUNCHER, "-n", str(n), "--launcher", "local",
         sys.executable, PAYLOAD],
        env=_clean_env(), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    for rank in range(n):
        assert f"RANK {rank}/{n} OK" in proc.stdout


def test_weak_scaling_curve_8procs():
    """Up to 8 procs x 2 devices of the compiled cross-process collective
    path: every launch exits 0 and reports its process and device
    counts. The step times are printed and not compared: they are
    wall-clock CPU timings of processes that share the cores with the
    other test workers (the 4- and 8-proc steps read 8.9x and 16.7x
    the 1-proc one alone, 6.7x and 59.9x beside five workers), and a
    CPU timing ratio is not speed."""
    import json

    payload = os.path.join(REPO, "tests", "dist_scaling_payload.py")
    results = {}
    for n in (1, 2, 4, 8):
        proc = subprocess.run(
            [sys.executable, LAUNCHER, "-n", str(n), "--launcher", "local",
             sys.executable, payload],
            env=_clean_env(), capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, (
            f"n={n}\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
        # ranks share the pipe, so the JSON can share a line with other
        # ranks' output on either side — extract the {...} span
        import re as _re

        m = _re.search(r'\{"procs".*?\}', proc.stdout)
        assert m, (f"n={n}: no JSON\nstdout:\n{proc.stdout}"
                   f"\nstderr:\n{proc.stderr[-2000:]}")
        results[n] = json.loads(m.group(0))
        assert results[n]["procs"] == n
        assert results[n]["devices"] == 2 * n
    print("weak-scaling:", results)


def test_comm_compute_overlap_measurement_2procs():
    """VERDICT r5 item 8: the comm/compute-overlap payload runs on a
    2-process mesh and reports the three bounds + overlap fraction.
    The assertion is structural (numbers exist and are positive) — the
    overlap FRACTION is environment-dependent (localhost Gloo vs real
    ICI) and is recorded in PROFILE.md, not asserted here."""
    import json
    import re as _re

    payload = os.path.join(REPO, "tests", "dist_overlap_payload.py")
    proc = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "2", "--launcher", "local",
         sys.executable, payload],
        env=_clean_env(), capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}")
    m = _re.search(r'\{"procs".*?\}', proc.stdout)
    assert m, proc.stdout
    r = json.loads(m.group(0))
    assert r["procs"] == 2
    assert r["t_step_ms"] > 0 and r["t_comp_ms"] > 0 and \
        r["t_comm_ms"] > 0
    # no ratio between the three is asserted: they are wall-clock
    # timings of three separate loops on a CPU the other test workers
    # load unevenly, so any bound tight enough to mean something fails
    # on a busy machine
    print("overlap:", r)


def test_launcher_runs_zero3_overlap_payload_2procs():
    """ISSUE 18: the double-buffered ZeRO-3 bounds case on a 2-process
    mesh — t_step (scan with in-loop param all-gathers) vs t_comp
    (pre-replicated) vs t_comm (the gathers alone), hidden fraction
    reported. The GSPMD jit path needs multi-process computations the
    CPU backend doesn't implement (unlike the shard_map pmean path the
    all-reduce case rides), so on this container the payload records a
    structured env-skip and the test skips with that reason; the TPU
    tier runs the real measurement."""
    import json
    import re

    payload = os.path.join(REPO, "tests", "dist_overlap_payload.py")
    proc = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "2", "--launcher", "local",
         sys.executable, payload, "--zero3-overlap"],
        env=_clean_env(), capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}")
    skip = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("ZERO3-OVERLAP SKIP:")]
    if skip:
        pytest.skip(f"payload env-skip: {skip[0]}")
    m = re.search(r'\{"case": "zero3-overlap".*?\}', proc.stdout)
    assert m, proc.stdout
    r = json.loads(m.group(0))
    assert r["procs"] == 2 and r["layers"] >= 2
    assert r["t_step_ms"] > 0 and r["t_comp_ms"] > 0 and \
        r["t_comm_ms"] > 0
    # the double-buffered step sits between the bounds (modulo noise)
    assert r["t_step_ms"] > 0.5 * r["t_comp_ms"], r
    assert r["t_step_ms"] < 1.5 * (r["t_comp_ms"] + r["t_comm_ms"]), r
    for rank in range(2):
        assert f"RANK {rank}/2 ZERO3-OVERLAP OK" in proc.stdout


@pytest.mark.slow
def test_launcher_runs_migrate_payload_2procs():
    """ISSUE 15: the in-ICI migrate payload on a 2-process mesh — each
    process receives ONLY its destination ranges (plan-accounted per
    device, migrated shards bit-identical to the oracle's destination
    slices, peak host bytes 0). Slow tier: the TPU driver runs it
    alongside the other dist_* payloads, where the exchange really
    crosses ICI; this container's CPU backend has no multiprocess
    collectives, matching the other launcher tests."""
    payload = os.path.join(REPO, "tests", "dist_migrate_payload.py")
    proc = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "2", "--launcher", "local",
         sys.executable, payload],
        env=_clean_env(), capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-2000:]}")
    for rank in range(2):
        assert f"RANK {rank}/2 MIGRATE OK" in proc.stdout


def test_launcher_accepts_reference_cli_shape():
    """-s servers accepted (ignored with a note), matching reference CLI."""
    proc = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "1", "-s", "1",
         sys.executable, "-c", "print('worker ran')"],
        env=_clean_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    assert "worker ran" in proc.stdout
    assert "num-servers ignored" in proc.stderr


def test_launcher_propagates_failure():
    proc = subprocess.run(
        [sys.executable, LAUNCHER, "-n", "2",
         sys.executable, "-c", "import sys; sys.exit(3)"],
        env=_clean_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 3
