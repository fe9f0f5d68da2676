"""Helpers of the chipbench tests: a rehearsal run in this process."""

import json

import pytest


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """The program's telemetry is global to the process: a rehearsal's
    sessions must not leave recompile events for whatever test the worker
    runs next."""
    from incubator_mxnet_tpu import telemetry

    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture
def rehearse(capsys):
    """``rehearse(workload, *extra)`` runs ``chipbench.run --rehearse`` in
    this process and returns ``(exit code, last stdout line as a dict)``."""
    from chipbench import run

    def go(workload, *extra, seed=7, seconds=1.5, trace=0):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--rehearse", *extra])
        lines = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(lines[-1])

    return go
