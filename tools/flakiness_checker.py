#!/usr/bin/env python
"""Flakiness checker (reference ``tools/flakiness_checker.py``): re-run a
named test N times, each with a different random seed, and report the
pass/fail tally. Seeds are injected through ``MXNET_TEST_SEED`` — the same
env knob the test fixtures honor (SURVEY.md §4 "seed discipline").

This parent never imports jax: each trial is a fresh pytest process, run
one after another, so even under ``MXTPU_TEST_PLATFORM=tpu`` only one
process at a time asks for the chip.

Usage:
    python tools/flakiness_checker.py tests/test_operator.py::test_dropout
    python tools/flakiness_checker.py -n 50 --seed-start 1000 \
        tests/test_gluon.py::test_batchnorm
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def run_trials(test_id: str, trials: int, seed_start: int,
               verbose: bool = False) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    failures = []
    for i in range(trials):
        seed = seed_start + i
        env = dict(os.environ)
        env["MXNET_TEST_SEED"] = str(seed)
        env["MXTPU_TEST_SEED"] = str(seed)
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", test_id, "-q", "-x",
             "--no-header", "-p", "no:cacheprovider"],
            cwd=repo, env=env, capture_output=True, text=True)
        ok = proc.returncode == 0
        print(f"trial {i + 1}/{trials} seed={seed}: "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            failures.append(seed)
            if verbose:
                print(proc.stdout[-3000:])
    print(f"\n{trials - len(failures)}/{trials} passed"
          + (f"; failing seeds: {failures} "
             f"(repro: MXNET_TEST_SEED={failures[0]} pytest {test_id})"
             if failures else " — no flakiness detected"))
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("test", help="pytest node id, e.g. "
                                 "tests/test_operator.py::test_dropout")
    ap.add_argument("-n", "--trials", type=int, default=10)
    ap.add_argument("--seed-start", type=int, default=0)
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print failing trial output")
    args = ap.parse_args()
    sys.exit(run_trials(args.test, args.trials, args.seed_start,
                        args.verbose))


if __name__ == "__main__":
    main()
