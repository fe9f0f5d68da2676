"""Median host time of the jitted step call inside ``trainer.step``:
phase ``dispatch`` of the window's ``spmd.step`` ledger records (the
rest of the call is its ``h2d``, ``rng`` and ``meter`` phases)."""
from chipbench import ledger, stats


def read(record):
    return ledger.ms(stats.median(
        ledger.phase_sums(record, "step", "dispatch")))
