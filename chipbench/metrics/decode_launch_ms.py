"""Mean host time of a decode step before its executable is enqueued:
phases ``h2d`` (the two small host-to-device copies) + ``dispatch`` (the
executable call returning) of the window's ``step`` records."""
from chipbench import ledger


def read(record):
    return ledger.ms(ledger.mean(
        ledger.phase_sums(record, "step", "h2d", "dispatch")))
