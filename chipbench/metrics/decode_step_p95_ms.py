"""95th percentile of the host time of one decode step: ``dur_s`` of the
window's ``step`` records in the program's turn ledger."""
from chipbench import ledger, stats


def read(record):
    return ledger.ms(stats.percentile(ledger.durations(record, "step"), 95))
