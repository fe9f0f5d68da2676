"""Median host time of one prefill + join, dispatch to the first token
host-visible: ``dur_s`` of the window's ``prefill`` records in the
program's turn ledger (the interval summed into ``prefill_seconds``)."""
from chipbench import ledger, stats


def read(record):
    return ledger.ms(stats.median(ledger.durations(record, "prefill")))
