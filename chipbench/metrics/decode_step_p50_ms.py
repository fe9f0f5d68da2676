"""Median host time of one decode step, dispatch to the D2H fence: the
``dur_s`` of the window's ``step`` records in the program's turn ledger
(the interval the session sums into ``decode_seconds``)."""
from chipbench import ledger, stats


def read(record):
    return ledger.ms(stats.median(ledger.durations(record, "step")))
