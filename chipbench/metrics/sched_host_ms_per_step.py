"""Mean host time per decode step that the scheduler thread spends on
its own bookkeeping: phases ``sched`` (between two records: admissions,
swap check, the wait loop's turns) + ``deliver`` (tokens handed to the
callers) + ``finish`` (slots retired) of the window's ``step`` records.
``idle``, the time it waits with nothing to run, is left out."""
from chipbench import ledger


def read(record):
    return ledger.ms(ledger.mean(
        ledger.phase_sums(record, "step", "sched", "deliver", "finish")))
