"""Mean host time of one prefill + join, dispatch to the first token
host-visible: cumulative ``prefill_seconds`` over ``prefills``."""
from chipbench.reduce import counter_delta


def read(record):
    n = counter_delta(record, "prefills")
    if not n:
        return None
    return 1e3 * counter_delta(record, "prefill_seconds") / n
