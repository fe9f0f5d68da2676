"""Mean host time of one decode step, dispatch to the D2H fence: the
session's cumulative ``decode_seconds`` over its ``steps``."""
from chipbench.reduce import counter_delta


def read(record):
    steps = counter_delta(record, "steps")
    if not steps:
        return None
    return 1e3 * counter_delta(record, "decode_seconds") / steps
