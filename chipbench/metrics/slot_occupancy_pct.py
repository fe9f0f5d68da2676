"""Mean active slots per decode step over the slots there are, from the
session's cumulative counters (tokens less prefill tokens, over steps)."""
from chipbench.reduce import counter_delta


def read(record):
    steps = counter_delta(record, "steps")
    if not steps:
        return None
    tokens = counter_delta(record, "tokens") \
        - counter_delta(record, "prefills")
    return 100.0 * tokens / steps / record["max_slots"]
