"""95th percentile of submit -> admission to a slot, as the session's
``observe_admit`` recorded it for the window's prefills."""
from chipbench.stats import percentile


def read(record):
    p = percentile(record.get("queue_waits_s") or [], 95)
    return None if p is None else p * 1e3
