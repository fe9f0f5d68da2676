"""Median host time of one ``trainer.step`` call: the enqueue."""
from chipbench.stats import median


def read(record):
    m = median(record.get("host_step_s") or [])
    return None if m is None else m * 1e3
