"""95th percentile, over all requests submitted in the window, of the
client-side time from submit (or, in an open loop, from when the request
was due) to the first token."""
from chipbench.stats import percentile


def read(record):
    p = percentile(record["window"]["ttft_s"], 95)
    return None if p is None else p * 1e3
