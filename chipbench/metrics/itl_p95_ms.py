"""95th percentile over all gaps between successive tokens of all requests
submitted in the window, stamped on the client side."""
from chipbench.stats import percentile


def read(record):
    p = percentile(record["window"]["gaps_s"], 95)
    return None if p is None else p * 1e3
