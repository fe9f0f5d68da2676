"""Percentiles and window arithmetic on client-side stamps."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional

import numpy as np


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Nearest-rank percentile of all the values: the smallest value with
    at least p% of the sample at or below it. None on an empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(1, math.ceil(p / 100.0 * len(vals)))
    return vals[k - 1]


def median(values: Iterable[float]) -> Optional[float]:
    vals = sorted(values)
    if not vals:
        return None
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])


def in_window(t: float, t0: float, t1: float) -> bool:
    return t0 <= t < t1


def serve_window(requests: List[dict], t0: float, t1: float) -> dict:
    """The serving cell's end-to-end numbers from client-side stamps.

    ``requests``: ``{"submit": s, "stamps": [t of each token], "failed":
    bool, "prompt_len": n}``. Tokens count where they are delivered inside
    [t0, t1); TTFT and gaps belong to requests SUBMITTED inside it, whole,
    wherever their later tokens fell (they are drained after the close)."""
    tokens = sum(1 for r in requests for t in r["stamps"]
                 if in_window(t, t0, t1))
    mine = [r for r in requests if in_window(r["submit"], t0, t1)]
    ok = [r for r in mine if not r["failed"] and r["stamps"]]
    ttft = [r["stamps"][0] - r["submit"] for r in ok]
    gaps = [b - a for r in ok for a, b in zip(r["stamps"], r["stamps"][1:])]
    return {"window_s": t1 - t0, "tokens_in_window": tokens,
            "attempted": len(mine), "failed": len(mine) - len(ok),
            "ttft_s": ttft, "gaps_s": gaps}


# scales tried by ``noise_scale``: 1e-4 .. 1, half a percent apart
_SCALES = np.exp(np.arange(math.log(1e-4), 0.0, 0.005))


def noise_scale(margins, flipped) -> float:
    """The noise on a logit difference that best explains which tokens
    fell off the reference's best. ``margins[i]`` is the reference's best
    logit less its second best at position i, ``flipped[i]`` whether the
    token produced there was not the reference's best. The model: the best
    loses with probability Phi(-margin / s); the s of greatest likelihood
    is returned (1e-4, the least tried, where nothing flipped). Unlike a
    mean or a widest gap it reads every near-tie, flipped or not, so it
    does not swing with how many near-ties a seed's prompts hold."""
    from scipy.special import log_ndtr

    m = np.asarray(margins, np.float64)
    f = np.asarray(flipped, bool)
    ll = np.zeros(_SCALES.size)
    for a in range(0, m.size, 4096):        # bounded memory
        z = m[None, a:a + 4096] / _SCALES[:, None]
        ll += np.where(f[None, a:a + 4096], log_ndtr(-z), log_ndtr(z)).sum(1)
    return float(_SCALES[int(np.argmax(ll))])
