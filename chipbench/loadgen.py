"""The one general traffic generator. A mix is a data file under
``chipbench/traffic/``; nothing here knows a mix by name.

Every seed sends the same sizes in the same order (and, for ``poisson``,
at the same due times), so that runs of different seeds do the same work:
the sizes are a stratified grid over the stated distribution, paired and
ordered once by the file's own ``shape_seed``; ``--seed`` draws the token
ids (and the weights). A seeded order was tried first and moved the tails
by 6-8% from seed to seed while two runs of one seed agreed within 1%.

Kinds:
- ``closed``: ``clients`` callers, each sends its next request when its
  last one ended.
- ``poisson``: an open loop; arrivals on a seeded schedule at ``rate_per_s``
  whether or not earlier requests have finished.
- ``train_stream``: a pool of ``pool_batches`` batches of token rows that
  all differ, fed one per step, round and round.
"""

from __future__ import annotations

import math
from typing import List, Tuple

import numpy as np


def with_rehearsal(spec: dict, rehearse: bool) -> dict:
    """The file's ``rehearse`` block laid over it (CPU rehearsals only)."""
    out = {k: v for k, v in spec.items() if k != "rehearse"}
    if rehearse:
        out.update(spec.get("rehearse", {}))
    return out


def grid(dist: dict, n: int) -> np.ndarray:
    """``n`` whole numbers at the mid-quantiles of ``dist``: ``uniform`` or
    ``loguniform`` between ``lo`` and ``hi`` inclusive, or ``fixed``."""
    q = (np.arange(n) + 0.5) / n
    kind = dist.get("dist", "fixed")
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if kind == "uniform":
        x = lo - 0.5 + q * (hi - lo + 1.0)
    elif kind == "loguniform":
        x = np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _rng(*words) -> np.random.Generator:
    # --seed may pass 2**31; SeedSequence takes any non-negative ints
    return np.random.default_rng([int(w) & 0xFFFFFFFF for w in words]
                                 + [int(words[0]) >> 32])


class RequestMix:
    """Requests of a serving mix: ``request(k)`` is the k-th request sent
    in the run, the same for the same seed whatever thread asks."""

    def __init__(self, spec: dict, seed: int, vocab_size: int):
        n = int(spec["pool"])
        shape = np.random.default_rng(int(spec["shape_seed"]))
        prompts = shape.permutation(grid(spec["prompt_len"], n))
        outs = shape.permutation(grid(spec["max_new_tokens"], n))
        self.pairs: List[Tuple[int, int]] = [
            (int(a), int(b)) for a, b in zip(prompts, outs)]
        self.seed, self.vocab = int(seed), int(vocab_size)

    def request(self, k: int):
        n, m = self.pairs[k % len(self.pairs)]
        ids = _rng(self.seed, 2, k).integers(0, self.vocab, n,
                                             dtype=np.int64)
        return ids.astype(np.int32), m

    def longest(self) -> int:
        return max(n + m for n, m in self.pairs)


def arrivals(spec: dict, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of a ``poisson`` mix: the gaps are the
    mid-quantiles of the exponential law at ``rate_per_s``, a fixed
    multiset of ``pool`` gaps, cycled in the ``shape_seed``'s order (the
    same under every ``--seed``, as the sizes are)."""
    rate = float(spec["rate_per_s"])
    n = int(spec["pool"])
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps *= (1.0 / rate) / gaps.mean()      # the grid's mean, made exact
    order = np.random.default_rng(
        [int(spec["shape_seed"]), 3]).permutation(n)
    reps = int(math.ceil(seconds * rate / n)) + 2
    due = np.cumsum(np.tile(gaps[order], reps))
    return due[due < seconds]


def train_pool(spec: dict, seed: int, batch: int, seq: int,
               vocab_size: int):
    """``pool_batches`` pairs ``(tokens, labels)`` of (batch, seq) int32:
    rows of seq + 1 ids uniform over the vocabulary, every row its own;
    the labels are the row shifted by one."""
    rng = _rng(seed, 4)
    out = []
    for _ in range(int(spec["pool_batches"])):
        rows = rng.integers(0, vocab_size, (batch, seq + 1), dtype=np.int64)
        out.append((rows[:, :-1].astype(np.int32),
                    rows[:, 1:].astype(np.int32)))
    return out
