"""The one traffic generator: every mix file is read here.

A run's work is fixed by the mix and the cell, not by the seed: lengths
are the quantiles of the mix's distribution (the same set for every
seed) and Poisson gaps the quantiles of the exponential.  The seed only
orders them and draws the token ids, so runs with different seeds do the
same amount of work and differ in which request comes when.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    uid: int
    due: float              # seconds after the window opens (open loop)
    prompt: np.ndarray      # int32 token ids
    max_new: int


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 1/2)/n of the spec's
    distribution, clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(x) for x in u])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def requests(mix: dict, n: int, seed: int, vocab: int,
             first_uid: int = 0) -> List[Request]:
    """``n`` requests: the mix's fixed sets of prompt and output lengths,
    each shuffled by the seed, with token ids drawn from the seed."""
    rng = np.random.default_rng(seed)
    plens = rng.permutation(quantile_lengths(mix["prompt"], n))
    olens = rng.permutation(quantile_lengths(mix["output"], n))
    return [Request(first_uid + i, 0.0,
                    rng.integers(0, vocab, size=int(p), dtype=np.int32),
                    int(o))
            for i, (p, o) in enumerate(zip(plens, olens))]


def open_loop(mix: dict, rate: float, seconds: float, seed: int,
              vocab: int, first_uid: int = 0) -> List[Request]:
    """Requests due over a window of ``seconds`` at ``rate`` per second.

    Poisson arrivals: the ``round(rate * seconds)`` gaps are the
    exponential's mid-quantiles in an order drawn from the seed, scaled
    so that the arrivals span the window."""
    if mix.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = max(1, round(rate * seconds))
    reqs = requests(mix, n, seed, vocab, first_uid)
    u = (np.arange(n) + 0.5) / n
    gaps = np.random.default_rng([seed, 1]).permutation(-np.log1p(-u))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due *= seconds / float(np.sum(gaps))
    for r, d in zip(reqs, due):
        r.due = float(d)
    return reqs


def calibration(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """The prune job's calibration tokens, (samples, length) int32."""
    c = mix["calibration"]
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(c["samples"], c["length"]),
                        dtype=np.int32)

