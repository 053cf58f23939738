"""The one traffic generator: a mix file of parameters in, a request plan out.

Requests arrive in an open loop: each is sent when its schedule says it is
due, whether or not earlier ones have finished.  The plan is cut into blocks
of ``block`` consecutive requests, and every block holds the same multiset of
prompt lengths, answer lengths and inter-arrival gaps, drawn at fixed
quantiles of the mix's distributions; the seed only orders each block and
draws the token ids.  So every seed offers the same work, and any stretch of
a few blocks holds nearly the same work, in another order: the spread between
seeds is the system's, not the generator's.

Mix file keys (``traffic/<name>.json``):

``arrival``   ``"poisson"``: exponential gaps at the cell's ``rate_per_s``
``prompt``, ``answer``  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
              or ``{"dist": "uniform", "min", "max"}`` (token counts)
``block``     requests per block (the number of quantiles drawn)
``warm_in_s`` seconds of traffic before the measured window opens
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Plan:
    due: np.ndarray              # seconds from the traffic origin
    prompts: List[np.ndarray]    # int32 token ids
    answers: np.ndarray          # max_new_tokens per request

    def __len__(self) -> int:
        return len(self.prompts)


def seed_words(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named use of the seed (any size of int)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of the distribution, clipped."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps at the mid-quantiles, scaled so
    that their mean is exactly ``1 / rate`` (a Poisson process's gaps, as a
    fixed multiset)."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / (g.mean() * rate)


def n_open_loop(rate: float, seconds: float, warm_in_s: float, block: int) -> int:
    """Requests a plan holds: the whole warm-in and window, with room for a
    window that closes late, in whole blocks."""
    n = int(math.ceil(rate * (warm_in_s + seconds) * 1.25)) + 16
    return block * -(-n // block)


def make_plan(mix: Dict[str, Any], seed: int, seconds: float, vocab: int,
              rate: float) -> Plan:
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    if rate <= 0:
        raise ValueError("an open-loop mix needs the cell's rate_per_s > 0")
    k = int(mix["block"])
    n = n_open_loop(rate, seconds, float(mix["warm_in_s"]), k)
    order = seed_words(seed, 1)

    def blocks(base: np.ndarray) -> np.ndarray:
        return np.concatenate([order.permutation(base) for _ in range(n // k)])

    p_len = blocks(quantile_lengths(mix["prompt"], k))
    a_len = blocks(quantile_lengths(mix["answer"], k))
    due = np.cumsum(blocks(exp_gaps(rate, k)))
    ids = seed_words(seed, 2).integers(0, vocab, size=int(p_len.sum()), dtype=np.int32)
    prompts = np.split(ids, np.cumsum(p_len)[:-1])
    return Plan(due, prompts, a_len)
