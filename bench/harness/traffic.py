"""The one generator of open-loop request schedules.

A mix is a data file (``bench/traffic/<mix>.json``):

    {"kind": "open_loop", "rate_rps": 4.0, "order_seed": 0,
     "prompt_len": {"median": 256, "sigma": 1.0, "min": 16, "max": 1536},
     "output_len": {"median": 64, "sigma": 1.0, "min": 8, "max": 384}}

Lengths are lognormal and arrivals Poisson, both drawn by stratified
quantiles: for n requests the i-th value sits at quantile (i + 0.5) / n,
in an order drawn from the mix's ``order_seed``. So every run of a mix
serves the same requests, of the same sizes, at the same times; the
run's seed draws their token ids (uniform over the vocabulary), as it
draws the weights. With a few tens of requests in a window the order
alone sets the tail (which long prompts queue behind which), so it
belongs to the mix, not to the run.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Schedule:
    arrival_s: np.ndarray     # (n,) seconds from the window's start, sorted
    prompts: list             # n int32 arrays
    max_new: np.ndarray       # (n,) output tokens asked for

    def __len__(self) -> int:
        return len(self.arrival_s)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) / rate


def open_loop(mix: dict, seconds: float, seed: int, vocab: int) -> Schedule:
    """The requests due in a window of ``seconds``."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"not an open-loop mix: {mix['kind']!r}")
    n = int(math.floor(mix["rate_rps"] * seconds))
    order = np.random.default_rng([mix["order_seed"], 0x0D3E])
    gaps = order.permutation(poisson_gaps(mix["rate_rps"], n))
    plen = order.permutation(lognormal_lengths(mix["prompt_len"], n))
    olen = order.permutation(lognormal_lengths(mix["output_len"], n))
    ids = np.random.default_rng([seed, 0x5EED])
    prompts = [ids.integers(0, vocab, size=int(p), dtype=np.int32) for p in plen]
    return Schedule(np.cumsum(gaps), prompts, olen)
