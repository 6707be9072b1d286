"""The one general traffic generator: a mix is a data file of parameters.

Every seed gets the same set of sizes, in another order, and its own token
ids: lengths are the evenly spaced quantiles of the mix's distribution, so a
seed changes which request comes when, never how much work there is.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int):
    """Independent generator for one purpose (`stream`) of one seed."""
    return np.random.default_rng([int(seed), int(stream)])


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the mid-point quantiles of the mix's log-normal
    distribution, clipped to its `min` and `max`."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def token_batches(mix: dict, vocab_size: int, seed: int) -> np.ndarray:
    """[distinct_dispatches, steps_per_dispatch, batch, seq_len] int32 token
    ids, every row different."""
    shape = (mix["distinct_dispatches"], mix["steps_per_dispatch"],
             mix["batch"], mix["seq_len"])
    return rng_for(seed, 1).integers(0, vocab_size, shape, dtype=np.int32)


def requests(mix: dict, vocab_size: int, seed: int) -> list:
    """Four rounds of the pool in this seed's order: dicts of `prompt`
    (int32 ids) and `max_new`. The pool holds `lengths_pool` prompt and
    output lengths, the quantiles of the two distributions, paired by two
    permutations drawn from the seed, so which long prompts meet is the
    seed's draw, as it is for real clients; later rounds send the same sizes
    again with fresh ids, so that every seed sends the same work and no
    prompt is ever sent twice."""
    n = mix["lengths_pool"]
    rng = rng_for(seed, 2)
    plens = quantile_lengths(mix["prompt_len"], n)[rng.permutation(n)]
    olens = quantile_lengths(mix["output_len"], n)[rng.permutation(n)]
    return [{"prompt": rng.integers(1, vocab_size, int(plens[i % n]),
                                    dtype=np.int32),
             "max_new": int(olens[i % n])} for i in range(4 * n)]


def open_loop_schedule(arrival: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop with
    Poisson arrivals at `rate_per_s`. The client reports how late each
    request really left; unused by closed-loop mixes."""
    if arrival["kind"] != "poisson":
        raise ValueError(f"unknown open-loop arrival {arrival['kind']!r}")
    rate = float(arrival["rate_per_s"])
    n = int(math.ceil(rate * seconds * 1.5)) + 16
    due = np.cumsum(rng_for(seed, 3).exponential(1.0 / rate, n))
    return due[due < seconds]
