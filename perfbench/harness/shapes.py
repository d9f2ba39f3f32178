"""The replayed request set: a fixed multiset of (prompt length, output
length) per traffic file, independent of --seed.

Lengths are the quantiles (i + 0.5) / n of the distribution the traffic
file states, so every run of every PR serves the same shapes; only the
order, the token contents and the arrival jitter follow --seed.  The
pairing of prompt and output lengths is shuffled by the file's own
`shape_seed`.  Standard library only: the load generator's process
imports this file's neighbours and must never import jax or numpy.
"""
from __future__ import annotations

import math
import random
from statistics import NormalDist

_STD_NORMAL = NormalDist()


def quantile(dist, u):
    """Inverse CDF of a length distribution at u in (0, 1), clipped and
    rounded to a whole number of tokens.  `dist` is
    {"kind": "lognormal", "median", "sigma", "min", "max"} or
    {"kind": "uniform", "min", "max"}."""
    kind = dist["kind"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _STD_NORMAL.inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(dist["max"], max(dist["min"], round(x))))


def replayed_set(prompt_dist, output_dist, n, shape_seed):
    """n (prompt_len, output_len) pairs: each side the n quantiles of its
    distribution, outputs paired with prompts in an order shuffled by
    shape_seed.  The same call gives the same list, always."""
    us = [(i + 0.5) / n for i in range(n)]
    prompts = [quantile(prompt_dist, u) for u in us]
    outputs = [quantile(output_dist, u) for u in us]
    random.Random(shape_seed).shuffle(outputs)
    return list(zip(prompts, outputs))


def seeded_order(pairs, seed, stream):
    """The same multiset in an order decided by (--seed, stream)."""
    pairs = list(pairs)
    random.Random(f"{int(seed)}/{stream}").shuffle(pairs)
    return pairs


def paced_arrivals(n, rate, jitter, seed, stream, start=0.0):
    """Constant-arrival-rate open loop: request k is due at
    start + (k + 0.5 + j_k) / rate with j_k uniform in +-jitter, so all n
    fall strictly inside [start, start + n / rate]."""
    rng = random.Random(f"{int(seed)}/{stream}/arrivals")
    return [start + (k + 0.5 + rng.uniform(-jitter, jitter)) / rate
            for k in range(n)]
