"""Percentile, spread and failed-request arithmetic."""
from __future__ import annotations

import math
import statistics

# A failed request has no latency: it sorts beyond every percentile.
FAILED = math.inf


def percentile(values, q):
    """Nearest-rank percentile of `values` (q in (0, 100]); the smallest
    value with at least q% of the samples at or below it.  No
    interpolation: a tail is one of the observed requests.  Returns None
    for no samples, and FAILED where the rank lands on a failed one."""
    ordered = sorted(values)
    if not ordered:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def tail_with_failures(latencies, n_failed, q, worst):
    """The q-th percentile over `latencies` plus `n_failed` requests that
    count as beyond every percentile.  Where the rank lands on a failed
    request the tail is `worst` (the request limit), so the metric stays
    a number and a failure can never read as a short latency."""
    value = percentile(list(latencies) + [FAILED] * int(n_failed), q)
    if value is None:
        return None
    return worst if value == FAILED else value


def samples_beyond(n, q):
    """How many of n samples lie beyond the q-th percentile's rank."""
    return n - max(1, math.ceil(q / 100.0 * n)) if n else 0


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, by statistics.quantiles(n=4) — the driver's definition."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
