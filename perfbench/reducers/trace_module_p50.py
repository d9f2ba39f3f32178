"""Median device duration (ms) of the executed programs whose name matches
`pattern`, from the XLA-modules line."""
import statistics

from perfbench.harness import trace as T


def reduce(ctx, pattern):
    if ctx["trace"] is None:
        return None
    durations = T.module_durations(ctx["trace"], pattern)
    return statistics.median(durations) * 1e3 if durations else None
