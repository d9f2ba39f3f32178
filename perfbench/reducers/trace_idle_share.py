"""1 minus the union of the device's op intervals over the traced window,
averaged over the chips, %."""
from perfbench.harness import trace as T


def reduce(ctx):
    if ctx["trace"] is None or not ctx["trace"].devices:
        return None
    share = T.idle_share(ctx["trace"])
    return None if share is None else 100.0 * share
