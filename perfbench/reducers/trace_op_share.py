"""Device time of the ops whose label matches `pattern` over the device's
busy time in the traced window, %."""
import re

from perfbench.harness import trace as T


def reduce(ctx, pattern):
    if ctx["trace"] is None or not ctx["trace"].devices:
        return None
    busy, _ = T.busy_seconds(ctx["trace"])
    rx = re.compile(pattern)
    secs = sum(v for k, v in T.op_seconds(ctx["trace"]).items()
               if rx.search(k))
    return 100.0 * secs / busy if busy and secs else None
