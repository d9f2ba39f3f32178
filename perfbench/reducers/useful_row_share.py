"""Rows that carried a token over rows the step programs computed, %.

Useful: the window's growth of the `useful` counters.  Computed: for each
sampling interval, the steps taken in it times the lane bucket times the
row bucket in force (gauges, sampled every 50 ms against steps of 100 ms
and more)."""


def reduce(ctx, useful, steps, lane_bucket, row_bucket):
    samples = ctx["obs"].get("samples") or []
    if len(samples) < 2:
        return None
    first, last = samples[0][1], samples[-1][1]
    done = sum(last.get(k, 0) - first.get(k, 0) for k in useful)
    computed = 0
    for (_, a), (_, b) in zip(samples, samples[1:]):
        computed += (b.get(steps, 0) - a.get(steps, 0)) \
            * b.get(lane_bucket, 0) * b.get(row_bucket, 0)
    return 100.0 * done / computed if computed else None
