"""A number from a counter the program keeps on the device and hands to
its registry by reference (framework.monitor.stat_registry.hold; read
here once, after the window, so no step pays a sync): rows are the layers
that count, columns what they count, the last column the overflow bucket.

  max_over_mean   the busiest of the counted columns over their mean, per
                  row, averaged over the rows
  counted_share   the counted columns' share of everything, %

A program without the registry's `held`, or that holds no such counter,
reads nothing."""


def reduce(ctx, stat, form):
    try:
        from paddle_tpu.framework.monitor import stat_registry
    except ImportError:
        return None
    held = getattr(stat_registry, "held", None)
    counts = held(stat) if held else None
    if counts is None or counts.ndim != 2 or counts.shape[1] < 2 \
            or not counts.sum():
        return None
    counted = counts[:, :-1].astype(float)
    if form == "counted_share":
        return 100.0 * counted.sum() / counts.sum()
    if form == "max_over_mean":
        means = counted.mean(axis=1)
        rows = means > 0
        if not rows.any():
            return None
        return float((counted.max(axis=1)[rows] / means[rows]).mean())
    raise ValueError(f"unknown form {form!r}")
