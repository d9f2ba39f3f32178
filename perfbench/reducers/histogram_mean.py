"""Mean of a program histogram's observations inside the window."""


def reduce(ctx, histogram):
    count, total = (ctx["obs"].get("histograms") or {}).get(histogram,
                                                           (0, 0.0))
    return total / count if count else None
