"""Mean of a sampled gauge over the window; `times` names a second gauge
each sample is multiplied by (occupancy x bucket = lanes in use)."""


def reduce(ctx, gauge, times=None):
    samples = ctx["obs"].get("samples") or []
    vals = [v[gauge] * (v.get(times, 0) if times else 1)
            for _, v in samples if gauge in v]
    return sum(vals) / len(vals) if vals else None
