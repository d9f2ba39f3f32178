"""Growth of a sampled counter over the window (added by the preset to
show that a reducer is one new file)."""


def reduce(ctx, counter):
    samples = ctx["obs"].get("samples") or []
    if len(samples) < 2:
        return None
    return samples[-1][1].get(counter, 0) - samples[0][1].get(counter, 0)
