"""Device idle time laid against the program's own spans, % of the traced
window.

The idle intervals are trace_idle_share's (same ops, same window, mean
over the chips), intersected interval by interval with the spans named:

  inside   idle time under any of these spans ...
  but_not  ... that is under none of these (an engine step's time outside
           its admission and consume phases)
  outside  idle time under no span of this name (the caller's own time)

So the metrics of one cell that split the window between them (inside a,
inside b, inside step but_not a and b, outside step) add up to the idle
share.  A program that opens no spans reads nothing."""
from perfbench.harness import spans as S
from perfbench.harness import trace as T


def reduce(ctx, inside=(), but_not=(), outside=None):
    trace, spans = ctx["trace"], S.of(ctx)
    if trace is None or not trace.devices or not spans:
        return None
    lo, hi = trace.window()
    if hi <= lo:
        return None
    if outside is not None:
        where = T.gaps(S.union(S.named(spans, [outside]), lo, hi), lo, hi)
    else:
        where = S.overlap(
            S.union(S.named(spans, inside), lo, hi),
            T.gaps(S.union(S.named(spans, but_not), lo, hi), lo, hi))
    idle = [S.seconds(S.overlap(T.gaps(T.merge(
        [(s, s + d) for _, s, d in dev["ops"]], lo, hi), lo, hi), where))
        for dev in trace.devices.values()]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
