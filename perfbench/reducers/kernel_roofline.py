"""A kernel's share of its roofline, %: the least time the chip could take
for the calls seen (max of operations over peak FLOP/s and bytes over peak
bytes/s, from the shape function named in harness/flops.py) over the
device time of those calls.  One call is one layer's attention over the
sequences one chip holds."""
from perfbench.harness import flops as F
from perfbench.harness import trace as T


def reduce(ctx, pattern, shape_fn, calls_per_layer=1):
    if ctx["trace"] is None:
        return None
    cfg, values = ctx["job"].config, ctx["values"]
    secs, calls = T.op_calls(ctx["trace"], pattern)
    if not calls or "sequences_per_chip" not in values:
        return None
    layer_calls = calls / calls_per_layer
    flops, nbytes = getattr(F, shape_fn)(
        values["sequences_per_chip"], values["heads_per_chip"],
        values["seq_len"], cfg["n_embd"] // cfg["n_head"])
    least, _ = F.roofline_seconds(flops, nbytes, ctx["peaks"]())
    return 100.0 * least * layer_calls / secs
