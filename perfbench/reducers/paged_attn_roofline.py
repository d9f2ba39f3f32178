"""The paged-attention kernel's share of its roofline, %.

Least time: for every `span` event of the program that starts inside the
traced window, the larger of operations over the bf16 peak and bytes over
the HBM peak of one layer's call (harness/flops_paged.py, from the span's
own stats), times the layers.  Device time: the ops matching `pattern`.
The host dispatches a step ahead of the device, so the spans that start
in the window and the step programs (`module`) the device ran in it can
differ by one at either end: the least time is scaled by programs over
spans."""
from perfbench.harness import flops as F
from perfbench.harness import flops_paged as P
from perfbench.harness import spans as S
from perfbench.harness import trace as T


def reduce(ctx, span, pattern, module):
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    lo, hi = trace.window()
    steps = [s for s in S.named(S.of(ctx), [span])
             if lo <= s.start < hi and "attn_pairs" in s.stats]
    secs, calls = T.op_calls(trace, pattern)
    programs = len(T.module_durations(trace, module)) / len(trace.devices)
    if not steps or not calls or not programs:
        return None
    cfg, peaks = ctx["job"].config, ctx["peaks"]()
    heads, item = cfg["n_head"], P.kv_item_bytes(cfg)
    least = 0.0
    for s in steps:
        st = s.stats
        flops, nbytes = P.paged_attention(
            st["attn_pairs"], st["ctx_tokens"],
            st["decode_rows"] + st["prefill_rows"], heads,
            cfg["n_embd"] // heads, item)
        least += F.roofline_seconds(flops, nbytes, peaks)[0]
    return 100.0 * least * cfg["n_layer"] * (programs / len(steps)) / secs
