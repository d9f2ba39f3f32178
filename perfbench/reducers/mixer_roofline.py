"""A sequence mixer's share of its roofline, %, for architectures whose
reference states `mixer_shapes(config)` (heads, q/k and v head sizes, per
kind of mixer): the least time the chip could take for the calls seen
(harness/flops_hybrid.py: max of operations over peak FLOP/s and bytes
over peak bytes/s) over the device time of the operations whose label
matches `pattern`.  `calls_per_unit` matched operations make one unit of
`shape_fn` (a forward kernel: 1; a backward made of two kernels: 2; an op
that shows as a forward loop, the forward loop again under
rematerialisation and a backward loop, counted as one forward and one
backward: 3).  A mixer that states `kv_heads` (fewer KV heads than query
heads) has its K and V counted at that many.  A reference without
`mixer_shapes` (another architecture, an older commit) reads nothing."""
from perfbench.harness import flops as F
from perfbench.harness import flops_hybrid as H
from perfbench.harness import trace as T


def reduce(ctx, pattern, shape_fn, mixer, calls_per_unit=1):
    if ctx["trace"] is None:
        return None
    job, values = ctx["job"], ctx["values"]
    shapes = getattr(job.manifest.reference(job.config), "mixer_shapes", None)
    shape = shapes(job.config).get(mixer) if shapes else None
    secs, calls = T.op_calls(ctx["trace"], pattern)
    if not shape or not calls or "sequences_per_chip" not in values:
        return None
    grouped = {"kv_heads": shape["kv_heads"]} if "kv_heads" in shape else {}
    flops, nbytes = getattr(H, shape_fn)(
        values["sequences_per_chip"], values["seq_len"], shape["heads"],
        shape["dk"], shape["dv"], **grouped)
    least, _ = F.roofline_seconds(flops, nbytes, ctx["peaks"]())
    return 100.0 * least * (calls / calls_per_unit) / secs
