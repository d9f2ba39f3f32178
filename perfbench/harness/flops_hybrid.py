"""Operations and bytes, from shapes, of the sequence mixers of hybrid
linear-attention models — the benchmark's own count of what the ALGORITHM
needs, beside harness/flops.py and under its rules: padding, recomputation
and whatever else an implementation chooses are not counted, so a share of
a peak cannot pass 100%."""
from __future__ import annotations


def attn_fwd(batch, seq, heads, dk, dv, dtype_bytes=2, kv_heads=None):
    """Causal attention whose q/k head size differs from its v head size:
    QK^T at 2*S*S*dk and PV at 2*S*S*dv per query head, halved by the
    mask.  Bytes: q (dk) read and o (dv) written at the query heads'
    count, k (dk) and v (dv) read at the KV heads' (the query heads' where
    none is given: every head has its own), once."""
    kv_heads = heads if kv_heads is None else kv_heads
    flops = 2 * seq * seq * (dk + dv) * batch * heads * 0.5
    nbytes = (dk + dv) * batch * (heads + kv_heads) * seq * dtype_bytes
    return flops, nbytes


def attn_bwd(batch, seq, heads, dk, dv, dtype_bytes=2, kv_heads=None):
    """Backward: S again, dQ and dK at the q/k head size, dP and dV at
    the v head size — 2.5 x the forward by the same rule as
    flops.flash_bwd.  Bytes: q, o, dO read and dq written at the query
    heads' count; k, v read and dk, dv written at the KV heads'."""
    kv_heads = heads if kv_heads is None else kv_heads
    flops = 2 * seq * seq * (3 * dk + 2 * dv) * batch * heads * 0.5
    nbytes = (2 * dk + 2 * dv) * batch * (heads + kv_heads) * seq \
        * dtype_bytes
    return flops, nbytes


def delta_rule_fwd(batch, seq, heads, dk, dv, dtype_bytes=2):
    """The gated delta rule by its recurrence, per token and head:
    S'^T k, k u^T and S^T q at 2*dk*dv each, the decay's dk*dv.  Bytes:
    q, k, the log decay (float32) and v read, beta, o written; the state
    stays on the chip."""
    flops = 7 * dk * dv * batch * heads * seq
    nbytes = batch * heads * seq * (
        (2 * dk + 2 * dv) * dtype_bytes + 4 * dk + dtype_bytes)
    return flops, nbytes


def delta_rule_train(batch, seq, heads, dk, dv, dtype_bytes=2):
    """One forward and one backward of the delta rule: the backward is
    twice the forward's operations and reads and writes what the forward
    moved once more each way.  A forward repeated for rematerialisation
    is the program's choice and is not counted."""
    flops, nbytes = delta_rule_fwd(batch, seq, heads, dk, dv, dtype_bytes)
    return 3 * flops, 3 * nbytes
