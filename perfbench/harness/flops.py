"""Operations and bytes from shapes — the benchmark's own count, never
XLA's cost_analysis().  What the ALGORITHM needs: recomputation a kernel
chooses to do is not counted, so a share of peak cannot pass 100%."""
from __future__ import annotations


def flash_fwd(batch, heads, seq, head_dim, causal=True, dtype_bytes=2):
    """Causal attention forward: S = QK^T and O = PV, two matmuls of
    2*S*S*D each per head, halved by the causal mask.  Bytes: q, k, v read
    and o written once."""
    share = 0.5 if causal else 1.0
    flops = 2 * 2 * seq * seq * head_dim * batch * heads * share
    nbytes = 4 * batch * heads * seq * head_dim * dtype_bytes
    return flops, nbytes


def flash_bwd(batch, heads, seq, head_dim, causal=True, dtype_bytes=2):
    """Backward needs five matmuls (S again, dP, dV, dK, dQ): 2.5 x the
    forward.  A kernel pair that forms S and dP twice does seven; the two
    extra are its choice and lower its share.  Bytes: q, k, v, o, dO read,
    dq, dk, dv written."""
    share = 0.5 if causal else 1.0
    flops = 5 * 2 * seq * seq * head_dim * batch * heads * share
    nbytes = 8 * batch * heads * seq * head_dim * dtype_bytes
    return flops, nbytes


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peaks["bf16_flops_per_s"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), ("flops" if t_f >= t_b else "bytes")


def train_flops_per_token(n_params, n_position_params, layers, hidden, seq,
                          causal=True):
    """6 x (parameters that multiply: all but the position table; the tied
    token table counts once, as the head) plus causal attention's
    3 x (2 matmuls x 2*S*hidden) per layer, halved by the mask."""
    share = 0.5 if causal else 1.0
    return 6 * (n_params - n_position_params) \
        + 3 * 2 * 2 * seq * hidden * layers * share
