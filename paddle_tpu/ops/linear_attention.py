"""Gated delta-rule linear attention with a per-channel decay (the KDA
mixer of the Kimi-Linear family; Yang et al. 2024 "Gated Delta Networks",
Kimi Team 2025 "Kimi Linear").

Per head, with a matrix state S in R^{Dk x Dv}, S_0 = 0:

    S' = diag(exp(g_t)) S_{t-1}
    u  = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t u^T
    o_t = S_t^T q_t

`gated_delta_rule_recurrent` is that, token by token (`lax.scan` over T):
the exact twin the chunked form is tested against.  `gated_delta_rule` is
the program's path: chunks of CHUNK tokens, the state carried from chunk
to chunk, the work inside a chunk as matrix products, forward and backward
both linear in T and neither ever stepping over single tokens.

Inside a chunk, with G_t the running sum of g from the chunk's start
(inclusive) and A_ij = sum_c k_ic k_jc exp(G_ic - G_jc) for j < i:

    (I + diag(beta) A) U = diag(beta) (V - (K * exp(G)) S_0)
    O   = (Q * exp(G)) S_0 + B U,   B_ij = sum_c q_ic k_jc exp(G_ic - G_jc), j <= i
    S_C = diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

The decay is per channel, so exp(G_i - G_j) does not factor into
(k_i exp(G_i)) . (k_j exp(-G_j)) without overflow: at 0.5 a token exp(-G)
passes float32 inside one chunk.  Every exponent formed here is <= 0: a
chunk is cut into sub-blocks of SUB tokens; a pair of different sub-blocks
factors around the later block's first position (both factors decay away
from it), and inside one sub-block the difference is formed directly.

Two executions of that one algorithm, told apart by what the code can see
(`gated_delta_rule`, as ops/attention.py routes flash; `ROUTE_STATS` counts
them where the op is traced).  On a TPU with head sizes of one lane tile
(128), in a step not traced for a mesh, it is two Pallas kernels, forward
and backward, under a `jax.custom_vjp` (pallas_ops/delta_rule.py): a
device trace shows the op as Mosaic `custom-call`s — a layer's forward,
its forward again under recomputation, its backward — and no loop.
Anywhere else it is `gated_delta_rule_chunked`, the kernels' XLA twin:
everything inside a `lax.scan` over groups of chunks whose body is
rematerialised, so autodiff keeps one state per group, not every chunk's
products, and a trace shows `while` operations and what runs under them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._helpers import to_tensor_like
from .dispatch import apply

CHUNK = 64          # tokens whose interactions are one set of matrix products
SUB = 16            # sub-block inside which exp(G_i - G_j) is formed directly
GROUP = 4           # chunks a scan step handles (its intra-chunk work batched)
_HI = jax.lax.Precision.HIGHEST

# trace-time routing telemetry, as ops/attention.py ROUTE_STATS: which
# execution each traced call of `gated_delta_rule` was built with
ROUTE_STATS = {"pallas": 0, "xla": 0}


def gated_delta_rule_recurrent(q, k, v, g, beta):
    """q, k [B, T, H, Dk], v [B, T, H, Dv], g [B, T, H, Dk] log decay
    (<= 0), beta [B, T, H] -> o [B, T, H, Dv], float32, one token a step."""
    f32 = jnp.float32
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]

    def step(S, x):
        qt, kt, vt, gt, bt = x                       # [B, H, .]
        S = jnp.exp(gt)[..., None] * S
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt,
                                             precision=_HI))
        S = S + kt[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, Dk, Dv), f32), xs)
    return jnp.moveaxis(o, 0, 1)


def _unit_lower_inverse(L):
    """Inverse of unit lower-triangular matrices [..., n, n] (n a power of
    two times SUB) by matrix products alone: the SUB x SUB diagonal blocks
    through (I + N)^-1 = (I - N)(I + N^2)(I + N^4)... (N is nilpotent),
    then pairs of blocks merged, [[P, 0], [R, Q]]^-1 = [[P^-1, 0],
    [-Q^-1 R P^-1, Q^-1]], until one block is left."""
    n = L.shape[-1]
    nb = n // SUB
    lead = L.shape[:-2]
    blocks = L.reshape(lead + (nb, SUB, nb, SUB))
    eye = jnp.eye(SUB, dtype=L.dtype)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    N = diag - eye
    inv = eye - N
    power = N
    for _ in range(max(1, SUB.bit_length() - 2)):
        power = jnp.matmul(power, power, precision=_HI)
        inv = inv + jnp.matmul(inv, power, precision=_HI)
    size = SUB
    while size < n:
        half = inv.shape[-3] // 2
        P, Q = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        full = L.reshape(lead + (half, 2, size, half, 2, size))
        R = jnp.stack([full[..., i, 1, :, i, 0, :] for i in range(half)],
                      axis=-3)
        low = -jnp.matmul(jnp.matmul(Q, R, precision=_HI), P, precision=_HI)
        top = jnp.concatenate([P, jnp.zeros_like(P)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([low, Q], axis=-1)], axis=-2)
        size *= 2
    return inv[..., 0, :, :]


def _decay_products(q, k, G):
    """(A, B) [..., C, C] of one chunk from q, k [..., C, Dk] float32 and
    the running log decay G: A strictly lower, B lower with its diagonal;
    no exponent above 0."""
    C = q.shape[-2]
    ns = C // SUB
    lead = q.shape[:-2]
    sub = lambda a: a.reshape(lead + (ns, SUB, a.shape[-1]))
    qs, ks, Gs = sub(q), sub(k), sub(G)
    # G just before each sub-block's first token
    ref = jnp.concatenate(
        [jnp.zeros_like(Gs[..., :1, -1, :]), Gs[..., :-1, -1, :]], axis=-2)
    inside = jnp.exp(Gs - ref[..., None, :])
    q_in, k_in = qs * inside, ks * inside
    # inside one sub-block: the difference itself, masked before exp
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))
    diff = Gs[..., :, None, :] - Gs[..., None, :, :]
    E = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    kE = ks[..., None, :, :] * E
    A_d = jnp.sum(ks[..., :, None, :] * kE, axis=-1) \
        * jnp.tril(jnp.ones((SUB, SUB), q.dtype), -1)
    B_d = jnp.sum(qs[..., :, None, :] * kE, axis=-1)
    rows_A, rows_B = [], []
    for a in range(ns):
        blocks_A, blocks_B = [], []
        if a:
            # earlier tokens, decayed up to this sub-block's first one
            k_out = jnp.swapaxes(k[..., :a * SUB, :] * jnp.exp(
                ref[..., a, None, :] - G[..., :a * SUB, :]), -1, -2)
            blocks_A.append(jnp.matmul(k_in[..., a, :, :], k_out,
                                       precision=_HI))
            blocks_B.append(jnp.matmul(q_in[..., a, :, :], k_out,
                                       precision=_HI))
        pad = jnp.zeros(lead + (SUB, C - (a + 1) * SUB), q.dtype)
        rows_A.append(jnp.concatenate(
            blocks_A + [A_d[..., a, :, :], pad], axis=-1))
        rows_B.append(jnp.concatenate(
            blocks_B + [B_d[..., a, :, :], pad], axis=-1))
    return jnp.concatenate(rows_A, axis=-2), jnp.concatenate(rows_B, axis=-2)


def _group_step(S, x, mm_dtype):
    """One scan step: GROUP chunks.  x = (q, k, v, g, beta) shaped
    [N, GROUP, CHUNK, .]; S [N, Dk, Dv] float32.  The products inside the
    chunks are formed for the whole group at once; the state then walks
    the group's chunks."""
    f32 = jnp.float32
    q, k, v, g, beta = x
    qf, kf = q.astype(f32), k.astype(f32)
    G = jnp.cumsum(g.astype(f32), axis=-2)
    A, Bm = _decay_products(qf, kf, G)
    bcol = beta.astype(f32)[..., None]
    Tm = _unit_lower_inverse(
        jnp.eye(CHUNK, dtype=f32) + bcol * A) * jnp.swapaxes(bcol, -1, -2)
    decay = jnp.exp(G)
    mm = functools.partial(jnp.matmul, preferred_element_type=f32)
    c = lambda a: a.astype(mm_dtype)
    Tc = c(Tm)
    W = mm(Tc, c(kf * decay))                        # [N, GROUP, C, Dk]
    U0 = mm(Tc, c(v))                                # [N, GROUP, C, Dv]
    q_dec = c(qf * decay)
    k_left = c(kf * jnp.exp(G[..., -1:, :] - G))     # decayed to chunk end
    last = decay[..., -1, :]                         # [N, GROUP, Dk]
    Bc, Wc = c(Bm), c(W)
    outs = []
    for i in range(q.shape[1]):
        Sc = c(S)
        U = U0[:, i] - mm(Wc[:, i], Sc)
        Uc = c(U)
        outs.append(mm(q_dec[:, i], Sc) + mm(Bc[:, i], Uc))
        S = last[:, i, :, None] * S \
            + mm(jnp.swapaxes(k_left[:, i], -1, -2), Uc)
    return S, jnp.stack(outs, axis=1)


def gated_delta_rule_chunked(q, k, v, g, beta, mm_dtype=None):
    """The chunked form of `gated_delta_rule_recurrent`, same arguments;
    o in q's dtype.  `mm_dtype`: the dtype the large matrix products take
    their operands in (accumulation, the decay and the chunk's triangular
    inverse stay float32); q's dtype by default."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    mm_dtype = mm_dtype or q.dtype
    span = CHUNK * GROUP
    Tp = -(-T // span) * span

    def lay(a):
        # padding tokens (k = 0, beta = 0, g = 0) leave the state as it is
        a = jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
        a = jnp.moveaxis(a, 2, 1).reshape(
            B * H, Tp // span, GROUP, CHUNK, a.shape[-1])
        return jnp.moveaxis(a, 1, 0)

    xs = tuple(lay(a) for a in (q, k, v, g)) + (lay(beta[..., None])[..., 0],)
    step = jax.checkpoint(functools.partial(_group_step, mm_dtype=mm_dtype))
    _, o = jax.lax.scan(step, jnp.zeros((B * H, Dk, Dv), jnp.float32), xs)
    o = jnp.moveaxis(o, 0, 1).reshape(B, H, Tp, Dv)[:, :, :T]
    return jnp.moveaxis(o, 1, 2).astype(q.dtype)


def gated_delta_rule(q, k, v, g, beta, name=None):
    """Tensor entry: q, k [B, T, H, Dk], v [B, T, H, Dv], g [B, T, H, Dk]
    (log decay, float32 kept), beta [B, T, H] -> o [B, T, H, Dv].  Under
    `amp.auto_cast` the large matrix products take bfloat16 operands like
    every matmul; the decay and the state stay float32."""
    from .dispatch import _amp_should_cast
    from .pallas_ops import delta_rule, flash_attention

    mm_dtype = _amp_should_cast("matmul_v2")
    q, k, v, g, beta = (to_tensor_like(a) for a in (q, k, v, g, beta))
    # a step traced for a mesh (`partitioned_over`) keeps the XLA form,
    # which GSPMD partitions: a Mosaic call would need a shard_map
    pallas = jax.default_backend() == "tpu" \
        and delta_rule.fits(q.shape[-1], v.shape[-1]) \
        and getattr(flash_attention._partition, "spec", None) is None
    ROUTE_STATS["pallas" if pallas else "xla"] += 1
    form = delta_rule.gated_delta_rule_kernel if pallas \
        else gated_delta_rule_chunked

    def f(q, k, v, g, beta):
        return form(q, k, v, g, beta, mm_dtype=mm_dtype or q.dtype)

    return apply("gated_delta_rule", f, q, k, v, g, beta)
