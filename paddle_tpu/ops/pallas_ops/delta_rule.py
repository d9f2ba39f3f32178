"""The gated delta rule (ops/linear_attention.py) as two Pallas TPU
kernels, forward and backward, under one `jax.custom_vjp`.

Same mathematics as the XLA chunked form, its twin: chunks of CHUNK = 64
tokens, a per-channel decay, no exponent above 0, the inverse of the unit
lower-triangular I + diag(beta) A by matrix products alone, the state
[Dk, Dv] float32 walked from chunk to chunk.  What differs is where it
runs: a grid step takes a PAIR of chunks (128 tokens, one lane tile) of
one head, the pairs of a head in sequence on an "arbitrary" axis with the
states of a block's eight heads in VMEM scratch, every product in tiles
whose minor dimension is 128, nothing of a chunk (A, B, the inverse, U)
ever in HBM.

- q, k, v, g arrive [B, T, H, D] and are read where they lie: a block is
  a pair of EIGHT heads, [128, 8, 128] — eight sublanes of the array's own
  (8, 128) tiles, so XLA relayouts nothing — and the grid's last axis
  walks the block's heads, each one a strided read of its sublane (as
  [B, T, H * D] the head would pick a column block, but XLA then copies
  every operand between the two tilings: 60 ms of a 589-ms step).
- A pair's two chunks are carried side by side as block-diagonal
  [128, 128] matrices (A, B, the inverse), so every product fills the
  MXU's 128 x 128.
- Pairs of different 16-token sub-blocks factor around the later block's
  first position, three [64, 128] x [128, 128] products a pair.  The 16
  x 16 diagonal blocks form exp(G_i - G_j) itself, masked before `exp`,
  on the VPU with the CHANNELS on sublanes and the tokens on lanes: token
  j = i - r is a lane roll by r, the sum over channels is a sum of vector
  registers, and the sixteen diagonals r = 0..15 are then laid into the
  blocks by selects.
- Float32, `precision=HIGHEST` (Mosaic's fp32 contract precision): the
  running decay, A, B and the inverse, and in the backward everything
  that flows into them.  The large products with the state take
  `mm_dtype` operands with float32 accumulation — the set the XLA form
  casts, no more.
- The forward emits o and the state at every pair's start (the backward's
  residual); the backward walks the pairs in reverse with dS in scratch,
  recomputes a pair's A, B, inverse and U from q, k, v, g, beta and that
  state, and produces dq, dk, dv, dg (float32) and dbeta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .contracts import CONTRACTS

_C = CONTRACTS["delta_rule_fwd"]
PAIR = _C.dim("pair")           # tokens a grid step handles: two chunks
CHUNK = PAIR // 2
SUB = _C.dim("sub")             # as ops/linear_attention.py's
LANE = _C.dim("lane")
_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
_NEG = -jnp.inf


def fits(dk, dv):
    """Head sizes the kernels take: one lane tile each (the state is one
    [128, 128] block, a chunk pair of q, k, g one [128, 128] tile)."""
    return dk == LANE and dv == LANE


# --------------------------------------------------------------------------
# products
# --------------------------------------------------------------------------
def _hi(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=_F32)


def _hi_nt(a, b):                                   # a @ b.T
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _hi_tn(a, b):                                   # a.T @ b
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())), precision=_HI,
                           preferred_element_type=_F32)


def _mm(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _mm_nt(a, b):
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=_F32)


def _mm_tn(a, b):
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=_F32)


def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _one(cond):
    return jnp.where(cond, 1.0, 0.0).astype(_F32)


# --------------------------------------------------------------------------
# a pair's state-free part: G, A, B, the inverse
# --------------------------------------------------------------------------
def _sub_rows(a, ch, s):
    """The 16 rows of sub-block s of chunk ch."""
    start = ch * CHUNK + s * SUB
    return a[start:start + SUB]


def _diagonals(kT, GT):
    """[(E, kT_j E)] for the sixteen diagonals of the 16 x 16 blocks: at
    lane i the partner is token j = i - r of the same sub-block,
    E = exp(G_i - G_j), masked before exp (None for r = 0: it is 1)."""
    pos = _iota((1, PAIR), 1) % SUB
    out = [(None, kT)]
    for r in range(1, SUB):
        E = jnp.exp(GT - pltpu.roll(GT, r, 1)
                    + jnp.where(pos >= r, 0.0, _NEG))
        out.append((E, pltpu.roll(kT, r, 1) * E))
    return out


def _diag_blocks(qT, kT, diagonals):
    """(A_d, B_d) [PAIR, PAIR]: the 16 x 16 diagonal blocks of A (strictly
    lower) and B (lower with its diagonal), zero elsewhere; operands
    [Dk, PAIR] (channels on sublanes, tokens on lanes)."""
    pos = _iota((SUB, PAIR), 1) % SUB
    dist = pos - _iota((SUB, PAIR), 0)              # i - j inside a block
    ZA = jnp.zeros((SUB, PAIR), _F32)
    ZB = jnp.zeros((SUB, PAIR), _F32)
    for r, (_, kE) in enumerate(diagonals):
        ZB = jnp.where(dist == r, jnp.sum(qT * kE, axis=0, keepdims=True),
                       ZB)
        if r:
            ZA = jnp.where(dist == r,
                           jnp.sum(kT * kE, axis=0, keepdims=True), ZA)
    # Z[jj, i] is the entry (i, 16 * (i // 16) + jj): tile it down the
    # rows, keep each block's own columns, transpose
    block = (_iota((PAIR, PAIR), 0) // SUB) == (_iota((PAIR, PAIR), 1) // SUB)
    spread = lambda Z: jnp.where(
        block, jnp.concatenate([Z] * (PAIR // SUB), axis=0), 0.0).T
    return spread(ZA), spread(ZB)


def _off_factors(k, G, ref, a):
    """Tokens before sub-block a of their chunk, decayed up to its first
    position: (k * exp(ref_a - G), exp(ref_a - G)), zero from a on."""
    tok = _iota((PAIR, 1), 0) % CHUNK
    refrow = jnp.concatenate(
        [jnp.broadcast_to(ref[ch * CHUNK + a * SUB:ch * CHUNK + a * SUB + 1],
                          (CHUNK, ref.shape[1])) for ch in (0, 1)], axis=0)
    eo = jnp.exp(jnp.where(tok < a * SUB, refrow - G, _NEG))
    return k * eo, eo


def _off_lhs(q_in, k_in, a):
    return jnp.concatenate([_sub_rows(k_in, 0, a), _sub_rows(q_in, 0, a),
                            _sub_rows(k_in, 1, a), _sub_rows(q_in, 1, a)],
                           axis=0)


def _unit_lower_inverse(N):
    """(I + N)^-1 for N [PAIR, PAIR] strictly lower inside each chunk's
    block, as linear_attention._unit_lower_inverse: the 16 x 16 diagonal
    blocks through (I - N)(I + N^2)(I + N^4)(I + N^8), then pairs of
    blocks merged, [[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1,
    Q^-1]] — every product on whole [PAIR, PAIR] block-diagonal
    matrices."""
    row, col = _iota(N.shape, 0), _iota(N.shape, 1)
    within = lambda n: (row // n) == (col // n)
    Nd = jnp.where(within(SUB), N, 0.0)
    X = _one(row == col) - Nd
    power = Nd
    for _ in range(max(1, SUB.bit_length() - 2)):
        power = _hi(power, power)
        X = X + _hi(X, power)
    size = SUB
    while size < CHUNK:
        R = jnp.where(within(2 * size) & ~within(size), N, 0.0)
        X = X - _hi(_hi(X, R), X)
        size *= 2
    return X


def _pair_parts(q, k, g, bcol, brow):
    """Everything of a pair that does not read the state.  q, k, g
    [PAIR, Dk] float32, beta as a column and as a row."""
    P = PAIR
    row, col = _iota((P, P), 0), _iota((P, P), 1)
    same = (row // CHUNK) == (col // CHUNK)
    # the running log decay G, and G just before each sub-block's first
    # token, in one product
    sums = _hi(jnp.concatenate([_one(same & (row >= col)),
                                _one(same & (col // SUB < row // SUB))],
                               axis=0), g)
    G, ref = sums[:P], sums[P:]
    inside = jnp.exp(G - ref)
    q_in, k_in = q * inside, k * inside
    offs = {a: _off_factors(k, G, ref, a) for a in range(1, CHUNK // SUB)}
    outs = {a: _hi_nt(_off_lhs(q_in, k_in, a), k_out)
            for a, (k_out, _) in offs.items()}
    zero = jnp.zeros((SUB, P), _F32)
    off = lambda at: jnp.where(same, jnp.concatenate(
        [zero] + [outs[a][at:at + SUB] for a in outs]
        + [zero] + [outs[a][at + 2 * SUB:at + 3 * SUB] for a in outs],
        axis=0), 0.0)
    qT, kT, GT = q.T, k.T, G.T
    diagonals = _diagonals(kT, GT)
    A_d, B_d = _diag_blocks(qT, kT, diagonals)
    A, Bm = off(0) + A_d, off(SUB) + B_d
    X = _unit_lower_inverse(bcol * A)
    decay = jnp.exp(G)
    # G at the chunk's last token, per token of the pair, both layouts
    lastT = jnp.where(_iota((1, P), 1) < CHUNK, GT[:, CHUNK - 1:CHUNK],
                      GT[:, P - 1:P])
    elT = jnp.exp(lastT - GT)
    return dict(G=G, inside=inside, q_in=q_in, k_in=k_in, offs=offs,
                qT=qT, kT=kT, GT=GT, diagonals=diagonals, A=A, Bm=Bm, X=X,
                Tm=X * brow, decay=decay, elT=elT, klT=kT * elT,
                lastcol=[jnp.exp(GT[:, CHUNK - 1:CHUNK]),
                         jnp.exp(GT[:, P - 1:P])])


def _walk(parts, q, k, v, S, mm_dtype):
    """The state's walk over the pair's two chunks: (U [PAIR, Dv]
    float32, the state before each chunk, the state after the pair, W in
    `mm_dtype`)."""
    c = lambda a: a.astype(mm_dtype)
    Tc = c(parts["Tm"])
    W = c(_mm(Tc, c(k * parts["decay"])))
    U0 = _mm(Tc, c(v))
    klT = c(parts["klT"])
    Us, Ss = [], []
    for ch in (0, 1):
        rows = slice(ch * CHUNK, (ch + 1) * CHUNK)
        Ss.append(S)
        U = U0[rows] - _mm(W[rows], c(S))
        Us.append(U)
        S = parts["lastcol"][ch] * S + _mm(klT[:, rows], c(U))
    return jnp.concatenate(Us, axis=0), Ss, S, W


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------
def _heads(ref, step, n):
    """The [PAIR, D] float32 tiles of heads step * n .. + n of a
    [PAIR, heads, D] block.  A head is a sublane of the block's tiles: a
    32-bit ref takes a dynamic index there, a packed one (bfloat16) only
    a static one."""
    if ref.dtype == _F32:
        return [ref[:, step * n + i, :] for i in range(n)]
    return lax.switch(step, [
        lambda at=at: [ref[:, at * n + i, :].astype(_F32) for i in range(n)]
        for at in range(ref.shape[1] // n)])


def _put_heads(ref, step, values):
    n = len(values)
    if ref.dtype == _F32:
        for i, value in enumerate(values):
            ref[:, step * n + i, :] = value
        return
    for at in range(ref.shape[1] // n):
        @pl.when(step == at)
        def _(at=at):
            for i, value in enumerate(values):
                ref[:, at * n + i, :] = value.astype(ref.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, bcol_ref, brow_ref,
                o_ref, s_ref, state, *, mm_dtype):
    """One grid step: a pair of chunks of `n` heads, whose chains of
    products do not depend on each other and are scheduled together."""
    step, n = pl.program_id(3), s_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        for i in range(n):
            state[step * n + i] = jnp.zeros(state.shape[1:], _F32)

    tiles = [_heads(ref, step, n) for ref in (q_ref, k_ref, v_ref, g_ref)]
    outs = []
    for i in range(n):
        S0 = state[step * n + i]
        s_ref[i] = S0
        o, S = _fwd_head(*(t[i] for t in tiles), bcol_ref[i], brow_ref[i],
                         S0, mm_dtype)
        state[step * n + i] = S
        outs.append(o)
    _put_heads(o_ref, step, outs)


def _fwd_head(q, k, v, g, bcol, brow, S0, mm_dtype):
    """(o [PAIR, Dv], the state after the pair) of one head."""
    c = lambda a: a.astype(mm_dtype)
    parts = _pair_parts(q, k, g, bcol, brow)
    U, Ss, S, _ = _walk(parts, q, k, v, S0, mm_dtype)
    q_dec = c(q * parts["decay"])
    o = _mm(c(parts["Bm"]), c(U))
    o = o + jnp.concatenate(
        [_mm(q_dec[ch * CHUNK:(ch + 1) * CHUNK], c(Ss[ch]))
         for ch in (0, 1)], axis=0)
    return o, S


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, bcol_ref, brow_ref, s_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *,
                mm_dtype):
    step, n = pl.program_id(3), s_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        for i in range(n):
            dstate[step * n + i] = jnp.zeros(dstate.shape[1:], _F32)

    tiles = [_heads(ref, step, n)
             for ref in (q_ref, k_ref, v_ref, g_ref, do_ref)]
    outs = []
    for i in range(n):
        *grads, dbeta, dS = _bwd_head(
            *(t[i] for t in tiles), bcol_ref[i], brow_ref[i], s_ref[i],
            dstate[step * n + i], mm_dtype)
        dstate[step * n + i] = dS
        db_ref[i] = dbeta
        outs.append(grads)
    for ref, values in zip((dq_ref, dk_ref, dv_ref, dg_ref), zip(*outs)):
        _put_heads(ref, step, values)


def _bwd_head(q, k, v, g, dO, bcol, brow, S0, dS, mm_dtype):
    """(dq, dk, dv, dg, dbeta, dS before the pair) of one head, from the
    state at the pair's start and dS after it."""
    P = PAIR
    c = lambda a: a.astype(mm_dtype)
    p = _pair_parts(q, k, g, bcol, brow)
    U, Ss, _, Wc = _walk(p, q, k, v, S0, mm_dtype)
    row, col = _iota((P, P), 0), _iota((P, P), 1)
    same = (row // CHUNK) == (col // CHUNK)
    lower, upper = same & (row >= col), same & (row <= col)

    # --- the state's walk, backwards: dU, and what reads the state -------
    decay, G = p["decay"], p["G"]
    kd, qd = k * decay, q * decay
    Tc, Bc, Uc = c(p["Tm"]), c(p["Bm"]), c(U)
    klT = c(p["klT"])
    dOc = c(dO)
    dU_o = _mm_tn(Bc, dOc)                          # Bm^T dO, all at once
    dUs, dWs, dqd, dklT, dlast = [None] * 2, [None] * 2, [None] * 2, \
        [None] * 2, [None] * 2
    for ch in (1, 0):
        rows = slice(ch * CHUNK, (ch + 1) * CHUNK)
        Sc, dSc = c(Ss[ch]), c(dS)
        dU = dU_o[rows] + _mm_tn(klT[:, rows], dSc)
        dUs[ch] = dU
        dUc = c(dU)
        dklT[ch] = _mm_nt(dSc, Uc[rows])            # [Dk, CHUNK]
        dlast[ch] = jnp.sum(Ss[ch] * dS, axis=1, keepdims=True)  # [Dk, 1]
        dWs[ch] = -_mm_nt(dUc, Sc)                  # [CHUNK, Dk]
        dqd[ch] = _mm_nt(dOc[rows], Sc)
        dS = p["lastcol"][ch] * dS + _mm_tn(c(qd)[rows], dOc[rows]) \
            - _mm_tn(Wc[rows], dUc)
    dU = jnp.concatenate(dUs, axis=0)
    dW, dQd = jnp.concatenate(dWs, axis=0), jnp.concatenate(dqd, axis=0)
    dUc, dWc = c(dU), c(dW)
    # U0 = Tm v, W = Tm kd
    dTm = _mm_nt(dUc, c(v)) + _mm_nt(dWc, c(kd))
    dv = _mm_tn(Tc, dUc)
    dKd = _mm_tn(Tc, dWc)
    dBm = jnp.where(lower, _mm_nt(dOc, Uc), 0.0)
    dBmT = jnp.where(upper, _mm_nt(Uc, dOc), 0.0)

    # --- Tm = X diag(beta), X = (I + diag(beta) A)^-1 ----------------------
    X, A = p["X"], p["A"]
    dX = dTm * brow
    dL = -_hi_tn(X, _hi_nt(dX, X))                  # -X^T dX X^T
    strict = same & (row > col)
    dbeta = jnp.sum(jnp.where(strict, dL * A, 0.0), axis=1, keepdims=True) \
        + _hi_tn(dTm * X, jnp.ones((P, 1), _F32))
    dA = jnp.where(strict, bcol * dL, 0.0)
    dAT = dA.T

    # --- A, B: pairs of different sub-blocks --------------------------------
    q_in, k_in, inside = p["q_in"], p["k_in"], p["inside"]
    subs = CHUNK // SUB
    dq_in = [[None] * subs, [None] * subs]
    dk_in = [[None] * subs, [None] * subs]
    dk = jnp.zeros_like(k)
    dG = jnp.zeros_like(k)
    dref_rows = {}
    for a, (k_out, eo) in p["offs"].items():
        d_out = jnp.concatenate([_sub_rows(dA, 0, a), _sub_rows(dBm, 0, a),
                                 _sub_rows(dA, 1, a), _sub_rows(dBm, 1, a)],
                                axis=0)                         # [64, P]
        dX_a = _hi(d_out, k_out)                                # [64, Dk]
        for ch in (0, 1):
            dk_in[ch][a] = dX_a[2 * ch * SUB:(2 * ch + 1) * SUB]
            dq_in[ch][a] = dX_a[(2 * ch + 1) * SUB:(2 * ch + 2) * SUB]
        dk_out = _hi_tn(d_out, _off_lhs(q_in, k_in, a))         # [P, Dk]
        dk = dk + dk_out * eo
        t = dk_out * k_out          # d(ref_a - G) of the earlier tokens
        dG = dG - t
        dref_rows[a] = [jnp.sum(t[ch * CHUNK:(ch + 1) * CHUNK], axis=0,
                                keepdims=True) for ch in (0, 1)]
    zero = jnp.zeros((SUB, k.shape[1]), _F32)
    gather = lambda d: jnp.concatenate(
        [zero if d[ch][a] is None else d[ch][a]
         for ch in (0, 1) for a in range(subs)], axis=0)
    dq_in_all, dk_in_all = gather(dq_in), gather(dk_in)
    dq = dq_in_all * inside
    dk = dk + dk_in_all * inside
    t = dq_in_all * q_in + dk_in_all * k_in         # d(G - ref) of a row
    dG = dG + t
    # ref is one row a sub-block: minus the rows' sum, plus what the
    # earlier tokens' factors sent it; ref = G at the row before
    tsum = lambda a, ch: jnp.sum(_sub_rows(t, ch, a), axis=0, keepdims=True)
    one_hot = lambda at: _one(_iota((P, 1), 0) == at)
    for ch in (0, 1):
        for a in range(1, subs):
            dref = dref_rows[a][ch] - tsum(a, ch)
            dG = dG + one_hot(ch * CHUNK + a * SUB - 1) * dref

    # --- A, B: the 16 x 16 diagonal blocks ----------------------------------
    qT, kT, GT = p["qT"], p["kT"], p["GT"]
    squeeze = lambda M: sum(
        jnp.where(_iota((SUB, P), 1) // SUB == s, M[s * SUB:(s + 1) * SUB],
                  0.0) for s in range(P // SUB))    # [16, P]: rows jj
    ZA, ZB = squeeze(dAT), squeeze(dBmT)
    dist = _iota((SUB, P), 1) % SUB - _iota((SUB, P), 0)
    dqT = jnp.zeros_like(qT)
    dk_rowT = jnp.zeros_like(qT)
    dk_colT = jnp.zeros_like(qT)
    for r, (E, kE) in enumerate(p["diagonals"]):
        mB = jnp.sum(jnp.where(dist == r, ZB, 0.0), axis=0, keepdims=True)
        dqT = dqT + mB * kE
        back = mB * qT
        if r:
            mA = jnp.sum(jnp.where(dist == r, ZA, 0.0), axis=0,
                         keepdims=True)
            dk_rowT = dk_rowT + mA * kE
            back = pltpu.roll((back + mA * kT) * E, P - r, 1)
        dk_colT = dk_colT + back
    # the state's decay and k decayed to the chunk's end live in this
    # layout too: klT = kT exp(G_last - GT), lastcol = exp(G_last)
    dklT = jnp.concatenate(dklT, axis=1)                        # [Dk, P]
    lane = _iota((1, P), 1)
    t = dklT * p["klT"]
    dGT = qT * dqT + kT * (dk_rowT - dk_colT) - t
    for ch in (0, 1):
        mine = (lane // CHUNK) == ch
        to_last = dlast[ch] * p["lastcol"][ch] + jnp.sum(
            jnp.where(mine, t, 0.0), axis=1, keepdims=True)     # [Dk, 1]
        dGT = dGT + jnp.where(lane == (ch + 1) * CHUNK - 1, to_last, 0.0)
    dq = dq + dqT.T
    dk = dk + (dk_rowT + dk_colT + dklT * p["elT"]).T
    dG = dG + dGT.T

    # --- the decayed copies of q and k --------------------------------------
    dq = dq + dQd * decay
    dk = dk + dKd * decay
    dG = dG + dQd * qd + dKd * kd

    # G is the running sum of g inside a chunk
    return dq, dk, dv, _hi(_one(upper), dG), dbeta, dS


# --------------------------------------------------------------------------
# calls
# --------------------------------------------------------------------------
def _params():
    # float32 operands at eight heads a block pass the 16 MiB a kernel is
    # given by default (blocks 13 MiB double-buffered + ~4 MiB a head of
    # temporaries); the chip's VMEM is 128 MiB
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary",
                             "arbitrary"),
        vmem_limit_bytes=_C.dim("vmem_limit_mib") * 2 ** 20)


def _head_block(H):
    """(heads a block holds: a tile's eight sublanes, or all of them;
    heads a grid step takes of them)."""
    hb = _C.dim("heads") if H % _C.dim("heads") == 0 else H
    return hb, (_C.dim("together") if hb % _C.dim("together") == 0 else 1)


def _specs(H, Dk, Dv, NP, reverse):
    """Block specs of (q or k or g, v, beta column, beta row, state) over
    [B, Tp, H, D], [B, H, Tp, 1], [B, H, 1, Tp], [B, H, NP, Dk, Dv] for the
    grid (batch, head blocks, pairs, steps of a block): a block of q, k,
    v, g holds a pair of every head of its block and stays where it is
    while the last axis walks those heads, `n` a step."""
    hb, n = _head_block(H)
    per = hb // n
    at = (lambda p: NP - 1 - p) if reverse else (lambda p: p)
    tok = lambda D: pl.BlockSpec((None, PAIR, hb, D),
                                 lambda b, g, p, h: (b, at(p), g, 0))
    return (tok(Dk), tok(Dv),
            pl.BlockSpec((None, n, PAIR, 1),
                         lambda b, g, p, h: (b, g * per + h, at(p), 0)),
            pl.BlockSpec((None, n, 1, PAIR),
                         lambda b, g, p, h: (b, g * per + h, 0, at(p))),
            pl.BlockSpec((None, n, None, Dk, Dv),
                         lambda b, g, p, h: (b, g * per + h, at(p), 0, 0)))


# jitted so that the layers of a step share one trace of each kernel
# body: a body is thousands of equations, and a step calls it per layer,
# forward, forward again under recomputation, and backward
@functools.partial(jax.jit, static_argnames=("mm_dtype", "interpret"))
def _fwd_call(q, k, v, g, bcol, brow, mm_dtype, interpret):
    B, Tp, H, Dk = q.shape
    Dv, NP = v.shape[-1], Tp // PAIR
    hb, n = _head_block(H)
    qk, vs, bc, br, st = _specs(H, Dk, Dv, NP, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, mm_dtype=mm_dtype),
        grid=(B, H // hb, NP, hb // n),
        in_specs=[qk, qk, vs, qk, bc, br],
        out_specs=[vs, st],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H, Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, NP, Dk, Dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, Dk, Dv), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, bcol, brow)


@functools.partial(jax.jit, static_argnames=("mm_dtype", "interpret"))
def _bwd_call(q, k, v, g, bcol, brow, states, do, mm_dtype, interpret):
    B, Tp, H, Dk = q.shape
    Dv, NP = v.shape[-1], Tp // PAIR
    hb, n = _head_block(H)
    qk, vs, bc, br, st = _specs(H, Dk, Dv, NP, reverse=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, mm_dtype=mm_dtype),
        grid=(B, H // hb, NP, hb // n),
        in_specs=[qk, qk, vs, qk, bc, br, st, vs],
        out_specs=[qk, qk, vs, qk, bc],
        out_shape=[like(q), like(k), like(v), like(g), like(bcol)],
        scratch_shapes=[pltpu.VMEM((hb, Dk, Dv), _F32)],
        compiler_params=_params(), interpret=interpret,
    )(q, k, v, g, bcol, brow, states, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _delta_rule(q, k, v, g, bcol, mm_dtype, interpret):
    return _fwd_call(q, k, v, g, bcol, jnp.swapaxes(bcol, 2, 3), mm_dtype,
                     interpret)[0]


def _delta_rule_fwd(q, k, v, g, bcol, mm_dtype, interpret):
    o, states = _fwd_call(q, k, v, g, bcol, jnp.swapaxes(bcol, 2, 3),
                          mm_dtype, interpret)
    return o, (q, k, v, g, bcol, states)


def _delta_rule_bwd(mm_dtype, interpret, res, do):
    q, k, v, g, bcol, states = res
    return tuple(_bwd_call(q, k, v, g, bcol, jnp.swapaxes(bcol, 2, 3),
                           states, do, mm_dtype, interpret))


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def gated_delta_rule_kernel(q, k, v, g, beta, mm_dtype=None,
                            interpret=False):
    """`linear_attention.gated_delta_rule_chunked` through the kernels:
    q, k [B, T, H, Dk], v [B, T, H, Dv], g [B, T, H, Dk] log decay,
    beta [B, T, H] -> o [B, T, H, Dv] in q's dtype; Dk = Dv = 128, any T
    (padded to a pair of chunks with k = 0, beta = 0, g = 0: such tokens
    leave the state as it is).  Differentiable in all five."""
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    if not fits(Dk, Dv):
        raise ValueError(f"head sizes {Dk}, {Dv}: the kernels take 128")
    mm_dtype = jnp.dtype(mm_dtype or q.dtype)
    Tp = -(-T // PAIR) * PAIR
    pad = lambda a: a if Tp == T else jnp.pad(
        a, ((0, 0), (0, Tp - T)) + ((0, 0),) * (a.ndim - 2))
    bcol = jnp.moveaxis(pad(beta.astype(_F32)), 2, 1)[..., None]
    o = _delta_rule(pad(q), pad(k), pad(v), pad(g), bcol, mm_dtype,
                    interpret)
    return o[:, :T]
