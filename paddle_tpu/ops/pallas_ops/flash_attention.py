"""Pallas flash attention (TPU) — mask + dropout capable, Pallas backward.

New capability vs the reference (SURVEY §5.7: the reference's
MultiHeadAttention materializes full QK^T — nn/layer/transformer.py:115).
Tiled online-softmax attention: per (batch·head, q-block) grid cell the kernel
streams KV blocks through VMEM, keeping running max/denominator — O(S) memory
instead of O(S²), MXU-shaped 128-wide tiles.

Round-2 upgrades (VERDICT r1 #2):
- **Padding mask**: a per-token kv validity mask [B, S] (the BERT padding
  form) rides along as an O(S) input; masked keys get -inf logits in-kernel.
  Arbitrary [B, H, S, S] masks stay on the XLA path (they are O(S²) by
  construction and defeat flash).
- **Dropout**: attention-prob dropout inside the kernel using a counter-based
  hash of (seed, batch·head, global row, global col) computed with plain
  uint32 vector ops — platform-independent (works under interpret mode on
  CPU, unlike pltpu.prng_*) and exactly reproducible in the backward kernels.
- **Pallas backward**: dk/dv and dq kernels (two passes, standard flash-2
  split) recompute probabilities blockwise from the saved logsumexp and
  regenerate identical dropout bits — no S×S residual in either direction.
- **Shape freedom**: sequence length is padded to the block size and head_dim
  padded to an MXU-friendly width inside the wrapper; outputs are sliced back.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from .contracts import FLASH_FWD

# tuned on v5e @ S=4096, D=128 (0.41 ms vs 2.17 ms XLA fused attention):
# big q/k blocks keep the MXU busy and amortize per-block scratch
# updates.  The values live in the declared KernelContract
# (contracts.FLASH_FWD) — single source of truth for the kernels, the
# pallas-contract lint and the autotuner.
DEFAULT_BLOCK_Q = FLASH_FWD.dim("block_q")
DEFAULT_BLOCK_K = FLASH_FWD.dim("block_k")
_LANE = FLASH_FWD.dim("lane")
NEG_INF = -1e30


def _pick_block(default, seq_len):
    """Largest power-of-two divisor of seq_len, capped at `default` (≥128
    where possible to satisfy mosaic lane tiling)."""
    b = min(default, seq_len)
    while b > _LANE and seq_len % b:
        b //= 2
    if seq_len % b:
        b = seq_len  # no clean divisor: single block
    return b


def _resolved_blocks(seq_len_padded):
    """Preferred (block_q, block_k) for this padded sequence length:
    tuning-table hit (validate()-gated at the shape bucket) -> contract
    default; both then pass the `_pick_block` divisor guard, because a
    bucket covers every x128-padded length below it and the kernel
    needs blocks that tile THIS array exactly (docs/TUNING.md)."""
    from ...tune.runtime import lookup_dims

    tuned = lookup_dims(FLASH_FWD, {"block_q": seq_len_padded,
                                    "block_k": seq_len_padded})
    if tuned is None:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    return (tuned.get("block_q", DEFAULT_BLOCK_Q),
            tuned.get("block_k", DEFAULT_BLOCK_K))


def _keep_mask(seed, bh, rows, cols, dropout_p):
    """Deterministic dropout keep-mask: xorshift-mix hash of the GLOBAL
    (row, col) position + seed + batch·head.  Independent of block shape, so
    forward and both backward kernels regenerate identical bits."""
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         ^ cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
    x = x + seed.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    x = x ^ (bh.astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x2C1B3C6D)
    x = x ^ (x >> 12)
    x = x * jnp.uint32(0x297A2D39)
    x = x ^ (x >> 15)
    thresh = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return x >= thresh


def _global_rc(qi, j, block_q, block_k):
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return rows, cols


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_sc, m_sc, l_sc, *, scale, causal, dropout_p,
                block_q, block_k, nk):
    """Grid (BH, nq, nk) with KV innermost: pallas double-buffers the KV block
    DMAs while the MXU works; running max/denominator live in VMEM scratch."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_sc[:] = jnp.zeros_like(acc_sc)
        m_sc[:] = jnp.full_like(m_sc, NEG_INF)
        l_sc[:] = jnp.zeros_like(l_sc)

    if causal:
        # skip compute for blocks entirely above the diagonal
        compute = j * block_k <= (qi + 1) * block_q - 1
    else:
        compute = j >= 0

    @pl.when(compute)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale  # [BQ, D]
        kblk = k_ref[0].astype(jnp.float32)  # [BK, D]
        vblk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        rows, cols = _global_rc(qi, j, block_q, block_k)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        # kv validity mask (1.0 = attend) — [1, BK] broadcast over rows
        s = jnp.where(mask_ref[0] > 0, s, NEG_INF)
        m_prev = m_sc[:, :1]  # [BQ, 1]
        l_prev = l_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0], b, rows, cols, dropout_p)
            # dropout scales the PV accumulation only; the softmax
            # denominator keeps the full probability mass
            p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
        else:
            p_acc = p
        acc_sc[:] = acc_sc[:] * alpha + jax.lax.dot_general(
            p_acc, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[:] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[:] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == nk - 1)
    def _write():
        l_safe = jnp.maximum(l_sc[:, :1], 1e-30)
        o_ref[0] = (acc_sc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_sc[:, :1] + jnp.log(l_safe)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, mask_ref, dk_ref, dv_ref, dk_sc, dv_sc, *,
                    scale, causal, dropout_p, block_q, block_k, nq, group=1):
    """Grid (B*Hkv, nk, group*nq): fixed KV block, stream q/do blocks,
    accumulate dk/dv in VMEM scratch.  With `group` query heads to a KV
    head the innermost axis walks the group's heads one after another, so
    dk/dv are summed over the group in the float32 scratch and written
    once."""
    b = pl.program_id(0)
    jj = pl.program_id(1)
    t = ii = pl.program_id(2)
    if group > 1:
        # b the query head (the dropout hash's index), ii its q block
        b, ii = b * group + t // nq, t % nq

    @pl.when(t == 0)
    def _init():
        dk_sc[:] = jnp.zeros_like(dk_sc)
        dv_sc[:] = jnp.zeros_like(dv_sc)

    if causal:
        compute = (ii + 1) * block_q - 1 >= jj * block_k
    else:
        compute = ii >= 0

    @pl.when(compute)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale     # [BQ, D]
        kblk = k_ref[0].astype(jnp.float32)          # [BK, D]
        vblk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)           # [BQ, D]
        lse = lse_ref[0]                             # [BQ, 1]
        delta = delta_ref[0]                         # [BQ, 1]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows, cols = _global_rc(ii, jj, block_q, block_k)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        s = jnp.where(mask_ref[0] > 0, s, NEG_INF)
        p = jnp.exp(s - lse)                         # normalized probs
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0], b, rows, cols, dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            p_v = jnp.where(keep, p * inv, 0.0)      # dropped probs for dv
            dpn = jnp.where(keep, dp * inv, 0.0)     # d(prob) through dropout
        else:
            p_v = p
            dpn = dp
        dv_sc[:] += jax.lax.dot_general(p_v, do, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        ds = p * (dpn - delta)
        # q was pre-scaled → this accumulates scale * dsᵀ·q = dk
        dk_sc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(t == group * nq - 1)
    def _write():
        dk_ref[0] = dk_sc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, mask_ref, dq_ref, dq_sc, *, scale, causal,
                   dropout_p, block_q, block_k, nk):
    """Grid (BH, nq, nk): fixed q block, stream KV blocks, accumulate dq."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_sc[:] = jnp.zeros_like(dq_sc)

    if causal:
        compute = j * block_k <= (qi + 1) * block_q - 1
    else:
        compute = j >= 0

    @pl.when(compute)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        kblk = k_ref[0].astype(jnp.float32)
        vblk = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        rows, cols = _global_rc(qi, j, block_q, block_k)
        if causal:
            s = jnp.where(rows >= cols, s, NEG_INF)
        s = jnp.where(mask_ref[0] > 0, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, vblk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref[0], b, rows, cols, dropout_p)
            dpn = jnp.where(keep, dp * (1.0 / (1.0 - dropout_p)), 0.0)
        else:
            dpn = dp
        ds = p * (dpn - delta)
        dq_sc[:] += jax.lax.dot_general(ds, kblk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _write():
        dq_ref[0] = (dq_sc[:] * scale).astype(dq_ref.dtype)


def _interpret_mode() -> bool:
    """Pallas interpret mode off-TPU (CPU tests exercise the same kernel)."""
    return jax.default_backend() != "tpu"


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _sds(shape, dtype, ref):
    """ShapeDtypeStruct inheriting `ref`'s shard_map varying axes (vma) —
    required when the kernel runs inside shard_map (ring attention)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(ref).vma)


def _kv_block(group):
    """Index map of a K or V block under a grid whose first axis is the
    QUERY head (batch * H + h) and whose last is the KV block: query head
    h reads KV head h // group straight from the [B * Hkv, S, D] array, so
    no copy of K or V at the query heads' count is ever written."""
    if group == 1:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (b // group, j, 0)


def _flash_fwd_bhsd(q, k, v, mask, seed, scale, causal, dropout_p,
                    block_q, block_k):
    B, H, S, D = q.shape
    Dv = v.shape[-1]            # the v/o head size may differ from q/k's
    Hkv = k.shape[1]            # fewer KV heads: each serves H // Hkv
    kv = _kv_block(H // Hkv)
    nk = S // block_k
    grid = (B * H, S // block_q, nk)

    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * Hkv, S, D)
    v3 = v.reshape(B * Hkv, S, Dv)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          dropout_p=dropout_p, block_q=block_q,
                          block_k=block_k, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv),
            pl.BlockSpec((1, block_k, Dv), kv),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j, h=H: (b // h, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            # TPU mosaic tiling: trailing dims of a block must be (8k, 128k)
            # or equal to the array dims — hence lse carried as [BH, S, 1]
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((B * H, S, Dv), q.dtype, q3),
            _sds((B * H, S, 1), jnp.float32, q3),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret_mode(),
    )(seed, q3, k3, v3, mask)
    return out.reshape(B, H, S, Dv), lse


def _flash_dkv_bhsd(q, k, v, g, lse, delta, mask, seed, scale, causal,
                    dropout_p, block_q, block_k):
    """dk/dv for one (q-block set, kv chunk) pair.  lse/delta are the
    GLOBAL per-row stats of the visiting queries — summing chunk results
    over all visiting q sets gives the exact global dk/dv."""
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[-1]
    group = H // Hkv
    q3 = q.reshape(B * H, Sq, D)
    k3 = k.reshape(B * Hkv, Sk, D)
    v3 = v.reshape(B * Hkv, Sk, Dv)
    g3 = g.reshape(B * H, Sq, Dv)
    nq, nk = Sq // block_q, Sk // block_k
    if group == 1:
        rows = lambda b, jj, ii: (b, ii, 0)
    else:
        # the grid's first axis is the KV head; its last walks the q
        # blocks of each of the group's query heads in turn
        rows = lambda b, jj, t: (b * group + t // nq, t % nq, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, scale=scale, causal=causal,
                          dropout_p=dropout_p, block_q=block_q,
                          block_k=block_k, group=group),
        grid=(B * Hkv, nk, group * nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), rows),
            pl.BlockSpec((1, block_k, D), lambda b, jj, ii: (b, jj, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, jj, ii: (b, jj, 0)),
            pl.BlockSpec((1, block_q, Dv), rows),
            pl.BlockSpec((1, block_q, 1), rows),
            pl.BlockSpec((1, block_q, 1), rows),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, jj, ii, h=Hkv: (b // h, 0, jj)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, jj, ii: (b, jj, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, jj, ii: (b, jj, 0)),
        ],
        out_shape=[
            _sds((B * Hkv, Sk, D), k.dtype, k3),
            _sds((B * Hkv, Sk, Dv), v.dtype, k3),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=_compiler_params(),
        interpret=_interpret_mode(),
    )(seed, q3, k3, v3, g3, lse, delta, mask)
    return dk.reshape(B, Hkv, Sk, D), dv.reshape(B, Hkv, Sk, Dv)


def _flash_dq_bhsd(q, k, v, g, lse, delta, mask, seed, scale, causal,
                   dropout_p, block_q, block_k):
    """dq for the local queries against one kv chunk (global lse/delta)."""
    B, H, Sq, D = q.shape
    Hkv, Sk, Dv = k.shape[1], k.shape[2], v.shape[-1]
    kv = _kv_block(H // Hkv)
    q3 = q.reshape(B * H, Sq, D)
    k3 = k.reshape(B * Hkv, Sk, D)
    v3 = v.reshape(B * Hkv, Sk, Dv)
    g3 = g.reshape(B * H, Sq, Dv)
    nq, nk = Sq // block_q, Sk // block_k
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, scale=scale, causal=causal,
                          dropout_p=dropout_p, block_q=block_q,
                          block_k=block_k),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv),
            pl.BlockSpec((1, block_k, Dv), kv),
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j, h=H: (b // h, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[_sds((B * H, Sq, D), q.dtype, q3)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=_interpret_mode(),
    )(seed, q3, k3, v3, g3, lse, delta, mask)[0]
    return dq.reshape(B, H, Sq, D)


def _flash_bwd_bhsd(q, k, v, o, lse, g, mask, seed, scale, causal, dropout_p,
                    block_q, block_k):
    B, H, S, D = q.shape
    # delta = rowsum(dO ⊙ O): O(S·D), precomputed once in XLA
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(B * H, S, 1)
    dk, dv = _flash_dkv_bhsd(q, k, v, g, lse, delta, mask, seed, scale,
                             causal, dropout_p, block_q, block_k)
    dq = _flash_dq_bhsd(q, k, v, g, lse, delta, mask, seed, scale, causal,
                        dropout_p, block_q, block_k)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_attention_core(q, k, v, mask, seed, scale, causal, dropout_p,
                          block_q, block_k):
    out, _ = _flash_fwd_bhsd(q, k, v, mask, seed, scale, causal, dropout_p,
                             block_q, block_k)
    return out


def _core_fwd(q, k, v, mask, seed, scale, causal, dropout_p, block_q, block_k):
    out, lse = _flash_fwd_bhsd(q, k, v, mask, seed, scale, causal, dropout_p,
                               block_q, block_k)
    return out, (q, k, v, out, lse, mask, seed)


def _core_bwd(scale, causal, dropout_p, block_q, block_k, res, g):
    q, k, v, o, lse, mask, seed = res
    dq, dk, dv = _flash_bwd_bhsd(q, k, v, o, lse, g, mask, seed, scale,
                                 causal, dropout_p, block_q, block_k)
    return dq, dk, dv, jnp.zeros_like(mask), jnp.zeros_like(seed)


_flash_attention_core.defvjp(_core_fwd, _core_bwd)


# XLA's SPMD partitioner cannot split a Mosaic call ("Mosaic kernels
# cannot be automatically partitioned"), so a GSPMD-jitted step over a
# real TPU mesh has to say how: while it traces under `partitioned_over`
# the kernel runs inside shard_map, split along the two dims attention
# is independent across — batch and heads.
_partition = threading.local()


# the mesh axis the models' partition_specs split the heads over
_HEAD_AXIS = "mp"


@contextlib.contextmanager
def partitioned_over(mesh, batch_axes):
    """While tracing under this context, `flash_attention_bshd` shards its
    kernel over `mesh`: batch over `batch_axes`, heads over 'mp'
    (`distributed.make_sharded_train_step` traces its step here)."""
    prev = getattr(_partition, "spec", None)
    _partition.spec = (mesh, tuple(batch_axes))
    try:
        yield
    finally:
        _partition.spec = prev


def _shard_over(core, spec, B, H, per_shard_seed):
    """`core(q, k, v, mask, seed)` on [B, H, S, D] under shard_map.  An
    axis splits a dim only where it divides it; otherwise every shard
    along that axis computes the dim whole (the operands are replicated
    over it)."""
    mesh, batch_axes = spec
    b = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    if not b or B % math.prod(mesh.shape[a] for a in b):
        b = None
    h = _HEAD_AXIS if mesh.shape.get(_HEAD_AXIS, 1) > 1 else None
    if h is not None and H % mesh.shape[h]:
        h = None
    split = (b or ()) + ((h,) if h else ())

    def body(q, k, v, mask, seed):
        if per_shard_seed and split:
            # the kernel hashes (seed, LOCAL batch*head index, row, col):
            # without this every shard would drop the same positions
            seed = seed + jax.lax.axis_index(split).astype(seed.dtype)
        return core(q, k, v, mask, seed)

    P = PartitionSpec
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(b, h), P(b, h), P(b, h), P(b), P()),
                         out_specs=P(b, h))


def _pad_head_dim(d):
    """MXU-friendly head width: 64 stays, otherwise next multiple of 128."""
    if d <= _LANE // 2:
        return _LANE // 2
    return -(-d // _LANE) * _LANE


def flash_attention_bshd(q, k, v, causal=False, kv_mask=None, dropout_p=0.0,
                         seed=None, block_q=None, block_k=None):
    """Flash attention on [B, S, H, D] arrays (paddle layout). Returns BSHD.

    kv_mask: optional [B, S] validity mask (True/1 = attend) — the padding
    form every BERT-style model produces.  dropout_p: attention-prob dropout
    applied in-kernel with deterministic counter-based bits (`seed`).
    Sequence length and head_dim are padded to kernel-friendly shapes
    internally and sliced back.  v's head size may differ from q/k's; the
    scale is 1/sqrt(q/k head size).  k and v may have fewer heads than q
    (grouped-query attention): query head h reads KV head h // (H // Hkv)
    inside the kernels, and dk / dv come back at the KV heads' count.
    """
    B, S, H, D = q.shape
    Dv, Hkv = v.shape[-1], k.shape[2]
    if H % Hkv or v.shape[2] != Hkv:
        raise ValueError(f"{H} query heads over {Hkv} / {v.shape[2]} KV "
                         f"heads: the KV heads must divide the query heads")
    scale = 1.0 / math.sqrt(D)

    Sp = -(-S // _LANE) * _LANE
    # q/k and v/o are padded each to its own width (latent attention: q/k
    # 192 -> 256, v 128 as it is)
    Dp, Dvp = _pad_head_dim(D), _pad_head_dim(Dv)
    if kv_mask is None:
        mask = jnp.ones((B, Sp), jnp.float32)
        if Sp != S:
            mask = mask.at[:, S:].set(0.0)
    else:
        mask = kv_mask.astype(jnp.float32)
        if Sp != S:
            mask = jnp.pad(mask, ((0, 0), (0, Sp - S)))
    # carried as [B, 1, Sp]: mosaic wants the last-two block dims (1, block_k)
    # to tile the array dims exactly — a 2D (B, Sp) mask with block (1, bk)
    # violates the 8×128 rule when B isn't a multiple of 8
    mask = mask.reshape(B, 1, Sp)
    if Sp != S or Dp != D:
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, Dp - D))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
    if Sp != S or Dvp != Dv:
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, Dvp - Dv)))

    pref_q, pref_k = (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K) \
        if (block_q and block_k) else _resolved_blocks(Sp)
    bq = block_q or _pick_block(pref_q, Sp)
    bk = block_k or _pick_block(pref_k, Sp)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape(-1)[:1]

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    def core(q, k, v, mask, seed):
        return _flash_attention_core(q, k, v, mask, seed, scale, causal,
                                     float(dropout_p), bq, bk)

    spec = getattr(_partition, "spec", None)
    if spec is not None:
        # a head axis must divide the KV heads (which divide the query's)
        core = _shard_over(core, spec, B, Hkv,
                           per_shard_seed=dropout_p > 0.0)
    out = jnp.swapaxes(core(qt, kt, vt, mask, seed), 1, 2)
    if Sp != S or Dvp != Dv:
        out = out[:, :S, :, :Dv]
    return out
