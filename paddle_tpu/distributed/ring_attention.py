"""Ring attention — context/sequence parallelism over a mesh axis.

New capability vs the reference (SURVEY §5.7: no CP/SP exists there).  The
sequence dimension is sharded over the 'sp' mesh axis; each device holds a
local Q/K/V shard and the KV shards rotate around the ring via
jax.lax.ppermute (ICI neighbor exchange) while each device accumulates its
queries' attention with online-softmax merging — full attention over
sequences n_devices× longer than one chip's memory, with communication
overlapped by XLA's async collectives.

Use inside shard_map (see sequence_parallel_attention) or through
paddle_tpu.nn.functional.ring_attention.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

NEG_INF = -1e30


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   use_flash: Optional[bool] = None):
    """Blockwise ring attention.

    q, k, v: local shards [B, S_local, H, D] (BSHD, paddle layout) inside a
    shard_map over `axis_name`. Returns local output shard [B, S_local, H, D].

    Local compute routes through the Pallas flash kernel when S_local is
    kernel-shaped (>=128, divisible by 128) — O(block) memory per ring
    step instead of an S_local×S_local f32 score matrix — with online-
    softmax stats (m/l as logsumexp) carried ACROSS ring steps.  Small /
    odd shapes fall back to the einsum path.
    """
    B, S, H, D = q.shape
    if use_flash is None:
        use_flash = S >= 128 and S % 128 == 0
    if use_flash:
        return _ring_attention_flash(q, k, v, axis_name, causal)
    return _ring_attention_naive(q, k, v, axis_name, causal)


def _ring_attention_naive(q, k, v, axis_name: str, causal: bool = False):
    """einsum fallback (full local score matrix — fine for short shards)."""
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)

    # work in BHSD
    qt = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    # derive initial carries from the data so their shard_map varying-axis
    # types match the loop outputs on any mesh
    zero = jnp.sum(qt * 0.0, axis=-1)  # [B,H,S] varying like qt
    acc0 = qt * 0.0
    m0 = zero + NEG_INF
    l0 = zero

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        acc, m, l, k_cur, v_cur = carry
        src = (my_idx - i) % n  # whose KV shard we hold this round
        s = jnp.einsum("bhqd,bhkd->bhqk", qt, k_cur.astype(jnp.float32))
        if causal:
            q_pos = my_idx * S + jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
            k_pos = src * S + jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc_new, m_new, l_new, k_next, v_next

    acc, m, l, _, _ = jax.lax.fori_loop(0, n, step, (acc0, m0, l0, kt, vt))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash-kernel ring (VERDICT r4 next-round #3): per-chunk Pallas flash
# forward with lse carried across ring steps; custom backward runs two
# counter-rotating rings through the flash dq / dkv kernels.
# ---------------------------------------------------------------------------

def _chunk_stats_fwd(qt, k_cur, v_cur, causal, scale, bq, bk):
    """One ring step's local flash: normalized chunk output + chunk lse.
    qt/k_cur/v_cur BHSD (D already kernel-padded); returns
    (o [B,H,S,D] f32, lse [B,H,S] f32)."""
    from ..ops.pallas_ops.flash_attention import _flash_fwd_bhsd

    B, H, S, D = qt.shape
    mask = jnp.ones((B, 1, S), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
    o, lse = _flash_fwd_bhsd(qt, k_cur, v_cur, mask, seed, scale,
                             causal, 0.0, bq, bk)
    return o.astype(jnp.float32), lse.reshape(B, H, S)


def _pad_d(x):
    """Zero-pad head_dim to the kernel's MXU-friendly width (same rule as
    flash_attention_bshd — interpret mode doesn't care, real mosaic
    lowering does).  Zero pad dims don't change q·k scores and produce
    zero output columns, sliced off by the caller."""
    from ..ops.pallas_ops.flash_attention import _pad_head_dim

    D = x.shape[-1]
    Dp = _pad_head_dim(D)
    if Dp == D:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, Dp - D)]
    return jnp.pad(x, pad)


def _ring_blocks(S):
    from ..ops.pallas_ops.flash_attention import (_pick_block,
                                                  DEFAULT_BLOCK_K,
                                                  DEFAULT_BLOCK_Q)

    return (_pick_block(DEFAULT_BLOCK_Q, S), _pick_block(DEFAULT_BLOCK_K, S))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_attention_flash(q, k, v, axis_name, causal):
    out, _ = _ring_flash_fwd(q, k, v, axis_name, causal)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal):
    """Ring of per-chunk flash calls.  Chunk visibility under causal
    masking is STATIC per step (only step 0 touches the diagonal; step
    i>=1 sees a chunk that is fully past — visible — iff i <= my_index),
    so each step uses a statically-shaped kernel and invisible chunks
    are dropped at the lse merge."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)  # scale from the REAL head width, pre-pad
    bq, bk = _ring_blocks(S)
    qt = _pad_d(jnp.swapaxes(q, 1, 2))
    kt = _pad_d(jnp.swapaxes(k, 1, 2))
    vt = _pad_d(jnp.swapaxes(v, 1, 2))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(o, lse, o_i, lse_i):
        # merge normalized partials in lse space (the kernel's online
        # softmax lifted to ring steps).  Step 0 is the self chunk
        # (diagonal visible), so lse is finite for every row before any
        # masked chunk arrives; a dropped chunk's weight underflows to 0.
        m = jnp.maximum(lse, lse_i)
        w0 = jnp.exp(lse - m)
        w1 = jnp.exp(lse_i - m)
        den = jnp.maximum(w0 + w1, 1e-30)
        o = (o * w0[..., None] + o_i * w1[..., None]) / den[..., None]
        return o, m + jnp.log(den)

    # step 0: self chunk (diagonal)
    o, lse = _chunk_stats_fwd(qt, kt, vt, causal, scale, bq, bk)
    k_cur = jax.lax.ppermute(kt, axis_name, perm)
    v_cur = jax.lax.ppermute(vt, axis_name, perm)
    for i in range(1, n):
        o_i, lse_i = _chunk_stats_fwd(qt, k_cur, v_cur, False, scale,
                                      bq, bk)
        if causal:
            lse_i = jnp.where(i <= my, lse_i, NEG_INF)
        o, lse = merge(o, lse, o_i, lse_i)
        if i < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
    out = jnp.swapaxes(o[..., :D], 1, 2).astype(q.dtype)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, res, g):
    from ..ops.pallas_ops.flash_attention import (_flash_dkv_bhsd,
                                                  _flash_dq_bhsd)

    q, k, v, out, lse = res
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    bq, bk = _ring_blocks(S)
    qt = _pad_d(jnp.swapaxes(q, 1, 2))
    kt = _pad_d(jnp.swapaxes(k, 1, 2))
    vt = _pad_d(jnp.swapaxes(v, 1, 2))
    ot = _pad_d(jnp.swapaxes(out, 1, 2))
    do = _pad_d(jnp.swapaxes(g, 1, 2).astype(qt.dtype))
    # global per-row stats (delta = rowsum(dO ⊙ O)); lse is already global
    delta = jnp.sum(do.astype(jnp.float32) * ot.astype(jnp.float32),
                    axis=-1).reshape(B * H, S, 1)
    lse3 = lse.reshape(B * H, S, 1)
    mask = jnp.ones((B, 1, S), jnp.float32)
    seed = jnp.zeros((1,), jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # step 0: self chunk (diagonal) — both directions locally
    dq = _flash_dq_bhsd(qt, kt, vt, do, lse3, delta, mask, seed, scale,
                        causal, 0.0, bq, bk).astype(jnp.float32)
    dk_i, dv_i = _flash_dkv_bhsd(qt, kt, vt, do, lse3, delta, mask, seed,
                                 scale, causal, 0.0, bq, bk)
    dk = dk_i.astype(jnp.float32)
    dv = dv_i.astype(jnp.float32)

    k_cur = jax.lax.ppermute(kt, axis_name, perm)
    v_cur = jax.lax.ppermute(vt, axis_name, perm)
    q_vis = jax.lax.ppermute(qt, axis_name, perm)
    do_vis = jax.lax.ppermute(do, axis_name, perm)
    lse_vis = jax.lax.ppermute(lse3, axis_name, perm)
    delta_vis = jax.lax.ppermute(delta, axis_name, perm)
    for i in range(1, n):
        # dq: my queries × visiting kv chunk.  Under causal masking the
        # chunk from step i>=1 is fully past (visible) iff i <= my.
        dq_i = _flash_dq_bhsd(qt, k_cur, v_cur, do, lse3, delta, mask,
                              seed, scale, False, 0.0, bq, bk)
        # dk/dv: visiting queries (from device (my-i) mod n) × my kv.
        # Those queries see my kv fully iff they are globally after it,
        # i.e. iff i > my (the wrap case) — complement of the dq side.
        dk_i, dv_i = _flash_dkv_bhsd(q_vis, kt, vt, do_vis, lse_vis,
                                     delta_vis, mask, seed, scale, False,
                                     0.0, bq, bk)
        if causal:
            dq_i = jnp.where(i <= my, dq_i, 0)
            dk_i = jnp.where(i > my, dk_i, 0)
            dv_i = jnp.where(i > my, dv_i, 0)
        dq = dq + dq_i.astype(jnp.float32)
        dk = dk + dk_i.astype(jnp.float32)
        dv = dv + dv_i.astype(jnp.float32)
        if i < n - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            q_vis = jax.lax.ppermute(q_vis, axis_name, perm)
            do_vis = jax.lax.ppermute(do_vis, axis_name, perm)
            lse_vis = jax.lax.ppermute(lse_vis, axis_name, perm)
            delta_vis = jax.lax.ppermute(delta_vis, axis_name, perm)
    to_bshd = lambda x, ref: jnp.swapaxes(x[..., :D], 1, 2).astype(ref.dtype)
    return to_bshd(dq, q), to_bshd(dk, k), to_bshd(dv, v)


_ring_attention_flash.defvjp(
    lambda q, k, v, axis_name, causal: _ring_flash_fwd(q, k, v, axis_name,
                                                       causal),
    _ring_flash_bwd)


def sequence_parallel_attention(q, k, v, mesh=None, axis_name: str = "sp",
                                causal: bool = False):
    """Whole-array entry: q/k/v are global [B, S, H, D]; runs ring attention
    with S sharded over `axis_name` of the (global) mesh."""
    from .mesh import get_mesh
    from jax import shard_map

    mesh = mesh or get_mesh()
    spec = PartitionSpec(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                      local_attention=None):
    """DeepSpeed-Ulysses style SP: all-to-all scatters heads / gathers
    sequence so each device runs FULL-sequence attention on H/n heads, then
    all-to-all back.  Complements ring attention (better for moderate S,
    head-divisible meshes).  Call inside shard_map with S sharded over
    axis_name; q/k/v local [B, S_local, H, D]."""
    n = jax.lax.psum(1, axis_name)
    B, S, H, D = q.shape

    def a2a(x, split_axis, concat_axis):
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    # heads→devices, gather sequence: [B, S_loc, H, D] -> [B, S_full, H/n, D]
    qh = a2a(q, 2, 1)
    kh = a2a(k, 2, 1)
    vh = a2a(v, 2, 1)
    if local_attention is None:
        from ..ops.attention import _sdpa_core

        qs = jnp.swapaxes(qh, 1, 2)
        ks = jnp.swapaxes(kh, 1, 2)
        vs = jnp.swapaxes(vh, 1, 2)
        o = _sdpa_core(qs, ks, vs, None, 0.0, causal, None)
        o = jnp.swapaxes(o, 1, 2)
    else:
        o = local_attention(qh, kh, vh, causal)
    # sequence→devices, gather heads back
    return a2a(o, 1, 2)
