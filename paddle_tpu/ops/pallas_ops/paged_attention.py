"""Ragged paged-attention Pallas kernel (TPU) — decode-time attention over
a block-paged KV cache.

Kernel recipe after "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (PAPERS.md): each in-flight
sequence owns a *page table* — a row of page ids into a global pool of
fixed-size KV pages — and attention streams exactly the pages a sequence
owns, masked to its true (ragged) length.  Kept deliberately small and
composable (Tensor Processing Primitives style) next to
``flash_attention.py``: ONE kernel body (``_ragged_body``) serves every
form — a lane of Q query rows against its page-table row, online-softmax
accumulation one group of pages at a time; decode is Q = 1, the int8 and
the mesh (stats) forms are flags of the same body.

TPU mechanics: ``pltpu.PrefetchScalarGridSpec`` prefetches the page
tables + sequence lengths into SMEM so the BlockSpec ``index_map`` can
pick which physical KV pages to DMA for grid cell (g, i) — the kernel
never materializes a gathered [B, S, H, D] KV copy (the XLA fallback
below does exactly that, which is why it loses at scale).

A grid step does a lane's LIVE work, along the pages and along the rows
(ISSUE 29).  Pages: one step covers ``pages_per_step`` pages (8 of 16
positions: a [rows, 128] score block, one lane tile); a group entirely
past the lane's longest row is skipped with ``pl.when`` and — its table
entries re-aimed at the blocks already resident — moves no bytes.  Rows:
a lane carries the step's whole row bucket, but is computed only up to
the row block that covers its last live row (rows 0-7, or all of them):
a decode lane riding a 64-row mixed step costs 8 rows.  So cost follows
real tokens up to those two roundings — a lane pays for its context
rounded up to a page group and its rows rounded up to a row block — and
not the padded page count or the row bucket.  On the v5e at the serve
cell's shape (48 lanes x 64 rows, 39 of them decode lanes, 32-63 live
pages a lane) a call takes 0.85 ms where one page a step over all 64
rows took 4.94.

Pool layout (ISSUE 26): the pools are stored ``[N, P, H*D]`` — heads
and head_dim fused in one row — and the kernel's page block is that
row-major page itself, ``(1, P, H*D)``: tile-exact for every (H, D), so
the stored bytes are the logical bytes and NOTHING of pool size is
padded, transposed or copied on the way in (a per-head ``[N, P, 12, 64]``
pool cost GPT-2 small two relayouts and a pad of every pool in every
step, 55% of the step on the v5e).  q and o ride in the same fused-row
layout; the body walks the row in 128-lane windows (``_lane_groups``),
two D = 64 heads to a window.  Every entry point
below takes pools in this layout and refuses any other.

Page-table convention (shared with serving/kv_cache.py): page id 0 is a
reserved trash page — padding entries point at it and masked/inactive
lanes scatter into it — so every page-table entry is always a valid
index and the kernel needs no bounds checks.  What the trash page HOLDS
never reaches the arithmetic: the launcher re-aims every entry past a
lane's longest row (or not owned by the shard) at a live page of the
same lane, whose columns are masked (``_live_page_tables``).

Quantized KV (the int8 serving path): when the page pools are int8 the
caller passes per-page-per-head fp32 scale arrays ``k_scales`` /
``v_scales`` ([N, H]); the kernel DMAs the page's scale row alongside
the page and dequantizes IN-REGISTER (each lane window of the int8 page
is converted to f32 as it is loaded) — the q·k logits pick up each
page's K scale as a per-head multiply of its columns after the dot, the
probabilities its V scale the same way before theirs, so HBM streams 1
byte per KV element instead of 2 and the f32 softmax math is unchanged.
Layout and the write-time quantization live in serving/kv_cache.py and
text/generation.py.

CPU story: interpret mode runs the very same kernel under
``JAX_PLATFORMS=cpu`` (tier-1 tests); the default CPU *routing* choice
is the exact XLA gather reference, the kernel is forced with
``PADDLE_TPU_FORCE_PAGED=1``.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .contracts import (PAGED_RAGGED, PAGED_RAGGED_INT8,
                        PAGED_RAGGED_STATS)

NEG_INF = -1e30

# constants from the declared KernelContract (contracts.py): the lane
# width the fused row is walked in, and the padding floor of the
# per-lane query-row dim (the ONLY thing the wrapper pads) — the
# pallas-contract lint checks the same values the kernel runs with
_LANE = PAGED_RAGGED.dim("lane")
_RAGGED_Q_ALIGN = PAGED_RAGGED.dim("q_align")
_RAGGED_PAGES_PER_STEP = PAGED_RAGGED.dim("pages_per_step")
_RAGGED_FUSED_DEQUANT = PAGED_RAGGED_INT8.dim("fused_dequant")
# mesh-aware head-shard stats form (ISSUE 19)
_STATS_Q_ALIGN = PAGED_RAGGED_STATS.dim("q_align")
_STATS_PAGES_PER_STEP = PAGED_RAGGED_STATS.dim("pages_per_step")


def _ragged_resolved_dims(H, D, quantized):
    """(q_align, fused_dequant, pages_per_step) for a ragged-query call:
    tuning-table hit (validate()-gated at the (heads, head_dim) shape
    bucket) -> contract default.  With no table installed this is a
    single None check."""
    from ...tune.runtime import lookup_dims

    contract = PAGED_RAGGED_INT8 if quantized else PAGED_RAGGED
    tuned = lookup_dims(contract, {"heads": H, "head_dim": D},
                        dtype="int8" if quantized else "float32") or {}
    return (tuned.get("q_align", _RAGGED_Q_ALIGN),
            bool(tuned.get("fused_dequant", _RAGGED_FUSED_DEQUANT)),
            tuned.get("pages_per_step", _RAGGED_PAGES_PER_STEP))

# trace-time routing telemetry, mirroring ops/attention.py ROUTE_STATS
PAGED_ROUTE_STATS = {"pallas": 0, "xla": 0}


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def paged_attention_kernel(q, k_pages, v_pages, page_tables, seq_lens,
                           k_scales=None, v_scales=None, *, interpret=None,
                           fused_dequant=None, pages_per_step=None):
    """One decode query per sequence — the ragged-query kernel at Q = 1
    (the same computation: one query row per lane against the lane's
    page-table row), so there is one kernel body to compile.

    q           [B, H, D]   one decode query per sequence
    k_pages     [N, P, H*D] global K page pool (page_size = P), stored
                             with heads and head_dim fused in one row
    v_pages     [N, P, H*D] global V page pool
    page_tables [B, M] int32 page ids per sequence (pad with 0)
    seq_lens    [B] int32    valid KV length per sequence (0 = inactive)
    k_scales    [N, H] fp32  per-page-per-head K dequant scales
                             (required iff k_pages is int8)
    v_scales    [N, H] fp32  per-page-per-head V dequant scales

    Returns [B, H, D]; softmax scale 1/sqrt(D) is applied internally.
    """
    return ragged_paged_attention_kernel(
        q[:, None], k_pages, v_pages, page_tables, seq_lens[:, None],
        k_scales, v_scales, interpret=interpret,
        fused_dequant=fused_dequant, pages_per_step=pages_per_step)[:, 0]


def paged_attention_xla(q, k_pages, v_pages, page_tables, seq_lens,
                        k_scales=None, v_scales=None):
    """Exact XLA reference: gather the sequence's pages (stored
    [N, P, H*D]) into a dense [B, M*P, H, D] view — the split of the
    fused row touches the gathered pages only, never the pool — and run
    masked attention.  O(B·M·P·H·D) memory
    traffic per decode step — the thing the kernel exists to avoid — but
    bit-exact f32 softmax math, so it is the default CPU route.  Int8
    pages are dequantized after the gather with their per-page-per-head
    scales (same math as the kernel's in-register dequant)."""
    B, H, D = q.shape
    page_size = k_pages.shape[1]
    M = page_tables.shape[1]
    S = M * page_size
    k = k_pages[page_tables].reshape(B, S, H, D)
    v = v_pages[page_tables].reshape(B, S, H, D)
    if k_pages.dtype == jnp.int8:
        if k_scales is None or v_scales is None:
            raise ValueError("int8 KV pages require k_scales/v_scales")
        ks = k_scales[page_tables]                     # [B, M, H]
        vs = v_scales[page_tables]
        ks = jnp.repeat(ks, page_size, axis=1)         # [B, S, H]
        vs = jnp.repeat(vs, page_size, axis=1)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = jnp.arange(S)[None, None, :] < seq_lens[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    # empty lanes: all-masked softmax is uniform garbage -> pin to 0 to
    # match the kernel's zero-initialised accumulator
    ctx = jnp.where(seq_lens[:, None, None] > 0, ctx, 0.0)
    return ctx.astype(q.dtype)


def paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                    k_scales=None, v_scales=None):
    """Routing entry (the serving decode step calls this): Pallas kernel
    on TPU (or when PADDLE_TPU_FORCE_PAGED=1 forces interpret mode for
    tests), exact XLA gather reference elsewhere.  Pass per-page-per-head
    ``k_scales``/``v_scales`` ([N, H] fp32) when the page pools are int8."""
    forced = os.environ.get("PADDLE_TPU_FORCE_PAGED") == "1"
    if forced or jax.default_backend() == "tpu":
        PAGED_ROUTE_STATS["pallas"] += 1
        return paged_attention_kernel(q, k_pages, v_pages, page_tables,
                                      seq_lens, k_scales, v_scales)
    PAGED_ROUTE_STATS["xla"] += 1
    return paged_attention_xla(q, k_pages, v_pages, page_tables, seq_lens,
                               k_scales, v_scales)


# ===========================================================================
# Unified ragged-QUERY paged attention (ISSUE 18, PAPERS.md [1]).
#
# One grid group = one serving lane carrying Qb query rows that share a
# single page-table row: a decode lane uses 1 real row, a chunked-
# prefill lane up to ``prefill_chunk`` rows, a spec-verify lane K rows.
# The page DMA (and its scale rows on the int8 path) is paid ONCE per
# lane per page instead of once per query row, and one dispatch carries
# a mixed batch of all three lane kinds — the engine's separate
# prefill/decode/spec programs collapse onto this kernel.
#
# Raggedness is per ROW, not per lane: ``row_lens[g, r]`` is row r's own
# causal KV horizon (its absolute position + 1), so prefill rows within
# one chunk see staircase masks while the lane streams each page once.
# Padded rows carry row_len 0 and write exact zeros.
# ===========================================================================


def _lane_groups(H, D):
    """Static ``(lo, hi)`` lane windows that tile the fused ``H*D`` row.

    A head narrower than the 128-lane tile shares a window with its
    neighbours (GPT-2's D = 64: two heads a window), so every load,
    store and dot operand in the body starts on a lane-tile boundary
    and is a whole tile wide; a head of one or more whole lane tiles is
    its own window."""
    W = _LANE if (D < _LANE and _LANE % D == 0) else D
    return [(lo, min(lo + W, H * D)) for lo in range(0, H * D, W)]


def _row_ends(Qp, q_align):
    """Static row-block boundaries of a ``Qp``-row lane: ``q_align`` and
    ``Qp`` — ``(8, 64)`` for a 64-row mixed step, ``(8,)`` for the decode
    entry and every row bucket up to ``q_align`` (one block, no branch).
    A lane is computed up to the first boundary that covers its live-row
    extent; the row blocks past it do no work and are written as zeros."""
    return (q_align, Qp) if Qp > q_align else (Qp,)


def ragged_rows_skipped(live_rows, rows):
    """Rows of a ``rows``-row bucket the kernel does NOT compute for a
    lane whose last live row is row ``live_rows - 1``: the bucket beyond
    the lane's extent rounded up to its row block (``_row_ends``, at the
    contract's ``q_align``).  Host arithmetic for the engine's
    ``attn_rows_skipped`` counter — the same rule the kernel body
    branches on."""
    Qp = -(-rows // _RAGGED_Q_ALIGN) * _RAGGED_Q_ALIGN
    return Qp - next(r for r in _row_ends(Qp, _RAGGED_Q_ALIGN)
                     if live_rows <= r)


def _ragged_body(*refs, scale, page_size, pages_per_step, row_ends, heads,
                 head_dim, quantized, stats, fused_dequant):
    """Grid (G, page groups), groups innermost: per lane g the body
    visits the lane's pages ``pages_per_step`` at a time, keeping
    flash-style running max/denominator per (head, query row) in VMEM
    scratch; the pages to DMA were chosen by the index_maps from the
    prefetched page table, one BlockSpec per page of the group.

    One body serves every form (``quantized``: int8 pages + scale rows;
    ``stats``: page-ownership mask + lse output) because each is the same
    group step over the pages AS STORED — ``[P, H*D]`` tiles, q and o in
    the same fused-row layout ``[Qp, H*D]``, so nothing is padded,
    transposed or strided:

    - the fused row is walked in static lane windows (``_lane_groups``);
      a window's k/v slabs are plain aligned slices of the page tiles,
      loaded (and converted to f32) once per window and stacked into ONE
      ``[n*P, W]`` operand — a group of 8 pages of 16 makes the score
      block ``[rows, 128]``, a whole lane tile, and the online-softmax
      bookkeeping is paid once per group instead of once per page;
    - heads are a STATIC loop of 2-D matmuls inside their window
      ([R, W] x [W, n*P] and [R, n*P] x [n*P, W]).  Where a window holds
      several heads (D < 128), head h's query is staged ONCE per lane
      (``q_sc``: scaled, the other heads' lanes zeroed — they add exact
      zeros to q.k) and its accumulator is window-wide, the other
      heads' lanes of ``p @ v`` riding along unread until the final
      write picks each head's own lanes;
    - rows: the lane's prefetched live-row extent (``ext_ref``) picks ONE
      static row count R from ``row_ends`` — init, group step and final
      write touch rows ``[0, R)`` only, rows ``[R, Qp)`` are written as
      exact zeros (lse NEG_INF).  A decode lane in a 64-row mixed step
      costs 8 rows, not 64; with one boundary (``Qp == q_align``) there
      is no branch at all.

    The group early-out keys on the lane's LONGEST row (``gl_ref``; in
    the stats form also on the group holding an owned page); rows
    shorter than that mask the tail per row, and a page the shard does
    not own masks its columns.  The wrapper re-aims every dead
    page-table entry at a live page of the same lane, so a partly live
    group multiplies ``p == 0`` by a live page's V, never by the trash
    page's.  A row fully masked on a live group keeps m == NEG_INF, so
    probabilities are re-masked AFTER the exp (exp(NEG_INF - NEG_INF)
    == 1 would otherwise corrupt l)."""
    # refs arrive as: scalar prefetch, inputs, outputs, scratch — the
    # optional ones present only in the form that uses them
    n = pages_per_step
    it = iter(refs)
    # the page table itself is read by the index_maps only
    _pt_ref, gl_ref, ext_ref = next(it), next(it), next(it)
    ok_ref = next(it) if stats else None
    rl_ref, q_ref = next(it), next(it)
    k_refs = [next(it) for _ in range(n)]
    v_refs = [next(it) for _ in range(n)]
    ks_refs = [next(it) for _ in range(n)] if quantized else None
    vs_refs = [next(it) for _ in range(n)] if quantized else None
    o_ref = next(it)
    lse_ref = next(it) if stats else None
    q_sc, acc_sc, m_sc, l_sc = (next(it) for _ in range(4))

    g = pl.program_id(0)
    i = pl.program_id(1)
    D = head_dim
    Qp = q_sc.shape[1]
    span = n * page_size                     # KV positions a grid step
    groups = _lane_groups(heads, D)

    def for_live_rows(fn):
        """Run ``fn(R)`` with R the first of ``row_ends`` that covers the
        lane's live-row extent."""
        if len(row_ends) == 1:
            return fn(row_ends[0])
        first, whole = row_ends
        short = ext_ref[g] <= first
        pl.when(short)(functools.partial(fn, first))
        pl.when(jnp.logical_not(short))(functools.partial(fn, whole))

    def own_lanes(lo, hi, R):
        """Per head of the window [lo, hi): (head, the [R, hi-lo] mask
        of its own lanes — None where the window is one head)."""
        first = lo // D
        if hi - lo == D:
            return [(first, None)]
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, hi - lo), 1)
        return [(first + j, (lane >= j * D) & (lane < (j + 1) * D))
                for j in range((hi - lo) // D)]

    def init_rows(R):
        acc_sc[:, :R] = jnp.zeros((heads, R) + acc_sc.shape[2:],
                                  jnp.float32)
        m_sc[:, :R] = jnp.full((heads, R, _LANE), NEG_INF, jnp.float32)
        l_sc[:, :R] = jnp.zeros((heads, R, _LANE), jnp.float32)
        for lo, hi in groups:
            qw = q_ref[0, :R, lo:hi].astype(jnp.float32) * scale
            for h, own in own_lanes(lo, hi, R):
                q_sc[h, :R, :hi - lo] = (
                    qw if own is None else jnp.where(own, qw, 0.0))

    @pl.when(i == 0)
    def _init():
        for_live_rows(init_rows)

    # ragged early-out: groups entirely past the lane's longest row (and,
    # in the stats form, groups with no page this shard owns) do no work
    live = i * span < gl_ref[g]
    if stats:
        oks = [ok_ref[g, i * n + j] != 0 for j in range(n)]
        live = live & functools.reduce(jnp.logical_or, oks)

    def per_page(values):
        """The [1, n*P] row vector holding ``values[j]`` over the columns
        of page j of the group."""
        col = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        out = jnp.broadcast_to(values[0], (1, span))
        for j in range(1, n):
            out = jnp.where(col >= j * page_size, values[j], out)
        return out

    def step_rows(R):
        rl = rl_ref[0, :R]                                # [R, 1] int32
        pos = i * span + jax.lax.broadcasted_iota(
            jnp.int32, (1, span), 1)
        if stats:
            # a page this shard does not own is past every row's horizon
            far = jnp.iinfo(jnp.int32).max
            pos = jnp.maximum(pos, per_page(
                [jnp.where(ok, 0, far) for ok in oks]))
        valid = pos < rl                                  # [R, n*P]
        if quantized:
            ks_rows = [r[0] for r in ks_refs]             # n x [1, H] f32
            vs_rows = [r[0] for r in vs_refs]
        for lo, hi in groups:
            W = hi - lo
            kslabs = [r[0, :, lo:hi].astype(jnp.float32) for r in k_refs]
            vslabs = [r[0, :, lo:hi].astype(jnp.float32) for r in v_refs]
            kw = jnp.concatenate(kslabs, axis=0)          # [n*P, W]
            vw = jnp.concatenate(vslabs, axis=0)
            for h in range(lo // D, hi // D):
                q = q_sc[h, :R, :W]                       # [R, W]
                k, v = kw, vw
                if quantized:
                    ks = [r[:, h:h + 1] for r in ks_rows]  # n x [1, 1]
                    vs = [r[:, h:h + 1] for r in vs_rows]
                    if not fused_dequant:                 # dequant pre-dot
                        k = jnp.concatenate(
                            [a * b for a, b in zip(kslabs, ks)], axis=0)
                        v = jnp.concatenate(
                            [a * b for a, b in zip(vslabs, vs)], axis=0)
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if quantized and fused_dequant:
                    s = s * per_page(ks)                  # dequant K
                s = jnp.where(valid, s, NEG_INF)          # [R, n*P]
                m_prev = m_sc[h, :R, :1]                  # [R, 1]
                l_prev = l_sc[h, :R, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                if quantized and fused_dequant:
                    p = p * per_page(vs)                  # dequant V
                ctx = jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)   # [R, W]
                acc_sc[h, :R, :W] = acc_sc[h, :R, :W] * alpha + ctx
                # column 0 carries the value (a masked store; filling
                # the lane tile cost 15% of the kernel on the v5e)
                m_sc[h, :R, :1] = m_new
                l_sc[h, :R, :1] = l_new

    @pl.when(live)
    def _step():
        for_live_rows(step_rows)

    def write_rows(R):
        for lo, hi in groups:
            out = None
            for h, own in own_lanes(lo, hi, R):
                # rows with row_len == 0 (padding) have l == 0 -> zeros
                l_cur = l_sc[h, :R, :1]
                l_safe = jnp.maximum(l_cur, 1e-30)
                o_h = acc_sc[h, :R, :hi - lo] / l_safe
                out = o_h if out is None else jnp.where(own, o_h, out)
                if stats:
                    # a row with NO owned/visible positions keeps l == 0:
                    # lse is NEG_INF so the merge weight exp(lse - M)
                    # underflows
                    lse_ref[0, h, :R] = jnp.where(
                        l_cur > 0, m_sc[h, :R, :1] + jnp.log(l_safe),
                        NEG_INF)
            o_ref[0, :R, lo:hi] = out.astype(o_ref.dtype)
        if R < Qp:
            # the row blocks past the lane's extent: every row_len is 0
            o_ref[0, R:] = jnp.zeros((Qp - R, o_ref.shape[2]), o_ref.dtype)
            if stats:
                lse_ref[0, :, R:] = jnp.full((heads, Qp - R, 1), NEG_INF,
                                             jnp.float32)

    @pl.when(i == pl.num_programs(1) - 1)
    def _write():
        for_live_rows(write_rows)


def _live_page_tables(page_tables, group_lens, page_ok, page_size, n):
    """The page table the index_maps read, ``[G, M']`` with ``M'`` the
    width rounded up to ``n`` pages a grid step (pad 0 — a [G, M] int32
    array, never a pool): every DEAD entry — past the lane's longest row,
    or not owned by this shard — is re-aimed at a live entry of the same
    lane.  Which one: the entry its slot of the group held the last time
    it was live, so the block index does not change and the pipeline
    issues no DMA for it (the trailing dead groups of a lane move no
    bytes at all); else the lane's first live entry.  The kernel then
    never dereferences a dead entry of a lane that has a live one: what
    the trash page holds cannot reach the arithmetic."""
    G, M = page_tables.shape
    pad = -M % n
    pt = jnp.pad(page_tables.astype(jnp.int32), ((0, 0), (0, pad)))
    idx = jnp.arange(M + pad, dtype=jnp.int32)
    live = idx[None, :] * page_size < group_lens[:, None]
    ok = None
    if page_ok is not None:
        ok = jnp.pad(page_ok.astype(jnp.int32), ((0, 0), (0, pad)))
        live = live & (ok != 0)
    src = jnp.where(live, idx[None, :], -1).reshape(G, -1, n)
    src = jax.lax.cummax(src, axis=1).reshape(G, M + pad)
    first = jnp.argmax(live, axis=1).astype(jnp.int32)
    src = jnp.where(src < 0, first[:, None], src)
    return jnp.take_along_axis(pt, src, axis=1), ok


@functools.partial(jax.jit, static_argnames=(
    "interpret", "q_align", "fused_dequant", "pages_per_step"))
def _ragged_call(q, k_pages, v_pages, page_tables, row_lens, page_ok,
                 k_scales, v_scales, *, interpret, q_align,
                 fused_dequant, pages_per_step):
    """Lay out and launch ``_ragged_body`` on the pools AS STORED
    (``[N, P, H*D]``: the page block is a whole tile, nothing of pool
    size is padded, transposed or copied); returns ``(out, lse)`` with
    ``lse`` None unless ``page_ok`` selects the stats form.

    Jitted so that the layers of a step program, which call it with the
    same shapes, share ONE trace and lowering of the body (unrolled over
    heads, row blocks and pages: a twelve-layer step program lowers in
    1.2 s so, in 6.7 s with a trace a layer)."""
    G, Qb, H, D = q.shape
    HD = H * D
    if k_pages.shape[2:] != (HD,) or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"KV pools must be stored [pages, page_size, heads*head_dim] "
            f"= [N, P, {H * D}] for q {q.shape}; got {k_pages.shape} / "
            f"{v_pages.shape}")
    page_size = k_pages.shape[1]
    quantized = k_pages.dtype == jnp.int8
    stats = page_ok is not None
    if quantized and (k_scales is None or v_scales is None):
        raise ValueError("int8 KV pages require k_scales/v_scales")
    scale = 1.0 / math.sqrt(D)
    row_lens = row_lens.astype(jnp.int32)

    # q rides in the pools' own fused-row layout (the projection's
    # [rows, hidden] output, viewed per lane); only the query-row dim is
    # padded, to the contract floor — padded rows carry row_len 0 and
    # are sliced off
    Qp = -(-Qb // q_align) * q_align
    q = q.reshape(G, Qb, HD)
    if Qp != Qb:
        q = jnp.pad(q, ((0, 0), (0, Qp - Qb), (0, 0)))
        row_lens = jnp.pad(row_lens, ((0, 0), (0, Qp - Qb)))
    # the lane's page early-out keys on its longest row, its row blocks
    # on the extent of its live rows (index of the last one, plus one)
    group_lens = jnp.max(row_lens, axis=1)
    live_rows = jnp.max(
        jnp.where(row_lens > 0, jnp.arange(1, Qp + 1, dtype=jnp.int32), 0),
        axis=1)
    # a table narrower than a group is one group: a lane of 3 live pages
    # does one grid step, not ``pages_per_step`` single ones
    n = max(1, min(int(pages_per_step), page_tables.shape[1]))
    page_tables, page_ok = _live_page_tables(page_tables, group_lens,
                                             page_ok, page_size, n)

    def lane(*tail):
        return lambda g, i, *prefetch: (g,) + tail

    def page(j):
        return lambda g, i, pt, *prefetch: (pt[g, i * n + j], 0, 0)

    # every trailing-dims pair below is (8k, 128k) or the whole array
    # extent, the rule the TPU lowering enforces: row_lens rides as
    # [G, Qp, 1], the scale rows as [N, 1, H].  Each pool is passed once
    # per page of the group (the same array, no copy), each with the
    # index_map of its slot
    pages = [pl.BlockSpec((1, page_size, HD), page(j)) for j in range(n)]
    in_specs = [
        pl.BlockSpec((1, Qp, 1), lane(0, 0)),
        pl.BlockSpec((1, Qp, HD), lane(0, 0)),
    ] + pages + pages
    operands = [row_lens[:, :, None], q] + [k_pages] * n + [v_pages] * n
    if quantized:
        # the scale rows ride the same page-table index_maps as the pages
        rows = [pl.BlockSpec((1, 1, H), page(j)) for j in range(n)]
        in_specs += rows + rows
        operands += ([k_scales.astype(jnp.float32)[:, None, :]] * n
                     + [v_scales.astype(jnp.float32)[:, None, :]] * n)
    out_specs = [pl.BlockSpec((1, Qp, HD), lane(0, 0))]
    out_shape = [jax.ShapeDtypeStruct((G, Qp, HD), q.dtype)]
    if stats:
        out_specs.append(pl.BlockSpec((1, H, Qp, 1), lane(0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((G, H, Qp, 1), jnp.float32))
    # per head: the staged query and the accumulator, one lane window
    # wide; the running max / denominator, in column 0 of a lane tile
    W = max(hi - lo for lo, hi in _lane_groups(H, D))
    scratch_shapes = [
        pltpu.VMEM((H, Qp, W), jnp.float32),
        pltpu.VMEM((H, Qp, W), jnp.float32),
        pltpu.VMEM((H, Qp, _LANE), jnp.float32),
        pltpu.VMEM((H, Qp, _LANE), jnp.float32),
    ]
    prefetch = [page_tables, group_lens, live_rows]
    if stats:
        prefetch.append(page_ok)

    outs = pl.pallas_call(
        functools.partial(_ragged_body, scale=scale, page_size=page_size,
                          pages_per_step=n,
                          row_ends=_row_ends(Qp, q_align), heads=H,
                          head_dim=D, quantized=quantized, stats=stats,
                          fused_dequant=fused_dequant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(G, page_tables.shape[1] // n),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*prefetch, *operands)
    out = outs[0][:, :Qb].reshape(G, Qb, H, D)
    if not stats:
        return out, None
    return out, jnp.swapaxes(outs[1][..., 0], 1, 2)[:, :Qb]


def ragged_paged_attention_kernel(q, k_pages, v_pages, page_tables,
                                  row_lens, k_scales=None, v_scales=None,
                                  *, interpret=None, q_align=None,
                                  fused_dequant=None, pages_per_step=None):
    """The ragged-query Pallas kernel proper (interpret mode off-TPU
    unless forced).

    q           [G, Qb, H, D]  Qb query rows per lane (decode lane: row 0
                               real, rest padded; prefill lane: chunk
                               rows; spec-verify lane: K rows)
    k_pages     [N, P, H*D]    global K page pool, as stored
    v_pages     [N, P, H*D]    global V page pool
    page_tables [G, M] int32   ONE page-table row per lane (pad with 0)
    row_lens    [G, Qb] int32  per-ROW causal KV horizon (row's absolute
                               position + 1; 0 = padded/inactive row)
    k_scales    [N, H] fp32    per-page-per-head K scales (iff int8)
    v_scales    [N, H] fp32    per-page-per-head V scales

    Returns [G, Qb, H, D]; softmax scale 1/sqrt(D) applied internally.
    ``q_align``/``fused_dequant``/``pages_per_step`` resolve explicit
    argument > tuning-table hit > contract default.
    """
    H, D = q.shape[2:]
    quantized = k_pages.dtype == jnp.int8
    if None in (q_align, pages_per_step) or (quantized
                                             and fused_dequant is None):
        t_q, t_fused, t_pages = _ragged_resolved_dims(H, D, quantized)
        q_align = t_q if q_align is None else q_align
        fused_dequant = t_fused if fused_dequant is None else fused_dequant
        pages_per_step = t_pages if pages_per_step is None \
            else pages_per_step
    if interpret is None:
        interpret = _interpret_mode()
    return _ragged_call(q, k_pages, v_pages, page_tables, row_lens, None,
                        k_scales, v_scales, interpret=interpret,
                        q_align=q_align, fused_dequant=bool(fused_dequant),
                        pages_per_step=pages_per_step)[0]


def ragged_paged_attention_xla(q, k_pages, v_pages, page_tables,
                               row_lens, k_scales=None, v_scales=None):
    """Exact XLA reference for the ragged-query kernel: flatten the
    G x Qb rows, repeat each lane's page-table row across its queries
    and delegate to :func:`paged_attention_xla` — byte-identical to
    running each query row through the decode reference on its own,
    BY CONSTRUCTION (that is the split-program path the unified engine
    dispatch must match)."""
    G, Qb, H, D = q.shape
    rows_q = q.reshape(G * Qb, H, D)
    rows_pt = jnp.repeat(page_tables, Qb, axis=0)
    rows_len = row_lens.reshape(G * Qb)
    out = paged_attention_xla(rows_q, k_pages, v_pages, rows_pt,
                              rows_len, k_scales, v_scales)
    return out.reshape(G, Qb, H, D)


def ragged_paged_attention(q, k_pages, v_pages, page_tables, row_lens,
                           k_scales=None, v_scales=None):
    """Routing entry for the unified serving dispatch: Pallas kernel on
    TPU (or under PADDLE_TPU_FORCE_PAGED=1), exact XLA gather reference
    elsewhere — the same routing contract as :func:`paged_attention`."""
    forced = os.environ.get("PADDLE_TPU_FORCE_PAGED") == "1"
    if forced or jax.default_backend() == "tpu":
        PAGED_ROUTE_STATS["pallas"] += 1
        return ragged_paged_attention_kernel(q, k_pages, v_pages,
                                             page_tables, row_lens,
                                             k_scales, v_scales)
    PAGED_ROUTE_STATS["xla"] += 1
    return ragged_paged_attention_xla(q, k_pages, v_pages, page_tables,
                                      row_lens, k_scales, v_scales)


# ===========================================================================
# Mesh-aware head-shard form (ISSUE 19): partial-softmax stats.
#
# Under sequence (sp) sharding each chip holds 1/sp of the page pool
# (and, under tp, its head-shard of every page).  A shard cannot
# normalize the softmax alone — it reduces over only the pages it OWNS
# and returns the ragged kernel's running stats instead of a normalized
# context: ``(o, lse)`` where ``o`` is the shard-local softmax over the
# owned pages and ``lse = m + log(l)`` its log-sum-exp (NEG_INF for a
# row with no owned/visible positions).  The caller merges shards in
# lse space (distributed/ring_attention.py's recipe):
#
#   M   = pmax(lse)            w = exp(lse - M)
#   ctx = psum(o * w) / psum(w)
#
# ``page_ok [G, M]`` masks page-table entries by OWNERSHIP: a non-owned
# entry was remapped to the shard's local trash row, whose zero content
# would otherwise contribute exp(0) terms to the softmax — ownership
# masking (not just the positional row_lens mask) is what keeps the
# merged result equal to the unsharded softmax.
# ===========================================================================


def ragged_paged_attention_stats_kernel(q, k_pages, v_pages, page_tables,
                                        row_lens, page_ok, k_scales=None,
                                        v_scales=None, *, interpret=None,
                                        q_align=None, fused_dequant=None,
                                        pages_per_step=None):
    """The stats-form Pallas kernel proper — ``ragged_paged_attention_kernel``
    plus a ``page_ok [G, M]`` ownership mask (fourth scalar prefetch) and
    an lse output.  Returns ``(o [G, Qb, H, D], lse [G, Qb, H] f32)``."""
    if q_align is None:
        q_align = _STATS_Q_ALIGN
    if fused_dequant is None:
        fused_dequant = bool(_RAGGED_FUSED_DEQUANT)
    if pages_per_step is None:
        pages_per_step = _STATS_PAGES_PER_STEP
    if interpret is None:
        interpret = _interpret_mode()
    return _ragged_call(q, k_pages, v_pages, page_tables, row_lens,
                        page_ok, k_scales, v_scales, interpret=interpret,
                        q_align=q_align, fused_dequant=bool(fused_dequant),
                        pages_per_step=pages_per_step)


def ragged_paged_attention_stats_xla(q, k_pages, v_pages, page_tables,
                                     row_lens, page_ok, k_scales=None,
                                     v_scales=None):
    """Exact XLA reference for the stats form: gather, mask by position
    AND page ownership, and return the locally-normalized context with
    its log-sum-exp — the same (o, lse) definition the kernel emits."""
    G, Qb, H, D = q.shape
    page_size = k_pages.shape[1]
    M = page_tables.shape[1]
    S = M * page_size
    k = k_pages[page_tables].reshape(G, S, H, D)
    v = v_pages[page_tables].reshape(G, S, H, D)
    if k_pages.dtype == jnp.int8:
        if k_scales is None or v_scales is None:
            raise ValueError("int8 KV pages require k_scales/v_scales")
        ks = jnp.repeat(k_scales[page_tables], page_size, axis=1)
        vs = jnp.repeat(v_scales[page_tables], page_size, axis=1)
        k = k.astype(jnp.float32) * ks[..., None]
        v = v.astype(jnp.float32) * vs[..., None]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("gqhd,gshd->gqhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = (jnp.arange(S)[None, None, :]
             < row_lens[:, :, None])                      # [G, Qb, S]
    ok = jnp.repeat(page_ok.astype(bool), page_size, axis=1)
    valid = valid & ok[:, None, :]
    vmask = valid[:, :, None, :]                          # [G, Qb, 1, S]
    s = jnp.where(vmask, s, NEG_INF)
    m = jnp.max(s, axis=-1)                               # [G, Qb, H]
    p = jnp.where(vmask, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)                               # [G, Qb, H]
    l_safe = jnp.maximum(l, 1e-30)
    o = jnp.einsum("gqhs,gshd->gqhd", p,
                   v.astype(jnp.float32)) / l_safe[..., None]
    lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
    return o.astype(q.dtype), lse.astype(jnp.float32)


def ragged_paged_attention_stats(q, k_pages, v_pages, page_tables,
                                 row_lens, page_ok, k_scales=None,
                                 v_scales=None):
    """Routing entry for the mesh-sharded (sp) serving core: Pallas
    kernel on TPU (or under PADDLE_TPU_FORCE_PAGED=1), exact XLA gather
    reference elsewhere — the same routing contract as
    :func:`ragged_paged_attention`.  ``page_ok [G, M]`` marks the
    page-table entries this shard owns; returns ``(o, lse)`` partial
    stats for the cross-shard lse-space merge."""
    forced = os.environ.get("PADDLE_TPU_FORCE_PAGED") == "1"
    if forced or jax.default_backend() == "tpu":
        PAGED_ROUTE_STATS["pallas"] += 1
        return ragged_paged_attention_stats_kernel(
            q, k_pages, v_pages, page_tables, row_lens, page_ok,
            k_scales, v_scales)
    PAGED_ROUTE_STATS["xla"] += 1
    return ragged_paged_attention_stats_xla(
        q, k_pages, v_pages, page_tables, row_lens, page_ok,
        k_scales, v_scales)
