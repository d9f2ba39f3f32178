"""Paged KV write (TPU): a serving step's freshly projected K and V rows go
into the page pools as whole pages and live rows, in place.

The unified ragged step projects K and V for every row of its bucket of G
lanes x Q rows, most of them junk: bucket padding, a decode lane's rows
past row 0.  The row scatter (``pool.at[page, slot].set(rows)``) writes
all G*Q of them, one serial row update each — 3,072 a pool and step in the
long-prompt serve cell, 9.3 ms of a 25-ms step on the v5e.  This kernel
writes the live rows only, and a page they cover whole as one copy.

Live rows.  The engine plans each lane's live rows as a PREFIX of its Q
rows with consecutive positions (a chunk's rows ``cpos`` up to the
prompt's length, spec-verify rows ``pos + arange(K)``, a decode lane's row
0; junk rows carry ``row_valid`` 0), so a lane is two scalars: the
position of its row 0 and its live-row count (``tests/test_serving_ragged.py``
pins the planner to that shape).

Mechanics, one grid step a lane.  The lane's rows arrive in VMEM through
the pipeline — its first tile of rows where that holds them all (a decode
lane), else the whole lane; a sublane roll by ``first % P`` lays them out
page-aligned in an image, so the lane's j-th page is image rows
``[j*P, (j+1)*P)``.  Each page the lane touches is then ONE ``[P, H*D]``
HBM copy out of the image.  The first and last page, where the live rows
cover them in part, are read from the pool first and merged into the image
around the live rows — the first page through the pipeline (prefetched a
lane ahead), the last by a DMA of its own.  A lane with no live row moves
no bytes: its blocks are re-aimed at the previous lane's, which the
pipeline does not fetch again.  Every HBM slice a DMA takes is tile-aligned,
``(8, 128)`` for float32 — the v5e lowering refuses a one-row slice of a
tiled HBM array, hence page copies and not row copies, and a page size
that is a multiple of 8.  On the v5e at the serve cell's step (48 lanes
of 64 rows, 35 decode lanes, 12 calls for 24 pools of [3073, 16, 768])
the calls take 0.50 ms of device time where the row scatter took 9.3 —
the grid step's own cost, ~1 us a lane.  A decode lane reading its first
tile of rows and not its whole block saves 0.14 ms of it; waiting for a
lane's copies one lane later, from a ring of images, would save 0.05
more, which does not pay for the ring.

The pools are aliased to the outputs and written in place on the buffers
the engine donates.  Each pool is passed to the call ONCE, as the
first-page input: a second operand on the same buffer would make XLA copy
the whole pool to keep that operand intact.

Where it runs (``routes``): a TPU (or ``PADDLE_TPU_FORCE_PAGED=1``, which
interprets it, as it does the paged-attention kernels), 32-bit pools (the
roll is a 32-bit rotate), a page size the tile divides.  The caller
(``text/generation.py::_make_gpt_paged_core``) adds the rest: the ragged
layout, native pools, no sequence sharding — everywhere else the row
scatter stays, and it is this kernel's reference in the tests.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .contracts import PAGED_KV_WRITE

# the sublane tile of a 32-bit array: the row granularity of every DMA
_TILE = PAGED_KV_WRITE.dim("tile")

# trace-time routing telemetry, as paged_attention.PAGED_ROUTE_STATS:
# how each traced pool write of the ragged step was built
WRITE_ROUTE_STATS = {"pallas": 0, "scatter": 0}


def _interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def routes(pool) -> bool:
    """Whether a ``[N, P, H*D]`` pool takes the kernel: on a TPU (or with
    ``PADDLE_TPU_FORCE_PAGED=1``), stored in a 32-bit type, with a page
    size that is a whole number of sublane tiles."""
    forced = os.environ.get("PADDLE_TPU_FORCE_PAGED") == "1"
    return ((forced or jax.default_backend() == "tpu")
            and jnp.dtype(pool.dtype).itemsize == 4
            and pool.shape[1] % _TILE == 0)


def kv_write_counts(first: int, live: int, page_size: int):
    """``(rows, page_copies)`` for a lane whose live rows are positions
    ``[first, first + live)``: the live rows the kernel writes, and the
    pages they cover whole — copied from the new rows with nothing read
    first.  Host arithmetic for the engine's ``kv_rows_written`` and
    ``kv_page_copies`` counters, by the rule the kernel body follows."""
    if live <= 0:
        return 0, 0
    whole = (first + live) // page_size - (-(-first // page_size))
    return live, max(0, whole)


def _last_of(need, values):
    """``values`` with every entry whose ``need`` is False replaced by the
    nearest earlier needed one (the first needed one before any): an
    index map that repeats the previous block makes the pipeline fetch
    nothing."""
    G = need.shape[0]
    idx = jnp.where(need, jnp.arange(G, dtype=jnp.int32), -1)
    idx = jax.lax.cummax(idx, axis=0)
    idx = jnp.where(idx < 0, jnp.argmax(need).astype(jnp.int32), idx)
    return values[idx]


def _write_body(pt_ref, first_ref, live_ref, _small_ref, _big_ref,
                _head_ref, *refs, page_size, pages_per_seq, max_pages, split):
    """Grid (G,): lane g's live rows into its pages, K and V together."""
    it = iter(refs)
    k_small, v_small = next(it), next(it)
    k_big, v_big = (next(it), next(it)) if split else (None, None)
    k_head, v_head, k_out, v_out = (next(it) for _ in range(4))
    k_img, v_img, k_tail, v_tail, sems = (next(it) for _ in range(5))
    g = pl.program_id(0)
    P = page_size
    T = k_small.shape[1]
    sides = ((k_small, k_big, k_head, k_out, k_img, k_tail),
             (v_small, v_big, v_head, v_out, v_img, v_tail))
    first, n = first_ref[g], live_ref[g]
    s = first % P
    end = s + n                          # the live image rows: [s, end)
    col0 = first // P
    pages = jnp.where(n > 0, (end + P - 1) // P, 0)
    last = pages - 1
    head_part = (n > 0) & ((s > 0) | (end < P))
    tail_part = (n > 0) & (last > 0) & (end % P != 0)

    def page_of(j):
        # positions past the table's width clamp to its last entry, as
        # the row scatter's page lookup does
        return pt_ref[g, jnp.minimum(col0 + j, pages_per_seq - 1)]

    def tail_read(side):
        out, tail = sides[side][3], sides[side][5]
        return pltpu.make_async_copy(out.at[page_of(last)], tail,
                                     sems.at[0, side])

    @pl.when(tail_part)
    def _fetch_tail():
        tail_read(0).start()
        tail_read(1).start()

    rows = k_img.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (P, 1), 0)

    def lay(which, n_rows):
        """Roll the lane's first ``n_rows`` rows page-aligned into the
        image: ``img[s + r] = row r`` over the pages they can reach."""
        reach = min(rows, (n_rows + 2 * P - 2) // P * P)
        for side in sides:
            x = side[which][0]
            x = jnp.concatenate(
                [x, jnp.zeros((reach - n_rows, x.shape[1]), x.dtype)], axis=0)
            side[4][:reach] = pltpu.roll(x, s, 0)

    if split:
        pl.when(n <= T)(functools.partial(lay, 0, T))
        pl.when(n > T)(functools.partial(lay, 1, k_big.shape[1]))
    else:
        lay(0, T)

    @pl.when(head_part)
    def _merge_head():
        keep = (row >= s) & (row < end)
        for side in sides:
            img = side[4]
            img[:P] = jnp.where(keep, img[:P], side[2][0])

    @pl.when(tail_part)
    def _merge_tail():
        at = pl.multiple_of(last * P, _TILE)
        keep = row + at < end
        for i, side in enumerate(sides):
            img = side[4]
            tail_read(i).wait()
            img[pl.ds(at, P)] = jnp.where(keep, img[pl.ds(at, P)],
                                          side[5][...])

    def copies(start):
        for j in range(max_pages):
            @pl.when(j < pages)
            def _page():
                for i, side in enumerate(sides):
                    c = pltpu.make_async_copy(side[4].at[pl.ds(j * P, P)],
                                              side[3].at[page_of(j)],
                                              sems.at[1, i])
                    c.start() if start else c.wait()

    copies(True)
    copies(False)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _write_call(k_rows, v_rows, k_pool, v_pool, page_tables, first, live,
                *, interpret):
    N, P, HD = k_pool.shape
    G, M = page_tables.shape
    Q = k_rows.shape[0] // G
    if v_pool.shape != k_pool.shape or k_rows.shape != (G * Q, HD) \
            or v_rows.shape != k_rows.shape:
        raise ValueError(
            f"rows must be [lanes * rows, {HD}] for pools {k_pool.shape}; "
            f"got {k_rows.shape} / {v_rows.shape} / {v_pool.shape}")
    if P % _TILE:
        raise ValueError(f"page size {P} is not a multiple of the "
                         f"{_TILE}-row tile")
    # a lane's rows in whole tiles: padded where Q is not (the steady
    # decode step's Q = 1); at Q = 64 the reshape is a view
    Qp = -(-Q // _TILE) * _TILE
    rows = k_rows.reshape(G, Q, HD), v_rows.reshape(G, Q, HD)
    if Qp != Q:
        rows = tuple(jnp.pad(x, ((0, 0), (0, Qp - Q), (0, 0))) for x in rows)

    page_tables = page_tables.astype(jnp.int32)
    first = first.astype(jnp.int32)
    live = jnp.clip(live.astype(jnp.int32), 0, Q)
    lane = jnp.arange(G, dtype=jnp.int32)
    s = first % P
    head_page = page_tables[lane, jnp.minimum(first // P, M - 1)]
    # a lane of one tile of rows or fewer (a decode lane) reads its first
    # tile only; the others their whole block.  Blocks a lane does not
    # read repeat the previous lane's, which the pipeline does not fetch
    split = Qp > _TILE
    small = (live > 0) & (live <= _TILE) if split else live > 0
    prefetch = (page_tables, first, live, _last_of(small, lane),
                _last_of(live > _TILE, lane),
                _last_of((live > 0) & ((s > 0) | (s + live < P)),
                         head_page))
    # pages a lane's rows can touch: Qp rows from any slot of a page
    max_pages = (Qp + 2 * P - 2) // P

    def small_map(g, pt, first, live, small, big, head):
        return (small[g], 0, 0)

    def big_map(g, pt, first, live, small, big, head):
        return (big[g], 0, 0)

    def head_map(g, pt, first, live, small, big, head):
        return (head[g], 0, 0)

    in_specs = [pl.BlockSpec((1, _TILE, HD), small_map)] * 2
    operands = list(rows)
    if split:
        in_specs += [pl.BlockSpec((1, Qp, HD), big_map)] * 2
        operands += list(rows)
    in_specs += [pl.BlockSpec((1, P, HD), head_map)] * 2
    operands += [k_pool, v_pool]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    pool_at = len(prefetch) + len(operands) - 2
    return pl.pallas_call(
        functools.partial(_write_body, page_size=P, pages_per_seq=M,
                          max_pages=max_pages, split=split),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(G,),
            in_specs=in_specs,
            out_specs=[any_spec, any_spec],
            scratch_shapes=[
                pltpu.VMEM((max_pages * P, HD), k_pool.dtype),
                pltpu.VMEM((max_pages * P, HD), v_pool.dtype),
                pltpu.VMEM((P, HD), k_pool.dtype),
                pltpu.VMEM((P, HD), v_pool.dtype),
                # K and V: the last page's read, the page copies
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        input_output_aliases={pool_at: 0, pool_at + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *operands)


def paged_kv_write(k_rows, v_rows, k_pool, v_pool, page_tables, first, live,
                   *, interpret=None):
    """Write a ragged step's new K and V rows into their pools.

    k_rows, v_rows  [G*Q, H*D]   the step's projected rows, lane-major
    k_pool, v_pool  [N, P, H*D]  the pools as stored (donated: written in
                                 place, returned)
    page_tables     [G, M] int32 one page-table row per lane
    first           [G] int32    each lane's row-0 position
    live            [G] int32    each lane's live rows: rows [0, live)
                                 hold positions first, first + 1, ...

    Returns ``(k_pool', v_pool')``: on every page but the trash page 0,
    the row scatter's result — the live rows in their slots, every other
    byte as it was.  Page 0 gets only the live rows a table sends there
    (the scatter also fills it with the junk rows)."""
    if interpret is None:
        interpret = _interpret_mode()
    return tuple(_write_call(k_rows, v_rows, k_pool, v_pool, page_tables,
                             first, live, interpret=interpret))
