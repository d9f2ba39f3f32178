"""The paged KV write kernel (``ops/pallas_ops/paged_kv_write.py``) against
the row scatter it replaces on the TPU's ragged path, in Pallas interpret
mode: for every lane mix the engine plans, the pools after the kernel are
byte-identical to the scatter's on every page but the trash page 0, no
page a live row does not map to is touched, and the engine's counters'
rule (``kv_write_counts``) counts what the kernel writes.

The lowering for a v5e is ``tests/test_pallas_tpu_lowering.py``'s (the
case table's ``paged_kv_write`` entry and the whole serve step); numerics
on the chip are ``chip_smoke.py``'s kernels phase."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_ops import paged_kv_write as kw
from paddle_tpu.ops.pallas_ops.cases import (kv_write_inputs,
                                             kv_write_scatter)

HEADS, HEAD_DIM = 2, 64          # one lane tile of fused row
P = 16


def _decode_lanes(rng, G):
    return [(int(rng.randint(0, 120)), 1) for _ in range(G)]


# (rows Q, [(row-0 position, live rows)] a lane) — each a mix the engine's
# planner emits: steady decode (Q = 1), a full chunk from a page boundary,
# a chunk from mid-page to mid-page, a prompt's short final chunk (rows past
# the prompt junk), spec-verify rows across a page boundary, idle lanes,
# and all of them at the serve cell's 48 lanes x 64 rows
def _mixes():
    rng = np.random.RandomState(38)
    mix48 = ([(64 * int(rng.randint(0, 6)), 64) for _ in range(6)]
             + [(int(rng.randint(0, 300)), int(rng.randint(1, 64)))
                for _ in range(5)]
             + [(128, 37), (200, 5), (15, 4), (0, 0), (77, 0)]
             + _decode_lanes(rng, 32))
    return {
        "decode_q1": (1, _decode_lanes(rng, 5)),
        "aligned_chunk": (64, [(64, 64), (0, 64)]),
        "mid_page_chunk": (64, [(21, 64), (7, 40)]),
        "short_final_chunk": (64, [(128, 37), (192, 1)]),
        "spec_verify": (4, [(30, 4), (47, 4), (16, 4), (3, 2)]),
        "idle_lanes": (8, [(0, 0), (40, 0), (9, 8)]),
        "mix_48x64": (64, mix48),
    }


MIXES = _mixes()


def _case(Q, lanes):
    table = 24                        # 384 positions a lane
    args = kv_write_inputs(HEADS, HEAD_DIM, P, lanes=lanes, rows=Q,
                           pages=len(lanes) * table + 1, table=table)
    return args


def _written(lanes, tables):
    """{(page, slot)} the live rows of every lane land in."""
    out = set()
    for (first, live), row in zip(lanes, tables):
        for pos in range(first, first + live):
            out.add((int(row[min(pos // P, len(row) - 1)]), pos % P))
    return out


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_kernel_writes_what_the_row_scatter_writes(mix):
    Q, lanes = MIXES[mix]
    args = _case(Q, lanes)
    k_pool, v_pool, tables = (np.asarray(args[i]) for i in (2, 3, 4))
    got = kw.paged_kv_write(*args, interpret=True)
    want = kv_write_scatter(*args)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g)[1:], np.asarray(w)[1:])
    # the trash page and every page no live row maps to keep their bytes
    written = _written(lanes, tables)
    pages = {page for page, _ in written}
    for out, before in zip(got, (k_pool, v_pool)):
        out = np.asarray(out)
        for page in range(out.shape[0]):
            if page not in pages:
                assert np.array_equal(out[page], before[page]), page
        # inside a written page, the slots no live row lands in too
        for page in pages:
            for slot in range(P):
                if (page, slot) not in written:
                    assert np.array_equal(out[page, slot],
                                          before[page, slot])
    # the engine's counters' rule counts what the kernel wrote: the live
    # rows, and the (lane, page) pairs whose every slot a live row fills
    rows = pages_whole = 0
    for (first, live), row in zip(lanes, tables):
        r, p = kw.kv_write_counts(first, live, P)
        rows += r
        pages_whole += p
        filled = {}
        for pos in range(first, first + live):
            filled.setdefault(pos // P, set()).add(pos % P)
        assert p == sum(len(s) == P for s in filled.values())
    assert rows == sum(live for _, live in lanes) == len(written)


def test_counts_rule():
    assert kw.kv_write_counts(0, 0, 16) == (0, 0)
    assert kw.kv_write_counts(37, 1, 16) == (1, 0)
    assert kw.kv_write_counts(64, 64, 16) == (64, 4)
    assert kw.kv_write_counts(21, 64, 16) == (64, 3)
    assert kw.kv_write_counts(128, 37, 16) == (37, 2)
    assert kw.kv_write_counts(30, 4, 16) == (4, 0)
    # one row is a whole page only when a page is one row, wherever it sits
    assert {kw.kv_write_counts(f, 1, 1) for f in range(5)} == {(1, 1)}


def test_routes_only_what_the_kernel_can_write(monkeypatch):
    f32 = jnp.zeros((3, 16, 128), jnp.float32)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PAGED", raising=False)
    assert not kw.routes(f32)                       # the CPU: the scatter
    monkeypatch.setenv("PADDLE_TPU_FORCE_PAGED", "1")
    assert kw.routes(f32)
    assert kw.routes(jnp.zeros((3, 8, 128), jnp.float32))
    # the roll is a 32-bit rotate; DMA slices are whole (8, 128) tiles
    assert not kw.routes(jnp.zeros((3, 16, 128), jnp.int8))
    assert not kw.routes(jnp.zeros((3, 16, 128), jnp.bfloat16))
    assert not kw.routes(jnp.zeros((3, 4, 128), jnp.float32))
