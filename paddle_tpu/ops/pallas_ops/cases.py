"""Every kernel form ``CONTRACTS`` governs, with inputs and its XLA twin —
the ONE table of them.

``chip_smoke.py``'s kernels phase runs it on the chip,
``tests/test_pallas_tpu_lowering.py`` compiles it for a v5e from the CPU,
and the tune runners (``tune/runners.py``) draw their inputs from the
same builders at their own bucket sizes.  A new kernel form is added
here, once; ``kernel_cases`` refuses to return while a contract has no
case (``MIXER_CONTRACTS`` have theirs in ``mixer_cases``: forms that exist
at one head size, whatever ``kernel_cases`` is asked for).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import numpy as np

from .contracts import CONTRACTS

__all__ = ["KernelCase", "kernel_cases", "mixer_cases", "grouped_cases",
           "serve_cell_case", "MIXER_CONTRACTS",
           "flash_inputs", "paged_inputs", "qmm_inputs", "kv_write_inputs",
           "kv_write_scatter"]


PAGE_SIZE = 16                      # the serving default
# contracts whose only head size is 128: their cases are `mixer_cases`'
MIXER_CONTRACTS = ("delta_rule_fwd", "delta_rule_bwd")
FLASH_SEQ, FLASH_BLOCK = 512, 256   # 2 x 2 blocks: the causal skip runs


class KernelCase(NamedTuple):
    contract: str       # the CONTRACTS entry that governs this form
    label: str
    kernel: Callable    # the Pallas form, never interpreted
    twin: Callable      # its XLA twin, same arguments
    args: Tuple


def flash_inputs(B, H, S, D):
    """(q, k, v, g) f32 [B, H, S, D], the all-valid kv mask [B, 1, S] and
    the zero dropout seed — the operands of the three flash kernels."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    qkvg = tuple(jnp.asarray(rng.standard_normal((B, H, S, D))
                             .astype(np.float32)) for _ in range(4))
    return qkvg, jnp.ones((B, 1, S), jnp.float32), jnp.zeros((1,), jnp.int32)


def paged_inputs(H, D, page_size, *, int8, pages=40, rows=16, table=8):
    """A MIXED three-lane batch for the ragged-query kernels, ragged as
    the engine dispatches it: a steady-decode lane (one live row), a
    prefill-chunk lane (every row live, ascending positions) and a
    spec-verify-shaped lane (a few rows).  Returns ``(q [3, rows, H, D],
    k_pool, v_pool [pages, page_size, H*D] — the stored layout —
    page_tables [3, table], row_lens [3, rows], page_ok [3, table],
    k_scales, v_scales)``; the pools are int8 with per-page-per-head
    scales when ``int8``, else f32 and the scales are None."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    cap = table * page_size
    q = rng.standard_normal((3, rows, H, D)).astype(np.float32) * 0.5
    kf = rng.standard_normal((pages, page_size, H, D)).astype(np.float32)
    vf = rng.standard_normal((pages, page_size, H, D)).astype(np.float32)
    pt = rng.randint(1, pages, (3, table)).astype(np.int32)
    few = max(1, rows // 4)
    rl = np.zeros((3, rows), np.int32)
    rl[0, 0] = cap * 5 // 8 + 3
    rl[1, :] = np.arange(cap // 4, cap // 4 + rows)
    rl[2, :few] = np.arange(cap * 3 // 4, cap * 3 // 4 + few)
    ok = rng.randint(0, 2, (3, table)).astype(np.int32)
    ks = vs = None
    if int8:
        ks = (np.abs(kf).max(axis=(1, 3)) / 127 + 1e-9).astype(np.float32)
        vs = (np.abs(vf).max(axis=(1, 3)) / 127 + 1e-9).astype(np.float32)
        kf = np.clip(np.round(kf / ks[:, None, :, None]), -127,
                     127).astype(np.int8)
        vf = np.clip(np.round(vf / vs[:, None, :, None]), -127,
                     127).astype(np.int8)
        ks, vs = jnp.asarray(ks), jnp.asarray(vs)
    fused = (pages, page_size, H * D)
    return (jnp.asarray(q), jnp.asarray(kf.reshape(fused)),
            jnp.asarray(vf.reshape(fused)),
            jnp.asarray(pt), jnp.asarray(rl), jnp.asarray(ok), ks, vs)


def serve_cell_case(pages=513):
    """The ragged kernel as the long-prompt serve cell dispatches it
    (``serve-longprompt-batch``: one 64-row mixed step of 48 lanes, 64
    pages of 16 a lane, GPT-2 small's 12 heads of 64): 39 lanes with ONE
    live row over a context of 512-1,008 positions, the rest
    chunked-prefill lanes — full 64-row chunks at staggered starts, the
    last one a 16-row tail chunk.  The pool of ``pages`` pages is a
    sixth of the cell's (lanes share pages; attention only reads them)."""
    import jax.numpy as jnp

    from . import paged_attention as pa

    heads, head_dim, lanes, rows, table, decode_lanes = 12, 64, 48, 64, 64, 39
    rng = np.random.RandomState(29)
    cap = table * PAGE_SIZE
    q = rng.standard_normal((lanes, rows, heads, head_dim)
                            ).astype(np.float32) * 0.5
    pool = (pages, PAGE_SIZE, heads * head_dim)
    kp = rng.standard_normal(pool).astype(np.float32)
    vp = rng.standard_normal(pool).astype(np.float32)
    pt = rng.randint(1, pages, (lanes, table)).astype(np.int32)
    rl = np.zeros((lanes, rows), np.int32)
    rl[:decode_lanes, 0] = rng.randint(cap // 2, cap - PAGE_SIZE + 1,
                                       decode_lanes)
    for lane in range(decode_lanes, lanes):
        live = PAGE_SIZE if lane == lanes - 1 else rows
        start = rng.randint(0, cap - rows)
        rl[lane, :live] = start + 1 + np.arange(live)
    q, kp, vp, pt, rl = map(jnp.asarray, (q, kp, vp, pt, rl))

    def twin(kp, vp):
        # a lane at a time: the reference gathers every ROW's pages
        # (3 MB a row here), all 3,072 rows at once would not fit
        import jax

        return jax.lax.map(
            lambda lane: pa.ragged_paged_attention_xla(
                lane[0][None], kp, vp, lane[1][None], lane[2][None])[0],
            (q, pt, rl))

    return KernelCase(
        "paged_attention_ragged", "ragged native, serve cell's step",
        lambda kp, vp: pa.ragged_paged_attention_kernel(
            q, kp, vp, pt, rl, interpret=False), twin, (kp, vp))


# (row-0 position, live rows) of each lane kind the ragged step writes,
# as the engine plans them, at rows 16 and pages of 16: a decode lane, an
# aligned full chunk, a chunk from mid-page to mid-page, a prompt's short
# final chunk, spec-verify rows across a page boundary, an idle lane
KV_WRITE_LANES = ((37, 1), (32, 16), (21, 16), (48, 5), (62, 4), (0, 0))


def kv_write_inputs(H, D, page_size, *, lanes=KV_WRITE_LANES, rows=16,
                    pages=64, table=8, seed=7):
    """The operands of the paged KV write for ``lanes`` ((first, live)
    pairs): ``(k_rows, v_rows [lanes * rows, H*D], k_pool, v_pool [pages,
    page_size, H*D], page_tables [lanes, table] — distinct pages, none of
    them the trash page — first [lanes], live [lanes])``."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    G, HD = len(lanes), H * D
    pool = (pages, page_size, HD)
    pt = (1 + rng.permutation(pages - 1)[:G * table]).reshape(G, table)
    first = np.array([f for f, _ in lanes], np.int32)
    live = np.array([n for _, n in lanes], np.int32)
    arr = lambda *shape: jnp.asarray(
        rng.standard_normal(shape).astype(np.float32))
    return (arr(G * rows, HD), arr(G * rows, HD), arr(*pool), arr(*pool),
            jnp.asarray(pt.astype(np.int32)), jnp.asarray(first),
            jnp.asarray(live))


def kv_write_scatter(k_rows, v_rows, k_pool, v_pool, page_tables, first,
                     live):
    """The paged KV write's reference: the ragged step's row scatter —
    every row into page ``table[min(pos // P, M - 1)]`` slot ``pos % P``,
    rows past a lane's live ones into the trash page 0 — with page 0 then
    put back as it was (the kernel leaves it alone where no live row maps
    to it, and what it holds is junk either way)."""
    import jax.numpy as jnp

    G, M = page_tables.shape
    Q = k_rows.shape[0] // G
    P = k_pool.shape[1]
    r = jnp.arange(Q, dtype=jnp.int32)
    pos = (first[:, None] + r[None, :]).reshape(-1)
    page = jnp.take_along_axis(jnp.repeat(page_tables, Q, axis=0),
                               jnp.minimum(pos // P, M - 1)[:, None],
                               axis=1)[:, 0]
    page = jnp.where((r[None, :] < live[:, None]).reshape(-1), page, 0)
    return tuple(pool.at[page, pos % P].set(new).at[0].set(pool[0])
                 for pool, new in ((k_pool, k_rows), (v_pool, v_rows)))


def qmm_inputs(M, K, N):
    """(x [M, K] f32, w_q [K, N] int8, w_scale [N] f32) for the
    weight-only int8 matmul."""
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    return (jnp.asarray(rng.standard_normal((M, K)).astype(np.float32)),
            jnp.asarray(rng.randint(-127, 128, (K, N)).astype(np.int8)),
            jnp.asarray(rng.uniform(0.5, 1.5, (N,)).astype(np.float32)
                        / 127.0))


def _flash_cases(qkvg, mask, seed, block, label=""):
    """The three flash kernels on (q, k, v, g) [B, H or Hkv, S, D] in
    square causal blocks of `block`, the backward kernels on the forward's
    own statistics, each against XLA attention and its vjp."""
    import jax
    import jax.numpy as jnp

    from ..attention import _sdpa_core
    from . import flash_attention as fa

    B, H, S, D = qkvg[0].shape
    tail = (1.0 / float(np.sqrt(D)), True, 0.0, block, block)

    def xla_attn(q, k, v):
        return _sdpa_core(q, k, v, None, 0.0, True, None)

    def xla_grads(q, k, v, g):
        return jax.vjp(xla_attn, q, k, v)[1](g)

    def flash_fwd(q, k, v, g):
        return fa._flash_fwd_bhsd(q, k, v, mask, seed, *tail)[0]

    def flash_stats(q, k, v, g):
        o, lse = fa._flash_fwd_bhsd(q, k, v, mask, seed, *tail)
        return lse, jnp.sum(g * o, axis=-1).reshape(B * H, S, 1)

    def flash_dkv(q, k, v, g):
        return fa._flash_dkv_bhsd(q, k, v, g, *flash_stats(q, k, v, g),
                                  mask, seed, *tail)

    def flash_dq(q, k, v, g):
        return fa._flash_dq_bhsd(q, k, v, g, *flash_stats(q, k, v, g),
                                 mask, seed, *tail)

    return [
        KernelCase("flash_attention_fwd", "flash fwd" + label, flash_fwd,
                   lambda q, k, v, g: xla_attn(q, k, v), qkvg),
        KernelCase("flash_attention_bwd_dkv", "flash bwd dk/dv" + label,
                   flash_dkv, lambda *a: xla_grads(*a)[1:], qkvg),
        KernelCase("flash_attention_bwd_dq", "flash bwd dq" + label,
                   flash_dq, lambda *a: xla_grads(*a)[0], qkvg),
    ]


def kernel_cases(heads, head_dim):
    """The cases at (heads, head_dim): f32 inputs of unit scale; the paged
    kernels take ``interpret=False``, the flash wrappers decide from
    ``flash_attention._interpret_mode()`` (the chip, or a test's patch)."""
    from . import paged_attention as pa
    from . import quantized_matmul as qm

    H, D = heads, head_dim
    cases = []

    # --- flash: fwd, then the two backward kernels on the fwd's stats ----
    qkvg, mask, seed = flash_inputs(1, H, FLASH_SEQ, D)
    cases += _flash_cases(qkvg, mask, seed, FLASH_BLOCK)

    # --- paged: ragged, decode (ragged at Q = 1) and stats, x native/int8 --
    def paged(int8):
        q, kp, vp, pt, rl, ok, ks, vs = paged_inputs(H, D, PAGE_SIZE,
                                                     int8=int8)
        pools = (kp, vp) + ((ks, vs) if int8 else ())
        kind = "int8" if int8 else "native"
        suffix = "_int8" if int8 else ""

        def ragged(kp, vp, *sc):
            return pa.ragged_paged_attention_kernel(q, kp, vp, pt, rl, *sc,
                                                    interpret=False)

        def decode(kp, vp, *sc):
            return pa.paged_attention_kernel(q[:, 0], kp, vp, pt, rl[:, 0],
                                             *sc, interpret=False)

        def stats(kp, vp, *sc):
            return pa.ragged_paged_attention_stats_kernel(
                q, kp, vp, pt, rl, ok, *sc, interpret=False)

        return [
            KernelCase("paged_attention_ragged" + suffix, f"ragged {kind}",
                       ragged,
                       lambda kp, vp, *sc: pa.ragged_paged_attention_xla(
                           q, kp, vp, pt, rl, *sc), pools),
            KernelCase("paged_attention_ragged" + suffix,
                       f"decode {kind} (ragged at Q=1)", decode,
                       lambda kp, vp, *sc: pa.paged_attention_xla(
                           q[:, 0], kp, vp, pt, rl[:, 0], *sc), pools),
            KernelCase("paged_attention_ragged_stats",
                       f"ragged-stats {kind}", stats,
                       lambda kp, vp, *sc:
                       pa.ragged_paged_attention_stats_xla(
                           q, kp, vp, pt, rl, ok, *sc), pools),
        ]

    cases += paged(False) + paged(True)

    # --- the ragged step's K/V write: one lane of each kind --------------
    from . import paged_kv_write as kw

    cases.append(KernelCase(
        "paged_kv_write", "kv write native, lane mix",
        lambda *a: kw.paged_kv_write(*a, interpret=False),
        kv_write_scatter, kv_write_inputs(H, D, PAGE_SIZE)))

    # --- weight-only int8 matmul at the model's own projection shape -----
    cases.append(KernelCase(
        "quantized_matmul", "int8 weight-only matmul",
        lambda *a: qm.quantized_matmul_kernel(*a, interpret=False),
        qm.quantized_matmul_xla, qmm_inputs(64, H * D, 3 * H * D)))

    missing = set(CONTRACTS) - {c.contract for c in cases} \
        - set(MIXER_CONTRACTS)
    if missing:
        raise AssertionError(f"contracts without a kernel case: {missing}")
    return cases


def mixer_cases(heads=4, seq=FLASH_SEQ + 72):
    """The sequence mixers of the hybrid linear-attention models, each
    against its XLA twin, forward and gradients: flash attention whose q/k
    head size (192) is not its v head size (128), through the public
    wrapper (padding and all), and the gated delta rule's two Pallas
    kernels (forward; backward through the `custom_vjp`) at head size 128
    against the token-by-token recurrence, at a length that is no
    multiple of a chunk pair and decays from 0.999 down to 0.2 a token."""
    import jax
    import jax.numpy as jnp

    from .. import linear_attention as la
    from ..attention import _sdpa_core
    from . import delta_rule as dr
    from . import flash_attention as fa

    rng = np.random.RandomState(11)
    arr = lambda *shape: jnp.asarray(
        rng.standard_normal(shape).astype(np.float32))
    q, k, v, g = (arr(1, seq, heads, 192), arr(1, seq, heads, 192),
                  arr(1, seq, heads, 128), arr(1, seq, heads, 128))

    def flash(q, k, v, g):
        return fa.flash_attention_bshd(q, k, v, causal=True)

    def xla_attn(q, k, v, g):
        t = lambda a: jnp.swapaxes(a, 1, 2)
        return t(_sdpa_core(t(q), t(k), t(v), None, 0.0, True, None))

    def grads_of(fn):
        return lambda *a: jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, a[3]) * a[3]),
            argnums=(0, 1, 2))(*a[:3])

    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    dq, dk, dv = (unit(arr(1, seq, heads, 128)) * 128 ** -0.5,
                  unit(arr(1, seq, heads, 128)), arr(1, seq, heads, 128))
    decay = jnp.log(jnp.asarray(rng.uniform(
        0.2, 0.999, (1, seq, heads, 128)).astype(np.float32)))
    beta = jnp.asarray(rng.uniform(0.05, 0.95, (1, seq, heads)
                                   ).astype(np.float32))
    w = arr(1, seq, heads, 128)
    delta = (dq, dk, dv, decay, beta)

    def delta_grads(fn):
        return lambda *a: jax.grad(
            lambda *b: jnp.sum(fn(*b) * w), argnums=(0, 1, 2, 3, 4))(*a)

    return [
        KernelCase("flash_attention_fwd", "flash fwd q/k 192, v 128", flash,
                   xla_attn, (q, k, v, g)),
        KernelCase("flash_attention_bwd_dkv", "flash bwd q/k 192, v 128",
                   grads_of(flash), grads_of(xla_attn), (q, k, v, g)),
        KernelCase("delta_rule_fwd", "delta rule fwd",
                   dr.gated_delta_rule_kernel,
                   la.gated_delta_rule_recurrent, delta),
        KernelCase("delta_rule_bwd", "delta rule bwd",
                   delta_grads(dr.gated_delta_rule_kernel),
                   delta_grads(la.gated_delta_rule_recurrent), delta),
    ]


def grouped_cases(heads=32, kv_heads=8, head_dim=64, seq=FLASH_SEQ,
                  block=FLASH_BLOCK):
    """The three flash kernels with fewer KV heads than query heads (the
    grouped-query attention of the LFM2 models: 32 / 8 heads of 64), each
    against its XLA twin on K and V repeated to the query heads' count:
    the forward and dq kernels read KV head h // group through their
    index maps, the dk/dv kernel sums the group in its scratch.  Square
    blocks of `block`, 2 x 2 of them by default, so the causal skip and
    the walk over a group's heads both run."""
    (q, _, _, g), mask, seed = flash_inputs(1, heads, seq, head_dim)
    (k, v, _, _), _, _ = flash_inputs(1, kv_heads, seq, head_dim)
    return _flash_cases((q, k, v, g), mask, seed, block,
                        f" {heads}/{kv_heads} heads x {head_dim}")
