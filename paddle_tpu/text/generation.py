"""Incremental (KV-cache) decoding for the GPT flagship.

Reference analog: the reference decodes seq2seq with BeamSearchDecoder +
per-step Cache (nn/layer/transformer.py MultiHeadAttention.Cache /
gen_cache — concat-grown, dynamic shapes).  TPU-native re-design: the
cache is a FIXED [B, max_len, H, D] ring per layer written with one
``.at[pos].set`` scatter per step; attention masks positions > pos.
Everything is static-shaped, so the whole decode jits into one lax.scan
(nn/decode.py) and the MXU sees batched [B*K] matmuls.

The functional step math mirrors GPTModel.forward exactly — a parity
test (tests/test_gpt_generation.py) pins incremental logits to the full
forward's."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..jit.functional import get_state

__all__ = ["make_gpt_decode_step", "make_gpt_paged_decode_step",
           "make_gpt_paged_prefill_step", "make_gpt_paged_fused_decode_step",
           "make_gpt_paged_spec_verify_step", "make_gpt_paged_ragged_step",
           "RAGGED_NO_LIMIT", "ServingMeshLayout", "prefill", "generate"]

# per-row KV-horizon sentinel for the unified ragged step (ISSUE 18): a
# decode/spec row carries this instead of a real valid_len, making the
# core's padding clamps exact integer identities (min(pos+1, BIG) ==
# pos+1, pos < BIG always) — the row behaves bit-for-bit like the split
# programs' valid_len=None path
RAGGED_NO_LIMIT = 1 << 30


def _ln(x, w, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    m = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - m) * jax.lax.rsqrt(v + eps)
    return (out * w + b).astype(x.dtype)


def _gelu(x):
    # exact form (functional/activation.py gelu approximate=False)
    from jax.scipy.stats import norm

    return x * norm.cdf(x)


# ---------------------------------------------------------------------------
# int8 quantization plumbing shared by the dense and paged decode cores.
#
# Weight-only matmul: ``weight_quant`` maps a param name (e.g.
# "layers.0.attn.q_proj.weight") to an (int8 [K, N], fp32 [N]) pair as
# produced by slim.export_serving_quant; matmuls against a quantized name
# route through ops/pallas_ops/quantized_matmul (in-register dequant on
# TPU, exact XLA dequant-matmul on CPU).  Biases/LN/embeddings stay float.
#
# KV quantization: pages/caches store int8 with fp32 scales.  Two modes:
#   static  — calibrated per-layer-per-head scales (slim bridge); writes
#             CLIP at ±127, no scale state mutates, so results are
#             layout-independent (paged engine == dense generate).
#   dynamic — per-page scales grow via scatter-max at write time and the
#             page's prior int8 content is requantized under the new
#             scale (one page gather/scatter per write — bounded, N pages
#             per step).  No calibration needed; scales are reset when a
#             page is (re)allocated so results depend only on the tokens
#             written since allocation, never on page-reuse history.
# ---------------------------------------------------------------------------

_KV_QMAX = 127.0


def _make_mm(params, weight_quant):
    """Returns ``mm(x, name)`` computing ``x @ params[name]`` — through
    the weight-only int8 kernel when ``name`` is quantized."""
    if not weight_quant:
        return lambda x, name: x @ params[name]
    from ..ops.pallas_ops.quantized_matmul import quantized_matmul

    wq = {name: (jnp.asarray(q), jnp.asarray(s, jnp.float32))
          for name, (q, s) in weight_quant.items()}

    def mm(x, name):
        ent = wq.get(name)
        if ent is None:
            return x @ params[name]
        return quantized_matmul(x, ent[0], ent[1])

    return mm


def _quant_write_page(pages, scales, page_idx, slot, val, static_scale):
    """Scatter one new [N, H, D] K or V slab into int8 pages (stored
    [pages, P, H*D] like every pool: rows of the fused width).

    static_scale is the calibrated [H] scale (static mode) or None
    (dynamic mode: grow the written pages' [N, H] scales by abs-max and
    requantize their prior content under the new scale).  Returns
    (pages', scales').  Duplicate page indices (a prefill chunk writing
    several slots of one page) are safe: the scale update is a
    scatter-MAX and every duplicate computes identical rescaled content.
    The per-head split below is of the N gathered pages / new rows only,
    never of the pool.
    """
    N, H, D = val.shape
    valf = val.astype(jnp.float32)

    def rows(x):                       # quantized [N, H, D] -> pool rows
        return jnp.clip(jnp.round(x), -_KV_QMAX,
                        _KV_QMAX).astype(jnp.int8).reshape(N, H * D)

    if static_scale is not None:
        q = rows(valf / static_scale[None, :, None])
        return pages.at[page_idx, slot].set(q), scales
    amax = jnp.max(jnp.abs(valf), axis=-1)                   # [N, H]
    cand = jnp.maximum(amax / _KV_QMAX, 1e-8)
    s_old = scales[page_idx]                                 # [N, H]
    scales = scales.at[page_idx].max(cand)
    s_new = scales[page_idx]
    old = pages[page_idx].astype(jnp.float32)                # [N, P, H*D]
    resc = jnp.round(old.reshape(N, -1, H, D)
                     * (s_old / s_new)[:, None, :, None])
    pages = pages.at[page_idx].set(
        resc.astype(jnp.int8).reshape(old.shape))
    q = rows(valf / s_new[:, :, None])
    return pages.at[page_idx, slot].set(q), scales


def _as_layer_scales(kv_scales, L, H):
    """Normalize a slim kv-scale export ({"k": [L x [H]], "v": ...}) to
    per-layer jnp f32 arrays; None stays None (dynamic mode)."""
    if kv_scales is None:
        return None, None
    ks = [jnp.asarray(np.asarray(kv_scales["k"][i], np.float32))
          for i in range(L)]
    vs = [jnp.asarray(np.asarray(kv_scales["v"][i], np.float32))
          for i in range(L)]
    for arr in ks + vs:
        if arr.shape != (H,):
            raise ValueError(
                f"kv_scales entries must be [{H}] per layer, got "
                f"{arr.shape}")
    return ks, vs


# ---------------------------------------------------------------------------
# Mesh-sharded serving (ISSUE 19): one replica spans tp*sp chips.
#
# ``ServingMeshLayout`` is the SpecLayout-style per-parameter-name spec
# assignment: a frozen layout object mapping every weight name / KV-pool
# leaf to a PartitionSpec over a named (tp, sp, data) mesh.
#
#   tp — HEAD sharding.  qkv/fc1 weights are column-sharded by head, so
#        each chip projects and attends over H/tp heads against its
#        head-shard of every KV page ([N, P, (H/tp)*D] locally: H/tp
#        heads are a contiguous slice of the pool's fused row); the
#        per-head context is reassembled with one tiled all-gather and
#        out_proj/fc2 run replicated.  Every per-element reduction is
#        the same dot the single-device core computes, so the tp path
#        is BITWISE identical to the unsharded core — decode just
#        streams the pools at tp-chip aggregate HBM bandwidth.
#   sp — SEQUENCE (page-dim) sharding for long contexts.  The page pool
#        splits along pages ([N/sp, P, (H/tp)*D] locally): global page p
#        lives on shard p // (N/sp) at local row p % (N/sp).  Each shard
#        runs the ragged kernel's partial-softmax form over the pages it
#        OWNS (ownership-masked) and the shards exchange running-max /
#        denominator stats in lse space (the ring_attention.py merge):
#        m = pmax(lse), o = psum(o·e^{lse-m}) / psum(e^{lse-m}).  A
#        non-owned row scatters into the shard's reserved local trash
#        row — the allocator reserves global page s·(N/sp) on every
#        shard s (kv_cache.PagedKVCache reserved_pages).
# ---------------------------------------------------------------------------

# parameter-name fragments whose weights column-shard over tp (output
# dim = heads·head_dim for qkv, ffn for fc1); everything else replicates
_TP_COLUMN_SHARDED = (".attn.q_proj.", ".attn.k_proj.", ".attn.v_proj.",
                      ".fc1.")


@dataclass(frozen=True)
class ServingMeshLayout:
    """Sharding layout of one mesh-sized serving replica.

    ``param_spec(name)`` assigns each parameter its PartitionSpec by
    name (the SpecLayout pattern); ``page_spec``/``scale_spec`` lay out
    the paged KV pools.  ``size == tp * sp`` chips form the replica.
    """

    tp: int = 1
    sp: int = 1
    tp_axis: str = "tp"
    sp_axis: str = "sp"
    data_axis: str = "data"

    def __post_init__(self):
        if int(self.tp) < 1 or int(self.sp) < 1:
            raise ValueError(
                f"mesh degrees must be >= 1, got tp={self.tp} sp={self.sp}")

    @property
    def size(self) -> int:
        return int(self.tp) * int(self.sp)

    def axes(self):
        """Named-mesh axis sizes for ``distributed.mesh.init_mesh``."""
        return {self.tp_axis: int(self.tp), self.sp_axis: int(self.sp),
                self.data_axis: 1}

    def param_spec(self, name: str):
        from jax.sharding import PartitionSpec

        if any(frag in name for frag in _TP_COLUMN_SHARDED):
            if name.endswith(".weight"):
                return PartitionSpec(None, self.tp_axis)
            if name.endswith(".bias"):
                return PartitionSpec(self.tp_axis)
        return PartitionSpec()

    def page_spec(self):
        """[num_pages, P, H*D] pool: pages over sp, heads over tp (a
        head shard is a contiguous slice of the fused row)."""
        from jax.sharding import PartitionSpec

        return PartitionSpec(self.sp_axis, None, self.tp_axis)

    def scale_spec(self):
        """[num_pages, H] int8 dequant scales ride their pool's split."""
        from jax.sharding import PartitionSpec

        return PartitionSpec(self.sp_axis, self.tp_axis)

    def kv_spec(self, kv):
        """PartitionSpec pytree matching a paged-KV pool pytree."""
        return {key: [self.scale_spec() if key.endswith("_scale")
                      else self.page_spec() for _ in leaves]
                for key, leaves in kv.items()}

    def reserved_pages(self, num_pages: int):
        """Global page ids reserved as per-shard trash rows: shard s's
        local row 0 is global page s*(num_pages//sp) — non-owned and
        masked-lane scatters land there, so it can never hold live KV.
        Degenerates to (0,) (the classic trash page) at sp == 1."""
        pl = int(num_pages) // int(self.sp)
        return tuple(s * pl for s in range(int(self.sp)))


def _gpt_geometry(model):
    """``(L, H, D, hidden, max_pos, vocab)`` of a GPTModel — the one place
    the serving path (the step builders here, ``ServingEngine``) reads the
    model's shape."""
    vocab, hidden = model.wte.weight.shape
    H = int(model.layers[0].attn.num_heads)
    return (len(model.layers), H, int(hidden) // H, int(hidden),
            int(model.wpe.weight.shape[0]), int(vocab))


def _gpt_embed(p, tokens, pos, max_pos):
    # positions past the table belong to masked lanes (bucket padding)
    # whose output is discarded: clamp instead of relying on gather
    # clipping
    return p["wte.weight"][tokens] + p["wpe.weight"][
        jnp.minimum(pos, max_pos - 1)]


def _gpt_head(p, x):
    x = _ln(x, p["ln_f.weight"], p["ln_f.bias"])
    return x @ p["wte.weight"].T                             # tied head


def _gpt_block(p, mm, i, x, attend, tp_axis=None):
    """Layer ``i`` of the GPT-2 stack over N independent rows ``x``
    [N, hidden] — THE block: the dense step and the paged core (one chip
    or a mesh) all run this one.

    The seam is block against cache: ``attend(i, q, k1, v1) -> ctx
    [N, hidden]`` takes the layer's projections as flat rows (under a
    mesh, this shard's heads), owns the cache write and the attention
    over it, and hands back the context of ALL heads.  ``tp_axis`` names
    the mesh axis fc1's columns are sharded over (None on one chip): the
    activations are all-gathered before the replicated fc2."""
    def lp(name):
        return p[f"layers.{i}.{name}"]

    h = _ln(x, lp("ln1.weight"), lp("ln1.bias"))
    q = mm(h, f"layers.{i}.attn.q_proj.weight") + lp("attn.q_proj.bias")
    k1 = mm(h, f"layers.{i}.attn.k_proj.weight") + lp("attn.k_proj.bias")
    v1 = mm(h, f"layers.{i}.attn.v_proj.weight") + lp("attn.v_proj.bias")
    ctx = attend(i, q, k1, v1)
    x = x + (mm(ctx, f"layers.{i}.attn.out_proj.weight")
             + lp("attn.out_proj.bias"))
    h2 = _ln(x, lp("ln2.weight"), lp("ln2.bias"))
    ff = _gelu(mm(h2, f"layers.{i}.fc1.weight") + lp("fc1.bias"))
    if tp_axis is not None:
        ff = jax.lax.all_gather(ff, tp_axis, axis=1, tiled=True)
    return x + mm(ff, f"layers.{i}.fc2.weight") + lp("fc2.bias")


def _check_kv_cache_dtype(kv_cache_dtype):
    if kv_cache_dtype not in (None, "int8"):
        raise ValueError(f"kv_cache_dtype must be None or 'int8', got "
                         f"{kv_cache_dtype!r}")
    return kv_cache_dtype == "int8"


def make_gpt_decode_step(model, max_len: int, *, kv_cache_dtype=None,
                         kv_scales=None, weight_quant=None):
    """Build (step_fn, init_state) for a GPTModel.

    step_fn(tokens [N], state) -> (logits [N, vocab], state) — one decode
    position per call, cache-backed; the state's leaves all have leading
    dim N so nn.decode's beam reordering (s[parent]) works unchanged.

    Quantized variants (docs/SERVING.md "Quantized serving"):
    ``kv_cache_dtype="int8"`` stores the ring cache as int8 with the
    calibrated per-layer-per-head ``kv_scales`` (REQUIRED here — the
    dense ring has no per-page scale state, so only the static mode
    applies); new K/V is quantized at write time with the same scales
    the paged serving path uses, so greedy tokens match the quantized
    engine's.  ``weight_quant`` routes the projection/MLP matmuls
    through the weight-only int8 kernel.
    """
    params, _ = get_state(model)
    L, H, D, hidden, max_pos, _ = _gpt_geometry(model)
    scale = 1.0 / np.sqrt(D)
    quant_kv = _check_kv_cache_dtype(kv_cache_dtype)
    if quant_kv and kv_scales is None:
        raise ValueError("the dense decode cache supports int8 only with "
                         "calibrated kv_scales (slim.export_serving_quant)")
    k_sc, v_sc = _as_layer_scales(kv_scales, L, H)
    mm = _make_mm(params, weight_quant)

    def init_state(batch: int):
        cache_dtype = jnp.int8 if quant_kv else params["wte.weight"].dtype
        z = jnp.zeros((batch, max_len, H, D), cache_dtype)
        return {
            "k": [z for _ in range(L)],
            "v": [z for _ in range(L)],
            # per-lane position: decode.py reorders every leaf by the
            # parent beam via s[idx], so even this scalar-ish field rides
            # with leading dim N
            "pos": jnp.zeros((batch,), jnp.int32),
        }

    def _store(val, i, sc):
        """Cache-dtype conversion for one new [N, H, D] slab."""
        if not quant_kv:
            return val
        return jnp.clip(jnp.round(val.astype(jnp.float32)
                                  / sc[i][None, :, None]),
                        -_KV_QMAX, _KV_QMAX).astype(jnp.int8)

    def _load(cache, i, sc):
        if not quant_kv:
            return cache
        return cache.astype(jnp.float32) * sc[i][None, None, :, None]

    def step_fn(tokens, state):
        pos = state["pos"]                                   # [N]
        N = tokens.shape[0]
        ks, vs = [], []

        def attend(i, q, k1, v1):
            # the dense ring: write this position, attend over the
            # cache's valid prefix (<= pos)
            kc = state["k"][i].at[jnp.arange(N), pos].set(
                _store(k1.reshape(N, H, D), i, k_sc))
            vc = state["v"][i].at[jnp.arange(N), pos].set(
                _store(v1.reshape(N, H, D), i, v_sc))
            ks.append(kc)
            vs.append(vc)
            logits = jnp.einsum("nhd,nshd->nhs", q.reshape(N, H, D),
                                _load(kc, i, k_sc)) * scale
            valid = (jnp.arange(max_len)[None, :]
                     <= pos[:, None])[:, None, :]            # [N,1,S]
            logits = jnp.where(valid, logits, -1e9)
            probs = jax.nn.softmax(logits, axis=-1)
            return jnp.einsum("nhs,nshd->nhd", probs,
                              _load(vc, i, v_sc)).reshape(N, hidden)

        x = _gpt_embed(params, tokens, pos, max_pos)         # [N, hidden]
        for i in range(L):
            x = _gpt_block(params, mm, i, x, attend)
        return _gpt_head(params, x), {"k": ks, "v": vs, "pos": pos + 1}

    return step_fn, init_state


def _make_gpt_paged_core(model, page_size: int, pages_per_seq: int, *,
                         kv_cache_dtype=None, kv_scales=None,
                         weight_quant=None, mesh_layout=None):
    """THE paged-KV transformer core behind the serving step builders —
    on one chip and, with a ``mesh_layout`` (a ``ServingMeshLayout``)
    spanning > 1 chip, over the named (tp, sp, data) mesh.

    Returns ``(core, init_pages)`` where ``core(tokens [N], pos [N],
    page_tables [N, M], kv, valid_len=None, with_head=True)`` runs one
    forward over N independent query positions: each lane's new k/v is
    scattered into page ``page_tables[n, pos // P]`` slot ``pos % P`` and
    its attention covers positions ``< pos + 1`` of its page table.  The
    two serving shapes are both this one computation:

    - decode: N = batch lanes, one position per in-flight sequence
      (``page_tables`` differs per lane);
    - chunked prefill: N = chunk positions of ONE sequence
      (``page_tables`` is the same row broadcast N times, per-lane
      ``seq_lens = pos + 1`` gives exact causal masking WITHIN the chunk
      because the whole chunk is scattered before attention runs).

    ``valid_len`` (scalar, traced) masks bucket padding: lanes with
    ``pos >= valid_len`` scatter into the reserved trash page 0 and clamp
    their attention span, so padded lanes can never touch live pages.
    In the ragged layout with native pools, on a TPU and without ``sp``,
    the write is the paged KV write kernel instead
    (``ops/pallas_ops/paged_kv_write.py``): each lane's live rows — a
    prefix of its Q rows at consecutive positions, as the engine plans
    them — go into their pages, whole pages as one copy, and padded rows
    go nowhere.
    ``with_head=False`` skips the [N, V] logits matmul (prefill discards
    logits — the first decode step consumes the last prompt token).

    ``qgroup=Q`` selects the ragged-group layout (ISSUE 18): the N rows
    are G = N // Q lanes of Q query rows each and ``page_tables`` is ONE
    row per lane ([G, M]); the scatter path expands it per row while
    attention takes the grouped form so the ragged kernel pays each
    lane's page DMA once per page, not once per row.  The flat form
    (``qgroup=None``) serves the split builders, on one chip only.

    One ``body`` serves every layout; what only a mesh needs sits behind
    the layout's STATIC degrees.  On one chip (no layout, or one of size
    1) the body is called directly — no ``shard_map``, no ``device_put``,
    no collective is traced.  Over a mesh (ISSUE 19) the same body runs
    under an explicit ``shard_map``: weights enter pre-sharded per
    ``layout.param_spec``, the KV pools per ``page_spec``/``scale_spec``,
    and the partial-softmax exchange is spelled out in code (pmax/psum
    of lse-space stats) rather than left to GSPMD — which is what keeps
    the tp path bitwise identical to the one-chip program and the sp
    merge auditable.

    Quantization (docs/SERVING.md "Quantized serving"):
    ``kv_cache_dtype="int8"`` makes ``init_pages`` return int8 pools
    plus per-page-per-head fp32 scale arrays (``k_scale``/``v_scale``,
    [N, H] per layer); writes quantize in the jitted step and attention
    dequantizes in-register in the paged-attention kernel.  With
    calibrated ``kv_scales`` the scale arrays are CONSTANT (static
    mode); without, they grow per page by scatter-max and the page is
    requantized on scale growth (dynamic mode — the engine resets a
    page's scales when it is reallocated).  ``weight_quant`` routes the
    projection/MLP matmuls through the weight-only int8 kernel.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..ops.pallas_ops import paged_kv_write as kv_write
    from ..ops.pallas_ops.paged_attention import (
        paged_attention as paged_attn,
        ragged_paged_attention as ragged_paged_attn,
        ragged_paged_attention_stats as ragged_stats)

    params, _ = get_state(model)
    L, H, D, hidden, max_pos, _ = _gpt_geometry(model)
    layout = mesh_layout if mesh_layout is not None else ServingMeshLayout()
    tp, sp = int(layout.tp), int(layout.sp)
    tpn, spn = layout.tp_axis, layout.sp_axis
    meshed = layout.size > 1
    if H % tp:
        raise ValueError(
            f"num_heads ({H}) must be divisible by tp ({tp})")
    H_loc = H // tp
    quant_kv = _check_kv_cache_dtype(kv_cache_dtype)
    k_sc, v_sc = _as_layer_scales(kv_scales, L, H)
    if meshed:
        from ..distributed import mesh as mesh_lib

        mesh = mesh_lib.init_mesh(layout.axes())

        def put(v, spec):
            return jax.device_put(v, NamedSharding(mesh, spec))
    else:
        def put(v, spec):
            return v

    # THE one site that gathers what the step programs close over:
    # weights, int8 weights, static KV scales (ROADMAP S4 — weights as
    # arguments — edits here).  Under a mesh they land on-device
    # PRE-SHARDED (tp column shards for qkv/fc1, replicated otherwise):
    # the compiled step's input layouts already match, so no weight
    # moves per dispatch and decode streams each chip's weight shard at
    # that chip's HBM bandwidth.
    cspecs = {"p": {name: layout.param_spec(name) for name in params}}
    consts = {"p": {name: put(v, cspecs["p"][name])
                    for name, v in params.items()}}
    if weight_quant:
        consts["wq"], cspecs["wq"] = {}, {}
        for name, (qv, sv) in weight_quant.items():
            qspec = layout.param_spec(name)
            sspec = P(tpn) if qspec != P() else P()
            consts["wq"][name] = (put(jnp.asarray(qv), qspec),
                                  put(jnp.asarray(sv, jnp.float32), sspec))
            cspecs["wq"][name] = (qspec, sspec)
    if k_sc is not None:
        consts["ksc"] = [put(a, P(tpn)) for a in k_sc]
        consts["vsc"] = [put(a, P(tpn)) for a in v_sc]
        cspecs["ksc"] = cspecs["vsc"] = [P(tpn)] * L

    def init_pages(num_pages: int):
        if num_pages % sp:
            raise ValueError(
                f"num_pages ({num_pages}) must be divisible by sp ({sp})")

        # one DISTINCT buffer per layer/side: the engine donates the
        # pools to the jitted step, and XLA rejects donating one buffer
        # twice (a shared zeros array would alias all 2L entries)
        # stored [pages, P, H*D]: the layout the ragged kernel's page
        # block reads and the step's scatter writes — tile-exact for
        # every (H, D), so no program pads, transposes or copies a pool
        def z():
            dt = jnp.int8 if quant_kv else params["wte.weight"].dtype
            return put(jnp.zeros((num_pages, page_size, H * D), dt),
                       layout.page_spec())

        kv = {"k": [z() for _ in range(L)], "v": [z() for _ in range(L)]}
        if quant_kv:
            # static mode: the calibrated scale broadcast per page (the
            # write path never mutates it); dynamic: the eps floor, grown
            # by scatter-max as pages fill
            def sc(static):
                from ..serving.kv_cache import KV_SCALE_EPS

                if static is None:
                    arr = jnp.full((num_pages, H), KV_SCALE_EPS,
                                   jnp.float32)
                else:
                    arr = jnp.broadcast_to(
                        static[None, :],
                        (num_pages, H)).astype(jnp.float32) + 0
                return put(arr, layout.scale_spec())
            kv["k_scale"] = [sc(k_sc[i] if k_sc else None)
                             for i in range(L)]
            kv["v_scale"] = [sc(v_sc[i] if v_sc else None)
                             for i in range(L)]
        return kv

    def body(consts, tokens, pos, page_tables, vlen, kv, with_head, Q):
        p = consts["p"]
        mm = _make_mm(p, consts.get("wq"))
        ksc, vsc = consts.get("ksc"), consts.get("vsc")
        N = tokens.shape[0]
        row_tables = (page_tables if Q is None
                      else jnp.repeat(page_tables, Q, axis=0))
        x = _gpt_embed(p, tokens, pos, max_pos)
        # positions past the page table width belong to masked lanes too
        page_of = jnp.minimum(pos // page_size, pages_per_seq - 1)
        page_idx = jnp.take_along_axis(row_tables, page_of[:, None],
                                       axis=1)[:, 0]
        slot = pos % page_size
        seq_lens = pos + 1
        if vlen is not None:
            # padded lanes write to the trash page and attend to nothing
            # past the real prompt — live pages stay untouched
            page_idx = jnp.where(pos < vlen, page_idx, 0)
            seq_lens = jnp.minimum(seq_lens, vlen)
        if sp > 1:
            # global -> shard-local page ids: a non-owned row scatters
            # into this shard's reserved trash row (local 0, a global
            # reserved page) and attention masks pages by OWNERSHIP, so
            # each chip holds and streams 1/sp of every sequence's KV
            sp_i = jax.lax.axis_index(spn)
            pages_local = kv["k"][0].shape[0]
            page_idx = jnp.where((page_idx // pages_local) == sp_i,
                                 page_idx % pages_local, 0)
            pt_owner = (page_tables // pages_local) == sp_i
            page_tables = jnp.where(pt_owner, page_tables % pages_local, 0)
        # the ragged layout's live rows are a prefix of each lane's Q rows
        # at consecutive positions (the engine plans them so): one Pallas
        # call a layer writes them as whole pages and live rows.  The row
        # scatter stays where that does not hold or the kernel cannot
        # run: the split programs, int8 pools (scales grow per page), sp
        # (non-owned rows go to the trash page mid-range), the CPU
        write_pages = (Q is not None and not quant_kv and sp == 1
                       and kv_write.routes(kv["k"][0]))
        if write_pages:
            live = pos < vlen if vlen is not None else pos >= 0
            w_first = pos.reshape(N // Q, Q)[:, 0]
            w_live = jnp.sum(live.reshape(N // Q, Q), axis=1,
                             dtype=jnp.int32)
        kv_out = {key: [] for key in kv}

        def attend(i, q, k1, v1):
            # k1/v1 stay [N, H_loc*D]: the projection's rows ARE the
            # pool's rows, written in place on the donated buffer
            kv_write.WRITE_ROUTE_STATS[
                "pallas" if write_pages else "scatter"] += 1
            if quant_kv:
                kc, ks_ = _quant_write_page(
                    kv["k"][i], kv["k_scale"][i], page_idx, slot,
                    k1.reshape(N, H_loc, D), ksc[i] if ksc else None)
                vc, vs_ = _quant_write_page(
                    kv["v"][i], kv["v_scale"][i], page_idx, slot,
                    v1.reshape(N, H_loc, D), vsc[i] if vsc else None)
                kv_out["k_scale"].append(ks_)
                kv_out["v_scale"].append(vs_)
                scales = (ks_, vs_)
            elif write_pages:
                kc, vc = kv_write.paged_kv_write(
                    k1, v1, kv["k"][i], kv["v"][i], page_tables, w_first,
                    w_live)
                scales = ()
            else:
                kc = kv["k"][i].at[page_idx, slot].set(k1)
                vc = kv["v"][i].at[page_idx, slot].set(v1)
                scales = ()
            kv_out["k"].append(kc)
            kv_out["v"].append(vc)
            if Q is None:
                ctx = paged_attn(q.reshape(N, H_loc, D), kc, vc,
                                 page_tables, seq_lens, *scales)
                return ctx.reshape(N, hidden)
            qg = q.reshape(N // Q, Q, H_loc, D)
            sl = seq_lens.reshape(N // Q, Q)
            if sp == 1:
                ctx = ragged_paged_attn(qg, kc, vc, page_tables, sl,
                                        *scales)
            else:
                # partial-softmax exchange: each shard reduces over its
                # OWNED pages only, then the running-max / denominator
                # stats merge across sp in lse space (the
                # ring_attention.py recipe)
                o, lse = ragged_stats(qg, kc, vc, page_tables, sl,
                                      pt_owner, *scales)
                mx = jax.lax.pmax(lse, spn)
                w = jnp.exp(lse - mx)
                num = jax.lax.psum(o * w[..., None], spn)
                den = jax.lax.psum(w, spn)
                ctx = num / jnp.maximum(den, 1e-30)[..., None]
            if tp > 1:
                ctx = jax.lax.all_gather(ctx.reshape(N, H_loc, D), tpn,
                                         axis=1, tiled=True)
            return ctx.reshape(N, hidden)

        for i in range(L):
            x = _gpt_block(p, mm, i, x, attend,
                           tp_axis=tpn if tp > 1 else None)
        return (_gpt_head(p, x) if with_head else None), kv_out

    def core(tokens, pos, page_tables, kv, valid_len=None, with_head=True,
             qgroup=None):
        Q = None if qgroup is None else int(qgroup)
        if not meshed:
            return body(consts, tokens, pos, page_tables, valid_len, kv,
                        with_head, Q)
        if Q is None:
            raise NotImplementedError(
                "the mesh-sharded paged core serves the unified ragged "
                "layout only (the mesh engine runs ragged=True)")
        has_vl = valid_len is not None

        def shard(consts_l, tokens, pos, page_tables, vlen, kv_l):
            logits, kv_out = body(consts_l, tokens, pos, page_tables,
                                  vlen if has_vl else None, kv_l,
                                  with_head, Q)
            return (logits, kv_out) if with_head else kv_out

        kvs = layout.kv_spec(kv)
        # check_vma=False for the whole core, because the one typing
        # problem cannot be opted out of alone: the logits are replicated
        # in VALUE (every shard all-gathers the same context), but jax
        # types an all_gather result as varying, jax 0.9 has no public
        # invariant all_gather (`all_gather_invariant` lives in jax._src)
        # and pcast only goes invariant -> varying.  The value assumption
        # is pinned by the byte-identity tests in test_serving_mesh.py.
        f = jax.shard_map(shard, mesh=mesh,
                          in_specs=(cspecs, P(), P(), P(), P(), kvs),
                          out_specs=(P(), kvs) if with_head else kvs,
                          check_vma=False)
        out = f(consts, tokens, pos, page_tables,
                valid_len if has_vl else jnp.zeros((), jnp.int32), kv)
        return out if with_head else (None, out)

    return core, init_pages


def make_gpt_paged_decode_step(model, page_size: int, pages_per_seq: int, *,
                               kv_cache_dtype=None, kv_scales=None,
                               weight_quant=None):
    """Paged-KV variant of ``make_gpt_decode_step`` — the serving engine's
    decode step (paddle_tpu/serving/engine.py).

    Instead of a dense per-sequence [B, max_len, H, D] ring, KV lives in a
    GLOBAL pool of fixed-size pages shared by all in-flight sequences; each
    sequence owns a page-table row of page ids.  Builds
    (step_fn, init_pages):

    ``init_pages(num_pages)`` -> {"k": [L x [N, P, H*D]], "v": ...}

    ``step_fn(tokens [B], pos [B], page_tables [B, M], kv)`` ->
    (logits [B, V], kv') — one decode position per call: the new k/v is
    scattered into page ``page_tables[b, pos // P]`` slot ``pos % P`` and
    attention runs over the sequence's pages masked to length pos+1 via
    ``ops.attention`` paged attention (Pallas kernel on TPU, XLA gather
    reference on CPU).

    Page-id 0 is the reserved trash page: inactive batch lanes (pos 0,
    all-zero page table) and positions past a sequence's allocation
    scatter there harmlessly and are never attended to (seq_len masks
    them), so the step needs no per-lane branching and its shape — hence
    its trace — depends only on the batch bucket.

    ``kv_cache_dtype``/``kv_scales``/``weight_quant`` select the int8
    serving path (see ``_make_gpt_paged_core``).
    """
    core, init_pages = _make_gpt_paged_core(
        model, page_size, pages_per_seq, kv_cache_dtype=kv_cache_dtype,
        kv_scales=kv_scales, weight_quant=weight_quant)

    def step_fn(tokens, pos, page_tables, kv):
        return core(tokens, pos, page_tables, kv)

    return step_fn, init_pages


def make_gpt_paged_prefill_step(model, page_size: int, pages_per_seq: int, *,
                                kv_cache_dtype=None, kv_scales=None,
                                weight_quant=None):
    """Chunked parallel prefill over the paged KV cache — C prompt tokens
    per device program instead of a token-at-a-time scan, so a prompt
    costs O(P / C) dispatches instead of O(P) sequential steps.

    Builds ``(chunk_fn, init_pages)``:

    ``chunk_fn(tokens [C], positions [C], page_table_row [M],
    valid_len (), kv) -> kv'`` teacher-forces one chunk: all C k/v pairs
    are scattered into the sequence's pages first, then every position
    attends over the pages with ``seq_lens = pos + 1`` — exact causal
    attention within the chunk AND over all previously-prefilled chunks,
    through the same ragged paged-attention primitive the decode step
    uses (Pallas kernel on TPU, XLA gather reference on CPU).  No logits
    head: prefill output is the KV state, the first decode step consumes
    the last prompt token (mirroring ``generate``).

    ``valid_len`` masks bucket padding (positions >= valid_len scatter to
    the trash page and are never attended), so chunk sizes can be pow2
    buckets (utils.bucketing.chunk_schedule) without junk escaping into
    live pages.
    """
    core, init_pages = _make_gpt_paged_core(
        model, page_size, pages_per_seq, kv_cache_dtype=kv_cache_dtype,
        kv_scales=kv_scales, weight_quant=weight_quant)

    def chunk_fn(tokens, positions, page_table_row, valid_len, kv):
        C = tokens.shape[0]
        tables = jnp.broadcast_to(page_table_row[None, :],
                                  (C, page_table_row.shape[0]))
        _, kv = core(tokens, positions, tables, kv,
                     valid_len=valid_len, with_head=False)
        return kv

    return chunk_fn, init_pages


def make_gpt_paged_fused_decode_step(model, page_size: int,
                                     pages_per_seq: int, num_steps: int, *,
                                     kv_cache_dtype=None, kv_scales=None,
                                     weight_quant=None,
                                     with_guard: bool = False):
    """Fused K-step greedy decode: one device program advances every lane
    ``num_steps`` positions through a ``lax.fori_loop`` (KV pools carried
    in-place through the loop), returning all K tokens in one [K, B]
    transfer — K fewer dispatches and K fewer host round-trips per token
    when the engine knows no admission can interleave.

    Builds ``(fused_fn, init_pages)``:

    ``fused_fn(tokens [B], pos [B], page_tables [B, M], kv) ->
    (out_tokens [K, B], tokens' [B], pos' [B], kv')`` — greedy argmax is
    fed back inside the loop, so the emitted stream is identical to K
    single steps.  EOS cannot retire a lane mid-loop; the engine drops
    post-EOS tokens on host (the one-step-lag rule, just K steps wide)
    and must pre-reserve pages covering ``pos + K`` for every live lane.

    ``with_guard=True`` (ISSUE 13 numeric guards) folds a per-lane
    logit-finiteness verdict INTO the returned token matrix: a
    position whose logits were non-finite comes back NEGATIVE-PACKED
    (``-1 - tok``) — in-band, so the guard costs no extra outputs or
    host transfers and guarded steady decode stays
    transfer-guard-clean.  The clean argmax still feeds back inside
    the loop (device state never sees a packed id).
    """
    if num_steps < 1:
        raise ValueError("num_steps must be >= 1")
    core, init_pages = _make_gpt_paged_core(
        model, page_size, pages_per_seq, kv_cache_dtype=kv_cache_dtype,
        kv_scales=kv_scales, weight_quant=weight_quant)

    def fused_fn(tokens, pos, page_tables, kv):
        B = tokens.shape[0]
        out0 = jnp.zeros((num_steps, B), jnp.int32)

        def body(j, carry):
            tok, p, kv, out = carry
            logits, kv = core(tok, p, page_tables, kv)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            row = nxt
            if with_guard:
                fin = jnp.all(jnp.isfinite(logits), axis=-1)
                row = jnp.where(fin, nxt, -1 - nxt)
            return nxt, p + 1, kv, out.at[j].set(row)

        tok, p, kv, out = jax.lax.fori_loop(
            0, num_steps, body, (tokens, pos, kv, out0))
        return out, tok, p, kv

    return fused_fn, init_pages


def make_gpt_paged_spec_verify_step(model, page_size: int,
                                    pages_per_seq: int, num_steps: int, *,
                                    sequential: bool = False,
                                    kv_cache_dtype=None, kv_scales=None,
                                    weight_quant=None,
                                    with_guard: bool = False):
    """Speculative-decoding verifier: teacher-force ``num_steps`` tokens
    per lane through the paged core in ONE device program and return the
    greedy argmax at every position — the drafted continuation is
    accepted exactly as far as it matches (serving/spec_decode.py owns
    the accept rule; this is just the batched primitive).

    Builds ``(verify_fn, init_pages)``:

    ``verify_fn(tokens [K, B], pos [B], page_tables [B, M], kv) ->
    (out [K, B], kv')`` — row ``tokens[j]`` is the input every lane
    consumes at position ``pos + j`` (``tokens[0]`` is the lane's
    current next_token, rows 1.. the drafted continuation, junk-padded
    past each lane's real draft), ``out[j]`` the verifier's argmax at
    that position.  K/V for all K positions is written into the lanes'
    pages exactly like the fused K-step path — positions past the
    accepted prefix hold junk that the next real decode write overwrites
    BEFORE any attention can reach it (``seq_lens`` masks it until
    then), so native and int8_static KV need no device-side rollback.

    ``sequential=False`` (the throughput shape) runs all B*K positions
    as one ragged chunked-prefill-style forward — the weight set streams
    from HBM ONCE per K tokens instead of once per token, which is the
    whole speculative-decoding bandwidth win.  ``sequential=True`` runs
    a ``lax.fori_loop`` of K single-position steps (teacher-forced
    ``make_gpt_paged_fused_decode_step``): required by int8_dynamic KV,
    where per-page scale growth couples positions within a page — the
    sequential schedule reproduces the plain decode loop's progressive
    quantization bit for bit (docs/SERVING.md "Speculative decoding").

    ``with_guard=True`` (ISSUE 13) folds the per-lane logit-finiteness
    verdict INTO the returned ``out`` matrix — a non-finite position's
    token comes back negative-packed (``-1 - tok``), in-band like the
    decode step's, so the verifier inherits the guard at zero extra
    outputs.
    """
    if num_steps < 2:
        raise ValueError("num_steps must be >= 2 (1 is plain decode)")
    core, init_pages = _make_gpt_paged_core(
        model, page_size, pages_per_seq, kv_cache_dtype=kv_cache_dtype,
        kv_scales=kv_scales, weight_quant=weight_quant)
    K = int(num_steps)

    def _pack(nxt, logits):
        if not with_guard:
            return nxt
        fin = jnp.all(jnp.isfinite(logits), axis=-1)
        return jnp.where(fin, nxt, -1 - nxt)

    if sequential:
        def verify_fn(tokens, pos, page_tables, kv):
            B = pos.shape[0]
            out0 = jnp.zeros((K, B), jnp.int32)

            def body(j, carry):
                kv, out = carry
                logits, kv = core(tokens[j], pos + j, page_tables, kv)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return kv, out.at[j].set(_pack(nxt, logits))

            kv, out = jax.lax.fori_loop(0, K, body, (kv, out0))
            return out, kv
    else:
        def verify_fn(tokens, pos, page_tables, kv):
            B = pos.shape[0]
            # one ragged forward over B*K rows: row (b, j) consumes
            # tokens[j, b] at position pos[b] + j against lane b's page
            # table — the chunked-prefill broadcast trick, per lane.
            # Causality within the draft comes for free: all K k/v
            # slabs scatter first, then row (b, j) attends with
            # seq_lens = pos[b] + j + 1.
            toks = tokens.T.reshape(-1)                       # [B*K]
            posf = (pos[:, None]
                    + jnp.arange(K, dtype=pos.dtype)).reshape(-1)
            tables = jnp.repeat(page_tables, K, axis=0)       # [B*K, M]
            logits, kv = core(toks, posf, tables, kv)
            out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return _pack(out, logits).reshape(B, K).T, kv

    return verify_fn, init_pages


def make_gpt_paged_ragged_step(model, page_size: int, pages_per_seq: int, *,
                               kv_cache_dtype=None, kv_scales=None,
                               weight_quant=None, with_guard: bool = False,
                               mesh_layout=None):
    """Unified ragged step (ISSUE 18): ONE device program carries a mixed
    batch of {steady-decode, chunked-prefill, spec-verify} lanes, each
    lane a group of Q query rows against its single page-table row, so
    the engine stops serializing prefill chunks ahead of decode ticks.

    Builds ``(ragged_fn, init_pages)``:

    ``ragged_fn(state_tok [B], state_pos [B], page_tables [B, M],
    rows_tok [B, Q], rows_pos [B, Q], row_valid [B, Q], advance [B], kv)
    -> (out_rows [B, Q], out_dec [B], state_tok' [B], state_pos' [B],
    kv')``.

    Per lane ``b``:

    - ``advance[b] > 0`` — a DECODE lane: row 0's token/position are
      taken from the device-resident ``state_tok``/``state_pos`` (the
      greedy feedback loop never round-trips the host) and the lane's
      state advances to (argmax, pos + 1).  With ``row_valid[b, 0] ==
      RAGGED_NO_LIMIT`` and Q == 1 this is bit-identical to the split
      ``serving.decode`` program: the padding clamps are exact integer
      identities and the attention reduces to the same flat rows.
    - ``advance[b] == 0`` — a PREFILL-CHUNK or SPEC-VERIFY lane: rows
      carry host-provided (token, position, valid_len) triples exactly
      as the split ``serving.prefill`` / ``serving.spec_verify``
      programs would see them; device state is untouched.
    - junk rows (bucket padding past a lane's chunk) carry
      ``row_valid == 0``: they scatter into the reserved trash page and
      attend to nothing, so live pages can never see padding.

    ``out_rows`` is the greedy argmax at every row (spec-verify accept
    rule reads it), ``out_dec`` its row-0 column (the decode stream).
    ``with_guard=True`` negative-packs non-finite rows in-band, exactly
    like the split programs; the clean argmax still feeds device state.

    ``mesh_layout`` (ISSUE 19) runs the same core under the mesh: same
    host-visible contract, device state sharded per the layout — the
    engine's one-mixed-batch-program-per-step dispatch drives tp*sp
    chips.
    """
    core, init_pages = _make_gpt_paged_core(
        model, page_size, pages_per_seq, kv_cache_dtype=kv_cache_dtype,
        kv_scales=kv_scales, weight_quant=weight_quant,
        mesh_layout=mesh_layout)

    def ragged_fn(state_tok, state_pos, page_tables, rows_tok, rows_pos,
                  row_valid, advance, kv):
        B, Q = rows_tok.shape
        live = advance > 0
        eff_tok = rows_tok.at[:, 0].set(
            jnp.where(live, state_tok, rows_tok[:, 0]))
        eff_pos = rows_pos.at[:, 0].set(
            jnp.where(live, state_pos, rows_pos[:, 0]))
        logits, kv = core(eff_tok.reshape(-1), eff_pos.reshape(-1),
                          page_tables, kv,
                          valid_len=row_valid.reshape(-1), qgroup=Q)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = nxt
        if with_guard:
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
            out = jnp.where(fin, nxt, -1 - nxt)
        out = out.reshape(B, Q)
        clean0 = nxt.reshape(B, Q)[:, 0]
        new_tok = jnp.where(live, clean0, state_tok)
        new_pos = jnp.where(live, state_pos + 1, state_pos)
        return out, out[:, 0], new_tok, new_pos, kv

    return ragged_fn, init_pages


def prefill(step_fn, state, prompt: jnp.ndarray):
    """Feed the prompt through the cache (teacher-forced scan); returns
    (state_after_prompt, logits_of_last_position [B, V])."""

    def body(st, tok):
        logits, st = step_fn(tok, st)
        return st, logits

    state, logits_seq = jax.lax.scan(body, state,
                                     jnp.moveaxis(prompt, 1, 0))
    return state, logits_seq[-1]


def generate(model, input_ids, max_new_tokens: int = 32, end_id: int = 0,
             decode_strategy: str = "greedy", num_beams: int = 4,
             length_penalty: float = 0.0, quant=None):
    """GPTModel text generation (the serving decode path).

    input_ids: [B, P] prompt (np/jnp int).  Returns [B, T] (greedy) or
    [B, K, T] (beam_search) continuations, T = max_new_tokens.

    ``quant``: an export from ``slim.export_serving_quant`` — runs the
    decode with the int8 KV cache and/or weight-only int8 matmuls it
    describes (the reference stream the quantized serving engine is
    pinned byte-identical to; int8 KV here requires the export's
    calibrated kv_scales)."""
    from ..nn.decode import beam_search_decode, greedy_search_decode
    from ..tensor import Tensor
    from ..utils.profiler import RecordEvent

    ids = input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(np.asarray(input_ids))
    ids = ids.astype(jnp.int32)
    B, P = ids.shape
    max_len = P + max_new_tokens + 1
    max_pos = _gpt_geometry(model)[4]
    if P + max_new_tokens > max_pos:
        # past the wpe table the gather would silently clamp positions —
        # degraded text with no error (review r4)
        raise ValueError(
            f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"the model's max_seq_len ({max_pos})")
    qkw = {}
    if quant is not None:
        if quant.get("kv_cache_dtype") == "int8":
            qkw.update(kv_cache_dtype="int8",
                       kv_scales=quant.get("kv_scales"))
        if quant.get("weight_dtype") == "int8":
            qkw.update(weight_quant=quant.get("weights"))
    step_fn, init_state = make_gpt_decode_step(model, max_len, **qkw)

    if decode_strategy == "greedy":
        with RecordEvent("text.generation", strategy="greedy",
                         batch=B, prompt_len=P):
            state = init_state(B)
            # prefill all but the last prompt token; the decode loop's
            # first step consumes the last one and emits new token #1
            if P > 1:
                with RecordEvent("text.generation/prefill"):
                    state, _ = prefill(step_fn, state, ids[:, :-1])
            with RecordEvent("text.generation/decode"):
                out_ids, scores = greedy_search_decode(
                    step_fn, state, batch_size=B, max_len=max_new_tokens,
                    bos_id=ids[:, -1], end_id=end_id)
            return Tensor(out_ids), Tensor(scores)
    if decode_strategy == "beam_search":
        K = num_beams
        # prefill ONCE per sequence (batch B), then expand the cache to
        # the B*K beam lanes — K identical prompt forwards would be pure
        # waste (review r4)
        with RecordEvent("text.generation", strategy="beam_search",
                         batch=B, prompt_len=P, num_beams=K):
            state_b = init_state(B)
            if P > 1:
                with RecordEvent("text.generation/prefill"):
                    state_b, _ = prefill(step_fn, state_b, ids[:, :-1])
            state = jax.tree_util.tree_map(
                lambda s: jnp.repeat(s, K, axis=0), state_b)
            lanes = jnp.repeat(ids, K, axis=0)               # [B*K, P]
            with RecordEvent("text.generation/decode"):
                res = beam_search_decode(
                    step_fn, state, batch_size=B, beam_size=K,
                    max_len=max_new_tokens,
                    bos_id=lanes[:, -1].reshape(B, K), end_id=end_id,
                    length_penalty=length_penalty)
            return Tensor(res.ids), Tensor(res.scores)
    raise ValueError(
        f"decode_strategy must be 'greedy' or 'beam_search', "
        f"got {decode_strategy!r}")
