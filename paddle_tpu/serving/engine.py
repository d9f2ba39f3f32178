"""ServingEngine — the pipelined continuous-batching core.

``add_request`` enqueues, ``step`` runs one scheduler iteration,
``drain`` steps until idle.  The hot path is ASYNCHRONOUS: decode state
(tokens / positions / page tables) lives on device between steps,
``step`` dispatches decode step N and only then consumes step N-1's
tokens (double-buffered ``jax.device_get``), so host-side scheduling,
EOS scanning and metrics hide behind device compute instead of adding to
the critical path.  ``sync_mode=True`` restores the PR-1
dispatch-then-consume-immediately behavior; either way the token stream
is identical to ``text.generation.generate(decode_strategy="greedy")``.

Execution model
---------------
- **Chunked parallel prefill**: admission teacher-forces ``prompt[:-1]``
  through ``text.generation.make_gpt_paged_prefill_step`` — a whole
  chunk of up to ``prefill_chunk`` positions per device program (causal
  within the chunk via per-query ragged seq_lens, paged-KV writes), so a
  prompt costs O(P / C) dispatches instead of the former token-at-a-time
  scan's O(P) sequential steps.  Chunk shapes come from
  ``utils.bucketing.chunk_schedule`` (full chunks + one pow2 tail), so
  the trace set stays {pow2 <= C}.
- **Device-resident decode state**: tokens/pos/page-tables are jax
  arrays reused across steps; the decode program itself advances them
  (argmax feed-back, pos+1).  Host events touch only deltas: an
  admission uploads one lane (token, pos, table row), retirement /
  preemption zeroes one lane, page growth re-uploads one table row.
  The per-step numpy rebuild + full H2D upload of the synchronous
  engine is gone; in steady state a step performs no implicit host
  transfer at all (``jax.transfer_guard``-clean, see
  tests/test_serving_async.py).
- **Dispatch-ahead decode**: one decode step stays in flight; EOS and
  budget retirement decisions lag one step (the lagged lane decodes one
  junk token into its still-allocated pages — harmless, dropped on
  host), which is invisible in the emitted stream.  When no admissions
  are pending and every lane has >= ``fused_steps`` budget left, a
  fused K-step ``lax.fori_loop`` decode
  (``make_gpt_paged_fused_decode_step``) amortizes K tokens per dispatch
  and per host transfer (pages for pos+K are reserved up front;
  exhaustion falls back to single steps).
- The decode batch is padded to a pow2 lane bucket, so jax.jit RETRACES
  ONLY ON BUCKET CHANGE; inactive lanes carry pos=0 and an all-zero page
  table (their scatter lands in the reserved trash page 0), so no
  per-lane branching exists on device.  Greedy decoding only.
"""
from __future__ import annotations

import re
import time
import weakref
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.concurrency import OrderedLock
from ..framework.errors import (AlreadyExistsError, InternalError,
                                InvalidArgumentError)
from ..profiler.flight_recorder import (EV_ADMITTED, EV_FIRST_TOKEN,
                                        EV_PREFILL_CHUNK, EV_PREFIX_HIT,
                                        EV_SPECULATED)
from ..profiler.flight_recorder import recorder as flight
from ..profiler.jit_cost import cost_registry, profiled_jit
from ..testing.chaos import chaos_site
from ..utils.bucketing import chunk_schedule, next_pow2, smallest_bucket
from ..utils.profiler import RecordEvent
from .kv_cache import (KV_SCALE_EPS, PagedKVCache, dequantize_kv_page,
                       quantize_kv_page)
from .metrics import ServingMetrics
from .resilience import EngineSnapshot
from .scheduler import Request, Scheduler, Sequence

__all__ = ["ServingEngine", "create_serving_engine", "whole_pool_relayouts",
           "aliased_arguments"]


def whole_pool_relayouts(program_text: str, num_pages: int,
                         page_size: int) -> List[str]:
    """The ``pad`` / ``copy`` / ``transpose`` instructions of a step
    program whose RESULT is pool-shaped (leads with ``[num_pages,
    page_size, ...]``; the int8 ``[num_pages, H]`` scale rows are not) —
    i.e. that rewrite a whole KV pool — as ``"<op> <result type>"``
    strings.  Reads both texts a ``jax.stages`` object gives:
    ``Lowered.as_text()`` (StableHLO: what the program's own code asks
    for) and ``Compiled.as_text()`` (optimised HLO: what the compiler
    made of it, fusion bodies included).  The pools are stored in the
    layout the ragged kernel reads so that this list is EMPTY for every
    step program (ISSUE 26: such ops were 55% of the serve step on the
    v5e); ``chip_smoke.py``, tests/test_serving_ragged.py and
    tests/test_pallas_tpu_lowering.py hold the programs to it."""
    n, p = int(num_pages), int(page_size)
    hlo = re.findall(
        rf"= (\w+\[{n},{p},[\d,]+\])\S* (pad|copy|copy-start|transpose)\(",
        program_text)
    mlir = re.findall(
        rf"stablehlo\.(pad|transpose)\b[^\n]*-> (tensor<{n}x{p}x\w+>)",
        program_text)
    return [f"{op} {shape}" for shape, op in hlo] \
        + [f"{op} {shape}" for op, shape in mlir]


def aliased_arguments(compiled_text: str) -> int:
    """How many arguments of a compiled program (``Compiled.as_text()``)
    are aliased to an output — updated in place on their donated buffer.
    A step program must alias every KV pool (and int8 scale array)."""
    return compiled_text.split("\n", 1)[0].count("-alias)")


# --- shared compiled-program bundles -----------------------------------------
# Replicas of one serving configuration (the frontend's fleet, a test's
# engine-per-scenario) would otherwise each rebuild and RECOMPILE the
# identical jitted step programs — on a 2-replica frontend that doubles
# every XLA compile for zero benefit.  Bundles are keyed per MODEL
# OBJECT (weak — dropping the model drops its programs) and, inside,
# by parameter identity plus every knob the traced programs close over:
# jax arrays are immutable, so training/replacing a param changes its
# id and misses the cache.  Page POOLS stay per-engine (init_pages
# builds fresh buffers each call); only the pure compiled programs and
# the derived int8 weights are shared.
_PROGRAM_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_PROGRAM_LOCK = OrderedLock("serving.programs")


def _shared_programs(model, *, page_size: int, pages_per_seq: int,
                     kv_cache_dtype, weight_dtype, kv_scales, weights,
                     fused_steps: int, spec_steps: int = 0,
                     spec_sequential: bool = False,
                     numeric_guards: bool = True,
                     mesh_layout=None) -> dict:
    from ..jit.functional import get_state
    from ..text.generation import (make_gpt_paged_decode_step,
                                   make_gpt_paged_prefill_step,
                                   make_gpt_paged_ragged_step)

    params, _ = get_state(model)
    # BASE key deliberately excludes fused_steps/spec_steps: the
    # decode/prefill/maintenance programs are identical across those
    # configs, so a fused or speculative engine reuses the plain
    # engine's compiles and only its fused/spec_verify program is
    # per-variant (cached under the base bundle's "_variants")
    key = (page_size, pages_per_seq, kv_cache_dtype, weight_dtype,
           numeric_guards, mesh_layout,
           None if kv_scales is None else id(kv_scales),
           None if weights is None else id(weights),
           tuple(sorted((k, id(v)) for k, v in params.items())))
    # the ids above are only stable while the keyed objects are ALIVE —
    # retain them with the bundle so a freed export/param can never be
    # id-recycled into a stale cache hit (stored under "_key_refs" in
    # the bundle below)
    key_refs = (kv_scales, weights, list(params.values()))
    with _PROGRAM_LOCK:
        per_model = _PROGRAM_CACHE.get(model)
        if per_model is None:
            per_model = _PROGRAM_CACHE[model] = {}
        base = per_model.get(key)
    if base is not None:
        return _with_variants(base, model, page_size, pages_per_seq,
                              kv_cache_dtype, kv_scales, fused_steps,
                              spec_steps, spec_sequential,
                              numeric_guards)

    weight_quant = weights
    if weight_dtype == "int8" and weight_quant is None:
        from ..slim.serving_export import quantize_gpt_weights

        weight_quant = quantize_gpt_weights(model)
    if weight_quant is not None:
        # ONE device copy shared by the decode/prefill/fused step
        # builders (jnp.asarray is a no-op on jax arrays, so the
        # builders' own conversion reuses these buffers)
        weight_quant = {
            name: (jnp.asarray(q), jnp.asarray(s, jnp.float32))
            for name, (q, s) in weight_quant.items()}
    qkw = dict(kv_cache_dtype=kv_cache_dtype, kv_scales=kv_scales,
               weight_quant=weight_quant)

    # the pools come from the builder that knows the mesh layout; mesh
    # engines run ragged-only, so the split decode/prefill programs are
    # never traced there (profiled_jit is lazy)
    step_fn, _ = make_gpt_paged_decode_step(
        model, page_size, pages_per_seq, **qkw)
    prefill_fn, _ = make_gpt_paged_prefill_step(
        model, page_size, pages_per_seq, **qkw)
    ragged_fn, init_pages = make_gpt_paged_ragged_step(
        model, page_size, pages_per_seq, with_guard=numeric_guards,
        mesh_layout=mesh_layout, **qkw)

    def _decode(tokens, pos, page_tables, kv):
        logits, kv = step_fn(tokens, pos, page_tables, kv)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # the program advances its own state: argmax feeds back as
        # the next input token, pos steps forward — nothing for the
        # host to rebuild or upload between steady-state steps
        if numeric_guards:
            # ISSUE 13 device-side guard: the per-lane logit-finiteness
            # verdict is folded INTO the token array the host already
            # consumes — a non-finite lane's token comes back
            # NEGATIVE-PACKED (-1 - tok, never emitted anyway: it is
            # an argmax over NaN).  Zero extra host transfers, zero
            # extra outputs: guarded steady decode stays
            # transfer-guard- and compile_budget(0)-clean.  The clean
            # argmax still feeds back on device so the device state
            # never sees a packed id.
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
            return (nxt, jnp.where(fin, nxt, -1 - nxt)), pos + 1, kv
        return nxt, pos + 1, kv

    def _lane_set(tokens, pos, page_tables, lane, tok, p, row):
        return (tokens.at[lane].set(tok), pos.at[lane].set(p),
                page_tables.at[lane].set(row))

    def _row_set(page_tables, lane, row):
        return page_tables.at[lane].set(row)

    # jit caches per shape: decode retraces per lane bucket, prefill
    # per chunk bucket — both change rarely by construction.  The kv
    # pools are donated: the engine reassigns self._kv from the result
    # right after each call, letting XLA alias the .at[].set update
    # in place instead of copying every layer's page pool per token
    # (platforms without donation support just warn and copy).
    # profiled_jit attributes FLOPs/bytes + compile count/time to
    # "serving.*" names in profiler.cost_registry.
    progs = {
        "_key_refs": key_refs,
        "init_pages": init_pages,
        "weight_quant": weight_quant,
        "decode": profiled_jit("serving.decode", _decode,
                               donate_argnums=(3,)),
        "prefill": profiled_jit("serving.prefill", prefill_fn,
                                donate_argnums=(4,)),
        # the unified mixed-batch program (ISSUE 18): decode, prefill
        # chunks and spec verify all ride ONE dispatch.  In the BASE
        # bundle, not a variant — replicas and plain/spec mixes of one
        # config all share its compiles, and a ragged engine never
        # compiles the split decode/prefill/spec programs at all
        # (profiled_jit traces lazily).  Retraces only on (lane bucket,
        # row bucket) change, like decode x prefill today.
        "ragged": profiled_jit("serving.ragged_step", ragged_fn,
                               donate_argnums=(7,)),
        # NOT donated: self._tokens aliases the newest _Pending entry's
        # handle (single-step dispatch returns one buffer for both), so
        # donating it into a lane clear would delete tokens still
        # awaiting consumption — the arrays are [bucket] ints, copying
        # is nothing
        "lane_set": profiled_jit("serving.lane_update", _lane_set),
        "row_set": profiled_jit("serving.table_update", _row_set),
        # fused/spec_verify programs are PER-VARIANT (keyed by their
        # step counts) and live in this sub-cache; the returned view
        # carries the requested variant under "fused"/"spec_verify"
        "_variants": {},
        "scale_reset": None,
    }
    if kv_cache_dtype == "int8" and kv_scales is None:
        def _scale_reset(kv, rows):
            # rows: [R] page ids (pow2-padded with the trash page 0 —
            # resetting its scale is harmless); back to the eps floor
            # so a reallocated page quantizes from scratch
            out = dict(kv)
            out["k_scale"] = [s.at[rows].set(KV_SCALE_EPS)
                              for s in kv["k_scale"]]
            out["v_scale"] = [s.at[rows].set(KV_SCALE_EPS)
                              for s in kv["v_scale"]]
            return out

        progs["scale_reset"] = profiled_jit("serving.kv_scale_reset",
                                            _scale_reset,
                                            donate_argnums=(0,))

    # --- resilience: snapshot gather / restore scatter ---------------
    # page payloads move as [R, P, H*D] blocks per layer/side — whole
    # pages in the pools' stored layout, so gather/put/cow are row
    # copies that never relayout a pool; rows
    # are pow2-padded with the trash page 0 so the trace set stays
    # {pow2} (padding writes zeros into the trash page — harmless by
    # the trash-page convention)
    def _page_gather(kv, rows):
        out = {"k": [jnp.take(p, rows, axis=0) for p in kv["k"]],
               "v": [jnp.take(p, rows, axis=0) for p in kv["v"]]}
        if "k_scale" in kv:
            out["k_scale"] = [jnp.take(s, rows, axis=0)
                              for s in kv["k_scale"]]
            out["v_scale"] = [jnp.take(s, rows, axis=0)
                              for s in kv["v_scale"]]
        return out

    def _page_put(kv, rows, payload):
        out = dict(kv)
        out["k"] = [p.at[rows].set(d)
                    for p, d in zip(kv["k"], payload["k"])]
        out["v"] = [p.at[rows].set(d)
                    for p, d in zip(kv["v"], payload["v"])]
        if "k_scale" in payload:
            out["k_scale"] = [s.at[rows].set(d) for s, d in
                              zip(kv["k_scale"], payload["k_scale"])]
            out["v_scale"] = [s.at[rows].set(d) for s, d in
                              zip(kv["v_scale"], payload["v_scale"])]
        return out

    progs["page_gather"] = profiled_jit("serving.page_gather",
                                        _page_gather)
    progs["page_put"] = profiled_jit("serving.page_restore",
                                     _page_put, donate_argnums=(0,))

    # --- prefix cache: copy-on-write page copy (ISSUE 10) ------------
    # device-to-device: one page's payload (every layer/side, scale
    # rows included) duplicated from src to dst without a host round
    # trip — the write half of COW divergence.  src/dst are () int32
    # device scalars, so the trace is shape-stable (compiles once).
    def _page_cow(kv, src, dst):
        out = dict(kv)
        for side in ("k", "v"):
            out[side] = [p.at[dst].set(p[src]) for p in kv[side]]
        if "k_scale" in kv:
            out["k_scale"] = [s.at[dst].set(s[src])
                              for s in kv["k_scale"]]
            out["v_scale"] = [s.at[dst].set(s[src])
                              for s in kv["v_scale"]]
        return out

    progs["page_cow"] = profiled_jit("serving.page_cow", _page_cow,
                                     donate_argnums=(0,))
    with _PROGRAM_LOCK:
        # a racing duplicate build is harmless — first one in wins
        base = per_model.setdefault(key, progs)
    return _with_variants(base, model, page_size, pages_per_seq,
                          kv_cache_dtype, kv_scales, fused_steps,
                          spec_steps, spec_sequential, numeric_guards)


def _with_variants(base: dict, model, page_size: int, pages_per_seq: int,
                   kv_cache_dtype, kv_scales, fused_steps: int,
                   spec_steps: int, spec_sequential: bool,
                   numeric_guards: bool) -> dict:
    """Shallow view over a base program bundle with the requested
    fused/spec_verify variant programs filled in (built once per
    (steps, schedule) and cached under ``base["_variants"]`` — a
    fused_steps=4 engine shares every base compile with a plain one)."""
    from ..text.generation import (make_gpt_paged_fused_decode_step,
                                   make_gpt_paged_spec_verify_step)

    qkw = dict(kv_cache_dtype=kv_cache_dtype, kv_scales=kv_scales,
               weight_quant=base["weight_quant"])
    out = dict(base)
    out["fused"] = None
    out["spec_verify"] = None
    if fused_steps > 1:
        vkey = ("fused", fused_steps)
        with _PROGRAM_LOCK:
            prog = base["_variants"].get(vkey)
        if prog is None:
            fused_fn, _ = make_gpt_paged_fused_decode_step(
                model, page_size, pages_per_seq, fused_steps,
                with_guard=numeric_guards, **qkw)
            prog = profiled_jit("serving.decode_fused", fused_fn,
                               donate_argnums=(3,))
            with _PROGRAM_LOCK:
                prog = base["_variants"].setdefault(vkey, prog)
        out["fused"] = prog
    if spec_steps > 1:
        # speculative decoding (ISSUE 12): one dispatch teacher-forces
        # K tokens per lane — the weight set streams from HBM once per
        # K positions.  int8_dynamic engines get the sequential
        # schedule (per-page scale growth must replay the plain decode
        # loop's progressive quantization exactly).
        vkey = ("spec", spec_steps, spec_sequential)
        with _PROGRAM_LOCK:
            prog = base["_variants"].get(vkey)
        if prog is None:
            verify_fn, _ = make_gpt_paged_spec_verify_step(
                model, page_size, pages_per_seq, spec_steps,
                sequential=spec_sequential, with_guard=numeric_guards,
                **qkw)
            prog = profiled_jit("serving.spec_verify", verify_fn,
                                donate_argnums=(3,))
            with _PROGRAM_LOCK:
                prog = base["_variants"].setdefault(vkey, prog)
        out["spec_verify"] = prog
    return out


class _Pending:
    """One in-flight decode dispatch: the device token handle plus the
    lane binding it was dispatched against (seq, epoch) — the epoch drops
    results that a preemption has since invalidated.  With numeric
    guards on, ``tokens`` carries the guard verdict in-band: a
    non-finite lane's token is negative-packed (``-1 - tok``)."""

    __slots__ = ("tokens", "steps", "lanes")

    def __init__(self, tokens, steps: int,
                 lanes: Tuple[Optional[Tuple[Sequence, int]], ...]):
        self.tokens = tokens        # [B] (steps == 1) or [steps, B] int32
        self.steps = steps
        self.lanes = lanes


class SnapshotCapture:
    """One request checkpoint CAPTURED but not yet on the host: the host
    half of the snapshot (consumed tokens, ``pos``, drafter state) read
    at capture, plus the device handles of the gathered KV pages whose
    copy to the host was started and not waited for.  The gather sits in
    stream order behind every step already dispatched and ahead of every
    later one, so the pages are those ``jax.device_get`` would have
    returned at capture — however many steps run before
    ``ServingEngine.land_snapshot`` turns this into an EngineSnapshot.
    ``stale`` tells a request that finished, was aborted or was
    preempted since (by ``(seq, epoch)``, the ``_Pending`` rule): the
    pages are still the capture's, but the periodic path drops it."""

    __slots__ = ("seq", "epoch", "generated", "pos", "rows", "gathered",
                 "spec", "created_at", "land_wait_s")

    def __init__(self, seq: Sequence, generated: np.ndarray, pos: int,
                 rows: int, gathered: Optional[dict],
                 spec: Optional[dict]):
        self.seq = seq
        self.epoch = seq.epoch
        self.generated = generated
        self.pos = pos
        self.rows = rows              # live pages of the pow2-padded gather
        self.gathered = gathered      # device arrays; None once landed
        self.spec = spec
        self.created_at = time.monotonic()
        # host seconds land_snapshot waited for the bytes (None: not
        # landed) — ~0 when the copy crossed under a running step
        self.land_wait_s: Optional[float] = None

    @property
    def request_id(self) -> str:
        return self.seq.seq_id

    @property
    def stale(self) -> bool:
        return self.seq.done or self.seq.epoch != self.epoch


class ServingEngine:
    """Continuous-batching serving over a paged KV cache."""

    def __init__(self, model, *, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_batch_size: int = 8,
                 max_seq_len: Optional[int] = None,
                 bucket_sizes: Optional[List[int]] = None,
                 eos_id: int = 0,
                 metrics: Optional[ServingMetrics] = None,
                 prefill_chunk: int = 64,
                 sync_mode: bool = False,
                 fused_steps: int = 1,
                 ragged: Optional[bool] = None,
                 mesh_axes: Optional[dict] = None,
                 kv_cache_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 quant_scales: Optional[dict] = None,
                 prefix_cache: bool = False,
                 kv_tiering=False,
                 spec_decode=False,
                 spec_drafter=None,
                 numeric_guards: bool = True,
                 token_callback: Optional[Callable[[str, int, int],
                                                   None]] = None):
        self.model = model
        self.page_size = int(page_size)
        from ..text.generation import _gpt_geometry

        _, heads, _, _, model_max, _ = _gpt_geometry(model)
        self.max_seq_len = int(max_seq_len) if max_seq_len else model_max
        if self.max_seq_len > model_max:
            raise InvalidArgumentError(
                f"max_seq_len ({self.max_seq_len}) exceeds the model's "
                f"position table ({model_max})")
        self.pages_per_seq = -(-self.max_seq_len // self.page_size)
        # --- mesh-sharded replica (ISSUE 19, docs/SERVING.md
        # "Mesh-sharded replicas"): mesh_axes={"tp": N} and/or
        # {"sp": N} spans this ONE engine across tp*sp chips — qkv/ffn
        # weights and the KV pools' head dim shard over tp (decode at
        # aggregate HBM bandwidth, bitwise-identical streams), the page
        # dim shards over sp (long-context partial-softmax exchange).
        # The host side (scheduler, page tables, lane state) is
        # unchanged: one logical replica, uploads replicated via _dput.
        self._mesh_layout = None
        if mesh_axes is not None:
            if not isinstance(mesh_axes, dict):
                # the watchdog=/brownout= validation discipline
                raise InvalidArgumentError(
                    f"mesh_axes must be a dict of axis degrees "
                    f"(tp=/sp=), got {mesh_axes!r}")
            unknown = set(mesh_axes) - {"tp", "sp"}
            if unknown:
                raise InvalidArgumentError(
                    f"unknown mesh_axes key(s) {sorted(unknown)}; "
                    "expected tp (head sharding) / sp (sequence "
                    "sharding)")
            try:
                mesh_tp = int(mesh_axes.get("tp", 1))
                mesh_sp = int(mesh_axes.get("sp", 1))
            except (TypeError, ValueError):
                raise InvalidArgumentError(
                    f"mesh_axes degrees must be ints, got {mesh_axes!r}")
            if mesh_tp < 1 or mesh_sp < 1:
                raise InvalidArgumentError(
                    f"mesh_axes degrees must be >= 1, got tp={mesh_tp} "
                    f"sp={mesh_sp}")
            if mesh_tp * mesh_sp > 1:
                if heads % mesh_tp:
                    raise InvalidArgumentError(
                        f"mesh_axes tp={mesh_tp} must divide the "
                        f"model's num_heads ({heads})")
                if mesh_tp * mesh_sp > jax.device_count():
                    raise InvalidArgumentError(
                        f"mesh_axes needs tp*sp = "
                        f"{mesh_tp * mesh_sp} devices but only "
                        f"{jax.device_count()} are available")
                from ..text.generation import ServingMeshLayout
                self._mesh_layout = ServingMeshLayout(tp=mesh_tp,
                                                      sp=mesh_sp)
        self._mesh_sharding = None
        if self._mesh_layout is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..distributed.mesh import init_mesh
            mesh = init_mesh(self._mesh_layout.axes())
            self._mesh_sharding = NamedSharding(mesh, PartitionSpec())
        if num_pages is None:
            # roomy default: every slot can hold a full-length sequence
            num_pages = max_batch_size * self.pages_per_seq + 1
            if self._mesh_layout is not None:
                # the pool must split evenly across sequence shards
                num_pages += (-num_pages) % self._mesh_layout.sp
        elif self._mesh_layout is not None \
                and int(num_pages) % self._mesh_layout.sp:
            raise InvalidArgumentError(
                f"num_pages ({num_pages}) must be divisible by mesh "
                f"sp ({self._mesh_layout.sp}) — the page pool splits "
                "evenly across sequence shards")
        reserved = ((0,) if self._mesh_layout is None else
                    self._mesh_layout.reserved_pages(int(num_pages)))
        self.cache = PagedKVCache(num_pages, self.page_size,
                                  self.pages_per_seq,
                                  reserved_pages=reserved)
        self.scheduler = Scheduler(self.cache, max_batch_size,
                                   bucket_sizes=bucket_sizes)
        self.metrics = metrics or ServingMetrics()
        self.eos_id = int(eos_id)
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.sync_mode = bool(sync_mode)
        self.fused_steps = max(1, int(fused_steps))
        # --- unified ragged dispatch (ISSUE 18, docs/SERVING.md
        # "Unified ragged dispatch"): ONE serving.ragged_step program
        # carries the whole mixed batch — steady decode rows, prefill
        # CHUNK rows (one chunk per planned lane per step, riding
        # BESIDE the decode ticks instead of serializing ahead of
        # them) and spec-verify rows.  Per-lane streams stay
        # byte-identical to the split programs' by construction (the
        # Q=1 all-advance shape IS the split decode computation).
        # Default on; fused_steps > 1 keeps the split path (the fused
        # K-step fori_loop is a different dispatch-amortization axis
        # and stays a split-program variant).
        if ragged is None:
            ragged = self.fused_steps == 1
        if not isinstance(ragged, bool):
            # the watchdog=/brownout= validation discipline
            raise InvalidArgumentError(
                f"ragged must be a bool, got {ragged!r}")
        if ragged and self.fused_steps > 1:
            raise InvalidArgumentError(
                "ragged=True is incompatible with fused_steps > 1 — the "
                "fused K-step loop is a split-program variant; pass "
                "ragged=False (or drop fused_steps) ")
        self.ragged = ragged
        if self._mesh_layout is not None and not self.ragged:
            raise InvalidArgumentError(
                "mesh_axes requires the unified ragged dispatch — the "
                "sharded core serves only the ragged layout; drop "
                "ragged=False (and fused_steps)")
        self.outputs: Dict[str, np.ndarray] = {}
        self._ttft_recorded = set()      # per REQUEST, preemption-proof
        # streaming hook: called as (request_id, index, token) for every
        # CONSUMED token, in emission order — the single consume path
        # (_consume_one) serves sync, pipelined and fused modes alike,
        # so the callback stream is byte-identical across all three.
        # After a recompute-preemption the deterministic replay re-emits
        # indices from 0; consumers keep only forward progress
        # (index == tokens_seen), which reconstructs the exact stream.
        self.token_callback = token_callback
        # request ids whose deadline expired (queued or mid-decode) —
        # drained by the frontend via take_expired()
        self._expired: List[str] = []
        # --- numeric guards (ISSUE 13, docs/SERVING.md "Logit
        # quarantine"): the decode/fused/spec programs additionally
        # return per-lane logit-finiteness flags (computed on device,
        # consumed with the tokens — zero extra syncs); a non-finite
        # lane QUARANTINES its request: failed with a typed
        # NumericalFaultError within one engine step, lane reset,
        # pages scrubbed + freed (drained via take_faulted()).
        if not isinstance(numeric_guards, bool):
            # the watchdog=/brownout= validation discipline
            raise InvalidArgumentError(
                f"numeric_guards must be a bool, got {numeric_guards!r}")
        self.numeric_guards = numeric_guards
        # request ids failed by the numeric guard since the last
        # take_faulted() — the frontend resolves them as failed/500
        self._faulted: List[str] = []
        # sequences flagged mid-consume, quarantined at the end of the
        # step (after the pipeline is collapsed — pages are never freed
        # with a dispatch still in flight)
        self._quarantine_pending: List[Sequence] = []

        # --- int8 serving path (docs/SERVING.md "Quantized serving") ---
        # kv_cache_dtype="int8": pages store int8 + per-page-per-head
        # fp32 scales; with calibrated quant_scales["kv_scales"] (slim
        # bridge) the scales are static, otherwise they grow per page at
        # write time and are reset when a page is reallocated.
        # weight_dtype="int8": projection/MLP matmuls stream int8
        # weights through the weight-only kernel; scales come from the
        # export or are derived data-free here (abs-max, exact recipe).
        for d, knob in ((kv_cache_dtype, "kv_cache_dtype"),
                        (weight_dtype, "weight_dtype")):
            if d not in (None, "int8"):
                # no silent degradation: the pools/weights stay in the
                # model's native dtype unless int8 is asked for
                raise InvalidArgumentError(
                    f"{knob} must be None or 'int8', "
                                 f"got {d!r}")
        self.kv_cache_dtype = kv_cache_dtype
        self.weight_dtype = weight_dtype
        if quant_scales is not None and kv_cache_dtype is None \
                and weight_dtype is None:
            # an export without the knobs would silently run native —
            # an "int8 vs native" comparison measuring native vs native
            raise InvalidArgumentError(
                "quant_scales was provided but kv_cache_dtype and "
                "weight_dtype are both unset — pass kv_cache_dtype='int8' "
                "and/or weight_dtype='int8' (e.g. via "
                "Config.enable_serving) to activate the quantized path")
        qs = quant_scales or {}
        kv_scales = (qs.get("kv_scales")
                     if self.kv_cache_dtype == "int8" else None)
        # kept for the quarantine scrub (ISSUE 13): int8_static pool
        # scale rows are calibrated constants, so healing a poisoned
        # row means restoring THESE values (dynamic rows reset to the
        # eps floor via the scale_reset program instead)
        self._static_kv_scales = kv_scales
        # dynamic per-page scales need resetting when pages are
        # reallocated (results must not depend on page-reuse history)
        self._kv_dynamic = self.kv_cache_dtype == "int8" and \
            kv_scales is None

        # --- speculative decoding (docs/SERVING.md "Speculative
        # decoding"): bool (True = default K of 4) or an explicit int
        # K-token verify horizon — the established validated-knob
        # style.  K is a traced-over constant of the ONE spec_verify
        # program, never a per-call scalar (RH001).
        if not isinstance(spec_decode, (bool, int)):
            raise InvalidArgumentError(
                f"spec_decode must be a bool or an int K-token verify "
                f"horizon, got {spec_decode!r}")
        if isinstance(spec_decode, bool):
            spec_k = 4 if spec_decode else 0
        else:
            spec_k = int(spec_decode)
            if spec_k < 2:
                raise InvalidArgumentError(
                    f"spec_decode={spec_k} — the int form is the "
                    "K-token verify horizon and must be >= 2 (K=1 is "
                    "plain decode; pass False to disable)")
        if spec_drafter is not None and not spec_k:
            # truthy configs must not silently do nothing (the
            # watchdog=/brownout= validation discipline)
            raise InvalidArgumentError(
                "spec_drafter was provided but spec_decode is off — "
                "pass spec_decode=True (or an int horizon) to enable "
                "speculative decoding")
        if spec_k and self._mesh_layout is not None and self._kv_dynamic:
            # int8_dynamic speculation verifies through the split
            # SEQUENTIAL program (progressive scale-growth replay) —
            # a split program the sharded core does not serve
            raise InvalidArgumentError(
                "mesh_axes with spec_decode requires native or "
                "int8_static KV — the int8_dynamic sequential verifier "
                "is a split program the mesh-sharded core does not "
                "serve")
        self.spec = None
        if spec_k:
            from .spec_decode import SpecDecoder

            self.spec = SpecDecoder(spec_k, drafter=spec_drafter,
                                    metrics=self.metrics,
                                    sequential=self._kv_dynamic)

        # ragged engines fold spec verify into the ragged program (a
        # verify lane IS a ragged-query lane) — EXCEPT int8_dynamic,
        # which keeps the split SEQUENTIAL verifier: its rollback
        # replays progressive per-page scale growth bit-for-bit, a
        # schedule the one-shot ragged forward cannot reproduce
        spec_folds = self.ragged and not self._kv_dynamic
        progs = _shared_programs(
            model, page_size=self.page_size,
            pages_per_seq=self.pages_per_seq,
            kv_cache_dtype=self.kv_cache_dtype,
            weight_dtype=self.weight_dtype, kv_scales=kv_scales,
            weights=qs.get("weights") if self.weight_dtype == "int8"
            else None,
            fused_steps=self.fused_steps,
            spec_steps=0 if spec_folds else spec_k,
            spec_sequential=self._kv_dynamic,
            numeric_guards=self.numeric_guards,
            mesh_layout=self._mesh_layout)
        self._kv = progs["init_pages"](num_pages)
        self._weight_quant = progs["weight_quant"]
        self._decode_jit = progs["decode"]
        self._prefill_jit = progs["prefill"]
        self._lane_set_jit = progs["lane_set"]
        self._row_set_jit = progs["row_set"]
        self._fused_jit = progs["fused"]
        self._spec_jit = progs["spec_verify"]
        self._ragged_jit = progs["ragged"]
        self._scale_reset_jit = progs["scale_reset"]
        self._page_gather_jit = progs["page_gather"]
        self._page_put_jit = progs["page_put"]
        self._page_cow_jit = progs["page_cow"]
        if self._mesh_layout is not None:
            # snapshots / tiering / scrubs on a SHARDED pool assemble or
            # scatter pages across every shard (jax.device_get gathers a
            # sharded array transparently — EngineSnapshot stays
            # portable to any mesh shape, including single-device) —
            # count those cross-shard moves so the failover/tiering
            # cost of a mesh replica is observable (serving.shard.*)
            _gather, _put = self._page_gather_jit, self._page_put_jit

            def _mesh_gather(kv, rows, _g=_gather):
                self.metrics.on_shard_page_gather()
                return _g(kv, rows)

            def _mesh_put(kv, rows, payload, _p=_put):
                self.metrics.on_shard_page_scatter()
                return _p(kv, rows, payload)

            self._page_gather_jit = _mesh_gather
            self._page_put_jit = _mesh_put
            self.metrics.on_shard_config(
                tp=self._mesh_layout.tp, sp=self._mesh_layout.sp,
                devices=self._mesh_layout.size)

        # --- prefix cache (docs/SERVING.md "Prefix caching") -----------
        # opt-in radix index over resident full prompt/output pages:
        # admission maps hits into the page table and the chunked
        # prefill starts at the first uncached token.  int8_dynamic
        # BYPASSES the index (documented scale contract: dynamic
        # per-page scale growth under a reader would requantize the
        # shared content under every other reader) — requests run
        # uncached, exactly as with the knob off.
        if not isinstance(prefix_cache, bool):
            # truthy configs must not silently become defaults (the
            # watchdog=/brownout= validation discipline)
            raise InvalidArgumentError(
                f"prefix_cache must be a bool, got {prefix_cache!r}")
        self.prefix_cache = None
        self._prefix_bypass_reason = None
        if prefix_cache:
            if self._kv_dynamic:
                self._prefix_bypass_reason = (
                    "int8_dynamic KV: per-page scales are device state "
                    "grown by the writer — shared pages require "
                    "int8_static or native KV (docs/SERVING.md)")
            else:
                from .prefix_cache import PrefixCache

                self.prefix_cache = PrefixCache(self.cache,
                                                metrics=self.metrics)
                self.scheduler.prefix_cache = self.prefix_cache

        # --- tiered KV transport (ISSUE 16, docs/SERVING.md "Tiered KV
        # & disaggregation"): evicted prefix pages demote to a host-RAM
        # tier (spilling to a CRC'd disk tier) instead of discarding,
        # and tier hits promote back with one H2D page_restore — ≈10x
        # cheaper than re-prefilling.  False | True (host tier only,
        # default capacity) | dict(host_pages=, disk_dir=, disk_pages=).
        if not isinstance(kv_tiering, (bool, dict)):
            raise InvalidArgumentError(
                f"kv_tiering must be a bool or a dict of tier options "
                f"(host_pages/disk_dir/disk_pages), got {kv_tiering!r}")
        if kv_tiering and not prefix_cache:
            # truthy configs must not silently do nothing (the
            # watchdog=/brownout= validation discipline)
            raise InvalidArgumentError(
                "kv_tiering was provided but prefix_cache is off — the "
                "tiers extend the radix index (pass prefix_cache=True)")
        self.kv_transport = None
        if kv_tiering and self.prefix_cache is not None:
            # int8_dynamic bypasses the prefix cache (and therefore the
            # tiers) with _prefix_bypass_reason already set — same
            # documented scale contract
            opts = dict(kv_tiering) if isinstance(kv_tiering, dict) else {}
            unknown = set(opts) - {"host_pages", "disk_dir", "disk_pages"}
            if unknown:
                raise InvalidArgumentError(
                    f"unknown kv_tiering option(s) {sorted(unknown)}; "
                    "expected host_pages/disk_dir/disk_pages")
            disk_store = None
            if opts.get("disk_dir"):
                from ..io.checkpoint import CheckpointStore

                disk_store = CheckpointStore(str(opts["disk_dir"]))
            from .kv_transport import PageTransport

            self.kv_transport = PageTransport(
                self._tier_gather, self._tier_restore,
                host_pages=int(opts.get("host_pages", 64)),
                disk_store=disk_store,
                disk_pages=int(opts.get("disk_pages", 0)),
                metrics=self.metrics)
            self.prefix_cache.attach_transport(self.kv_transport)
        # chaos-injection key for the "engine.step" site (the frontend
        # sets this to the owning replica's id so fault schedules count
        # per replica instead of racing across pump threads)
        self.chaos_key: Optional[str] = None

        # device-resident decode state (grown/rebuilt lazily)
        self._tokens = None              # [bucket] int32
        self._pos = None                 # [bucket] int32
        self._tables = None              # [bucket, pages_per_seq] int32
        self._state_bucket = 0
        self._lanes: List[Optional[Sequence]] = []
        self._lane_ids: List = []        # device () int32 per lane index
        self._zero_i32 = self._dput(np.int32(0))
        self._zero_row = self._dput(
            np.zeros((self.pages_per_seq,), np.int32))
        self._pending: Deque[_Pending] = deque()
        self._last_dispatch: Optional[float] = None
        # page count per seq_id as last uploaded to the device table —
        # ANY growth (ensure_decode_pages or the fused horizon reserve)
        # must re-upload the row before the next dispatch, or writes
        # past the stale row land in the trash page
        self._uploaded_pages: Dict[str, int] = {}
        # --- unified ragged dispatch state (ISSUE 18) ------------------
        # per-request prefill PLAN: the chunk queue admission builds
        # instead of dispatching — each engine step pops one chunk per
        # planned lane into the mixed ragged dispatch, so decode ticks
        # never stall behind a long prompt.  A lane is inert (its
        # device state untouched, advance=0) until its plan drains.
        self._prefill_plans: Dict[str, dict] = {}
        # per-bucket cached steady-decode row arrays (all-zero rows,
        # no-limit row_valid, all-advance) — uploaded once per bucket so
        # steady ragged decode stays transfer-guard- and
        # compile_budget(0)-clean like the split decode path
        self._ragged_steady: Dict[int, tuple] = {}
        from ..text.generation import RAGGED_NO_LIMIT
        self._ragged_no_limit = RAGGED_NO_LIMIT
        # the kernels' own rules, for the attn_rows_skipped count and the
        # pool write's kv_rows_written / kv_page_copies
        from ..ops.pallas_ops.paged_attention import ragged_rows_skipped
        from ..ops.pallas_ops.paged_kv_write import kv_write_counts
        self._rows_skipped = ragged_rows_skipped
        self._kv_write_counts = kv_write_counts

    def _dput(self, x):
        """Host→device upload for engine state.  In mesh mode every
        upload is REPLICATED over the replica's (tp, sp, data) mesh —
        a plain ``jax.device_put`` would commit the array to one device
        and the jitted programs would reject mixing it with the
        mesh-sharded pools; replicated inputs cost nothing extra (XLA
        broadcasts once) and keep every host path mesh-agnostic."""
        if self._mesh_sharding is not None:
            return jax.device_put(x, self._mesh_sharding)
        return jax.device_put(x)

    # --- request intake ---------------------------------------------------
    def check_request(self, prompt, max_new_tokens: int = 32) -> np.ndarray:
        """Validate a prospective request against this engine's static
        limits WITHOUT enqueuing it; returns the canonicalized int32
        prompt.  Raises ValueError on anything that could never run —
        the frontend calls this at submit time so an impossible request
        is rejected synchronously instead of failing inside a pump
        thread."""
        if hasattr(prompt, "numpy"):
            prompt = prompt.numpy()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise InvalidArgumentError("empty prompt")
        if max_new_tokens < 1:
            raise InvalidArgumentError("max_new_tokens must be >= 1")
        if prompt.size + max_new_tokens > self.max_seq_len:
            # mirror generate()'s guard: past the wpe table the position
            # gather would silently clamp — degraded text with no error
            raise InvalidArgumentError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_seq_len "
                f"({self.max_seq_len})")
        # a request that could never fit even running ALONE would sit in
        # the admission queue forever (nothing to preempt) — reject loudly
        need = self.cache.pages_needed(prompt.size + max_new_tokens - 1)
        cap = min(self.cache.allocatable_pages, self.pages_per_seq)
        if need > cap:
            raise InvalidArgumentError(
                f"request needs {need} KV pages (prompt {prompt.size} + "
                f"{max_new_tokens} new tokens @ page_size "
                f"{self.page_size}) but the cache caps a sequence at "
                f"{cap} pages — raise num_pages or lower max_new_tokens")
        return prompt

    def add_request(self, prompt, max_new_tokens: int = 32,
                    request_id: Optional[str] = None,
                    deadline: Optional[float] = None,
                    prefix_cache: bool = True,
                    arrival_time: Optional[float] = None) -> str:
        """Enqueue a generation request; returns its id.  Non-blocking —
        admission happens inside step() when a slot and pages are free.
        ``deadline`` is an ABSOLUTE ``time.monotonic()`` instant: once
        passed, the request is dropped from the queue (never admitted)
        or aborted mid-decode with its pages freed; either way its id
        surfaces through ``take_expired()``.  ``prefix_cache=False``
        opts this request out of the engine's prefix cache (no index
        lookup, its pages are never sealed for other requests); a no-op
        when the engine has none.  ``arrival_time`` is the
        ``time.monotonic()`` instant the request reached the system (the
        frontend passes its submit time) — ``serving.queue_wait_ms`` and
        ``serving.ttft_ms`` start there; default: now."""
        prompt = self.check_request(prompt, max_new_tokens)
        if not isinstance(prefix_cache, bool):
            raise InvalidArgumentError(
                f"prefix_cache must be a bool, got {prefix_cache!r}")
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      request_id=request_id or "", deadline=deadline,
                      use_prefix_cache=prefix_cache,
                      arrival_time=(time.monotonic() if arrival_time is None
                                    else float(arrival_time)))
        self._check_not_live(req.request_id)
        self.scheduler.add(req)
        return req.request_id

    def _check_not_live(self, request_id: str):
        # a duplicate id would alias two live sequences onto one KV page
        # table (cross-contaminated attention, double-free) — reject it
        live = (request_id in self.outputs
                or any(r.request_id == request_id
                       for r in self.scheduler.waiting)
                or any(s.seq_id == request_id
                       for s in self.scheduler.running))
        if live:
            raise AlreadyExistsError(
                f"request_id {request_id!r} is already in flight or "
                "has an unconsumed output")

    # --- abort ------------------------------------------------------------
    def abort(self, request_id: str) -> bool:
        """Retire a queued or in-flight sequence NOW: no output is
        recorded, its pages and batch lane are freed, and (dynamic int8
        mode) the freed pages' scales return to the eps floor so their
        next owner quantizes from scratch.  Returns True when something
        was aborted; False when the id is unknown or already finished
        (a finished request's output stays in ``outputs``).

        Survivor safety: the pipeline is collapsed first, so every
        already-dispatched token is applied before the lane disappears —
        survivors' streams are byte-identical with and without the abort
        (tests/test_serving_abort.py pins this).  Not thread-safe: call
        from the thread that drives ``step()``.
        """
        sched = self.scheduler
        # still waiting (including a preempted sequence's requeued
        # request): nothing on device, nothing to free
        for req in sched.waiting:
            if req.request_id == request_id:
                sched.waiting.remove(req)
                self._forget(request_id)
                self.metrics.on_abort()
                return True
        seq = next((s for s in sched.running if s.seq_id == request_id),
                   None)
        if seq is None:
            return False
        # apply in-flight tokens before tearing the lane down; the
        # target may complete here, in which case it finished first and
        # the abort is a no-op
        self._sync_pending()
        if seq.done or seq not in sched.running:
            return False
        page_ids = self.cache.seq_page_ids(seq.seq_id)
        sched.finish(seq)                 # frees pages, leaves running
        seq.done = True
        seq.epoch += 1                    # any stale device result drops
        self._reset_page_scales(page_ids)
        self._forget(request_id)
        for i, lane_seq in enumerate(self._lanes):
            if lane_seq is seq:
                self._lanes[i] = None
                self._clear_lane(i)
        self.metrics.on_abort()
        return True

    def _forget(self, request_id: str):
        """Drop per-request engine bookkeeping (abort/expiry path)."""
        self._ttft_recorded.discard(request_id)
        self._uploaded_pages.pop(request_id, None)
        stale = self._drop_plan(request_id)
        if stale:
            self._preempt_plan_sharers(stale)
        if self.spec is not None:
            self.spec.on_drop(request_id)

    def take_expired(self) -> List[str]:
        """Request ids whose deadline expired since the last call
        (queued → dropped before admission; mid-decode → aborted, pages
        freed).  Each id appears exactly once, and never in
        ``outputs``."""
        out, self._expired = self._expired, []
        return out

    # --- numeric quarantine (docs/SERVING.md "Logit quarantine") ----------
    def take_faulted(self) -> List[str]:
        """Request ids quarantined by the numeric guard since the last
        call (non-finite decode/verify logits → failed with
        NumericalFaultError, lane reset, pages scrubbed + freed).  Each
        id appears exactly once, and never in ``outputs``."""
        out, self._faulted = self._faulted, []
        return out

    def _scrub_pages(self, page_ids):
        """Zero the payload of pages being freed by a quarantine so the
        NaN they carry can never reach a future owner: attention masks
        unwritten positions, but a NaN at a masked position is one
        where-vs-additive-mask kernel subtlety away from escaping —
        the fault path pays one scatter instead of relying on it.
        Scale rows: int8_static rows are restored to their CALIBRATED
        values (a nan_logits poison writes NaN into the scale row, and
        static mode has no other reset path — without this, one
        injected fault would cascade NaN through every future owner of
        the physical page); dynamic rows are reset to the eps floor by
        ``_reset_page_scales``; native pools have none."""
        if not page_ids:
            return
        R = next_pow2(len(page_ids))
        rows_np = np.zeros((R,), np.int32)
        rows_np[: len(page_ids)] = page_ids
        payload = {
            side: [self._dput(np.zeros((R,) + tuple(p.shape[1:]),
                                       p.dtype)) for p in self._kv[side]]
            for side in ("k", "v")}
        if self._static_kv_scales is not None:
            for side in ("k", "v"):
                payload[f"{side}_scale"] = [
                    self._dput(np.broadcast_to(
                        np.asarray(s, np.float32)[None, :],
                        (R, np.asarray(s).shape[0])).copy())
                    for s in self._static_kv_scales[side]]
        self._kv = self._page_put_jit(self._kv,
                                      self._dput(rows_np), payload)

    def _quarantine(self, seq: Sequence):
        """Fail one guard-flagged request NOW (pipeline already
        collapsed): no output, typed NumericalFaultError surfaced via
        ``take_faulted()``, lane zeroed, pages scrubbed + freed — the
        damage is contained to this one request within the step that
        consumed it."""
        if seq.done or seq not in self.scheduler.running:
            return
        rid = seq.seq_id
        page_ids = self.cache.seq_page_ids(rid)
        self.scheduler.finish(seq)        # frees pages, leaves running
        seq.done = True
        seq.epoch += 1                    # stale device results drop
        # scrub ONLY pages that actually returned to the free list: a
        # prefix-cache-shared page still has readers (or sits resident
        # in the radix index) after our decref, and its content is the
        # CLEAN prefill the sharers rely on — zeroing it would corrupt
        # their streams.  The poisoned page is always in the freed set:
        # decode-write pages are private by the COW contract.
        freed = [p for p in page_ids if self.cache.is_free(p)]
        self._scrub_pages(freed)
        self._reset_page_scales(freed)
        self._forget(rid)
        for i, lane_seq in enumerate(self._lanes):
            if lane_seq is seq:
                self._lanes[i] = None
                self._clear_lane(i)
        self._faulted.append(rid)
        self.metrics.on_quarantine()
        flight.request_terminal(rid, "failed", replica=self.chaos_key,
                                reason="numerical_fault",
                                tokens=seq.num_generated)

    def _process_quarantines(self):
        """Collapse the pipeline, then quarantine every flagged lane
        (collapsing may flag more — loop until drained).  Runs at the
        end of the step that consumed the damage: 'failed within one
        engine step' is the quarantine contract."""
        while self._quarantine_pending:
            self._sync_pending()
            pending, self._quarantine_pending = \
                self._quarantine_pending, []
            for seq in pending:
                self._quarantine(seq)

    def _poison_lane(self, seq: Sequence):
        """Chaos ``serving.logits`` ``nan_logits`` action: drive the
        NEXT decode's logits for exactly this lane non-finite ON
        DEVICE — native KV poisons the page content at the lane's last
        written position, int8 KV poisons that page's scale row (int8
        payloads cannot hold NaN; a NaN scale makes every dequant of
        the page NaN).  Real device-side propagation, not a faked
        flag: the guard reduction must catch it inside the jitted
        program.

        Injection-targeting note: once the lane has dispatched at
        least once (fault ``at >= 2``), pos-1 is a decode-write
        position — always PRIVATE by the prefix-cache COW contract, so
        the damage is surgically one request's.  An ``at=1`` injection
        on a fresh prefix-hit lane would target the last PROMPT
        position, which can sit in a shared page and (faithfully to
        real SDC in shared memory) damage every reader — schedule
        chaos plans accordingly."""
        table = self.cache.seq_page_ids(seq.seq_id)
        if not table:
            return
        pos = max(seq.pos - 1, 0)
        page = table[min(pos // self.page_size, len(table) - 1)]
        rows = self._dput(np.asarray([page], np.int32))
        payload = {key: [np.array(a) for a in arrs]    # writable copies
                   for key, arrs in jax.device_get(
                       self._page_gather_jit(self._kv, rows)).items()}
        if "k_scale" in payload:
            for arr in payload["k_scale"]:
                arr[...] = np.nan
        else:
            for arr in payload["k"]:
                arr[...] = np.nan
        dev = {key: [self._dput(a) for a in arrs]
               for key, arrs in payload.items()}
        self._kv = self._page_put_jit(self._kv, rows, dev)

    # --- checkpoint / warm failover (docs/SERVING.md "Resilience") --------
    def kv_mode(self) -> str:
        """The snapshot-contract mode of this engine's KV pools."""
        if self.kv_cache_dtype != "int8":
            return "native"
        return "int8_dynamic" if self._kv_dynamic else "int8_static"

    def snapshot(self, request_id: str) -> Optional[EngineSnapshot]:
        """Checkpoint one RUNNING request: consumed tokens + the KV pages
        covering them, portable to ``restore()`` on another engine built
        from the same model/config.  Returns None when the id is not
        currently decoding (queued / preempted-back-to-queue / finished
        — the caller keeps its previous snapshot).

        The SYNCHRONOUS form — ``capture_snapshot`` landed at once: the
        caller waits for the in-flight step and for the pages to cross
        to the host.  Callers that need the bytes now (the prefill →
        decode ship, tests) use it; the frontend's periodic checkpoint
        captures in one pump turn and lands in the next instead.

        Consistency: ``generated`` is the CONSUMED stream (what the
        token_callback has emitted); the pages may additionally contain
        writes from a still-in-flight dispatch — harmless, the resumed
        decode deterministically rewrites every position >= ``pos``.
        Call from the thread that drives ``step()`` (the pump thread).
        """
        cap = self.capture_snapshot(request_id)
        return None if cap is None else self.land_snapshot(cap)

    def capture_snapshot(self, request_id: str
                         ) -> Optional[SnapshotCapture]:
        """The half of ``snapshot`` that costs its caller no wait: read
        ``generated`` / ``pos`` / the page ids on the host, enqueue the
        page gather behind the dispatched steps, start its copy to the
        host, fetch nothing.  Same refusals as ``snapshot`` (None: not
        decoding, or mid prefill plan).  ``land_snapshot`` finishes it —
        any number of steps later: pages this request frees meanwhile,
        and whoever is prefilled into them, are behind the gather in
        stream order.  Pump thread only."""
        seq = next((s for s in self.scheduler.running
                    if s.seq_id == request_id and not s.done), None)
        if seq is None:
            return None
        if request_id in self._prefill_plans:
            # mid-plan (ragged mode): the prompt pages are only
            # partially written — a snapshot here would capture a
            # half-prefilled sequence that restore would wrongly resume
            # as fully prefilled.  The caller keeps its previous
            # snapshot; the plan drains within a few steps.
            return None
        g = len(seq.generated)
        pos = seq.request.prompt.size - 1 + g
        need = self.cache.pages_needed(pos)
        rows = self.cache.seq_page_ids(request_id)[:need]
        gathered = None
        if rows:
            padded = np.zeros((next_pow2(len(rows)),), np.int32)
            padded[: len(rows)] = rows
            gathered = self._page_gather_jit(self._kv, self._dput(padded))
            for a in jax.tree_util.tree_leaves(gathered):
                a.copy_to_host_async()
        spec_state = None
        if self.spec is not None:
            # the drafter's adaptive lane state rides along so a
            # resumed request keeps speculating where the donor left
            # off (its n-gram index rebuilds from prompt + generated)
            spec_state = self.spec.drafter.export_lane(request_id) or None
        return SnapshotCapture(seq, np.asarray(seq.generated, np.int32),
                               int(pos), len(rows), gathered, spec_state)

    def land_snapshot(self, cap: SnapshotCapture) -> EngineSnapshot:
        """Finish a capture: wait for its pages on the host (the wait is
        left in ``cap.land_wait_s``), release the gathered device
        buffers, build the EngineSnapshot — of the request as it stood
        at capture, whatever became of it since (``cap.stale`` is the
        caller's to ask)."""
        gathered, cap.gathered = cap.gathered, None
        seq = cap.seq
        pages: Dict[str, List[np.ndarray]] = {"k": [], "v": []}
        mode = self.kv_mode()
        t0 = time.perf_counter()
        got = None if gathered is None else jax.device_get(gathered)
        cap.land_wait_s = time.perf_counter() - t0
        del gathered
        if got is not None:
            R = cap.rows
            if mode == "int8_dynamic":
                # dynamic per-page scales are device state owned by the
                # donor pool: store DEQUANTIZED pages (restore re-derives
                # abs-max scales — the documented contract).  The pinned
                # kv_cache reference fns ARE the quantization contract —
                # snapshot/restore reuse them so the math lives once.
                # (the reference fns speak [P, H, D]; a host reshape of
                # the stored [P, H*D] page is a free view)
                for side in ("k", "v"):
                    for q, s in zip(got[side], got[f"{side}_scale"]):
                        q, s = np.asarray(q), np.asarray(s)
                        P, H = q.shape[1], s.shape[1]
                        pages[side].append(np.stack(
                            [dequantize_kv_page(q[i].reshape(P, H, -1),
                                                s[i]).reshape(P, -1)
                             for i in range(R)]))
            else:
                for side in ("k", "v"):
                    pages[side] = [np.asarray(p[:R]) for p in got[side]]
        snap = EngineSnapshot(
            request_id=cap.request_id, prompt=seq.request.prompt,
            max_new_tokens=seq.request.max_new_tokens,
            deadline=seq.request.deadline,
            generated=cap.generated, pos=cap.pos,
            kv_mode=mode, page_size=self.page_size, pages=pages,
            created_at=cap.created_at, spec=cap.spec)
        self.metrics.on_snapshot(snap.nbytes)
        return snap

    def restore(self, snap: EngineSnapshot) -> str:
        """Re-admit a snapshotted request MID-STREAM: enqueues a resume
        request whose admission uploads the snapshot's KV pages instead
        of prefilling, then decoding continues from ``snap.pos`` — token
        callbacks fire from index ``snap.num_generated`` onward.  The
        deadline rides along unchanged (failover never extends an SLO).
        Raises ValueError on geometry/mode mismatch or a live duplicate
        id."""
        if snap.page_size != self.page_size:
            raise InvalidArgumentError(
                f"snapshot page_size {snap.page_size} != engine "
                f"page_size {self.page_size}")
        if snap.kv_mode != self.kv_mode():
            raise InvalidArgumentError(
                f"snapshot kv_mode {snap.kv_mode!r} != engine kv_mode "
                f"{self.kv_mode()!r} — snapshots are portable only "
                "between replicas of one serving configuration")
        prompt = self.check_request(snap.prompt, snap.max_new_tokens)
        self._check_not_live(snap.request_id)
        req = Request(prompt=prompt,
                      max_new_tokens=int(snap.max_new_tokens),
                      request_id=snap.request_id, deadline=snap.deadline,
                      resume=snap)
        self.scheduler.add(req)
        return req.request_id

    def _upload_snapshot(self, seq: Sequence):
        """Admission path for a resume request: scatter the snapshot's
        page payloads into the freshly allocated physical pages (the
        restore-side of the snapshot contract; replaces prefill)."""
        snap = seq.request.resume
        rows = self.cache.seq_page_ids(seq.seq_id)
        if not rows:
            return                       # 1-token prompt, 0 tokens in
        R = len(rows)
        payload = {}
        if snap.kv_mode == "int8_dynamic":
            # re-derive fresh abs-max scales from the dequantized pages
            # and requantize (via the pinned kv_cache reference fns —
            # the quantization contract lives in one place) — the
            # restored pool's scales then depend only on this
            # sequence's content, preserving the dynamic mode's
            # page-reuse-independence invariant
            H = int(self._kv["k_scale"][0].shape[1])
            for side in ("k", "v"):
                qs, ss = [], []
                for page_fp in snap.pages[side]:        # [R, P, H*D]
                    P = page_fp.shape[1]
                    pairs = [quantize_kv_page(page_fp[i].reshape(P, H, -1))
                             for i in range(len(page_fp))]
                    qs.append(np.stack([q.reshape(P, -1)
                                        for q, _ in pairs]))
                    ss.append(np.stack([s for _, s in pairs]
                                       ).astype(np.float32))
                payload[side] = qs
                payload[f"{side}_scale"] = ss
        else:
            dt = np.int8 if snap.kv_mode == "int8_static" else None
            for side in ("k", "v"):
                payload[side] = [np.asarray(p, dt) if dt else p
                                 for p in snap.pages[side]]
        Rp = next_pow2(R)
        rows_np = np.zeros((Rp,), np.int32)
        rows_np[:R] = rows
        dev = {}
        for key, arrs in payload.items():
            padded = []
            for a in arrs:
                if Rp != R:
                    a = np.concatenate(
                        [a, np.zeros((Rp - R,) + a.shape[1:], a.dtype)])
                padded.append(self._dput(a))
            dev[key] = padded
        if snap.kv_mode == "native":
            # pools carry the model dtype (e.g. bf16) — cast on device
            model_dt = self._kv["k"][0].dtype
            dev["k"] = [a.astype(model_dt) for a in dev["k"]]
            dev["v"] = [a.astype(model_dt) for a in dev["v"]]
        self._kv = self._page_put_jit(self._kv, self._dput(rows_np),
                                      dev)
        if snap.num_generated:
            # TTFT already happened on the donor replica — a resumed
            # request must not re-enter the TTFT histogram
            self._ttft_recorded.add(seq.seq_id)
            seq.first_token_time = snap.created_at
        self.metrics.on_restore()

    # --- tiered KV transport closures (ISSUE 16) ---------------------------
    # The PageTransport is device-free: these two closures are its only
    # window onto the pools, reusing the snapshot machinery's
    # page_gather / page_restore programs and pow2 row padding (bounded
    # compile cache).  Both run only at the admission boundary (the
    # demote window / promote_for), never in steady decode.
    def _tier_gather(self, page_ids: List[int]) -> List[dict]:
        """D2H: one payload dict per page, in ``page_ids`` order —
        per-layer [P, H*D] k/v arrays plus [H] scale rows in
        int8_static mode (the pool's own dtypes, so a restore is
        bit-exact)."""
        rows = np.asarray(page_ids, np.int32)
        R = len(rows)
        padded = np.zeros((next_pow2(R),), np.int32)
        padded[:R] = rows
        got = jax.device_get(
            self._page_gather_jit(self._kv, self._dput(padded)))
        return [{key: [np.asarray(a[i]) for a in arrs]
                 for key, arrs in got.items()} for i in range(R)]

    def _tier_restore(self, page_ids: List[int], payloads: List[dict]):
        """H2D: scatter promoted payloads into freshly taken pages (the
        inverse of ``_tier_gather`` — same keys, same dtypes)."""
        R = len(page_ids)
        Rp = next_pow2(R)
        rows_np = np.zeros((Rp,), np.int32)
        rows_np[:R] = np.asarray(page_ids, np.int32)
        dev = {}
        for key in payloads[0]:
            arrs = []
            for li in range(len(payloads[0][key])):
                stacked = np.stack([p[key][li] for p in payloads])
                if Rp != R:
                    stacked = np.concatenate(
                        [stacked,
                         np.zeros((Rp - R,) + stacked.shape[1:],
                                  stacked.dtype)])
                arrs.append(self._dput(stacked))
            dev[key] = arrs
        if self.kv_cache_dtype != "int8":
            # native pools carry the model dtype — cast on device, the
            # _upload_snapshot discipline (no-op when already equal)
            model_dt = self._kv["k"][0].dtype
            dev["k"] = [a.astype(model_dt) for a in dev["k"]]
            dev["v"] = [a.astype(model_dt) for a in dev["v"]]
        self._kv = self._page_put_jit(self._kv, self._dput(rows_np),
                                      dev)

    # --- device-resident lane state ---------------------------------------
    def _grow_state(self, new_bucket: int):
        """Pad the device state up to ``new_bucket`` lanes (device-side
        pad — no host re-upload of live lanes).  Only called with the
        pipeline drained: in-flight steps pin the lane layout."""
        assert not self._pending
        M = self.pages_per_seq
        if self._state_bucket == 0:
            self._tokens = self._dput(np.zeros((new_bucket,), np.int32))
            self._pos = self._dput(np.zeros((new_bucket,), np.int32))
            self._tables = self._dput(np.zeros((new_bucket, M), np.int32))
        else:
            pad = new_bucket - self._state_bucket
            self._tokens = jnp.pad(self._tokens, (0, pad))
            self._pos = jnp.pad(self._pos, (0, pad))
            self._tables = jnp.pad(self._tables, ((0, pad), (0, 0)))
        self._lanes.extend([None] * (new_bucket - self._state_bucket))
        self._state_bucket = new_bucket
        self._lane_ids = [self._dput(np.int32(i))
                          for i in range(new_bucket)]

    def _bind_lane(self, seq: Sequence) -> int:
        """Bind an admitted sequence to the lowest free lane, growing the
        bucket when none is free; uploads ONLY that lane's delta."""
        lane = next((i for i, s in enumerate(self._lanes) if s is None), -1)
        if lane < 0:
            self._grow_state(smallest_bucket(len(self._lanes) + 1,
                                             self.scheduler.bucket_sizes))
            lane = self._lanes.index(None)
        self._lanes[lane] = seq
        row = self._dput(self.cache.page_table_row(seq.seq_id))
        self._tokens, self._pos, self._tables = self._lane_set_jit(
            self._tokens, self._pos, self._tables, self._lane_ids[lane],
            self._dput(np.int32(seq.next_token)),
            self._dput(np.int32(seq.pos)), row)
        self._uploaded_pages[seq.seq_id] = self.cache.seq_pages(seq.seq_id)
        return lane

    def _clear_lane(self, lane: int):
        """Zero one lane on device (pos=0 + all-trash page table — the
        inactive-lane convention the decode step relies on)."""
        self._tokens, self._pos, self._tables = self._lane_set_jit(
            self._tokens, self._pos, self._tables, self._lane_ids[lane],
            self._zero_i32, self._zero_i32, self._zero_row)

    def _refresh_row(self, lane: int, seq: Sequence):
        """Page growth changed the sequence's table — re-upload one row
        (and, in dynamic int8 mode, reset the grown pages' scales: they
        may have been freed by another sequence with a larger scale)."""
        table = self.cache.seq_page_ids(seq.seq_id)
        self._reset_page_scales(
            table[self._uploaded_pages.get(seq.seq_id, 0):])
        row = self._dput(self.cache.page_table_row(seq.seq_id))
        self._tables = self._row_set_jit(self._tables,
                                         self._lane_ids[lane], row)
        self._uploaded_pages[seq.seq_id] = len(table)

    def _reset_page_scales(self, page_ids):
        """Dynamic int8 KV only: return freshly (re)allocated pages'
        scales to the eps floor BEFORE anything is written through them,
        so quantization depends only on the owning sequence's tokens —
        never on page-reuse history (which differs across engine modes
        and would break the byte-identity guarantee)."""
        if self._scale_reset_jit is None or not page_ids:
            return
        rows = np.zeros((next_pow2(len(page_ids)),), np.int32)
        rows[: len(page_ids)] = page_ids
        self._kv = self._scale_reset_jit(self._kv, self._dput(rows))

    def _sync_rows(self, active: List[Tuple[int, "Sequence"]]):
        """Re-upload every device table row whose host allocation grew
        since its last upload — MUST run between any page allocation and
        the dispatch that writes into the new pages."""
        for lane, seq in active:
            if (self.cache.seq_pages(seq.seq_id)
                    != self._uploaded_pages.get(seq.seq_id)):
                self._refresh_row(lane, seq)

    def _maybe_shrink(self):
        """With the pipeline drained, compact lanes down to the smallest
        covering bucket (rebuild from the host mirror — every lane's
        token/pos is known once nothing is in flight), or drop the state
        entirely when no lane is live."""
        if self._pending or not self._state_bucket:
            return
        active = [s for s in self._lanes if s is not None]
        if not active:
            self._tokens = self._pos = self._tables = None
            self._state_bucket = 0
            self._lanes = []
            self._lane_ids = []
            # idle boundary: the next burst's first dispatch must not
            # record the idle period as a "gap" (it would own p99/max)
            self._last_dispatch = None
            return
        desired = smallest_bucket(len(active), self.scheduler.bucket_sizes)
        if desired >= self._state_bucket:
            return
        tokens = np.zeros((desired,), np.int32)
        pos = np.zeros((desired,), np.int32)
        tables = np.zeros((desired, self.pages_per_seq), np.int32)
        for i, s in enumerate(active):
            tokens[i] = s.next_token
            pos[i] = s.pos
            tables[i] = self.cache.page_table_row(s.seq_id)
        self._tokens = self._dput(tokens)
        self._pos = self._dput(pos)
        self._tables = self._dput(tables)
        self._lanes = active + [None] * (desired - len(active))
        self._state_bucket = desired
        self._lane_ids = [self._dput(np.int32(i))
                          for i in range(desired)]

    # --- prefill ----------------------------------------------------------
    def _prefill_seq(self, seq: Sequence) -> int:
        """Teacher-force prompt[:-1] through the paged cache in parallel
        chunks of up to ``prefill_chunk`` positions — O(P/C) dispatches.
        Padded tail positions scatter into the trash page (valid_len
        mask), so chunk shapes are pow2 buckets shared across prompts.

        Prefix-cache skip: positions below ``seq.cached_tokens`` already
        sit in shared index pages mapped at admission — prefill starts
        at the first uncached token (the ``valid_len`` machinery handles
        the ragged start; positions are absolute, so the chunk queries
        attend over the shared pages like any previously-written ones).
        A fully-covered prompt dispatches NOTHING.  Returns the chunks
        dispatched."""
        prompt = seq.request.prompt
        n = prompt.size - 1
        start = min(seq.cached_tokens, n)
        if n - start == 0:
            return 0
        spans = chunk_schedule(n - start, self.prefill_chunk)
        row = self._dput(self.cache.page_table_row(seq.seq_id))
        n_dev = self._dput(np.int32(n))
        t0 = time.perf_counter()
        with RecordEvent("serving/prefill", chunks=len(spans),
                         prompt_len=int(prompt.size)):
            for off, size in spans:
                s0 = start + off
                ctok = np.zeros((size,), np.int32)
                valid = min(s0 + size, n) - s0
                ctok[:valid] = prompt[s0:s0 + valid]
                cpos = (s0 + np.arange(size)).astype(np.int32)
                flight.request_event(seq.seq_id, EV_PREFILL_CHUNK,
                                     replica=self.chaos_key, size=size)
                with RecordEvent("serving/prefill_chunk", size=size):
                    self._kv = self._prefill_jit(
                        self._dput(ctok), self._dput(cpos),
                        row, n_dev, self._kv)
            # sync inside the timed window: dispatch is async, and the
            # decode that follows needs this kv anyway — without the
            # block the histogram would record µs dispatch times
            jax.block_until_ready(self._kv)
        dt = time.perf_counter() - t0
        self.metrics.on_prefill(dt)
        self.metrics.on_prefill_chunks(len(spans), n - start, dt)
        return len(spans)

    # --- unified ragged dispatch (ISSUE 18) -------------------------------
    def _plan_prefill(self, seq: Sequence, awaits=()) -> int:
        """Ragged-mode admission: BUILD the chunk plan (host arrays
        only, no dispatch) — each following engine step pops one chunk
        into the mixed ragged dispatch, interleaved with every other
        lane's decode tick.  Same chunk_schedule spans, positions and
        valid_len masking as ``_prefill_seq``, so each chunk's rows are
        bit-identical to what the split prefill program would consume.
        A fully-covered prompt (prefix hit) plans nothing: the lane
        decodes on the very next step, exactly like the split path.

        Write-visibility bookkeeping (prefix cache): admission seals a
        prompt's full pages into the index BEFORE this plan has written
        them, so the plan registers them as ``unwritten`` and clears
        each one as the chunk covering it is issued.  ``awaits`` lists
        shared pages THIS sequence reads that some other live plan has
        not written yet — the lane idles (no chunk, no decode, no COW
        copy) until every awaited page's write has been dispatched, so
        device program order commits the payload before any read.  A
        fully-covered prompt with a non-empty barrier gets a chunkless
        plan that exists only to hold the lane idle.  Returns the chunks
        planned."""
        prompt = seq.request.prompt
        n = prompt.size - 1
        start = min(seq.cached_tokens, n)
        awaits = set(awaits)
        cow = seq.cow_pair is not None and bool(awaits)
        if n - start == 0 and not awaits:
            return 0
        chunks: Deque[Tuple[np.ndarray, np.ndarray, int]] = deque()
        for off, size in chunk_schedule(n - start, self.prefill_chunk) \
                if n - start else ():
            s0 = start + off
            ctok = np.zeros((size,), np.int32)
            valid = min(s0 + size, n) - s0
            ctok[:valid] = prompt[s0:s0 + valid]
            cpos = (s0 + np.arange(size)).astype(np.int32)
            chunks.append((ctok, cpos, n))
        pend: List[Tuple[int, int]] = []
        pc = self.prefix_cache
        if chunks and pc is not None and seq.request.resume is None \
                and seq.request.use_prefix_cache:
            # the pages admission just sealed but this plan has yet to
            # write: page j is complete once positions through
            # (j+1)*P - 1 have been issued
            P = self.page_size
            ids = self.cache.seq_page_ids(seq.seq_id)
            for j in range(start // P, n // P):
                pid = int(ids[j])
                pc.unwritten.add(pid)
                pend.append((pid, (j + 1) * P - 1))
        self._prefill_plans[seq.seq_id] = {
            "chunks": chunks, "t0": time.perf_counter(),
            "count": len(chunks), "tokens": n - start,
            "await": awaits, "cow": cow, "pending": pend}
        return len(chunks)

    def _drop_plan(self, seq_id: str) -> set:
        """Remove a sequence's prefill plan (preemption / abort /
        expiry mid-plan).  Pages the plan never wrote through were
        sealed at admission but hold no valid KV: un-publish them so no
        future request can hit them, and return them so current
        sharers can be recomputed too (``_preempt_plan_sharers``)."""
        plan = self._prefill_plans.pop(seq_id, None)
        if plan is None:
            return set()
        stale = {pid for pid, _ in plan["pending"]}
        if stale and self.prefix_cache is not None:
            self.prefix_cache.invalidate_pages(stale)
        return stale

    def _preempt_plan_sharers(self, stale: set):
        """Cascade recompute: every running sequence still barrier-held
        on one of the ``stale`` pages shared KV that will now never be
        written — preempt it back to the queue (deterministic replay,
        like any recompute-preemption) before it can read garbage."""
        for s in list(self.scheduler.running):
            plan = self._prefill_plans.get(s.seq_id)
            if plan is None or not (plan["await"] & stale):
                continue
            self.scheduler.preempt(s)
            self.metrics.on_preemption(1)
            self._uploaded_pages.pop(s.seq_id, None)
            sub = self._drop_plan(s.seq_id)
            if self.spec is not None:
                self.spec.on_drop(s.seq_id)
            for i, lane_seq in enumerate(self._lanes):
                if lane_seq is s:
                    self._lanes[i] = None
                    self._clear_lane(i)
            if sub:
                self._preempt_plan_sharers(sub)

    def _steady_rows(self, bucket: int):
        """The steady-decode ragged inputs for one lane bucket (Q=1,
        every lane advancing, no KV horizon) — device arrays cached per
        bucket, so steady decode performs no host transfer at all."""
        ent = self._ragged_steady.get(bucket)
        if ent is None:
            ent = (self._dput(np.zeros((bucket, 1), np.int32)),
                   self._dput(np.zeros((bucket, 1), np.int32)),
                   self._dput(np.full((bucket, 1),
                                          self._ragged_no_limit,
                                          np.int32)),
                   self._dput(np.ones((bucket,), np.int32)))
            self._ragged_steady[bucket] = ent
        return ent

    def _dispatch_ragged(self, active: List[Tuple[int, Sequence]]) -> int:
        """Issue ONE mixed ragged dispatch: every bound lane rides —
        decode lanes advance one position on device; lanes with a
        pending prefill plan carry their next chunk's rows (advance=0,
        device state untouched until the plan drains).  Steady decode
        (no plans) reuses per-bucket cached input arrays and is
        bit-identical to the split decode program."""
        B = self._state_bucket
        with RecordEvent("serving/plan_rows") as ev:
            chunks: Dict[int, Tuple[np.ndarray, np.ndarray, int]] = {}
            idle: set = set()
            done_plans: List[Tuple[str, dict]] = []
            # barrier snapshot BEFORE this dispatch issues anything: a lane
            # may only read a shared page once the chunk writing it was
            # issued by an EARLIER dispatch (device program order then
            # commits the payload ahead of the read)
            pc = self.prefix_cache
            pending_before = set(pc.unwritten) if pc is not None \
                and pc.unwritten else ()
            for lane, seq in active:
                plan = self._prefill_plans.get(seq.seq_id)
                if plan is None:
                    continue
                aw = plan["await"]
                if aw:
                    aw.intersection_update(pending_before)
                if aw:
                    idle.add(lane)           # barrier holds: no chunk, no
                    continue                 # decode, device state frozen
                if plan["cow"]:
                    # deferred copy-on-write: the source page's payload is
                    # committed now — duplicate it before this dispatch
                    self._apply_cow(seq)
                    plan["cow"] = False
                if plan["chunks"]:
                    ctok, cpos, n = plan["chunks"].popleft()
                    chunks[lane] = (ctok, cpos, n)
                    pend = plan["pending"]
                    if pend:
                        # sealed pages this chunk writes through are now
                        # issued — readers may pass their barrier next step
                        through = min(int(cpos[-1]), n - 1)
                        while pend and pend[0][1] <= through:
                            pc.unwritten.discard(pend.pop(0)[0])
                if not plan["chunks"]:
                    done_plans.append(
                        (seq.seq_id,
                         self._prefill_plans.pop(seq.seq_id)))
            self._sync_rows(active)
            t = time.perf_counter()
            if self._last_dispatch is not None:
                self.metrics.on_dispatch_gap(t - self._last_dispatch)
            self._last_dispatch = t
            prefill_rows = 0
            if not chunks and not idle:
                Q = 1
                rows_tok, rows_pos, row_valid, advance = self._steady_rows(B)
            else:
                # mixed step: fresh host rows for this step's chunk mix —
                # pow2 row bucket (chunk sizes already are), junk padding
                # rows carry row_valid 0 (trash-page scatter, zero
                # attention span)
                Q = max((c[0].size for c in chunks.values()), default=1)
                rt = np.zeros((B, Q), np.int32)
                rp = np.zeros((B, Q), np.int32)
                rv = np.zeros((B, Q), np.int32)
                adv = np.ones((B,), np.int32)
                rv[:, 0] = self._ragged_no_limit
                for lane, (ctok, cpos, n) in chunks.items():
                    sz = ctok.size
                    rt[lane, :sz] = ctok
                    rp[lane, :sz] = cpos
                    rv[lane, :] = 0
                    rv[lane, :sz] = n
                    adv[lane] = 0
                    prefill_rows += sz
                for lane in idle:
                    # barrier-held lane: every row junk, no advance — the
                    # device state is untouched until the awaited pages'
                    # writes have been issued
                    rv[lane, :] = 0
                    adv[lane] = 0
                for lane, seq in active:
                    if lane in chunks:
                        flight.request_event(
                            seq.seq_id, EV_PREFILL_CHUNK,
                            replica=self.chaos_key,
                            size=int(chunks[lane][0].size))
                rows_tok = self._dput(rt)
                rows_pos = self._dput(rp)
                row_valid = self._dput(rv)
                advance = self._dput(adv)
            # the step's work, from host state alone (no device read):
            # each decode lane reads its pos + 1 KV positions for one
            # row; a chunk's rows that carry a prompt token (positions
            # under the prompt's horizon n) each read their own
            # position + 1, the lane as a whole the last of them
            decode_rows = 0
            ctx_tokens = 0
            for lane, seq in active:
                if lane not in chunks and lane not in idle:
                    decode_rows += 1
                    ctx_tokens += seq.pos + 1
            attn_pairs = ctx_tokens
            for ctok, cpos, n in chunks.values():
                first = int(cpos[0])
                last = min(first + ctok.size, n)
                if last > first:
                    ctx_tokens += last
                    attn_pairs += (first + 1 + last) * (last - first) // 2
            # rows of the bucket the kernel skips: a lane is computed up
            # to the row block covering its last live row — a chunk lane
            # its chunk's rows, an idle lane none, every other lane row 0
            attn_rows_skipped = (
                (B - len(chunks) - len(idle)) * self._rows_skipped(1, Q)
                + len(idle) * self._rows_skipped(0, Q)
                + sum(self._rows_skipped(c[0].size, Q)
                      for c in chunks.values()))
            # the pool write's work: every lane but a chunk or idle one
            # writes its row 0 (whether one row is a whole page does not
            # depend on where it sits), a chunk lane the rows under its
            # prompt's length, an idle lane nothing
            rows1, pages1 = self._kv_write_counts(0, 1, self.page_size)
            kv_rows = (B - len(chunks) - len(idle)) * rows1
            kv_pages = (B - len(chunks) - len(idle)) * pages1
            for ctok, cpos, n in chunks.values():
                first = int(cpos[0])
                r, p = self._kv_write_counts(
                    first, min(ctok.size, n - first), self.page_size)
                kv_rows += r
                kv_pages += p
            ev.set(chunks=len(chunks), idle=len(idle))
        if self._mesh_layout is not None:
            # chaos site ``serving.shard_sync``: the last host boundary
            # before the mesh-wide sharded dispatch — ``delay`` models a
            # straggler shard holding the collective back, ``raise``
            # models a failed cross-shard exchange (the frontend treats
            # an engine-step exception as a replica crash and fails the
            # whole mesh replica over, which is exactly the blast
            # radius of a dead chip in a tp/sp group)
            chaos_site("serving.shard_sync", key=self.chaos_key)
            self.metrics.on_shard_step()
        with RecordEvent("serving/ragged_step", bucket=B, rows=Q,
                         decode_rows=decode_rows, prefill_rows=prefill_rows,
                         ctx_tokens=ctx_tokens, attn_pairs=attn_pairs):
            (_out_rows, out_dec, self._tokens, self._pos,
             self._kv) = self._ragged_jit(
                self._tokens, self._pos, self._tables, rows_tok,
                rows_pos, row_valid, advance, self._kv)
        # chunk lanes did not decode this step: their out_dec entry is
        # junk and their host mirror must not advance — snapshot them
        # as None so the consume loop skips them
        snapshot = tuple(
            (s, s.epoch) if s is not None and i not in chunks
            and i not in idle else None
            for i, s in enumerate(self._lanes))
        for lane, s in active:
            if lane not in chunks and lane not in idle:
                s.pos += 1
        self._pending.append(_Pending(out_dec, 1, snapshot))
        self.metrics.on_ragged(
            decode_rows=decode_rows, prefill_rows=prefill_rows, q_bucket=Q,
            rows_computed=B * Q, ctx_tokens=ctx_tokens,
            attn_pairs=attn_pairs, attn_rows_skipped=attn_rows_skipped,
            kv_rows_written=kv_rows, kv_page_copies=kv_pages)
        for sid, plan in done_plans:
            if not plan["count"]:
                # barrier-only plan (fully-covered prefix hit): the
                # split path records no prefill either
                continue
            # the plan drained: prefill accounting records wall time
            # since admission (the chunks ran interleaved across steps)
            dt = time.perf_counter() - plan["t0"]
            self.metrics.on_prefill(dt)
            self.metrics.on_prefill_chunks(plan["count"],
                                           plan["tokens"], dt)
        return 1

    # --- prefix cache (docs/SERVING.md "Prefix caching") ------------------
    def _apply_cow(self, seq: Sequence):
        """Perform the device half of a copy-on-write admission: the
        scheduler already swapped the shared page for a fresh one in the
        host table; duplicate the payload src -> dst on device
        (``serving.page_cow`` — no host round trip) so the sequence's
        decode writes diverge privately."""
        src, dst = seq.cow_pair
        self._kv = self._page_cow_jit(self._kv,
                                      self._dput(np.int32(src)),
                                      self._dput(np.int32(dst)))
        self.prefix_cache.on_cow()

    def _seal_prefix(self, seq: Sequence, upto_pos: int):
        """Publish ``seq``'s full pages covering positions
        ``[0, upto_pos)`` into the prefix index, keyed by the token ids
        that produced them (prompt + generated).  Only pages the
        sequence will NEVER write again are sealable: callers pass the
        first position any future write of this sequence can touch.
        Pure host work — steady decode stays transfer-guard-clean."""
        pc = self.prefix_cache
        req = seq.request
        if pc is None or req.resume is not None \
                or not req.use_prefix_cache:
            return
        full = upto_pos // self.page_size
        if full <= 0:
            return
        tokens = req.prompt
        if full * self.page_size > tokens.size:
            tokens = np.concatenate(
                [tokens, np.asarray(seq.generated, np.int32)])
        pc.insert(tokens, self.cache.seq_page_ids(seq.seq_id), full)

    # --- pipelined decode -------------------------------------------------
    def _remaining(self, seq: Sequence) -> int:
        """Dispatch budget left: max_new_tokens minus tokens already
        DISPATCHED (seq.pos advances at dispatch, ahead of consume)."""
        return (seq.request.max_new_tokens
                - (seq.pos - (seq.request.prompt.size - 1)))

    def _dispatch(self, active: List[Tuple[int, Sequence]]) -> int:
        """Issue one decode program (single or fused K-step) against the
        device-resident state; returns the number of steps dispatched."""
        if self.ragged:
            return self._dispatch_ragged(active)
        k = 1
        if (self._fused_jit is not None and not self.sync_mode
                and not self.scheduler.waiting
                and min(self._remaining(s) for _, s in active)
                >= self.fused_steps):
            # reserve pages covering pos+K for every lane WITHOUT
            # preemption — speculative capacity must not evict anyone;
            # partial reservations are kept (they're used within K steps)
            if all(self.scheduler.reserve(s, s.pos + self.fused_steps)
                   for _, s in active):
                k = self.fused_steps
        # the reservation above (and any partial one) may have grown
        # tables — the device rows must cover every position this
        # program writes, or the writes fall into the trash page
        self._sync_rows(active)
        t = time.perf_counter()
        if self._last_dispatch is not None:
            self.metrics.on_dispatch_gap(t - self._last_dispatch)
        self._last_dispatch = t
        with RecordEvent("serving/decode_step", bucket=self._state_bucket,
                         steps=k):
            if k == 1:
                out, self._pos, self._kv = self._decode_jit(
                    self._tokens, self._pos, self._tables, self._kv)
                if self.numeric_guards:
                    # (clean argmax for device feedback, guard-packed
                    # copy for host consumption) — one transfer either way
                    clean, out = out
                    self._tokens = clean
                else:
                    self._tokens = out
            else:
                out, self._tokens, self._pos, self._kv = self._fused_jit(
                    self._tokens, self._pos, self._tables, self._kv)
        snapshot = tuple((s, s.epoch) if s is not None else None
                         for s in self._lanes)
        for _, s in active:
            s.pos += k                   # host mirror: dispatch-advanced
        self._pending.append(_Pending(out, k, snapshot))
        return k

    def _consume_one(self) -> int:
        """Block on the OLDEST in-flight step's tokens (the newest keeps
        running), apply them to the host mirror, retire finished lanes;
        returns tokens emitted."""
        ent = self._pending.popleft()
        t0 = time.perf_counter()
        with RecordEvent("serving/fetch_tokens"):
            toks = np.asarray(jax.device_get(ent.tokens))
        self.metrics.on_decode(time.perf_counter() - t0)
        rows = toks if ent.steps > 1 else toks[None, :]
        now = time.monotonic()
        emitted = 0
        for krow in rows:
            for lane, binding in enumerate(ent.lanes):
                if binding is None:
                    continue
                seq, epoch = binding
                # retired (one-step EOS lag), preempted-since (epoch
                # bump) or already guard-flagged: the device token is
                # junk — drop it
                if seq.done or seq.epoch != epoch or seq.numeric_fault:
                    continue
                tok = int(krow[lane])
                if tok < 0:
                    # guard verdict, in-band: argmax is always >= 0, so
                    # a negative token is the device-side guard's
                    # non-finite-logits flag (-1 - tok).  NEVER
                    # emitted; the request is quarantined (failed,
                    # pages scrubbed + freed) once the step's pipeline
                    # collapses.
                    self.metrics.on_nan_lane()
                    seq.numeric_fault = True
                    self._quarantine_pending.append(seq)
                    continue
                emitted += 1
                self._emit_token(seq, lane, tok, now)
        return emitted

    def _emit_token(self, seq: Sequence, lane: int, tok: int,
                    now: float) -> bool:
        """Apply ONE consumed token to a live sequence — the single
        emission path (the pipelined consume loop and the spec-decode
        accept loop both feed it, so the callback stream is identical
        across every mode): TTFT bookkeeping, stream callback, drafter
        observation, EOS/budget retirement.  Returns True when the
        token retired the sequence."""
        if seq.first_token_time is None:
            seq.first_token_time = now
            if seq.seq_id not in self._ttft_recorded:
                self._ttft_recorded.add(seq.seq_id)
                self.metrics.on_first_token(
                    seq.request.arrival_time, now)
                flight.request_event(seq.seq_id, EV_FIRST_TOKEN,
                                     replica=self.chaos_key)
        seq.generated.append(tok)
        seq.next_token = tok
        if self.spec is not None:
            self.spec.on_token(seq.seq_id, tok)
        if self.token_callback is not None:
            self.token_callback(seq.seq_id,
                                seq.num_generated - 1, tok)
        if (tok == self.eos_id
                or seq.num_generated >= seq.request.max_new_tokens):
            self._retire(seq, lane)
            return True
        return False

    def _retire(self, seq: Sequence, lane: int):
        """EOS / budget retirement: final — the id never reappears."""
        self.outputs[seq.seq_id] = np.asarray(seq.generated, np.int32)
        if self.spec is not None:
            # publish the finished stream into the drafter's shared
            # n-gram corpus (the same chain _seal_prefix publishes as
            # radix-index pages) and drop the lane state
            self.spec.on_retire(seq)
        # seal BEFORE finish: the full pages this request wrote (prompt
        # AND generated tokens) stay resident in the prefix index after
        # its references drop — a completed request is the donor the
        # next shared-prefix arrival hits
        self._seal_prefix(seq, seq.request.prompt.size - 1
                          + seq.num_generated)
        self.scheduler.finish(seq)
        seq.done = True
        self._ttft_recorded.discard(seq.seq_id)
        self._uploaded_pages.pop(seq.seq_id, None)
        self.metrics.on_completion()
        # first-wins with the frontend's own resolve (same status) —
        # standalone engines get terminal-complete traces too
        flight.request_terminal(seq.seq_id, "completed",
                                replica=self.chaos_key,
                                tokens=seq.num_generated)
        if (lane < len(self._lanes)) and self._lanes[lane] is seq:
            self._lanes[lane] = None
            self._clear_lane(lane)

    def _sync_pending(self) -> int:
        """Collapse the pipeline: consume every in-flight step.  Every
        caller collapses through here, so the span and the counter see
        each collapse that drained a step, whoever asked for it."""
        if not self._pending:
            return 0
        emitted = 0
        with RecordEvent("serving/collapse", steps=len(self._pending)):
            while self._pending:
                emitted += self._consume_one()
        self.metrics.on_pipeline_collapse()
        return emitted

    # --- speculative decoding (docs/SERVING.md "Speculative decoding") ----
    def _spec_touched_pages(self, seq: Sequence) -> List[int]:
        """The allocated pages a spec dispatch can write for ``seq``:
        pages covering positions [pos, pos + K) that exist in its table
        (junk past the allocation lands in the trash page)."""
        P = self.page_size
        table = self.cache.seq_page_ids(seq.seq_id)
        p0 = seq.pos // P
        p1 = min((seq.pos + self.spec.k - 1) // P, len(table) - 1)
        return table[p0: p1 + 1] if p1 >= p0 else []

    def _spec_rollback(self, seq: Sequence, saved, inputs, pos0: int,
                       took: int):
        """int8_dynamic rollback: junk writes past the accepted prefix
        grew per-page scales and requantized page content — restore the
        dispatch's touched pages from the pre-dispatch device gather,
        then replay the ``took`` emitted positions ONE AT A TIME through
        the prefill program, so per-page scale growth is progressive
        exactly like the plain decode loop's (the documented dynamic
        byte-identity contract).  Native / int8_static modes never get
        here: their junk is inert until overwritten."""
        rows_dev, payload = saved
        self._kv = self._page_put_jit(self._kv, rows_dev, payload)
        row = self._dput(self.cache.page_table_row(seq.seq_id))
        for j in range(took):
            self._kv = self._prefill_jit(
                self._dput(np.asarray([inputs[j]], np.int32)),
                self._dput(np.asarray([pos0 + j], np.int32)),
                row, self._dput(np.int32(pos0 + j + 1)), self._kv)

    def _spec_step(self, active) -> Optional[dict]:
        """Attempt one drafter/verifier speculation step.  Returns None
        when nothing was touched (the caller runs the plain/fused
        dispatch: no drafts plausible, chaos ``spec.draft`` denial,
        admissions waiting, or a lane too close to its position
        ceiling); otherwise a ``{"emitted", "bucket", "lanes"}`` dict —
        including the degraded case where drafts evaporated after the
        pipeline collapse and a plain dispatch ran instead.

        Synchronous by design: the accept decision gates the NEXT
        dispatch's positions, so the pipeline is collapsed first and
        the verify dispatch is consumed immediately — the win is K
        tokens per weight-set stream, not dispatch overlap."""
        spec = self.spec
        K = spec.k
        if self._prefill_plans:
            # ragged mode: a lane mid-prefill-plan carries chunk rows
            # every step — speculation resumes once the plans drain
            return None
        # NOTE: unlike fused mode there is no ``scheduler.waiting``
        # gate — a verify is ONE dispatch (admission latency matches a
        # plain step, and admission runs before dispatch every step),
        # whereas fused mode holds the device for K sequential steps.
        # Queue-pressure page safety comes from the non-preempting
        # per-lane reserve below: a lane whose horizon cannot be
        # covered degrades to a plain ride-along, never evicts anyone.
        # position ceiling: the verify program writes K positions per
        # lane; past max_seq_len the core's clamps would fold junk into
        # a live page — degrade instead
        if any(s.pos + K > self.max_seq_len for _, s in active):
            return None
        # chaos site ``spec.draft``: deny => this step degrades to
        # plain decode (never fails or corrupts a request)
        fault = chaos_site("spec.draft", key=self.chaos_key)
        if fault is not None and fault.action == "deny":
            spec.on_degraded()
            return None
        # cheap probe on the (possibly one-dispatch-stale) host mirror
        # BEFORE collapsing the pipeline: a draftless steady state keeps
        # dispatch-ahead intact.  The probe is the throttle clock
        # (tick=True): per-lane cooldowns count spec-considered engine
        # steps, whether or not a dispatch follows
        if not any(len(d) for d in
                   spec.propose(active, tick=True).values()):
            return None
        emitted = self._sync_pending()
        active = [(i, s) for i, s in enumerate(self._lanes)
                  if s is not None]
        if not active:
            return {"emitted": emitted, "bucket": 0, "lanes": 0}
        # real proposals against the now-current history (the probe
        # already ticked the throttle — tick=False here), then reserve
        # each drafted lane's K-token horizon WITHOUT preemption —
        # denial degrades that lane to a plain ride-along within the
        # same dispatch
        drafts = spec.propose(active, tick=False)
        for lane, seq in active:
            d = drafts.get(lane)
            if d is not None and len(d) \
                    and not self.scheduler.reserve(seq, seq.pos + K):
                spec.on_degraded()
                drafts[lane] = d[:0]
        if not any(len(d) for d in drafts.values()):
            # the probe's candidates evaporated (consumed tokens or
            # reservation denial): plain dispatch so the step still
            # makes progress — a permanent denial must not livelock
            self._dispatch(active)
            return {"emitted": emitted, "bucket": self._state_bucket,
                    "lanes": len(active)}
        bucket = self._state_bucket
        # device table rows must cover every reserved position
        self._sync_rows(active)
        saved = {}
        if self._kv_dynamic:
            # pre-dispatch device-to-device gather of the write-span
            # pages: junk writes grow per-page scales irreversibly, so
            # rejection restores from this copy (no host round trip)
            for lane, seq in active:
                rows = self._spec_touched_pages(seq)
                if rows:
                    padded = np.zeros((next_pow2(len(rows)),), np.int32)
                    padded[: len(rows)] = rows
                    rows_dev = self._dput(padded)
                    saved[lane] = (rows_dev, self._page_gather_jit(
                        self._kv, rows_dev))
        # [K, bucket] teacher-forcing inputs: row 0 every lane's real
        # next token, rows 1.. the draft (junk-padded to the traced K —
        # outputs past the real draft are ignored host-side, their
        # writes land in reserved pages or the trash page)
        draft_mat = np.zeros((K, bucket), np.int32)
        for lane, seq in active:
            draft_mat[0, lane] = seq.next_token
            d = drafts.get(lane)
            if d is not None and len(d):
                draft_mat[1: 1 + len(d), lane] = d
        t = time.perf_counter()
        if self._last_dispatch is not None:
            self.metrics.on_dispatch_gap(t - self._last_dispatch)
        self._last_dispatch = t
        with RecordEvent("serving/spec_verify", bucket=bucket, steps=K):
            if self._spec_jit is not None:
                out, self._kv = self._spec_jit(
                    self._dput(draft_mat), self._pos, self._tables,
                    self._kv)
                t0 = time.perf_counter()
                toks = np.asarray(jax.device_get(out))    # [K, bucket]
            else:
                # ragged fold-in: the verify rides the unified kernel —
                # K teacher-forcing rows per lane, advance=0 everywhere
                # (the accept decision below uploads the surviving
                # state wholesale, exactly like the split path)
                rows_tok = np.ascontiguousarray(draft_mat.T)
                rows_pos = np.zeros((bucket, K), np.int32)
                rows_val = np.zeros((bucket, K), np.int32)
                for lane, seq in active:
                    rows_pos[lane] = seq.pos + np.arange(K)
                    rows_val[lane] = self._ragged_no_limit
                (out_rows, _dec, self._tokens, self._pos,
                 self._kv) = self._ragged_jit(
                    self._tokens, self._pos, self._tables,
                    self._dput(rows_tok), self._dput(rows_pos),
                    self._dput(rows_val),
                    self._dput(np.zeros((bucket,), np.int32)),
                    self._kv)
                # K rows per lane at pos .. pos + K - 1, each reading
                # its own position + 1
                ctx = sum(seq.pos + K for _, seq in active)
                self.metrics.on_ragged(
                    spec_rows=K * len(active), q_bucket=K,
                    rows_computed=bucket * K, ctx_tokens=ctx,
                    attn_pairs=K * ctx - len(active) * K * (K - 1) // 2,
                    attn_rows_skipped=(bucket - len(active))
                    * self._rows_skipped(0, K),
                    kv_rows_written=K * len(active),
                    kv_page_copies=sum(
                        self._kv_write_counts(seq.pos, K,
                                              self.page_size)[1]
                        for _, seq in active))
                t0 = time.perf_counter()
                toks = np.ascontiguousarray(              # [K, bucket]
                    np.asarray(jax.device_get(out_rows)).T)
            self.metrics.on_decode(time.perf_counter() - t0)
        now = time.monotonic()
        results = []
        for lane, seq in active:
            d = drafts.get(lane)
            dn = len(d) if d is not None else 0
            col = toks[:, lane]
            # prefix-match-then-take-the-verifier's-next-token: exact
            # greedy byte-identity whatever the drafter proposed
            a = spec.accept_len(d if dn else col[:0], col)
            e = min(a, self._remaining(seq))
            pos0 = seq.pos
            took = 0
            done = False
            for i in range(e):
                if col[i] < 0:
                    # the verifier inherits the decode guard: a
                    # negative-packed verify token means non-finite
                    # logits at that position — the lane is
                    # quarantined, nothing at or past it is emitted.
                    # (A packed token also never equals a draft token,
                    # so accept_len cannot extend past the damage.)
                    self.metrics.on_nan_lane()
                    seq.numeric_fault = True
                    self._quarantine_pending.append(seq)
                    break
                seq.pos += 1
                took += 1
                emitted += 1
                done = self._emit_token(seq, lane, int(col[i]), now)
                if done:
                    break
            if dn:
                results.append((seq.seq_id, dn, a - 1))
                flight.request_event(seq.seq_id, EV_SPECULATED,
                                     replica=self.chaos_key,
                                     drafted=dn, accepted=a - 1)
            if self._kv_dynamic and not done and not seq.numeric_fault \
                    and lane in saved \
                    and min(pos0 + K, self.cache.allocated_tokens(
                        seq.seq_id)) > pos0 + took:
                self._spec_rollback(seq, saved[lane], draft_mat[:, lane],
                                    pos0, took)
        spec.on_verify(results)
        # one wholesale upload of the surviving lanes' (token, pos) —
        # the verify program advances nothing on device, the accept
        # decision lives here on host
        tokens = np.zeros((self._state_bucket,), np.int32)
        pos = np.zeros((self._state_bucket,), np.int32)
        for i, s in enumerate(self._lanes):
            if s is not None:
                tokens[i] = s.next_token
                pos[i] = s.pos
        self._tokens = self._dput(tokens)
        self._pos = self._dput(pos)
        return {"emitted": emitted, "bucket": bucket,
                "lanes": len(active)}

    def _admit_waiting(self) -> List[Sequence]:
        """Admit what fits from the waiting queue (pipeline already
        collapsed) and run each admission's device half: page-scale
        reset, snapshot upload or COW copy + prefill plan, lane bind.
        The scheduler's choice is one span (``serving/schedule``), each
        admitted sequence's set-up another (``serving/admit_request``)."""
        sched = self.scheduler
        with RecordEvent("serving/schedule") as ev:
            if self.kv_transport is not None:
                # admission boundary (ISSUE 16): promote tier hits for
                # the waiting prompts, and open the ONLY window where
                # evictions demote (admission-pressure reclaims gather
                # D2H here; decode-time pressure keeps discarding, so
                # steady decode never pays a transfer)
                self.kv_transport.chaos_key = self.chaos_key
                self.kv_transport.demote_window = True
                try:
                    for req in sched.waiting:
                        if req.resume is None and req.use_prefix_cache:
                            self.prefix_cache.promote_for(req.prompt)
                    admitted = sched.admit()
                finally:
                    self.kv_transport.demote_window = False
            else:
                admitted = sched.admit()
            ev.set(admitted=len(admitted))
        now = time.monotonic()
        self.metrics.on_admission(
            len(admitted),
            queue_waits=[now - s.request.arrival_time for s in admitted])
        for seq in admitted:
            resume = seq.request.resume is not None
            flight.request_event(seq.seq_id, EV_ADMITTED,
                                 replica=self.chaos_key, resume=resume)
            if not resume and seq.cached_tokens:
                flight.request_event(seq.seq_id, EV_PREFIX_HIT,
                                     replica=self.chaos_key,
                                     tokens=int(seq.cached_tokens))
            with RecordEvent("serving/admit_request", resume=int(resume),
                             cached_tokens=int(seq.cached_tokens)) as ev:
                ev.set(chunks=self._admit_one(seq, resume))
        return admitted

    def _admit_one(self, seq: Sequence, resume: bool) -> int:
        """One admitted sequence's device half; returns the prefill
        chunks planned (or dispatched, split mode) — 0 for a resume or
        a prompt the prefix cache covers whole."""
        chunks = 0
        # freshly allocated pages must quantize from scratch (dynamic
        # int8 mode; no-op otherwise — and dynamic mode bypasses the
        # prefix cache, so no shared page can ever be scale-reset here)
        self._reset_page_scales(self.cache.seq_page_ids(seq.seq_id))
        if resume:
            # warm-failover resume: upload checkpoint pages instead of
            # prefilling — decode continues mid-stream
            self._upload_snapshot(seq)
        else:
            # hit/miss accounting and the sealing of prompt pages
            # happened inside Scheduler.admit (host-side, so intra-batch
            # sharing works); the device halves — the COW page copy and
            # the suffix prefill — run here in admission order
            deps = ()
            if self.ragged and seq.cached_tokens \
                    and self.prefix_cache is not None:
                # shared pages this sequence READS whose writer is
                # itself still mid-plan: the lane must idle until their
                # writes are issued (and the COW copy below must wait
                # with it — it would duplicate an empty page)
                ids = self.cache.seq_page_ids(seq.seq_id)
                unw = self.prefix_cache.unwritten
                deps = {int(p) for p in
                        ids[:seq.cached_tokens // self.page_size]
                        if int(p) in unw}
                if seq.cow_pair is not None \
                        and int(seq.cow_pair[0]) in unw:
                    # the COW SOURCE is no longer in this sequence's
                    # table (the host already swapped in the copy) but
                    # the copy's payload comes from it
                    deps.add(int(seq.cow_pair[0]))
            if seq.cow_pair is not None and not deps:
                self._apply_cow(seq)
            if self.ragged:
                # unified dispatch: plan now, chunks ride the mixed
                # ragged steps (no dedicated prefill program, no
                # serialization ahead of decode)
                chunks = self._plan_prefill(seq, awaits=deps)
            else:
                chunks = self._prefill_seq(seq)
        self._bind_lane(seq)
        if self.spec is not None:
            # seed the drafter with the lane's full history (prompt,
            # plus generated for a snapshot resume — which also
            # restores the drafter's adaptive state)
            self.spec.on_admit(seq)
        return chunks

    # --- one scheduler iteration -----------------------------------------
    def step(self) -> dict:
        """Admit + prefill waiting requests, then dispatch one decode
        program and consume the previous one.  Returns the step's stats.

        Chaos site ``engine.step``: ``delay`` injects artificial step
        latency (a straggler — inside the timed window, so the watchdog
        and ``serving.step_latency_ms`` both see it), ``raise`` throws
        InternalError mid-step (the frontend treats an engine-step
        exception as a replica crash and fails its requests over)."""
        t_step = time.perf_counter()
        chaos_site("engine.step", key=self.chaos_key)
        with RecordEvent("serving/step"):
            return self._step_inner(t_step)

    def _step_inner(self, t_step: float) -> dict:
        sched = self.scheduler
        admitted: List[Sequence] = []
        emitted = 0
        # deadline enforcement: expired-in-queue requests are dropped
        # BEFORE admission (same `now` for the whole step, so a request
        # expiring exactly on the admission step is rejected, never
        # prefilled); expired-mid-decode sequences are aborted and their
        # pages freed.  Pure host python — the steady-state decode loop
        # stays transfer-guard-clean.
        now = time.monotonic()
        for req in sched.expire_queued(now):
            self._expired.append(req.request_id)
            self.metrics.on_deadline_miss()
            flight.request_terminal(req.request_id, "deadline_miss",
                                    replica=self.chaos_key)
        for seq in [s for s in sched.running if s.request.expired(now)]:
            if self.abort(seq.seq_id):
                self._expired.append(seq.seq_id)
                self.metrics.on_deadline_miss()
                flight.request_terminal(seq.seq_id, "deadline_miss",
                                        replica=self.chaos_key)
        # admission needs ground truth (free lanes/pages come from
        # retirements hiding in the pipeline), so it collapses the
        # pipeline first; a FULL batch skips the attempt entirely and
        # stays pipelined under queue pressure
        if sched.waiting and len(sched.running) < sched.max_batch_size:
            with RecordEvent("serving/admit") as ev:
                collapsed = self._sync_pending()
                admitted = self._admit_waiting()
                ev.set(admitted=len(admitted), collapsed=collapsed)
            emitted += collapsed

        bucket = 0
        dispatched_lanes = 0
        active = [(i, s) for i, s in enumerate(self._lanes) if s is not None]
        if any(self._remaining(s) > 0 for _, s in active):
            # pages for the positions this dispatch writes; preemption
            # may strike lanes (including ones with results in flight —
            # their epochs are bumped, pending tokens become no-ops)
            with RecordEvent("serving/ensure_pages") as ev:
                preempted = sched.ensure_decode_pages(
                    [s for _, s in active if self._remaining(s) > 0])
                ev.set(preempted=len(preempted))
                if preempted:
                    self.metrics.on_preemption(len(preempted))
                for victim in preempted:
                    self._uploaded_pages.pop(victim.seq_id, None)
                    stale = self._drop_plan(victim.seq_id)
                    if self.spec is not None:
                        self.spec.on_drop(victim.seq_id)
                    for i, lane_seq in enumerate(self._lanes):
                        if lane_seq is victim:
                            self._lanes[i] = None
                            self._clear_lane(i)
                    if stale:
                        # mid-plan victim: sharers of its never-written
                        # sealed pages must recompute too
                        self._preempt_plan_sharers(stale)
            active = [(i, s) for i, s in enumerate(self._lanes)
                      if s is not None]
            if any(self._remaining(s) > 0 for _, s in active):
                # chaos site ``serving.logits`` (ISSUE 13): one visit
                # per active lane, keyed by its request id — a
                # ``nan_logits`` fault poisons that lane's KV on device
                # so the NEXT dispatch's logits are non-finite for
                # exactly that lane (a single global read per lane when
                # no plan is installed)
                for _lane, s in active:
                    fault = chaos_site("serving.logits", key=s.seq_id)
                    if fault is not None \
                            and fault.action == "nan_logits":
                        self._poison_lane(s)
                spec_res = (self._spec_step(active)
                            if self.spec is not None else None)
                if spec_res is not None:
                    emitted += spec_res["emitted"]
                    bucket = spec_res["bucket"]
                    dispatched_lanes = spec_res["lanes"]
                else:
                    bucket = self._state_bucket
                    dispatched_lanes = len(active)
                    self._dispatch(active)

        # dispatch-ahead: keep ONE step in flight (none in sync_mode or
        # when nothing was dispatched — then drain fully so retirements
        # and the final outputs land)
        target_depth = 0 if (self.sync_mode or not bucket) else 1
        with RecordEvent("serving/consume") as ev:
            consumed = 0
            while len(self._pending) > target_depth:
                consumed += self._consume_one()
            # guard verdicts land here: a lane flagged by this step's
            # consume is failed within this same step (pipeline
            # collapsed first so pages are never freed under an
            # in-flight dispatch)
            if self._quarantine_pending:
                self._process_quarantines()
            ev.set(emitted=consumed)
        emitted += consumed
        self._maybe_shrink()

        step_seconds = time.perf_counter() - t_step
        self.metrics.on_step(
            queue_depth=sched.queue_depth(),
            # lanes actually dispatched this step (pre-retirement), so a
            # fully-occupied step whose sequences all finish still
            # records occupancy 1.0, not 0
            running=dispatched_lanes if bucket else len(sched.running),
            bucket=bucket, pages_in_use=self.cache.pages_in_use,
            tokens_emitted=emitted,
            step_seconds=step_seconds,
            kv_cache_bytes=self.kv_cache_bytes())
        flight.on_step(self.chaos_key, bucket=bucket,
                       lanes=dispatched_lanes,
                       pages_in_use=self.cache.pages_in_use,
                       step_ms=step_seconds * 1e3)
        return {
            "admitted": len(admitted),
            "running": len(sched.running),
            "queue_depth": sched.queue_depth(),
            "bucket": bucket,
            "tokens_emitted": emitted,
            "pages_in_use": self.cache.pages_in_use,
            "in_flight": len(self._pending),
        }

    # --- run to completion ------------------------------------------------
    def drain(self, max_steps: int = 100_000) -> Dict[str, np.ndarray]:
        """Step until queue, batch and pipeline are empty; returns (and
        takes ownership of) all accumulated {request_id: generated
        tokens} — a long-lived server must consume outputs (here or via
        ``take_output``) or ``self.outputs`` grows without bound."""
        steps = 0
        while self.scheduler.has_work() or self._pending:
            self.step()
            steps += 1
            if steps > max_steps:
                raise InternalError(
                    f"drain did not converge within {max_steps} steps")
        out, self.outputs = self.outputs, {}
        return out

    def take_output(self, request_id: str):
        """Pop one finished request's tokens (None if not finished) —
        the streaming-server consumption path that keeps ``outputs``
        bounded."""
        return self.outputs.pop(request_id, None)

    def lower_ragged_step(self, rows: int, lanes: Optional[int] = None):
        """``jax.stages.Lowered`` of the unified step program at ``lanes``
        (default: the largest lane bucket) x ``rows`` query rows against
        this engine's own pools — what ``step()`` would dispatch, without
        running it.  For inspecting the program (``whole_pool_relayouts``,
        ``compile().memory_analysis()``); never on the serving path."""
        B = int(lanes if lanes is not None
                else self.scheduler.bucket_sizes[-1])

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        kv = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), self._kv)
        return self._ragged_jit.lower(
            i32(B), i32(B), i32(B, self.pages_per_seq), i32(B, rows),
            i32(B, rows), i32(B, rows), i32(B), kv)

    def kv_cache_bytes(self) -> int:
        """Actual device bytes of the KV page pools, scales included —
        the resident footprint AND (pool-proportionally) the bytes the
        bytes-bound decode loop streams per step."""
        return int(sum(leaf.nbytes for side in self._kv.values()
                       for leaf in side))

    def kv_bytes_per_token(self) -> float:
        """K+V bytes one cached token costs across all layers (scale
        rows amortized over their page) — the per-token form of the
        int8-vs-bf16 reduction bench reports."""
        return self.kv_cache_bytes() / (self.cache.num_pages
                                        * self.page_size)

    def stats(self) -> dict:
        """Engine + cache + metrics snapshot, incl. per-jit cost
        attribution (FLOPs/bytes/compile counts) for the engine's
        compiled programs.  ``jit_costs`` reads the process-global
        cost_registry: with several engines in one process it is the
        MERGED serving attribution, not per-engine (the quant
        ``matmul_route`` trace counters are process-global the same
        way)."""
        from ..ops.pallas_ops.quantized_matmul import QMM_ROUTE_STATS

        costs = cost_registry.snapshot()
        weight_bytes = None
        if self._weight_quant is not None:
            weight_bytes = int(sum(q.nbytes + s.nbytes
                                   for q, s in self._weight_quant.values()))
        return {
            "metrics": self.metrics.snapshot(),
            "cache": self.cache.stats(self.scheduler.seq_lens()),
            "preemptions": self.scheduler.num_preemptions,
            "pipeline": {
                "sync_mode": self.sync_mode,
                "fused_steps": self.fused_steps,
                "ragged": self.ragged,
                "prefill_chunk": self.prefill_chunk,
                "in_flight": len(self._pending),
                "state_bucket": self._state_bucket,
                "numeric_guards": self.numeric_guards,
                "mesh": (None if self._mesh_layout is None else {
                    "tp": self._mesh_layout.tp,
                    "sp": self._mesh_layout.sp,
                    "devices": self._mesh_layout.size,
                }),
            },
            "prefix_cache": (
                self.prefix_cache.stats()
                if self.prefix_cache is not None else
                {"enabled": False,
                 "bypass_reason": self._prefix_bypass_reason}),
            "spec": (self.spec.stats() if self.spec is not None
                     else {"enabled": False}),
            "quant": {
                "kv_cache_dtype": self.kv_cache_dtype or "native",
                "weight_dtype": self.weight_dtype or "native",
                "kv_scale_mode": ("dynamic" if self._kv_dynamic else
                                  "static" if self.kv_cache_dtype
                                  else None),
                "kv_cache_bytes": self.kv_cache_bytes(),
                "kv_bytes_per_token": self.kv_bytes_per_token(),
                "quant_weight_bytes": weight_bytes,
                "matmul_route": dict(QMM_ROUTE_STATS),
            },
            "jit_costs": {k: v for k, v in costs.items()
                          if k.startswith("serving.")},
        }


def create_serving_engine(model, config=None, **overrides) -> ServingEngine:
    """Build a ServingEngine from an ``inference.Config`` on which
    ``enable_serving()`` was called (the reference-style entry point);
    kwargs override config values."""
    kwargs = {}
    if config is not None:
        if not getattr(config, "serving_enabled", lambda: False)():
            raise InvalidArgumentError(
                "config has serving disabled — call "
                "Config.enable_serving(...) first")
        kwargs.update(config.serving_config())
    kwargs.update(overrides)
    return ServingEngine(model, **kwargs)
