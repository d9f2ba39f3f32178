"""Unified ragged dispatch (ISSUE 18): ONE ``serving.ragged_step``
program carries a mixed batch of {chunked-prefill, steady-decode,
spec-verify} rows per engine step, replacing the split
``serving.{prefill,decode,spec_verify}`` dispatch set.

Acceptance anchors:
- mixed-batch token streams are BYTE-IDENTICAL to the split-program
  engine (``ragged=False``) across native and int8 KV, with chunked
  prefill interleaving against in-flight decode lanes;
- spec-verify FOLDS IN: a ragged spec engine never builds the split
  verify program (``_spec_jit is None``) yet matches the split spec
  engine's streams byte-for-byte; int8_dynamic keeps the documented
  sequential split verifier;
- the steady mixed state stays ``jax.transfer_guard("disallow")``- and
  ``compile_budget(0, prefix="serving.")``-clean (per-bucket cached
  row inputs — no per-step host uploads);
- double-drive determinism on the ragged engine;
- ragged accounting: ``serving.prefill_chunks`` counts the plan's
  chunks, ``serving.ragged.*`` counts rows by stream (promised by the
  split-dispatch pin in test_serving_async.py);
- the ``ragged`` knob validates (non-bool rejected, ``fused_steps``
  conflict rejected) and surfaces in ``stats()["pipeline"]``.

Compile-count pins live in test_jit_ledger.py; this module rides the
session-shared model so the ragged program compiles once for the
whole suite.
"""
import numpy as np
import pytest

import jax

from paddle_tpu.framework.errors import InvalidArgumentError
from paddle_tpu.profiler.jit_cost import compile_budget
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.metrics import stat_registry

VOCAB = 50


@pytest.fixture(scope="module")
def gpt(shared_gpt_small):
    # session-shared model (conftest): the serving programs compile
    # once for the whole suite; weights identical to every reference
    return shared_gpt_small


@pytest.fixture(scope="module")
def quant(gpt):
    from paddle_tpu.slim import export_serving_quant

    rng = np.random.RandomState(3)
    return export_serving_quant(
        gpt, calib_prompts=rng.randint(1, VOCAB, (4, 12)).astype(np.int32))


def _mixed_prompts(rng, lens=(3, 9, 5, 2)):
    # 9 tokens spans three 4-token chunks; 2 and 3 fit in one — the
    # plan mix exercises multi-chunk, single-chunk and sub-chunk rows
    return [rng.randint(1, VOCAB, (n,)).astype(np.int32) for n in lens]


def _drive(eng, prompts, budget=10):
    ids = [eng.add_request(p, max_new_tokens=budget) for p in prompts]
    outs = eng.drain()
    return [outs[rid] for rid in ids]


def _engines(gpt, **kw):
    """(split reference, unified ragged) over identical settings."""
    base = dict(page_size=4, max_batch_size=4, prefill_chunk=4, eos_id=0)
    base.update(kw)
    return (ServingEngine(gpt, ragged=False, **base),
            ServingEngine(gpt, **base))


# =============================================================================
# mixed-batch byte-identity vs the split-program reference
# =============================================================================
class TestByteIdentity:
    def test_native_mixed_batch_matches_split(self, gpt):
        split, ragged = _engines(gpt)
        prompts = _mixed_prompts(np.random.RandomState(0))
        ref = _drive(split, prompts)
        r0 = stat_registry.get("serving.ragged.steps").get()
        got = _drive(ragged, prompts)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        snap = ragged.metrics.snapshot()["ragged"]
        # the whole workload ran ragged: decode AND prefill rows
        assert stat_registry.get("serving.ragged.steps").get() > r0
        assert snap["decode_rows"] > 0 and snap["prefill_rows"] > 0
        assert ragged.cache.pages_in_use == 0

    def test_int8_mixed_batch_matches_split(self, gpt, quant):
        split, ragged = _engines(gpt, kv_cache_dtype="int8",
                                 quant_scales=quant)
        prompts = _mixed_prompts(np.random.RandomState(1))
        for a, b in zip(_drive(split, prompts), _drive(ragged, prompts)):
            np.testing.assert_array_equal(a, b)

    def test_spec_verify_folds_into_ragged(self, gpt):
        """A spec-verify lane IS a ragged K-row lane: the ragged spec
        engine never builds the split verify program yet its streams
        equal the split spec engine's byte-for-byte."""
        split, ragged = _engines(gpt, spec_decode=4)
        assert ragged._spec_jit is None          # folded, not compiled
        assert split._spec_jit is not None       # the split reference
        rng = np.random.RandomState(2)
        # repetitive suffixes so the n-gram drafter actually proposes
        # and K-row verify lanes ride the ragged dispatch
        prompts = [np.tile(rng.randint(1, VOCAB, (p,)).astype(np.int32), 4)
                   for p in (2, 3)]
        ref = _drive(split, prompts, budget=16)
        r0 = stat_registry.get("serving.ragged.spec_rows").get()
        got = _drive(ragged, prompts, budget=16)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert stat_registry.get("serving.ragged.spec_rows").get() > r0
        assert ragged.stats()["spec"]["drafted"] > 0

    def test_int8_dynamic_spec_keeps_split_verifier(self, gpt):
        """Dynamic per-page scales need the gather/restore/replay
        rollback, which the ragged fold-in does not carry — the engine
        must keep the documented sequential split verifier (and still
        match the split engine's streams)."""
        split, ragged = _engines(gpt, spec_decode=4,
                                 kv_cache_dtype="int8")
        assert ragged._spec_jit is not None
        assert ragged.spec.sequential
        rng = np.random.RandomState(3)
        prompts = [np.tile(rng.randint(1, VOCAB, (3,)).astype(np.int32), 3)]
        for a, b in zip(_drive(split, prompts, budget=8),
                        _drive(ragged, prompts, budget=8)):
            np.testing.assert_array_equal(a, b)

    def test_double_drive_deterministic(self, gpt):
        """Same engine, same workload, twice: byte-identical streams —
        the ragged row packing has no order- or time-dependence."""
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            prefill_chunk=4, eos_id=0)
        prompts = _mixed_prompts(np.random.RandomState(4))
        first = _drive(eng, prompts, budget=8)
        second = _drive(eng, prompts, budget=8)
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)


# =============================================================================
# hot-path cleanliness
# =============================================================================
class TestSteadyStateClean:
    def test_steady_mixed_decode_transfer_and_retrace_clean(self, gpt):
        """Once every plan has drained, the ragged steady state reuses
        per-bucket cached device rows: >= 8 steps with zero implicit
        transfers and zero serving retraces."""
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            prefill_chunk=4, eos_id=-1)
        rng = np.random.RandomState(5)
        for p in (3, 9, 5, 2):
            eng.add_request(rng.randint(1, VOCAB, (p,)).astype(np.int32),
                            max_new_tokens=32)
        for _ in range(6):                   # admit + drain every plan
            eng.step()
        assert not eng._prefill_plans
        assert all(s is not None for s in eng._lanes)
        with jax.transfer_guard("disallow"), \
                compile_budget(0, prefix="serving."):
            for _ in range(8):
                stats = eng.step()
                assert stats["bucket"] == 4
        eng.drain()


# =============================================================================
# knob + accounting
# =============================================================================
class TestKnobAndAccounting:
    def test_ragged_knob_validates(self, gpt):
        with pytest.raises(InvalidArgumentError, match="ragged"):
            ServingEngine(gpt, page_size=4, eos_id=0, ragged="yes")
        with pytest.raises(InvalidArgumentError, match="fused_steps"):
            ServingEngine(gpt, page_size=4, eos_id=0, ragged=True,
                          fused_steps=4)

    def test_pipeline_stats_surface_the_mode(self, gpt):
        plain = ServingEngine(gpt, page_size=4, eos_id=0)
        fused = ServingEngine(gpt, page_size=4, eos_id=0, fused_steps=4)
        assert plain.stats()["pipeline"]["ragged"] is True
        # fused_steps keeps the split K-step program: ragged defaults
        # off rather than conflicting
        assert fused.stats()["pipeline"]["ragged"] is False

    def test_prefill_chunk_accounting(self, gpt):
        """The accounting pin promised by test_serving_async.py's
        split-dispatch test: a 9-token prompt prefills its first 8
        tokens (the 9th seeds the decode state) — at prefill_chunk=4
        that is TWO chunks of 4 rows: serving.prefill_chunks counts
        the chunks, serving.ragged.prefill_rows the rows."""
        eng = ServingEngine(gpt, page_size=4, max_batch_size=2,
                            prefill_chunk=4, eos_id=-1)
        rng = np.random.RandomState(6)
        c0 = stat_registry.get("serving.prefill_chunks").get()
        p0 = stat_registry.get("serving.ragged.prefill_rows").get()
        eng.add_request(rng.randint(1, VOCAB, (9,)).astype(np.int32),
                        max_new_tokens=4)
        eng.drain()
        assert stat_registry.get("serving.prefill_chunks").get() - c0 == 2
        assert stat_registry.get(
            "serving.ragged.prefill_rows").get() - p0 == 8


class TestPoolLayout:
    """The KV pools are stored in the layout the ragged kernel reads
    (ISSUE 26): the step program may not pad, copy or transpose a whole
    pool, and must update every pool in place on its donated buffer.  On
    the v5e those relayouts were 55% of the serve step; this holds the
    program to it from the CPU (``chip_smoke.py`` asks the chip's
    compiler the same question)."""

    PAGES = 37          # a prime no other dim of the program shares

    @pytest.fixture(scope="class")
    def odd_gpt(self):
        # heads no multiple of 8, head_dim (8) a sixteenth of a lane
        # tile: the shape class whose pools the old layout padded
        import paddle_tpu
        from paddle_tpu.text.models import GPTModel

        paddle_tpu.seed(5)
        m = GPTModel(vocab_size=VOCAB, hidden_size=24, num_layers=2,
                     num_heads=3, ffn_size=48, max_seq_len=64, dropout=0.0)
        m.eval()
        return m

    def test_relayout_finder_reads_both_texts(self):
        from paddle_tpu.serving.engine import whole_pool_relayouts

        hlo = ("%pad.2 = f32[37,16,16,128]{3,2,1,0:T(8,128)} pad(%b, %c)\n"
               "%copy.9 = f32[37,16,12,64]{0,3,2,1:T(8,128)} copy(%p)\n"
               "%copy.1 = f32[48,64]{1,0} copy(%q)\n"
               "%copy.4 = f32[37,16]{1,0} copy(%scale_rows)\n"
               "%fusion.3 = f32[37,16,768]{2,1,0} fusion(%p, %u)\n")
        assert whole_pool_relayouts(hlo, 37, 16) == [
            "pad f32[37,16,16,128]", "copy f32[37,16,12,64]"]
        mlir = ("%5 = stablehlo.pad %arg7, %cst, low = [0, 0, 0, 0] : "
                "(tensor<37x4x3x8xf32>, tensor<f32>) -> "
                "tensor<37x4x8x128xf32>\n"
                "%6 = stablehlo.pad %1, %cst : (tensor<4x5xf32>, "
                "tensor<f32>) -> tensor<4x8xf32>\n")
        assert whole_pool_relayouts(mlir, 37, 4) == [
            "pad tensor<37x4x8x128xf32>"]

    @pytest.mark.parametrize("kv_dtype", [None, "int8"],
                             ids=["native", "int8"])
    @pytest.mark.parametrize("rows", [1, 4], ids=["decode", "mixed"])
    def test_step_program_never_relayouts_a_pool(self, odd_gpt, kv_dtype,
                                                 rows, monkeypatch):
        import warnings

        from paddle_tpu.serving.engine import (aliased_arguments,
                                               whole_pool_relayouts)

        # the kernel route (what the chip runs), not its XLA twin
        monkeypatch.setenv("PADDLE_TPU_FORCE_PAGED", "1")
        eng = ServingEngine(odd_gpt, page_size=4, max_batch_size=2,
                            num_pages=self.PAGES, prefill_chunk=4,
                            eos_id=-1, kv_cache_dtype=kv_dtype)
        pools = jax.tree_util.tree_leaves(eng._kv)
        assert all(p.shape[0] == self.PAGES for p in pools)
        # stored rows are heads x head_dim fused: 3 x 8
        assert eng._kv["k"][0].shape == (self.PAGES, 4, 24)
        lowered = eng.lower_ragged_step(rows)
        assert whole_pool_relayouts(lowered.as_text(), self.PAGES, 4) == []
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            compiled = lowered.compile()
        assert not [str(w.message) for w in caught
                    if "donated" in str(w.message)]
        # every pool (and int8 scale array) is updated in place.  (What
        # the CPU compiler copies around the INTERPRETED kernel says
        # nothing of the chip: tests/test_pallas_tpu_lowering.py asks the
        # v5e's compiler for its optimised program.)
        assert aliased_arguments(compiled.as_text()) == len(pools)


# =============================================================================
# the pool write: live rows as whole pages, where the kernel can run
# =============================================================================
def _fresh_gpt():
    """The shared model's twin (same seed, same weights) as a NEW object:
    the engine caches step programs per model object, and a route is
    chosen when a program is traced."""
    import paddle_tpu
    from paddle_tpu.text.models import GPTModel

    paddle_tpu.seed(11)
    m = GPTModel(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                 num_heads=2, ffn_size=64, max_seq_len=64, dropout=0.0)
    m.eval()
    return m


def _spy_rows(eng):
    """Record (state pos, rows_pos, row_valid, advance) of every ragged
    dispatch, as the device was handed them."""
    seen = []
    real = eng._ragged_jit

    def spy(tokens, pos, tables, rows_tok, rows_pos, row_valid, advance,
            kv):
        seen.append(tuple(np.asarray(jax.device_get(a)) for a in
                          (pos, rows_pos, row_valid, advance)))
        return real(tokens, pos, tables, rows_tok, rows_pos, row_valid,
                    advance, kv)

    eng._ragged_jit = spy
    return seen


def _lane_rows(state_pos, rows_pos, row_valid, advance):
    """Per lane, (effective position, live) of each row — as the step
    computes them: an advancing lane's row 0 sits at its device position,
    a row is live where its position is under its valid length."""
    eff = rows_pos.copy()
    eff[:, 0] = np.where(advance > 0, state_pos, rows_pos[:, 0])
    return eff, eff < row_valid


class TestPoolWrite:
    """The ragged step hands each layer's K/V rows to the paged KV write
    kernel (``ops/pallas_ops/paged_kv_write.py``) where it can run and the
    engine's rows have the shape it needs; the row scatter stays
    everywhere else."""

    @pytest.mark.parametrize("drive", ["chunks_decode_idle", "spec_verify"])
    def test_planned_live_rows_are_a_prefix_at_consecutive_positions(
            self, gpt, drive):
        """The kernel describes a lane by its row-0 position and its live
        rows, which holds only while the planner lays every lane's live
        rows out as a prefix of its Q rows at consecutive positions: chunk
        rows (short final chunks padded with junk), decode lanes (row 0),
        barrier-held idle lanes (none), steady decode (Q = 1) and
        spec-verify rows.  The counters the engine computes at dispatch
        by the kernel's rule equal that rule applied to those arrays."""
        from paddle_tpu.ops.pallas_ops.paged_kv_write import kv_write_counts

        rng = np.random.RandomState(38)
        page = 4
        if drive == "spec_verify":
            eng = ServingEngine(gpt, page_size=page, max_batch_size=4,
                                prefill_chunk=4, eos_id=-1, spec_decode=4)
        else:
            eng = ServingEngine(gpt, page_size=page, max_batch_size=4,
                                prefill_chunk=4, eos_id=-1,
                                prefix_cache=True)
        seen = _spy_rows(eng)
        r0 = stat_registry.get("serving.ragged.kv_rows_written").get()
        p0 = stat_registry.get("serving.ragged.kv_page_copies").get()
        if drive == "spec_verify":
            for p in (2, 3):
                eng.add_request(np.tile(rng.randint(1, VOCAB, (p,))
                                        .astype(np.int32), 4),
                                max_new_tokens=16)
        else:
            shared = rng.randint(1, VOCAB, (8,)).astype(np.int32)
            eng.add_request(rng.randint(1, VOCAB, (3,)).astype(np.int32),
                            max_new_tokens=12)
            eng.step()
            eng.step()
            for tail in (5, 3):
                eng.add_request(np.concatenate(
                    [shared, rng.randint(1, VOCAB, (tail,))
                     .astype(np.int32)]), max_new_tokens=4)
            eng.add_request(rng.randint(1, VOCAB, (14,)).astype(np.int32),
                            max_new_tokens=3)
        eng.drain()
        kinds = set()
        rows = pages = 0
        for state_pos, rows_pos, row_valid, advance in seen:
            eff, live = _lane_rows(state_pos, rows_pos, row_valid, advance)
            Q = live.shape[1]
            for b in range(live.shape[0]):
                n = int(live[b].sum())
                # a prefix of the lane's rows, at consecutive positions
                assert live[b, :n].all() and not live[b, n:].any(), b
                assert (eff[b, :n] == eff[b, 0] + np.arange(n)).all(), b
                r, p = kv_write_counts(int(eff[b, 0]), n, page)
                rows += r
                pages += p
                kinds.add("steady" if Q == 1 else "decode" if advance[b]
                          else "idle" if n == 0
                          else "spec" if (row_valid[b] > 1 << 20).all()
                          else "short_chunk" if n < Q else "chunk")
        want = ({"steady", "spec"} if drive == "spec_verify" else
                {"steady", "decode", "idle", "chunk", "short_chunk"})
        assert want <= kinds, kinds
        assert stat_registry.get(
            "serving.ragged.kv_rows_written").get() - r0 == rows
        assert stat_registry.get(
            "serving.ragged.kv_page_copies").get() - p0 == pages
        if drive != "spec_verify":
            assert pages > 0          # 4-row chunks from a page boundary
        assert eng.cache.pages_in_use == 0

    @pytest.mark.parametrize("where", [
        "cpu", "forced", "int8", "split", "page4", "sp2", "tp2"])
    def test_kernel_routes_only_where_it_applies(self, monkeypatch, where):
        """Interpret mode stands in for the TPU (``PADDLE_TPU_FORCE_PAGED``,
        as for the paged-attention kernels).  The kernel takes the pool
        write of a ragged step with native 32-bit pools, pages of whole
        tiles and no sequence sharding; int8 pools (scales grow per
        page), the split programs (no lane layout), sp (non-owned rows go
        to the trash page mid-range), 4-row pages and the CPU keep the
        row scatter.  Under tp the kernel runs per shard."""
        from paddle_tpu.ops.pallas_ops.paged_kv_write import (
            WRITE_ROUTE_STATS)

        if where != "cpu":
            monkeypatch.setenv("PADDLE_TPU_FORCE_PAGED", "1")
        kw = dict(page_size=4 if where == "page4" else 8, max_batch_size=2,
                  prefill_chunk=8, eos_id=-1)
        if where == "int8":
            kw["kv_cache_dtype"] = "int8"
        if where in ("sp2", "tp2"):
            kw["mesh_axes"] = {where[:2]: 2}
        before = dict(WRITE_ROUTE_STATS)
        if where == "split":
            eng = ServingEngine(_fresh_gpt(), ragged=False, **kw)
            eng.add_request(np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=2)
            eng.drain()
        else:
            eng = ServingEngine(_fresh_gpt(), **kw)
            eng.lower_ragged_step(8)
        grew = {k: WRITE_ROUTE_STATS[k] - before[k] for k in before}
        if where in ("forced", "tp2"):
            assert grew == {"pallas": 2, "scatter": 0}   # two layers
        else:
            assert grew["pallas"] == 0 and grew["scatter"] >= 2

    @pytest.mark.parametrize("mode", ["mixed", "prefix_cache", "spec"])
    def test_kernel_route_serves_the_same_tokens(self, monkeypatch, mode):
        """An engine whose pool writes go through the kernel (interpret
        mode) streams the same tokens as one on the row scatter: mixed
        chunk / decode / padded-chunk steps, prefix hits with barrier-held
        lanes, and spec-verify rows."""
        from paddle_tpu.ops.pallas_ops.paged_kv_write import (
            WRITE_ROUTE_STATS)

        rng = np.random.RandomState(39)
        shared = rng.randint(1, VOCAB, (16,)).astype(np.int32)
        if mode == "spec":
            prompts = [np.tile(rng.randint(1, VOCAB, (p,)).astype(np.int32),
                               5) for p in (2, 3, 4)]
        elif mode == "prefix_cache":
            prompts = [np.concatenate([shared, rng.randint(
                1, VOCAB, (n,)).astype(np.int32)]) for n in (5, 2, 11)]
        else:
            prompts = _mixed_prompts(rng, lens=(3, 19, 11, 2, 27))
        kw = dict(page_size=8, max_batch_size=4, prefill_chunk=8,
                  eos_id=-1, prefix_cache=mode == "prefix_cache",
                  spec_decode=4 if mode == "spec" else False)
        monkeypatch.delenv("PADDLE_TPU_FORCE_PAGED", raising=False)
        ref = _drive(ServingEngine(_fresh_gpt(), **kw), prompts, budget=9)
        monkeypatch.setenv("PADDLE_TPU_FORCE_PAGED", "1")
        before = WRITE_ROUTE_STATS["pallas"]
        eng = ServingEngine(_fresh_gpt(), **kw)
        got = _drive(eng, prompts, budget=9)
        assert WRITE_ROUTE_STATS["pallas"] > before
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert eng.cache.pages_in_use == 0
