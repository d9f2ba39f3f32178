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
