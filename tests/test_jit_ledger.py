"""Runtime compile ledger (ISSUE 8): per-callable trace/compile
accounting + the ``compile_budget`` assertion context, and the serving
compile-count contracts it exists to pin:

- a 2-replica fleet compiles each shared program EXACTLY ONCE (the
  PR-6 shared-program-cache contract, now machine-pinned) — under the
  unified ragged dispatch (ISSUE 18) that is serving.ragged_step plus
  maintenance, STRICTLY fewer programs than the split set;
- steady-state decode retraces ZERO times across >= 32 steps;
- a lane-bucket change retraces the ragged program EXACTLY ONCE.

Each serving test builds its OWN GPTModel: the shared program cache is
keyed per model object, so a fresh model guarantees a cold cache and
exact compile counts.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.profiler.jit_cost import (CompileBudgetExceeded,
                                          CompileLedger, compile_budget,
                                          compile_ledger, profiled_jit)
from paddle_tpu.serving import ServingEngine, ServingFrontend

VOCAB, HID, LAYERS, HEADS = 50, 32, 2, 2


def fresh_gpt(seed=11):
    from paddle_tpu.text.models import GPTModel

    paddle.seed(seed)
    m = GPTModel(vocab_size=VOCAB, hidden_size=HID, num_layers=LAYERS,
                 num_heads=HEADS, ffn_size=64, max_seq_len=64,
                 dropout=0.0)
    m.eval()
    return m


# =============================================================================
# ledger + budget units (host-only)
# =============================================================================
class TestLedgerUnits:
    def test_counts_total_events_reset(self):
        led = CompileLedger()
        led.on_compile("serving.decode", "(4,):int32")
        led.on_compile("serving.decode", "(8,):int32")
        led.on_compile("serving.prefill", "(4,):int32", fallback=True)
        assert led.counts() == {"serving.decode": 2,
                                "serving.prefill": 1}
        assert led.counts("serving.d") == {"serving.decode": 2}
        assert led.total() == 3 and led.total("serving.p") == 1
        assert led.events()[-1] == ("serving.prefill", "(4,):int32",
                                    True)
        led.reset()
        assert led.counts() == {} and led.events() == []

    def test_budget_record_mode_deltas(self):
        led = CompileLedger()
        led.on_compile("a.x", "s0")       # pre-existing history
        with compile_budget(None, ledger=led) as cb:
            assert cb.compiles() == {}
            led.on_compile("a.x", "s1")
            led.on_compile("b.y", "s0")
        assert cb.compiles() == {"a.x": 1, "b.y": 1}
        assert cb.total() == 2

    def test_budget_raise_mode_and_filters(self):
        led = CompileLedger()
        with pytest.raises(CompileBudgetExceeded, match="a.x x2"):
            with compile_budget(1, ledger=led):
                led.on_compile("a.x", "s0")
                led.on_compile("a.x", "s1")
        # scoping: out-of-prefix compiles never count
        with compile_budget(0, prefix="serving.", ledger=led):
            led.on_compile("train.step", "s0")
        with compile_budget(0, names=("a.x",), ledger=led):
            led.on_compile("a.y", "s0")
        # a budget that holds exactly does not raise
        with compile_budget(1, ledger=led):
            led.on_compile("a.x", "s2")

    def test_budget_does_not_mask_body_exception(self):
        led = CompileLedger()
        with pytest.raises(ValueError, match="body"):
            with compile_budget(0, ledger=led):
                led.on_compile("a.x", "s0")
                raise ValueError("body")

    def test_profiled_jit_feeds_global_ledger(self):
        f = profiled_jit("ledger.unit_add", lambda x: x + 1)
        with compile_budget(None, prefix="ledger.") as cb:
            f(jnp.zeros((4,)))
            f(jnp.ones((4,)))             # same signature: cached
            f(jnp.zeros((8,)))            # new signature: recompile
        assert cb.compiles() == {"ledger.unit_add": 2}

    def test_compile_error_surfaces(self, monkeypatch):
        """A compile failure is raised as itself — never swallowed and
        retried through plain jit (which would hide a kernel the
        compiler refused), and never remembered as a dead signature."""
        from paddle_tpu.profiler import jit_cost

        real = jit_cost.ProfiledJit._compile_for
        monkeypatch.setattr(
            jit_cost.ProfiledJit, "_compile_for",
            lambda self, sig, a, k: (_ for _ in ()).throw(
                RuntimeError("Mosaic refused the kernel")))
        f = profiled_jit("ledger.unit_fb", lambda x: x * 2)
        with compile_budget(None, prefix="ledger.") as cb:
            with pytest.raises(RuntimeError, match="Mosaic refused"):
                f(jnp.ones((3,)))
        assert cb.compiles() == {}
        monkeypatch.setattr(jit_cost.ProfiledJit, "_compile_for", real)
        np.testing.assert_array_equal(np.asarray(f(jnp.ones((3,)))),
                                      [2, 2, 2])


# =============================================================================
# serving compile contracts
# =============================================================================
class TestServingCompilePins:
    def test_fleet_of_2_compiles_each_program_exactly_once(self):
        """The shared-program-cache contract, pinned by count: two
        replica engines serving one request each must compile every
        serving program EXACTLY once per signature — not once per
        replica.  Under the unified ragged dispatch (ISSUE 18) the
        whole workload runs on ONE program name: serving.ragged_step
        at two row shapes (the 5-token prompts' 4-row chunk step +
        the 1-row steady shape) plus the two maintenance programs —
        serving.{prefill,decode} never compile at all.
        max_batch_size=1 keeps every dispatch at lane bucket 1."""
        gpt = fresh_gpt(21)
        fe = ServingFrontend(gpt, replicas=2, queue_cap=8,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=1,
                                                eos_id=-1))
        try:
            rng = np.random.RandomState(3)
            prompts = [rng.randint(1, VOCAB, (5,)).astype(np.int32)
                       for _ in range(2)]
            with compile_budget(None, prefix="serving.") as cb:
                handles = [fe.submit(p, max_new_tokens=6)
                           for p in prompts]
                assert [h.wait(timeout=300) for h in handles] \
                    == ["completed"] * 2
            delta = cb.compiles()
            assert delta, "no serving compiles recorded — cold cache?"
            assert delta == {"serving.ragged_step": 2,
                             "serving.lane_update": 1,
                             "serving.table_update": 1}, delta
        finally:
            fe.close()

    def test_ragged_strictly_fewer_compiles_than_split(self):
        """The ISSUE 18 acceptance pin: the SAME 2-replica fleet
        workload (prompt lengths 5 and 2 — two chunk shapes) compiles
        STRICTLY fewer serving programs unified than split.  Split
        pays prefill at both chunk shapes + decode + maintenance (5);
        ragged folds all three streams into serving.ragged_step, whose
        1-row chunk step IS the steady-decode signature (4).  A second
        ragged fleet on the same model then adds ZERO compiles — the
        ragged program lives in the shared BASE bundle."""
        rng = np.random.RandomState(6)
        prompts = [rng.randint(1, VOCAB, (5,)).astype(np.int32),
                   rng.randint(1, VOCAB, (2,)).astype(np.int32)]

        def drive(fe, tag):
            handles = [fe.submit(p, max_new_tokens=6) for p in prompts]
            assert [h.wait(timeout=300) for h in handles] \
                == ["completed"] * 2, tag

        totals = {}
        for tag, ragged in (("split", False), ("ragged", None)):
            gpt = fresh_gpt(31 if ragged is None else 32)
            kw = dict(page_size=4, max_batch_size=1, eos_id=-1)
            if ragged is not None:
                kw["ragged"] = ragged
            fe = ServingFrontend(gpt, replicas=2, queue_cap=8,
                                 engine_kwargs=kw)
            try:
                with compile_budget(None, prefix="serving.") as cb:
                    drive(fe, tag)
                totals[tag] = cb.total()
                if tag == "ragged":
                    assert set(cb.compiles()) == {
                        "serving.ragged_step", "serving.lane_update",
                        "serving.table_update"}, cb.compiles()
            finally:
                fe.close()
            if tag == "ragged":
                # replica count is not a compile axis: a whole second
                # fleet on the same model stays compile-free
                fe2 = ServingFrontend(gpt, replicas=2, queue_cap=8,
                                      engine_kwargs=kw)
                try:
                    with compile_budget(0, prefix="serving."):
                        drive(fe2, "ragged-2nd-fleet")
                finally:
                    fe2.close()
        assert totals["ragged"] < totals["split"], totals
        assert totals == {"split": 5, "ragged": 4}, totals

    def test_fused_variant_shares_base_programs(self):
        """ISSUE 15 suite health: ``fused_steps`` is a per-variant
        PROGRAM cached on the shared base bundle, not a new bundle key
        — an engine mixing plain and fused modes on one model compiles
        the decode/prefill/maintenance set once, and only the fused
        K-step program is variant-specific."""
        gpt = fresh_gpt(24)
        rng = np.random.RandomState(4)

        def drive(eng):
            for p in (3, 5):
                eng.add_request(
                    rng.randint(1, VOCAB, (p,)).astype(np.int32),
                    max_new_tokens=4)
            eng.drain()

        # ragged=False: the point is fused-vs-plain SPLIT program
        # sharing — a ragged first engine would leave decode/prefill
        # cold and the delta would show them, not the fused variant
        plain = ServingEngine(gpt, page_size=4, max_batch_size=2,
                              eos_id=-1, ragged=False)
        drive(plain)
        with compile_budget(None, prefix="serving.") as cb:
            fused = ServingEngine(gpt, page_size=4, max_batch_size=2,
                                  eos_id=-1, fused_steps=4)
            drive(fused)
        delta = {k: v for k, v in cb.compiles().items() if v}
        assert set(delta) == {"serving.decode_fused"}, delta

    def test_steady_state_decode_zero_retraces_32_steps(self):
        """The acceptance pin: once the lane bucket is stable, >= 32
        decode steps perform ZERO retraces of ANY serving program —
        compile_budget(0) raises on the first drift."""
        gpt = fresh_gpt(22)
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            eos_id=-1)
        rng = np.random.RandomState(5)
        for p in (3, 5, 7, 9):
            eng.add_request(rng.randint(1, VOCAB, (p,)).astype(np.int32),
                            max_new_tokens=48)
        for _ in range(4):                       # admissions + compiles
            eng.step()
        assert all(s is not None for s in eng._lanes)
        with compile_budget(0, prefix="serving."):
            for _ in range(32):
                stats = eng.step()
                assert stats["bucket"] == 4
        outs = eng.drain()
        assert len(outs) == 4

    def test_bucket_change_retraces_exactly_once(self):
        """Growing the lane bucket is the ONE sanctioned retrace: the
        unified ragged program recompiles exactly once for the new
        bucket and never again.  The joining prompt is 2 tokens, so
        its single 1-token chunk step shares the steady 1-row
        signature — ONE compile covers both."""
        gpt = fresh_gpt(23)
        eng = ServingEngine(gpt, page_size=4, max_batch_size=2,
                            eos_id=-1)
        rng = np.random.RandomState(9)
        eng.add_request(rng.randint(1, VOCAB, (5,)).astype(np.int32),
                        max_new_tokens=40, request_id="a")
        for _ in range(3):
            eng.step()                           # bucket 1 decoding
        assert eng._state_bucket == 1
        with compile_budget(None, names=("serving.ragged_step",)) as cb:
            eng.add_request(rng.randint(1, VOCAB, (2,)).astype(np.int32),
                            max_new_tokens=40, request_id="b")
            for _ in range(6):
                eng.step()                       # admit -> bucket 2
            assert eng._state_bucket == 2
        assert cb.compiles() == {"serving.ragged_step": 1}
        # ... and steady at the new bucket: zero further retraces
        with compile_budget(0, prefix="serving."):
            for _ in range(8):
                eng.step()
        eng.drain()
