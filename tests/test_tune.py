"""Pallas kernel autotuner (ISSUE 14): contract-gated search, the
persistent tuning table, the kernel resolution seam.

Acceptance anchors (docs/TUNING.md):

- candidate enumeration is pruned through ``KernelContract.validate()``
  — every rule (lane, sublane floor, bucket divisibility, VMEM budget)
  exercised as a REJECTION here, so an invalid config never compiles;
- the on-disk table commits atomically (chaos-killed at both
  ``ckpt.write`` injection points) and a corrupt / newer-schema /
  missing table degrades to contract defaults, never a wrong kernel;
- winner selection is deterministic under a scripted timer, and a
  faster-but-divergent candidate NEVER wins (parity gate);
- with no table installed the kernels resolve exactly their historical
  contract-default dims (zero behavior change), and tuned configs
  resolved THROUGH the table produce outputs identical to defaults.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import tune
from paddle_tpu.framework.errors import (InternalError,
                                         TuningTableCorruptError,
                                         TuningTableIncompatibleError)
from paddle_tpu.framework.monitor import stat_get
from paddle_tpu.ops.pallas_ops.contracts import (CONTRACTS, BlockDecl,
                                                 KernelContract,
                                                 QUANTIZED_MATMUL)
from paddle_tpu.testing import chaos
from paddle_tpu.testing.chaos import ChaosPlan, Fault
from paddle_tpu.tune.table import _MAGIC


@pytest.fixture(autouse=True)
def _no_active_table():
    tune.reset()
    yield
    tune.reset()


def _contract(**over):
    base = dict(
        name="t", module="m.py", grid=("i",),
        dims={"b": 128, "d": 128},
        blocks=(BlockDecl("x", "in", ("b", "d"), "float32"),),
        shape_buckets={"b": (256,)})
    base.update(over)
    return KernelContract(**base)


# =============================================================================
# Buckets + enumeration/pruning
# =============================================================================
class TestBucketing:
    def test_rounds_up_to_default_multiples(self):
        c = _contract()
        assert tune.shape_bucket(c, {"b": 1}) == {"b": 128}
        assert tune.shape_bucket(c, {"b": 128}) == {"b": 128}
        assert tune.shape_bucket(c, {"b": 129}) == {"b": 256}
        assert tune.bucket_key(c, {"d": 300, "b": 5}) \
            == "b=128,d=384"           # sorted, canonical

    def test_bucket_is_stable_under_tuned_configs(self):
        """The key derives from contract DEFAULTS, so installing a
        tuned config can never move later lookups to another key."""
        qmm = QUANTIZED_MATMUL
        key = tune.bucket_key(qmm, {"block_m": 8, "block_k": 256,
                                    "block_n": 200})
        assert key == "block_k=256,block_m=128,block_n=256"

    def test_entry_key_rejects_separator(self):
        with pytest.raises(ValueError, match="may not contain"):
            tune.entry_key("a|b", "x", "f32", "cpu")


class TestEnumerationPruning:
    """Every validate() rule fires as a candidate REJECTION."""

    def test_default_enumerates_first_and_always_member(self):
        c = _contract(sweep={"b": (64, 128)})
        valid, rejected = tune.enumerate_candidates(c, {"b": 128})
        assert valid[0] == {"b": 128}          # the default, first
        assert {"b": 64} in valid and rejected == []

    def test_lane_rule_prunes(self):
        c = _contract(sweep={"d": (96, 128)})
        valid, rejected = tune.enumerate_candidates(c, {"b": 128})
        assert {"d": 128} in valid
        assert any(choice == {"d": 96} and "lane" in viol[0]
                   for choice, viol in rejected)

    def test_sublane_floor_rule_prunes_dtype_correct(self):
        c = _contract(
            blocks=(BlockDecl("x", "in", ("b", "d"), "int8"),),
            dims={"b": 32, "d": 128}, shape_buckets={},
            sweep={"b": (16, 32)})
        valid, rejected = tune.enumerate_candidates(c, {"b": 32})
        assert valid == [{"b": 32}]
        assert any("int8 tile floor 32" in viol[0]
                   for _, viol in rejected)

    def test_divisibility_rule_prunes_at_the_target_bucket(self):
        """The same candidate is legal at one bucket and pruned at
        another — validation happens AT the sweep's bucket, which is
        what makes per-bucket tuning sound."""
        c = _contract(sweep={"b": (64, 128, 256)})
        valid256, rej256 = tune.enumerate_candidates(c, {"b": 256})
        assert {"b": 256} in valid256
        valid128, rej128 = tune.enumerate_candidates(c, {"b": 128})
        assert {"b": 256} not in valid128
        assert any(choice == {"b": 256} and "not divisible" in viol[0]
                   for choice, viol in rej128)

    def test_vmem_budget_rule_prunes(self):
        c = _contract(
            dims={"b": 1024, "d": 1024}, shape_buckets={},
            blocks=(BlockDecl("x", "in", ("b", "d"), "float32"),
                    BlockDecl("s", "scratch", ("b", "d"), "float32")),
            sweep={"b": (1024, 2048)})
        valid, rejected = tune.enumerate_candidates(c, {"b": 2048})
        assert {"b": 1024} in valid            # 12MiB: exactly budget
        assert any(choice == {"b": 2048} and "exceeds" in viol[0]
                   for choice, viol in rejected)

    def test_sweep_axis_must_bind_a_dim(self):
        c = _contract(sweep={"ghost": (1, 2)})
        with pytest.raises(ValueError, match="not bound in dims"):
            tune.enumerate_candidates(c, {"b": 128})

    def test_repo_contracts_declare_sound_sweeps(self):
        """Every registered contract's sweep axes bind dims, and the
        default config is a valid member of its own search space at
        every declared bench bucket."""
        from paddle_tpu.tune.__main__ import DEFAULT_EXTENTS

        for name, c in CONTRACTS.items():
            for sym in c.sweep:
                assert sym in c.dims, (name, sym)
            for extents in DEFAULT_EXTENTS.get(name, []):
                valid, _ = tune.enumerate_candidates(
                    c, tune.shape_bucket(c, extents))
                assert valid[0] == {s: c.dim(s)
                                    for s in sorted(c.sweep)}, name


# =============================================================================
# Table persistence
# =============================================================================
class TestTable:
    def _filled(self, path=None):
        t = tune.TuningTable(path)
        t.put("quantized_matmul", "block_k=256,block_m=128,block_n=256",
              "int8_weights", "cpu",
              {"block_m": 128, "block_n": 256, "block_k": 128},
              best_ms=1.5, default_ms=2.0, speedup_x=1.33,
              is_default=False)
        return t

    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "t.ptt")
        t = self._filled(p)
        t.save()
        t2 = tune.TuningTable.load(p)
        assert len(t2) == 1
        e = t2.get("quantized_matmul",
                   "block_k=256,block_m=128,block_n=256",
                   "int8_weights", "cpu")
        assert e["dims"] == {"block_m": 128, "block_n": 256,
                             "block_k": 128}
        assert e["speedup_x"] == 1.33 and e["schema"] == 1

    @pytest.mark.parametrize("point", ["temp", "rename"])
    def test_chaos_kill_during_commit_keeps_old_table(self, tmp_path,
                                                      point):
        p = str(tmp_path / "t.ptt")
        t = self._filled(p)
        t.save()
        t.put("flash_attention_fwd", "block_k=1024,block_q=1024",
              "float32", "cpu", {"block_q": 512, "block_k": 1024})
        plan = ChaosPlan([Fault("ckpt.write", at=1, action=chaos.RAISE,
                                match=point)])
        with chaos.running(plan):
            with pytest.raises(InternalError):
                t.save()
        assert plan.fired_log()[0]["key"] == point
        # the aborted commit is invisible; the previous table loads
        old = tune.TuningTable.load(p)
        assert len(old) == 1

    def test_corrupt_magic_strict_raises_soft_falls_back(self, tmp_path):
        p = tmp_path / "bad.ptt"
        p.write_bytes(b"garbage")
        with pytest.raises(TuningTableCorruptError, match="bad magic"):
            tune.TuningTable.load(str(p))
        t, reason = tune.TuningTable.load_or_default(str(p))
        assert len(t) == 0 and "bad magic" in reason
        assert t.fallback_reason == reason

    def test_payload_crc_mismatch_detected(self, tmp_path):
        p = str(tmp_path / "t.ptt")
        self._filled(p).save()
        blob = bytearray(open(p, "rb").read())
        blob[-3] ^= 0xFF                     # flip a payload byte
        open(p, "wb").write(bytes(blob))
        with pytest.raises(TuningTableCorruptError, match="CRC"):
            tune.TuningTable.load(p)
        _, reason = tune.TuningTable.load_or_default(p)
        assert "CRC" in reason

    def test_truncated_manifest_detected(self, tmp_path):
        p = tmp_path / "t.ptt"
        p.write_bytes(_MAGIC + (400).to_bytes(4, "big") + b"{}")
        with pytest.raises(TuningTableCorruptError, match="truncated"):
            tune.TuningTable.load(str(p))

    def test_malformed_manifest_values_stay_typed(self, tmp_path):
        """Review fix: the manifest is NOT payload-CRC'd — a mangled
        schema field (null/string) must be a TYPED corruption so the
        soft loader's never-raise contract holds."""
        import json
        import zlib

        payload = json.dumps({}).encode()
        for manifest in ({"schema": None, "crc32": zlib.crc32(payload)},
                         {"schema": "2", "crc32": zlib.crc32(payload)},
                         ["not", "a", "dict"]):
            m = json.dumps(manifest).encode()
            p = tmp_path / "m.ptt"
            p.write_bytes(_MAGIC + len(m).to_bytes(4, "big") + m
                          + payload)
            with pytest.raises(TuningTableCorruptError,
                               match="schema field"):
                tune.TuningTable.load(str(p))
            t, reason = tune.TuningTable.load_or_default(str(p))
            assert len(t) == 0 and "schema field" in reason

    def test_non_dict_entry_payload_is_corrupt(self, tmp_path):
        import json
        import zlib

        payload = json.dumps({"k|b|d|p": "not-a-dict"}).encode()
        m = json.dumps({"schema": 1,
                        "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
                        "entries": 1}).encode()
        p = tmp_path / "e.ptt"
        p.write_bytes(_MAGIC + len(m).to_bytes(4, "big") + m + payload)
        with pytest.raises(TuningTableCorruptError,
                           match="entry mapping"):
            tune.TuningTable.load(str(p))

    def test_newer_schema_strict_raises_soft_falls_back(self, tmp_path,
                                                        monkeypatch):
        p = str(tmp_path / "t.ptt")
        monkeypatch.setattr(tune.table, "TUNE_SCHEMA_VERSION", 99)
        self._filled(p).save()
        monkeypatch.undo()
        with pytest.raises(TuningTableIncompatibleError, match="newer"):
            tune.TuningTable.load(p)
        t, reason = tune.TuningTable.load_or_default(p)
        assert len(t) == 0 and "newer" in reason

    def test_missing_file_is_a_soft_fallback(self, tmp_path):
        t, reason = tune.TuningTable.load_or_default(
            str(tmp_path / "nope.ptt"))
        assert len(t) == 0 and reason == "missing"

    def test_save_requires_a_path(self):
        with pytest.raises(ValueError, match="needs a path"):
            tune.TuningTable().save()


# =============================================================================
# Winner selection (scripted timer — deterministic by construction)
# =============================================================================
class _ScriptedTimer:
    """Each (start, stop) perf_counter pair consumes one scripted
    duration, in seconds."""

    def __init__(self, durations):
        self._t = 0.0
        self._durs = iter(durations)
        self._pending = None

    def __call__(self):
        if self._pending is None:
            self._pending = next(self._durs)
            return self._t
        self._t += self._pending
        self._pending = None
        return self._t


def _toy_runner(outputs):
    """Runner factory whose run() returns outputs[choice-as-key]."""
    def factory(contract, bucket, dtype):
        def run(choice):
            key = tuple(sorted(choice.items()))
            out = outputs[key]
            if isinstance(out, Exception):
                raise out
            return out
        return run
    return factory


class TestWinnerSelection:
    def _sweep(self, durations, outputs, **kw):
        c = _contract(sweep={"b": (64, 128)})
        return tune.sweep_kernel(
            c, {"b": 128}, repeats=kw.pop("repeats", 1),
            timer=_ScriptedTimer(durations),
            runner=_toy_runner(outputs), **kw)

    def test_faster_candidate_wins_deterministically(self, tmp_path):
        same = np.arange(6.0)
        table = tune.TuningTable(str(tmp_path / "t.ptt"))
        # default 2ms, candidate 1ms
        rep = self._sweep([0.002, 0.001],
                          {(("b", 128),): same, (("b", 64),): same},
                          table=table)
        assert rep.winner.choice == {"b": 64}
        assert rep.default_ms == pytest.approx(2.0)
        assert rep.winner.wall_ms == pytest.approx(1.0)
        assert rep.speedup_x == pytest.approx(2.0)
        e = table.get("t", "b=128", "float32",
                      rep.platform)
        assert e["dims"] == {"b": 64} and e["is_default"] is False
        assert e["candidates"] == 2 and e["pruned"] == 0

    def test_tie_keeps_the_default(self):
        same = np.arange(6.0)
        rep = self._sweep([0.002, 0.002],
                          {(("b", 128),): same, (("b", 64),): same})
        assert rep.winner.choice == {"b": 128}
        assert rep.speedup_x == pytest.approx(1.0)

    def test_min_of_n_takes_the_best_repeat(self):
        same = np.arange(6.0)
        # default repeats: 5ms, 2ms -> 2ms; candidate: 3ms, 4ms -> 3ms
        rep = self._sweep([0.005, 0.002, 0.003, 0.004],
                          {(("b", 128),): same, (("b", 64),): same},
                          repeats=2)
        assert rep.default_ms == pytest.approx(2.0)
        assert rep.winner.choice == {"b": 128}

    def test_divergent_candidate_never_wins(self):
        """Parity gate: faster but output-different -> rejected."""
        rep = self._sweep([0.002, 0.001],
                          {(("b", 128),): np.arange(6.0),
                           (("b", 64),): np.arange(6.0) + 1e-3})
        assert rep.winner.choice == {"b": 128}
        bad = next(r for r in rep.results if r.choice == {"b": 64})
        assert bad.rejected.startswith("parity")
        assert bad.max_abs_diff == pytest.approx(1e-3)

    def test_atol_admits_bounded_drift(self):
        rep = self._sweep([0.002, 0.001],
                          {(("b", 128),): np.arange(6.0),
                           (("b", 64),): np.arange(6.0) + 1e-7},
                          atol=1e-6)
        assert rep.winner.choice == {"b": 64}

    def test_erroring_candidate_rejected_not_fatal(self):
        rep = self._sweep([0.002],
                          {(("b", 128),): np.arange(6.0),
                           (("b", 64),): RuntimeError("boom")})
        assert rep.winner.choice == {"b": 128}
        bad = next(r for r in rep.results if r.choice == {"b": 64})
        assert bad.rejected.startswith("error: RuntimeError")

    def test_shape_drift_rejected(self):
        rep = self._sweep([0.002],
                          {(("b", 128),): np.arange(6.0),
                           (("b", 64),): np.arange(7.0)})
        bad = next(r for r in rep.results if r.choice == {"b": 64})
        assert "shape/dtype drift" in bad.rejected


# =============================================================================
# Runtime resolution seam
# =============================================================================
class TestRuntimeResolution:
    def _table(self, dims=None):
        t = tune.TuningTable()
        t.put("quantized_matmul", "block_k=256,block_m=128,block_n=256",
              "int8_weights", "cpu",
              dims or {"block_m": 128, "block_n": 256, "block_k": 128})
        return t

    def test_no_table_resolves_contract_defaults(self):
        """The zero-behavior-change pin: with no table, every kernel
        module resolves exactly its historical contract dims."""
        from paddle_tpu.ops.pallas_ops import (flash_attention,
                                               paged_attention,
                                               quantized_matmul)

        assert tune.get_active_table() is None
        assert quantized_matmul._resolved_blocks(8, 256, 256) \
            == (128, 128, 128)
        assert flash_attention._resolved_blocks(1024) == (512, 1024)
        assert paged_attention._ragged_resolved_dims(2, 16, False) \
            == (8, True, 8)
        assert paged_attention._ragged_resolved_dims(2, 16, True) \
            == (8, True, 8)

    def test_hit_miss_and_counter_accounting(self):
        from paddle_tpu.ops.pallas_ops import quantized_matmul as qmm

        tune.set_active_table(self._table())
        h0 = stat_get("tune.table.hits") or 0
        m0 = stat_get("tune.table.misses") or 0
        assert qmm._resolved_blocks(8, 256, 256) == (128, 256, 128)
        assert qmm._resolved_blocks(8, 512, 512) == (128, 128, 128)
        assert (stat_get("tune.table.hits") or 0) == h0 + 1
        assert (stat_get("tune.table.misses") or 0) == m0 + 1

    def test_invalid_row_is_dropped_not_compiled(self):
        from paddle_tpu.ops.pallas_ops import quantized_matmul as qmm

        tune.set_active_table(self._table(
            {"block_m": 128, "block_n": 100, "block_k": 128}))
        i0 = stat_get("tune.table.invalid") or 0
        assert qmm._resolved_blocks(8, 256, 256) == (128, 128, 128)
        assert (stat_get("tune.table.invalid") or 0) == i0 + 1

    def test_non_numeric_dims_row_dropped_never_raises(self):
        """Review fix: a hand-edited row with non-numeric dims is an
        invalid row (defaults used), not a trace-time crash."""
        from paddle_tpu.ops.pallas_ops import quantized_matmul as qmm

        t = tune.TuningTable()
        t.put("quantized_matmul", "block_k=256,block_m=128,block_n=256",
              "int8_weights", "cpu", {"block_m": 128, "block_n": 128,
                                      "block_k": 128})
        t._entries[next(iter(t._entries))]["dims"] = {"block_m": "big"}
        tune.set_active_table(t)
        i0 = stat_get("tune.table.invalid") or 0
        assert qmm._resolved_blocks(8, 256, 256) == (128, 128, 128)
        assert (stat_get("tune.table.invalid") or 0) == i0 + 1

    def test_env_var_loads_lazily_and_corrupt_env_falls_back(
            self, tmp_path, monkeypatch):
        from paddle_tpu.ops.pallas_ops import quantized_matmul as qmm
        from paddle_tpu.tune import runtime

        p = str(tmp_path / "env.ptt")
        t = self._table()
        t.save(p)
        monkeypatch.setenv(runtime.ENV_TABLE, p)
        tune.reset()                       # re-arm the env probe
        assert qmm._resolved_blocks(8, 256, 256) == (128, 256, 128)
        assert tune.active_source() == f"env:{p}"
        # corrupt file behind the env var: defaults + fallback counter
        open(p, "wb").write(b"garbage")
        tune.reset()
        f0 = stat_get("tune.table.fallbacks") or 0
        assert qmm._resolved_blocks(8, 256, 256) == (128, 128, 128)
        assert (stat_get("tune.table.fallbacks") or 0) == f0 + 1

    def test_explicit_argument_beats_the_table(self):
        from paddle_tpu.ops.pallas_ops.quantized_matmul import (
            quantized_matmul_kernel)

        tune.set_active_table(self._table())
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 256).astype(np.float32))
        w = jnp.asarray(rng.randint(-127, 128, (256, 256)
                                    ).astype(np.int8))
        s = jnp.asarray((rng.rand(256) * 0.1).astype(np.float32))
        a = quantized_matmul_kernel(x, w, s, interpret=True,
                                    block_m=128, block_n=128,
                                    block_k=128)
        tune.reset()
        b = quantized_matmul_kernel(x, w, s, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# =============================================================================
# Kernel parity: tuned configs == contract defaults, bit for bit
# =============================================================================
class TestKernelParityPins:
    def test_qmm_tuned_blocks_match_default_through_the_table(self):
        from paddle_tpu.ops.pallas_ops.quantized_matmul import (
            quantized_matmul_kernel)

        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(8, 200).astype(np.float32))
        w = jnp.asarray(rng.randint(-127, 128, (200, 250)
                                    ).astype(np.int8))
        s = jnp.asarray((rng.rand(250) * 0.1).astype(np.float32))
        ref = np.asarray(quantized_matmul_kernel(x, w, s,
                                                 interpret=True))
        t = tune.TuningTable()
        t.put("quantized_matmul",
              tune.bucket_key(CONTRACTS["quantized_matmul"],
                              {"block_m": 8, "block_k": 200,
                               "block_n": 250}),
              "int8_weights", "cpu",
              {"block_m": 128, "block_n": 256, "block_k": 128})
        tune.set_active_table(t)
        out = np.asarray(quantized_matmul_kernel(x, w, s,
                                                 interpret=True))
        np.testing.assert_array_equal(out, ref)

    def test_flash_block_q_partition_is_exact(self):
        from paddle_tpu.ops.pallas_ops.flash_attention import (
            flash_attention_bshd)

        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(1, 256, 2, 32).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 256, 2, 32).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 256, 2, 32).astype(np.float32))
        ref = np.asarray(flash_attention_bshd(q, k, v, causal=True,
                                              block_q=256, block_k=256))
        out = np.asarray(flash_attention_bshd(q, k, v, causal=True,
                                              block_q=128, block_k=256))
        np.testing.assert_array_equal(out, ref)

    def test_flash_tuned_block_guarded_by_divisor_pick(self):
        """A tuned block preference that does not divide THIS padded
        length falls back through _pick_block instead of mis-tiling."""
        from paddle_tpu.ops.pallas_ops import flash_attention as fa

        t = tune.TuningTable()
        t.put("flash_attention_fwd",
              tune.bucket_key(CONTRACTS["flash_attention_fwd"],
                              {"block_q": 384, "block_k": 384}),
              "float32", "cpu", {"block_q": 256, "block_k": 512})
        tune.set_active_table(t)
        # Sp=384: preference 256 halves to 128 (divides), 512 -> 384
        assert fa._resolved_blocks(384) == (256, 512)
        assert fa._pick_block(256, 384) == 128
        rng = np.random.RandomState(5)
        q = jnp.asarray(rng.randn(1, 384, 1, 32).astype(np.float32))
        ref_off = None
        out_on = np.asarray(fa.flash_attention_bshd(q, q, q,
                                                    causal=True))
        tune.reset()
        ref_off = np.asarray(fa.flash_attention_bshd(q, q, q,
                                                     causal=True))
        # block_q choice partitions rows -> identical; block_k pref 512
        # does not divide 384 so _pick_block falls back to the SAME
        # divisor the default path picks -> bit-identical end to end
        np.testing.assert_array_equal(out_on, ref_off)

    def test_paged_q_align_tuned_matches_default(self):
        """The one relayout knob the ragged kernel has left (the pool is
        read as stored: there is no head padding to tune)."""
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            ragged_paged_attention_kernel)

        rng = np.random.RandomState(6)
        q = jnp.asarray(rng.randn(2, 5, 3, 20).astype(np.float32))
        kp = jnp.asarray(rng.randn(6, 4, 3 * 20).astype(np.float32))
        vp = jnp.asarray(rng.randn(6, 4, 3 * 20).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0]], np.int32))
        rl = jnp.asarray(np.array([[7, 8, 9, 10, 11], [6, 0, 0, 0, 0]],
                                  np.int32))
        ref = np.asarray(ragged_paged_attention_kernel(
            q, kp, vp, pt, rl, interpret=True))
        out = np.asarray(ragged_paged_attention_kernel(
            q, kp, vp, pt, rl, interpret=True, q_align=16))
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("pages", [1, 2, 4])
    def test_paged_pages_per_step_tuned_matches_default(self, pages):
        """The pages a grid step covers, resolved through the table like
        q_align: a tuned value regroups the online softmax (the same
        sums in another order), so the default's result to rounding."""
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            _ragged_resolved_dims, ragged_paged_attention_kernel)

        rng = np.random.RandomState(8)
        H, D = 3, 20
        q = jnp.asarray(rng.randn(2, 5, H, D).astype(np.float32))
        kp = jnp.asarray(rng.randn(12, 4, H * D).astype(np.float32))
        vp = jnp.asarray(rng.randn(12, 4, H * D).astype(np.float32))
        pt = jnp.asarray(rng.randint(1, 12, (2, 9)).astype(np.int32))
        rl = jnp.asarray(np.array([[30, 31, 32, 33, 34], [17, 0, 0, 0, 0]],
                                  np.int32))
        ref = np.asarray(ragged_paged_attention_kernel(
            q, kp, vp, pt, rl, interpret=True))
        contract = CONTRACTS["paged_attention_ragged"]
        t = tune.TuningTable()
        t.put(contract.name,
              tune.bucket_key(contract, {"heads": H, "head_dim": D}),
              "float32", "cpu", {"q_align": 8, "pages_per_step": pages})
        tune.set_active_table(t)
        assert _ragged_resolved_dims(H, D, False) == (8, True, pages)
        out = np.asarray(ragged_paged_attention_kernel(
            q, kp, vp, pt, rl, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_paged_int8_epilogue_choice_bounded_not_identical(self):
        """The fused-dequant axis is measurable but NOT bit-exact —
        which is exactly why the default sweep (atol=0) rejects the
        non-default choice (docs/TUNING.md)."""
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            paged_attention_kernel)

        rng = np.random.RandomState(7)
        N, P, H, D = 5, 4, 2, 16
        kf = rng.randn(N, P, H, D).astype(np.float32)
        vf = rng.randn(N, P, H, D).astype(np.float32)
        ks = (np.abs(kf).max(axis=(1, 3)) / 127 + 1e-9).astype(
            np.float32)
        vs = (np.abs(vf).max(axis=(1, 3)) / 127 + 1e-9).astype(
            np.float32)
        kq = np.clip(np.round(kf / ks[:, None, :, None]), -127,
                     127).astype(np.int8)
        vq = np.clip(np.round(vf / vs[:, None, :, None]), -127,
                     127).astype(np.int8)
        q = jnp.asarray(rng.randn(1, H, D).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2]], np.int32))
        sl = jnp.asarray(np.array([7], np.int32))
        args = (q, jnp.asarray(kq.reshape(N, P, H * D)),
                jnp.asarray(vq.reshape(N, P, H * D)), pt, sl,
                jnp.asarray(ks), jnp.asarray(vs))
        fused = np.asarray(paged_attention_kernel(
            *args, interpret=True, fused_dequant=True))
        pre = np.asarray(paged_attention_kernel(
            *args, interpret=True, fused_dequant=False))
        np.testing.assert_allclose(pre, fused, rtol=1e-4, atol=1e-5)


# =============================================================================
# CLI
# =============================================================================
class TestCLI:
    def test_sweep_show_verify_roundtrip(self, tmp_path, capsys):
        from paddle_tpu.tune.__main__ import main

        p = str(tmp_path / "t.ptt")
        rc = main(["sweep", "--table", p, "--kernel",
                   "quantized_matmul", "--extent",
                   "block_m=128,block_k=128,block_n=128",
                   "--repeats", "1"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "winner" in out and "committed 1" in out
        assert main(["show", "--table", p]) == 0
        out = capsys.readouterr().out
        assert "quantized_matmul @ " in out
        assert main(["verify", "--table", p, "--no-run"]) == 0
        out = capsys.readouterr().out
        assert "all 1 entries verified" in out

    def test_verify_fails_on_corrupt_and_invalid(self, tmp_path,
                                                 capsys):
        from paddle_tpu.tune.__main__ import main

        p = str(tmp_path / "t.ptt")
        open(p, "wb").write(b"junk")
        assert main(["verify", "--table", p]) == 1
        assert "TuningTableCorruptError" in capsys.readouterr().out
        # a validate()-breaking hand edit fails verify even host-only
        t = tune.TuningTable(p)
        t.put("quantized_matmul", "block_k=256,block_m=128,block_n=256",
              "int8_weights", "cpu",
              {"block_m": 128, "block_n": 100, "block_k": 128})
        t.save()
        assert main(["verify", "--table", p, "--no-run"]) == 1
        assert "validate()" in capsys.readouterr().out

    def test_show_reports_fallback_for_corrupt_table(self, tmp_path,
                                                     capsys):
        from paddle_tpu.tune.__main__ import main

        p = str(tmp_path / "bad.ptt")
        open(p, "wb").write(b"junk")
        assert main(["show", "--table", p]) == 1
        assert "FALLBACK to contract defaults" in \
            capsys.readouterr().out

    def test_unknown_kernel_is_a_usage_error(self, tmp_path):
        from paddle_tpu.tune.__main__ import main

        assert main(["sweep", "--table", str(tmp_path / "t.ptt"),
                     "--kernel", "nope"]) == 2

    def test_verify_counts_malformed_bucket_as_failure(self, tmp_path,
                                                       capsys):
        """Review fix: a programmatically-written entry with a
        non-canonical bucket string must FAIL verification, not crash
        the gate with a parse traceback."""
        from paddle_tpu.tune.__main__ import main

        p = str(tmp_path / "t.ptt")
        t = tune.TuningTable(p)
        t.put("quantized_matmul", "block_m=abc", "int8_weights", "cpu",
              {"block_m": 128, "block_n": 128, "block_k": 128})
        t.save()
        assert main(["verify", "--table", p, "--no-run"]) == 1
        assert "malformed bucket" in capsys.readouterr().out
        # a dims-less entry is likewise a counted FAIL, not a KeyError
        t = tune.TuningTable(p)
        t.put("quantized_matmul", "block_k=256,block_m=128,block_n=256",
              "int8_weights", "cpu", {"block_m": 128, "block_n": 128,
                                      "block_k": 128})
        del t._entries[next(iter(t._entries))]["dims"]
        t.save()
        assert main(["verify", "--table", p, "--no-run"]) == 1
        assert "missing or non-numeric dims" in capsys.readouterr().out


class TestRunnerCompileDiscipline:
    def test_runner_compiles_once_per_choice(self):
        """Review fix: the timed min-of-N repeats must hit ONE compiled
        executable per candidate — the sweep measures kernel time, not
        retrace time."""
        from paddle_tpu.profiler.jit_cost import cost_registry
        from paddle_tpu.tune.runners import runner_for

        contract = CONTRACTS["quantized_matmul"]
        choice = {"block_m": 128, "block_k": 128, "block_n": 128}
        run = runner_for("quantized_matmul")(contract, dict(choice),
                                             "int8_weights")
        before = cost_registry.snapshot().get(
            "tune.quantized_matmul", {}).get("compile_count", 0)
        for _ in range(3):
            run(choice)
        after = cost_registry.snapshot()[
            "tune.quantized_matmul"]["compile_count"]
        assert after - before == 1
