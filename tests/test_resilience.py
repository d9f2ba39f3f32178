"""Resilience layer acceptance (ISSUE 6): engine state checkpoint +
warm failover, watchdog/backoff, overload brownout, typed error
taxonomy, and the deterministic chaos acceptance run.

Acceptance bars exercised here:

- warm failover is pinned BYTE-IDENTICAL: a request killed mid-decode
  resumes from its last snapshot on a survivor and its full token
  stream equals the uninterrupted ``generate(greedy)`` reference, with
  measured recompute <= K (the checkpoint interval), under both fp and
  int8-static KV modes;
- the seeded chaos plan (1 kill + 1 straggler + 1 allocator-exhaustion
  over 8 requests / 2 replicas) is deterministic — same seed, same
  fault schedule, same final statuses — every request reaches exactly
  one terminal status, and survivors leak zero pages;
- watchdog trips pull a straggling replica from the routing pool and
  re-admit it after exponential backoff; hung steps escalate to dead;
- brownout degrades in documented stages (shed lowest-slack -> clamp
  budgets -> reject) under sustained pressure, with hysteresis;
- a failed-over request's deadline stays anchored to its ORIGINAL
  submit time — requeue never extends an SLO (the router-requeue
  regression fix);
- HTTP status codes derive from the framework.errors taxonomy.

The full randomized chaos soak is ``slow``-marked (tier-1 runs
``-m 'not slow'``).
"""
import gc
import threading
import time
import weakref
from collections import Counter

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import errors
from paddle_tpu.serving import (BrownoutController, BrownoutPolicy,
                                ServingEngine, ServingFrontend, Watchdog,
                                WatchdogConfig)
from paddle_tpu.serving.resilience import (BROWNOUT_CLAMP, BROWNOUT_NORMAL,
                                           BROWNOUT_REJECT, BROWNOUT_SHED)
from paddle_tpu.serving.router import DEAD, HEALTHY, SUSPECT
from paddle_tpu.testing import chaos
from paddle_tpu.testing.chaos import ChaosPlan, Fault


@pytest.fixture(autouse=True)
def _lock_witness():
    """ISSUE 7: every run of this file doubles as a deadlock detector —
    the framework.concurrency witness records lock-order inversions
    (ABBA cycles, declared-hierarchy violations) across all the threads
    the scenarios spin up, and teardown asserts ZERO were seen.
    Record-only mode: raising inside a pump thread would masquerade as
    an engine crash and derail the scenario under test."""
    from paddle_tpu.framework import concurrency

    with concurrency.witness(raise_on_violation=False):
        yield
    concurrency.assert_clean()

VOCAB, HID, LAYERS, HEADS = 50, 32, 2, 2
ENGINE_KW = dict(page_size=4, max_batch_size=4, eos_id=0)


@pytest.fixture(scope="module")
def gpt(shared_gpt_small):
    # session-shared model (conftest): identical seed/dims to
    # what this module built privately — the serving programs
    # compile once for the whole suite instead of per module
    return shared_gpt_small


@pytest.fixture(scope="module")
def quant(gpt):
    """Calibrated static KV scales — the int8_static snapshot mode."""
    from paddle_tpu.slim import export_serving_quant

    rng = np.random.RandomState(3)
    return export_serving_quant(
        gpt, calib_prompts=rng.randint(1, VOCAB, (4, 12)).astype(np.int32))


# session-scoped generate() memo (conftest greedy_ref_memo, ISSUE 14
# suite health): the failover scenarios re-derive the same greedy refs
# across tests — each distinct reference compiles once per suite
_MEMO = None
_QUANT_KEY = "calib-seed3-4x12"  # identical export in resilience+spec_decode


@pytest.fixture(autouse=True)
def _bind_ref_memo(greedy_ref_memo):
    global _MEMO
    _MEMO = greedy_ref_memo


def _reference(gpt, prompt, budget, quant=None):
    w = _MEMO(gpt, prompt, budget, end_id=0, quant=quant,
              quant_key=None if quant is None else _QUANT_KEY)
    if (w == 0).any():
        w = w[: int(np.argmax(w == 0)) + 1]
    return w


def _drain(eng):
    while eng.scheduler.has_work() or eng._pending:
        eng.step()


# =============================================================================
# Error taxonomy (satellite: typed errors -> HTTP statuses)
# =============================================================================
class TestErrorTaxonomy:
    def test_http_status_mapping(self):
        assert errors.http_status_for(errors.ResourceExhaustedError) == 429
        assert errors.http_status_for(errors.UnavailableError) == 503
        assert errors.http_status_for(errors.DeadlineExceededError) == 504
        assert errors.http_status_for(errors.ExecutionTimeoutError) == 504
        assert errors.http_status_for(errors.InternalError) == 500
        assert errors.http_status_for(errors.InvalidArgumentError) == 400

    def test_instances_and_mro_walk(self):
        # instances map like their classes; unlisted subclasses inherit
        # the nearest listed ancestor's status
        assert errors.http_status_for(errors.UnavailableError("x")) == 503

        class MySubclass(errors.DeadlineExceededError):
            pass

        assert errors.http_status_for(MySubclass) == 504
        assert errors.http_status_for(RuntimeError("x"), default=500) == 500

    def test_taxonomy_shape(self):
        # DeadlineExceeded is a shade of timeout; Internal is framework
        # fault — both catchable via the reference-style base
        assert issubclass(errors.DeadlineExceededError,
                          errors.ExecutionTimeoutError)
        assert issubclass(errors.InternalError, errors.EnforceNotMet)


# =============================================================================
# Watchdog state machine (unit, synthetic clock)
# =============================================================================
class TestWatchdog:
    def test_threshold_tracks_rolling_p99(self):
        wd = Watchdog(WatchdogConfig(min_threshold_s=0.1,
                                     p99_multiplier=8.0))
        assert wd.threshold_s("r0") == 0.1          # no data: floor
        for _ in range(100):
            wd.observe_step("r0", 0.05)
        assert wd.threshold_s("r0") == pytest.approx(0.4, rel=0.05)

    def test_cold_replica_exempt_until_first_step(self):
        """No latency history = compiling, not hanging: only the
        cold-grace ceiling applies before the first completed step."""
        cfg = WatchdogConfig(min_threshold_s=0.2, hang_timeout_s=5.0,
                             cold_grace_s=60.0)
        wd = Watchdog(cfg)
        # busy far past both thresholds but cold: never suspect
        assert wd.check("r0", busy_for=30.0, now=0.0) == "ok"
        assert wd.trips("r0") == 0
        assert wd.check("r0", busy_for=61.0, now=1.0) == "dead"
        # one observed step ends the exemption
        wd.observe_step("r1", 0.01)
        assert wd.check("r1", busy_for=0.3, now=2.0) == "suspect"

    def test_ok_suspect_dead_escalation(self):
        cfg = WatchdogConfig(min_threshold_s=0.2, hang_timeout_s=5.0)
        wd = Watchdog(cfg)
        wd.observe_step("r0", 0.01)        # warm: cold grace over
        t = 100.0
        assert wd.check("r0", busy_for=0.1, now=t) == "ok"
        assert wd.check("r0", busy_for=0.3, now=t + 1) == "suspect"
        # same incident: no re-trip while still overdue
        assert wd.check("r0", busy_for=0.5, now=t + 2) == "ok"
        assert wd.trips("r0") == 1
        assert wd.check("r0", busy_for=6.0, now=t + 3) == "dead"

    def test_readmit_waits_exponential_backoff(self):
        cfg = WatchdogConfig(min_threshold_s=0.2, backoff_initial_s=1.0,
                             backoff_max_s=16.0)
        wd = Watchdog(cfg)
        wd.observe_step("r0", 0.01)        # warm: cold grace over
        t = 0.0
        assert wd.check("r0", busy_for=0.5, now=t) == "suspect"
        # recovered (idle) but backoff (1s after recovery seen) not up
        assert wd.check("r0", busy_for=None, now=t + 0.1) == "ok"
        assert wd.check("r0", busy_for=None, now=t + 0.5) == "ok"
        assert wd.check("r0", busy_for=None, now=t + 1.2) == "readmit"
        # second incident doubles the backoff
        assert wd.check("r0", busy_for=0.5, now=t + 2) == "suspect"
        assert wd.backoff_s("r0") == 2.0
        assert wd.check("r0", busy_for=None, now=t + 3) == "ok"
        assert wd.check("r0", busy_for=None, now=t + 5.1) == "readmit"

    def test_busy_replica_readmits_after_completed_step(self):
        """A suspect replica serving back-to-back steps is never
        sampled idle — a COMPLETED step is recovery evidence that arms
        the backoff, and the busy-but-not-overdue branch re-admits."""
        cfg = WatchdogConfig(min_threshold_s=0.2, p99_multiplier=0.0,
                             backoff_initial_s=1.0)
        wd = Watchdog(cfg)
        wd.observe_step("r0", 0.01)
        assert wd.check("r0", busy_for=0.5, now=10.0) == "suspect"
        # the overdue step finally completes; the next steps are fast
        # and the replica goes straight into them (never idle)
        wd.observe_step("r0", 0.5, now=11.0)       # arms backoff -> 12.0
        assert wd.check("r0", busy_for=0.05, now=11.5) == "ok"
        assert wd.check("r0", busy_for=0.05, now=12.1) == "readmit"
        # but an OVERDUE current step never readmits
        assert wd.check("r0", busy_for=0.5, now=13.0) == "suspect"

    def test_backoff_caps(self):
        wd = Watchdog(WatchdogConfig(backoff_initial_s=1.0,
                                     backoff_max_s=4.0))
        wd.observe_step("r0", 0.01)        # warm: cold grace over
        for i in range(6):
            wd.check("r0", busy_for=99.0, now=float(i))  # trips suspect
            wd._w("r0").suspect_since = None             # force recovery
        assert wd.backoff_s("r0") <= 4.0


# =============================================================================
# Brownout controller (unit)
# =============================================================================
class TestBrownoutController:
    def test_stage_thresholds(self):
        pol = BrownoutPolicy(shed_at=0.6, clamp_at=0.8, reject_at=0.95)
        assert pol.target_stage(0.3) == BROWNOUT_NORMAL
        assert pol.target_stage(0.7) == BROWNOUT_SHED
        assert pol.target_stage(0.85) == BROWNOUT_CLAMP
        assert pol.target_stage(1.2) == BROWNOUT_REJECT

    def test_sustain_required_to_escalate(self):
        bc = BrownoutController(BrownoutPolicy(sustain_evals=3))
        assert bc.evaluate(0.7) == BROWNOUT_NORMAL   # 1 of 3
        assert bc.evaluate(0.7) == BROWNOUT_NORMAL   # 2 of 3
        assert bc.evaluate(0.7) == BROWNOUT_SHED     # sustained
        # a dip resets the streak toward the next stage: the two
        # pre-dip CLAMP-ward evaluations don't count, three fresh
        # consecutive ones do
        assert bc.evaluate(0.85) == BROWNOUT_SHED
        assert bc.evaluate(0.7) == BROWNOUT_SHED
        assert bc.evaluate(0.85) == BROWNOUT_SHED
        assert bc.evaluate(0.85) == BROWNOUT_SHED
        assert bc.evaluate(0.85) == BROWNOUT_CLAMP

    def test_oscillation_across_stage_boundary_still_escalates(self):
        """Pressure alternating between the SHED and CLAMP bands is
        sustained overload — the streak converges on the stage every
        sample justified instead of resetting on each flip."""
        bc = BrownoutController(BrownoutPolicy(sustain_evals=2))
        assert bc.evaluate(0.75) == BROWNOUT_NORMAL   # target SHED
        assert bc.evaluate(0.875) == BROWNOUT_SHED    # target CLAMP:
        #                        streak of 2, min(SHED, CLAMP) = SHED
        assert bc.evaluate(0.875) == BROWNOUT_SHED    # fresh streak
        assert bc.evaluate(0.875) == BROWNOUT_CLAMP

    def test_sustain_s_requires_wall_clock_span(self):
        """sustain_evals counts SAMPLES (pump ticks arrive every ~5 ms),
        so sustain_s additionally requires the streak to span real
        time — rapid ticks alone must not escalate."""
        bc = BrownoutController(BrownoutPolicy(sustain_evals=2,
                                               sustain_s=0.5))
        t = 10.0
        assert bc.evaluate(0.7, now=t) == BROWNOUT_NORMAL
        # plenty of samples, but only 10 ms of wall clock: hold
        for i in range(20):
            assert bc.evaluate(0.7, now=t + 0.0005 * i) == BROWNOUT_NORMAL
        assert bc.evaluate(0.7, now=t + 0.6) == BROWNOUT_SHED

    def test_hysteresis_on_release(self):
        pol = BrownoutPolicy(shed_at=0.6, release_margin=0.1,
                             sustain_evals=1)
        bc = BrownoutController(pol)
        assert bc.evaluate(0.65) == BROWNOUT_SHED
        # 0.55 is below shed_at but inside the release margin: hold
        assert bc.evaluate(0.55) == BROWNOUT_SHED
        assert bc.evaluate(0.45) == BROWNOUT_NORMAL

    def test_stage_gauge_exported(self):
        from paddle_tpu.framework.monitor import stat_registry

        bc = BrownoutController(BrownoutPolicy(sustain_evals=1))
        bc.evaluate(0.99)
        assert stat_registry.get("serving.brownout_stage").get() == 3
        bc.evaluate(0.0)
        assert stat_registry.get("serving.brownout_stage").get() == 0


# =============================================================================
# Engine snapshot / restore (the checkpoint contract)
# =============================================================================
class TestSnapshotRestore:
    def _run_until(self, eng, rid, ntokens):
        """Step until ``rid`` has consumed >= ntokens generated tokens."""
        for _ in range(200):
            seq = next((s for s in eng.scheduler.running
                        if s.seq_id == rid), None)
            if seq is not None and len(seq.generated) >= ntokens:
                return seq
            if not (eng.scheduler.has_work() or eng._pending):
                break
            eng.step()
        raise AssertionError(f"{rid} never reached {ntokens} tokens")

    @pytest.mark.parametrize("mode", ["native", "int8_static"])
    def test_restore_on_second_engine_byte_identical(self, gpt, quant,
                                                     mode):
        """Kill the donor mid-decode; the survivor resumes from the
        snapshot and the spliced stream equals the uninterrupted
        reference — the acceptance pin for fp AND int8-static KV."""
        kw = dict(ENGINE_KW)
        q = None
        if mode == "int8_static":
            kw.update(kv_cache_dtype="int8", quant_scales=quant)
            q = quant
        rng = np.random.RandomState(5)
        prompt = rng.randint(1, VOCAB, (6,)).astype(np.int32)
        budget = 12

        donor = ServingEngine(gpt, **kw)
        assert donor.kv_mode() == mode
        rid = donor.add_request(prompt, max_new_tokens=budget)
        self._run_until(donor, rid, 5)
        snap = donor.snapshot(rid)
        assert snap is not None and snap.kv_mode == mode
        assert snap.num_generated >= 5
        assert snap.nbytes > 0
        # survivor: a fresh engine of the same configuration
        surv = ServingEngine(gpt, **kw)
        surv.restore(snap)
        _drain(surv)
        got = surv.take_output(rid)
        np.testing.assert_array_equal(got, _reference(gpt, prompt, budget,
                                                      quant=q))
        assert surv.cache.pages_in_use == 0
        # recompute on the survivor is bounded by the checkpoint lag
        assert len(got) - snap.num_generated <= budget

    def test_restore_int8_dynamic_rederives_scales(self, gpt):
        """Dynamic per-page scales are device state of the donor pool:
        the snapshot carries dequantized pages and restore requantizes
        with fresh abs-max scales — equal within quantization noise
        (byte-identity is NOT the contract in this mode)."""
        kw = dict(ENGINE_KW, kv_cache_dtype="int8")
        rng = np.random.RandomState(9)
        prompt = rng.randint(1, VOCAB, (5,)).astype(np.int32)
        donor = ServingEngine(gpt, **kw)
        assert donor.kv_mode() == "int8_dynamic"
        rid = donor.add_request(prompt, max_new_tokens=10)
        self._run_until(donor, rid, 4)
        snap = donor.snapshot(rid)
        assert snap.kv_mode == "int8_dynamic"
        # dequantized payload: float pages, no scale arrays
        assert snap.pages["k"][0].dtype == np.float32
        surv = ServingEngine(gpt, **kw)
        surv.restore(snap)
        _drain(surv)
        got = surv.take_output(rid)
        ref = _reference(gpt, prompt, 10)
        # int8 round-trip noise can flip a token only where top-2 logit
        # margins are razor-thin; on the calibrated toy model the greedy
        # stream holds (same physics as test_quant_serving parity pins)
        np.testing.assert_array_equal(got, ref)
        assert surv.cache.pages_in_use == 0

    def test_snapshot_of_unknown_or_queued_request_is_none(self, gpt):
        eng = ServingEngine(gpt, **ENGINE_KW)
        assert eng.snapshot("nope") is None

    def test_restore_rejects_geometry_and_mode_mismatch(self, gpt):
        eng = ServingEngine(gpt, **ENGINE_KW)
        rid = eng.add_request(np.array([3, 5, 7], np.int32),
                              max_new_tokens=8)
        self._run_until(eng, rid, 2)
        snap = eng.snapshot(rid)
        other_ps = ServingEngine(gpt, page_size=8, max_batch_size=4,
                                 eos_id=0)
        with pytest.raises(ValueError, match="page_size"):
            other_ps.restore(snap)
        other_mode = ServingEngine(gpt, kv_cache_dtype="int8", **ENGINE_KW)
        with pytest.raises(ValueError, match="kv_mode"):
            other_mode.restore(snap)
        # a live duplicate id is rejected like add_request
        with pytest.raises(ValueError, match="in flight"):
            eng.restore(snap)

    def test_snapshot_metrics(self, gpt):
        eng = ServingEngine(gpt, **ENGINE_KW)
        before = eng.metrics.snapshot()["snapshots"]
        rid = eng.add_request(np.array([4, 9], np.int32), max_new_tokens=8)
        self._run_until(eng, rid, 2)
        eng.snapshot(rid)
        after = eng.metrics.snapshot()
        assert after["snapshots"] == before + 1


# =============================================================================
# Warm failover through the frontend
# =============================================================================
class TestWarmFailover:
    def test_failover_resumes_from_checkpoint_byte_identical(self, gpt):
        K = 4
        fe = ServingFrontend(gpt, replicas=2, queue_cap=32,
                             engine_kwargs=ENGINE_KW, snapshot_interval=K)
        try:
            rng = np.random.RandomState(7)
            prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                       for p in (3, 5, 9, 4, 7, 6, 8, 2)]
            budget = 12
            handles = [fe.submit(p, max_new_tokens=budget)
                       for p in prompts]
            fe.inject_failure("replica-0", at_step=7)
            statuses = [h.wait(timeout=300) for h in handles]
            assert statuses == ["completed"] * 8
            resumed = [h for h in handles if h.resumed_from is not None]
            assert resumed, "no request resumed from a checkpoint"
            for h in resumed:
                assert h.retried
                # resumption happens at a checkpoint boundary
                assert h.resumed_from >= 1
                assert h.resumed_from % K == 0
            # byte-identity incl. resumed streams
            for p, h in zip(prompts, handles):
                np.testing.assert_array_equal(
                    h.tokens, _reference(gpt, p, budget))
            # a replay of a finished resumed handle surfaces the (never
            # consumed live) resume marker, with the tokens intact and
            # no restart marker — the stream was spliced, not reset
            for h in resumed:
                evs = list(h.events())
                assert ("resume", h.resumed_from) in evs
                assert ("restart",) not in evs
                np.testing.assert_array_equal(
                    [e[2] for e in evs if e[0] == "token"], h.tokens)
            # warm failover accounting: tokens before the checkpoint
            # were NOT recomputed (fresh metrics per frontend instance)
            snap = fe.metrics.snapshot()
            assert snap["recompute_saved_tokens"] == sum(
                h.resumed_from for h in resumed) > 0
            es = fe.engine_metrics.snapshot()
            assert es["restores"] == len(resumed)
            assert es["snapshots"] >= len(resumed)
            # kill→first-resumed-token timing recorded for every victim
            # that produced a post-failover token (resumed or restarted)
            assert es["failover_recovery_ms"]["count"] >= len(resumed)
            assert es["failover_recovery_ms"]["p50"] > 0
            for rep in fe._replicas:
                if rep.state != DEAD:
                    assert rep.engine.cache.pages_in_use == 0
        finally:
            fe.close()

    def test_live_stream_resume_marker_and_recompute_bound(self, gpt):
        """A client holding the stream open across the kill sees its
        delivered tokens stay valid (no restart, no index regression),
        one resume marker, and measured recompute bounded by the
        checkpoint interval: resumed_from is within K + in-flight slack
        of what the client already held when the replica died."""
        K = 3
        fe = ServingFrontend(gpt, replicas=2, queue_cap=8,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=4,
                                                eos_id=-1),
                             snapshot_interval=K)
        try:
            prompt = np.array([3, 5, 9], np.int32)
            h = fe.submit(prompt, max_new_tokens=14)
            seen = []
            resume_at = None
            seen_at_kill = None
            for ev in h.events():
                if ev[0] == "token":
                    assert ev[1] == len(seen)   # indices never regress
                    seen.append(ev[2])
                    if len(seen) == K + 1 and seen_at_kill is None:
                        seen_at_kill = len(seen)
                        fe.inject_failure("replica-0", at_step=1)
                elif ev[0] == "resume":
                    resume_at = ev[1]
                elif ev[0] == "restart":
                    pytest.fail("warm failover must resume, not restart")
            assert h.status == "completed" and h.retried
            assert resume_at is not None
            assert h.resumed_from == resume_at
            # the checkpoint the stream resumed from is at most K (+ a
            # couple of tokens in flight around the kill) behind what
            # the client had already been streamed
            assert resume_at >= 1
            assert len(seen) - resume_at <= 14  # resumed mid-stream
            assert resume_at >= seen_at_kill - (K + 3)
            np.testing.assert_array_equal(
                np.asarray(seen, np.int32), _reference(gpt, prompt, 14))
            np.testing.assert_array_equal(h.tokens, seen)
        finally:
            fe.close()

    def test_int8_static_warm_failover_byte_identical(self, gpt, quant):
        """The acceptance bar's second KV mode: int8 static scales ride
        along as engine config, failover stays byte-identical.  The
        oracle is the UNINTERRUPTED engine stream (same compute path) —
        dense ``generate(quant=...)`` parity vs the paged int8 kernel
        is PR-4's separate (margin-dependent) property, not failover's."""
        qkw = dict(ENGINE_KW, kv_cache_dtype="int8", quant_scales=quant)
        rng = np.random.RandomState(13)
        prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                   for p in (4, 6, 3, 8)]
        ref_eng = ServingEngine(gpt, **qkw)
        rids = [ref_eng.add_request(p, max_new_tokens=12)
                for p in prompts]
        _drain(ref_eng)
        refs = [ref_eng.take_output(r) for r in rids]
        fe = ServingFrontend(gpt, replicas=2, queue_cap=16,
                             engine_kwargs=qkw, snapshot_interval=4)
        try:
            handles = [fe.submit(p, max_new_tokens=12) for p in prompts]
            fe.inject_failure("replica-0", at_step=7)
            sts = [h.wait(timeout=300) for h in handles]
            assert sts == ["completed"] * 4
            assert any(h.retried for h in handles)
            for ref, h in zip(refs, handles):
                np.testing.assert_array_equal(h.tokens, ref)
            resumed = [h for h in handles if h.resumed_from is not None]
            assert resumed, "no request resumed from a checkpoint"
            assert fe.engine_metrics.snapshot()["restores"] >= len(resumed)
        finally:
            fe.close()


# =============================================================================
# Deferred periodic checkpoints (ISSUE 31): captured on the device in
# stream order in one pump turn, landed on the host in the next
# =============================================================================
class _SnapshotTap:
    """Records, in the order the pump thread does them, every capture
    (``capture_snapshot`` that returned one), landing (``land_snapshot``)
    and device-to-host fetch of gathered KV pages (``jax.device_get`` of
    a page dict) with the replica's step count at that moment, keeps a
    weak reference to every gathered device array, and runs
    ``on_capture(eng, cap)`` on the pump thread right after a capture —
    the deterministic place to arm a kill, a cancel or a denial between
    a capture and its landing."""

    def __init__(self, monkeypatch):
        self.events = []    # (kind, rid, generated, replica steps, replica)
        self.refs = []
        self.unfetched_after_turn = []
        self.on_capture = None
        self.fe = None
        real_capture = ServingEngine.capture_snapshot
        real_land = ServingEngine.land_snapshot
        real_maybe = ServingFrontend._maybe_snapshot
        real_get = jax.device_get

        def capture(eng, rid):
            cap = real_capture(eng, rid)
            if cap is not None:
                self.refs += [weakref.ref(a)
                              for arrs in (cap.gathered or {}).values()
                              for a in arrs]
                self._note("capture", eng, cap)
                if self.on_capture is not None:
                    self.on_capture(eng, cap)
            return cap

        def land(eng, cap):
            self._note("land", eng, cap)
            return real_land(eng, cap)

        def device_get(x):
            if isinstance(x, dict) and "k" in x:
                self.events.append(("fetch",))
            return real_get(x)

        def maybe_snapshot(fe, rep, eng):
            real_maybe(fe, rep, eng)
            # what the turn leaves pending has no host value yet
            self.unfetched_after_turn += [
                getattr(a, "_npy_value", None) is None
                for _, cap in rep.captures
                for arrs in (cap.gathered or {}).values() for a in arrs]

        monkeypatch.setattr(ServingEngine, "capture_snapshot", capture)
        monkeypatch.setattr(ServingEngine, "land_snapshot", land)
        monkeypatch.setattr(ServingFrontend, "_maybe_snapshot",
                            maybe_snapshot)
        monkeypatch.setattr(jax, "device_get", device_get)

    def replica(self, eng):
        return next(r for r in self.fe._replicas if r.engine is eng)

    def _note(self, kind, eng, cap):
        rep = self.replica(eng)
        self.events.append((kind, cap.request_id, len(cap.generated),
                            rep.steps, rep.id))

    def of(self, kind, rid=None):
        return [e for e in self.events
                if e[0] == kind and rid in (None, e[1])]

    def assert_released(self):
        gc.collect()
        assert self.refs, "no page gather was ever captured"
        assert all(r() is None for r in self.refs)
        assert all(not rep.captures for rep in self.fe._replicas)


@pytest.fixture
def tap(monkeypatch):
    return _SnapshotTap(monkeypatch)


def _dropped():
    from paddle_tpu.framework.monitor import stat_registry

    return stat_registry.get("serving.snapshots_dropped").get()


class TestDeferredSnapshots:
    K = 4
    FE_KW = dict(queue_cap=8, snapshot_interval=K,
                 engine_kwargs=dict(page_size=4, max_batch_size=4,
                                    eos_id=-1))

    @pytest.mark.parametrize("mode",
                             ["native", "int8_static", "int8_dynamic"])
    def test_capture_landed_later_equals_snapshot_at_capture(
            self, gpt, quant, mode):
        """(a) The gather sits in stream order: a capture landed after
        the request finished, its pages were freed and ANOTHER request
        was prefilled into them is, array for array, the synchronous
        ``snapshot()`` taken at the moment of capture."""
        kw = dict(page_size=4, max_batch_size=4, eos_id=-1, num_pages=6)
        if mode != "native":
            kw["kv_cache_dtype"] = "int8"
        if mode == "int8_static":
            kw["quant_scales"] = quant
        rng = np.random.RandomState(31)
        eng = ServingEngine(gpt, **kw)
        assert eng.kv_mode() == mode
        a = eng.add_request(rng.randint(1, VOCAB, (6,)).astype(np.int32),
                            max_new_tokens=8)
        TestSnapshotRestore()._run_until(eng, a, 5)
        cap = eng.capture_snapshot(a)
        want = eng.snapshot(a)
        held = set(eng.cache.seq_page_ids(a)[:cap.rows])
        assert cap.rows == want.num_pages >= 3 and not cap.stale
        # five usable pages: B's prompt fits only once A is gone
        b = eng.add_request(rng.randint(1, VOCAB, (12,)).astype(np.int32),
                            max_new_tokens=4)
        reused = set()
        while eng.scheduler.has_work() or eng._pending:
            eng.step()
            reused |= held & set(eng.cache.seq_page_ids(b))
        assert cap.stale and a in eng.outputs and b in eng.outputs
        assert reused, "B was never prefilled into A's freed pages"
        got = eng.land_snapshot(cap)
        assert cap.gathered is None and cap.land_wait_s >= 0
        assert (got.request_id, got.pos, got.kv_mode, got.page_size,
                got.max_new_tokens, got.nbytes) == (
            want.request_id, want.pos, want.kv_mode, want.page_size,
            want.max_new_tokens, want.nbytes)
        np.testing.assert_array_equal(got.generated, want.generated)
        np.testing.assert_array_equal(got.prompt, want.prompt)
        for side in ("k", "v"):
            assert len(got.pages[side]) == len(want.pages[side]) == LAYERS
            for g, w in zip(got.pages[side], want.pages[side]):
                assert g.dtype == w.dtype and g.shape == w.shape
                np.testing.assert_array_equal(g, w)
        gs, ws = got.to_state(), want.to_state()
        assert sorted(gs) == sorted(ws)
        assert eng.cache.pages_in_use == 0

    def test_capturing_turn_fetches_nothing_next_turn_lands_before_kill(
            self, gpt, tap):
        """(b) The turn that captures performs no device-to-host fetch of
        KV pages; the NEXT turn lands the capture before its
        failure-injection check — a replica armed to die on that turn
        hands the request over at exactly the captured checkpoint."""
        fe = tap.fe = ServingFrontend(gpt, replicas=2, **self.FE_KW)
        armed = []

        def die_next_turn(eng, cap):
            if not armed:
                rep = tap.replica(eng)
                armed.append(rep)
                rep.fail_at_step = rep.steps + 1

        tap.on_capture = die_next_turn
        try:
            prompt = np.array([3, 5, 9], np.int32)
            h = fe.submit(prompt, max_new_tokens=14)
            assert h.wait(timeout=300) == "completed"
            rid = h.request_id
            first = tap.of("capture", rid)[0]
            assert first[2] == self.K
            i = tap.events.index(first)
            # next thing the pump did for snapshots: land it, one turn on
            assert tap.events[i + 1] == ("land", rid, self.K, first[3] + 1,
                                         first[4])
            assert tap.events[i + 2][0] == "fetch"
            for j, ev in enumerate(tap.events):
                if ev[0] == "fetch":
                    assert tap.events[j - 1][0] == "land"
            assert tap.unfetched_after_turn \
                and all(tap.unfetched_after_turn)
            assert armed[0].state == DEAD
            assert h.retried and h.resumed_from == self.K
            np.testing.assert_array_equal(
                h.tokens, _reference(gpt, prompt, 14)[:14])
            hist = fe.engine_metrics.snapshot()["snapshot_land_wait_ms"]
            assert hist["count"] == len(tap.of("land")) >= 1
        finally:
            fe.close()
        tap.assert_released()

    def test_kill_with_captures_pending_resumes_from_last_landed(
            self, gpt, tap):
        """(c) A replica that dies between a capture and its landing
        drops the capture: every victim resumes from its last LANDED
        snapshot, within snapshot_interval + one step of what it had
        consumed, byte-identical; the counter counts the dropped."""
        K = self.K
        fe = tap.fe = ServingFrontend(gpt, replicas=2, **self.FE_KW)
        kill = {}

        def die_this_turn(eng, cap):
            if not kill and len(cap.generated) >= 2 * K:
                rep = tap.replica(eng)
                with fe._lock:
                    consumed = {e.handle.request_id: e.handle.num_tokens
                                for e in fe._live.values()
                                if e.replica is rep}
                kill.update(rep=rep, steps=rep.steps, consumed=consumed)
                rep.fail_at_step = rep.steps

        tap.on_capture = die_this_turn
        try:
            rng = np.random.RandomState(17)
            prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                       for p in (3, 6, 5)]
            handles = [fe.submit(p, max_new_tokens=14) for p in prompts]
            assert [h.wait(timeout=300) for h in handles] \
                == ["completed"] * 3
            rep = kill["rep"]
            assert rep.state == DEAD
            pending = [e for e in tap.of("capture")
                       if e[3:] == (kill["steps"], rep.id)]
            # every capture is landed one turn later or dropped, the
            # pending ones of the dying turn among the dropped
            lands = {(rid, g, steps - 1, at) for _, rid, g, steps, at in
                     tap.of("land")}
            unlanded = [e for e in tap.of("capture")
                        if e[1:] not in lands]
            assert pending and set(pending) <= set(unlanded)
            assert _dropped() == len(unlanded)
            landed = {}
            for _, rid, g, _, at in tap.of("land"):
                if at == rep.id:
                    landed[rid] = g
            victims = [h for h in handles
                       if h.request_id in kill["consumed"]]
            assert victims and all(h.retried for h in victims)
            for h in victims:
                last = landed.get(h.request_id)
                assert h.resumed_from == last
                assert kill["consumed"][h.request_id] - (last or 0) \
                    <= K + 1
            assert any(h.resumed_from == K for h in victims)
            for p, h in zip(prompts, handles):
                np.testing.assert_array_equal(
                    h.tokens, _reference(gpt, p, 14)[:14])
            for r in fe._replicas:
                if r.state != DEAD:
                    assert r.engine.cache.pages_in_use == 0
        finally:
            fe.close()
        tap.assert_released()

    @pytest.mark.parametrize("fate", ["completes", "cancelled",
                                      "preempted"])
    def test_request_gone_before_landing_installs_and_leaks_nothing(
            self, gpt, tap, fate):
        """(d) A request that completes, is cancelled or is preempted
        between its capture and the landing turn installs nothing (its
        capture is dropped unfetched) and leaks nothing."""
        K = self.K
        fe = tap.fe = ServingFrontend(gpt, replicas=1, **self.FE_KW)
        eng = fe._replicas[0].engine
        prompts = [np.array([3, 5, 9], np.int32),
                   np.array([7, 2, 8, 4], np.int32)]
        budget = K + 1 if fate == "completes" else 14
        state = {}

        def between(eng_, cap):
            if "hit" in state or cap.request_id != state["target"]:
                return
            state["hit"] = cap
            if fate == "cancelled":
                state["handle"].cancel()
            elif fate == "preempted":
                # an allocation denied to the OLDER request evicts the
                # youngest other one — the captured request
                real = eng.cache.allocate

                def deny_once(seq_id, n):
                    if seq_id == state["other"] and "denied" not in state:
                        state["denied"] = True
                        return False
                    return real(seq_id, n)

                eng.cache.allocate = deny_once

        tap.on_capture = between
        try:
            if fate == "preempted":
                older = fe.submit(prompts[1], max_new_tokens=budget)
                state["other"] = older.request_id
            h = fe.submit(prompts[0], max_new_tokens=budget)
            state["handle"], state["target"] = h, h.request_id
            want = "cancelled" if fate == "cancelled" else "completed"
            assert h.wait(timeout=300) == want
            cap = state.pop("hit")
            assert cap.stale and cap.land_wait_s is None
            del cap
            c = next(e for e in tap.of("capture", h.request_id))
            # the turn after the capture landed nothing of this request
            assert ("land", h.request_id, c[2], c[3] + 1, c[4]) \
                not in tap.events
            assert _dropped() >= 1
            if fate == "preempted":
                assert state.get("denied")
                assert older.wait(timeout=300) == "completed"
                np.testing.assert_array_equal(
                    older.tokens, _reference(gpt, prompts[1], 14)[:14])
                assert eng.scheduler.num_preemptions >= 1
            else:
                assert not tap.of("land") and not tap.of("fetch")
                assert fe.engine_metrics.snapshot()["snapshots"] == 0
            if fate != "cancelled":
                np.testing.assert_array_equal(
                    h.tokens, _reference(gpt, prompts[0], budget)[:budget])
            assert eng.cache.pages_in_use == 0
        finally:
            fe.close()
        tap.assert_released()

    def test_close_with_captures_pending_leaves_no_device_buffer(
            self, gpt, tap):
        """(e) ``close()`` called while a capture is pending returns;
        the pump lands or drops what it holds on its way out and no
        gathered device buffer stays alive."""
        fe = tap.fe = ServingFrontend(gpt, replicas=1, **self.FE_KW)
        captured, go = threading.Event(), threading.Event()

        def hold(eng, cap):
            if not captured.is_set():
                captured.set()
                assert go.wait(timeout=60)

        tap.on_capture = hold
        closer = threading.Thread(target=fe.close)
        try:
            h = fe.submit(np.array([3, 5, 9], np.int32), max_new_tokens=14)
            assert captured.wait(timeout=300)
            closer.start()
            deadline = time.monotonic() + 60
            while not fe._closing and time.monotonic() < deadline:
                time.sleep(0.001)
            assert fe._closing and fe._replicas[0].captures == []
            go.set()
            closer.join(timeout=120)
            assert not closer.is_alive()
            # closing drains what was admitted: the stream finished and
            # its pending capture landed on the way
            assert h.status == "completed"
            assert tap.of("land", h.request_id)
            assert fe._replicas[0].engine.cache.pages_in_use == 0
        finally:
            go.set()
            if closer.is_alive() or not closer.ident:
                fe.close()
        tap.assert_released()


# =============================================================================
# Deterministic chaos acceptance (the tier-1 seeded plan)
# =============================================================================
def _chaos_plan():
    """The pinned tier-1 schedule: 1 replica kill + 1 straggler step +
    1 allocator denial (ISSUE 6 acceptance)."""
    return ChaosPlan([
        Fault("replica.kill", at=6, action="kill", match="replica-0"),
        Fault("engine.step", at=9, action="delay", delay_s=0.05),
        Fault("kv.allocate", at=5, action="deny"),
    ], name="tier1-acceptance")


def _drive_chaos(gpt, plan):
    fe = ServingFrontend(gpt, replicas=2, queue_cap=32,
                         engine_kwargs=ENGINE_KW, snapshot_interval=4)
    try:
        rng = np.random.RandomState(7)
        prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                   for p in (3, 5, 9, 4, 7, 6, 8, 2)]
        with chaos.running(plan):
            handles = [fe.submit(p, max_new_tokens=10) for p in prompts]
            statuses = [h.wait(timeout=300) for h in handles]
        leaks = {rep.id: rep.engine.cache.pages_in_use
                 for rep in fe._replicas if rep.state != DEAD}
        states = {rep.id: rep.state for rep in fe._replicas}
        return prompts, handles, statuses, leaks, states
    finally:
        fe.close()


class TestChaosAcceptance:
    def test_seeded_plan_terminal_identical_deterministic(self, gpt):
        plan_a = _chaos_plan()
        prompts, handles, statuses, leaks, states = _drive_chaos(
            gpt, plan_a)
        # 1) every chaos fault actually fired
        assert sorted(e["site"] for e in plan_a.fired_log()) == [
            "engine.step", "kv.allocate", "replica.kill"]
        # 2) every request reached exactly ONE terminal status, no hangs
        assert statuses == ["completed"] * 8
        assert all(h.done for h in handles)
        # 3) the killed replica died; the survivor leaked zero pages
        assert states["replica-0"] == DEAD
        assert states["replica-1"] == HEALTHY
        assert leaks == {"replica-1": 0}
        # 4) streams (incl. resumed ones) byte-identical to the
        #    uninterrupted greedy reference
        for p, h in zip(prompts, handles):
            np.testing.assert_array_equal(h.tokens,
                                          _reference(gpt, p, 10))
        assert any(h.retried for h in handles)
        # 5) DETERMINISM: replaying the same schedule reproduces the
        #    same fault sequence and the same final statuses
        plan_b = _chaos_plan()
        assert plan_b.schedule() == plan_a.schedule()
        p2, h2, statuses_b, leaks_b, states_b = _drive_chaos(gpt, plan_b)
        assert statuses_b == statuses
        assert states_b == states and leaks_b == leaks
        # the determinism CONTRACT is the schedule + per-request
        # outcomes; the wall-clock interleaving of fired-log entries
        # across two free-running pump threads is not part of it (the
        # unmatched straggler/alloc faults count GLOBAL site visits, so
        # which pump logs first is a scheduling race — made visible by
        # the ISSUE-7 lock-witness overhead, present all along)
        assert (sorted(e["site"] for e in plan_b.fired_log())
                == sorted(e["site"] for e in plan_a.fired_log()))
        for a, b in zip(handles, h2):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_allocator_denial_defers_not_fails(self, gpt):
        """A transient kv.allocate denial defers admission; the request
        still completes with the exact greedy stream."""
        plan = ChaosPlan([Fault("kv.allocate", at=1, action="deny",
                                count=2)])
        fe = ServingFrontend(gpt, replicas=1, queue_cap=8,
                             engine_kwargs=ENGINE_KW)
        try:
            p = np.array([3, 5, 9], np.int32)
            with chaos.running(plan):
                h = fe.submit(p, max_new_tokens=8)
                assert h.wait(timeout=300) == "completed"
            assert len(plan.fired_log()) == 2
            np.testing.assert_array_equal(h.tokens, _reference(gpt, p, 8))
            assert fe._replicas[0].engine.cache.pages_in_use == 0
        finally:
            fe.close()

    def test_engine_step_exception_fails_over(self, gpt):
        """A raised engine-step exception is a replica crash: requests
        fail over to the survivor and complete byte-identically."""
        plan = ChaosPlan([Fault("engine.step", at=4, action="raise",
                                match="replica-0")])
        fe = ServingFrontend(gpt, replicas=2, queue_cap=16,
                             engine_kwargs=ENGINE_KW, snapshot_interval=4)
        try:
            rng = np.random.RandomState(3)
            prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                       for p in (4, 6, 3, 7)]
            with chaos.running(plan):
                handles = [fe.submit(p, max_new_tokens=10)
                           for p in prompts]
                sts = [h.wait(timeout=300) for h in handles]
            assert sts == ["completed"] * 4
            states = {r.id: r.state for r in fe._replicas}
            assert states["replica-0"] == DEAD
            assert "InternalError" in fe.router.get("replica-0").dead_reason
            for p, h in zip(prompts, handles):
                np.testing.assert_array_equal(h.tokens,
                                              _reference(gpt, p, 10))
        finally:
            fe.close()


# =============================================================================
# Watchdog end-to-end (straggler -> suspect -> readmit)
# =============================================================================
class TestWatchdogEndToEnd:
    def test_straggler_trips_suspect_then_readmits(self, gpt):
        # p99_multiplier=0 pins a FIXED 0.15 s threshold: the adaptive
        # p99 term (covered by the unit tests) would absorb compile-time
        # outliers from a cold program cache and make this e2e timing-
        # dependent — in a fresh process warm steps are ~2 s compiles,
        # putting 8 x p99 far above any reasonable injected delay
        wd = WatchdogConfig(min_threshold_s=0.15, p99_multiplier=0.0,
                            hang_timeout_s=60.0, backoff_initial_s=0.05,
                            check_interval_s=0.005)
        fe = ServingFrontend(gpt, replicas=2, queue_cap=32,
                             engine_kwargs=ENGINE_KW, watchdog=wd)
        try:
            # warm BOTH replicas first: a cold replica is exempt from
            # the overdue threshold (cold_grace_s), so the straggler
            # must hit a replica with step-latency history
            warm = [fe.submit(np.arange(1, 4, dtype=np.int32),
                              max_new_tokens=3) for _ in range(2)]
            assert [h.wait(timeout=300) for h in warm] == ["completed"] * 2
            # delay must clear max(min_threshold_s, 8 x warm-step p99)
            # unambiguously — host timing outliers put warm p99 in the
            # tens of ms, so a sub-second delay is flaky
            plan = ChaosPlan([Fault("engine.step", at=3, action="delay",
                                    delay_s=1.5)])
            with chaos.running(plan):
                hs = [fe.submit(np.arange(1, 5, dtype=np.int32),
                                max_new_tokens=10) for _ in range(4)]
                sts = [h.wait(timeout=300) for h in hs]
            # a straggler is NOT a failure: everything completes
            assert sts == ["completed"] * 4
            assert plan.fired_log()
            es = fe.engine_metrics.snapshot()
            assert es["watchdog_trips"] >= 1
            # after backoff the suspect replica re-enters the pool
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                states = {r["id"]: r["state"]
                          for r in fe.health()["replicas"]}
                if all(s == HEALTHY for s in states.values()):
                    break
                time.sleep(0.02)
            assert all(s == HEALTHY for s in states.values())
            assert fe.health()["suspect_replicas"] == 0
        finally:
            fe.close()

    def test_suspect_replica_not_routable(self):
        from paddle_tpu.serving.router import Replica, Router

        r = Router()
        rep0, rep1 = Replica("replica-0", None), Replica("replica-1", None)
        r.add(rep0)
        r.add(rep1)
        assert r.mark_suspect(rep0)
        assert rep0.state == SUSPECT
        assert not r.mark_suspect(rep0)       # already suspect: no-op
        # placement skips the suspect replica
        for _ in range(4):
            assert r.pick(cost=8).id == "replica-1"
        assert r.mark_healthy(rep0)
        assert rep0.state == HEALTHY
        assert r.healthz()["suspect_replicas"] == 0

    def test_all_suspect_placement_retries_with_backoff(self, gpt):
        """Transient all-SUSPECT fleet: pick_with_retry sleeps through
        a backoff instead of failing the submission on first error."""
        fe = ServingFrontend(gpt, replicas=1, queue_cap=8,
                             engine_kwargs=ENGINE_KW,
                             placement_attempts=6,
                             placement_backoff_s=0.02)
        try:
            rep0 = fe.router.get("replica-0")
            fe.router.mark_suspect(rep0)
            before = fe.engine_metrics.snapshot()["retries_backoff"]

            def readmit():
                time.sleep(0.05)
                fe.router.mark_healthy(rep0)

            import threading

            t = threading.Thread(target=readmit)
            t.start()
            h = fe.submit(np.array([3, 5, 9], np.int32), max_new_tokens=6)
            t.join()
            assert h.wait(timeout=300) == "completed"
            assert fe.engine_metrics.snapshot()["retries_backoff"] > before
        finally:
            fe.close()

    def test_terminally_dead_fleet_gives_up_without_backoff(self):
        from paddle_tpu.serving.router import Replica, Router

        r = Router()
        rep0 = Replica("replica-0", None)
        r.add(rep0)
        r.mark_dead(rep0, "test")
        t0 = time.monotonic()
        # nothing to wait FOR: no recoverable replica, so no sleeps
        # even with a large attempts/backoff budget
        assert r.pick_with_retry(attempts=8, backoff_s=0.5) is None
        assert time.monotonic() - t0 < 0.4


# =============================================================================
# Brownout end-to-end (shed -> clamp -> reject)
# =============================================================================
def _immune_seeds(fe, n, budget=16, timeout=120.0):
    """Submit ``n`` no-deadline requests, one at a time, waiting until
    each is DECODING (>= 1 token) before the next: decoding requests
    are never shed candidates, so the seeds hold queue pressure at a
    deterministic level (and are themselves shed-proof) while flood
    arrivals — starved of lanes by max_batch_size — stay backlog-only."""
    seeds = []
    deadline = time.monotonic() + timeout
    for i in range(n):
        h = fe.submit(np.arange(2 + i, 6 + i, dtype=np.int32),
                      max_new_tokens=budget)
        seeds.append(h)
        while h.num_tokens < 1:
            if h.done or time.monotonic() >= deadline:
                raise AssertionError(
                    f"seed {i} never started decoding ({h.status})")
            time.sleep(0.005)
    return seeds


class TestBrownoutEndToEnd:
    def test_shed_stage_picks_lowest_slack_backlog(self, gpt):
        """3 lane-pinned decodes hold pressure over shed_at; flood
        arrivals are backlog-only (no free lane), and each triggering
        submission sheds the backlog request with the LOWEST deadline
        slack — not FIFO, not the arrival itself."""
        pol = BrownoutPolicy(shed_at=0.55, clamp_at=5.0, reject_at=6.0,
                             sustain_evals=1)
        fe = ServingFrontend(gpt, replicas=1, queue_cap=8,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=3,
                                                num_pages=64,
                                                eos_id=-1),
                             brownout=pol)
        try:
            seeds = _immune_seeds(fe, 3, budget=48)  # all 3 lanes pinned
            # flood: pressure is evaluated BEFORE placing the arrival,
            # so f0 (3/8) and f1 (4/8) land below shed_at and only f2's
            # submission (5/8 = 0.625) starts shedding.  Deadlines are
            # chosen so the lowest-slack victim is NOT submission order.
            f0 = fe.submit(np.array([3, 5], np.int32), max_new_tokens=4,
                           deadline_ms=60000)
            f1 = fe.submit(np.array([4, 6], np.int32), max_new_tokens=4,
                           deadline_ms=10000)
            # sheds the lowest-slack backlog request: f1 (10s < 60s)
            f2 = fe.submit(np.array([5, 7], np.int32), max_new_tokens=4,
                           deadline_ms=30000)
            assert f1.wait(timeout=60) == "rejected"
            assert "brownout shed" in f1.detail
            assert f1.error_cls is errors.UnavailableError
            # sheds f2 (30s) — f3 itself is the arrival (shielded) and
            # f0 (60s) has more slack
            f3 = fe.submit(np.array([6, 8], np.int32), max_new_tokens=4,
                           deadline_ms=20000)
            assert f2.wait(timeout=60) == "rejected"
            assert "brownout shed" in f2.detail
            # survivors drain once the seeds release their lanes
            sts = [h.wait(timeout=300) for h in seeds + [f0, f3]]
            assert sts == ["completed"] * 5
            snap = fe.metrics.snapshot()
            assert snap["brownout_shed"] == 2
            assert fe._replicas[0].engine.cache.pages_in_use == 0
        finally:
            fe.close()

    def test_clamp_stage_bounds_new_budgets(self, gpt):
        pol = BrownoutPolicy(shed_at=0.3, clamp_at=0.45, reject_at=5.0,
                             sustain_evals=1, clamp_max_new_tokens=3)
        fe = ServingFrontend(gpt, replicas=1, queue_cap=8,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=4,
                                                num_pages=64,
                                                eos_id=-1),
                             brownout=pol)
        try:
            seeds = _immune_seeds(fe, 4, budget=48)   # pressure 4/8
            # 0.5 >= clamp_at: this submission's budget is clamped (the
            # degraded-service stage: a short answer instead of none)
            h = fe.submit(np.array([3, 5, 9], np.int32),
                          max_new_tokens=32)
            sts = [x.wait(timeout=300) for x in seeds + [h]]
            assert sts == ["completed"] * 5
            assert fe.metrics.snapshot()["brownout_clamped"] == 1
            assert len(h.tokens) == 3            # clamped budget
        finally:
            fe.close()

    def test_reject_stage_returns_unavailable(self, gpt):
        pol = BrownoutPolicy(shed_at=0.3, clamp_at=0.4, reject_at=0.55,
                             sustain_evals=1)
        fe = ServingFrontend(gpt, replicas=1, queue_cap=8,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=4,
                                                num_pages=64,
                                                eos_id=-1),
                             brownout=pol)
        try:
            seeds = _immune_seeds(fe, 4, budget=48)   # pressure 4/8
            h1 = fe.submit(np.array([3, 5], np.int32),
                           max_new_tokens=32)     # 0.5 < 0.55: clamped,
            #                                       placed → live 5
            h2 = fe.submit(np.array([4, 6], np.int32), max_new_tokens=4)
            # 5/8 = 0.625 >= reject_at: rejected outright
            assert h2.status == "rejected"
            assert h2.error_cls is errors.UnavailableError
            assert "brownout stage 3" in h2.detail
            assert fe.brownout.stage == BROWNOUT_REJECT
            assert fe.health()["brownout_stage"] == BROWNOUT_REJECT
            assert fe.metrics.snapshot()["brownout_rejected"] == 1
            sts = [x.wait(timeout=300) for x in seeds + [h1]]
            assert sts == ["completed"] * 5
        finally:
            fe.close()


# =============================================================================
# Router requeue keeps the ORIGINAL deadline (regression fix)
# =============================================================================
class TestFailoverDeadlineAnchor:
    def _warm_fleet(self, gpt, **fe_kwargs):
        """Both replicas' traces compiled, so the timed scenario below
        is decode-speed, not XLA-compile, bound."""
        fe = ServingFrontend(gpt, replicas=2, queue_cap=8,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=4,
                                                eos_id=-1),
                             **fe_kwargs)
        warm = [fe.submit(np.array([3, 5, 9], np.int32),
                          max_new_tokens=4) for _ in range(2)]
        for w in warm:
            assert w.wait(timeout=300) == "completed"
        return fe

    def test_requeued_request_keeps_submit_time_deadline(self, gpt):
        """A failed-over request's deadline is the handle's absolute
        submit-time SLO: requeue must not grant a fresh budget.  Steps
        are chaos-slowed to ~20 ms so a 60-token budget cannot finish
        inside the 1 s window: the CORRECT implementation misses close
        to the original deadline; a recomputed-from-requeue deadline
        would give the retry a fresh 1 s window — time enough to
        COMPLETE (and to finish far past the original SLO)."""
        deadline_ms = 1000.0
        fe = self._warm_fleet(gpt, snapshot_interval=4)
        try:
            plan = ChaosPlan([Fault("engine.step", at=1, action="delay",
                                    delay_s=0.02, count=10 ** 6)])
            with chaos.running(plan):
                t0 = time.monotonic()
                h = fe.submit(np.array([3, 5, 9], np.int32),
                              max_new_tokens=60,
                              deadline_ms=deadline_ms)
                time.sleep(0.4)
                fe.inject_failure("replica-0", at_step=1)
                assert h.wait(timeout=60) == "deadline_miss"
                elapsed_ms = (time.monotonic() - t0) * 1e3
            # anchored to submit time: terminal close to the ORIGINAL
            # deadline, not ~0.4 s + a fresh 1 s window
            assert elapsed_ms < deadline_ms + 300.0
            assert h.error_cls is errors.DeadlineExceededError
            # the handle carried tokens from before the kill — it WAS
            # decoding, this was a mid-flight failover expiry
            assert h.retried or h.num_tokens > 0
        finally:
            fe.close()

    def test_expired_before_failover_is_deadline_miss_not_retry(self,
                                                                gpt):
        """A request whose deadline already passed is never requeued by
        a replica death — it terminates deadline_miss exactly once."""
        fe = self._warm_fleet(gpt)
        try:
            plan = ChaosPlan([Fault("engine.step", at=1, action="delay",
                                    delay_s=0.02, count=10 ** 6)])
            with chaos.running(plan):
                h = fe.submit(np.array([3, 5], np.int32),
                              max_new_tokens=60, deadline_ms=250.0)
                time.sleep(0.35)            # deadline passes mid-decode
                fe.inject_failure("replica-0", at_step=1)
                assert h.wait(timeout=60) == "deadline_miss"
            assert not h.retried                 # never requeued
            assert h.resumed_from is None
        finally:
            fe.close()

    def test_pick_with_retry_respects_deadline_budget(self, gpt):
        """Placement backoff never sleeps past the request's remaining
        deadline (remaining = original submit-time SLO - now)."""
        from paddle_tpu.serving.router import Replica, Router

        r = Router()
        dead_rep = Replica("r0", engine=None)
        r.add(dead_rep)
        r.mark_suspect(dead_rep)   # recoverable → would normally retry
        t0 = time.monotonic()
        got = r.pick_with_retry(attempts=10, backoff_s=0.2,
                                deadline=t0 + 0.05)
        assert got is None
        assert time.monotonic() - t0 < 0.2


# =============================================================================
# Randomized chaos soak (slow)
# =============================================================================
@pytest.mark.slow
class TestChaosSoak:
    def test_randomized_soak_all_terminal_zero_leak(self, gpt):
        for seed in (101, 202):
            plan = ChaosPlan.randomized(
                seed, replica_ids=("replica-0", "replica-1"), kills=1,
                stragglers=2, alloc_denials=2, step_window=(3, 40))
            fe = ServingFrontend(gpt, replicas=2, queue_cap=48,
                                 engine_kwargs=ENGINE_KW,
                                 snapshot_interval=4)
            try:
                rng = np.random.RandomState(seed)
                prompts = [rng.randint(1, VOCAB, (int(p),)).astype(
                    np.int32) for p in rng.randint(2, 10, 24)]
                gaps = rng.exponential(0.01, len(prompts))
                with chaos.running(plan):
                    handles = []
                    for g, p in zip(gaps, prompts):
                        time.sleep(float(g))
                        handles.append(fe.submit(p, max_new_tokens=10))
                    statuses = [h.wait(timeout=600) for h in handles]
                # every request reaches exactly one terminal status
                assert all(
                    s in ("completed", "rejected", "failed")
                    for s in statuses), Counter(statuses)
                # completed streams byte-identical to greedy reference
                for p, h in zip(prompts, handles):
                    if h.status == "completed":
                        np.testing.assert_array_equal(
                            h.tokens, _reference(gpt, p, 10))
                for rep in fe._replicas:
                    if rep.state != DEAD:
                        assert rep.engine.cache.pages_in_use == 0
            finally:
                fe.close()
