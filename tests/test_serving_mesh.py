"""Mesh-sharded serving (ISSUE 19): one ServingEngine runs as ONE
shard_map program over a named ``(tp, sp)`` device mesh —
tensor-parallel head shards (each chip reads its head-shard of every
KV page at aggregate bandwidth) and sequence-parallel page shards
(one sequence's paged KV split across chips, per-shard partial
softmax stats merged in lse space, the serving twin of ring
attention's running-max/denominator exchange).

Acceptance anchors (docs/SERVING.md "Mesh-sharded replicas"):
- tp=2 / sp=2 / tp=2,sp=2 token streams are BYTE-IDENTICAL to the
  1-chip engine across native, int8_static, int8_dynamic and
  spec-decode workloads;
- double-drive determinism on a mesh engine;
- steady mesh decode stays ``jax.transfer_guard("disallow")``- and
  ``compile_budget(0, prefix="serving.")``-clean;
- the ``mesh_axes`` knob validates (typed InvalidArgumentError for
  every rejected composition) and surfaces in
  ``stats()["pipeline"]["mesh"]``;
- the ``serving.shard_sync`` chaos site drills the mesh failure
  domain (straggler shard = delayed step, failed exchange = replica
  crash);
- ``serving.shard.*`` metrics count mesh dispatches and cross-shard
  maintenance gathers/scatters;
- the router normalizes placement by ``mesh_size`` and reports chip
  capacity;
- the stats-form kernel (``paged_attention_ragged_stats`` contract)
  matches its exact XLA reference in interpret mode, f32 and int8;
- PagedKVCache reserves one trash page PER sp shard and keeps the
  leak invariant over ``allocatable_pages``.
"""
import numpy as np
import pytest

import jax

from paddle_tpu.framework.errors import (InternalError,
                                         InvalidArgumentError)
from paddle_tpu.profiler.jit_cost import compile_budget
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.kv_cache import PagedKVCache
from paddle_tpu.serving.metrics import stat_registry
from paddle_tpu.serving.router import Replica, Router
from paddle_tpu.testing import chaos
from paddle_tpu.testing.chaos import ChaosPlan, Fault

VOCAB = 50


@pytest.fixture(scope="module")
def gpt(shared_gpt_small):
    # session-shared model (conftest): mesh program sets are keyed per
    # (model, mesh_layout), so each mesh shape compiles once for the
    # whole module
    return shared_gpt_small


@pytest.fixture(scope="module")
def quant(gpt):
    from paddle_tpu.slim import export_serving_quant

    rng = np.random.RandomState(3)
    return export_serving_quant(
        gpt, calib_prompts=rng.randint(1, VOCAB, (4, 12)).astype(np.int32))


def _mixed_prompts(rng, lens=(3, 9, 5, 2)):
    return [rng.randint(1, VOCAB, (n,)).astype(np.int32) for n in lens]


def _drive(eng, prompts, budget=10):
    ids = [eng.add_request(p, max_new_tokens=budget) for p in prompts]
    outs = eng.drain()
    return [outs[rid] for rid in ids]


def _engines(gpt, axes, **kw):
    """(1-chip reference, mesh engine over ``axes``), same settings."""
    base = dict(page_size=4, max_batch_size=4, prefill_chunk=4, eos_id=0)
    base.update(kw)
    return (ServingEngine(gpt, **base),
            ServingEngine(gpt, mesh_axes=axes, **base))


@pytest.fixture(scope="module")
def native_ref(gpt):
    """One 1-chip reference stream shared by every NATIVE mesh-shape
    identity test (tp2 / tp2sp2 / chaos straggler): same prompts, same
    budget — the mesh arms differ only in sharding, so one reference
    drive serves them all."""
    prompts = _mixed_prompts(np.random.RandomState(0))
    eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                        prefill_chunk=4, eos_id=0)
    return prompts, _drive(eng, prompts)


# =============================================================================
# byte-identity vs the 1-chip engine
# =============================================================================
class TestByteIdentity:
    def test_tp2_matches_single_chip(self, gpt, native_ref):
        prompts, ref = native_ref
        mesh = ServingEngine(gpt, page_size=4, max_batch_size=4,
                             prefill_chunk=4, eos_id=0,
                             mesh_axes={"tp": 2})
        s0 = stat_registry.get("serving.shard.steps").get()
        got = _drive(mesh, prompts)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        # every mesh dispatch counted; topology gauges read live
        assert stat_registry.get("serving.shard.steps").get() > s0
        assert stat_registry.get("serving.shard.tp").get() == 2
        assert stat_registry.get("serving.shard.devices").get() == 2
        assert mesh.cache.pages_in_use == 0

    def test_sp2_long_prompt_matches_single_chip(self, gpt):
        """The scaled-down long-document path: a prompt spanning many
        pages, its KV page-sharded over sp=2 — each shard attends its
        own pages and the lse merge reassembles the exact context."""
        plain, mesh = _engines(gpt, {"sp": 2})
        rng = np.random.RandomState(1)
        # 24 and 33 tokens at page_size=4: 6-9 pages per sequence,
        # split across the two page shards
        prompts = [rng.randint(1, VOCAB, (n,)).astype(np.int32)
                   for n in (24, 33, 5)]
        for a, b in zip(_drive(plain, prompts, budget=12),
                        _drive(mesh, prompts, budget=12)):
            np.testing.assert_array_equal(a, b)
        assert mesh.stats()["pipeline"]["mesh"] == {
            "tp": 1, "sp": 2, "devices": 2}

    def test_tp2_sp2_matches_single_chip(self, gpt, native_ref):
        prompts, ref = native_ref
        mesh = ServingEngine(gpt, page_size=4, max_batch_size=4,
                             prefill_chunk=4, eos_id=0,
                             mesh_axes={"tp": 2, "sp": 2})
        for a, b in zip(ref, _drive(mesh, prompts)):
            np.testing.assert_array_equal(a, b)
        assert mesh.stats()["pipeline"]["mesh"]["devices"] == 4

    def test_int8_static_matches_single_chip(self, gpt, quant):
        plain, mesh = _engines(gpt, {"tp": 2, "sp": 2},
                               kv_cache_dtype="int8", quant_scales=quant)
        prompts = _mixed_prompts(np.random.RandomState(3))
        for a, b in zip(_drive(plain, prompts), _drive(mesh, prompts)):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.slow
    def test_int8_dynamic_matches_single_chip(self, gpt):
        # slow tier: a 3rd full program pair (~8s) whose sharding layout
        # is identical to the static arm's — the tier-1 int8 witness is
        # test_int8_static_matches_single_chip above
        plain, mesh = _engines(gpt, {"tp": 2}, kv_cache_dtype="int8")
        prompts = _mixed_prompts(np.random.RandomState(4))
        for a, b in zip(_drive(plain, prompts), _drive(mesh, prompts)):
            np.testing.assert_array_equal(a, b)

    def test_spec_decode_under_tp_matches_single_chip(self, gpt):
        """Spec-verify rows fold into the mesh ragged dispatch exactly
        as on one chip (native KV; the dynamic-int8 split verifier is
        rejected at construction instead)."""
        plain, mesh = _engines(gpt, {"tp": 2}, spec_decode=4)
        rng = np.random.RandomState(5)
        prompts = [np.tile(rng.randint(1, VOCAB, (p,)).astype(np.int32), 4)
                   for p in (2, 3)]
        ref = _drive(plain, prompts, budget=16)
        got = _drive(mesh, prompts, budget=16)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        assert mesh.stats()["spec"]["drafted"] > 0

    def test_double_drive_deterministic(self, gpt):
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            prefill_chunk=4, eos_id=0,
                            mesh_axes={"tp": 2, "sp": 2})
        prompts = _mixed_prompts(np.random.RandomState(6))
        first = _drive(eng, prompts, budget=8)
        second = _drive(eng, prompts, budget=8)
        for a, b in zip(first, second):
            np.testing.assert_array_equal(a, b)

    def test_snapshot_portable_across_mesh_shapes(self, gpt):
        """Warm failover for a dead mesh replica: a snapshot gathered
        off a tp=2,sp=2 pool restores on a 1-chip engine and the
        continuation is byte-identical to the uninterrupted stream."""
        base = dict(page_size=4, max_batch_size=4, prefill_chunk=4,
                    eos_id=0)
        rng = np.random.RandomState(7)
        prompt = rng.randint(1, VOCAB, (9,)).astype(np.int32)
        full = ServingEngine(gpt, mesh_axes={"tp": 2, "sp": 2}, **base)
        rid = full.add_request(prompt, max_new_tokens=10)
        expect = full.drain()[rid]

        mesh = ServingEngine(gpt, mesh_axes={"tp": 2, "sp": 2}, **base)
        g0 = stat_registry.get("serving.shard.page_gathers").get()
        rid = mesh.add_request(prompt, max_new_tokens=10)
        for _ in range(6):
            mesh.step()
        snap = mesh.snapshot(rid)
        assert snap is not None
        # the snapshot gather crossed the sharded pool
        assert stat_registry.get(
            "serving.shard.page_gathers").get() > g0
        mesh.abort(rid)
        mesh.drain()

        plain = ServingEngine(gpt, **base)
        rid2 = plain.restore(snap)
        got = plain.drain()[rid2]
        combined = np.concatenate([np.asarray(snap.generated, np.int64),
                                   np.asarray(got, np.int64)])
        if not np.array_equal(np.asarray(got, np.int64),
                              np.asarray(expect, np.int64)):
            np.testing.assert_array_equal(combined, expect)


# =============================================================================
# hot-path cleanliness
# =============================================================================
class TestSteadyStateClean:
    def test_steady_mesh_decode_transfer_and_retrace_clean(self, gpt):
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            prefill_chunk=4, eos_id=-1,
                            mesh_axes={"tp": 2, "sp": 2})
        rng = np.random.RandomState(8)
        for p in (3, 9, 5, 2):
            eng.add_request(rng.randint(1, VOCAB, (p,)).astype(np.int32),
                            max_new_tokens=32)
        for _ in range(6):                   # admit + drain every plan
            eng.step()
        assert not eng._prefill_plans
        with jax.transfer_guard("disallow"), \
                compile_budget(0, prefix="serving."):
            for _ in range(8):
                stats = eng.step()
                assert stats["bucket"] == 4
        eng.drain()


# =============================================================================
# knob validation + stats surface
# =============================================================================
class TestKnobValidation:
    BASE = dict(page_size=4, eos_id=0)

    def test_mesh_axes_must_be_dict(self, gpt):
        with pytest.raises(InvalidArgumentError, match="mesh_axes"):
            ServingEngine(gpt, mesh_axes=2, **self.BASE)

    def test_unknown_axis_rejected(self, gpt):
        with pytest.raises(InvalidArgumentError, match="mesh_axes"):
            ServingEngine(gpt, mesh_axes={"dp": 2}, **self.BASE)

    def test_axis_sizes_validate(self, gpt):
        with pytest.raises(InvalidArgumentError, match="mesh_axes"):
            ServingEngine(gpt, mesh_axes={"tp": 0}, **self.BASE)

    def test_tp_must_divide_heads(self, gpt):
        # shared_gpt_small has 2 heads
        with pytest.raises(InvalidArgumentError, match="head"):
            ServingEngine(gpt, mesh_axes={"tp": 3}, **self.BASE)

    def test_mesh_must_fit_devices(self, gpt):
        too_many = jax.device_count() * 2
        with pytest.raises(InvalidArgumentError, match="device"):
            ServingEngine(gpt, mesh_axes={"sp": too_many}, **self.BASE)

    def test_mesh_requires_ragged(self, gpt):
        with pytest.raises(InvalidArgumentError, match="ragged"):
            ServingEngine(gpt, mesh_axes={"tp": 2}, ragged=False,
                          **self.BASE)

    def test_mesh_spec_int8_dynamic_rejected(self, gpt):
        with pytest.raises(InvalidArgumentError, match="spec_decode"):
            ServingEngine(gpt, mesh_axes={"tp": 2}, spec_decode=4,
                          kv_cache_dtype="int8", **self.BASE)

    def test_explicit_num_pages_must_divide_sp(self, gpt):
        with pytest.raises(InvalidArgumentError, match="num_pages"):
            ServingEngine(gpt, mesh_axes={"sp": 2}, num_pages=31,
                          **self.BASE)

    def test_plain_engine_reports_no_mesh(self, gpt):
        eng = ServingEngine(gpt, **self.BASE)
        assert eng.stats()["pipeline"]["mesh"] is None

    def test_trivial_mesh_is_single_chip(self, gpt):
        # tp=1, sp=1 is a 1-chip layout: no mesh program, no mesh row
        eng = ServingEngine(gpt, mesh_axes={"tp": 1, "sp": 1},
                            **self.BASE)
        assert eng.stats()["pipeline"]["mesh"] is None


# =============================================================================
# chaos: the mesh failure domain
# =============================================================================
class TestShardSyncChaos:
    def test_straggler_shard_delays_but_stream_unchanged(self, gpt,
                                                         native_ref):
        prompts, ref = native_ref
        mesh = ServingEngine(gpt, page_size=4, max_batch_size=4,
                             prefill_chunk=4, eos_id=0,
                             mesh_axes={"tp": 2})
        plan = ChaosPlan([Fault("serving.shard_sync", at=2,
                                action="delay", delay_s=0.02)])
        with chaos.running(plan):
            got = _drive(mesh, prompts)
        assert plan.fired and plan.fired[0]["site"] == "serving.shard_sync"
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)

    def test_failed_exchange_is_a_replica_crash(self, gpt):
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            prefill_chunk=4, eos_id=0,
                            mesh_axes={"tp": 2})
        rng = np.random.RandomState(10)
        eng.add_request(rng.randint(1, VOCAB, (5,)).astype(np.int32),
                        max_new_tokens=8)
        plan = ChaosPlan([Fault("serving.shard_sync", at=1,
                                action="raise")])
        with chaos.running(plan):
            with pytest.raises(InternalError, match="chaos"):
                for _ in range(16):
                    eng.step()

    def test_site_never_fires_on_single_chip(self, gpt):
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4,
                            prefill_chunk=4, eos_id=0)
        rng = np.random.RandomState(11)
        eng.add_request(rng.randint(1, VOCAB, (3,)).astype(np.int32),
                        max_new_tokens=4)
        plan = ChaosPlan([Fault("serving.shard_sync", at=1,
                                action="raise")])
        with chaos.running(plan):
            eng.drain()                      # no mesh, no shard site
        assert not plan.fired


# =============================================================================
# router: chips are the capacity unit
# =============================================================================
class TestRouterMeshSize:
    def test_mesh_size_defaults_from_engine(self, gpt):
        eng = ServingEngine(gpt, page_size=4, eos_id=0,
                            mesh_axes={"tp": 2, "sp": 2})
        rep = Replica("r0", eng)
        assert rep.mesh_size == 4
        assert Replica("r1", object()).mesh_size == 1
        assert rep.status()["mesh_size"] == 4

    def test_mesh_size_validates(self):
        with pytest.raises(InvalidArgumentError, match="mesh_size"):
            Replica("r0", object(), mesh_size=0)

    def test_pick_normalizes_outstanding_by_chips(self):
        router = Router()
        big = Replica("big", object(), mesh_size=4)
        small = Replica("small", object(), mesh_size=1)
        router.add(big)
        router.add(small)
        # equal RAW backlog: the 4-chip replica drains 4x faster, so
        # per-chip load 25 < 100 and it takes the next request
        router.charge(big, 100)
        router.charge(small, 100)
        assert router.pick() is big
        # 4x the backlog equalizes per-chip load; ties break by id
        router.charge(big, 300)
        assert router.pick() is big          # "big" < "small"
        router.charge(big, 1)
        assert router.pick() is small

    def test_healthz_reports_chips(self):
        router = Router()
        router.add(Replica("r0", object(), mesh_size=4))
        router.add(Replica("r1", object(), mesh_size=1))
        hz = router.healthz()
        assert hz["total_chips"] == 5 and hz["healthy_chips"] == 5
        router.mark_dead(router.get("r0"), "test")
        hz = router.healthz()
        assert hz["total_chips"] == 5 and hz["healthy_chips"] == 1


# =============================================================================
# stats-form kernel parity (the sp shard's attention primitive)
# =============================================================================
class TestStatsKernelParity:
    def _case(self, rng, quantized, H=3, D=20):
        import jax.numpy as jnp

        # pools as a shard stores them: [pages, P, H*D] at its local H
        G, Qb, N, P, M = 2, 2, 6, 4, 3
        q = jnp.asarray(rng.randn(G, Qb, H, D).astype(np.float32))
        if quantized:
            kp = jnp.asarray(
                rng.randint(-127, 128, (N, P, H * D)).astype(np.int8))
            vp = jnp.asarray(
                rng.randint(-127, 128, (N, P, H * D)).astype(np.int8))
            # per-page-per-head scale rows, [N, H] fp32
            ks = jnp.asarray((rng.rand(N, H) * 0.05 + 1e-3
                              ).astype(np.float32))
            vs = jnp.asarray((rng.rand(N, H) * 0.05 + 1e-3
                              ).astype(np.float32))
        else:
            kp = jnp.asarray(rng.randn(N, P, H * D).astype(np.float32))
            vp = jnp.asarray(rng.randn(N, P, H * D).astype(np.float32))
            ks = vs = None
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 5, 0]], np.int32))
        row_lens = jnp.asarray(
            np.array([[11, 12], [6, 7]], np.int32))
        # shard ownership mask: group 0 owns its first two table
        # entries, group 1 only its first — the masked-out pages are
        # what the OTHER shard's partial stats would cover
        page_ok = jnp.asarray(np.array([[1, 1, 0], [1, 0, 0]], np.int32))
        return q, kp, vp, pt, row_lens, page_ok, ks, vs

    # heads x head_dim: a row that is no multiple of anything, the
    # lane-aligned shape, and GPT-2's (12 x 64; its tp2 shard is 6 x 64)
    @pytest.mark.parametrize("H,D", [(3, 20), (8, 128), (12, 64), (6, 64)],
                             ids=["h3d20", "h8d128", "h12d64", "h6d64"])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["f32", "int8"])
    def test_kernel_matches_xla_reference(self, quantized, H, D):
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            ragged_paged_attention_stats_kernel,
            ragged_paged_attention_stats_xla)

        rng = np.random.RandomState(12)
        q, kp, vp, pt, rl, ok, ks, vs = self._case(rng, quantized, H, D)
        o, lse = ragged_paged_attention_stats_kernel(
            q, kp, vp, pt, rl, ok, ks, vs, interpret=True)
        ro, rlse = ragged_paged_attention_stats_xla(
            q, kp, vp, pt, rl, ok, ks, vs)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                                   rtol=2e-5, atol=2e-5)


# =============================================================================
# kv cache: per-shard reserved trash pages
# =============================================================================
class TestReservedPages:
    def test_reserved_pages_excluded_from_allocation(self):
        cache = PagedKVCache(num_pages=16, page_size=4, pages_per_seq=4,
                             reserved_pages=(0, 8))
        assert cache.reserved_pages == (0, 8)
        assert cache.allocatable_pages == 14
        seen = set()
        i = 0
        while cache.free_pages:
            assert cache.allocate(f"s{i}", 4)          # one page each
            seen.update(cache.seq_page_ids(f"s{i}"))
            i += 1
        assert 0 not in seen and 8 not in seen
        assert len(seen) == 14
        assert not cache.allocate("overflow", 4)       # all-or-nothing

    def test_leak_invariant_over_allocatable(self):
        cache = PagedKVCache(num_pages=8, page_size=4, pages_per_seq=4,
                             reserved_pages=(0, 4))
        assert cache.allocate("s", 10)                 # 3 pages
        assert (cache.pages_in_use + cache.pages_cached
                + cache.free_pages == cache.allocatable_pages)
        cache.free("s")
        assert cache.free_pages == cache.allocatable_pages == 6
        assert cache.stats()["num_pages"] == 6

    def test_share_rejects_reserved_ids(self):
        cache = PagedKVCache(num_pages=8, page_size=4, pages_per_seq=4,
                             reserved_pages=(0, 4))
        with pytest.raises(InvalidArgumentError, match="reserved"):
            cache.share("s", [4])

    def test_all_pages_reserved_rejected(self):
        with pytest.raises(InvalidArgumentError):
            PagedKVCache(num_pages=2, page_size=4, pages_per_seq=1,
                         reserved_pages=(0, 1))
