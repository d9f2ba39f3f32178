"""paddle_tpu.serving — continuous batching over a paged KV cache.

Acceptance anchors (ISSUE 1):
- the ragged paged-attention Pallas kernel (interpret mode on CPU)
  matches dense attention within 1e-3 for ragged lengths;
- the scheduler completes 64 staggered-arrival requests with mixed
  prompt lengths with NO page leak (pages-in-use returns to 0 after
  drain) and produces token-identical output to the sequential
  text.generation.generate greedy path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas_ops.paged_attention import (
    paged_attention_kernel, paged_attention_xla,
    ragged_paged_attention_kernel, ragged_paged_attention_xla)
from paddle_tpu.serving import PagedKVCache, Request, Scheduler, ServingEngine
from paddle_tpu.text.generation import generate, make_gpt_paged_decode_step
from paddle_tpu.text.models import GPTModel

VOCAB, HID, LAYERS, HEADS = 50, 32, 2, 2


@pytest.fixture(scope="module")
def gpt(shared_gpt_small):
    # session-shared model (conftest): identical seed/dims to
    # what this module built privately — the serving programs
    # compile once for the whole suite instead of per module
    return shared_gpt_small


def _dense_ref(q, k_pages, v_pages, page_tables, seq_lens):
    """Numpy dense attention over the gathered pages (no online softmax);
    the pools arrive as stored, [N, P, H*D]."""
    q, kp, vp = map(np.asarray, (q, k_pages, v_pages))
    pt, sl = np.asarray(page_tables), np.asarray(seq_lens)
    B, H, D = q.shape
    ps = kp.shape[1]
    out = np.zeros((B, H, D), np.float32)
    for b in range(B):
        L = int(sl[b])
        if L == 0:
            continue
        k = kp[pt[b]].reshape(-1, H, D)[:L]
        v = vp[pt[b]].reshape(-1, H, D)[:L]
        s = np.einsum("hd,shd->hs", q[b], k) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[b] = np.einsum("hs,shd->hd", p, v)
    return out


class TestPagedAttentionKernel:
    def _case(self, B=4, H=2, D=16, ps=4, M=6, N=16, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(B, H, D).astype(np.float32))
        # pools as the engine stores them: heads x head_dim in one row
        kp = jnp.asarray(rng.randn(N, ps, H * D).astype(np.float32))
        vp = jnp.asarray(rng.randn(N, ps, H * D).astype(np.float32))
        pt = jnp.asarray(rng.randint(1, N, (B, M)).astype(np.int32))
        # ragged lengths: empty, mid-page, page-aligned, full
        sl = jnp.asarray(np.array([0, 7, ps * 2, M * ps], np.int32))[:B]
        return q, kp, vp, pt, sl

    def test_kernel_matches_dense_ragged(self):
        """The acceptance bar: interpret-mode kernel vs dense, 1e-3."""
        args = self._case()
        out = paged_attention_kernel(*args, interpret=True)
        np.testing.assert_allclose(np.asarray(out), _dense_ref(*args),
                                   rtol=1e-3, atol=1e-3)

    def test_xla_reference_matches_dense(self):
        args = self._case(seed=1)
        out = paged_attention_xla(*args)
        np.testing.assert_allclose(np.asarray(out), _dense_ref(*args),
                                   rtol=1e-3, atol=1e-3)

    def test_kernel_under_jit(self):
        args = self._case(seed=2)
        out = jax.jit(paged_attention_kernel)(*args)
        np.testing.assert_allclose(np.asarray(out), _dense_ref(*args),
                                   rtol=1e-3, atol=1e-3)

    def test_empty_sequence_outputs_zero(self):
        q, kp, vp, pt, sl = self._case()
        out = np.asarray(paged_attention_kernel(
            q, kp, vp, pt, jnp.zeros_like(sl)))
        np.testing.assert_array_equal(out, 0.0)

    @pytest.mark.parametrize("per_head", [False, True],
                             ids=["stored", "per_head"])
    def test_ops_attention_entry(self, per_head):
        """The Tensor-level route through ops/attention.py takes the
        pools as stored ([N, P, H*D]) or split per head ([N, P, H, D])."""
        from paddle_tpu.ops.attention import paged_attention

        args = self._case(seed=3)
        given = list(args)
        if per_head:
            H, D = args[0].shape[1:]
            given[1] = args[1].reshape(*args[1].shape[:2], H, D)
            given[2] = args[2].reshape(*args[2].shape[:2], H, D)
        out = paged_attention(*(paddle.to_tensor(np.asarray(a))
                                for a in given))
        np.testing.assert_allclose(out.numpy(), _dense_ref(*args),
                                   rtol=1e-3, atol=1e-3)

    def test_per_head_pool_is_refused_by_the_kernel(self):
        """The kernels read the STORED layout only: a [N, P, H, D] pool
        is an error, not a silent whole-pool relayout."""
        q, kp, vp, pt, sl = self._case()
        H, D = q.shape[1:]
        with pytest.raises(ValueError, match="heads\\*head_dim"):
            paged_attention_kernel(q, kp.reshape(*kp.shape[:2], H, D),
                                   vp.reshape(*vp.shape[:2], H, D), pt, sl,
                                   interpret=True)

    # the shapes that matter on the chip: the lane-aligned one, and
    # GPT-2's (heads no multiple of 8, head_dim half a lane tile: two
    # heads share each 128-lane window of the page's fused row)
    @pytest.mark.parametrize("H,D", [(8, 128), (12, 64)],
                             ids=["h8d128", "h12d64"])
    @pytest.mark.parametrize("form", ["decode", "mixed"])
    def test_kernel_matches_xla_at_model_shapes(self, H, D, form):
        rng = np.random.RandomState(5)
        N, ps, M = 24, 16, 8
        kp = jnp.asarray(rng.randn(N, ps, H * D).astype(np.float32))
        vp = jnp.asarray(rng.randn(N, ps, H * D).astype(np.float32))
        if form == "decode":
            B = 4
            q = jnp.asarray(rng.randn(B, H, D).astype(np.float32) * 0.5)
            pt = jnp.asarray(rng.randint(1, N, (B, M)).astype(np.int32))
            sl = jnp.asarray(np.array([0, 7, 2 * ps, M * ps], np.int32))
            out = paged_attention_kernel(q, kp, vp, pt, sl, interpret=True)
            ref = paged_attention_xla(q, kp, vp, pt, sl)
        else:
            # a mixed step: two decode lanes (one live row each) beside
            # two 64-row prefill chunks, the second with a ragged tail
            # (37 real rows, the rest padding with row_len 0)
            G, Qb = 4, 64
            q = jnp.asarray(rng.randn(G, Qb, H, D).astype(np.float32)
                            * 0.5)
            pt = jnp.asarray(rng.randint(1, N, (G, M)).astype(np.int32))
            rl = np.zeros((G, Qb), np.int32)
            rl[0, 0] = 101
            rl[1, 0] = 16
            rl[2, :] = np.arange(33, 33 + Qb)
            rl[3, :37] = np.arange(1, 38)
            rl = jnp.asarray(rl)
            out = ragged_paged_attention_kernel(q, kp, vp, pt, rl,
                                                interpret=True)
            ref = ragged_paged_attention_xla(q, kp, vp, pt, rl)
            assert np.abs(np.asarray(out)[3, 37:]).max() == 0.0
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    # ISSUE 29: a lane's rows are computed up to the row block covering
    # its last live row, and one grid step covers several pages
    def _mixed_step(self, H, D, M):
        """A 64-row mixed step as the engine dispatches it: a decode lane
        (one live row), a full chunk, a tail chunk of 16 and an idle lane
        (every row_len 0), each over a table of M pages whose entries past
        the lane's live pages are the trash page 0."""
        rng = np.random.RandomState(11)
        G, Qb, N, ps = 4, 64, 40, 16
        q = rng.randn(G, Qb, H, D).astype(np.float32) * 0.5
        kf = rng.randn(N, ps, H, D).astype(np.float32)
        vf = rng.randn(N, ps, H, D).astype(np.float32)
        rl = np.zeros((G, Qb), np.int32)
        rl[0, 0] = M * ps - 5
        rl[1, :] = np.arange(ps + 3, ps + 3 + Qb)
        rl[2, :16] = np.arange(2 * ps + 1, 2 * ps + 17)
        pt = rng.randint(1, N, (G, M)).astype(np.int32)
        live = -(-rl.max(axis=1) // ps)
        pt[np.arange(M)[None, :] >= live[:, None]] = 0
        return q, kf, vf, pt, rl

    @pytest.mark.parametrize("H,D", [(8, 128), (12, 64)],
                             ids=["h8d128", "h12d64"])
    @pytest.mark.parametrize("form", ["native", "int8_fused",
                                      "int8_prescaled", "stats"])
    def test_mixed_step_computes_live_row_blocks_only(self, H, D, form):
        """Every paged form at Qb = 64 over a table no multiple of the
        pages-a-step dim (12 -> two groups of 8), the trash page poisoned:
        the output is finite, equals the reference over a clean pool, and
        the rows past each lane's extent are exact zeros."""
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            ragged_paged_attention_stats_kernel,
            ragged_paged_attention_stats_xla)

        q, kf, vf, pt, rl = self._mixed_step(H, D, M=12)
        N, ps = kf.shape[:2]
        fused = (N, ps, H * D)
        scales = clean_scales = ()
        if form.startswith("int8"):
            ks = (np.abs(kf).max(axis=(1, 3)) / 127 + 1e-9
                  ).astype(np.float32)
            vs = (np.abs(vf).max(axis=(1, 3)) / 127 + 1e-9
                  ).astype(np.float32)
            kf = np.clip(np.round(kf / ks[:, None, :, None]), -127,
                         127).astype(np.int8)
            vf = np.clip(np.round(vf / vs[:, None, :, None]), -127,
                         127).astype(np.int8)
            clean_scales = (jnp.asarray(ks), jnp.asarray(vs))
            ks, vs = ks.copy(), vs.copy()
            ks[0], vs[0] = np.nan, np.nan       # int8 has no NaN: scales
            scales = (jnp.asarray(ks), jnp.asarray(vs))
            kf[0], vf[0] = 127, 127
            poisoned = (kf, vf)
        else:
            poisoned = (kf.copy(), vf.copy())
            poisoned[0][0] = np.nan
            poisoned[1][0] = np.nan
            kf[0], vf[0] = 0.0, 0.0
        clean = [jnp.asarray(a.reshape(fused)) for a in (kf, vf)]
        dirty = [jnp.asarray(a.reshape(fused)) for a in poisoned]
        q, pt, rl = jnp.asarray(q), jnp.asarray(pt), jnp.asarray(rl)
        if form == "stats":
            # a shard that owns every other live page; the caller aims
            # the entries it does not own at its trash page
            ok = (np.arange(pt.shape[1])[None, :] % 2 == 0) \
                & (np.asarray(pt) != 0)
            pt = jnp.asarray(np.where(ok, np.asarray(pt), 0))
            ok = jnp.asarray(ok.astype(np.int32))
            out, lse = ragged_paged_attention_stats_kernel(
                q, *dirty, pt, rl, ok, interpret=True)
            ref, ref_lse = ragged_paged_attention_stats_xla(
                q, *clean, pt, rl, ok)
            np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                                       rtol=2e-5, atol=2e-5)
        else:
            out = ragged_paged_attention_kernel(
                q, *dirty, pt, rl, *scales, interpret=True,
                fused_dequant=form != "int8_prescaled")
            ref = ragged_paged_attention_xla(q, *clean, pt, rl,
                                             *clean_scales)
        out = np.asarray(out)
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5,
                                   atol=2e-5)
        assert np.abs(out[0, 1:]).max() == 0.0      # decode lane's pad rows
        assert np.abs(out[2, 16:]).max() == 0.0     # past the tail chunk
        assert np.abs(out[3]).max() == 0.0          # the idle lane

    @pytest.mark.parametrize("n", [2, 4, 8])
    @pytest.mark.parametrize("rows", [1, 8], ids=["decode", "rows8"])
    def test_lanes_of_one_n_and_n_plus_one_live_pages(self, n, rows):
        """n pages a grid step over a table of 2n + 1 entries (padded to
        3n), lanes whose live pages end inside the first group, exactly
        on it and one page into the second — at the row buckets that have
        no second row block (the decode entry and Qb = 8)."""
        rng = np.random.RandomState(13)
        H, D, ps, N, M = 12, 64, 16, 24, 2 * n + 1
        kp = rng.randn(N, ps, H * D).astype(np.float32)
        vp = rng.randn(N, ps, H * D).astype(np.float32)
        pt = rng.randint(1, N, (3, M)).astype(np.int32)
        pages = np.array([1, n, n + 1])
        pt[np.arange(M)[None, :] >= pages[:, None]] = 0
        dirty_k, dirty_v = kp.copy(), vp.copy()
        dirty_k[0], dirty_v[0] = np.nan, np.nan
        kp[0], vp[0] = 0.0, 0.0
        rl = np.zeros((3, rows), np.int32)
        rl[:, 0] = pages * ps - np.array([9, 0, 15])
        if rows > 1:
            rl[1, :] = rl[1, 0] - np.arange(rows)[::-1]
        q = jnp.asarray(rng.randn(3, rows, H, D).astype(np.float32) * 0.5)
        pt, rl = jnp.asarray(pt), jnp.asarray(rl)
        if rows == 1:
            out = paged_attention_kernel(
                q[:, 0], jnp.asarray(dirty_k), jnp.asarray(dirty_v), pt,
                rl[:, 0], interpret=True, pages_per_step=n)[:, None]
        else:
            out = ragged_paged_attention_kernel(
                q, jnp.asarray(dirty_k), jnp.asarray(dirty_v), pt, rl,
                interpret=True, pages_per_step=n)
        ref = ragged_paged_attention_xla(q, jnp.asarray(kp),
                                         jnp.asarray(vp), pt, rl)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("live,rows,skipped", [
        (1, 64, 56), (8, 64, 56), (9, 64, 0), (16, 64, 0), (64, 64, 0),
        (0, 64, 56), (1, 1, 0), (0, 8, 0), (1, 16, 8), (3, 20, 16)])
    def test_rows_skipped_is_the_kernels_row_block_rule(self, live, rows,
                                                        skipped):
        from paddle_tpu.ops.pallas_ops.paged_attention import (
            ragged_rows_skipped)

        assert ragged_rows_skipped(live, rows) == skipped


class TestPagedKVCache:
    def test_alloc_free_roundtrip_and_stats(self):
        c = PagedKVCache(num_pages=9, page_size=4, pages_per_seq=4)
        assert c.free_pages == 8            # page 0 reserved
        assert c.allocate("a", 10)          # 3 pages
        assert c.allocate("b", 4)           # 1 page
        assert c.pages_in_use == 4
        assert c.allocate("a", 11)          # still 3 pages — no growth
        assert c.pages_in_use == 4
        assert c.allocate("a", 13)          # grows to 4
        assert c.pages_in_use == 5
        st = c.stats({"a": 13, "b": 3})
        assert st["peak_pages_in_use"] == 5
        assert st["internal_fragmentation_slots"] == (16 - 13) + (4 - 3)
        assert c.free("a") == 4
        assert c.free("b") == 1
        assert c.pages_in_use == 0
        assert c.total_allocs == c.total_frees == 5

    def test_exhaustion_is_all_or_nothing(self):
        c = PagedKVCache(num_pages=4, page_size=2, pages_per_seq=4)
        assert c.allocate("a", 4)           # 2 of 3 pages
        free_before = c.free_pages
        assert not c.allocate("b", 4)       # needs 2, only 1 free
        assert c.free_pages == free_before  # rollback: nothing taken
        assert c.seq_pages("b") == 0

    def test_per_seq_limit(self):
        c = PagedKVCache(num_pages=32, page_size=2, pages_per_seq=2)
        assert not c.allocate("a", 5)       # 3 pages > pages_per_seq

    def test_trash_page_never_allocated(self):
        c = PagedKVCache(num_pages=5, page_size=2, pages_per_seq=4)
        c.allocate("a", 8)                  # all 4 allocatable pages
        assert 0 not in c.page_table_row("a")[:4]
        row = c.page_table_row("a")
        assert row.shape == (4,)

    def test_page_table_row_pads_with_trash(self):
        c = PagedKVCache(num_pages=8, page_size=2, pages_per_seq=5)
        c.allocate("a", 3)
        row = c.page_table_row("a")
        assert (row[2:] == 0).all()


class TestScheduler:
    def _sched(self, num_pages=9, page_size=4, pages_per_seq=8,
               max_batch=4):
        cache = PagedKVCache(num_pages, page_size, pages_per_seq)
        return Scheduler(cache, max_batch)

    def test_fifo_admission_respects_slots_and_pages(self):
        s = self._sched(num_pages=5, page_size=4, pages_per_seq=4)
        for i in range(3):
            s.add(Request(prompt=np.arange(1, 9), request_id=f"r{i}"))
        admitted = s.admit()
        # 8-token prompts need 2 pages each; 4 allocatable -> 2 admitted
        assert [q.seq_id for q in admitted] == ["r0", "r1"]
        assert s.queue_depth() == 1

    def test_preemption_evicts_youngest_and_requeues_front(self):
        s = self._sched(num_pages=5, page_size=4, pages_per_seq=4)
        s.add(Request(prompt=np.arange(1, 9), request_id="old"))
        s.add(Request(prompt=np.arange(1, 9), request_id="young"))
        s.admit()
        old, young = s.running
        old.pos = 8                         # next write needs a 3rd page
        preempted = s.ensure_decode_pages()
        assert [p.seq_id for p in preempted] == ["young"]
        assert s.waiting[0].request_id == "young"
        assert young.pos == 0 and young.generated == []
        assert s.cache.seq_pages("old") == 3

    def test_victim_not_reallocated_within_same_pass(self):
        # regression: a victim preempted mid-pass is still in the loop's
        # snapshot; it must not get pages allocated while waiting
        s = self._sched(num_pages=5, page_size=4, pages_per_seq=4)
        s.add(Request(prompt=np.arange(1, 9), request_id="a"))
        s.add(Request(prompt=np.arange(1, 9), request_id="b"))
        s.admit()
        a, b = s.running
        a.pos = 8                           # forces b's eviction
        s.ensure_decode_pages()
        assert s.cache.seq_pages("b") == 0  # evicted seq holds nothing
        assert s.cache.seq_pages("a") == 3
        assert s.cache.pages_in_use == 3

    def test_cache_too_small_raises(self):
        s = self._sched(num_pages=3, page_size=2, pages_per_seq=8,
                        max_batch=1)
        s.add(Request(prompt=np.arange(1, 5), request_id="big"))
        s.admit()
        s.running[0].pos = 4                # needs 3 pages, only 2 exist
        with pytest.raises(RuntimeError, match="KV cache exhausted"):
            s.ensure_decode_pages()

    def test_bucket_is_smallest_cover(self):
        s = self._sched(max_batch=8)
        assert s.bucket_sizes == [1, 2, 4, 8]
        assert s.bucket() == 1              # empty running set
        s.running = [object()] * 3
        assert s.bucket() == 4


def _generate_ref(gpt, prompt, T, end_id=0):
    want, _ = generate(gpt, prompt[None, :], max_new_tokens=T, end_id=end_id)
    want = want.numpy()[0]
    if (want == end_id).any():
        want = want[: int(np.argmax(want == end_id)) + 1]
    return want


class TestServingEngine:
    @pytest.mark.slow
    def test_64_staggered_requests_match_generate_no_page_leak(self, gpt):
        """The acceptance scenario: 64 requests with mixed prompt lengths
        arriving over time; greedy output token-identical to the
        sequential generate path, pages-in-use 0 after drain.

        Demoted to ``slow`` in PR 11 (suite health): the tier-1 run
        carries the strictly-wider twin —
        tests/test_serving_async.py 64-staggered-Poisson pins the SAME
        64-request byte-identity vs generate() across sync, pipelined
        AND fused modes plus forced preemption; this PR-1-era
        sync-drive variant adds only the staggered-submission shape on
        top and stays in the slow tier."""
        rng = np.random.RandomState(7)
        n = 64
        # mixed lengths drawn from a small set so the reference
        # generate() calls can be batched per (P, T) — 4 compiles, not 64
        lens = [1, 4, 9, 16]
        plens = [lens[i % len(lens)] for i in range(n)]
        budgets = [6] * n
        prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                   for p in plens]
        eng = ServingEngine(gpt, page_size=4, num_pages=49,
                            max_batch_size=8, eos_id=0)
        # staggered arrivals: a few requests join between engine steps
        ids = []
        submitted = 0
        while submitted < n or eng.scheduler.has_work():
            for _ in range(3):
                if submitted < n:
                    ids.append(eng.add_request(
                        prompts[submitted],
                        max_new_tokens=budgets[submitted]))
                    submitted += 1
            eng.step()
        outs = dict(eng.outputs)
        assert len(outs) == n
        assert eng.cache.pages_in_use == 0          # no page leak
        assert eng.cache.total_allocs == eng.cache.total_frees

        # reference: batched sequential generate per (prompt_len, budget)
        groups = {}
        for i in range(n):
            groups.setdefault((plens[i], budgets[i]), []).append(i)
        for (P, T), members in groups.items():
            batch = np.stack([prompts[i] for i in members])
            want, _ = generate(gpt, batch, max_new_tokens=T, end_id=0)
            want = want.numpy()
            for row, i in enumerate(members):
                w = want[row]
                if (w == 0).any():
                    w = w[: int(np.argmax(w == 0)) + 1]
                np.testing.assert_array_equal(outs[ids[i]], w)

    @pytest.mark.slow
    def test_preemption_preserves_greedy_output(self, gpt):
        """A cache too small for the whole batch forces recompute
        preemption; deterministic greedy output must be unchanged.

        Demoted to ``slow`` in PR 11 (suite health): tier-1 keeps the
        preemption byte-identity pinned through
        tests/test_serving_async.py (forced preemption, pipelined ==
        sync == generate) and tests/test_prefix_cache.py (preemption
        under page pressure replays byte-identical over shared pages —
        a strictly harder variant of this scenario)."""
        rng = np.random.RandomState(8)
        plens = (6, 6, 5, 5, 4, 4)      # 3 (P, T) groups for batched refs
        prompts = [rng.randint(1, VOCAB, (p,)).astype(np.int32)
                   for p in plens]
        eng = ServingEngine(gpt, page_size=4, num_pages=11,
                            max_batch_size=6, eos_id=0)
        ids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        outs = eng.drain()
        assert eng.scheduler.num_preemptions > 0    # the point of the test
        assert eng.cache.pages_in_use == 0
        for P in set(plens):
            members = [i for i, p in enumerate(plens) if p == P]
            want, _ = generate(gpt, np.stack([prompts[i] for i in members]),
                               max_new_tokens=6, end_id=0)
            want = want.numpy()
            for row, i in enumerate(members):
                w = want[row]
                if (w == 0).any():
                    w = w[: int(np.argmax(w == 0)) + 1]
                np.testing.assert_array_equal(outs[ids[i]], w)

    def test_decode_retraces_only_on_bucket_change(self, gpt):
        """Admissions/retirements within a bucket reuse the compiled
        decode step; only bucket growth compiles a new one."""
        rng = np.random.RandomState(9)
        eng = ServingEngine(gpt, page_size=4, max_batch_size=4, eos_id=0)
        sizes = set()
        orig = eng._decode_jit

        def spy(tokens, pos, tables, kv):
            sizes.add(int(tokens.shape[0]))
            return orig(tokens, pos, tables, kv)

        eng._decode_jit = spy
        for p in (3, 5, 2, 4, 6):
            eng.add_request(rng.randint(1, VOCAB, (p,)).astype(np.int32),
                            max_new_tokens=3)
        eng.drain()
        assert sizes <= {1, 2, 4}                   # buckets, not raw counts

    def test_single_token_prompt_and_metrics(self, gpt):
        eng = ServingEngine(gpt, page_size=4, max_batch_size=2, eos_id=0)
        rid = eng.add_request(np.array([3], np.int32), max_new_tokens=4)
        outs = eng.drain()
        np.testing.assert_array_equal(
            outs[rid], _generate_ref(gpt, np.array([3], np.int32), 4))
        snap = eng.metrics.snapshot()
        assert snap["requests_completed"] == 1
        assert snap["tokens_generated"] == len(outs[rid])
        assert snap["mean_ttft_ms"] > 0
        from paddle_tpu.framework.monitor import stat_get
        assert stat_get("serving.requests_completed") >= 1

    def test_overlong_request_rejected(self, gpt):
        eng = ServingEngine(gpt, max_batch_size=2)   # max_seq_len = 64
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.add_request(np.ones(60, np.int32), max_new_tokens=10)

    def test_duplicate_request_id_rejected(self, gpt):
        # regression: a duplicate id would alias two sequences onto one
        # page table (shared KV writes, double free)
        eng = ServingEngine(gpt, page_size=4, max_batch_size=2, eos_id=0)
        eng.add_request(np.array([3, 4], np.int32), max_new_tokens=4,
                        request_id="dup")
        with pytest.raises(ValueError, match="in flight"):
            eng.add_request(np.array([5], np.int32), max_new_tokens=2,
                            request_id="dup")
        eng.drain()
        # consumed output frees the id for reuse
        eng.add_request(np.array([5], np.int32), max_new_tokens=2,
                        request_id="dup")
        eng.drain()

    def test_never_fitting_request_rejected_up_front(self, gpt):
        # regression: a request that cannot fit even running alone used
        # to sit in the admission queue forever (step() no-ops, drain()
        # spins to max_steps) — reject loudly at add_request
        eng = ServingEngine(gpt, page_size=4, num_pages=4,
                            max_batch_size=2)        # 3 allocatable pages
        with pytest.raises(ValueError, match="KV pages"):
            eng.add_request(np.ones(20, np.int32), max_new_tokens=10)

    def test_drain_takes_ownership_and_occupancy_counts_final_step(
            self, gpt):
        eng = ServingEngine(gpt, page_size=4, max_batch_size=2, eos_id=0)
        rid = eng.add_request(np.array([7, 3], np.int32), max_new_tokens=1)
        outs = eng.drain()
        # drain hands the outputs over; the engine store is bounded
        assert rid in outs and eng.outputs == {}
        assert eng.take_output(rid) is None
        # the only decode step ran fully occupied even though its
        # sequence retired within it (regression: occupancy was 0.0)
        assert eng.metrics.snapshot()["mean_batch_occupancy"] == \
            pytest.approx(1.0)

    def test_paged_step_matches_dense_step_logits(self, gpt):
        """Layer parity: the paged decode step's logits equal the dense
        ring-cache step's at every position."""
        from paddle_tpu.text.generation import make_gpt_decode_step

        rng = np.random.RandomState(10)
        B, S, ps, M = 2, 10, 4, 4
        ids = rng.randint(0, VOCAB, (B, S)).astype(np.int32)
        dense_step, dense_init = make_gpt_decode_step(gpt, max_len=S + 1)
        paged_step, init_pages = make_gpt_paged_decode_step(
            gpt, page_size=ps, pages_per_seq=M)
        kv = init_pages(1 + B * M)
        tables = jnp.asarray(
            np.arange(1, 1 + B * M, dtype=np.int32).reshape(B, M))
        dstate = dense_init(B)
        for t in range(S):
            tok = jnp.asarray(ids[:, t])
            pos = jnp.full((B,), t, jnp.int32)
            d_logits, dstate = dense_step(tok, dstate)
            p_logits, kv = paged_step(tok, pos, tables, kv)
            np.testing.assert_allclose(np.asarray(p_logits),
                                       np.asarray(d_logits),
                                       rtol=2e-4, atol=2e-4)


class TestServingConfigEntry:
    def test_config_enable_serving_builds_engine(self, gpt):
        from paddle_tpu.inference import Config
        from paddle_tpu.serving import create_serving_engine

        cfg = Config()
        assert not cfg.serving_enabled()
        cfg.enable_serving(max_batch_size=2, page_size=4, num_pages=17)
        eng = create_serving_engine(gpt, cfg)
        assert eng.page_size == 4
        assert eng.scheduler.max_batch_size == 2
        assert cfg.summary()["serving"]["page_size"] == 4
        rid = eng.add_request(np.array([5, 9], np.int32), max_new_tokens=3)
        outs = eng.drain()
        np.testing.assert_array_equal(
            outs[rid], _generate_ref(gpt, np.array([5, 9], np.int32), 3))

    def test_disabled_config_rejected(self, gpt):
        from paddle_tpu.inference import Config
        from paddle_tpu.serving import create_serving_engine

        with pytest.raises(ValueError, match="serving disabled"):
            create_serving_engine(gpt, Config())
