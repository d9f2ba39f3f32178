"""The engine's own spans and the work counted where it is dispatched
(ISSUE 25).

- the flat children of ``serving/step`` (admit, ensure_pages, plan_rows,
  ragged_step, consume) tile it: every one a DIRECT child, none
  overlapping, their sum inside the parent; ``serving/fetch_tokens``
  hangs under whichever of consume / admit called it;
- ``ctx_tokens`` / ``attn_pairs`` / ``rows_computed`` — span args and
  ``serving.ragged.*`` counters — equal a brute-force count over the
  arrays each dispatch actually handed the device, across steps that mix
  chunk, decode and barrier-idle lanes;
- ``serving.queue_wait_ms`` observes once per admitted request and starts
  at the frontend's ``submit_time``.
"""
import time

import numpy as np
import pytest

import jax

from paddle_tpu import profiler
from paddle_tpu.serving import ServingEngine, ServingFrontend
from paddle_tpu.serving.metrics import stat_registry

VOCAB = 50
STEP_CHILDREN = {"serving/admit", "serving/ensure_pages",
                 "serving/plan_rows", "serving/ragged_step",
                 "serving/consume"}


@pytest.fixture(scope="module")
def gpt(shared_gpt_small):
    return shared_gpt_small


def _mixed_engine(gpt, **kw):
    base = dict(page_size=4, max_batch_size=4, prefill_chunk=4, eos_id=-1)
    base.update(kw)
    return ServingEngine(gpt, **base)


def _spans_by_name():
    out = {}
    for sp in profiler.get_spans():
        out.setdefault(sp.name, []).append(sp)
    return out


def test_flat_children_tile_the_step(gpt):
    rng = np.random.RandomState(5)
    profiler.enable_tracing()
    try:
        eng = _mixed_engine(gpt)
        for n in (3, 9, 5, 2, 11):      # five requests on four lanes
            eng.add_request(rng.randint(1, VOCAB, (n,)).astype(np.int32),
                            max_new_tokens=6)
        eng.drain()
        spans = _spans_by_name()
    finally:
        profiler.disable_tracing()
    steps = {sp.span_id: sp for sp in spans["serving/step"]}
    assert STEP_CHILDREN <= set(spans)
    kids = {sid: [] for sid in steps}
    for name in STEP_CHILDREN:
        for sp in spans[name]:
            assert sp.parent_id in steps, (name, "is not a direct child")
            kids[sp.parent_id].append(sp)
    for sid, step in steps.items():
        ordered = sorted(kids[sid], key=lambda s: s.start_ns)
        assert sum(s.duration_ns for s in ordered) <= step.duration_ns
        for a, b in zip(ordered, ordered[1:]):
            assert a.end_ns <= b.start_ns, (a, b)
        for s in ordered:
            assert step.start_ns <= s.start_ns and s.end_ns <= step.end_ns
    # the token fetch is a child of the phase that waited for it
    phases = {sp.span_id: sp.name for n in ("serving/consume",
                                            "serving/admit")
              for sp in spans[n]}
    callers = {phases[sp.parent_id] for sp in spans["serving/fetch_tokens"]}
    assert callers == {"serving/consume", "serving/admit"}
    # args known only at the end of a phase are on its span
    assert sum(sp.args["admitted"] for sp in spans["serving/admit"]) == 5
    assert any(sp.args["collapsed"] > 0 for sp in spans["serving/admit"])
    assert sum(sp.args["emitted"] for sp in spans["serving/consume"]) \
        + sum(sp.args["collapsed"] for sp in spans["serving/admit"]) == 30
    assert all(sp.args["preempted"] == 0
               for sp in spans["serving/ensure_pages"])


def _brute_force(state_pos, rows_pos, row_valid, advance, bound):
    """(rows computed, ctx tokens, attention pairs) of one dispatch, from
    the arrays the device was handed: row (b, q) sits at the lane's device
    position where the lane advances (q == 0), else at rows_pos; it carries
    a token where its position is under its valid length; it reads every
    position up to its own."""
    B, Q = rows_pos.shape
    ctx = pairs = 0
    for b in bound:
        reach = 0
        for q in range(Q):
            p = int(state_pos[b]) if (q == 0 and advance[b] > 0) \
                else int(rows_pos[b, q])
            if p < int(row_valid[b, q]):
                pairs += p + 1
                reach = max(reach, p + 1)
        ctx += reach
    return B * Q, ctx, pairs


def test_work_counts_equal_a_brute_force_count(gpt):
    """A decoding lane, then a same-batch pair sharing a two-page prefix
    (the second idles behind the first's unwritten pages), then a long
    prompt: steps mix decode, chunk and idle lanes."""
    rng = np.random.RandomState(7)
    eng = _mixed_engine(gpt, prefix_cache=True)
    seen = []
    real = eng._ragged_jit

    def spy(tokens, pos, tables, rows_tok, rows_pos, row_valid, advance,
            kv):
        bound = [i for i, s in enumerate(eng._lanes) if s is not None]
        seen.append(_brute_force(*(np.asarray(jax.device_get(a)) for a in
                                   (pos, rows_pos, row_valid, advance)),
                                 bound))
        return real(tokens, pos, tables, rows_tok, rows_pos, row_valid,
                    advance, kv)

    eng._ragged_jit = spy
    shared = rng.randint(1, VOCAB, (8,)).astype(np.int32)
    profiler.enable_tracing()
    try:
        eng.add_request(rng.randint(1, VOCAB, (3,)).astype(np.int32),
                        max_new_tokens=12)
        eng.step()
        eng.step()
        for tail in (5, 3):
            eng.add_request(np.concatenate(
                [shared, rng.randint(1, VOCAB, (tail,)).astype(np.int32)]),
                max_new_tokens=4)
        eng.add_request(rng.randint(1, VOCAB, (14,)).astype(np.int32),
                        max_new_tokens=3)
        eng.drain()
        spans = _spans_by_name()
    finally:
        profiler.disable_tracing()
    steps = sorted(spans["serving/ragged_step"], key=lambda s: s.start_ns)
    plans = sorted(spans["serving/plan_rows"], key=lambda s: s.start_ns)
    assert len(steps) == len(seen) == len(plans) > 6
    for sp, (rows, ctx, pairs) in zip(steps, seen):
        a = sp.args
        assert (a["bucket"] * a["rows"], a["ctx_tokens"],
                a["attn_pairs"]) == (rows, ctx, pairs), a
        assert a["attn_pairs"] >= a["ctx_tokens"] >= a["decode_rows"]
    # the drive really was mixed
    assert any(p.args["chunks"] and p.args["idle"] and s.args["decode_rows"]
               for p, s in zip(plans, steps))
    assert any(s.args["rows"] == 1 for s in steps)          # steady decode
    # the counters hold the same sums
    for key, i in (("rows_computed", 0), ("ctx_tokens", 1),
                   ("attn_pairs", 2)):
        assert stat_registry.get(f"serving.ragged.{key}").get() \
            == sum(s[i] for s in seen)
    snap = eng.metrics.snapshot()["ragged"]
    assert snap["rows_computed"] == sum(s[0] for s in seen)
    assert eng.cache.pages_in_use == 0


@pytest.mark.parametrize("chunk", [4, 16, 32])
def test_rows_skipped_follow_the_kernels_row_blocks(gpt, chunk):
    """``attn_rows_skipped`` — span arg and counter, from host state —
    against the rule the kernel branches on, applied to the row lengths
    each dispatch handed it: the bucket beyond a lane's last live row,
    rounded up to its row block (ISSUE 29).  A chunk of 4 fits the first
    block, so nothing is ever skipped there."""
    from paddle_tpu.ops.pallas_ops.paged_attention import ragged_rows_skipped

    rng = np.random.RandomState(17)
    eng = _mixed_engine(gpt, prefill_chunk=chunk, prefix_cache=True)
    seen = []
    real = eng._ragged_jit

    def spy(tokens, pos, tables, rows_tok, rows_pos, row_valid, advance,
            kv):
        valid = np.asarray(jax.device_get(row_valid))
        Q = valid.shape[1]
        ext = np.where(valid > 0, np.arange(1, Q + 1), 0).max(axis=1)
        seen.append(sum(ragged_rows_skipped(int(e), Q) for e in ext))
        return real(tokens, pos, tables, rows_tok, rows_pos, row_valid,
                    advance, kv)

    eng._ragged_jit = spy
    base = stat_registry.get("serving.ragged.attn_rows_skipped").get()
    shared = rng.randint(1, VOCAB, (8,)).astype(np.int32)
    profiler.enable_tracing()
    try:
        eng.add_request(rng.randint(1, VOCAB, (3,)).astype(np.int32),
                        max_new_tokens=10)
        eng.step()
        for tail in (5, 3):
            eng.add_request(np.concatenate(
                [shared, rng.randint(1, VOCAB, (tail,)).astype(np.int32)]),
                max_new_tokens=4)
        eng.add_request(rng.randint(1, VOCAB, (37,)).astype(np.int32),
                        max_new_tokens=3)
        eng.drain()
        steps = sorted(_spans_by_name()["serving/ragged_step"],
                       key=lambda s: s.start_ns)
    finally:
        profiler.disable_tracing()
    assert [s.args["attn_rows_skipped"] for s in steps] == seen
    assert stat_registry.get("serving.ragged.attn_rows_skipped").get() \
        - base == sum(seen)
    assert (sum(seen) > 0) == (chunk > 8)
    assert all(0 <= k <= s.args["bucket"] * s.args["rows"]
               for k, s in zip(seen, steps))


def test_queue_wait_counts_each_admission_from_arrival(gpt):
    rng = np.random.RandomState(9)
    eng = _mixed_engine(gpt, max_batch_size=2)
    hist = stat_registry.histogram("serving.queue_wait_ms")
    assert hist.count == 0
    t0 = time.monotonic()
    # one request that "arrived" a quarter of a second ago, three now:
    # two lanes, so two of them also wait for a retirement
    eng.add_request(rng.randint(1, VOCAB, (4,)).astype(np.int32),
                    max_new_tokens=3, arrival_time=t0 - 0.25)
    for _ in range(3):
        eng.add_request(rng.randint(1, VOCAB, (4,)).astype(np.int32),
                        max_new_tokens=3)
    eng.drain()
    assert hist.count == 4 \
        == stat_registry.get("serving.requests_admitted").get()
    snap = eng.metrics.snapshot()["queue_wait_ms"]
    assert snap["count"] == 4
    shot = hist.snapshot()
    assert shot["max"] >= 250.0 and shot["min"] < 250.0


def test_the_pump_hands_the_engine_the_submit_time(gpt):
    fe = ServingFrontend(gpt, replicas=1, engine_kwargs=dict(
        page_size=4, max_batch_size=2, eos_id=-1))
    try:
        seen = []
        eng = fe._replicas[0].engine
        real = eng.add_request

        def spy(*a, **kw):
            seen.append(kw.get("arrival_time"))
            return real(*a, **kw)

        eng.add_request = spy
        rng = np.random.RandomState(3)
        hs = [fe.submit(rng.randint(1, VOCAB, (5,)).astype(np.int32),
                        max_new_tokens=3) for _ in range(3)]
        for h in hs:
            assert h.wait(120.0) == "completed"
        assert seen == [h.submit_time for h in hs]
        hist = stat_registry.histogram("serving.queue_wait_ms")
        assert hist.count == 3
        # engine-side TTFT now starts where the frontend's does
        ttft = stat_registry.histogram("serving.ttft_ms")
        for h in hs:
            assert h.ttft_ms is not None
        assert ttft.count == 3
        assert ttft.snapshot()["max"] <= max(h.ttft_ms for h in hs) + 50.0
    finally:
        fe.close()
