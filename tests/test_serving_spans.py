"""The engine's own spans and the work counted where it is dispatched
(ISSUE 25).

- the flat children of ``serving/step`` (admit, ensure_pages, plan_rows,
  ragged_step, consume) tile it: every one a DIRECT child, none
  overlapping, their sum inside the parent; ``serving/fetch_tokens``
  hangs under consume, or under the ``serving/collapse`` of the phase
  that drained the pipeline;
- ``serving/admit`` holds, in order, the collapse, the scheduler's choice
  (``serving/schedule``) and one ``serving/admit_request`` per admitted
  sequence; a collapse opens exactly when an in-flight step is drained,
  and ``serving.pipeline_collapses`` counts it;
- a periodic checkpoint's landing holds one ``serving/snapshot_land``
  under its ``serving/snapshot``, its capture none;
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
    # the token fetch is a child of the phase that waited for it: the
    # consume, or the collapse an admission opened
    by_id = {sp.span_id: sp for n in ("serving/consume", "serving/admit",
                                      "serving/collapse")
             for sp in spans[n]}
    callers = set()
    for sp in spans["serving/fetch_tokens"]:
        parent = by_id[sp.parent_id]
        if parent.name == "serving/collapse":
            parent = by_id[parent.parent_id]
        callers.add(parent.name)
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
    """``serving.ragged.attn_rows_skipped`` — the counter's growth over
    each step, from host state — against the rule the kernel branches on,
    applied to the row lengths each dispatch handed it: the bucket beyond
    a lane's last live row, rounded up to its row block (ISSUE 29).  A
    chunk of 4 fits the first block, so nothing is ever skipped there."""
    from paddle_tpu.ops.pallas_ops.paged_attention import ragged_rows_skipped

    rng = np.random.RandomState(17)
    eng = _mixed_engine(gpt, prefill_chunk=chunk, prefix_cache=True)
    counter = stat_registry.get("serving.ragged.attn_rows_skipped")
    seen, read = [], []
    real = eng._ragged_jit

    def spy(tokens, pos, tables, rows_tok, rows_pos, row_valid, advance,
            kv):
        valid = np.asarray(jax.device_get(row_valid))
        Q = valid.shape[1]
        ext = np.where(valid > 0, np.arange(1, Q + 1), 0).max(axis=1)
        seen.append(sum(ragged_rows_skipped(int(e), Q) for e in ext))
        # the counter as the previous step left it
        read.append(counter.get())
        return real(tokens, pos, tables, rows_tok, rows_pos, row_valid,
                    advance, kv)

    eng._ragged_jit = spy
    base = counter.get()
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
    read.append(counter.get())
    assert list(np.diff(read)) == seen
    assert read[0] == base and read[-1] - base == sum(seen)
    assert len(steps) == len(seen)
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


def _inside(outer, inner):
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


def test_admission_splits_into_schedule_and_one_span_a_request(gpt):
    """Seven prompts on four lanes, two of them sharing a two-page prefix:
    every ``serving/admit`` holds its collapse (when one drained a step),
    then ``serving/schedule``, then one ``serving/admit_request`` per
    sequence the scheduler returned — direct children, in that order, not
    overlapping, inside the parent — and each request's span carries what
    its admission did."""
    rng = np.random.RandomState(39)
    chunk = 4
    eng = _mixed_engine(gpt, prefill_chunk=chunk, prefix_cache=True)
    chosen = []
    real_admit = eng.scheduler.admit

    def spy_admit():
        out = real_admit()
        chosen.extend(out)
        return out

    eng.scheduler.admit = spy_admit
    shared = rng.randint(1, VOCAB, (8,)).astype(np.int32)
    prompts = [rng.randint(1, VOCAB, (n,)).astype(np.int32)
               for n in (3, 13, 6)]
    prompts += [np.concatenate([shared, rng.randint(1, VOCAB, (t,))
                                .astype(np.int32)]) for t in (2, 5)]
    profiler.enable_tracing()
    try:
        for p in prompts[:3]:
            eng.add_request(p, max_new_tokens=5)
        eng.step()
        eng.step()
        for p in prompts[3:] + [rng.randint(1, VOCAB, (n,))
                                .astype(np.int32) for n in (9, 2)]:
            eng.add_request(p, max_new_tokens=5)
        eng.drain()
        spans = _spans_by_name()
    finally:
        profiler.disable_tracing()
    admits = {sp.span_id: sp for sp in spans["serving/admit"]}
    kids = {sid: [] for sid in admits}
    for name in ("serving/collapse", "serving/schedule",
                 "serving/admit_request"):
        for sp in spans.get(name, ()):
            if sp.parent_id in admits:
                kids[sp.parent_id].append(sp)
            else:
                # only a collapse may open outside admission
                assert name == "serving/collapse", name
    for sp in spans["serving/schedule"] + spans["serving/admit_request"]:
        assert sp.parent_id in admits, sp
    requests, collapsed = [], 0
    for sid, admit in sorted(admits.items(),
                             key=lambda kv: kv[1].start_ns):
        ordered = sorted(kids[sid], key=lambda s: s.start_ns)
        names = [s.name for s in ordered]
        # tokens emitted mean a step was drained; a drained step may
        # also emit nothing (its lanes retired a step before)
        lead = names[:1] == ["serving/collapse"]
        assert lead or not admit.args["collapsed"], names
        collapsed += lead
        assert names[lead:] == ["serving/schedule"] \
            + ["serving/admit_request"] * admit.args["admitted"], names
        for a, b in zip(ordered, ordered[1:]):
            assert a.end_ns <= b.start_ns, (a, b)
        assert all(_inside(admit, s) for s in ordered)
        sched = next(s for s in ordered if s.name == "serving/schedule")
        assert sched.args["admitted"] == admit.args["admitted"]
        requests += [s for s in ordered if s.name == "serving/admit_request"]
    assert len(requests) == len(chosen) == 7 and collapsed
    for sp, seq in zip(requests, chosen):
        n = seq.request.prompt.size - 1
        todo = n - min(seq.cached_tokens, n)
        assert sp.args == {"resume": 0,
                           "cached_tokens": int(seq.cached_tokens),
                           "chunks": -(-todo // chunk)}, sp.args
    # the shared prefix was hit, and one plan was long enough to split
    assert any(sp.args["cached_tokens"] for sp in requests)
    assert max(sp.args["chunks"] for sp in requests) > 1
    assert eng.cache.pages_in_use == 0


@pytest.mark.parametrize("sync_mode", [False, True])
def test_a_collapse_opens_exactly_when_a_step_is_drained(gpt, sync_mode):
    """Brute force: every call of ``_sync_pending`` with the steps in
    flight it found.  Admissions behind retirements and an abort collapse
    the pipelined engine; a ``sync_mode`` engine never has a step in
    flight to drain."""
    rng = np.random.RandomState(40)
    eng = _mixed_engine(gpt, max_batch_size=2, sync_mode=sync_mode)
    found = []
    real = eng._sync_pending

    def spy():
        found.append(len(eng._pending))
        return real()

    eng._sync_pending = spy
    counter = stat_registry.get("serving.pipeline_collapses")
    base = counter.get()
    profiler.enable_tracing()
    try:
        rids = [eng.add_request(rng.randint(1, VOCAB, (n,)).astype(np.int32),
                                max_new_tokens=m)
                for n, m in ((3, 4), (5, 9), (4, 3), (6, 5), (2, 6))]
        for _ in range(4):
            eng.step()
        assert eng.abort(rids[1])
        eng.drain()
        spans = _spans_by_name().get("serving/collapse", [])
    finally:
        profiler.disable_tracing()
    drained = [k for k in found if k]
    assert [sp.args["steps"] for sp in sorted(
        spans, key=lambda s: s.start_ns)] == drained
    assert counter.get() - base == len(drained)
    if sync_mode:
        assert found and not drained
    else:
        assert len(drained) >= 2
    assert eng.cache.pages_in_use == 0


def test_a_landing_holds_one_snapshot_land_and_a_capture_none(
        gpt, monkeypatch):
    """A replica checkpointing every two tokens: each ``serving/snapshot``
    around a capture (told by the capture call inside it) has no
    ``serving/snapshot_land``; every other one is a landing and holds
    exactly one, a direct child covering the landing's work."""
    calls = {"capture": [], "land": []}
    for kind, attr in (("capture", "capture_snapshot"),
                       ("land", "land_snapshot")):
        real = getattr(ServingEngine, attr)

        def tap(eng, *a, _real=real, _kind=kind, **kw):
            calls[_kind].append(time.perf_counter_ns())
            return _real(eng, *a, **kw)

        monkeypatch.setattr(ServingEngine, attr, tap)
    rng = np.random.RandomState(41)
    profiler.enable_tracing()
    try:
        fe = ServingFrontend(gpt, replicas=1, snapshot_interval=2,
                             engine_kwargs=dict(page_size=4,
                                                max_batch_size=2,
                                                eos_id=-1))
        try:
            hs = [fe.submit(rng.randint(1, VOCAB, (5,)).astype(np.int32),
                            max_new_tokens=9) for _ in range(3)]
            for h in hs:
                assert h.wait(120.0) == "completed"
        finally:
            fe.close()
        spans = _spans_by_name()
    finally:
        profiler.disable_tracing()
    snaps = spans["serving/snapshot"]
    lands = spans.get("serving/snapshot_land", [])
    by_parent = {}
    for sp in lands:
        by_parent.setdefault(sp.parent_id, []).append(sp)
    captures = [s for s in snaps if any(s.start_ns <= t <= s.end_ns
                                        for t in calls["capture"])]
    assert len(captures) == len(calls["capture"]) > 0
    landings = [s for s in snaps if s not in captures]
    assert len(landings) >= len(calls["land"]) > 0
    for s in captures:
        assert s.span_id not in by_parent
    for s in landings:
        (land,) = by_parent[s.span_id]
        assert _inside(s, land)
    assert len(lands) == len(landings)
    for t in calls["land"]:
        assert any(sp.start_ns <= t <= sp.end_ns for sp in lands)
