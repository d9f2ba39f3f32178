"""The per-layer metrics that read the program's own spans (ISSUE 25):
device idle time split by the span it lies under, and the paged-attention
kernel's share of its roofline from the counts on `serving/ragged_step` —
on hand-made traces and spans, then on a small trace recorded on the v5e
(sample_spans.xplane.pb, by record_sample_spans.py)."""
import os
import types

import pytest

from preset_tree import ROOT
from perfbench.harness import flops_paged as P
from perfbench.harness import spans as S
from perfbench.harness import trace as T
from perfbench.harness.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
SAMPLE = os.path.join(HERE, "sample_spans.xplane.pb")
M = Manifest(ROOT)
PEAKS = M.peaks("TPU v5 lite")
IDLE = M.reducer("idle_under_span")
ROOFLINE = M.reducer("paged_attn_roofline")
CONFIG = {"n_embd": 768, "n_head": 12, "n_layer": 12,
          "serving": {"enable_serving": {}}}


def span(name, start, end, thread="pump", **stats):
    return S.Span(name, thread, start, end, stats)


def ctx_of(trace, spans, config=CONFIG, monkeypatch=None):
    """What run.py hands a reducer, with the spans given directly."""
    ctx = {"trace": trace, "obs": {"trace_path": "synthetic"},
           "job": types.SimpleNamespace(config=config),
           "peaks": lambda: PEAKS, "values": {}}
    monkeypatch.setattr(S, "load", lambda path: tuple(spans))
    return ctx


def _engine_trace():
    """Ten seconds; the device runs 0-2, 3-4, 6-9, so it idles 2-3, 4-6,
    9-10: 4 of 10 s.  Two engine steps (1-5 and 5.5-9.5): the first
    admits 1.5-2.5 and consumes 3.5-4.5, the second consumes 8.5-9.4."""
    ops = [("fusion", 0.0, 2.0), ("ragged_fn custom-call f32[4]", 3.0, 1.0),
           ("fusion", 6.0, 3.0)]
    trace = T.Trace({0: {"ops": ops, "modules": [
        ("jit_ragged_fn(7)", 0.0, 2.0), ("jit_ragged_fn(7)", 3.0, 1.0),
        ("jit_ragged_fn(7)", 6.0, 3.0), ("jit_other(1)", 6.0, 1.0)]}},
        [("perfbench/window", "main", 0.0, 10.0)])
    spans = [span("serving/step", 1.0, 5.0),
             span("serving/admit", 1.5, 2.5),
             span("serving/fetch_tokens", 1.6, 2.4),
             span("serving/consume", 3.5, 4.5),
             span("serving/step", 5.5, 9.5),
             span("serving/consume", 8.5, 9.4)]
    return trace, spans


def test_interval_helpers():
    assert S.overlap([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert S.seconds(S.overlap([(0, 2), (3, 5)], [(1, 4)])) == 2
    assert S.overlap([(0, 1)], [(1, 2)]) == []
    # what [(1, 4)] does not cover of the first list, inside 0-10
    assert S.overlap([(0, 2), (3, 5)], T.gaps([(1, 4)], 0, 10)) \
        == [(0, 1), (4, 5)]
    assert S.overlap([(0, 2)], T.gaps([], 0, 10)) == [(0, 2)]
    a = [span("x", 0.0, 2.0, "t1"), span("x", 1.0, 3.0, "t2"),
         span("y", 5.0, 6.0, "t1")]
    assert S.union(S.named(a, ["x"]), 0.5, 10) == [(0.5, 3.0)]


def test_idle_parts_sum_to_the_idle_share(monkeypatch):
    trace, spans = _engine_trace()
    # the window ends at the last recorded op (9.0), as trace_idle_share's
    assert trace.window() == (0.0, 9.0)
    ctx = ctx_of(trace, spans, monkeypatch=monkeypatch)
    admit = IDLE.reduce(ctx, inside=["serving/admit"])
    consume = IDLE.reduce(ctx, inside=["serving/consume"])
    dispatch = IDLE.reduce(ctx, inside=["serving/step"],
                           but_not=["serving/admit", "serving/consume"])
    outside = IDLE.reduce(ctx, outside="serving/step")
    # idle 2-3 and 4-6 of a 9-s window: the gap 2-3 straddles the end of
    # admit (2-2.5) and the step's own time (2.5-3); 4-6 straddles
    # consume (4-4.5), the step (4.5-5) and the pump (5-5.5), then the
    # second step's own time (5.5-6)
    assert admit == pytest.approx(100 * 0.5 / 9)
    assert consume == pytest.approx(100 * 0.5 / 9)
    assert dispatch == pytest.approx(100 * 1.5 / 9)
    assert outside == pytest.approx(100 * 0.5 / 9)
    assert admit + consume + dispatch + outside \
        == pytest.approx(100 * T.idle_share(trace))


def test_idle_is_averaged_over_the_chips(monkeypatch):
    trace = T.Trace({0: {"ops": [("a", 0.0, 4.0)], "modules": []},
                     1: {"ops": [("a", 0.0, 2.0)], "modules": []}},
                    [("perfbench/window", "main", 0.0, 4.0)])
    ctx = ctx_of(trace, [span("hapi/train_batch", 1.0, 3.0)],
                 monkeypatch=monkeypatch)
    under = IDLE.reduce(ctx, inside=["hapi/train_batch"])
    out = IDLE.reduce(ctx, outside="hapi/train_batch")
    assert under == pytest.approx(100 * (0 + 1.0) / 2 / 4)
    assert under + out == pytest.approx(100 * T.idle_share(trace))


def test_a_program_without_spans_reads_nothing(monkeypatch):
    trace, _ = _engine_trace()
    ctx = ctx_of(trace, [], monkeypatch=monkeypatch)
    assert IDLE.reduce(ctx, inside=["serving/admit"]) is None
    assert IDLE.reduce(ctx, outside="serving/step") is None
    assert ROOFLINE.reduce(ctx, span="serving/ragged_step",
                           pattern="custom-call", module="ragged_fn") is None
    # spans there, but none of this name in the slice: no idle under it
    ctx = ctx_of(trace, [span("serving/step", 1.0, 5.0)],
                 monkeypatch=monkeypatch)
    assert IDLE.reduce(ctx, inside=["serving/admit"]) == 0.0
    # no trace at all (an untraced run)
    assert IDLE.reduce(dict(ctx, trace=None), outside="serving/step") is None
    assert S.of({"obs": {}, "trace": trace}) == ()


def test_paged_attention_counts():
    flops, nbytes = P.paged_attention(1000, 100, 10, heads=12, head_dim=64,
                                      item_bytes=4)
    assert flops == 4 * 12 * 64 * 1000
    assert nbytes == (2 * 100 + 2 * 10) * 12 * 64 * 4
    assert P.kv_item_bytes(CONFIG) == 4
    int8 = {"serving": {"enable_serving": {"kv_cache_dtype": "int8"}}}
    assert P.kv_item_bytes(int8) == 1


def _least(pairs, ctx_tokens, rows):
    t_f = 4 * 12 * 64 * pairs / PEAKS["bf16_flops_per_s"]
    t_b = (2 * ctx_tokens + 2 * rows) * 12 * 64 * 4 / PEAKS["hbm_bytes_per_s"]
    return 12 * max(t_f, t_b)


def _roofline_ctx(monkeypatch, kernel_s, steps, programs=None):
    """`steps` ragged_step spans inside a 1-s window, each followed by one
    step program that spends `kernel_s` in the kernel."""
    n = len(steps)
    ops, modules, spans = [], [], []
    for i, stats in enumerate(steps):
        t = 0.05 + 0.9 * i / n
        spans.append(span("serving/ragged_step", t, t + 1e-4, **stats))
        if programs is None or i < programs:
            modules.append(("jit_ragged_fn(3)", t + 0.001, 0.8 / n))
            ops.append(("ragged_fn custom-call f32[48,16,64,128]",
                        t + 0.002, kernel_s))
    ops.append(("fusion", 0.0, 0.001))
    trace = T.Trace({0: {"ops": ops, "modules": modules}},
                    [("perfbench/window", "main", 0.0, 1.0)])
    return ctx_of(trace, spans, monkeypatch=monkeypatch)


STEADY = dict(bucket=48, rows=1, decode_rows=48, prefill_rows=0,
              ctx_tokens=48 * 650, attn_pairs=48 * 650)
MIXED = dict(bucket=48, rows=64, decode_rows=45, prefill_rows=192,
             ctx_tokens=45 * 650 + 3 * 512, attn_pairs=45 * 650 + 3 * 30000)
ARGS = dict(span="serving/ragged_step", pattern="custom-call",
            module="ragged_fn")


def test_roofline_share_from_the_spans_own_counts(monkeypatch):
    steps = [STEADY, MIXED, STEADY, STEADY]
    least = sum(_least(s["attn_pairs"], s["ctx_tokens"],
                       s["decode_rows"] + s["prefill_rows"]) for s in steps)
    ctx = _roofline_ctx(monkeypatch, 0.06, steps)
    got = ROOFLINE.reduce(ctx, **ARGS)
    assert got == pytest.approx(100 * least / (4 * 0.06))
    assert 3 < got < 8          # ISSUE 25's arithmetic: bytes-bound, ~5%
    # both peaks can bind: with hundreds of rows per context token the
    # operations do
    heavy = dict(STEADY, attn_pairs=48 * 650 * 600)
    assert _least(heavy["attn_pairs"], heavy["ctx_tokens"], 48) \
        > _least(STEADY["attn_pairs"], STEADY["ctx_tokens"], 48)


@pytest.mark.parametrize("slack", [1.0, 1.5, 20.0])
def test_roofline_never_passes_100_when_the_kernel_takes_the_least_time(
        monkeypatch, slack):
    steps = [STEADY, MIXED, MIXED]
    each = max(_least(s["attn_pairs"], s["ctx_tokens"],
                      s["decode_rows"] + s["prefill_rows"]) for s in steps)
    got = ROOFLINE.reduce(_roofline_ctx(monkeypatch, each * slack, steps),
                          **ARGS)
    assert 0 < got <= 100.0 + 1e-9
    if slack == 1.0:
        assert got > 50


def test_roofline_scales_by_programs_over_spans(monkeypatch):
    """The host runs a step ahead: a span at the window's end whose
    program ran after it is scaled away, not counted as free work."""
    four = ROOFLINE.reduce(_roofline_ctx(monkeypatch, 0.06, [STEADY] * 4),
                           **ARGS)
    ahead = ROOFLINE.reduce(_roofline_ctx(monkeypatch, 0.06, [STEADY] * 4,
                                          programs=3), **ARGS)
    assert ahead == pytest.approx(four)


def test_the_new_metric_files_name_these_readers():
    for name in ("idle_admit_share", "idle_consume_share",
                 "idle_dispatch_share", "idle_outside_step_share"):
        for suffix in (".batch", ".chat"):
            spec = M.layer_metric(name + suffix)
            assert spec["reducer"] == "idle_under_span"
            assert spec["source"] == "program_span"
    for suffix in (".batch", ".chat"):
        spec = M.layer_metric("ragged_attn_roofline" + suffix)
        assert spec["reducer"] == "paged_attn_roofline"
        assert spec["args"] == ARGS and spec["unit"] == "%"
    wait = M.layer_metric("queue_wait_mean_ms.chat")
    assert wait["args"] == {"histogram": "serving.queue_wait_ms"}
    assert (wait["layer"], wait["moves"]) == ("frontend", "ttft_p90_ms")
    # the .chat twins wait with their cell
    listed = {m["name"] for m in M.data["per_layer"]}
    assert not any(n.endswith(".chat") for n in listed)
    assert {"ragged_attn_roofline.batch", "idle_fetch_share.train"} <= listed


# -- the recorded sample ------------------------------------------------------
needs_sample = pytest.mark.skipif(not os.path.isfile(SAMPLE),
                                  reason="sample_spans.xplane.pb not recorded")


@pytest.fixture(scope="module")
def sample():
    from record_sample_spans import CONFIG as cfg

    trace = T.load(SAMPLE)
    return {"trace": trace, "obs": {"trace_path": SAMPLE},
            "job": types.SimpleNamespace(config=cfg),
            "peaks": lambda: PEAKS, "values": {}}


@needs_sample
def test_sample_holds_the_programs_spans_with_their_stats(sample):
    from record_sample_spans import NEW_TOKENS, PROMPTS, TRAIN_STEPS

    assert os.path.getsize(SAMPLE) < 2 * 1024 * 1024
    spans = S.of(sample)
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert {"serving/step", "serving/admit", "serving/ensure_pages",
            "serving/plan_rows", "serving/ragged_step", "serving/consume",
            "serving/fetch_tokens", "hapi/train_batch",
            "hapi/train_batch/inputs", "hapi/train_batch/dispatch",
            "hapi/train_batch/fetch_loss",
            "hapi/train_batch/metrics"} <= set(by)
    assert len({s.thread for s in spans}) == 1
    assert len(by["hapi/train_batch"]) == TRAIN_STEPS
    steps = by["serving/ragged_step"]
    assert all(set(s.stats) == {"bucket", "rows", "decode_rows",
                                "prefill_rows", "ctx_tokens", "attn_pairs"}
               for s in steps)
    # every prompt position but the last rides a chunk, every new token
    # but a request's last is a decode row's output
    assert sum(s.stats["prefill_rows"] for s in steps) \
        >= sum(n - 1 for n in PROMPTS)
    assert sum(s.stats["decode_rows"] for s in steps) \
        >= len(PROMPTS) * (NEW_TOKENS - 1)
    assert sum(s.stats["admitted"] for s in by["serving/admit"]) \
        == len(PROMPTS)
    # the window ends at the last device op: only the tail of the last
    # train step (its dispatch returns, its loss arrives) lies after it
    lo, hi = sample["trace"].window()
    assert all(lo <= s.start for s in spans)
    assert {s.name for s in spans if s.start > hi} \
        <= {"hapi/train_batch/dispatch", "hapi/train_batch/metrics",
            "hapi/train_batch/fetch_loss"}


@needs_sample
def test_sample_idle_parts_sum_to_the_idle_share(sample):
    share = 100 * T.idle_share(sample["trace"])
    parts = [IDLE.reduce(sample, **M.layer_metric(n + ".batch")["args"])
             for n in ("idle_admit_share", "idle_consume_share",
                       "idle_dispatch_share")]
    outside = IDLE.reduce(sample, outside="serving/step")
    assert all(p is not None and p >= 0 for p in parts + [outside])
    assert sum(parts) + outside == pytest.approx(share, abs=1e-6)
    assert parts[1] > 0             # the engine waits for tokens somewhere
    train = [IDLE.reduce(sample, **M.layer_metric(n + ".train")["args"])
             for n in ("idle_dispatch_share", "idle_fetch_share",
                       "idle_outside_step_share")]
    assert sum(train) == pytest.approx(share, abs=1e-6)
    assert train[1] > 0             # ... and the trainer for its loss


@needs_sample
def test_sample_roofline_share_is_a_share(sample):
    spec = M.layer_metric("ragged_attn_roofline.batch")
    got = ROOFLINE.reduce(sample, **spec["args"])
    programs = T.module_durations(sample["trace"], spec["args"]["module"])
    steps = S.named(S.of(sample), [spec["args"]["span"]])
    assert len(programs) == len(steps) == 15
    # one kernel call per layer and step program (the pattern's other
    # custom-calls are aliases of no duration), all of them timed
    secs, _ = T.op_calls(sample["trace"], spec["args"]["pattern"])
    kernel, calls = T.op_calls(sample["trace"], "^ragged_fn custom-call")
    assert calls == 2 * len(programs)
    assert secs == pytest.approx(kernel, rel=1e-3)
    # a small model on a large chip sits far under its roofline
    assert 0.5 < got < 5
