"""Percentile, spread and failed-request arithmetic; the scoring of the
load generator's records."""
import math

import pytest

import preset_tree  # noqa: F401 — puts the repo root on sys.path
from perfbench.harness import score, stats


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (95, 10), (100, 10),
                                    (10, 1), (1, 1)])
def test_nearest_rank_percentile(q, want):
    assert stats.percentile(range(1, 11), q) == want


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 90) is None
    assert stats.tail_with_failures([], 0, 90, 60000.0) is None


@pytest.mark.parametrize("n_failed,want", [(0, 90.0), (1, 91.0),
                                           (10, 100.0), (11, 60000.0)])
def test_failed_requests_sort_beyond_every_percentile(n_failed, want):
    lat = [float(i) for i in range(1, 101)]
    # n_failed of the 100 requests failed: they replace the fastest ones
    got = stats.tail_with_failures(lat[n_failed:], n_failed, 90, 60000.0)
    assert got == want


def test_a_failure_never_reads_as_a_short_latency():
    assert stats.tail_with_failures([1.0], 9, 90, 60000.0) == 60000.0
    assert stats.percentile([1.0, stats.FAILED], 100) == math.inf


@pytest.mark.parametrize("n,q,want", [(100, 90, 10), (101, 90, 10),
                                      (20, 95, 1), (0, 90, 0)])
def test_samples_beyond_the_rank(n, q, want):
    assert stats.samples_beyond(n, q) == want


def test_spread_is_the_drivers():
    vals = [100, 101, 102, 103, 104, 105]
    # statistics.quantiles(n=4) on six values: q1 = 100.75, q3 = 104.25
    assert stats.spread(vals) == pytest.approx(3.5 / 102.5)


def _rec(rid, phase, due, sent, times, budget=None, status="completed",
         http=200, error=None):
    return {"id": rid, "phase": phase, "due": due, "sent": sent,
            "first": times[0] if times else None, "token_times": times,
            "tokens": [1] * len(times), "http": http, "status": status,
            "error": error, "budget": len(times) if budget is None
            else budget, "end": times[-1] if times else sent}


def test_open_loop_ttft_runs_from_the_due_time():
    recs = [_rec(f"m{i}", "measured", 10.0 + i, 10.0 + i + 0.004,
                 [10.0 + i + 0.1 * (i + 1), 10.0 + i + 0.1 * (i + 1) + 0.02])
            for i in range(10)]
    recs.append(_rec("r0", "ramp", 1.0, 1.5, [2.0, 2.5]))
    out = score.score_open_loop(recs, 60.0)
    assert out["attempted"] == 10 and out["failed"] == 0
    assert out["ttft_p90_ms"] == pytest.approx(900.0)
    assert out["itl_p95_ms"] == pytest.approx(20.0)
    assert out["n_gaps"] == 10
    # the ramp request's 500 ms lateness is the generator's p99
    assert out["late_p99_ms"] == pytest.approx(500.0)


@pytest.mark.parametrize("kw", [
    {"status": "failed"}, {"http": 429}, {"error": "timed out"},
    {"budget": 5}], ids=["status", "refused", "error", "short"])
def test_each_way_to_fail(kw):
    assert score.request_failed(_rec("m0", "measured", 0, 0, [1, 2], **kw))
    assert not score.request_failed(_rec("m0", "measured", 0, 0, [1, 2]))


def test_open_loop_failures_take_the_tail():
    good = [_rec(f"m{i}", "measured", i, i, [i + 0.1, i + 0.2])
            for i in range(8)]
    bad = [_rec("m8", "measured", 8, 8, [], budget=4, status=None,
                error="refused"),
           _rec("m9", "measured", 9, 9, [9.1], budget=3)]
    out = score.score_open_loop(good + bad, 60.0)
    assert out["attempted"] == 10 and out["failed"] == 2
    assert out["ttft_p90_ms"] == 60000.0
    assert out["itl_p95_ms"] == 60000.0


def test_closed_loop_counts_tokens_inside_the_window_only():
    recs = [_rec("c0.0", "closed", None, 0.5, [0.9, 1.1, 1.2]),
            _rec("c1.0", "closed", None, 1.5, [1.9, 2.9, 3.1], budget=3),
            _rec("c2.0", "closed", None, 2.5, [2.8], budget=4,
                 status="aborted"),
            _rec("c3.0", "closed", None, 1.2, [], budget=4, status=None,
                 error="reset")]
    out = score.score_closed_loop(recs, 1.0, 3.0)
    # 1.1, 1.2, 1.9, 2.9, 2.8 fall in [1, 3)
    assert out["tokens_in_window"] == 5
    assert out["out_tok_s"] == pytest.approx(2.5)
    assert out["attempted"] == 3          # sent inside the window
    assert out["failed"] == 1             # the reset; the abort is the cut


def _sweep_module():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "perfbench_sweep", os.path.join(preset_tree.ROOT, "perfbench",
                                        "sweep.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_thirds_average_each_third_of_the_window():
    sweep = _sweep_module()
    samples = [(10.0 + 0.5 * k, {"q": k}) for k in range(12)]   # 10..15.5
    got = sweep.thirds(samples, 10.0, 16.0, lambda v: v["q"])
    assert got == [1.5, 5.5, 9.5]
    assert sweep.thirds([], 0.0, 3.0, lambda v: 0) == [None, None, None]


@pytest.mark.parametrize("queue,lanes,want", [
    ([0, 0, 0], [10, 20, 21], True),        # steady: lanes level off
    ([0, 0, 0], [10, 20, 30], False),       # lanes still climbing
    ([0, 0, 0.3], [10, 20, 20], False),     # a queue in the last third
    ([0, 0, 0], [10, 20, None], False),     # nothing sampled
])
def test_sweep_knee_rule(queue, lanes, want):
    row = {"queue_thirds": queue, "lanes_thirds": lanes}
    assert _sweep_module().judge(row) is want
