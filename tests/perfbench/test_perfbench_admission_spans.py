"""The readers of admission's parts and of the checkpoint's landing: device
idle under `serving/collapse`, `serving/schedule`, `serving/admit_request`
and `serving/snapshot_land`, and the share of engine turns that drained
the dispatch-ahead pipeline (`serving.pipeline_collapses` over
`serving.steps`) — five files naming reducers the benchmark already has,
each one entry of `BENCHMARK.json` for the serve cell alone; on
hand-made spans and counters, then in the preset's toy serve cell."""
import importlib.util
import json
import os
import types

import pytest

from preset_tree import ROOT, make_tree
from perfbench.harness import spans as S
from perfbench.harness import trace as T
from perfbench.harness.manifest import Manifest

M = Manifest(ROOT)
IDLE = M.reducer("idle_under_span")
SERVE = "serve-longprompt-batch"
READERS = {
    "idle_collapse_share.batch": (
        "engine", "program_span", "idle_under_span",
        {"inside": ["serving/collapse"]}),
    "idle_schedule_share.batch": (
        "engine", "program_span", "idle_under_span",
        {"inside": ["serving/schedule"]}),
    "idle_admit_request_share.batch": (
        "engine", "program_span", "idle_under_span",
        {"inside": ["serving/admit_request"]}),
    "pipeline_collapse_share.batch": (
        "engine", "program_counter", "useful_row_share",
        {"useful": ["serving.pipeline_collapses"],
         "computed": "serving.steps"}),
    "idle_snapshot_land_share.batch": (
        "frontend", "program_span", "idle_under_span",
        {"inside": ["serving/snapshot_land"], "but_not": ["serving/step"]}),
}
PARTS = ["idle_collapse_share.batch", "idle_schedule_share.batch",
         "idle_admit_request_share.batch"]
SEED = 2 ** 31 + 39039


@pytest.mark.parametrize("name", sorted(READERS))
def test_each_metric_is_one_file_and_one_serve_entry(name):
    layer, source, reducer, args = READERS[name]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = [m for m in json.load(f)["per_layer"]
                   if m["name"] == name]
    assert entries == [{"name": name, "unit": "%", "better": "lower",
                        "source": source, "layer": layer,
                        "moves": "out_tok_s", "workloads": [SERVE]}]
    assert M.layer_metric(name) == {
        "layer": layer, "unit": "%", "better": "lower", "source": source,
        "moves": "out_tok_s", "reducer": reducer, "args": args}
    assert callable(M.reducer(reducer).reduce)
    assert name in {m["name"] for m in M.metrics_of(SERVE, "per_layer")}
    for cell in M.data["workloads"]:
        if cell["name"] != SERVE:
            assert name not in {m["name"] for m in
                                M.metrics_of(cell["name"], "per_layer")}


def span(name, start, end, thread="pump"):
    return S.Span(name, thread, start, end, {})


def _ctx(spans, monkeypatch):
    """Ten seconds; the device runs 0-1, 3-4, 6-7 and 9-10, so it idles
    1-3, 4-6 and 7-9.  One turn admits 1-3: its collapse 1.0-1.8 (the
    token fetch under it), the scheduler 1.9-2.2, two requests 2.3-2.6
    and 2.6-2.9 — 0.3 s of admission's idle under none of them.  After
    the step (0.5-5.0) the pump captures 5.2-5.5 and lands 7.2-7.8, the
    landing's own span 7.25-7.8; a second step runs 8.0-9.5."""
    ops = [("fusion", 0.0, 1.0), ("fusion", 3.0, 1.0),
           ("fusion", 6.0, 1.0), ("fusion", 9.0, 1.0)]
    trace = T.Trace({0: {"ops": ops, "modules": []}},
                    [("perfbench/window", "main", 0.0, 10.0)])
    ctx = {"trace": trace, "obs": {"trace_path": "synthetic"},
           "job": types.SimpleNamespace(manifest=M), "values": {}}
    monkeypatch.setattr(S, "load", lambda path: tuple(spans))
    return ctx


TURN = [span("serving/step", 0.5, 5.0),
        span("serving/admit", 1.0, 3.0),
        span("serving/collapse", 1.0, 1.8),
        span("serving/fetch_tokens", 1.1, 1.5),
        span("serving/schedule", 1.9, 2.2),
        span("serving/admit_request", 2.3, 2.6),
        span("serving/admit_request", 2.6, 2.9),
        span("serving/consume", 4.5, 5.0),
        span("serving/snapshot", 5.2, 5.5),
        span("serving/snapshot", 7.2, 7.8),
        span("serving/snapshot_land", 7.25, 7.8),
        span("serving/step", 8.0, 9.5)]


def _read(name, ctx):
    spec = M.layer_metric(name)
    return M.reducer(spec["reducer"]).reduce(ctx, **spec["args"])


def test_admission_parts_lie_inside_the_admit_share(monkeypatch):
    ctx = _ctx(TURN, monkeypatch)
    admit = _read("idle_admit_share.batch", ctx)
    parts = [_read(n, ctx) for n in PARTS]
    assert admit == pytest.approx(20.0)
    assert parts == pytest.approx([8.0, 3.0, 6.0])
    # what admission does between its parts (its own bookkeeping) stays
    # the admit share's alone
    assert admit - sum(parts) == pytest.approx(3.0)
    # a span inside a part (the token fetch) adds nothing to it
    ctx = _ctx([s for s in TURN if s.name != "serving/fetch_tokens"],
               monkeypatch)
    assert _read(PARTS[0], ctx) == pytest.approx(8.0)


def test_the_landing_lies_inside_the_snapshot_share(monkeypatch):
    ctx = _ctx(TURN, monkeypatch)
    snap = _read("idle_snapshot_share.batch", ctx)
    land = _read("idle_snapshot_land_share.batch", ctx)
    assert snap == pytest.approx(100 * (0.3 + 0.6) / 10)
    assert land == pytest.approx(100 * 0.55 / 10) and land <= snap
    # a landing inside a step (never in the program) is clipped as the
    # snapshot share clips it
    ctx = _ctx(TURN + [span("serving/snapshot", 8.6, 9.2),
                       span("serving/snapshot_land", 8.6, 9.2)],
               monkeypatch)
    assert _read("idle_snapshot_land_share.batch", ctx) \
        == pytest.approx(land)
    assert _read("idle_snapshot_share.batch", ctx) == pytest.approx(snap)


def test_the_collapse_share_is_collapses_over_engine_turns():
    reduce = M.reducer("useful_row_share").reduce
    args = M.layer_metric("pipeline_collapse_share.batch")["args"]
    c, s = "serving.pipeline_collapses", "serving.steps"
    samples = [(0.0, {c: 40, s: 100}),
               (0.05, {c: 41, s: 102, "serving.tokens_generated": 70}),
               (0.10, {c: 49, s: 112}),
               (0.15, {c: 55, s: 121})]
    # 15 turns of 21 drained the pipeline
    assert reduce({"obs": {"samples": samples}}, **args) \
        == pytest.approx(100.0 * 15 / 21)
    assert reduce({"obs": {"samples": samples[:1]}}, **args) is None
    still = [(0.0, {c: 3, s: 9}), (0.05, {c: 3, s: 9})]
    assert reduce({"obs": {"samples": still}}, **args) is None


@pytest.fixture(scope="module")
def toy_line(tmp_path_factory):
    """A traced run of the preset's closed-loop serve cell, whose manifest
    lists every file of `layer_metrics/` by its suffix."""
    import jax

    tree = make_tree(tmp_path_factory.mktemp("bench"))
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return tree, run.run_cell(tree, "tiny-closed", SEED, 2.0, 1,
                              jax.devices()[:1])


def test_the_toy_serve_cell_runs_the_five_readers(toy_line):
    tree, line = toy_line
    assert set(READERS) <= {m["name"] for m in
                            tree.metrics_of("tiny-closed", "per_layer")}
    assert line["correct"] is True and line["failed"] == 0
    got = {k: v for k, v in line["metrics"].items() if k in READERS}
    # the counter is there wherever the program runs; the idle readers
    # need a device trace, which the CPU does not give
    share = got["pipeline_collapse_share.batch"]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
    for name, m in got.items():
        assert m["unit"] == "%" and 0 <= m["value"] <= 100, name
