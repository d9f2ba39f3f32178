"""`snapshot_land_wait_mean_ms.batch` (ISSUE 31): how long the pump waited
for a periodic request checkpoint's pages at its landing, read from the
program's histogram `serving.snapshot_land_wait_ms` by the reducer the
benchmark already has.  The toy closed-loop cell ends every request before
its first checkpoint (outputs 2-8 against an interval of 16), so there the
reader finds nothing and the line leaves the metric out; the same cell with
outputs past one checkpoint, written into the temporary tree only, reports
it."""
import json
import os

import pytest

from preset_tree import ROOT, make_tree

NAME = "snapshot_land_wait_mean_ms.batch"
SEED = 2 ** 31 + 31031


def _run_py():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """The preset tree plus `tiny-closed-long`: the toy closed loop with
    outputs of 18-22 tokens, so every request passes one checkpoint."""
    from perfbench.harness.manifest import Manifest

    tmp = str(tmp_path_factory.mktemp("bench"))
    made = make_tree(tmp)
    traffic = dict(made.traffic("tiny-closed"),
                   prompt={"kind": "uniform", "min": 16, "max": 36},
                   output={"kind": "uniform", "min": 18, "max": 22})
    with open(os.path.join(made.bench_dir, "traffic",
                           "tiny-closed-long.json"), "w") as f:
        json.dump(traffic, f)
    data = made.data
    data["workloads"].append(dict(made.cell("tiny-closed"),
                                  name="tiny-closed-long",
                                  traffic="tiny-closed-long"))
    for group in ("end_to_end", "per_layer"):
        for m in data[group]:
            if "tiny-closed" in m.get("workloads", ()):
                m["workloads"].append("tiny-closed-long")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    return Manifest(tmp)


@pytest.fixture(scope="module")
def lines(tree):
    import jax

    run = _run_py()
    return {cell: run.run_cell(tree, cell, SEED, 2.0, 1, jax.devices()[:1])
            for cell in ("tiny-closed", "tiny-closed-long")}


def test_the_metric_is_one_file_and_one_entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    with open(os.path.join(ROOT, "perfbench", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert entry == [{"name": NAME, "unit": "ms", "better": "lower",
                      "source": "program_counter", "layer": "frontend",
                      "moves": "out_tok_s",
                      "workloads": ["serve-longprompt-batch"]}]
    assert spec == {"layer": "frontend", "unit": "ms", "better": "lower",
                    "source": "program_counter", "moves": "out_tok_s",
                    "reducer": "histogram_mean",
                    "args": {"histogram": "serving.snapshot_land_wait_ms"}}


def test_no_checkpoint_reached_leaves_the_metric_out(tree, lines):
    assert NAME in {m["name"]
                    for m in tree.metrics_of("tiny-closed", "per_layer")}
    line = lines["tiny-closed"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"] and NAME not in line["metrics"]


def test_requests_past_a_checkpoint_report_the_landing_wait(lines):
    line = lines["tiny-closed-long"]
    assert line["correct"] is True, line["detail"]
    assert line["attempted"] > 0 and line["failed"] == 0
    m = line["metrics"][NAME]
    assert m["unit"] == "ms" and 0 <= m["value"] < 1e3
    compiles = [v["value"] for k, v in line["metrics"].items()
                if k.startswith("compiles_in_window")]
    assert compiles == [0.0]


@pytest.mark.parametrize("hist,want", [({}, None),
                                       ({"other": (3, 9.0)}, None),
                                       ({"h": (0, 0.0)}, None),
                                       ({"h": (4, 10.0)}, 2.5)])
def test_the_reducer_returns_nothing_where_the_program_has_no_histogram(
        tree, hist, want):
    # a parent that lacks the histogram is read without raising
    reduce = tree.reducer("histogram_mean").reduce
    assert reduce({"obs": {"histograms": hist}}, histogram="h") == want
    assert reduce({"obs": {}}, histogram="h") is None
