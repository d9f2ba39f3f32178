"""Every runner kind end to end on the CPU, through run_cell and the tiny
preset laid over a temporary copy of perfbench/ — which also shows that a
configuration, a traffic mix, a per-layer metric and a reducer are each new
files plus one manifest entry."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from preset_tree import ROOT, make_tree

SEED = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def _run_py():
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def lines(tree):
    """One untraced and one traced run of each preset cell."""
    import jax

    run = _run_py()
    out = {}
    for w in tree.data["workloads"]:
        devices = jax.devices()[:w["chips"]]
        for trace in (0, 1):
            out[w["name"], trace] = run.run_cell(
                tree, w["name"], SEED, 2.0, trace, devices)
    return out


CELLS = ["tiny-train", "tiny-closed", "tiny-open", "tiny-train-dp2mp2"]


@pytest.mark.parametrize("cell", CELLS)
def test_untraced_line_carries_the_cells_end_to_end_metrics(tree, lines,
                                                            cell):
    line = lines[cell, 0]
    assert line["correct"] is True, line["detail"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in tree.metrics_of(cell, "end_to_end")}
    assert set(line["metrics"]) == want and "setup_s" in want
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == tree.cell(cell)["chips"]
    json.dumps(line)


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_carries_layer_metrics_and_no_compile(tree, lines, cell):
    line = lines[cell, 1]
    assert line["correct"] is True, line["detail"]
    allowed = {m["name"] for m in tree.metrics_of(cell, "per_layer")}
    assert set(line["metrics"]) <= allowed and line["metrics"]
    compiles = [v["value"] for k, v in line["metrics"].items()
                if k.startswith("compiles_in_window")]
    assert compiles == [0.0]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "window_s" in line["device"] and "busy_s" in line["device"]


def test_the_added_metric_and_reducer_are_found_by_name(lines):
    m = lines["tiny-closed", 1]["metrics"]
    assert m["tokens_generated.tiny"]["value"] > 0
    assert m["lanes_in_use_mean.batch"]["value"] > 0
    assert 0 < m["useful_row_share.batch"]["value"] <= 100


def test_a_reader_with_nothing_to_read_leaves_its_metric_out(lines):
    # no device plane in a CPU trace: every device_trace metric is absent
    for cell in CELLS:
        for name in lines[cell, 1]["metrics"]:
            assert not name.startswith(("step_device_ms", "device_idle",
                                        "ragged_attn", "flash_"))


def test_train_check_is_sensitive(lines):
    d = lines["tiny-train", 0]["detail"]
    assert d["loss_gap_rel"] < d["loss_rtol"]
    # labels are the reference's argmax: the loss sits well under ln(vocab)
    assert d["reference_loss"] < 5.2


def test_serve_check_compares_logits(lines):
    c = lines["tiny-open", 0]["detail"]["check"]
    assert c["positions"] > 0 and c["worst_shortfall_rel"] <= c["margin_rel"]


def test_open_loop_schedule_is_the_replayed_set(tree):
    import collections

    runner = tree.runner("serve_open_loop")
    traffic = tree.traffic("tiny-open")
    a, window, _ = runner.build_schedule(traffic, 1, 5.0, 211)
    b, _, _ = runner.build_schedule(traffic, SEED, 5.0, 211)

    def shapes_of(s, phase):
        return collections.Counter(
            (len(r["prompt"]), r["max_new_tokens"])
            for r in s["requests"] if r["phase"] == phase)

    for phase in ("ramp", "measured", "cooldown"):
        assert shapes_of(a, phase) == shapes_of(b, phase)
    order = [len(r["prompt"]) for r in a["requests"]]
    assert order != [len(r["prompt"]) for r in b["requests"]]
    measured = [r for r in a["requests"] if r["phase"] == "measured"]
    assert len(measured) == int(traffic["rate_rps"] * 5.0)
    assert all(window[0] <= r["due"] < window[1] for r in measured)
    cool = [r for r in a["requests"] if r["phase"] == "cooldown"]
    assert min(r["due"] for r in cool) >= max(r["due"] for r in measured)


def test_the_command_refuses_anything_but_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "train-gpt2m-s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
