"""Every runner kind end to end on the CPU, through run_cell and the tiny
preset laid over a temporary copy of perfbench/ — which also shows that a
configuration, a traffic mix, a per-layer metric and a reducer are each new
files plus one manifest entry."""
import hashlib
import importlib.util
import json
import os
import subprocess
import sys
import types

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


CELLS = ["tiny-train", "tiny-closed", "tiny-open", "tiny-train-dp2mp2",
         "tiny-mlp-train"]


def _hashes(bench_dir):
    """relative path -> sha256 of every file under a perfbench/ tree."""
    out = {}
    for base, dirs, files in os.walk(bench_dir):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, bench_dir)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


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


def test_a_second_architecture_is_new_files_and_entries(tree, lines):
    """The preset's `token_mlp` configuration ran through the train_steps
    runner (its model, reference and tolerances found by model_type), and
    after every cell has run no file of the repo's perfbench/ differs in
    the copy: what the preset brought is files that were not there.  The
    one exception is peaks.json, where the copy alone learns the CPU."""
    cfg = tree.config("tiny-mlp-train")
    assert cfg["model_type"] == "token_mlp"
    assert cfg["reduced"] == ["num_hidden_layers"]
    for trace in (0, 1):
        line = lines["tiny-mlp-train", trace]
        assert line["correct"] is True and line["failed"] == 0
        check = line["checks"]["loss_gap_rel"]
        assert check["limit"] == tree.tolerance(cfg)["train_loss_rtol"] \
            != tree.tolerance(tree.config("tiny-train"))["train_loss_rtol"]
    assert lines["tiny-mlp-train", 0]["metrics"]["train_tok_s"]["value"] > 0
    assert lines["tiny-mlp-train", 1]["metrics"]["train_mfu"]["value"] > 0
    # the whole step's share of the peak, from the architecture's own count
    per_token = tree.reference(cfg).train_flops_per_token(cfg, 32)
    assert per_token == 6 * (2 * (2 * 32 * 64 + 64 + 3 * 32) + 2 * 32
                             + 32 * 211)
    ctx = {"job": types.SimpleNamespace(config=cfg, manifest=tree),
           "values": {"train_tok_s": 1e6, "seq_len": 32, "chips": 1},
           "peaks": lambda: tree.peaks("cpu")}
    assert tree.reducer("train_mfu").reduce(ctx) \
        == pytest.approx(100.0 * per_token * 1e6 / 1e12)

    before = _hashes(os.path.join(ROOT, "perfbench"))
    after = _hashes(tree.bench_dir)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {"peaks.json"}
    added = set(after) - set(before)
    preset = os.path.join(ROOT, "tests", "perfbench", "preset")
    assert added == set(_hashes(preset))
    assert {"models/token_mlp.py", "reference/token_mlp.py",
            "reference/token_mlp.tolerance.json",
            "published/tiny-mlp-train.json",
            "configs/tiny-mlp-train.json"} <= added


@pytest.mark.parametrize("cell", CELLS)
def test_the_line_ends_with_each_number_compared_beside_its_limit(lines,
                                                                  cell):
    for trace in (0, 1):
        line = lines[cell, trace]
        assert list(line)[-1] == "checks" and line["checks"]
        for c in line["checks"].values():
            assert 0 <= c["value"] <= c["limit"]


def test_the_added_metric_and_reducer_are_found_by_name(lines):
    m = lines["tiny-closed", 1]["metrics"]
    assert m["tokens_generated.tiny"]["value"] > 0
    assert m["lanes_in_use_mean.batch"]["value"] > 0
    assert 0 < m["useful_row_share.batch"]["value"] <= 100
    assert 0 < m["step_mfu.batch"]["value"] <= 100


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


def _job(tree, cell, seed=SEED):
    w = tree.cell(cell)
    return types.SimpleNamespace(manifest=tree, cell=w, seed=seed,
                                 config=tree.config(w["config"]),
                                 traffic=tree.traffic(w["traffic"]))


@pytest.mark.parametrize("fault", ["none", "token_altered"])
def test_a_token_altered_where_it_is_produced_fails_the_serve_check(tree,
                                                                    fault):
    """check_logits on a stream that IS the reference's greedy decode
    passes with a shortfall of 0; the same stream with one token replaced
    by the reference's least likely fails by the logit scale itself."""
    import numpy as np

    from perfbench.harness import serve
    from perfbench.harness.model import build

    job = _job(tree, "tiny-closed")
    cfg = job.config
    ref = tree.reference(cfg)
    _, weights = build(tree, cfg, job.seed)
    prompt = serve.token_ids(job.seed, 1, 12, cfg["vocab_size"])
    seq, worst_token = list(prompt), None
    for _ in range(8):
        ids = np.zeros((ref.max_positions(cfg),), np.int32)
        ids[:len(seq)] = seq
        row = np.asarray(ref.forward(weights, ids, cfg))[len(seq) - 1]
        worst_token = int(np.argmin(row))
        seq.append(int(np.argmax(row)))
    tokens = seq[len(prompt):]
    if fault == "token_altered":
        tokens[-1] = worst_token
    records = [{"id": "c0", "status": "completed", "tokens": tokens}]
    ok, checks, detail = serve.check_logits(job, weights, records,
                                            {"c0": prompt})
    limit = tree.tolerance(cfg)["serve_logit_margin_rel"]
    assert checks["logit_shortfall_rel"]["limit"] == limit
    if fault == "none":
        assert ok and checks["logit_shortfall_rel"]["value"] == 0.0
    else:
        assert not ok and detail["worst_shortfall_rel"] > 3 * limit


@pytest.mark.parametrize("scale,correct", [(1.0, True), (1.5, False)])
def test_a_program_that_departs_from_the_reference_fails_the_train_check(
        tree, monkeypatch, scale, correct):
    """The rest of a run with the timed path broken underneath: the
    program's model gets final-norm gains the reference does not have, and
    `correct` comes out false through run_cell."""
    import jax

    run = _run_py()
    runner = tree.runner("train_steps")

    def build_broken(manifest, config, seed):
        model, weights = runner_build(manifest, config, seed)
        for n, p in model.named_parameters():
            if n == "ln_f.weight":
                p._value = p._value * scale
        return model, weights

    runner_build = runner.build
    monkeypatch.setattr(runner, "build", build_broken)
    monkeypatch.setattr(tree, "runner", lambda kind: runner)
    line = run.run_cell(tree, "tiny-train", SEED + 1, 0.5, 0,
                        jax.devices()[:1])
    check = line["checks"]["loss_gap_rel"]
    assert line["correct"] is correct
    assert (check["value"] <= check["limit"]) is correct


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
