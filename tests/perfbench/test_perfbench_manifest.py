"""BENCHMARK.json against the contract's static rules and against the data
files it names."""
import json
import os
import re

import pytest

from preset_tree import ROOT
from perfbench.harness.manifest import Manifest

M = Manifest(ROOT)
B = M.data
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ALL_METRICS = B["end_to_end"] + B["per_layer"]
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys_and_size():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert B["paths"] == ["perfbench", "tests/perfbench"]
    assert B["command"] == ["python3", "perfbench/run.py"]


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in ALL_METRICS} | set(CELLS)
    | {c["name"] for c in B["configs"]}
    | {w["traffic"] for w in B["workloads"]}
    | {k for c in B["configs"] for k in c["reduced"]}))
def test_names_use_only_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entries(m):
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if m in B["end_to_end"]:
        assert set(m) <= allowed | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert set(m) <= allowed | {"layer", "moves"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for w in m.get("workloads", ()):
        assert w in CELLS


def test_names_are_unique():
    for group in (ALL_METRICS, B["workloads"], B["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in M.metrics_of(cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert M.metrics_of(cell, "per_layer")


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_a_layer_metric_moves_a_metric_its_cells_report(m):
    for cell in m.get("workloads", CELLS):
        assert m["moves"] in {e["name"]
                              for e in M.metrics_of(cell, "end_to_end")}


@pytest.mark.parametrize("m", B["per_layer"], ids=lambda m: m["name"])
def test_each_layer_metric_has_its_own_reader_file(m):
    spec = M.layer_metric(m["name"])
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == m[key], key
    reducer = M.reducer(spec["reducer"])
    assert callable(reducer.reduce)
    path = os.path.join(ROOT, "perfbench", "layer_metrics",
                        m["name"] + ".json")
    assert re.match(r"^[A-Za-z0-9_.\-/]+$", os.path.relpath(path, ROOT))


def test_roofline_metrics_are_named_and_united_as_the_contract_says():
    for m in B["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_configurations(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("perfbench/configs/")
    assert any(w["config"] == c["name"] for w in B["workloads"])
    cfg = M.config(c["name"])
    assert cfg["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
    for key in c["reduced"]:
        assert key in cfg
        # never a width
        assert not re.search(r"(_dim|_rank|embd|hidden|inner|head)", key)
    # the published sizes (HF config.json of each model)
    assert (cfg["vocab_size"], cfg["n_positions"]) == (50257, 1024)
    assert cfg["n_embd"] == 64 * cfg["n_head"]
    assert (cfg["n_layer"], cfg["n_embd"]) in {(12, 768), (24, 1024),
                                               (36, 1280)}
    assert c["source"].startswith("https://huggingface.co/")
    assert cfg["assumed"] and cfg["deployment"]


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cells(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    traffic = M.traffic(w["traffic"])
    assert callable(M.runner(traffic["kind"]).run)


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(B["workloads"]) // 4)


def test_run_seconds_fits_the_check_with_24_cells():
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_no_code_under_perfbench_names_a_cell():
    """Cells, configurations and mixes are data: no program file may
    branch on (or so much as mention) one of their names."""
    names = set(CELLS) | {c["name"] for c in B["configs"]} \
        | {w["traffic"] for w in B["workloads"]}
    for base, _, files in os.walk(os.path.join(ROOT, "perfbench")):
        if os.sep + "out" in base:
            continue
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    text = fh.read()
                for n in names:
                    assert n not in text, (f, n)


def test_the_chat_traffic_file_records_its_rate_knee_and_sweep():
    """Kept under unproven/ until the cell repeats; what the sweep found
    stays on record with it."""
    with open(os.path.join(ROOT, "perfbench", "unproven",
                           "chat-steady.json")) as f:
        t = json.load(f)
    assert t["rate_rps"] == int(0.7 * t["knee_rps"] / 0.25 + 1e-9) * 0.25
    assert t["shape_seed"] is not None and t["status"].startswith("unproven")
    rows = t["sweep"]["rows"]
    assert [r["rate_rps"] for r in rows] == [2.0, 2.5, 3.0, 3.5, 4.0]
    assert max(r["rate_rps"] for r in rows if r["sustained"]) \
        == t["knee_rps"]
    assert callable(M.runner(t["kind"]).run)


def test_nothing_under_unproven_is_named_by_the_manifest():
    text = json.dumps(B)
    assert "unproven" not in text


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        table = json.load(f)
    assert "Google Cloud" in table["source"]
    assert M.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert M.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        M.peaks("TPU v9")
