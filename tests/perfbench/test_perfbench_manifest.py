"""BENCHMARK.json against the contract's static rules and against the data
files it names."""
import json
import os
import re

import pytest

from preset_tree import ROOT, make_tree
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


WIDTH = re.compile(r"(_dim|_rank|_size)$|embd|inner|per_tok|^expand$")


def is_width(key):
    """A key `reduced` may never name: a hidden, intermediate, latent,
    state, head or projection size, an expansion factor, the experts per
    token.  Depth, the heads, experts and vocabulary rows held here may be
    cut (the model-configs guide, section 4)."""
    return key != "vocab_size" and bool(WIDTH.search(key))


def check_configuration(entry, cfg, published, cells):
    """The rules a `configs` entry, its file and the source's published
    config.json keep, whatever the architecture."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("perfbench/configs/")
    assert any(w["config"] == entry["name"] for w in cells)
    assert cfg["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert entry["source"].startswith("https://huggingface.co/")
    assert cfg["assumed"] and cfg["deployment"]
    assert cfg["model_type"] == published["model_type"]
    for key in entry["reduced"]:
        assert key in cfg and key in published, key
        assert not is_width(key), f"{key} is a width: never reduced"
        if isinstance(published[key], dict):
            # a nested group is listed by its top-level key; no width
            # inside it may change all the same
            for k, v in published[key].items():
                assert not is_width(k) or cfg[key].get(k) == v, (key, k)
    for key in set(cfg) & set(published):
        assert cfg[key] == published[key] or key in entry["reduced"], \
            f"{key} differs from the published file and is not in reduced"


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_configurations(c):
    check_configuration(c, M.config(c["name"]), M.published(c["name"]),
                        B["workloads"])


@pytest.fixture(scope="module")
def second_architecture(tmp_path_factory):
    """(entry, configuration, published) of the preset's `token_mlp`
    configuration, which lists `num_hidden_layers` under `reduced`."""
    tree = make_tree(tmp_path_factory.mktemp("manifest"))
    entry = next(c for c in tree.data["configs"]
                 if c["name"] == "tiny-mlp-train")
    entry = dict(entry, source="https://huggingface.co/ (none: a toy)")
    return (entry, tree.config(entry["name"]), tree.published(entry["name"]),
            tree.data["workloads"])


def test_a_second_architectures_configuration_keeps_the_rules(
        second_architecture):
    entry, cfg, published, cells = second_architecture
    assert cfg["model_type"] != "gpt2"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] != published["num_hidden_layers"]
    check_configuration(entry, cfg, published, cells)


@pytest.mark.parametrize("key,width", [
    ("num_hidden_layers", False), ("num_attention_heads", False),
    ("num_experts", False), ("vocab_size", False), ("n_layer", False),
    ("n_head", False), ("num_key_value_heads", False),
    ("hidden_size", True), ("head_dim", True), ("kv_lora_rank", True),
    ("moe_intermediate_size", True), ("n_embd", True), ("n_inner", True),
    ("qk_nope_head_dim", True), ("ssm_state_size", True),
    ("num_experts_per_tok", True), ("num_experts_per_token", True),
    ("expand", True)])
def test_reduced_may_name_a_count_and_never_a_width(second_architecture, key,
                                                    width):
    """The configuration's value of `key` is cut and listed: refused for a
    width, taken for a count."""
    entry, cfg, published, cells = second_architecture
    cfg = dict(cfg, **{key: 8}, reduced=cfg["reduced"] + [key])
    published = dict(published, **{key: 64})
    entry = dict(entry, reduced=cfg["reduced"])
    assert is_width(key) is width
    if width:
        with pytest.raises(AssertionError, match="is a width"):
            check_configuration(entry, cfg, published, cells)
    else:
        check_configuration(entry, cfg, published, cells)


@pytest.mark.parametrize("key", ["hidden_size", "num_hidden_layers",
                                 "vocab_size", "layer_norm_eps"])
def test_a_silent_difference_from_the_published_file_is_refused(
        second_architecture, key):
    entry, cfg, published, cells = second_architecture
    published = dict(published, **{key: published[key] * 2})
    if key in entry["reduced"]:
        entry = dict(entry, reduced=[])
        cfg = dict(cfg, reduced=[])
    with pytest.raises(AssertionError, match="differs from the published"):
        check_configuration(entry, cfg, published, cells)


def test_a_width_inside_a_listed_group_may_not_change(second_architecture):
    entry, cfg, published, cells = second_architecture
    group = {"head_dim": 128, "num_heads": 32}
    entry = dict(entry, reduced=entry["reduced"] + ["linear_attn_config"])
    published = dict(published, linear_attn_config=group)
    fewer_heads = dict(cfg, reduced=entry["reduced"],
                       linear_attn_config=dict(group, num_heads=4))
    check_configuration(entry, fewer_heads, published, cells)
    narrower = dict(fewer_heads, linear_attn_config=dict(group, head_dim=64))
    with pytest.raises(AssertionError, match="linear_attn_config"):
        check_configuration(entry, narrower, published, cells)


@pytest.mark.parametrize("name,layers,width,heads", [
    ("gpt2-small-serve", 12, 768, 12), ("gpt2-medium-train", 24, 1024, 16)])
def test_the_gpt2_published_files(name, layers, width, heads):
    """The sizes of the HF config.json each GPT-2 configuration names."""
    p = M.published(name)
    assert (p["vocab_size"], p["n_positions"], p["n_ctx"]) \
        == (50257, 1024, 1024)
    assert (p["n_layer"], p["n_embd"], p["n_head"]) == (layers, width, heads)
    assert p["n_embd"] == 64 * p["n_head"]
    assert p["activation_function"] == "gelu_new" and "n_inner" not in p
    assert (p["layer_norm_epsilon"], p["model_type"]) == (1e-5, "gpt2")


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


@pytest.mark.parametrize("sub", ["harness", "runners", "reducers"])
def test_no_architecture_is_named_outside_its_own_files(sub):
    """What belongs to an architecture is found by `model_type`: the
    harness, the runners and the reducers read none of GPT-2's keys."""
    words = re.compile(r"n_embd|n_head|n_layer|n_positions|GPTModel|gpt2")
    files = [f for f in os.listdir(os.path.join(ROOT, "perfbench", sub))
             if f.endswith(".py")]
    assert files
    for f in files:
        with open(os.path.join(ROOT, "perfbench", sub, f)) as fh:
            for i, text in enumerate(fh, 1):
                assert not words.search(text), (sub, f, i, text)


def test_an_unknown_model_type_names_the_files_to_add():
    for lookup in (M.model, M.reference, M.tolerance):
        with pytest.raises(FileNotFoundError) as e:
            lookup({"model_type": "no_such_arch"})
        for path in ("perfbench/models/no_such_arch.py",
                     "perfbench/reference/no_such_arch.py",
                     "perfbench/reference/no_such_arch.tolerance.json"):
            assert path in str(e.value)
    with pytest.raises(FileNotFoundError):
        M.reference({"vocab_size": 8})


def test_the_architectures_files_are_found_by_model_type():
    cfg = M.config(B["configs"][0]["name"])
    assert cfg["model_type"] == "gpt2"
    assert callable(M.model(cfg).construct)
    ref = M.reference(cfg)
    for fn in ("param_shapes", "forward", "n_params", "max_positions",
               "attention_shape", "train_flops_per_token"):
        assert callable(getattr(ref, fn)), fn
    tol = M.tolerance(cfg)
    assert tol["serve_logit_margin_rel"] == 2 ** -5
    assert tol["train_loss_rtol"] == 2 ** -9
    assert tol["serve_logit_margin_reason"] and tol["train_loss_reason"]


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
