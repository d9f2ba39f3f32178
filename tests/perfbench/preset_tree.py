"""Builds, in a temporary directory, a benchmark tree that is the repo's
perfbench/ plus the tiny preset of tests/perfbench/preset/: three toy
configurations, three toy traffic mixes, one added per-layer metric and one
added reducer — each as new files and one manifest entry, no file edited.
Its manifest lists every per-layer metric file of perfbench/layer_metrics/,
so the readers of a cell family are exercised whether or not the repo's own
BENCHMARK.json has a proven cell of that family yet.
The tests and the CPU rehearsal drive every runner kind through it."""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELLS = [("tiny-train", "tiny-train", "tiny-steps", 1),
         ("tiny-closed", "tiny-serve", "tiny-closed", 1),
         ("tiny-open", "tiny-serve", "tiny-open", 1),
         ("tiny-train-dp2mp2", "tiny-train-dp2mp2", "tiny-steps", 4)]
# which preset cells report a metric: by the family suffix of its name,
# whatever cells the repo's own BENCHMARK.json has proven so far
FAMILY = {"chat": ["tiny-open"], "batch": ["tiny-closed"],
          "train": ["tiny-train", "tiny-train-dp2mp2"],
          "tiny": ["tiny-closed"]}
UNSUFFIXED = {"loadgen_late_p99_ms": FAMILY["chat"],
              "train_mfu": FAMILY["train"]}
END_TO_END = [("ttft_p90_ms", "ms", "lower", FAMILY["chat"]),
              ("itl_p95_ms", "ms", "lower", FAMILY["chat"]),
              ("out_tok_s", "tokens/s", "higher", FAMILY["batch"]),
              ("train_tok_s", "tokens/s", "higher", FAMILY["train"]),
              ("setup_s", "s", "lower", None)]


def make_tree(tmp):
    """tmp/BENCHMARK.json + tmp/perfbench/...; returns its Manifest."""
    from perfbench.harness.manifest import Manifest

    tmp = str(tmp)
    bench = os.path.join(tmp, "perfbench")
    shutil.copytree(os.path.join(ROOT, "perfbench"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    preset = os.path.join(HERE, "preset")
    for sub in ("configs", "traffic", "layer_metrics", "reducers"):
        for name in os.listdir(os.path.join(preset, sub)):
            if not name.startswith("__"):
                shutil.copy(os.path.join(preset, sub, name),
                            os.path.join(bench, sub, name))
    # the CPU is no benchmark device: only this temporary copy knows it
    with open(os.path.join(bench, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["devices"]["cpu"] = {"bf16_flops_per_s": 1e12,
                               "hbm_bytes_per_s": 1e11}
    with open(os.path.join(bench, "peaks.json"), "w") as f:
        json.dump(peaks, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    data = dict(real)
    data["configs"] = [
        {"name": c, "source": "none", "reduced": [], "why": "toy",
         "file": f"perfbench/configs/{c}.json"}
        for c in sorted({c for _, c, _, _ in CELLS})]
    data["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "toy"}
        for n, c, t, k in CELLS]
    data["end_to_end"] = [
        dict({"name": n, "unit": u, "better": b, "bound": 0.1,
              "source": "host_clock"}, **({"workloads": w} if w else {}))
        for n, u, b, w in END_TO_END]
    data["per_layer"] = []
    metrics_dir = os.path.join(bench, "layer_metrics")
    for fname in sorted(os.listdir(metrics_dir)):
        name = fname[:-len(".json")]
        with open(os.path.join(metrics_dir, fname)) as f:
            spec = json.load(f)
        data["per_layer"].append(
            {"name": name, "unit": spec["unit"], "better": spec["better"],
             "source": spec["source"], "layer": spec["layer"],
             "moves": spec["moves"],
             "workloads": UNSUFFIXED.get(name)
             or FAMILY[name.rsplit(".", 1)[-1]]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    return Manifest(tmp)
