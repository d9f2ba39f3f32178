#!/usr/bin/env python3
"""perfbench — one cell of BENCHMARK.json, measured on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (perfbench/configs/) and a traffic mix
(perfbench/traffic/); the mix's `kind` names the runner
(perfbench/runners/<kind>.py).  With --trace 0 the last line of stdout
carries the cell's end-to-end metrics; with --trace 1 its per-layer metrics
(each computed by the reducer its file under perfbench/layer_metrics/ names)
and the breakdown of the traced slice.  Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def layer_metrics(job, manifest, result, trace):
    """name -> value for the cell's per-layer metrics.  A reducer that
    finds nothing to read returns None and the metric is left out."""
    ctx = {"job": job, "result": result, "obs": result["obs"],
           "values": dict(result["values"]), "trace": trace,
           "peaks": lambda: manifest.peaks(job.devices[0].device_kind)}
    out = {}
    for m in manifest.metrics_of(job.cell["name"], "per_layer"):
        spec = manifest.layer_metric(m["name"])
        value = manifest.reducer(spec["reducer"]).reduce(
            ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(manifest, name, seed, seconds, trace, devices, t0=None):
    """Run one cell on `devices` and return the result line as a dict.
    main() calls it after the TPU check; the tests call it on the CPU with
    the tiny preset."""
    from perfbench.harness import device, trace as trace_mod

    t0 = time.perf_counter() if t0 is None else t0
    cell = manifest.cell(name)
    traffic = manifest.traffic(cell["traffic"])
    clock = device.SetupClock(t0)
    clock.phase("import + backend")
    job = types.SimpleNamespace(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=traffic, seed=int(seed), seconds=float(seconds),
        trace=bool(trace), devices=devices, clock=clock,
        counter=device.CompileCounter())

    result = manifest.runner(traffic["kind"]).run(job)
    obs = result["obs"]
    setup_s = obs["window_start_perf"] - t0
    dev = device.describe(devices, obs["memory_peak_bytes"])
    result["values"].update(
        compiles_in_window=obs["compiles_in_window"],
        hbm_peak_gb=obs["memory_peak_bytes"] / 1e9, chips=len(devices))
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": dev}
    if not trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in manifest.metrics_of(cell["name"], "end_to_end")}
    else:
        loaded = trace_mod.load(obs["trace_path"])
        busy, span = trace_mod.busy_seconds(loaded)
        dev.update(busy_s=busy, window_s=span)
        line["metrics"] = layer_metrics(job, manifest, result, loaded)
        line["breakdown"] = trace_mod.breakdown(loaded)
    line["setup"] = dict(clock.as_dict(), setup_s=round(setup_s, 3),
                         compile_s=round(job.counter.seconds(), 3))
    line["detail"] = result["detail"]
    line["cell"] = {"workload": cell["name"], "seed": int(seed),
                    "seconds": float(seconds), "trace": int(trace)}
    # each number `correct` compared, beside its limit: the line's last key
    line["checks"] = result["checks"]
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench.harness import device
    from perfbench.harness.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)

    import paddle_tpu  # noqa: F401 — no backend yet

    device.place_compile_cache(ROOT)
    devices = device.require_tpu(int(cell["chips"]))
    line = run_cell(manifest, args.workload, args.seed, args.seconds,
                    args.trace, devices, t0=T0)
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
