#!/usr/bin/env python3
"""Finds the knee of an open-loop cell: one server, one process, the cell's
own shapes offered at each of a list of rates.

    python3 perfbench/sweep.py --workload <cell> --rates 2.0,2.5,3.0,3.5,4.0 \\
        --seconds 51 --seed 1 --out perfbench/out/sweep.json

For each rate: the traffic file's ramp, then a window of --seconds in which
the counters are sampled; the load stays on until the window's last request
has finished.  The knee is the highest rate whose queue depth in the last
third of the window stays at 0 and whose lanes in use do not climb from the
middle third to the last (by more than a tenth).  Run once when a cell is
defined, or when an optimisation has moved the knee; the cell's rate is then
fixed in its traffic file.  Refuses anything but a TPU, like run.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def thirds(samples, w0, w1, value):
    cut = [w0 + (w1 - w0) * k / 3 for k in range(4)]
    out = []
    for a, b in zip(cut, cut[1:]):
        vals = [value(v) for t, v in samples if a <= t < b]
        out.append(sum(vals) / len(vals) if vals else None)
    return out


def judge(row, climb=1.1):
    return row["queue_thirds"][2] == 0 and row["lanes_thirds"][2] is not None \
        and row["lanes_thirds"][2] <= climb * row["lanes_thirds"][1]


def sweep(job, rates):
    from perfbench.harness import score, serve

    runner = job.manifest.runner(job.traffic["kind"])
    server = serve.Server(job)
    rows = []
    try:
        server.warm_up(job.traffic["warmup_prompts"],
                       job.traffic["warmup_new_tokens"])
        for rate in rates:
            job.traffic = dict(job.traffic, rate_rps=rate)
            schedule, window, _ = runner.build_schedule(
                job.traffic, job.seed, job.seconds, job.config["vocab_size"])
            records, obs = serve.run_load(job, server, schedule, window,
                                          f"sweep-{rate}")
            w0, w1 = obs["t0"] + window[0], obs["t0"] + window[1]
            res = score.score_open_loop(records, serve.REQUEST_LIMIT_S)
            row = {
                "rate_rps": rate, "attempted": res["attempted"],
                "failed": res["failed"], "ttft_p50_ms": res["ttft_p50_ms"],
                "ttft_p90_ms": res["ttft_p90_ms"],
                "itl_p50_ms": res["itl_p50_ms"],
                "itl_p95_ms": res["itl_p95_ms"],
                "lifetime_mean_s": res["lifetime_mean_s"],
                "late_p99_ms": res["late_p99_ms"],
                "queue_thirds": thirds(
                    obs["samples"], w0, w1,
                    lambda v: v.get("serving.queue_depth", 0)),
                "lanes_thirds": thirds(
                    obs["samples"], w0, w1,
                    lambda v: v.get("serving.running_seqs", 0)),
                "compiles_in_window": obs["compiles_in_window"],
                "faults": server.faults()}
            row["sustained"] = judge(row)
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(2.0)
    finally:
        server.close()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from perfbench.harness import device
    from perfbench.harness.manifest import Manifest

    manifest = Manifest(ROOT)
    cell = manifest.cell(args.workload)
    traffic = manifest.traffic(cell["traffic"])

    import paddle_tpu  # noqa: F401 — no backend yet

    device.place_compile_cache(ROOT)
    devices = device.require_tpu(int(cell["chips"]))
    job = types.SimpleNamespace(
        manifest=manifest, cell=cell, config=manifest.config(cell["config"]),
        traffic=traffic, seed=args.seed, seconds=args.seconds, trace=False,
        sample_counters=True, devices=devices,
        clock=device.SetupClock(time.perf_counter()),
        counter=device.CompileCounter())
    rows = sweep(job, [float(r) for r in args.rates.split(",")])
    knee = max((r["rate_rps"] for r in rows if r["sustained"]), default=None)
    with open(args.out, "w") as f:
        json.dump({"cell": cell["name"], "seconds": args.seconds,
                   "knee_rps": knee, "rows": rows,
                   "device": device.describe(
                       devices, device.peak_bytes(devices))}, f, indent=1)
    print(json.dumps({"knee_rps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
