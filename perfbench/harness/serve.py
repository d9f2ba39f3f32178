"""What the two serving runners share: server bring-up through the
program's public entry points, warm-up, the load generator's process, the
traced slice, the server's release, and the logit check on the freed chip.

Bring-up is chip_smoke.py's: Config.enable_serving ->
create_serving_frontend -> start_http_server.  The load generator is
harness/loadgen.py in a process of its own (standard library only); this
process — the one that holds the chip — sleeps while it runs, apart from
the counter sampler and the profiler in a traced run.
"""
from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from . import device
from . import trace as trace_mod
from .manifest import BENCH_DIR
from .model import build
from .sampler import CounterSampler

REQUEST_LIMIT_S = 60.0


def token_ids(seed, stream, n, vocab):
    """n prompt tokens in [1, vocab) from (--seed, stream)."""
    rng = np.random.default_rng([int(seed), int(stream)])
    return rng.integers(1, vocab, size=int(n)).tolist()


class Server:
    """The system under test, up and warm."""

    def __init__(self, job):
        from paddle_tpu.inference import Config
        from paddle_tpu.serving import (create_serving_frontend,
                                        start_http_server)

        self.job = job
        cfg = job.config
        self.model, self.weights = build(job.manifest, cfg, job.seed)
        self.model.eval()
        job.clock.phase("model + weights")
        serving = cfg["serving"]
        conf = Config()
        conf.enable_serving(**serving["enable_serving"])
        self.frontend = create_serving_frontend(
            self.model, conf, **serving.get("overrides", {}))
        self.http = start_http_server(self.frontend, port=0)
        self.engine = self.frontend._replicas[0].engine
        job.clock.phase("frontend + KV pool")

    # -- warm-up -------------------------------------------------------------
    def warm_up(self, prompt_lens, new_tokens):
        """One request per length, each started when the one before has
        its first token: every prefill runs alone, so each length's tail
        chunk sets the step's row bucket and its program compiles here."""
        vocab = self.job.config["vocab_size"]
        threads = []
        for i, n in enumerate(prompt_lens):
            first = threading.Event()
            th = threading.Thread(
                target=self._stream_once, daemon=True,
                args=(token_ids(self.job.seed, 9000 + i, n, vocab),
                      new_tokens, first))
            th.start()
            threads.append(th)
            first.wait(1200.0)
        for th in threads:
            th.join(1200.0)
        self.job.clock.phase(f"warm-up ({len(prompt_lens)} requests)")

    def _stream_once(self, prompt, new_tokens, first):
        conn = http.client.HTTPConnection("127.0.0.1", self.http.port,
                                          timeout=1200.0)
        try:
            conn.request("POST", "/generate", body=json.dumps(
                {"prompt": prompt, "max_new_tokens": new_tokens}),
                headers={"Content-Type": "application/json",
                         "Connection": "close"})
            resp = conn.getresponse()
            while resp.readline():
                first.set()
        finally:
            first.set()
            conn.close()

    # -- health --------------------------------------------------------------
    def faults(self):
        """Reasons the run cannot be trusted, seen through the frontend's
        crash containment; empty when healthy."""
        out = []
        for rep in self.frontend._replicas:
            if rep.dead_reason:
                out.append(f"replica {rep.id} was killed: {rep.dead_reason}")
        stats = self.frontend.stats()
        fe, eng = stats["frontend"], stats["engines"]
        if fe["retries"] or fe["failures"]:
            out.append(f"frontend retries {fe['retries']}, failures "
                       f"{fe['failures']}")
        if eng["restores"] or eng["watchdog_trips"]:
            out.append(f"restores {eng['restores']}, watchdog trips "
                       f"{eng['watchdog_trips']}")
        if not self.engine.stats()["pipeline"]["ragged"]:
            out.append("the engine did not run the unified ragged step")
        return out

    def close(self):
        """Stop the server and let go of everything that holds device
        memory but the weights (the model's parameters ARE `weights`):
        the engine with its KV pools and the step programs, which close
        over a copy of the weights each."""
        import gc

        self.http.stop()
        self.frontend.close()
        self.http = self.frontend = self.engine = self.model = None
        gc.collect()


def run_load(job, server, schedule, window, label):
    """Write the schedule, run the load generator's process to its end,
    and return (records, observations).  `window` is (start, end) in
    seconds after the schedule's t0; in a traced run the counters are
    sampled over it and the profiler records `trace_s` seconds in its
    middle (the knee sweep samples the counters without a trace)."""
    out_dir = job.manifest.out_dir(job.cell["name"])
    sched_path = os.path.join(out_dir, f"{label}.schedule.json")
    result_path = os.path.join(out_dir, f"{label}.result.json")
    schedule = dict(schedule, host="127.0.0.1", port=server.http.port,
                    request_limit_s=REQUEST_LIMIT_S)
    t0 = time.monotonic() + 1.0
    schedule["t0"] = t0
    with open(sched_path, "w") as f:
        json.dump(schedule, f)
    if os.path.exists(result_path):
        os.remove(result_path)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"),
         sched_path, result_path], stdin=subprocess.DEVNULL)
    obs = {"t0": t0, "window": window}
    try:
        w0, w1 = t0 + window[0], t0 + window[1]
        sampler = None
        _sleep_until(w0)
        obs["window_start_perf"] = time.perf_counter()
        compiles = job.counter.mark()
        if job.trace or getattr(job, "sample_counters", False):
            sampler = CounterSampler()
            hist0 = sampler.histograms()
            sampler.start()
        if job.trace:
            span = min(float(job.traffic.get("trace_s", 4.0)),
                       (w1 - w0) / 2)
            _sleep_until((w0 + w1 - span) / 2)
            stop_trace = trace_mod.capture(os.path.join(out_dir, "trace"))
            time.sleep(span)
            obs["trace_path"] = stop_trace()
        _sleep_until(w1)
        obs["compiles_in_window"] = job.counter.since(compiles)
        obs["memory_peak_bytes"] = device.peak_bytes(job.devices)
        if sampler is not None:
            sampler.stop()
            obs["samples"] = sampler.between(w0, w1)
            hist1 = sampler.histograms()
            obs["histograms"] = {
                k: (hist1[k][0] - hist0.get(k, (0, 0.0))[0],
                    hist1[k][1] - hist0.get(k, (0, 0.0))[1])
                for k in hist1}
        proc.wait(timeout=REQUEST_LIMIT_S * 2 + window[1] + 60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0 or not os.path.exists(result_path):
        raise RuntimeError(f"the load generator exited with "
                           f"{proc.returncode} and left no result")
    with open(result_path) as f:
        records = json.load(f)["requests"]
    return records, obs


def _sleep_until(t):
    wait = t - time.monotonic()
    if wait > 0:
        time.sleep(wait)


def measure(job, build_schedule):
    """The part of a run that needs the server, the same for both serving
    runners: bring-up, warm-up, the schedule `build_schedule(traffic, seed,
    seconds, vocab)` makes, the load, the server's health — and then its
    release, so that the check which follows has the chip to itself beside
    the weights.  Returns (records, obs, window, prompts, faults,
    weights)."""
    traffic = job.traffic
    server = Server(job)
    try:
        server.warm_up(traffic["warmup_prompts"],
                       traffic["warmup_new_tokens"])
        schedule, window, prompts = build_schedule(
            traffic, job.seed, job.seconds, job.config["vocab_size"])
        records, obs = run_load(job, server, schedule, window, "load")
        faults = server.faults()
        weights = server.weights
    finally:
        server.close()
    return records, obs, window, prompts, faults, weights


def _request_of(record):
    """The schedule's request a record answers (a closed loop sends one
    request many times: `c3.0`, `c3.1`, ...)."""
    return record["id"].split(".")[0]


def sample_requests(records, prompts, seed, sample=4):
    """The completed requests the check follows: the longest (prompt plus
    served tokens — the positions where a long context's machinery alone
    differs from a short one's) and `sample` - 1 more drawn by --seed."""
    done = sorted((r for r in records
                   if r.get("status") == "completed" and r["tokens"]
                   and _request_of(r) in prompts),
                  key=lambda r: r["id"])
    if not done:
        return []
    longest = max(done, key=lambda r: len(prompts[_request_of(r)])
                  + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 77])
    return [longest] + [rest[i] for i in rng.choice(
        len(rest), size=min(sample - 1, len(rest)), replace=False)]


def check_logits(job, weights, records, prompts):
    """The sampled requests (sample_requests), teacher-forced through the
    plain reference: every token the engine streamed (prefill, then
    decode through the paged cache) must have a reference logit within
    the stated margin of the reference's maximum at its position.  All
    are padded to the reference's longest sequence, so one program
    serves them.  Returns (ok, {name: value beside limit}, detail)."""
    import jax
    import jax.numpy as jnp

    cfg = job.config
    ref = job.manifest.reference(cfg)
    margin = job.manifest.tolerance(cfg)["serve_logit_margin_rel"]
    picks = sample_requests(records, prompts, job.seed)
    if not picks:
        return False, {}, {"error": "no completed request to check"}
    pad_to = ref.max_positions(cfg)
    fwd = jax.jit(lambda w, ids: ref.forward(w, ids, cfg))
    worst, flips, positions, last = 0.0, 0, 0, 0
    for r in picks:
        prompt = prompts[_request_of(r)]
        seq = list(prompt) + [int(t) for t in r["tokens"]]
        ids = np.zeros((pad_to,), np.int32)
        ids[:len(seq) - 1] = seq[:-1]
        logits = np.asarray(fwd(weights, jnp.asarray(ids)), np.float32)
        if not np.all(np.isfinite(logits[:len(seq) - 1])):
            return False, {}, {"error": f"reference logits not finite "
                                        f"({r['id']})"}
        for j, tok in enumerate(r["tokens"]):
            row = logits[len(prompt) - 1 + j]
            scale = float(np.max(np.abs(row)))
            if not 0 <= int(tok) < row.shape[0]:
                return False, {}, {"error": f"token id {tok} out of range"}
            short = float(np.max(row) - row[int(tok)]) / scale
            worst = max(worst, short)
            flips += int(np.argmax(row) != int(tok))
            positions += 1
        last = max(last, len(seq) - 1)
    # `sampled_max_position`, the last position a sampled token was served
    # at (0-based), says how far into a context the comparison reached
    detail = {"worst_shortfall_rel": worst, "margin_rel": margin,
              "argmax_differs": flips, "positions": positions,
              "sampled_max_position": last,
              "requests": [r["id"] for r in picks]}
    checks = {"logit_shortfall_rel": {"value": worst, "limit": margin}}
    return worst <= margin, checks, detail
