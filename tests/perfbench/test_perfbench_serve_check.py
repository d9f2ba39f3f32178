"""The order of a serving run around its check (server released first,
the longest request always sampled), the one request limit no traffic
file can relax, the window's memory peak, and the roofline rule for fewer KV heads
than query heads."""
import json
import os
import types

import pytest

from preset_tree import make_tree
from perfbench.harness import flops_hybrid as H
from perfbench.harness import serve


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("serve_check"))


def _job(tree, cell, **more):
    import jax

    w = tree.cell(cell)
    return types.SimpleNamespace(
        manifest=tree, cell=w, seed=2 ** 31 + 35, seconds=2.0, trace=False,
        config=tree.config(w["config"]), traffic=tree.traffic(w["traffic"]),
        devices=jax.devices()[:1], **more)


def _rec(rid, phase, n_tokens, at):
    times = [at + 0.1 * (k + 1) for k in range(n_tokens)]
    return {"id": rid, "phase": phase, "due": at, "sent": at,
            "first": times[0], "token_times": times,
            "tokens": [1] * n_tokens, "http": 200, "status": "completed",
            "error": None, "budget": n_tokens, "end": times[-1]}


@pytest.mark.parametrize("cell,kind", [("tiny-closed", "serve_closed_loop"),
                                       ("tiny-open", "serve_open_loop")])
def test_the_server_is_released_before_the_check(tree, monkeypatch, cell,
                                                 kind):
    """A stub server records the order: health is read, then the server
    closes, and only then does the check start — with the weights and the
    records, and no server in its hands."""
    log = []

    class StubServer:
        def __init__(self, job):
            log.append("up")
            self.weights = {"w": "the installed buffers"}

        def warm_up(self, lens, new_tokens):
            log.append("warm")

        def faults(self):
            log.append("faults")
            return []

        def close(self):
            log.append("close")

    def run_load(job, server, schedule, window, label):
        log.append("load")
        records = [_rec(r["id"], r["phase"], r["max_new_tokens"],
                        r.get("due", window[0])) for r in schedule["requests"]]
        return records, {"window_start_perf": 0.0, "compiles_in_window": 0,
                         "memory_peak_bytes": 0}

    def check_logits(job, weights, records, prompts):
        log.append("check")
        assert weights == {"w": "the installed buffers"}
        assert records and set(r["id"] for r in records) <= set(prompts)
        return True, {"logit_shortfall_rel": {"value": 0.0, "limit": 1.0}}, {}

    monkeypatch.setattr(serve, "Server", StubServer)
    monkeypatch.setattr(serve, "run_load", run_load)
    monkeypatch.setattr(serve, "check_logits", check_logits)
    result = tree.runner(kind).run(_job(tree, cell))
    assert log == ["up", "warm", "load", "faults", "close", "check"]
    assert result["correct"] is True and result["attempted"] > 0


def test_the_server_is_released_when_the_load_fails(tree, monkeypatch):
    log = []

    class StubServer:
        def __init__(self, job):
            self.weights = {}

        def warm_up(self, lens, new_tokens):
            pass

        def close(self):
            log.append("close")

    def run_load(*a):
        raise RuntimeError("the load generator exited with 1")

    monkeypatch.setattr(serve, "Server", StubServer)
    monkeypatch.setattr(serve, "run_load", run_load)
    with pytest.raises(RuntimeError, match="load generator"):
        tree.runner("serve_closed_loop").run(_job(tree, "tiny-closed"))
    assert log == ["close"]


@pytest.mark.parametrize("seed", [0, 1, 7, 2 ** 31 + 35, 3000035001])
def test_the_longest_completed_request_is_always_in_the_sample(seed):
    prompts = {f"c{k}": [1] * (16 + 5 * k) for k in range(12)}
    records = [{"id": f"c{k}.0", "status": "completed",
                "tokens": [1] * (2 + k % 3)} for k in range(12)]
    # the longest by prompt + served tokens is c11 (71 + 4); one that did
    # not complete and one that is not of the measured set never count
    records += [{"id": "c11.1", "status": "aborted", "tokens": [1] * 40},
                {"id": "w0", "status": "completed", "tokens": [1] * 99}]
    picks = serve.sample_requests(records, prompts, seed)
    ids = [r["id"] for r in picks]
    assert ids[0] == "c11.0" and len(ids) == len(set(ids)) == 4
    assert all(r["status"] == "completed" and r["id"] != "w0" for r in picks)
    again = serve.sample_requests(list(reversed(records)), prompts, seed)
    assert [r["id"] for r in again] == ids        # by seed, not by order
    assert serve.sample_requests(records[:2], prompts, seed)[0]["id"] \
        == "c1.0"
    assert serve.sample_requests([], prompts, seed) == []


def test_the_check_reports_how_far_into_a_context_it_reached(tree):
    import numpy as np

    from perfbench.harness.model import build

    job = _job(tree, "tiny-closed")
    cfg = job.config
    ref = tree.reference(cfg)
    _, weights = build(tree, cfg, job.seed)
    prompts, records = {}, []
    for k, n in enumerate((5, 30, 12)):
        prompt = serve.token_ids(job.seed, k, n, cfg["vocab_size"])
        ids = np.zeros((ref.max_positions(cfg),), np.int32)
        ids[:n] = prompt
        row = np.asarray(ref.forward(weights, ids, cfg))[n - 1]
        prompts[f"c{k}"] = prompt
        records.append({"id": f"c{k}.0", "status": "completed",
                        "tokens": [int(np.argmax(row))]})
    ok, checks, detail = serve.check_logits(job, weights, records, prompts)
    assert ok and detail["requests"][0] == "c1.0"
    # the served token of the 30-token prompt sits at position 30
    # — a record, not a verdict: beside the check's detail, while `checks`
    # holds only what `correct` compared, each beside its limit
    assert detail["sampled_max_position"] == 30
    assert list(checks) == ["logit_shortfall_rel"]
    assert checks["logit_shortfall_rel"]["limit"] is not None


@pytest.mark.parametrize("stated,want", [(None, 60.0), (150, 60.0)])
def test_no_traffic_file_relaxes_the_request_limit(tree, monkeypatch, stated,
                                                   want):
    """What counts as `failed` is the yardstick's: the one
    REQUEST_LIMIT_S is what the load generator's schedule carries and
    what its process is given to finish in, whatever a traffic file
    says."""
    job = _job(tree, "tiny-closed", counter=types.SimpleNamespace(
        mark=lambda: 0, since=lambda m: 0))
    if stated is not None:
        job.traffic = dict(job.traffic, request_limit_s=stated)
    assert serve.REQUEST_LIMIT_S == want
    seen = {}

    class StubProcess:
        returncode = 0

        def __init__(self, argv, **kw):
            with open(argv[-2]) as f:
                seen["schedule"] = json.load(f)
            with open(argv[-1], "w") as f:
                json.dump({"requests": []}, f)

        def wait(self, timeout=None):
            seen["timeout"] = timeout

        def poll(self):
            return 0

    monkeypatch.setattr(serve.subprocess, "Popen", StubProcess)
    monkeypatch.setattr(serve, "_sleep_until", lambda t: None)
    server = types.SimpleNamespace(http=types.SimpleNamespace(port=1))
    records, obs = serve.run_load(job, server, {"mode": "closed",
                                                "requests": []},
                                  (1.0, 3.0), "limit-test")
    assert seen["schedule"]["request_limit_s"] == want
    assert seen["timeout"] == 2 * want + 3.0 + 60
    assert records == [] and obs["memory_peak_bytes"] == 0   # the CPU's


def test_hbm_peak_gb_is_the_peak_read_as_the_window_closed(tree,
                                                           monkeypatch):
    """run_cell reports the runner's own reading (`obs`), taken before the
    reference ran, as `hbm_peak_gb.*` and as the device's
    `memory_peak_bytes`; the peak at exit stays beside it."""
    import importlib.util

    import jax

    from preset_tree import ROOT

    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(ROOT, "perfbench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    real = tree.runner("train_steps")

    def runner_run(job):
        result = real.run(job)
        assert result["obs"]["memory_peak_bytes"] == 0       # the CPU's
        result["obs"]["memory_peak_bytes"] = 5_250_000_000
        return result

    monkeypatch.setattr(tree, "runner", lambda kind: types.SimpleNamespace(
        run=runner_run))
    for trace in (0, 1):
        line = run.run_cell(tree, "tiny-train", 3, 0.5, trace,
                            jax.devices()[:1])
        assert line["device"]["memory_peak_bytes"] == 5_250_000_000
        assert "memory_peak_bytes_at_exit" in line["device"]
    assert line["metrics"]["hbm_peak_gb.train"]["value"] == 5.25


# -- the roofline rule for fewer KV heads than query heads ---------------------
@pytest.mark.parametrize("fn,per_q,per_kv", [
    # forward: q read, o written a query head; k, v read a KV head
    (H.attn_fwd, lambda dk, dv: dk + dv, lambda dk, dv: dk + dv),
    # backward: q, o, dO read and dq written; k, v read and dk, dv written
    (H.attn_bwd, lambda dk, dv: 2 * dk + 2 * dv,
     lambda dk, dv: 2 * dk + 2 * dv)])
def test_attention_bytes_count_k_and_v_at_the_kv_heads(fn, per_q, per_kv):
    b, s, heads, kv, dk, dv = 1, 8192, 32, 8, 64, 64
    flops, nbytes = fn(b, s, heads, dk, dv, kv_heads=kv)
    assert nbytes == 2 * b * s * (heads * per_q(dk, dv) + kv * per_kv(dk, dv))
    equal_flops, equal_bytes = fn(b, s, heads, dk, dv)
    # the operations are the query heads' whatever the grouping; with no
    # count given every head has its own K and V (the rule as it was)
    assert flops == equal_flops
    assert (equal_flops, equal_bytes) == fn(b, s, heads, dk, dv,
                                            kv_heads=heads)
    assert equal_bytes == 2 * b * s * heads * 2 * per_q(dk, dv)
    assert nbytes / equal_bytes == (heads + kv) / (2 * heads)
    # at 8,192 tokens the operations bound the least time either way
    assert flops / 197e12 > 5 * equal_bytes / 819e9


def test_the_mixer_roofline_hands_the_kv_heads_to_the_shape_function(tree):
    from perfbench.harness import trace as T

    ops = [("checkpoint custom-call bf16[32,8192,64]", 0.0, 0.004),
           ("checkpoint custom-call bf16[8,8192,64] bf16[8,8192,64]",
            0.004, 0.006)]
    loaded = T.Trace({0: {"ops": ops, "modules": []}}, [])
    shapes = {"heads": 32, "kv_heads": 8, "dk": 64, "dv": 64}

    def ctx(shape):
        ref = types.SimpleNamespace(mixer_shapes=lambda cfg: {"gqa": shape})
        return {"trace": loaded, "peaks": lambda: tree.peaks("TPU v5 lite"),
                "job": types.SimpleNamespace(
                    config={}, manifest=types.SimpleNamespace(
                        reference=lambda cfg: ref)),
                "values": {"sequences_per_chip": 1, "seq_len": 8192}}

    spec = tree.layer_metric("gqa_flash_bwd_roofline.train")["args"]
    reduce = tree.reducer("mixer_roofline").reduce
    grouped = reduce(ctx(shapes), **spec)
    flops, _ = H.attn_bwd(1, 8192, 32, 64, 64, kv_heads=8)
    assert grouped == pytest.approx(100.0 * (flops / 197e12) / 0.010)
    # operations-bound: the share is what it read with K and V at 32 heads
    ungrouped = reduce(ctx({k: v for k, v in shapes.items()
                            if k != "kv_heads"}), **spec)
    assert grouped == ungrouped
    # where bytes decide, the KV heads' count shows
    short = dict(ctx(shapes), values={"sequences_per_chip": 1, "seq_len": 64})
    short_ungrouped = dict(short, job=ctx({k: v for k, v in shapes.items()
                                           if k != "kv_heads"})["job"])
    assert reduce(short, **spec) < reduce(short_ungrouped, **spec)
