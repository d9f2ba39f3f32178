"""Runner for traffic of kind `serve_closed_loop`: callers that each wait
for a reply — `clients` of them, each sending its next request when the
last completes, over a replayed set taken in --seed's order."""
from __future__ import annotations

from perfbench.harness import score, serve, shapes


def build_schedule(traffic, seed, seconds, vocab):
    pairs = shapes.seeded_order(
        shapes.replayed_set(traffic["prompt"], traffic["output"],
                            int(traffic["set_size"]), traffic["shape_seed"]),
        seed, "closed")
    requests, prompts = [], {}
    for k, (plen, olen) in enumerate(pairs):
        rid = f"c{k}"
        prompts[rid] = serve.token_ids(seed, 5000 + k, plen, vocab)
        requests.append({"id": rid, "phase": "closed", "prompt": prompts[rid],
                         "max_new_tokens": olen})
    ramp_s = float(traffic["ramp_s"])
    return ({"mode": "closed", "clients": int(traffic["clients"]),
             "end": ramp_s + seconds, "requests": requests},
            (ramp_s, ramp_s + seconds), prompts)


def run(job):
    traffic = job.traffic
    server = serve.Server(job)
    try:
        server.warm_up(traffic["warmup_prompts"],
                       traffic["warmup_new_tokens"])
        schedule, window, prompts = build_schedule(
            traffic, job.seed, job.seconds, job.config["vocab_size"])
        records, obs = serve.run_load(job, server, schedule, window, "load")
        faults = server.faults()
        result = score.score_closed_loop(records, *window)
        ok, detail = serve.check_logits(job, server, records, prompts)
    finally:
        server.close()
    return {
        "correct": bool(ok and not faults),
        "attempted": result["attempted"], "failed": result["failed"],
        "end_to_end": {"out_tok_s": result["out_tok_s"]},
        "values": {}, "obs": obs,
        "detail": dict(result, check=detail, faults=faults),
    }
