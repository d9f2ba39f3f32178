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
    records, obs, window, prompts, faults, weights = serve.measure(
        job, build_schedule)
    result = score.score_closed_loop(records, *window)
    ok, checks, detail = serve.check_logits(job, weights, records, prompts)
    return {
        "correct": bool(ok and not faults),
        "attempted": result["attempted"], "failed": result["failed"],
        "end_to_end": {"out_tok_s": result["out_tok_s"]},
        "values": {}, "obs": obs,
        "checks": dict(checks, server_faults={"value": len(faults),
                                              "limit": 0}),
        "detail": dict(result, check=detail, faults=faults),
    }
