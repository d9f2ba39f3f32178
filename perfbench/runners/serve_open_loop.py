"""Runner for traffic of kind `serve_open_loop`: independent users, a
constant arrival rate with jitter, a replayed set of request shapes, a ramp
to steady state before the window and load kept on after it until the last
measured request has finished."""
from __future__ import annotations

from perfbench.harness import score, serve, shapes


def build_schedule(traffic, seed, seconds, vocab):
    """(schedule, window, prompts by request id).  The measured set is the
    floor(rate * seconds) quantile shapes of the traffic file, the same
    multiset for every --seed; the ramp and the cool-down have sets of
    their own, built the same way."""
    rate, jitter = float(traffic["rate_rps"]), float(traffic["jitter"])
    ramp_s = float(traffic["ramp_s"])
    n = {"ramp": int(rate * ramp_s), "measured": int(rate * seconds),
         "cooldown": int(rate * float(traffic["cooldown_s"]))}
    start = {"ramp": 0.0, "measured": ramp_s,
             "cooldown": ramp_s + n["measured"] / rate}
    requests, prompts = [], {}
    for stream, phase in enumerate(("ramp", "measured", "cooldown")):
        if not n[phase]:
            continue
        pairs = shapes.seeded_order(
            shapes.replayed_set(traffic["prompt"], traffic["output"],
                                n[phase], traffic["shape_seed"]),
            seed, phase)
        dues = shapes.paced_arrivals(n[phase], rate, jitter, seed, phase,
                                     start[phase])
        for k, ((plen, olen), due) in enumerate(zip(pairs, dues)):
            rid = f"{phase[0]}{k}"
            prompts[rid] = serve.token_ids(seed, 1000 * (stream + 1) + k,
                                           plen, vocab)
            requests.append({"id": rid, "phase": phase, "due": due,
                             "prompt": prompts[rid],
                             "max_new_tokens": olen})
    return ({"mode": "open", "requests": requests},
            (ramp_s, ramp_s + seconds), prompts)


def run(job):
    records, obs, _, prompts, faults, weights = serve.measure(
        job, build_schedule)
    result = score.score_open_loop(records, serve.REQUEST_LIMIT_S)
    ok, checks, detail = serve.check_logits(job, weights, records, prompts)
    return {
        "correct": bool(ok and not faults),
        "attempted": result["attempted"], "failed": result["failed"],
        "end_to_end": {"ttft_p90_ms": result["ttft_p90_ms"],
                       "itl_p95_ms": result["itl_p95_ms"]},
        "values": {"loadgen_late_p99_ms": result["late_p99_ms"],
                   "client_ttft_p90_ms": result["ttft_p90_ms"],
                   "client_itl_p95_ms": result["itl_p95_ms"]},
        "obs": obs,
        "checks": dict(checks, server_faults={"value": len(faults),
                                              "limit": 0}),
        "detail": dict(result, check=detail, faults=faults),
    }
