"""From the load generator's per-request records to the serving metrics."""
from __future__ import annotations

from .stats import percentile, tail_with_failures


def request_failed(rec):
    """Errored, refused, past the limit, or short of its token budget."""
    return bool(rec.get("error")) or rec.get("http") != 200 \
        or rec.get("status") != "completed" \
        or len(rec.get("token_times", ())) != rec.get("budget")


def score_open_loop(records, limit_s):
    """Tails over the measured requests of an open loop.  TTFT runs from
    the instant the request was DUE (not sent), so a generator or a server
    stall counts against every request it delays."""
    measured = [r for r in records if r["phase"] == "measured"]
    good = [r for r in measured if not request_failed(r)]
    n_failed = len(measured) - len(good)
    ttft_ms = [(r["first"] - r["due"]) * 1e3 for r in good]
    gaps_ms = [(b - a) * 1e3 for r in good
               for a, b in zip(r["token_times"], r["token_times"][1:])]
    late_ms = [(r["sent"] - r["due"]) * 1e3 for r in records
               if r.get("sent") is not None and r.get("due") is not None]
    return {
        "attempted": len(measured),
        "failed": n_failed,
        "ttft_p90_ms": tail_with_failures(ttft_ms, n_failed, 90,
                                          limit_s * 1e3),
        "ttft_p50_ms": percentile(ttft_ms, 50),
        # a failed request's gaps are unknown: its whole budget counts as
        # gaps beyond the percentile
        "itl_p95_ms": tail_with_failures(
            gaps_ms, sum(max(0, r["budget"] - 1) for r in measured
                         if request_failed(r)), 95, limit_s * 1e3),
        "itl_p50_ms": percentile(gaps_ms, 50),
        "n_gaps": len(gaps_ms),
        "late_p99_ms": percentile(late_ms, 99),
        "lifetime_mean_s": (sum(r["end"] - r["sent"] for r in good)
                            / len(good)) if good else None,
    }


def score_closed_loop(records, start, end):
    """Output tokens that reached the clients inside [start, end), over
    the window.  A request counts as attempted where it was sent inside
    the window, and as failed by request_failed() unless the end of the
    window cut it (status "aborted")."""
    tokens = sum(1 for r in records for t in r["token_times"]
                 if start <= t < end)
    sent_in = [r for r in records
               if r.get("sent") is not None and start <= r["sent"] < end]
    failed = [r for r in sent_in
              if r.get("status") != "aborted" and request_failed(r)]
    done_in = [r for r in sent_in if not request_failed(r)
               and r["end"] < end]
    return {
        "attempted": len(sent_in),
        "failed": len(failed),
        "completed": len(done_in),
        "out_tok_s": tokens / (end - start),
        "tokens_in_window": tokens,
    }
