#!/usr/bin/env python3
"""The load generator: a process of its own, standard library only.

The chip-holding process writes a schedule file, starts this program once
the server listens, and reads the per-request timings back from the result
file.  It never imports jax, numpy or the program under test, so it shares
no interpreter lock with the HTTP handlers and the engine loop.

    python3 loadgen.py <schedule.json> <result.json>

Schedule: {"mode": "open" | "closed", "host", "port", "t0" (absolute
time.monotonic() of the schedule's zero; CLOCK_MONOTONIC is shared by the
processes of one machine), "end" (closed loop: seconds after t0 at which
clients stop), "clients" (closed loop), "request_limit_s", "requests":
[{"id", "phase", "due", "prompt", "max_new_tokens"}, ...]}.

open    every request is sent at its `due` time whether or not earlier
        ones have finished; requests of phase "cooldown" keep the load on
        and are sent only until the last "measured" request has finished.
closed  `clients` threads each send the next request of the list (taken
        in order, cyclically) as soon as their last one completes, until
        `end`.

All times in the result are seconds after t0 on this process's clock.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time


class Run:
    def __init__(self, schedule):
        self.s = schedule
        self.t0 = float(schedule["t0"])
        self.limit = float(schedule.get("request_limit_s", 60.0))
        self.stop = threading.Event()
        self.lock = threading.Lock()
        self.records = []
        self.measured_left = sum(
            1 for r in schedule["requests"] if r["phase"] == "measured")
        self.all_measured_done = threading.Event()
        if not self.measured_left:
            self.all_measured_done.set()

    def now(self):
        return time.monotonic() - self.t0

    def one_request(self, req):
        """POST /generate and read the NDJSON stream line by line, taking
        the clock at every token."""
        rec = {"id": req["id"], "phase": req["phase"],
               "due": req.get("due"), "sent": None, "first": None,
               "token_times": [], "tokens": [], "http": None,
               "status": None, "error": None,
               "budget": req["max_new_tokens"]}
        conn = http.client.HTTPConnection(self.s["host"], self.s["port"],
                                          timeout=self.limit)
        body = json.dumps({"prompt": req["prompt"],
                           "max_new_tokens": req["max_new_tokens"],
                           "stream": True})
        try:
            rec["sent"] = self.now()
            conn.request("POST", "/generate", body=body,
                         headers={"Content-Type": "application/json",
                                  "Connection": "close"})
            resp = conn.getresponse()
            rec["http"] = resp.status
            while True:
                line = resp.readline()
                if not line:
                    break
                t = self.now()
                ev = json.loads(line)
                if "token" in ev:
                    if not rec["token_times"]:
                        rec["first"] = t
                    rec["token_times"].append(t)
                    rec["tokens"].append(ev["token"])
                elif ev.get("restart"):
                    rec["token_times"], rec["tokens"] = [], []
                    rec["first"] = None
                elif ev.get("done") or "status" in ev or "error" in ev:
                    rec["status"] = ev.get("status") or ev.get("error")
                    break
                if t - rec["sent"] > self.limit:
                    rec["error"] = "passed the request limit"
                    break
                if self.stop.is_set() and req["phase"] != "measured":
                    rec["status"] = "aborted"
                    break
        except Exception as e:  # noqa: BLE001 — recorded, judged by the reader
            rec["error"] = f"{type(e).__name__}: {e}"
        finally:
            rec["end"] = self.now()
            conn.close()
        with self.lock:
            self.records.append(rec)
            if req["phase"] == "measured":
                self.measured_left -= 1
                if self.measured_left <= 0:
                    self.all_measured_done.set()

    # -- open loop ---------------------------------------------------------
    def run_open(self):
        threads = []
        for req in sorted(self.s["requests"], key=lambda r: r["due"]):
            if req["phase"] == "cooldown" and self.all_measured_done.is_set():
                break
            wait = req["due"] - self.now()
            if wait > 0:
                if req["phase"] == "cooldown":
                    # the cool-down ends when the last measured request does
                    if self.all_measured_done.wait(wait):
                        break
                else:
                    time.sleep(wait)
            th = threading.Thread(target=self.one_request, args=(req,),
                                  daemon=True)
            th.start()
            threads.append(th)
        self.all_measured_done.wait(self.limit + 5.0)
        self.stop.set()
        for th in threads:
            th.join(self.limit + 5.0)

    # -- closed loop -------------------------------------------------------
    def run_closed(self):
        reqs = self.s["requests"]
        end = float(self.s["end"])
        cursor = [0]

        def client():
            while self.now() < end:
                with self.lock:
                    k = cursor[0]
                    cursor[0] += 1
                req = dict(reqs[k % len(reqs)])
                req["id"] = f"{req['id']}.{k // len(reqs)}"
                self.one_request(req)

        wait = -self.now()
        if wait > 0:
            time.sleep(wait)
        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(int(self.s["clients"]))]
        for th in threads:
            th.start()
        wait = end - self.now()
        if wait > 0:
            time.sleep(wait)
        self.stop.set()
        for th in threads:
            th.join(self.limit + 5.0)


def main(argv):
    schedule_path, result_path = argv[1], argv[2]
    with open(schedule_path) as f:
        schedule = json.load(f)
    run = Run(schedule)
    {"open": run.run_open, "closed": run.run_closed}[schedule["mode"]]()
    with run.lock:
        records = list(run.records)
    with open(result_path, "w") as f:
        json.dump({"mode": schedule["mode"], "requests": records}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
