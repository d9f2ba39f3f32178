"""The load generator's process against a stub NDJSON server (standard
library on both sides; no jax anywhere)."""
import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from preset_tree import ROOT
from perfbench.harness import score

LOADGEN = os.path.join(ROOT, "perfbench", "harness", "loadgen.py")


class _Stub(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def do_POST(self):  # noqa: N802
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body["prompt"][0] == 99:
            payload = json.dumps({"error": "overloaded",
                                  "status": "rejected"}).encode()
            self.send_response(429)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
            return
        self.send_response(200)
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        try:
            for i in range(body["max_new_tokens"]):
                time.sleep(0.01)
                self._chunk({"token": 7, "index": i})
            self._chunk({"done": True, "status": "completed"})
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            pass

    def _chunk(self, obj):
        data = (json.dumps(obj) + "\n").encode()
        self.wfile.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()


@pytest.fixture(scope="module")
def stub():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    th = threading.Thread(target=srv.serve_forever, daemon=True)
    th.start()
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()


def _run(tmp_path, schedule):
    sched, result = tmp_path / "s.json", tmp_path / "r.json"
    schedule = dict(schedule, host="127.0.0.1", request_limit_s=10.0,
                    t0=time.monotonic() + 0.3)
    sched.write_text(json.dumps(schedule))
    subprocess.run([sys.executable, LOADGEN, str(sched), str(result)],
                   check=True, timeout=60)
    return json.loads(result.read_text())["requests"]


def test_the_generator_imports_nothing_but_the_standard_library():
    code = ("import sys, runpy; sys.argv=['x']\n"
            "try:\n runpy.run_path(%r)\nexcept BaseException: pass\n"
            "bad=[m for m in ('jax','numpy','paddle_tpu') if m in sys.modules]"
            "\nprint(bad)" % LOADGEN)
    out = subprocess.run([sys.executable, "-S", "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.stdout.strip() == "[]", out.stdout + out.stderr


def test_open_loop_paces_measures_and_cools_down(stub, tmp_path):
    reqs = [{"id": f"r{k}", "phase": "ramp", "due": 0.05 * k,
             "prompt": [1], "max_new_tokens": 3} for k in range(4)]
    reqs += [{"id": f"m{k}", "phase": "measured", "due": 0.2 + 0.05 * k,
              "prompt": [1], "max_new_tokens": 5} for k in range(10)]
    reqs += [{"id": f"c{k}", "phase": "cooldown", "due": 0.7 + 0.05 * k,
              "prompt": [1], "max_new_tokens": 3} for k in range(200)]
    recs = _run(tmp_path, {"mode": "open", "port": stub, "requests": reqs})
    out = score.score_open_loop(recs, 10.0)
    assert out["attempted"] == 10 and out["failed"] == 0
    assert out["n_gaps"] == 40
    assert 5.0 <= out["itl_p95_ms"] < 100.0
    assert out["late_p99_ms"] < 100.0
    for r in recs:
        if r["phase"] != "cooldown":
            assert r["sent"] >= r["due"]
            assert len(r["tokens"]) == r["budget"]
    # the cool-down stops once the last measured request has finished
    assert sum(r["phase"] == "cooldown" for r in recs) < 50


def test_closed_loop_keeps_each_client_busy_until_the_end(stub, tmp_path):
    reqs = [{"id": f"c{k}", "phase": "closed", "prompt": [1],
             "max_new_tokens": 4} for k in range(3)]
    recs = _run(tmp_path, {"mode": "closed", "port": stub, "clients": 2,
                           "end": 1.0, "requests": reqs})
    out = score.score_closed_loop(recs, 0.2, 1.0)
    assert out["failed"] == 0 and out["completed"] >= 10
    # two clients, ~45 ms a request of four tokens: some 170 tokens/s
    assert 60 <= out["out_tok_s"] <= 400
    ids = [r["id"] for r in recs]
    assert len(ids) == len(set(ids)) and "c0.1" in ids   # cycles the set
    assert max(r["sent"] for r in recs) < 1.0


def test_a_refused_request_is_a_failed_one(stub, tmp_path):
    reqs = [{"id": "m0", "phase": "measured", "due": 0.0, "prompt": [99],
             "max_new_tokens": 4},
            {"id": "m1", "phase": "measured", "due": 0.0, "prompt": [1],
             "max_new_tokens": 4}]
    recs = _run(tmp_path, {"mode": "open", "port": stub, "requests": reqs})
    by_id = {r["id"]: r for r in recs}
    assert by_id["m0"]["http"] == 429 and score.request_failed(by_id["m0"])
    assert not score.request_failed(by_id["m1"])
    out = score.score_open_loop(recs, 10.0)
    assert (out["attempted"], out["failed"]) == (2, 1)
    assert out["ttft_p90_ms"] == 10000.0
