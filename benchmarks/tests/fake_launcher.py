"""A stand-in for ``benchmarks/launcher.py`` with no JAX and no model, for
the tests of the runner's waits: the same arguments and the same two ports
(server: ``/health``, ``/metrics``, ``/v1/completions``; control:
``/device``, ``/compiles``, ``/reference``, ``/profile/start|stop``). A
served token is ``w1``, a token every 20 ms; the reference agrees with the
served log-probabilities to the last digit. ``FAKE_PROFILE_STOP`` says what
``/profile/stop`` does: ``ok`` (the default) puts the recorded chip trace of
``benchmarks/testdata`` where the profiler would have written, ``sleep:<s>``
waits first, ``hang`` never answers. Up in a tenth of a second, ended by
SIGTERM with exit code 0."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / "tiny_tpu.xplane.pb"
TOP = {str(i): -0.1 * i for i in range(1, 11)}  # every position's served top-10
BLOCKS_PER_S = 10.0
T0 = time.monotonic()


class Quiet(BaseHTTPRequestHandler):
    def log_message(self, *args):
        pass

    def send_json(self, payload, code=200, content_type="application/json"):
        data = payload if isinstance(payload, bytes) else json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def body(self) -> dict:
        return json.loads(self.rfile.read(int(self.headers.get("Content-Length") or 0)) or b"{}")


class Server(Quiet):
    def do_GET(self):
        if self.path == "/health":
            self.send_json({"status": "ok"})
        elif self.path == "/metrics":
            self.send_json((
                "mst_batch_slots_active 4\nmst_kv_pool_pages_in_use 8\n"
                "mst_batch_queue_depth 0\nmst_requests_failed_total 0\n"
                f"mst_decode_blocks_harvested_total {int((time.monotonic() - T0) * BLOCKS_PER_S)}\n"
            ).encode(), content_type="text/plain")
        else:
            self.send_json({"error": "unknown path"}, 404)

    def do_POST(self):
        req = self.body()
        n = int(req["max_tokens"])
        if not req.get("stream"):
            self.send_json({"choices": [{"logprobs": {
                "tokens": [1] * n, "top_logprobs": [TOP] * n}}]})
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        try:
            for i in range(n):
                time.sleep(0.02)
                chunk = {"choices": [{"text": "w1 ", "finish_reason":
                                      "length" if i == n - 1 else None}]}
                self.wfile.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
                self.wfile.flush()
            self.wfile.write(b"data: [DONE]\n\n")
        except OSError:  # the client cut its stream at the window's end
            pass


def make_control(profile_dir: Path):
    class Control(Quiet):
        def do_GET(self):
            if self.path == "/device":
                self.send_json({"platform": "cpu", "kind": "cpu", "count": 1,
                                "memory_peak_bytes": 1})
            elif self.path == "/compiles":
                self.send_json({"count": 0, "seconds": 0.0})
            else:
                self.send_json({"error": "unknown path"}, 404)

        def do_POST(self):
            req = self.body()
            if self.path == "/reference":
                rows = len(req["rows"])
                self.send_json({
                    "top_ids": [[int(k) for k in TOP]] * rows,
                    "top_logprobs": [list(TOP.values())] * rows,
                    "logprobs_at_wanted": [[TOP[str(t)] for t in wanted]
                                           for wanted in req["ids_wanted"]]})
            elif self.path == "/profile/stop":
                mode = os.environ.get("FAKE_PROFILE_STOP", "ok")
                if mode == "hang":
                    time.sleep(3600)
                if mode.startswith("sleep:"):
                    time.sleep(float(mode.split(":")[1]))
                out = profile_dir / "plugins" / "profile" / "recorded"
                out.mkdir(parents=True, exist_ok=True)
                shutil.copy(RECORDED, out / "fake.xplane.pb")
                self.send_json({})
            else:
                self.send_json({})

    return Control


def main() -> int:
    ap = argparse.ArgumentParser()
    for name in ("--config", "--seed", "--port", "--control-port", "--work", "--trace"):
        ap.add_argument(name, required=True)
    args = ap.parse_args()
    profile_dir = Path(args.work) / "profile"
    shutil.rmtree(profile_dir, ignore_errors=True)
    servers = [ThreadingHTTPServer(("127.0.0.1", int(args.port)), Server),
               ThreadingHTTPServer(("127.0.0.1", int(args.control_port)),
                                   make_control(profile_dir))]
    for s in servers:
        s.daemon_threads = True
        threading.Thread(target=s.serve_forever, daemon=True).start()
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    while True:
        time.sleep(3600)


if __name__ == "__main__":
    sys.exit(main())
