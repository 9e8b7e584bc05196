"""One run of one cell: ``python3 benchmarks/run.py --workload <name> --seed
<n> --seconds <s> --trace <0|1>``.

This process never imports JAX or the program. It starts the launcher (the
process that owns the chip and runs the program's server in-process), waits
for ``/health``, decides ``correct`` from seed-only check requests against
the plain reference while the server is otherwise idle, warms up, then
drives HTTP/SSE traffic from here, open or closed loop as the mix says — the
load generator must not share a GIL with the scheduler thread. Everything a
cell is made of is found by name: ``BENCHMARK.json`` →
``benchmarks/configs/<config>.json``, ``benchmarks/traffic/<traffic>.json``,
``benchmarks/cells/<cell>.json`` (the fixed load: a rate or a number of
clients) and one reader per metric: ``benchmarks/end_to_end/<metric>.py``,
``benchmarks/layer_metrics/<metric>.py``.

The last line of standard output is the one JSON object of the contract.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.util
import json
import math
import signal
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import stats, traffic  # noqa: E402
from benchmarks.config import server_flag  # noqa: E402
from benchmarks.programs import durations  # noqa: E402
from benchmarks.reduction import RunFailed, reduce_profile  # noqa: E402

BENCH_DIR = ROOT / "benchmarks"
LAUNCHER = BENCH_DIR / "launcher.py"  # the tests of the waits put a fake in its place
WORK = ROOT / ".bench_work"  # git-ignored: model dir, logs, profile
T_PROCESS_START = time.monotonic()

HEALTH_TIMEOUT_S = 1000.0  # a cold first run compiles and autotunes
DRAIN_S = 80.0  # after the window, for streams that are still open
CLIENT_LIMIT_S = 120.0  # a closed-loop client drops its stream at the first chunk past the window
SAMPLE_HZ = 10.0  # gauges, traced run only
# The profiler opens at TRACE_FROM of a traced window and stays open until the
# first of TRACE_S seconds and TRACE_BLOCKS decode blocks harvested since
# (BLOCKS_COUNTER in the 10 Hz samples; a program without it: the time bound
# alone). What the stop has to collect and write grows with what the chip ran
# while it was open, not with the seconds: at the accepted program's 1.34 s
# a block the 20 s end the trace; a faster program's trace is shorter and
# holds as much.
TRACE_FROM = 0.4
TRACE_S = 20.0
TRACE_BLOCKS = 16
BLOCKS_COUNTER = "mst_decode_blocks_harvested_total"
# Every wait that scales with the trace, seconds: what a chip run of
# dsv2-lite-q4.decode-sat took at the two bounds above (PR 26; the law over
# four trace lengths is in PERF.md section 7) times the factor beside it.
# Past its limit a wait fails the run and names its stage. Each alone, the
# others as measured, does so inside the 360 s a run may take.
PROFILE_START_LIMIT_S = 10.0  # 0.07 s measured: only a hang gets here
PROFILE_STOP_LIMIT_S = 110.0  # 2.0 x 53.8 s (16 blocks in the trace, 29 MB; 3.4 s a block)
TRACE_REDUCE_LIMIT_S = 30.0  # 5 x 6.0 s (2.9 s + 0.11 s a MB of .xplane.pb)
SCRAPE_LIMIT_S = 30.0  # one GET of /metrics or /compiles: milliseconds when the server lives
OBSERVER_LIMIT_S = 2 * SCRAPE_LIMIT_S + 5  # past the window's end: its two last GETs


def say(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS_START:7.1f}s] {msg}", flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(benchmark: Path, workload: str) -> dict:
    """Everything a cell is made of, found by name from the benchmark's
    table: ``{"bench", "cell", "config_path", "config", "mix", "load"}``."""
    bench = load_json(benchmark)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"unknown workload {workload!r}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT / entry["file"])
    if config["bench"]["chips"] != cell["chips"]:
        raise ValueError("the cell and its configuration disagree on chips")
    return {
        "bench": bench, "cell": cell, "config_path": ROOT / entry["file"],
        "config": config,
        "mix": load_json(BENCH_DIR / "traffic" / f"{cell['traffic']}.json"),
        "load": load_json(BENCH_DIR / "cells" / f"{cell['name']}.json"),
    }


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# --------------------------------------------------------------------------
# the launcher process


class Launcher:
    def __init__(self, config_path: Path, seed: int, trace: int, work: Path):
        self.port, self.control_port = free_port(), free_port()
        work.mkdir(parents=True, exist_ok=True)
        self.log_path = work / "launcher.log"
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, str(LAUNCHER),
             "--config", str(config_path), "--seed", str(seed),
             "--port", str(self.port), "--control-port", str(self.control_port),
             "--work", str(work), "--trace", str(trace)],
            cwd=ROOT, stdout=self._log, stderr=subprocess.STDOUT,
        )

    def tail(self, n: int = 40) -> str:
        return "\n".join(self.log_path.read_text(errors="replace").splitlines()[-n:])

    def _request(self, port: int, method: str, path: str, body=None, timeout=600.0):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        try:
            data = None if body is None else json.dumps(body).encode()
            conn.request(method, path, data, {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, r.read()
        except TimeoutError:
            raise RunFailed(f"{path}: no answer after {timeout:g} s") from None
        except (OSError, http.client.HTTPException) as e:
            raise RunFailed(f"{path}: {type(e).__name__}: {e}") from None
        finally:
            conn.close()

    def server(self, method: str, path: str, body=None, timeout=600.0):
        return self._request(self.port, method, path, body, timeout)

    def control(self, method: str, path: str, body=None, timeout=600.0) -> dict:
        status, data = self._request(self.control_port, method, path, body, timeout)
        if status != 200:
            raise RunFailed(f"control {path}: HTTP {status}: {data[:300]!r}")
        return json.loads(data)

    def metrics(self) -> dict:
        status, data = self.server("GET", "/metrics", timeout=SCRAPE_LIMIT_S)
        if status != 200:
            raise RunFailed(f"/metrics: HTTP {status}")
        return stats.parse_prometheus(data.decode())

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + HEALTH_TIMEOUT_S
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise RunFailed(f"launcher exited with code {rc} during start-up\n{self.tail()}")
            if time.monotonic() > deadline:
                raise RunFailed(f"server not healthy after {HEALTH_TIMEOUT_S:.0f} s\n{self.tail()}")
            try:
                if self.server("GET", "/health", timeout=5.0)[0] == 200:
                    return
            except RunFailed:  # not listening yet
                pass
            time.sleep(0.5)

    def stop(self) -> int:
        """SIGTERM, wait for a clean exit; kill if it does not come. Safe
        to call twice."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


# --------------------------------------------------------------------------
# correctness: served path against the plain reference, before the window


def check_prompts(config: dict, seed: int) -> list[tuple[list[int], int]]:
    """``[(prompt ids, tokens to generate)]``, a function of the seed only."""
    spec = config["bench"]["check"]
    out = []
    for i, n_prompt in enumerate(spec["prompt_tokens"]):
        rng = np.random.default_rng([int(seed), 1000 + i])
        out.append((rng.integers(1, config["vocab_size"], n_prompt).tolist(),
                    int(spec["generate"])))
    return out


def served_logprobs(launcher: Launcher, ids: list[int], n_out: int) -> tuple[list[int], list[dict]]:
    """The served path's greedy tokens and top-10 log-probabilities."""
    status, data = launcher.server("POST", "/v1/completions", {
        "prompt": " ".join(f"w{t}" for t in ids), "max_tokens": n_out,
        "temperature": 0.0, "logprobs": 10,
    })
    if status != 200:
        raise RunFailed(f"check request: HTTP {status}: {data[:300]!r}")
    lp = json.loads(data)["choices"][0]["logprobs"]
    tokens = [int(t) for t in lp["tokens"]]
    tops = [{int(k): float(v) for k, v in row.items()} for row in lp["top_logprobs"]]
    if len(tokens) != n_out or len(tops) != n_out:
        raise RunFailed(f"check request: asked for {n_out} tokens, got {len(tokens)}")
    return tokens, tops


def position_differences(launcher: Launcher, ids, tokens, tops, fault=None,
                         pad_to: int = 0) -> dict:
    """Per generated position, served minus reference log-probability for
    each of the served top-10 ids, under the same context (the prompt plus
    the served path's own earlier tokens: teacher forcing, so a flipped
    argmax cannot derail the comparison), and how many of the two top-10
    sets coincide."""
    seq = list(ids) + tokens[:-1]
    rows = [len(ids) - 1 + j for j in range(len(tokens))]
    wanted = [sorted(t) for t in tops]
    if any(len(w) != len(wanted[0]) for w in wanted):
        raise RunFailed("check request: ragged top-logprobs rows")
    ref = launcher.control("POST", "/reference", {
        "ids": seq, "rows": rows, "ids_wanted": wanted, "fault": fault,
        "pad_to": pad_to,
    })
    deltas, overlap = [], []
    for j, top in enumerate(tops):
        deltas.append([top[t] - ref["logprobs_at_wanted"][j][k]
                       for k, t in enumerate(wanted[j])])
        overlap.append(len(set(top) & set(ref["top_ids"][j][: len(top)])))
    return {"deltas": deltas, "overlap": overlap}


def summarize_check(per_prompt: list[dict], tol: dict) -> dict:
    """The absolute statistic. ``rms``: root mean square of served minus
    reference log-probability over every served top-10 id of every compared
    position — rounding noise and a routing flip at a few positions move it
    little, a wrong computation moves all of it. ``high``: the
    ``high_quantile`` over positions of the largest |difference| at a
    position, a looser cap against a fault that hits few positions. A maximum
    over positions is not used: on random weights it fails on some seeds and
    passes on others (ISSUE 23, Motivation 1)."""
    deltas = [row for p in per_prompt for row in p["deltas"]]
    worst = [max(abs(d) for d in row) for row in deltas]
    overlap = [o for p in per_prompt for o in p["overlap"]]
    flat = [d for row in deltas for d in row]
    out = {
        "positions": len(worst),
        "rms": math.sqrt(sum(d * d for d in flat) / len(flat)),
        "median": stats.percentile(worst, 50),
        "high": stats.percentile(worst, 100 * tol["high_quantile"]),
        "max": max(worst),
        "mean_overlap": sum(overlap) / len(overlap),
    }
    out["ok"] = bool(
        out["rms"] <= tol["rms_tol"] and out["high"] <= tol["high_tol"]
        and out["mean_overlap"] >= tol["min_mean_overlap"]
    )
    return out


def control_share(clean: list[dict], wrong: list[dict]) -> float:
    """How much of a deliberately wrong reference's signature the served
    path carries. Under one context the wrong variant moves the reference's
    log-probabilities by ``f = wrong - clean`` (the served path cancels out
    of the two differences: ``f = d_clean - d_wrong``); the served path's own
    error is ``d_clean``. The share is the projection ``sum(f * d_clean) /
    sum(f * f)``: about 0 for a served path that computes the clean model
    (its rounding noise does not line up with the fault), about 1 for one
    that has this fault, whatever the seed's noise floor is, since the floor
    is in both differences. At 0.5 the served path is as near to the wrong
    reference as to the clean one: ``share <= 0.5`` is ``rms(d_wrong) >=
    rms(d_clean)``."""
    num = den = 0.0
    for pc, pw in zip(clean, wrong):
        for rc, rw in zip(pc["deltas"], pw["deltas"]):
            for dc, dw in zip(rc, rw):
                f = dc - dw
                num += f * dc
                den += f * f
    return num / den if den else 0.0


def run_check(launcher: Launcher, config: dict, seed: int, extra_faults=(),
              raw: bool = False) -> dict:
    """``{"clean": {...}, <fault>: {...}, "ok": verdict, "seconds": ...}``.
    The verdict: the clean comparison within the configuration's absolute
    tolerances, and the served path nearer to the clean reference than to
    each of the configuration's ``controls`` (share at most
    ``max_control_share``). The controls run in every run, so every run
    shows that the check would have caught them on its seed."""
    t0 = time.monotonic()
    served = []
    for ids, n_out in check_prompts(config, seed):
        tokens, tops = served_logprobs(launcher, ids, n_out)
        served.append((ids, tokens, tops))
    t1 = time.monotonic()
    tol = config["bench"]["check"]
    controls = tuple(tol.get("controls", ()))
    # one padded length for every check prompt: one compiled reference
    pad_to = max(len(ids) + len(tokens) for ids, tokens, _ in served)
    out, per_fault = {}, {}
    for fault in dict.fromkeys((None, *controls, *extra_faults)):
        per_fault[fault] = [position_differences(launcher, *s, fault=fault, pad_to=pad_to)
                            for s in served]
        out[fault or "clean"] = summarize_check(per_fault[fault], tol)
        if fault is not None:
            out[fault]["share"] = control_share(per_fault[None], per_fault[fault])
        if raw:
            out[fault or "clean"]["deltas"] = [
                [[round(d, 5) for d in row] for row in p["deltas"]]
                for p in per_fault[fault]]
    out["ok"] = bool(out["clean"]["ok"] and all(
        out[c]["share"] <= tol["max_control_share"] for c in controls))
    out["seconds"] = {"served": t1 - t0, "reference": time.monotonic() - t1}
    return out


# --------------------------------------------------------------------------
# one streamed request


def stream_request(port: int, req: traffic.Planned, due_abs: float,
                   stop_at: float | None = None) -> dict:
    """POST a streaming completion and time its chunks on this side of the
    SSE stream. Times are ``time.monotonic()``. Past ``stop_at`` the stream
    is dropped at its next chunk and the record says ``cut``: neither a
    success nor a failure (a closed loop ends with its window)."""
    rec = {"due": due_abs, "prompt_tokens": req.prompt_tokens,
           "asked": req.output_tokens, "ok": False, "error": None, "cut": False,
           "sent": time.monotonic(), "first": None, "last": None, "done": None,
           "got": 0, "chunks": []}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300.0)
    try:
        conn.request("POST", "/v1/completions", json.dumps({
            "prompt": req.prompt, "max_tokens": req.output_tokens,
            "temperature": 0.0, "stream": True,
        }).encode(), {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}"
            resp.read()
            return rec
        finish, saw_done = None, False
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic()
            if stop_at is not None and now >= stop_at:
                rec["cut"] = True
                return rec
            payload = line[6:].strip()
            if payload == b"[DONE]":
                saw_done = True
                break
            chunk = json.loads(payload)
            if "error" in chunk:
                rec["error"] = str(chunk["error"])[:200]
                continue
            choice = chunk["choices"][0]
            text = choice.get("text") or ""
            n = len(text.split())
            if n:
                if rec["first"] is None:
                    rec["first"] = now
                rec["last"] = now
                rec["got"] += n
                rec["chunks"].append((now, n))
            if choice.get("finish_reason"):
                finish = choice["finish_reason"]
        rec["done"] = time.monotonic()
        if rec["error"] is None:
            if not saw_done:
                rec["error"] = "stream did not end in [DONE]"
            elif finish != "length":
                rec["error"] = f"finish_reason {finish!r}"
            elif rec["got"] != req.output_tokens:
                rec["error"] = f"{rec['got']} tokens, asked {req.output_tokens}"
            else:
                rec["ok"] = True
    except (OSError, http.client.HTTPException, ValueError, KeyError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


# --------------------------------------------------------------------------
# the measured window


class Profiler(threading.Thread):
    """The launcher's ``jax.profiler``, opened and closed from a thread of
    its own: the stop collects and writes the whole trace before it answers
    (tens of seconds), and neither call may hold up the observer's timeline.
    It opens when the thread starts and closes when ``close`` is set."""

    def __init__(self, launcher: Launcher):
        super().__init__(name="bench-profiler", daemon=True)
        self.launcher = launcher
        self.close = threading.Event()
        self.t_on = self.t_off = self.stop_s = None
        self.error = None

    def run(self) -> None:
        try:
            self.launcher.control("POST", "/profile/start", timeout=PROFILE_START_LIMIT_S)
            self.t_on = time.monotonic()
            self.close.wait()
            self.t_off = time.monotonic()
            self.launcher.control("POST", "/profile/stop", timeout=PROFILE_STOP_LIMIT_S)
            self.stop_s = time.monotonic() - self.t_off
        except Exception as e:  # noqa: BLE001 — surfaced by finish()
            self.error = e

    def finish(self) -> None:
        """Wait for the stop's answer. The two calls carry their own limits,
        so the join's is only a backstop."""
        self.close.set()
        self.join(timeout=PROFILE_START_LIMIT_S + PROFILE_STOP_LIMIT_S + 5)
        if self.is_alive():
            raise RunFailed("profiler: its thread did not end")
        if self.error is not None:
            raise RunFailed(f"profiler: {self.error}")


class Observer(threading.Thread):
    """Scrapes ``/metrics`` and the compile count at the window's two edges,
    on its own timeline whatever else is outstanding. In the traced run it
    also samples the gauges at ``SAMPLE_HZ`` from edge to edge, and decides
    from the samples when the profiler closes."""

    def __init__(self, launcher: Launcher, w0: float, w1: float, trace: bool):
        super().__init__(name="bench-observer", daemon=True)
        self.launcher, self.w0, self.w1 = launcher, w0, w1
        self.profiler = Profiler(launcher) if trace else None
        self.before = self.after = None
        self.compiles_before = self.compiles_after = None
        self.t_after = None  # when the second /metrics scrape answered
        self.samples: list[dict] = []
        self.blocks_traced = None  # decode blocks harvested while the profiler was open
        self.closed_by = None  # the bound that closed it: "blocks", "seconds" or "window"
        self.error = None

    def _sleep_until(self, t: float) -> None:
        d = t - time.monotonic()
        if d > 0:
            time.sleep(d)

    def _compiles(self) -> dict:
        return self.launcher.control("GET", "/compiles", timeout=SCRAPE_LIMIT_S)

    def _sample_and_trace(self) -> None:
        prof = self.profiler
        t_open = self.w0 + TRACE_FROM * (self.w1 - self.w0)
        blocks_at_open = None
        while (now := time.monotonic()) < self.w1:
            if now >= t_open and prof.ident is None:
                prof.start()
            m = self.launcher.metrics()
            blocks = stats.scalar(m, BLOCKS_COUNTER)
            self.samples.append({
                "t": now, "blocks_harvested": blocks,
                "slots_active": stats.scalar(m, "mst_batch_slots_active"),
                "pages_in_use": stats.scalar(m, "mst_kv_pool_pages_in_use"),
                "queue_depth": stats.scalar(m, "mst_batch_queue_depth"),
            })
            if prof.t_on is not None and now >= prof.t_on and not prof.close.is_set():
                if blocks is not None:
                    if blocks_at_open is None:
                        blocks_at_open = blocks
                    self.blocks_traced = blocks - blocks_at_open
                if self.blocks_traced is not None and self.blocks_traced >= TRACE_BLOCKS:
                    self.closed_by = "blocks"
                elif now >= t_open + TRACE_S:
                    self.closed_by = "seconds"
                elif now >= self.w1 - 0.5:
                    self.closed_by = "window"
                if self.closed_by:
                    prof.close.set()
            time.sleep(max(0.0, 1.0 / SAMPLE_HZ - (time.monotonic() - now)))

    def run(self) -> None:
        try:
            self._sleep_until(self.w0)
            self.before, self.compiles_before = self.launcher.metrics(), self._compiles()
            if self.profiler is not None:
                self._sample_and_trace()
            self._sleep_until(self.w1)
            self.after = self.launcher.metrics()
            self.t_after = time.monotonic()
            self.compiles_after = self._compiles()
        except Exception as e:  # noqa: BLE001 — surfaced by finish()
            self.error = e
        finally:
            if self.profiler is not None:
                self.profiler.close.set()

    def finish(self) -> None:
        """Both scrapes are there when this returns, and the profiler has
        written its trace; a ``RunFailed`` that names the stage otherwise."""
        self.join(timeout=max(0.0, self.w1 - time.monotonic()) + OBSERVER_LIMIT_S)
        if self.is_alive():
            raise RunFailed(f"observer: no second scrape {OBSERVER_LIMIT_S:g} s after the window")
        if self.error is not None:
            raise RunFailed(f"observer: {self.error}")
        if self.profiler is not None:
            if self.profiler.ident is None:
                raise RunFailed("profiler: the window ended before it opened")
            self.profiler.finish()


UNSENT = {"ok": False, "cut": False, "sent": None, "first": None, "last": None,
          "done": None, "got": 0, "chunks": []}


def send_open_loop(pool, port: int, planned, t_base: float, w1: float) -> list[dict]:
    """Every request at its due time, whatever the server does; then wait
    ``DRAIN_S`` for the streams still open."""
    futures = []
    for req in planned:
        d = t_base + req.due - time.monotonic()
        if d > 0:
            time.sleep(d)
        futures.append((req, pool.submit(stream_request, port, req, t_base + req.due)))
    deadline = w1 + DRAIN_S
    records = []
    for req, fut in futures:
        try:
            records.append(fut.result(timeout=max(0.1, deadline - time.monotonic())))
        except TimeoutError:
            records.append({**UNSENT, "due": t_base + req.due,
                            "error": "not finished when the drain ended",
                            "asked": req.output_tokens,
                            "prompt_tokens": req.prompt_tokens})
    return records


def send_closed_loop(pool, port: int, planned, clients: int, w1: float) -> list[dict]:
    """``clients`` clients, each sending the next ready request the moment
    its last one ended, until the window closes; streams open then are
    dropped at their next chunk (``cut``). A request is due when its client
    is free to send it."""
    ready = iter(planned)
    lock = threading.Lock()
    records: list[dict] = []

    def client() -> None:
        while time.monotonic() < w1:
            with lock:
                req = next(ready, None)
            if req is None:
                raise RunFailed("the mix's ready requests ran out before the window closed")
            rec = stream_request(port, req, time.monotonic(), stop_at=w1)
            with lock:
                records.append(rec)
            if rec["error"]:
                time.sleep(0.2)  # a refusing server must not be hammered

    for fut in [pool.submit(client) for _ in range(clients)]:
        try:
            fut.result(timeout=max(0.1, w1 - time.monotonic()) + CLIENT_LIMIT_S)
        except TimeoutError:
            raise RunFailed(f"closed loop: a client had not returned {CLIENT_LIMIT_S:g} s "
                            "after the window") from None
    return sorted(records, key=lambda r: r["due"])


def drive(launcher: Launcher, planned: list[traffic.Planned], lead: float,
          seconds: float, trace: bool, clients: int | None = None):
    """Drive the planned requests, open loop or (``clients``) closed;
    returns ``(records of the window, records of the lead-in, observer, w0,
    w1)``."""
    t_base = time.monotonic() + 0.2
    w0, w1 = t_base + lead, t_base + lead + seconds
    observer = Observer(launcher, w0, w1, trace)
    observer.start()
    pool = ThreadPoolExecutor(max_workers=128, thread_name_prefix="bench-req")
    try:
        if clients:
            records = send_closed_loop(pool, launcher.port, planned, clients, w1)
        else:
            records = send_open_loop(pool, launcher.port, planned, t_base, w1)
        observer.finish()
    finally:
        # a stream that never ends would hold the pool open: the caller
        # stops the server, which ends every open socket
        pool.shutdown(wait=False, cancel_futures=True)
    in_window = [r for r in records if w0 <= r["due"] < w1]
    lead_in = [r for r in records if r["due"] < w0]
    return in_window, lead_in, observer, w0, w1


def load_reader(kind: str, name: str):
    """The reader of one metric: ``benchmarks/<kind>/<name>.py``'s ``read``."""
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", default=str(ROOT / "BENCHMARK.json"),
                    help="the benchmark's table (tests pass a tiny one)")
    ap.add_argument("--check-only", action="store_true",
                    help="decide `correct`, print the statistic, stop")
    ap.add_argument("--faults", default="",
                    help="with --check-only: further wrong variants of the "
                         "reference, comma-separated, beside the configuration's controls")
    args = ap.parse_args(argv)

    try:
        found = load_cell(Path(args.benchmark), args.workload)
    except (KeyError, ValueError) as e:
        print(e, file=sys.stderr)
        return 2
    bench, cell, config, mix = (found[k] for k in ("bench", "cell", "config", "mix"))
    config_path, load = found["config_path"], found["load"]
    clients = int(load["clients"]) if mix.get("arrivals") == "closed" else None

    work = WORK / cell["name"]
    launcher = Launcher(config_path, args.seed, args.trace, work)
    try:
        launcher.wait_healthy()
        device = launcher.control("GET", "/device")
        say(f"server up on {device['count']} x {device['kind']} ({device['platform']})")

        faults = tuple(f for f in args.faults.split(",") if f)
        t_up = time.monotonic() - T_PROCESS_START
        check = run_check(launcher, config, args.seed, faults, raw=args.check_only)
        if args.check_only:
            launcher.stop()
            print(json.dumps({"seed": args.seed, "server_up_s": t_up, "check": check}))
            return 0 if check["ok"] else 1
        say("check " + json.dumps(check))

        # warm-up: the streamed path's programs (decode block without
        # log-probabilities, first-token sampler), on a two-chunk prompt
        rng = np.random.default_rng([args.seed, 999])
        warm = traffic.Planned(0.0, 300, 20, traffic.words(rng, 300, config["vocab_size"]))
        rec = stream_request(launcher.port, warm, time.monotonic())
        if not rec["ok"]:
            raise RunFailed(f"warm-up request failed: {rec['error']}")

        planned, lead = traffic.plan(mix, load, args.seconds, args.seed,
                                     config["vocab_size"],
                                     int(server_flag(config, "--max-seq", 4096)))
        say(f"{len(planned)} requests ready, " + (f"{clients} closed-loop clients" if clients
            else f"{load['rate']} /s") + f": lead-in {lead} s, window {args.seconds} s")
        setup_s = time.monotonic() - T_PROCESS_START + 0.2 + lead
        say(f"set-up: server up {t_up:.1f} s, check {check['seconds']['served']:.1f} s served + "
            f"{check['seconds']['reference']:.1f} s reference, warm-up "
            f"{setup_s - lead - 0.2 - t_up - sum(check['seconds'].values()):.1f} s, lead-in {lead:.1f} s")
        in_window, lead_in, obs, w0, w1 = drive(launcher, planned, lead, args.seconds,
                                                bool(args.trace), clients)

        # facts that depend on timing: earlier lines, not `correct`
        after_drain = launcher.metrics()
        for _ in range(0 if clients else 20):  # a finished slot frees its pages on the next tick
            if not stats.scalar(after_drain, "mst_kv_pool_pages_in_use", 0):
                break
            time.sleep(0.25)
            after_drain = launcher.metrics()
        device = launcher.control("GET", "/device")
        failed = [r for r in in_window if not r["ok"] and not r["cut"]]
        gaps = [b[0] - a[0] for r in in_window
                for a, b in zip(r["chunks"], r["chunks"][1:])]
        late = [(r["sent"] - r["due"]) * 1e3 for r in in_window if r["sent"]]
        compiles = obs.compiles_after["count"] - obs.compiles_before["count"]
        say(f"second scrape {obs.t_after - w1:+.3f} s from the window's end" + (
            f"; {len(obs.samples)} gauge samples, the last {w1 - obs.samples[-1]['t']:.3f} s "
            "before it" if obs.samples else ""))
        say("after the window: " + json.dumps({
            "server_failed_delta": stats.scalar(obs.after, "mst_requests_failed_total", 0)
            - stats.scalar(obs.before, "mst_requests_failed_total", 0),
            "client_failed": len(failed),
            "first_errors": sorted({r["error"] for r in failed})[:3],
            "preemptions_delta": stats.scalar(obs.after, "mst_preemptions_total", 0)
            - stats.scalar(obs.before, "mst_preemptions_total", 0),
            "pool_pages_in_use_after_drain": stats.scalar(after_drain, "mst_kv_pool_pages_in_use"),
            "compiles_in_window": compiles,
            "raw_chunk_gap_ms_p99": stats.percentile(gaps, 99) * 1e3 if gaps else None,
            "gen_late_ms_p95": stats.percentile(late, 95) if late else None,
            "completed_in_window": sum(1 for r in in_window + lead_in
                                       if r["ok"] and w0 <= r["done"] < w1),
            "cut_at_window_end": sum(1 for r in in_window + lead_in if r["cut"]),
            "lead_in_failed": sum(1 for r in lead_in if not r["ok"] and not r["cut"]),
        }))
        rc = launcher.stop()
        if rc != 0:
            raise RunFailed(f"launcher exited with code {rc}\n{launcher.tail()}")

        kind = "per_layer" if args.trace else "end_to_end"
        reported = [m for m in bench[kind]
                    if "workloads" not in m or cell["name"] in m["workloads"]]
        result = {"correct": check["ok"], "attempted": len(in_window),
                  "failed": len(failed), "metrics": {},
                  "device": {k: device[k] for k in
                             ("platform", "kind", "count", "memory_peak_bytes")}}
        # what every reader is handed; the trace and the gauges' samples
        # exist in the traced run only
        ctx = {"records": in_window, "all_records": in_window + lead_in, "w0": w0,
               "w1": w1, "setup_s": setup_s, "before": obs.before, "after": obs.after,
               "samples": obs.samples, "device": device, "compiles_in_window": compiles,
               "config": config, "cell": cell, "mix": mix, "load": load, "trace": None}
        if args.trace:
            trace, secs = reduce_profile("trace reduction", BENCH_DIR / "trace_reduce.py",
                                         work / "profile", TRACE_REDUCE_LIMIT_S)
            ctx["trace"], ctx["reduce_seconds"] = trace, {"trace_reduce": secs}
            result["device"]["busy_s"] = trace["busy_s"]
            result["device"]["window_s"] = trace["window_s"]
            result["breakdown"] = trace["breakdown"]
            for line in trace.get("notes", []):
                say(line)
        say("end to end: " + json.dumps({  # also in a traced run, for the reader of its log
            m["name"]: load_reader("end_to_end", m["name"])(ctx) for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]}))
        for meta in reported:
            value = load_reader("layer_metrics" if args.trace else "end_to_end",
                                meta["name"])(ctx)
            if value is not None:
                result["metrics"][meta["name"]] = {"value": value, "unit": meta["unit"]}
        if args.trace:  # what the next reader needs to see the margin of each limit above
            prof = obs.profiler
            say("profile: " + json.dumps({
                "open_s": prof.t_off - prof.t_on, "closed_by": obs.closed_by,
                "blocks_harvested_while_open": obs.blocks_traced,
                "decode_blocks_in_trace": len(durations(trace, "decode_block")),
                "stop_s": prof.stop_s,
                "xplane_bytes": sum(f.stat().st_size for f in
                                    (work / "profile").glob("**/*.xplane.pb")),
                "reduce_s": ctx["reduce_seconds"],
            }))
        print(json.dumps(result), flush=True)
        return 0
    except RunFailed as e:
        print(f"run failed: {e}", file=sys.stderr)
        return 1
    finally:
        launcher.stop()


if __name__ == "__main__":
    sys.exit(main())
