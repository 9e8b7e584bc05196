"""The runner end to end on the CPU, on the toy configuration (which is
added to the harness by files alone: configs/tiny-q4.json,
traffic/tiny.json, cells/tiny-q4.tiny.json and the entries of
tests/BENCHMARK.tiny.json). The served path goes through the launcher: the
replaced loader, the program's own server, SSE, and the correctness check
against the plain reference."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY = ROOT / "benchmarks/tests/BENCHMARK.tiny.json"


def run_cell(*extra, benchmark=TINY, workload="tiny-q4.tiny", timeout=600):
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/run.py"), "--benchmark", str(benchmark),
         "--workload", workload, "--seed", str(2**31 + 77), "--seconds", "3",
         *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=timeout,
    )
    return out


@pytest.fixture(scope="module")
def bench():
    return json.loads(TINY.read_text())


def last_json(out):
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_untraced_run_prints_the_end_to_end_metrics(bench):
    out = run_cell("--trace", "0")
    assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
    res = last_json(out)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 9
    assert set(res["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert "compiles_in_window\": 0" in out.stdout


def test_closed_loop_keeps_every_slot_taken_and_ends_with_its_window(bench):
    out = run_cell("--trace", "0", workload="tiny-q4.tiny-sat")
    assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
    res = last_json(out)
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert res["metrics"]["out_tok_s"]["value"] > 0
    facts = json.loads(next(line for line in out.stdout.splitlines()
                            if "after the window: " in line).split("after the window: ")[1])
    assert 1 <= facts["cut_at_window_end"] <= 4  # the streams open when it closed
    assert facts["compiles_in_window"] == 0


def test_traced_run_prints_the_per_layer_metrics_and_a_breakdown(bench):
    out = run_cell("--trace", "1")
    assert out.returncode == 0, out.stderr[-2000:] + out.stdout[-2000:]
    res = last_json(out)
    assert res["correct"] is True
    assert set(res["metrics"]) <= {m["name"] for m in bench["per_layer"]}
    for name in ("gen_late_ms.p95", "queue_wait_ms.p50", "slots_active.mean",
                 "compiles_in_window", "device_idle_share"):
        assert name in res["metrics"]
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > res["device"]["busy_s"]
    assert 1 <= len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_wrong_device_exits_non_zero_and_prints_no_result(tmp_path):
    """The same cell asking for a TPU, on this CPU: no fallback."""
    cfg = json.loads((ROOT / "benchmarks/configs/tiny-q4.json").read_text())
    cfg["bench"]["platform"] = "tpu"
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    table = json.loads(TINY.read_text())
    table["configs"][0]["file"] = str(tmp_path / "cfg.json")
    (tmp_path / "bench.json").write_text(json.dumps(table))
    out = run_cell("--trace", "0", benchmark=tmp_path / "bench.json")
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
    assert "wants 1 tpu device" in out.stderr
