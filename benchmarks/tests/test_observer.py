"""The waits of a traced run. The observer against a fake launcher object:
its second scrape belongs to the window's end whatever the profiler is doing,
the gauges are sampled to the end, and the profiler closes at the first of
its two bounds. Then the whole runner (``run.main``) against a fake launcher
process (``fake_launcher.py``: no JAX, no model): a wait that outlasts its
limit ends in exit code 1 and one line that names the stage, not in a
traceback."""
import json
import time
from pathlib import Path

import pytest

from benchmarks import run

ROOT = Path(__file__).resolve().parents[2]
TINY = ROOT / "benchmarks/tests/BENCHMARK.tiny.json"


class FakeLauncher:
    """What ``run.Observer`` and ``run.Profiler`` call, timed."""

    def __init__(self, blocks_per_s=None, stop_takes=0.0):
        self.blocks_per_s, self.stop_takes = blocks_per_s, stop_takes
        self.t0 = time.monotonic()
        self.called = {}  # control path → when

    def metrics(self):
        m = {"mst_batch_slots_active": 4.0, "mst_kv_pool_pages_in_use": 8.0,
             "mst_batch_queue_depth": 0.0}
        if self.blocks_per_s is not None:
            m[run.BLOCKS_COUNTER] = float(int((time.monotonic() - self.t0) * self.blocks_per_s))
        return m

    def control(self, method, path, body=None, timeout=600.0):
        self.called.setdefault(path, time.monotonic())
        if path == "/profile/stop":
            if self.stop_takes > timeout:
                time.sleep(timeout)
                raise run.RunFailed(f"{path}: no answer after {timeout:g} s")
            time.sleep(self.stop_takes)
        return {"count": 0, "seconds": 0.0}


@pytest.fixture
def short_trace(monkeypatch):
    """A 2 s window whose profiler opens at 0.8 s and may stay open 0.5 s."""
    monkeypatch.setattr(run, "TRACE_S", 0.5)

    def observe(launcher):
        w0 = time.monotonic() + 0.05
        obs = run.Observer(launcher, w0, w0 + 2.0, True)
        obs.start()
        return obs
    return observe


def test_second_scrape_is_at_the_windows_end_while_the_stop_is_still_out(short_trace):
    obs = short_trace(FakeLauncher(stop_takes=1.5))  # answers 0.8 s after the window
    obs.join(timeout=5)
    assert not obs.is_alive() and obs.error is None
    assert obs.profiler.is_alive() and obs.profiler.stop_s is None  # the stop is still out
    assert obs.after is not None and obs.compiles_after is not None
    assert 0 <= obs.t_after - obs.w1 < 0.5
    assert obs.w1 - obs.samples[-1]["t"] < 0.25  # the gauges reach the window's end
    assert len(obs.samples) >= 15
    obs.finish()
    assert obs.profiler.stop_s >= 1.5 and obs.closed_by == "seconds"


def test_profiler_closes_at_the_block_bound_when_it_comes_first(short_trace, monkeypatch):
    monkeypatch.setattr(run, "TRACE_BLOCKS", 16)
    launcher = FakeLauncher(blocks_per_s=100.0)  # 16 blocks in 0.16 s
    obs = short_trace(launcher)
    obs.finish()
    assert obs.closed_by == "blocks" and 16 <= obs.blocks_traced <= 40
    open_s = obs.profiler.t_off - obs.profiler.t_on
    assert 0.15 <= open_s < 0.45, open_s  # not the 0.5 s of the time bound


@pytest.mark.parametrize("blocks_per_s", [None, 1.0], ids=["no counter", "slow blocks"])
def test_profiler_closes_at_the_time_bound_otherwise(short_trace, blocks_per_s):
    launcher = FakeLauncher(blocks_per_s=blocks_per_s)
    obs = short_trace(launcher)
    obs.finish()
    assert obs.closed_by == "seconds"
    assert obs.blocks_traced is None if blocks_per_s is None else obs.blocks_traced <= 1
    t_open = obs.w0 + run.TRACE_FROM * (obs.w1 - obs.w0)
    assert 0 <= launcher.called["/profile/start"] - t_open < 0.2
    assert 0 <= launcher.called["/profile/stop"] - (t_open + run.TRACE_S) < 0.25


def test_a_stop_past_its_limit_fails_the_run_by_name(short_trace, monkeypatch):
    monkeypatch.setattr(run, "PROFILE_STOP_LIMIT_S", 0.3)
    obs = short_trace(FakeLauncher(stop_takes=60.0))
    with pytest.raises(run.RunFailed, match=r"profiler: /profile/stop: no answer after 0.3 s"):
        obs.finish()
    assert obs.after is not None  # the window's own scrapes were not held up


def test_an_observer_that_has_not_scraped_fails_the_run_by_name(monkeypatch):
    monkeypatch.setattr(run, "OBSERVER_LIMIT_S", 0.2)
    launcher = FakeLauncher()
    launcher.metrics = lambda: time.sleep(5) or {}
    w0 = time.monotonic()
    obs = run.Observer(launcher, w0, w0 + 0.1, False)
    obs.start()
    with pytest.raises(run.RunFailed, match="observer: no second scrape 0.2 s after the window"):
        obs.finish()


# --------------------------------------------------------------------------
# the whole runner against the fake launcher process


def run_main(monkeypatch, tmp_path, capfd, stop="ok", **limits):
    monkeypatch.setattr(run, "LAUNCHER", ROOT / "benchmarks/tests/fake_launcher.py")
    monkeypatch.setattr(run, "WORK", tmp_path)
    monkeypatch.setenv("FAKE_PROFILE_STOP", stop)
    for name, value in limits.items():
        monkeypatch.setattr(run, name, value)
    rc = run.main(["--benchmark", str(TINY), "--workload", "tiny-q4.tiny-sat",
                   "--seed", str(2**31 + 5), "--seconds", "1.5", "--trace", "1"])
    out = capfd.readouterr()
    return rc, out.out, out.err


def test_traced_run_with_a_slow_stop_scrapes_at_the_windows_end(monkeypatch, tmp_path, capfd):
    """The stop answers 2 s after the window; the run's log shows the second
    scrape at the window's end and one line with every margin."""
    rc, out, err = run_main(monkeypatch, tmp_path, capfd, stop="sleep:3")
    assert rc == 0, err[-2000:] + out[-2000:]
    lines = out.splitlines()
    scrape = next(line for line in lines if "second scrape" in line)
    assert abs(float(scrape.split("second scrape ")[1].split(" s")[0])) < 0.5
    profile = json.loads(next(line for line in lines if "profile: " in line).split("profile: ")[1])
    assert profile["stop_s"] >= 3 and profile["xplane_bytes"] > 0
    assert profile["closed_by"] in ("blocks", "seconds", "window")
    assert profile["reduce_s"]["trace_reduce"] > 0 and profile["decode_blocks_in_trace"] >= 1
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["slots_active.mean"]["value"] == 4.0
    assert result["device"]["busy_s"] > 0


@pytest.mark.parametrize("stop, limits, message", [
    ("hang", {"PROFILE_STOP_LIMIT_S": 0.5},
     "run failed: profiler: /profile/stop: no answer after 0.5 s"),
    ("ok", {"TRACE_REDUCE_LIMIT_S": 0.05},
     "run failed: trace reduction: not done after 0.05 s"),
], ids=["stop never returns", "reduction exceeds its limit"])
def test_a_wait_past_its_limit_is_exit_code_1_and_one_line(monkeypatch, tmp_path, capfd,
                                                           stop, limits, message):
    rc, out, err = run_main(monkeypatch, tmp_path, capfd, stop=stop, **limits)
    assert rc == 1
    assert message in err and "Traceback" not in err
    assert not any(line.startswith("{") for line in out.splitlines())  # no result line


def test_scope_reduction_past_its_limit_is_a_run_failed(monkeypatch, tmp_path):
    """The readers' own reduction (``scope_reduce.for_run``) raises the
    runner's failure, not ``subprocess.TimeoutExpired``."""
    from benchmarks import scope_reduce

    profile = tmp_path / "profile"
    profile.mkdir()
    (profile / "x.xplane.pb").write_bytes((ROOT / "benchmarks/testdata/tiny_tpu.xplane.pb").read_bytes())
    monkeypatch.setattr(scope_reduce, "SCOPE_REDUCE_LIMIT_S", 0.05)
    monkeypatch.setattr(scope_reduce, "profile_dir", lambda ctx: profile)
    with pytest.raises(run.RunFailed, match="scope reduction: not done after 0.05 s"):
        scope_reduce.for_run({"trace": {"busy_s": 1.0}, "cell": {"name": "x"}})
