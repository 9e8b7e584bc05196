"""The readers of the tick's device-empty account and of the dispatch
counters, on a recorded pair of scrapes (``testdata/tick_scrapes.json``) and
on a scrape without the families (a program from before them)."""
import json
from pathlib import Path

import pytest

from benchmarks import run, stats

ROOT = Path(__file__).resolve().parents[2]
READERS = ("device_empty_share", "join_empty_ms.mean", "join_ms.mean", "slow_path_dispatches")


@pytest.fixture()
def ctx():
    rec = json.loads((ROOT / "benchmarks/testdata/tick_scrapes.json").read_text())
    return {"w0": rec["w0"], "w1": rec["w1"],
            "before": stats.parse_prometheus(rec["before"]),
            "after": stats.parse_prometheus(rec["after"])}


def read(name, ctx):
    return run.load_reader("layer_metrics", name)(ctx)


def test_every_new_reader_has_its_entry_and_moves_out_tok_s():
    entries = {m["name"]: m for m in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert m["moves"] == "out_tok_s" and "workloads" not in m and m["better"] == "lower"
        assert m["source"] == "program_counter"
        assert m["layer"] == ("kernels" if name == "slow_path_dispatches" else "scheduler")


def test_readers_on_a_recorded_pair_of_scrapes(ctx, capsys):
    # deltas of mst_device_empty_seconds_total, idle_wait (0.0) left out
    empty = {"admit": 0.000189, "assign_slot": 0.002690, "dispatch": 0.000764,
             "emit": 0.001401, "housekeeping": 0.0, "other": 0.000243,
             "prefill_chunk": 0.003180, "handoff": 0.0, "harvest_wait": 0.0, "kv_import": 0.0}
    total = sum(empty.values())
    assert read("device_empty_share", ctx) == pytest.approx(100 * total / 0.719239, rel=1e-6)
    # three joins reached decode between the scrapes: 2.515087 - 1.643254 s
    assert read("join_empty_ms.mean", ctx) == pytest.approx(1e3 * total / 3, rel=1e-6)
    assert read("join_ms.mean", ctx) == pytest.approx(1e3 * 0.871833 / 3, rel=1e-6)
    # the CPU has no ragged kernel: one traced call took the XLA path; the
    # moe family is not in the scrape (its ops were never loaded)
    assert read("slow_path_dispatches", ctx) == 1.0
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines() if ln.startswith("[empty]")]  # once a run
    assert "prefill_chunk 0.003, assign_slot 0.003, emit 0.001" in line  # largest first
    assert "idle_wait 0.000; joins 3.0;" in line and "prefill_chunk entries 3.0" in line
    assert "harvest_wait 0.000" in line


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_families_reads_nothing(ctx, name, capsys):
    drop = ("mst_device_empty_seconds_total", "mst_join_seconds", "_dispatch_total")
    for scrape in ("before", "after"):
        ctx[scrape] = {k: v for k, v in ctx[scrape].items() if not any(d in k for d in drop)}
    assert ctx["after"]  # the older families are still there
    assert read(name, ctx) is None
    assert "[empty]" not in capsys.readouterr().out


@pytest.mark.parametrize("name", ["join_empty_ms.mean", "join_ms.mean"])
def test_a_window_without_a_join_reads_nothing(ctx, name):
    for key in ("mst_join_seconds_count", "mst_join_seconds_sum"):
        ctx["after"][key] = ctx["before"][key]
    assert read(name, ctx) is None
    assert read("device_empty_share", ctx) > 0  # the share needs no join
