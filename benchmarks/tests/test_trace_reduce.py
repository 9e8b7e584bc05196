"""The reduction from ``.xplane.pb`` to numbers, on a small trace recorded
on one v5e chip (``benchmarks/testdata/record.py``) and on synthetic
intervals."""
import json
from pathlib import Path

import pytest

from benchmarks import programs, trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"


def test_union_merges_overlaps_and_keeps_gaps():
    total, merged = trace_reduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)])
    assert merged == [(0, 20), (30, 45), (100, 101)]
    assert total == pytest.approx(36e-9)


def test_labels():
    assert trace_reduce.op_label("fusion.123") == "fusion"
    assert trace_reduce.op_label(
        "%fusion.485 = (bf16[16,6,1408]{2,1,0:T(8,128)(2,1)S(1)}, bf16[2]) fusion(%p.1)") == "fusion"
    assert trace_reduce.op_label("%while.58 = (s32[]{:T(128)}, bf16[16,1,2048]) while(%t)") == "while"
    assert trace_reduce.op_label("%quant_matmul.83 = bf16[256,1408]{1,0} custom-call(%a)") == "quant_matmul"
    assert trace_reduce.op_label("quant_gemv_pipelined") == "quant_gemv_pipelined"
    assert trace_reduce.module_label("jit_block(123456789)") == "jit_block"


def test_nested_operations_count_their_own_time_only():
    events = [("%while.1 = x", 0, 100), ("%fusion.2 = y", 10, 30),
              ("%fusion.3 = y", 30, 60), ("%copy.4 = z", 200, 250)]
    secs = trace_reduce.self_seconds(events)
    assert secs["while"] == pytest.approx(50e-9)
    assert secs["fusion"] == pytest.approx(50e-9)
    assert secs["copy"] == pytest.approx(50e-9)
    assert sum(secs.values()) == pytest.approx(150e-9)  # the union, no double count


def test_idle_gap_is_named_by_the_span_that_covers_most_of_it():
    host = [("outer", 0, 1000), ("mst.decode_block", 100, 200), ("other", 150, 160)]
    assert trace_reduce.covering_span(host, (110, 190)) == "mst.decode_block"
    assert trace_reduce.covering_span(host, (400, 500)) == "outer"
    assert trace_reduce.covering_span(host, (2000, 2100)) == "no-span"


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.reduce(DATA / "tiny_tpu.xplane.pb")


def test_recorded_tpu_trace_reduces_to_the_programs_that_ran(recorded):
    expected = json.loads((DATA / "tiny_tpu.expected.json").read_text())
    assert expected["platform"] == "tpu"
    assert recorded["devices"] == 1
    for name, n in expected["executions"].items():
        assert len(recorded["module_seconds"][name]) == n
    # every program's time is device time inside the window
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    ran = sum(sum(v) for v in recorded["module_seconds"].values())
    assert recorded["busy_s"] <= ran * 1.001
    # the matmul program dominates, and its gaps carry the host's span
    ops = dict(recorded["breakdown"]["device_ops"])
    assert max(ops, key=ops.get) in ("fusion", "convolution", "dot_general", "dot")
    assert any(name == "mst.decode_block" for name, _ in recorded["breakdown"]["idle_gaps"])
    assert len(recorded["breakdown"]["idle_gaps"]) <= 10


def test_program_durations_are_found_by_name(recorded):
    # the recording's functions are named like the served path's programs
    assert programs.median_seconds(recorded, "decode_block") > max(recorded["module_seconds"]["jit_step"]) > 0
    assert programs.decode_step_seconds(recorded) == pytest.approx(
        programs.median_seconds(recorded, "decode_block") / programs.PROGRAMS["decode_steps_per_block"])
    assert programs.median_seconds(None, "decode_block") is None
