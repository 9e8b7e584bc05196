"""The per-scope reduction of an ``.xplane.pb`` (``scope_reduce.py``), on a
small recording made on one v5e chip (``benchmarks/testdata/
record_scopes.py``: two scoped programs, one operation left unscoped) and on
synthetic intervals."""
import json
from pathlib import Path

import pytest

from benchmarks import reduction, scope_reduce, trace_reduce

DATA = Path(__file__).resolve().parents[1] / "testdata"


def test_scope_is_the_deepest_mst_component():
    assert scope_reduce.scope_of(
        "jit(block)/mst.kv_pool.regroup/while/body/closed_call/mst.attn.core/dot_general:") == "mst.attn.core"
    assert scope_reduce.scope_of(
        "jit(block)/mst.moe.experts/mst.moe.experts.matmul/mst.moe.experts.gather_dequant/mul") == "mst.moe.experts.gather_dequant"
    assert scope_reduce.scope_of("jit(block)/while/body/add") == "unscoped"
    assert scope_reduce.scope_of("") == "unscoped"
    assert scope_reduce.module_label("jit_block(123)") == ("jit_block", "123")
    assert scope_reduce.is_loop("%while.58 = (s32[]{:T(128)}, bf16[16,1,2048]) while(%t)")
    assert not scope_reduce.is_loop("%fusion.3 = bf16[2] fusion(%while.1)")


def _ev(name, start, end, scope, program="jit_block", nbytes=0, flops=0, loop_name=False):
    return {"name": name, "start": start, "end": end, "scope": scope,
            "program": program, "bytes": nbytes, "flops": flops,
            "loop_name": loop_name}


def test_self_time_bytes_of_leaves_and_where_unscoped_time_ran():
    ps = 1e12  # one second in the events' picoseconds
    events = [
        _ev("%while.1 = x", 0, 10 * ps, "mst.kv_pool.regroup", nbytes=999, loop_name=True),
        _ev("%fusion.2 = y", 1 * ps, 4 * ps, "mst.attn.core", nbytes=30, flops=7),
        _ev("%pad_add_fusion.9 = y", 6 * ps, 7 * ps, "mst.kv_pool.regroup", loop_name=True),
        _ev("%copy.3 = z", 4 * ps, 6 * ps, "unscoped", nbytes=20),
        _ev("%fusion.4 = y", 12 * ps, 13 * ps, "unscoped", "jit_prefill_chunk", nbytes=5),
    ]
    r = scope_reduce.reduce_events(events)
    cells = r["cells"]
    # the while's own 4 s and the 1 s of the fusion that carries its name
    assert cells[("jit_block", "mst.kv_pool.regroup")]["self_s"] == pytest.approx(5.0)
    assert r["named_by_loop"] == {"mst.kv_pool.regroup": pytest.approx(1.0)}
    assert cells[("jit_block", "mst.kv_pool.regroup")]["bytes"] == 0  # a container
    assert cells[("jit_block", "mst.attn.core")] == {
        "self_s": pytest.approx(3.0), "bytes": 30, "flops": 7, "events": 1}
    assert cells[("jit_block", "unscoped")]["self_s"] == pytest.approx(2.0)
    assert cells[("jit_prefill_chunk", "unscoped")]["bytes"] == 5
    assert sum(c["self_s"] for c in cells.values()) == pytest.approx(11.0)  # the union
    assert r["unscoped_under"] == {"mst.kv_pool.regroup": pytest.approx(2.0),
                                   "top level": pytest.approx(1.0)}
    assert r["inside_while_s"] == pytest.approx(6.0)


@pytest.fixture(scope="module")
def recorded():
    return scope_reduce.reduce(DATA / "scopes_tpu.xplane.pb")


def test_tick_spans_count_whole_ticks_only():
    """Synthetic host plane: the tick the trace's start cut open leaves an
    orphan ``mst.emit``, which is not counted."""
    pb = scope_reduce.xplane_pb2()
    space = pb.XSpace()
    plane = space.planes.add(name="/host:CPU")
    names = ["mst.tick", "mst.harvest_wait", "mst.emit", "mst.idle_wait", "PjitFunction(block)"]
    for i, n in enumerate(names, start=1):
        plane.event_metadata[i].name = n
    line = plane.lines.add(name="python3", timestamp_ns=1000)
    ps = 10 ** 12
    for name, start, dur in [("mst.emit", 0, 1), ("mst.tick", 2, 10), ("mst.harvest_wait", 3, 8),
                             ("mst.emit", 11, 1), ("PjitFunction(block)", 2, 1),
                             ("mst.tick", 13, 5), ("mst.idle_wait", 14, 2), ("mst.harvest_wait", 16, 1)]:
        line.events.add(metadata_id=names.index(name) + 1, offset_ps=start * ps, duration_ps=dur * ps)
    spans = scope_reduce.tick_spans(space)
    assert spans == {"ticks": 2, "tick_s": pytest.approx(15.0), "phase_s": {
        "mst.harvest_wait": pytest.approx(9.0), "mst.emit": pytest.approx(1.0),
        "mst.idle_wait": pytest.approx(2.0)}}
    assert scope_reduce.tick_spans(pb.XSpace()) == {}


def test_recorded_scopes_come_out_per_program(recorded):
    expected = json.loads((DATA / "scopes_tpu.expected.json").read_text())
    assert expected["platform"] == "tpu" and recorded["devices"] == 1
    assert recorded["scoped"]
    for program, scopes in expected["scopes"].items():
        got = recorded["programs"][program]
        assert set(got) == set(scopes), (program, set(got))
        assert all(c["self_s"] > 0 for c in got.values())
    # the same device time as the accepted reduction counts, split another way
    busy = trace_reduce.reduce(DATA / "scopes_tpu.xplane.pb")["busy_s"]
    assert recorded["total_s"] == pytest.approx(busy, rel=1e-3)
    assert sum(c["self_s"] for c in recorded["scopes"].values()) == pytest.approx(
        recorded["total_s"])
    # XLA's own flop count sits beside the scope: two matmuls a scanned layer
    n, layers = expected["executions"]["jit_block"], expected["layers"]
    block = recorded["programs"]["jit_block"]
    assert block["mst.attn.core"]["flops"] + block["mst.moe.experts.matmul"]["flops"] == (
        pytest.approx(2 * layers * n * expected["matmul_flops"], rel=0.02))
    assert block["mst.attn.core"]["bytes"] > 0
    # the scanned layers ran inside a while
    scanned = block["mst.attn.core"]["self_s"] + block["mst.moe.experts.matmul"]["self_s"]
    assert recorded["inside_while_s"] == pytest.approx(scanned, rel=1e-6)
    # the sort was left outside every scope, at the top level of its program;
    # so was, by the compiler, the matmul written under mst.head: XLA kept no
    # metadata on the fusion it rewrote it into, and its flops count there
    assert expected["dropped_by_the_compiler"] not in block
    assert block["unscoped"]["flops"] >= n * expected["matmul_flops"]
    assert recorded["unscoped_under"] == {"top level": pytest.approx(
        block["unscoped"]["self_s"] + recorded["programs"]["jit_prefill_chunk"]["unscoped"]["self_s"])}


def test_readers_share_one_reduction_and_return_none_without_scopes(recorded, monkeypatch, tmp_path):
    from benchmarks.run import load_reader

    calls = []

    def fake_run(cmd, **kw):
        calls.append(cmd)

        class Out:
            returncode, stderr = 0, ""
            stdout = json.dumps(recorded)
        return Out()

    monkeypatch.setattr(scope_reduce, "ROOT", tmp_path)
    monkeypatch.setattr(reduction.subprocess, "run", fake_run)
    monkeypatch.setattr(scope_reduce, "_RUNS", {})
    ctx = {"cell": {"name": "some.cell"}, "trace": {"busy_s": 1.0}}
    assert scope_reduce.share(ctx, "mst.moe.experts") is None  # no profile on disk
    assert scope_reduce.for_run(dict(ctx, trace=None)) is None  # an untraced run
    prof = tmp_path / ".bench_work" / "other.cell" / "profile" / "plugins"
    prof.mkdir(parents=True)
    (prof / "x.xplane.pb").write_bytes(b"")
    ctx = {"cell": {"name": "other.cell"}, "trace": {"busy_s": 1.0}}
    shares = [
        scope_reduce.share(ctx, "mst.moe.experts"),
        scope_reduce.share(ctx, "mst.attn."),
        scope_reduce.share(ctx, exact=("mst.kv_pool.regroup",)),
        scope_reduce.share(ctx, exact=("mst.head", "mst.sample", "mst.embed")),
        scope_reduce.share(ctx, exact=("mst.moe.shared", "mst.mlp.dense", "mst.norm")),
        scope_reduce.share(ctx, exact=("mst.moe.router",)),
        scope_reduce.share(ctx, exact=(scope_reduce.UNSCOPED,)),
    ]
    assert len(calls) == 1  # reduced once, kept for the other readers
    assert all(0 <= s <= 100 for s in shares) and sum(shares) == pytest.approx(100.0)
    # the tick's readers go by the spans of the same reduction
    spans = {"ticks": 10, "tick_s": 13.0, "phase_s": {
        "mst.harvest_wait": 12.5, "mst.idle_wait": 0.0, "mst.emit": 0.1}}
    monkeypatch.setitem(scope_reduce._RUNS[str(scope_reduce.profile_dir(ctx))], "tick_spans", spans)
    ctx.update(before={}, after={})
    assert load_reader("layer_metrics", "tick_host_ms.mean")(ctx) == pytest.approx(50.0)
    assert load_reader("layer_metrics", "tick_blocked_share")(ctx) == pytest.approx(100 * 12.5 / 13.0)
    # a program without any mst.* scope or span (the parent commit): nothing to read
    bare = dict(recorded, scoped=False, tick_spans={})
    monkeypatch.setattr(scope_reduce, "_RUNS", {})
    monkeypatch.setattr(reduction.subprocess, "run", lambda cmd, **kw: type(
        "Out", (), {"returncode": 0, "stderr": "", "stdout": json.dumps(bare)})())
    assert scope_reduce.share(ctx, "mst.moe.experts") is None
    assert load_reader("layer_metrics", "tick_blocked_share")(ctx) is None
    assert load_reader("layer_metrics", "tick_host_ms.mean")(ctx) is None
    assert load_reader("layer_metrics", "moe_experts_xla_gb_s")(ctx) is None


def test_counter_readers_take_window_deltas_and_none_without_counters():
    from benchmarks import tick_counters
    from benchmarks.run import load_reader

    before = {'mst_tick_phase_seconds_total{phase="harvest_wait"}': 10.0,
              'mst_tick_phase_seconds_total{phase="emit"}': 1.0,
              'mst_tick_phase_seconds_total{phase="idle_wait"}': 5.0,
              'mst_tick_phase_total{phase="idle_wait"}': 25.0,
              "mst_ticks_total": 35.0,
              "mst_decode_tokens_emitted_total": 100.0,
              'mst_decode_tokens_dropped_total{reason="slot_finished"}': 10.0,
              'mst_decode_tokens_dropped_total{reason="cancelled"}': 0.0,
              'mst_pipeline_drains_total{reason="admit"}': 2.0}
    # the second scrape comes late, the server idle: 40 s and 200 idle ticks
    after = {'mst_tick_phase_seconds_total{phase="harvest_wait"}': 58.0,
             'mst_tick_phase_seconds_total{phase="emit"}': 3.0,
             'mst_tick_phase_seconds_total{phase="idle_wait"}': 45.0,
             'mst_tick_phase_total{phase="idle_wait"}': 225.0,
             "mst_ticks_total": 275.0,
             "mst_decode_tokens_emitted_total": 4900.0,
             'mst_decode_tokens_dropped_total{reason="slot_finished"}': 210.0,
             'mst_decode_tokens_dropped_total{reason="cancelled"}': 256.0,
             'mst_pipeline_drains_total{reason="admit"}': 9.0,
             'mst_pipeline_drains_total{reason="idle"}': 1.0}
    ctx = {"before": before, "after": after, "w0": 0.0, "w1": 51.0}
    assert tick_counters.delta(ctx, "mst_pipeline_drains_total") == {"admit": 7.0, "idle": 1.0}
    assert load_reader("layer_metrics", "decode_delivered_share")(ctx) == pytest.approx(96.0)
    assert load_reader("layer_metrics", "pipeline_drains_in_window")(ctx) == 7.0
    old = {"before": {"mst_requests_total": 1.0}, "after": {"mst_requests_total": 9.0}}
    for name in ("decode_delivered_share", "pipeline_drains_in_window"):
        assert load_reader("layer_metrics", name)(old) is None
    chunk = load_reader("layer_metrics", "prefill_chunk_ms.p50")
    assert chunk({"trace": {"module_seconds": {"jit_prefill_chunk": [0.2, 0.1, 0.3]}}}) == pytest.approx(200.0)
    assert chunk({"trace": {"module_seconds": {"jit_step": [0.2]}}}) is None
    assert chunk({"trace": None}) is None
