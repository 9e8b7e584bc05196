"""Request-lifecycle tracing (tracing.py): flight recorder semantics,
Chrome ``trace_event`` export, fault-site post-mortems, the /admin/trace
HTTP surface, and the composed-stack acceptance timeline.

The cost contract is tested from both ends: ``--trace off`` adds zero
recorder state even while faults fire and real requests stream (the
static half of the same contract is mstcheck rule MST112), and with
tracing on, one timeline spans the full disagg + prefix-store +
cold-spill + async-sched path with no unexplained gaps and a span-level
TTFT that matches the client's measurement."""

import http.client
import json
import statistics
import threading
import time

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu import tracing
from mlx_sharding_tpu.analysis.lifecycle import KNOWN_FAULT_SITES
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.disagg import DisaggCoordinator
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.prefix_store import PrefixStore
from mlx_sharding_tpu.replicas import ReplicaSet
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.testing import faults
from mlx_sharding_tpu.tracing import (
    MAX_SNAPSHOTS,
    MAX_SPANS_PER_TRACE,
    SPAN_TYPES,
    RequestTrace,
    Tracer,
)
from tests.helpers import hard_timeout

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)


@pytest.fixture(autouse=True)
def _reset():
    yield
    faults.disarm()
    tracing.configure("off")


# ------------------------------------------------------------ unit layer
def test_off_mode_never_allocates():
    t = Tracer(mode="off")
    assert not t.enabled
    assert t.begin("r") is None
    t.finish(None)  # None-tolerant teardown
    s = t.stats()
    assert s["live"] == 0 and s["ring"] == 0 and s["begun"] == 0


def test_sampling_is_deterministic_one_in_n():
    t = Tracer(mode="sample", sample_n=4)
    got = [t.begin(f"r{i}") for i in range(12)]
    assert [i for i, g in enumerate(got) if g is not None] == [0, 4, 8]
    assert t.stats()["begun"] == 12 and t.stats()["sampled"] == 3


def test_ring_is_bounded_and_lookup_spans_live_and_ring():
    t = Tracer(mode="on", buffer=4)
    live = t.begin("still-live")
    for i in range(10):
        tr = t.begin(f"r{i}")
        tr.add("prefill", 0.0, 1.0)
        t.finish(tr)
    s = t.stats()
    assert s["ring"] == 4 and s["live"] == 1
    assert t.get("r3") is None  # cycled out of the ring
    assert t.get("r9")["done"] is True
    assert t.get("still-live")["done"] is False
    assert t.get("nope") is None and t.export_request("nope") is None
    t.finish(live)


def test_span_cap_truncates_instead_of_growing():
    tr = RequestTrace("r")
    for _ in range(MAX_SPANS_PER_TRACE + 5):
        tr.add("decode_tick", 0.0, 1.0)
    f = tr.freeze()
    assert len(f["spans"]) == MAX_SPANS_PER_TRACE
    assert f["dropped"] == 5


def test_bind_tolerates_none_and_restores():
    assert tracing.current() is None
    tr = RequestTrace("r")
    with tracing.bind(tr):
        assert tracing.current() is tr
        with tracing.bind(None):
            assert tracing.current() is None
        assert tracing.current() is tr
    assert tracing.current() is None


def test_chrome_export_shape():
    """One process per request, one named lane per span type, ph=X spans
    with microsecond ts/dur, ph=i marks — the contract chrome://tracing
    and Perfetto actually load."""
    t = Tracer(mode="on")
    tr = t.begin("req-x")
    tr.add("prefill", t.epoch + 0.01, t.epoch + 0.02, tokens=4)
    tr.point("first_token")
    t.finish(tr)
    out = t.export_request("req-x")
    evs = out["traceEvents"]
    json.dumps(out)  # must be JSON-serializable as-is
    lanes = {e["args"]["name"]: e["tid"]
             for e in evs if e["name"] == "thread_name"}
    assert set(lanes) == set(SPAN_TYPES)
    span = next(e for e in evs if e["name"] == "prefill")
    assert span["ph"] == "X"
    assert span["ts"] == pytest.approx(10000.0, abs=2.0)
    assert span["dur"] == pytest.approx(10000.0, abs=2.0)
    assert span["args"]["request_id"] == "req-x"
    assert span["tid"] == lanes["prefill"]
    mark = next(e for e in evs if e["name"] == "first_token")
    assert mark["ph"] == "i"


def test_snapshots_bounded_and_preserve_cycled_traces():
    t = Tracer(mode="on", buffer=2)
    victim = t.begin("victim")
    victim.point("fault:somewhere")
    for i in range(MAX_SNAPSHOTS + 3):
        t.snapshot(f"r{i}")
    snaps = t.snapshots()
    assert len(snaps) == MAX_SNAPSHOTS
    assert snaps[-1]["reason"] == f"r{MAX_SNAPSHOTS + 2}"
    # cycle the victim clean out of live+ring: the snapshot still serves it
    t.finish(victim)
    for i in range(3):
        t.finish(t.begin(f"filler{i}"))
    assert t.get("victim") is not None
    assert t.export_request("victim")["traceEvents"]
    dump = t.export_dump()
    assert any(s["reason"].startswith("r") for s in dump["snapshots"])


# ------------------------------------------- fault sites -> post-mortems
@pytest.mark.parametrize("site", sorted(KNOWN_FAULT_SITES))
def test_every_fault_site_stamps_timeline_and_snapshots(site):
    """For EVERY registered fault site: when the armed fault fires against
    a bound request, the victim's timeline carries the degradation mark
    and the flight recorder auto-snapshots under ``fault:<site>`` — the
    trace survives the incident even after the ring cycles."""
    tracer = tracing.configure("on", buffer=8)
    tr = tracing.begin("victim")
    faults.arm(site, exc=RuntimeError, times=1)
    with tracing.bind(tr):
        with pytest.raises(RuntimeError):
            faults.inject(site)
    assert f"fault:{site}" in tr.mark_names()
    snaps = tracer.snapshots()
    assert snaps and snaps[-1]["reason"] == f"fault:{site}"
    frozen = [f for f in snaps[-1]["traces"] if f["request_id"] == "victim"]
    assert frozen, "victim trace missing from the auto-snapshot"
    assert any(m[0] == f"fault:{site}" for m in frozen[0]["marks"])
    tracing.finish(tr)
    # and the snapshot is reachable through the Chrome dump summary
    assert "victim" in tracer.export_dump()["snapshots"][-1]["requests"]


def test_fault_firing_with_tracing_off_adds_zero_state():
    tracer = tracing.configure("off")
    faults.arm("scheduler.tick", exc=RuntimeError, times=1)
    with pytest.raises(RuntimeError):
        faults.inject("scheduler.tick")
    s = tracer.stats()
    assert s == dict(s, live=0, ring=0, snapshots=0, begun=0)


# ------------------------------------------------- composed-stack layer
def _mk_batcher(model, params, dev_idx, **kw):
    eng = PipelineEngine(
        model, params,
        make_mesh(pp=1, devices=jax.devices()[dev_idx:dev_idx + 1]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, pool_pages=10, page_size=8,
    )
    return ContinuousBatcher(eng, decode_block=3, **kw)


@pytest.fixture(scope="module")
def composed_stack():
    """The acceptance geometry: disaggregated prefill/decode pools, a
    prefix store on the admission path, cold-slot spill with prefetch and
    the async scheduler on the decode pool."""
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    store = PrefixStore(host_bytes=64 << 20)
    decode = _mk_batcher(model, params, 1, async_sched="on", overcommit=True,
                         spill_bytes=64 << 20, spill_cold_after=2,
                         kv_prefetch="on")
    co = DisaggCoordinator(
        ReplicaSet([_mk_batcher(model, params, 0, prefix_store=store)],
                   role="prefill", prefix_store=store),
        ReplicaSet([decode], role="decode"),
        prefix_store=store,
    )
    # warm both pools (prefill, handoff, decode compiles) so the traced
    # requests measure the serving path, not first-use jit compilation —
    # same prompt length as the traced request (the first-token graph is
    # shape-bucketed) but a different first page, so the store can't
    # short-circuit the traced handoff with a full-prefix hit. Two passes
    # with DISTINCT prefixes: the second request of a geometry compiles
    # its own (slot-reuse) variant of the sampling graph, and a repeated
    # prompt would store-hit and bypass the prefill pool instead
    for lo in (1, 101):
        for _ in co.generate_step(list(range(lo, lo + 10)), max_tokens=6):
            pass
    yield co, decode
    co.close()
    store.close()


def _covered_gaps(frozen, t_start, t_end):
    """Max uncovered gap inside [t_start, t_end] given the trace's spans
    (marks count as zero-width coverage points)."""
    ivs = [(t0, t1) for _, t0, t1, _ in frozen["spans"]]
    ivs += [(t, t) for _, t, _ in frozen["marks"]]
    ivs = sorted((max(t0, t_start), min(t1, t_end)) for t0, t1 in ivs
                 if t1 >= t_start and t0 <= t_end)
    gap, cursor = 0.0, t_start
    for t0, t1 in ivs:
        if t0 > cursor:
            gap = max(gap, t0 - cursor)
        cursor = max(cursor, t1)
    return max(gap, t_end - cursor)


@hard_timeout(240)
def test_composed_stack_timeline_end_to_end(composed_stack):
    """One trace spans the whole composed path — queue wait, store lookup,
    prefill, handoff export/transfer, decode ticks — with no unexplained
    gap bigger than a scheduler tick, and the trace's own TTFT (submit
    mark to first_token mark) matches the client-measured TTFT."""
    co, _ = composed_stack
    tracer = tracing.configure("on", buffer=16)
    tr = tracing.begin("acc-1")
    t_req = time.perf_counter()
    ttft = [None]
    toks = []
    # prompt >= one page (page_size=8) so the store's LPM probe actually
    # runs and self-records its prefix_lookup span
    prompt = [3, 17, 42, 5, 9, 11, 2, 8, 4, 6]
    for t, _ in co.generate_step(prompt, max_tokens=24, _trace=tr):
        if ttft[0] is None:
            ttft[0] = time.perf_counter() - t_req
        toks.append(t)
    tracing.finish(tr)
    assert len(toks) == 24
    frozen = tracer.get("acc-1")
    assert frozen is not None and frozen["done"]
    spans = {s[0] for s in frozen["spans"]}
    marks = {m[0] for m in frozen["marks"]}
    assert {"queue_wait", "prefix_lookup", "prefill", "handoff_export",
            "handoff_transfer", "decode_tick"} <= spans
    assert {"submit", "first_token", "finish"} <= marks
    # Both limits are sized from what the trace itself measures: the median
    # period of its decode ticks is what a tick takes on THIS machine NOW.
    # Under six xdist workers a decode block of this stack takes 0.1-0.2 s of
    # a shared CPU, and the fixed limits of a quiet machine (0.05 s, 0.25 s)
    # failed on the hole in front of the decode pool's first tick: its span
    # is the harvest's wait, so the block's own compute before the harvest
    # begins is uncovered, one tick long by construction.
    starts = sorted(s[1] for s in frozen["spans"] if s[0] == "decode_tick")
    tick = statistics.median(b - a for a, b in zip(starts, starts[1:]))
    # span-level TTFT vs the client's measurement: the client's stamp is
    # taken by a thread that has to be scheduled first, a tick late at most
    t_submit = next(t for n, t, _ in frozen["marks"] if n == "submit")
    t_first = next(t for n, t, _ in frozen["marks"] if n == "first_token")
    assert abs((t_first - t_submit) - ttft[0]) < max(0.05, tick)
    # the timeline is contiguous: no uncovered hole bigger than a few ticks
    t_finish = next(t for n, t, _ in frozen["marks"] if n == "finish")
    assert _covered_gaps(frozen, t_submit, t_finish) < max(0.25, 4 * tick)
    # and the whole thing exports as loadable Chrome JSON
    json.dumps(tracer.export_request("acc-1"))


@hard_timeout(240)
def test_composed_stack_spill_wake_on_timeline(composed_stack):
    """A stalled consumer cold-spills the decode slot; the same request's
    trace shows the residency round-trip: cold_spill, wake, and the
    decode ticks resuming after it."""
    co, decode = composed_stack
    tracing.configure("on", buffer=16)
    tr = tracing.begin("acc-spill")
    base = decode.spill_stats()["cold_spills"]
    stall = threading.Event()
    toks: list = []

    def consume():
        for i, (t, _) in enumerate(
                co.generate_step([7, 7, 2, 1], max_tokens=40, _trace=tr)):
            toks.append(t)
            # stall a few tokens INTO phase 2: the coordinator submits the
            # decode resume lazily on the pull after the first token, so a
            # stall at i=0 would block before the decode slot even exists
            if i == 4:
                stall.wait()

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if decode.spill_stats()["cold_spills"] > base:
            break
        time.sleep(0.02)
    assert decode.spill_stats()["cold_spills"] > base, "slot never went cold"
    stall.set()
    th.join(timeout=120)
    assert not th.is_alive(), "stream hung after wake"
    tracing.finish(tr)
    assert len(toks) == 40
    frozen = tracing.get_tracer().get("acc-spill")
    marks = [m[0] for m in frozen["marks"]]
    assert "cold_spill" in marks and "wake" in marks
    # decode kept ticking after the wake
    t_wake = next(t for n, t, _ in frozen["marks"] if n == "wake")
    assert any(n == "decode_tick" and t0 >= t_wake
               for n, t0, _, _ in frozen["spans"])


@hard_timeout(240)
def test_composed_stack_off_mode_zero_ring_growth(composed_stack):
    """The off-mode cost contract, dynamic half: real requests through the
    full composed stack leave the recorder completely untouched — no live
    traces, no ring entries, not even a begin() counted."""
    co, _ = composed_stack
    tracer = tracing.configure("off")
    toks = [t for t, _ in co.generate_step([9, 4, 4, 6], max_tokens=12)]
    assert len(toks) == 12
    s = tracer.stats()
    assert s["live"] == 0 and s["ring"] == 0 and s["begun"] == 0


# ----------------------------------------------------------- HTTP layer
@hard_timeout(240)
def test_admin_trace_endpoints(tmp_path):
    """The served surface: every response carries X-MST-Request-Id; with
    tracing on, /admin/trace/{id} replays that request as Chrome JSON
    (including sse_write spans for a streamed request), /admin/trace/dump
    returns the ring + snapshot summary, and with tracing off the
    endpoints 404 with a hint instead of an empty 200."""
    from mlx_sharding_tpu.server.openai_api import ModelProvider, make_server
    from tests.test_tokenizer_utils import ByteTokenizer

    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    batcher = _mk_batcher(model, params, 2)
    provider = ModelProvider.__new__(ModelProvider)
    provider.default_model = "tiny"
    provider.trust_remote_paths = False
    provider._key = None
    provider._load_lock = threading.Lock()
    provider._set("tiny", batcher, ByteTokenizer())
    tracing.configure("on", buffer=16)
    srv = make_server(provider, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request(
            "POST", "/v1/completions",
            json.dumps({"prompt": "hi", "max_tokens": 5}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rid = resp.getheader("X-MST-Request-Id")
        resp.read()
        assert rid
        conn.request("GET", f"/admin/trace/{rid}")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        names = {e["name"] for e in body["traceEvents"]}
        assert "prefill" in names and "decode_tick" in names

        # a streamed request records its SSE writes on the same timeline
        conn.request(
            "POST", "/v1/completions",
            json.dumps({"prompt": "hi", "max_tokens": 4, "stream": True}),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        srid = resp.getheader("X-MST-Request-Id")
        resp.read()
        conn.request("GET", f"/admin/trace/{srid}")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert "sse_write" in {e["name"] for e in body["traceEvents"]}

        conn.request("GET", "/admin/trace/dump")
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.status == 200
        assert "traceEvents" in body and "snapshots" in body

        conn.request("GET", "/admin/trace/not-a-request")
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 404

        tracing.configure("off")
        conn.request("GET", "/admin/trace/dump")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 404 and "--trace" in body
        conn.close()
    finally:
        srv.shutdown()
        batcher.close()


# ------------------------------------------ tick spans on the profiler's clock
def _host_spans(profile_dir):
    """``{name: [stats dict, ...]}`` of the ``mst.*`` events on the host
    plane of the one ``.xplane.pb`` under ``profile_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(profile_dir / "**" / "*.xplane.pb"), recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mst."):
                    out.setdefault(ev.name, []).append(
                        dict(ev.stats, _t0=ev.start_ns,
                             _t1=ev.start_ns + ev.duration_ns))
    return out


@hard_timeout(300)
def test_trace_profile_puts_tick_spans_on_the_profilers_clock(tmp_path):
    """``--trace on --trace-profile`` with ``jax.profiler`` open: the host
    plane holds the tick, its phases and the dispatched block, with the
    arguments that say what each one was (cause, shared identifiers)."""
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    tracing.configure("on", profile=True)
    batcher = _mk_batcher(model, params, 0, async_sched="on")
    try:
        assert batcher._trace_profile
        list(batcher.generate_step([3, 4, 5], max_tokens=4))  # compiles
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        pc0 = time.perf_counter()
        try:
            toks = list(batcher.generate_step(
                [3, 4, 5, 6, 7, 8, 9, 1, 2, 3], max_tokens=11, _trace=tracing.begin("r-prof")))
        finally:
            pc1 = time.perf_counter()
            jax.profiler.stop_trace()
        assert len(toks) == 11
    finally:
        batcher.close()
    spans = _host_spans(tmp_path)
    for name in ("mst.tick", "mst.housekeeping", "mst.admit",
                 "mst.prefill_chunk", "mst.decode_block", "mst.harvest_wait",
                 "mst.emit"):
        assert name in spans, sorted(spans)
    assert "mst.dispatch" not in spans  # the dispatch keeps its old name
    # mst.tick's pc is this process's perf_counter at the tick's entry: the
    # flight recorder's clock, on the profiler's timeline
    pcs = [float(s["pc"]) for s in spans["mst.tick"]]
    assert pcs == sorted(pcs) and pc0 - 1.0 < pcs[0] and pcs[-1] <= pc1
    # What the mechanism guarantees, whatever the machine's load: ``pc`` is
    # read BEFORE its tick's span opens and AFTER the tick before it closed,
    # so ONE offset between the two clocks puts every pc inside that gap.
    # (The gap itself is as long as the scheduler keeps the thread off the
    # CPU there: comparing the two clocks' elapsed times to 5 ms, as this
    # test did, failed under six workers.)
    ticks = spans["mst.tick"]
    assert len(ticks) >= 3
    latest_close = max(a["_t1"] / 1e9 - pc for a, pc in zip(ticks, pcs[1:]))
    earliest_open = min(t["_t0"] / 1e9 - pc for t, pc in zip(ticks, pcs))
    assert latest_close <= earliest_open, (latest_close, earliest_open)
    blocks = spans["mst.decode_block"]
    seqs = [int(s["seq"]) for s in blocks]
    assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
    assert all(int(s["live"]) == 1 and int(s["want_lp"]) == 0 for s in blocks)
    assert all(1 <= int(s["pages"]) <= 3 for s in blocks)  # 10 + 11 tokens, 8 a page
    # every wait names the block it waits for
    assert {int(s["seq"]) for s in spans["mst.harvest_wait"]} <= set(seqs) | {seqs[0] - 1}
    chunks = spans["mst.prefill_chunk"]
    assert [(s["rid"], int(s["pos"]), int(s["n_valid"])) for s in chunks] == [
        ("r-prof", 0, 8), ("r-prof", 8, 2)]
    # phases nest inside their tick
    tick_of = lambda s: [t for t in spans["mst.tick"]  # noqa: E731
                         if t["_t0"] <= s["_t0"] and s["_t1"] <= t["_t1"]]
    # (a tick still open at ``stop_trace`` leaves no span, its closed phases
    # do: under load the scheduler's thread is inside one more tick by then)
    last = max(t["_t1"] for t in spans["mst.tick"])
    nested = [len(tick_of(s)) for s in blocks + chunks if s["_t0"] < last]
    assert len(nested) >= len(blocks + chunks) - 1 and set(nested) == {1}


@hard_timeout(300)
def test_trace_profile_names_the_join_and_the_device_bit(tmp_path):
    """A request joining while another decodes, with ``jax.profiler`` open:
    ``mst.tick`` carries the cumulative device-empty seconds at its entry,
    the drain's ``mst.harvest_wait`` says why it drained, and
    ``mst.assign_slot`` shares its ``rid`` with the join's chunks."""
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    tracing.configure("on", profile=True)
    batcher = _mk_batcher(model, params, 0, async_sched="on")
    try:
        list(batcher.generate_step([3, 4, 5], max_tokens=4))  # compiles
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            first = batcher.generate_step(
                [3, 4, 5], max_tokens=40, _trace=tracing.begin("r-first"))
            head = [next(first) for _ in range(5)]  # decoding, a block ahead
            joined = list(batcher.generate_step(
                [9, 1, 4, 7, 2, 6, 8, 3, 5, 1], max_tokens=6,
                _trace=tracing.begin("r-join")))
            rest = list(first)
        finally:
            jax.profiler.stop_trace()
        snap = batcher.tick_phase_stats()  # later than every captured tick
        assert len(head) + len(rest) == 40 and len(joined) == 6
    finally:
        batcher.close()
    spans = _host_spans(tmp_path)
    # the tick's cumulative empty seconds never fall, rise over the join
    # (its slot claim and the host side of its chunks ran against a drained
    # pipeline) and stay within the account the batcher reports afterwards
    empties = [float(s["empty"]) for s in spans["mst.tick"]]
    assert empties == sorted(empties) and empties[-1] > empties[0]
    assert empties[-1] <= sum(snap["device_empty_seconds"].values())
    claims = {s["rid"]: s for s in spans["mst.assign_slot"]}
    assert set(claims) == {"r-first", "r-join"}
    assert int(claims["r-join"]["slot"]) != int(claims["r-first"]["slot"])
    assert int(claims["r-join"]["pages"]) == 2  # 10 + 6 tokens, 8 a page
    assert int(claims["r-join"]["reused"]) == 0
    assert [s["rid"] for s in spans["mst.prefill_chunk"]] == [
        "r-first", "r-join", "r-join"]
    # the join drained the lookahead block (once to admit, once before its
    # second chunk); a steady harvest names no drain
    drains = [s.get("drain") for s in spans["mst.harvest_wait"]]
    assert {"admit", "prefilling"} <= set(drains) <= {
        None, "admit", "prefilling", "idle"}
    assert drains.count(None) >= 3
    drained = [s for s in spans["mst.harvest_wait"] if s.get("drain") == "admit"]
    claim = claims["r-join"]
    assert any(d["_t1"] <= claim["_t0"] for d in drained)


@hard_timeout(240)
def test_trace_off_constructs_no_annotation(monkeypatch):
    """``--trace off`` (and ``--trace on`` without ``--trace-profile``):
    the tick never builds a ``TraceAnnotation``; its phase table runs."""
    built = []
    monkeypatch.setattr(
        tracing, "profile_span",
        lambda name, **args: built.append(name) or __import__("contextlib").nullcontext())
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    for mode in ("off", "on"):
        tracing.configure(mode)
        batcher = _mk_batcher(model, params, 0, async_sched="on")
        try:
            assert not batcher._trace_profile
            assert len(list(batcher.generate_step([3, 4, 5], max_tokens=7))) == 7
            stats = batcher.tick_phase_stats()
        finally:
            batcher.close()
        assert built == []
        assert stats["blocks_harvested"] >= 2 and stats["ticks"] >= 2
        assert stats["phase_seconds"]["harvest_wait"] > 0.0
    # and with the profile bridge on, the same path does build them
    tracing.configure("on", profile=True)
    batcher = _mk_batcher(model, params, 0, async_sched="on")
    try:
        list(batcher.generate_step([3, 4, 5], max_tokens=4))
    finally:
        batcher.close()
    assert {"mst.tick", "mst.assign_slot", "mst.decode_block",
            "mst.harvest_wait"} <= set(built)


def test_the_shared_experts_gate_has_a_scope_of_its_own_in_the_vocabulary():
    """``mst.moe.shared_gate`` (a shared expert behind a sigmoid gate of its
    own, ``models/qwen3_next.py``) stands beside ``mst.moe.shared`` in the
    flat vocabulary: a sibling, not a refinement — the trace reader goes by
    the DEEPEST ``mst.*`` component, and ``scope_share.mlp_shared_norm`` reads
    ``mst.moe.shared`` by its exact name, so neither reads the other's time."""
    from mlx_sharding_tpu import tracing

    scopes = tracing.MODEL_SCOPES
    assert len(set(scopes)) == len(scopes)
    at = scopes.index("mst.moe.shared_gate")
    assert scopes[at - 1] == "mst.moe.shared" and scopes[at + 1] == "mst.moe.latent"
    assert not "mst.moe.shared_gate".startswith("mst.moe.experts")
    # a gated delta-rule layer of either family opens the same six scopes
    assert [s for s in scopes if s.startswith("mst.kda.")] == [
        "mst.kda.proj", "mst.kda.conv", "mst.kda.gate", "mst.kda.scan",
        "mst.kda.step", "mst.kda.out"]
