"""The scheduler tick accounts for its time and its decode blocks
(``ContinuousBatcher.tick_phase_stats``): phases partition the tick
thread's wall time; every position a dispatched block computes is emitted,
dropped for a reason, or still in flight; every dispatched block is
harvested, abandoned, or in flight; every pipeline drain is counted under
its call site; beside each phase's seconds stands the part of them the
device had nothing to run, and every join that reaches decode is timed
once; and ``/metrics`` renders the families."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu import tracing
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.replicas import ReplicaSet
from mlx_sharding_tpu.scheduler import ContinuousBatcher, _InflightBlock
from mlx_sharding_tpu.testing import faults
from mlx_sharding_tpu.utils.observability import (
    ServingMetrics,
    _render_tick_phases,
)
from tests.helpers import hard_timeout

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)
BLOCK = 4


@pytest.fixture(scope="module")
def engine():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=3, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=20, page_size=8,
    )


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()


def _drive(batcher):
    """A join while another stream decodes, finishes in the middle of a
    block (41 and 26 decode tokens are no multiples of BLOCK) and a cancel."""
    done, decoding = {}, threading.Event()

    def run(i, prompt, n):
        done[i] = []
        for t, _ in batcher.generate_step(prompt, max_tokens=n):
            done[i].append(t)
            if len(done[i]) == 3:
                decoding.set()

    threads = [threading.Thread(target=run, args=(0, [3, 17, 42], 42))]
    threads[0].start()
    assert decoding.wait(timeout=60)  # the second joins a decoding batch
    threads.append(threading.Thread(target=run, args=(1, [9, 1, 4, 7], 27)))
    threads[1].start()
    # the third is cancelled after its second token: the consumer walks
    # away and the slot is reaped on a later tick
    gen = batcher.generate_step([5, 6, 2, 8, 8], max_tokens=40)
    next(gen), next(gen)
    gen.close()
    for th in threads:
        th.join(timeout=120)
        assert not th.is_alive()
    assert len(done[0]) == 42 and len(done[1]) == 27
    _settle(batcher)


def _settle(batcher):
    """Wait for the reap and the last drain to land: no slot active and no
    block between dispatch and harvest (a block the tick has taken out of
    ``_inflight`` to harvest is in neither place for a moment)."""
    def busy():
        s = batcher.tick_phase_stats()
        return batcher.stats()[1] or batcher._inflight is not None or (
            s["blocks_dispatched"] != s["blocks_harvested"] + s["blocks_abandoned"]
        )

    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and busy():
        time.sleep(0.02)
    assert not busy()


def _in_flight(batcher):
    inf = batcher._inflight
    return (1, inf.positions) if isinstance(inf, _InflightBlock) else (0, 0)


def _assert_identities(batcher):
    s = batcher.tick_phase_stats()
    blocks, positions = _in_flight(batcher)
    assert s["blocks_dispatched"] == (
        s["blocks_harvested"] + s["blocks_abandoned"] + blocks
    )
    assert s["positions_computed"] == (
        s["tokens_emitted"] + sum(s["tokens_dropped"].values()) + positions
    )
    return s


@hard_timeout(240)
@pytest.mark.parametrize("mode", ["on", "off"])
def test_blocks_and_tokens_are_all_accounted_for(engine, mode):
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched=mode)
    try:
        t_start = time.perf_counter()
        _drive(batcher)
        s = _assert_identities(batcher)
        wall = time.perf_counter() - t_start
        # 42 + 27 tokens, less the first token of each (prefill's), plus
        # whatever the cancelled stream decoded before it was reaped
        assert s["tokens_emitted"] >= 41 + 26 + 1
        assert s["blocks_harvested"] >= 3
        # a slot that finishes inside a block leaves the rest of it unread
        assert s["tokens_dropped"]["slot_finished"] > 0
        # ... and so does a consumer that walks away, under its own reason:
        # the block in flight when its slot is reaped (none in sync mode)
        assert (s["tokens_dropped"]["cancelled"] > 0) == (mode == "on")
        assert s["tokens_dropped"]["abandoned_block"] == 0
        assert s["positions_computed"] % BLOCK == 0
        assert s["phase_entries"]["dispatch"] == s["blocks_dispatched"]
        assert s["phase_entries"]["harvest_wait"] == s["blocks_harvested"]
        assert s["phase_entries"]["prefill_chunk"] >= 3
        assert s["phase_entries"]["idle_wait"] >= 1
        assert s["ticks"] >= s["blocks_dispatched"]
        # the phases partition the tick thread's time: they sum to the wall
        # time since its loop began (the thread starts with the first
        # request), whatever the mix of work and waiting was
        assert sum(s["phase_seconds"].values()) == pytest.approx(wall, rel=0.05)
        assert set(s["phase_seconds"]) == set(tracing.TICK_PHASES)
        if mode == "on":
            # joins and prefill chunks drain the lookahead block, and the
            # block dispatched past the last finish drains at idle
            d = s["drains"]
            assert d["admit"] + d["prefilling"] >= 1 and d["idle"] >= 1
            assert d["cold"] == d["growth"] == d["migrate"] == 0
        else:
            assert sum(s["drains"].values()) == 0  # nothing ever in flight
        # the one account /metrics exports keeps its keys
        assert set(s) == {
            "path", "ticks", "phase_seconds", "device_empty_seconds",
            "phase_entries", "blocks_dispatched", "blocks_harvested",
            "blocks_abandoned", "positions_computed", "tokens_emitted",
            "tokens_dropped", "drains", "blocks_by_sampler", "join_programs",
            "join_first_reads", "emit_held", "emit_hold_seconds", "emit_holds",
            "program_device_seconds", "program_dispatch_exposed_seconds",
            "program_runs", "program_late", "program_unread_seconds"}
        # a join that drained a decoding batch held the drain's tokens until
        # its chunk was dispatched; the sync tick never holds
        held = s["emit_held"]
        assert set(held) == {"chunk", "tick_end", "fail"} and held["fail"] == 0
        if mode == "on":
            assert held["chunk"] >= 1 and s["emit_holds"] >= 1
            assert 0 < s["emit_hold_seconds"] < wall
        else:
            assert sum(held.values()) == 0 == s["emit_holds"]
            assert s["emit_hold_seconds"] == 0
        assert batcher._held is None
        # every join that reached decode: one claim, one first token, and
        # a dispatch for each of its chunks
        joins = s["join_programs"]
        assert joins["claim"] == joins["finish"] >= 3 and joins["other"] == 0
        assert joins["chunk"] == s["phase_entries"]["prefill_chunk"]
        # each counted once by where its first token was read: behind the
        # block its last chunk was followed by, or (the sync tick) before it
        reads = s["join_first_reads"]
        assert reads == {"behind_block": joins["finish"] * (mode == "on"),
                         "before_block": joins["finish"] * (mode != "on")}
        assert 1 <= s["phase_entries"]["first_token"] <= joins["finish"]
        assert s["path"] == ("async" if mode == "on" else "sync")
        assert set(s["device_empty_seconds"]) == set(tracing.TICK_PHASES)
    finally:
        batcher.close()
    s = batcher.tick_phase_stats()
    assert s["blocks_dispatched"] == s["blocks_harvested"] + s["blocks_abandoned"]


def _stream(batcher, prompt, n, out, started=None):
    """Consume one stream into ``out``; a stream ended early (migrated,
    failed) leaves its exception there instead."""
    try:
        for t, _ in batcher.generate_step(prompt, max_tokens=n):
            out.append(t)
            if started is not None and len(out) == 3:
                started.set()
    except Exception as e:  # noqa: BLE001 — recorded for the caller
        out.append(e)


@hard_timeout(240)
def test_growth_drain_is_counted_under_its_call_site(engine):
    """Over-commit: 3 x (7 + 50) tokens want 24 pages of the pool's 20, so
    growth has to preempt, and only a drained pipeline may."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on",
                                overcommit=True)
    try:
        outs = [[], [], []]
        threads = [
            threading.Thread(
                target=_stream,
                args=(batcher, [3 + i, 17, 42, 5, 9, 11, 2], 50, outs[i]))
            for i in range(3)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
            assert not th.is_alive()
        assert all(len(o) == 50 for o in outs)
        _settle(batcher)
        s = _assert_identities(batcher)
        assert s["drains"]["growth"] >= 1
        assert s["drains"]["migrate"] == s["drains"]["cold"] == 0
    finally:
        batcher.close()


@hard_timeout(240)
def test_migrate_drain_is_counted_under_its_call_site(engine):
    """A stream in steady decode always has its lookahead block in flight
    between two ticks: migrate_out has exactly that one to drain."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        out, started = [], threading.Event()
        th = threading.Thread(
            target=_stream, args=(batcher, [3, 17, 42], 55, out, started))
        th.start()
        assert started.wait(timeout=60)
        batcher.migrate_out(deadline=30.0)
        th.join(timeout=120)
        assert not th.is_alive()
        assert isinstance(out[-1], Exception) and len(out) < 56
        _settle(batcher)
        s = _assert_identities(batcher)
        assert s["drains"]["migrate"] == 1
    finally:
        batcher.close()


@hard_timeout(240)
def test_failed_harvest_counts_its_blocks_abandoned(engine):
    """A harvest that dies drops its block, and _fail_all drops the
    lookahead block dispatched after it: both are abandoned, with their
    positions, and the identities still hold."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=6))) == 6
        _settle(batcher)  # the leftover lookahead block drains unharmed
        before = batcher.tick_phase_stats()
        faults.arm("scheduler.harvest", exc=faults.FaultError, times=1)
        with pytest.raises(faults.FaultError):
            list(batcher.generate_step([3, 4, 5], max_tokens=30))
        faults.disarm()
        # the consumer hears of the failure while _fail_all is still at work
        # (it sheds whatever is submitted until it ends): the tick after
        # next has certainly begun after it
        seen = batcher.tick_phase_stats()["ticks"]
        deadline = time.monotonic() + 30
        while (batcher.tick_phase_stats()["ticks"] < seen + 2
               and time.monotonic() < deadline):
            time.sleep(0.02)
        _settle(batcher)
        s = _assert_identities(batcher)
        assert s["blocks_abandoned"] - before["blocks_abandoned"] == 2
        assert (s["tokens_dropped"]["abandoned_block"]
                - before["tokens_dropped"]["abandoned_block"]) == 2 * BLOCK
        # the batcher serves on
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=5))) == 5
        _settle(batcher)
        _assert_identities(batcher)
    finally:
        batcher.close()


@hard_timeout(240)
def test_metrics_render_the_tick_families_summed_over_replicas(engine):
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=9))) == 9
        fleet = ReplicaSet([batcher, batcher])  # the same account, twice
        one, both = batcher.tick_phase_stats(), fleet.tick_phase_stats()
        assert both["positions_computed"] == 2 * one["positions_computed"]
        assert both["tokens_dropped"]["slot_finished"] == (
            2 * one["tokens_dropped"]["slot_finished"])
        assert both["phase_entries"]["dispatch"] == (
            2 * one["phase_entries"]["dispatch"])
        text = ServingMetrics(batcher_fn=lambda: batcher).render()
        for phase in tracing.TICK_PHASES:
            assert f'mst_tick_phase_seconds_total{{phase="{phase}"}}' in text
            assert f'mst_tick_phase_total{{phase="{phase}"}}' in text
        for family in (
            "mst_ticks_total", "mst_decode_blocks_dispatched_total",
            "mst_decode_blocks_harvested_total",
            "mst_decode_positions_computed_total",
            "mst_decode_tokens_emitted_total",
        ):
            assert f"\n{family} " in text and f"# HELP {family} " in text
        assert f"mst_decode_positions_computed_total {one['positions_computed']}" in text
        for reason in ("slot_finished", "cancelled", "abandoned_block"):
            assert f'mst_decode_tokens_dropped_total{{reason="{reason}"}}' in text
        for reason in ("admit", "prefilling", "cold", "growth", "migrate", "idle"):
            assert f'mst_pipeline_drains_total{{reason="{reason}"}}' in text
        for gone in ("mst_tick_host_ms", "mst_tick_device_blocked_ms",
                     "mst_kv_bytes_read_last_tick"):
            assert gone not in text
        read, claimed = (
            int(text.split(f"\nmst_kv_bytes_{kind}_total ")[1].split()[0])
            for kind in ("read", "claimed")
        )
        assert 0 < read <= claimed  # held and claimed K/V bytes side by side
    finally:
        batcher.close()


def test_phases_suspend_their_outer_phase_and_leftover_is_other():
    """The accounting rule on its own, with no batcher: a nested phase
    takes its time out of the outer one, uncovered time is ``other``, and a
    snapshot includes the part of the open phase that has passed."""
    ph = tracing.TickPhases()
    ph.start()
    t0 = time.perf_counter()
    with ph.tick():
        time.sleep(0.02)  # other
        with ph.span("admit"):
            time.sleep(0.02)
            with ph.span("harvest_wait"):
                time.sleep(0.03)
            inner = ph.last
            time.sleep(0.01)
        with ph.span("idle_wait"):
            mid = ph.snapshot()  # taken from inside an open phase
            time.sleep(0.02)
    snap = ph.snapshot()
    wall = time.perf_counter() - t0
    secs = snap["seconds"]
    # a partition: nothing lost, nothing counted twice (lower bounds on
    # each part and the exact sum bound every part from above as well)
    assert sum(secs.values()) == pytest.approx(wall, abs=5e-3)
    assert secs["harvest_wait"] >= 0.03 and secs["admit"] >= 0.03
    assert secs["other"] >= 0.02 and secs["idle_wait"] >= 0.02
    assert inner[1] - inner[0] == pytest.approx(secs["harvest_wait"], abs=1e-4)
    assert snap["entries"]["admit"] == snap["entries"]["harvest_wait"] == 1
    assert snap["ticks"] == 1
    assert mid["seconds"]["idle_wait"] < secs["idle_wait"] - 0.015
    ph.stop()
    frozen = ph.snapshot()
    time.sleep(0.01)
    assert ph.snapshot() == frozen  # a stopped clock does not run


# ------------------------------------------ the device's bit, by phase
def test_empty_seconds_follow_the_device_bit_on_tick_phases_alone():
    """The rule on its own: a phase's time with nothing dispatched and
    unread is empty time; the queue filling or emptying inside a phase
    closes the open interval, so a phase holds both kinds; a wait entered
    with a program queued is never empty; a snapshot from inside an open
    phase carries the part that has passed."""
    ph = tracing.TickPhases()
    assert not ph.busy
    ph.dispatched("chunk")  # before start(): nothing to close, the queue is kept
    assert ph.busy
    ph.ready()
    assert not ph.busy
    ph.start()
    with ph.tick():
        with ph.span("admit"):
            time.sleep(0.02)  # empty
            with ph.span("assign_slot"):
                time.sleep(0.01)  # empty
            with ph.span("prefill_chunk"):
                time.sleep(0.01)  # empty: before the dispatch
                ph.dispatched("chunk")
                ph.returned()
                block = ph.dispatched("block")  # behind it: nothing changes
                ph.returned()
                time.sleep(0.03)  # the device has the chunk
            with ph.span("harvest_wait"):
                time.sleep(0.02)
            # the read returned: the queue empties OUTSIDE the wait, at the
            # wait's own closing stamp
            ph.ready(block, at=ph.last[1])
            assert not ph.busy
            time.sleep(0.01)  # empty, in admit again
        with ph.span("idle_wait"):
            mid = ph.snapshot()
            time.sleep(0.02)
    snap = ph.snapshot()
    secs, empty = snap["seconds"], snap["empty_seconds"]
    assert set(empty) == set(tracing.TICK_PHASES)
    assert empty["harvest_wait"] == 0.0 and secs["harvest_wait"] >= 0.02
    assert empty["assign_slot"] == secs["assign_slot"] >= 0.01
    assert empty["idle_wait"] == secs["idle_wait"] >= 0.02
    assert 0.03 <= empty["admit"] < secs["admit"]
    assert 0.01 <= empty["prefill_chunk"] <= secs["prefill_chunk"] - 0.03
    for phase in tracing.TICK_PHASES:
        assert 0.0 <= empty[phase] <= secs[phase]
    assert 0.0 < mid["empty_seconds"]["idle_wait"] == mid["seconds"]["idle_wait"]
    assert mid["seconds"]["idle_wait"] < secs["idle_wait"] - 0.015
    ph.stop()
    frozen = ph.snapshot()
    time.sleep(0.01)
    assert ph.snapshot() == frozen


def _joins(batcher):
    return batcher.latency_stats()["join"]


@hard_timeout(240)
@pytest.mark.parametrize("mode", ["on", "off"])
def test_device_empty_identities_hold_on_a_batcher(engine, mode):
    """A join while another stream decodes: nobody waits on an empty
    device, no phase is emptier than it is long, the joins' host work is
    exposed, and with nothing in any slot the whole idle wait is empty."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched=mode)
    try:
        _drive(batcher)
        time.sleep(0.05)  # into the idle wait that follows the last finish
        s = batcher.tick_phase_stats()
        secs, empty = s["phase_seconds"], s["device_empty_seconds"]
        assert empty["harvest_wait"] == 0.0 and secs["harvest_wait"] > 0.0
        for phase in tracing.TICK_PHASES:
            assert 0.0 <= empty[phase] <= secs[phase], phase
        # a joiner's slot claim and the host side of its chunk run against
        # a drained pipeline (sync: against no pipeline at all)
        assert empty["assign_slot"] > 0.0 and empty["prefill_chunk"] > 0.0
        assert empty["assign_slot"] == secs["assign_slot"]
        assert empty["prefill_chunk"] < secs["prefill_chunk"]
        assert empty["idle_wait"] == secs["idle_wait"] > 0.0
        # three requests were admitted and none gave up before its first
        # token (the third's consumer left after two)
        assert s["phase_entries"]["assign_slot"] == 3
        j = _joins(batcher)
        assert j["count"] == 3 and j["sum"] > 0.0
        text = ServingMetrics(batcher_fn=lambda: batcher).render()
        assert "mst_join_seconds_count 3" in text
        assert 'mst_device_empty_seconds_total{phase="harvest_wait"} 0.000000' in text
        assert f"mst_sched_async {int(mode == 'on')}" in text
    finally:
        batcher.close()


@hard_timeout(240)
def test_steady_async_decode_grows_no_empty_seconds(engine):
    """Between two snapshots taken while one stream decodes under the
    double-buffered tick, with no join and no drain between them, the host
    works under a lookahead block: no phase's empty seconds grow."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=6))) == 6
        _settle(batcher)  # compiled; the counters below start from here
        # every tick waits 30 ms first: the 14 blocks of the stream take
        # long enough for two snapshots a few ticks apart
        faults.arm("scheduler.tick", delay=0.03)
        out, started = [], threading.Event()
        th = threading.Thread(
            target=_stream, args=(batcher, [3, 17, 42], 55, out, started))
        th.start()
        assert started.wait(timeout=60)  # block 1 harvested, block 2 in flight
        one = batcher.tick_phase_stats()
        time.sleep(0.15)
        two = batcher.tick_phase_stats()
        faults.disarm()
        th.join(timeout=120)
        assert not th.is_alive() and len(out) == 55
        # the pair brackets steady decode: ticks and blocks went by, and
        # nothing that drains the pipeline or claims a slot did
        assert two["ticks"] >= one["ticks"] + 2
        assert two["blocks_harvested"] >= one["blocks_harvested"] + 2
        assert two["drains"] == one["drains"]
        for phase in ("assign_slot", "prefill_chunk", "idle_wait"):
            assert two["phase_entries"][phase] == one["phase_entries"][phase]
        assert two["device_empty_seconds"] == one["device_empty_seconds"]
        assert two["phase_seconds"]["other"] > one["phase_seconds"]["other"]
    finally:
        faults.disarm()
        batcher.close()


@hard_timeout(240)
@pytest.mark.parametrize("mode", ["on", "off"])
def test_every_join_that_reaches_decode_is_timed_once(engine, mode):
    """Over-commit with a spill tier: growth preempts, the victim's pages
    park in the tier and it comes back through a block import. Every slot
    claim — first admissions and re-admissions, the imported slot among
    them — is one entry of ``assign_slot`` and one observation of
    ``mst_join_seconds``."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched=mode,
                                overcommit=True, spill_bytes=64 << 20)
    try:
        outs = [[], [], []]
        threads = [
            threading.Thread(
                target=_stream,
                args=(batcher, [3 + i, 17, 42, 5, 9, 11, 2], 50, outs[i]))
            for i in range(3)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
            assert not th.is_alive()
        assert all(len(o) == 50 for o in outs)
        _settle(batcher)
        s = _assert_identities(batcher)
        spill = batcher.spill_stats()
        assert batcher.preemptions >= 1 and spill["spill_hits"] >= 1
        claims = 3 + batcher.preemptions
        assert s["phase_entries"]["assign_slot"] == claims
        assert _joins(batcher)["count"] == claims
        # an import's scatter runs against a drained pipeline
        empty = s["device_empty_seconds"]
        assert empty["kv_import"] == s["phase_seconds"]["kv_import"] > 0.0
        assert empty["harvest_wait"] == 0.0
    finally:
        batcher.close()


@hard_timeout(240)
def test_join_cancelled_between_its_chunks_observes_nothing(engine):
    """A request given up between two of its prefill chunks claimed a slot
    and never decoded: one more ``assign_slot``, no join observed; and the
    chunk it left dispatched does not keep the idle wait 'busy'."""
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=6))) == 6
        _settle(batcher)
        before = batcher.tick_phase_stats()
        assert _joins(batcher)["count"] == 1
        faults.arm("scheduler.tick", delay=0.05)  # time between the chunks
        out = []
        th = threading.Thread(  # 20 tokens: three chunks of 8
            target=_stream, args=(batcher, list(range(1, 21)), 5, out))
        th.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            reqs = [r for r in batcher._slots
                    if r is not None and 0 < r.prefill_pos < r.prompt.size]
            if reqs:
                reqs[0].cancelled = True  # what a consumer's exit sets
                break
            time.sleep(0.002)
        th.join(timeout=120)
        faults.disarm()
        assert not th.is_alive() and out == []
        _settle(batcher)
        time.sleep(0.05)
        s = batcher.tick_phase_stats()
        assert (s["phase_entries"]["assign_slot"]
                == before["phase_entries"]["assign_slot"] + 1)
        assert (s["phase_entries"]["prefill_chunk"]
                > before["phase_entries"]["prefill_chunk"])
        assert _joins(batcher)["count"] == 1
        assert (s["device_empty_seconds"]["idle_wait"]
                == s["phase_seconds"]["idle_wait"])
        # the batcher serves on, and the next join is counted
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=5))) == 5
        assert _joins(batcher)["count"] == 2
    finally:
        faults.disarm()
        batcher.close()


@hard_timeout(240)
def test_device_empty_seconds_render_summed_over_replicas(engine):
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        _drive(batcher)
        fleet = ReplicaSet([batcher, batcher])  # the same account, twice
    finally:
        batcher.close()  # the clock stops: both reads below see one account
    one, both = batcher.tick_phase_stats(), fleet.tick_phase_stats()
    assert both["path"] == one["path"] == "async"
    lines: list = []
    _render_tick_phases(lines, both)
    for phase in tracing.TICK_PHASES:
        assert both["device_empty_seconds"][phase] == pytest.approx(
            2 * one["device_empty_seconds"][phase])
        assert (f'mst_device_empty_seconds_total{{phase="{phase}"}} '
                f'{2 * one["device_empty_seconds"][phase]:.6f}') in lines
    assert one["device_empty_seconds"]["assign_slot"] > 0.0
    assert "mst_sched_async 1" in lines
    # the joins' histogram merges like the other two
    assert fleet.latency_stats()["join"]["count"] == 2 * _joins(batcher)["count"] == 6
