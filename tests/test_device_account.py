"""The device's timeline as the tick thread's waits show it
(``tracing.TickPhases``: the queue of served programs dispatched and not yet
known to have ended; ``benchmarks/README.device.md``): the account's rules
under a scripted clock, beside the old device bit's arithmetic as their
oracle; the same account through a ``ContinuousBatcher``; what ``/metrics``,
``close()`` and the profiler's ``mst.tick`` carry of it; and the readers of
the five per-layer metrics over it."""

import contextlib
import json
import logging
import threading
import time
from pathlib import Path

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
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.testing import faults
from mlx_sharding_tpu.utils.observability import ServingMetrics
from tests.helpers import hard_timeout

KINDS = tracing.PROGRAM_KINDS
FAMILIES = ("mst_program_device_seconds_total",
            "mst_program_dispatch_exposed_seconds_total",
            "mst_program_runs_total", "mst_program_late_total")


# ------------------------------------------- the rules, on a scripted clock
class _Bit:
    """The oracle: the arithmetic ``TickPhases`` kept before this queue —
    one bit, set by ``device(True)`` right before a dispatch and cleared by
    ``device(False)`` where a blocking read left nothing dispatched and
    unread (or where nobody would read what was), every change closing the
    open interval — on the clock the script sets."""

    def __init__(self, clock):
        self.clock = clock
        self.seconds = dict.fromkeys(tracing.TICK_PHASES, 0.0)
        self.empty_seconds = dict.fromkeys(tracing.TICK_PHASES, 0.0)
        self.busy = False
        self._open = None
        self._outer = []

    def _switch(self, phase, now):
        cur = self._open
        if cur is not None:
            dt = now - cur[1]
            self.seconds[cur[0]] += dt
            if not cur[2]:
                self.empty_seconds[cur[0]] += dt
        self._open = None if phase is None else (phase, now, self.busy)

    def device(self, busy):
        if busy == self.busy:
            return
        self.busy = busy
        if self._open is not None:
            self._switch(self._open[0], self.clock())

    def start(self):
        self._switch("other", self.clock())

    def stop(self):
        self._switch(None, self.clock())

    def enter(self, phase):
        self._outer.append(self._open[0])
        self._switch(phase, self.clock())

    def leave(self):
        self._switch(self._outer.pop(), self.clock())


class _Script:
    """Drives a ``TickPhases`` and the oracle through one script of
    ``(seconds, step, *arguments)``: the clock stands at ``seconds`` while
    the step runs, so both see every event at the same instant however many
    times each reads the clock. Steps: ``in`` / ``out`` (a phase), ``call``
    kind name, ``ret``, ``ready`` name-or-None cleared [late] (``cleared``
    is what the old scheduler did at that read: ``device(False)`` or not),
    ``harvest`` name cleared (the block's read: ready at the wait's closing
    stamp), ``drop``."""

    def __init__(self, monkeypatch):
        self.now = 0.0
        monkeypatch.setattr(tracing.time, "perf_counter", lambda: self.now)
        self.ph = tracing.TickPhases()
        self.bit = _Bit(lambda: self.now)
        self.tickets = {}
        self._spans = contextlib.ExitStack()

    def run(self, steps):
        ph, bit = self.ph, self.bit
        for at, step, *args in steps:
            assert at >= self.now, "a script's clock never runs backwards"
            self.now = at
            if step == "start":
                ph.start(), bit.start()
            elif step == "stop":
                ph.stop(), bit.stop()
            elif step == "in":
                self._spans.enter_context(ph.span(args[0]))
                bit.enter(args[0])
            elif step == "out":
                self._spans.close()  # the scripts nest no phase in a phase
                bit.leave()
            elif step == "call":
                self.tickets[args[1]] = ph.dispatched(args[0])
                bit.device(True)
            elif step == "ret":
                assert ph.returned() == at
            elif step == "ready":
                name, cleared, *late = args
                ph.ready(self.tickets.get(name), late=bool(late))
                if cleared:
                    bit.device(False)
            elif step == "harvest":
                name, cleared = args
                ph.ready(self.tickets[name], at=ph.last[1])
                if cleared:
                    bit.device(False)
            elif step == "drop":
                ph.drop()
                bit.device(False)
            else:
                raise AssertionError(step)
            assert ph.busy == bit.busy, (at, step, args)
        return ph.snapshot()


def _full_pipeline():
    """Block after block, each dispatched before the one in front is read:
    a block's seconds are the interval from harvest to harvest."""
    steps = [(0.0, "start"),
             (1.0, "in", "dispatch"), (1.0, "call", "block", "b1"),
             (1.5, "ret"), (1.5, "out")]
    for i, t in ((2, 2.0), (3, 12.0), (4, 22.0)):
        steps += [(t, "in", "dispatch"), (t, "call", "block", f"b{i}"),
                  (t + 0.25, "ret"), (t + 0.25, "out"),
                  (t + 1.0, "in", "harvest_wait"), (t + 9.0, "out"),
                  (t + 9.0, "harvest", f"b{i - 1}", False)]
    steps += [(40.0, "in", "harvest_wait"), (41.0, "out"),
              (41.0, "harvest", "b4", True), (45.0, "stop")]
    want = {"device": {"block": 40.0}, "runs": {"block": 4},
            # b1 was called with the device empty: its whole call is exposed;
            # b2..b4 were called under the block in front: none of theirs
            "exposed": {"block": 0.5},
            # 11 - 1, then 21 - 11, 31 - 21, 41 - 31
            "empty": {"other": 1.0 + 4.0}}
    return steps, want


def _join(chunk_end):
    """Drain, a join's MIDDLE chunk, the block behind it, and the next
    tick's drain: the chunk's end is waited for in front of the harvest
    (``seen``), found there already passed (``late_at_wait``) or found
    passed when the block's dispatch call came back (``late_at_ret``)."""
    steps = [(0.0, "start"),
             (1.0, "in", "dispatch"), (1.0, "call", "block", "b1"),
             (1.25, "ret"), (1.25, "out"),
             (2.0, "in", "harvest_wait"), (9.0, "out"),
             (9.0, "harvest", "b1", True),  # a quiesce: nothing behind it
             (10.0, "in", "prefill_chunk"), (10.5, "call", "chunk", "c1"),
             (11.0, "ret"), (11.0, "out"),
             (12.0, "in", "dispatch"), (12.0, "call", "block", "b2"),
             (12.5, "ret")]
    if chunk_end == "late_at_ret":
        steps += [(12.5, "ready", "c1", False, "late")]
    steps += [(12.5, "out"), (13.0, "in", "harvest_wait")]
    if chunk_end == "seen":
        steps += [(14.0, "ready", "c1", False)]  # the wait on its logits
    elif chunk_end == "late_at_wait":
        steps += [(13.0, "ready", "c1", False, "late")]
    steps += [(20.0, "out"), (20.0, "harvest", "b2", True), (21.0, "stop")]
    end = {"seen": 14.0, "late_at_wait": 13.0, "late_at_ret": 12.5}[chunk_end]
    want = {"device": {"block": 8.0 + (20.0 - end), "chunk": end - 10.5},
            "runs": {"block": 2, "chunk": 1},
            "late": {"chunk": int(chunk_end != "seen")},
            # b1 and c1 were called with the device empty, b2 under c1
            "exposed": {"block": 0.25, "chunk": 0.5},
            "empty": {"other": 1.0 + 1.0 + 1.0, "prefill_chunk": 0.5}}
    return steps, want


def _two_chunks_nothing_decoding():
    """Two joiners' chunks in one tick with no stream decoding: the first
    ends unobserved; the second is a LAST chunk, and the read of its first
    token closes both. They share the interval, one run each."""
    steps = [(0.0, "start"),
             (1.0, "in", "prefill_chunk"), (1.5, "call", "chunk", "a"),
             (2.0, "ret"), (2.0, "out"),
             (3.0, "in", "prefill_chunk"), (3.5, "call", "chunk", "b"),
             (3.75, "ret"), (9.0, "ready", None, True), (9.5, "out"),
             (10.0, "stop")]
    want = {"device": {"chunk": 7.5}, "runs": {"chunk": 2},
            "exposed": {"chunk": 0.5},  # a's call; b's lay under a
            "empty": {"other": 1.0 + 0.5, "prefill_chunk": 0.5 + 0.5}}
    return steps, want


def _last_chunk_then_block():
    """A one-chunk join whose first token is read BEFORE the block (the
    sync tick, a ``prefill_only`` or speculating joiner): the chunk is a
    last chunk, ``int(tok)`` waits on it, and the block behind it is
    dispatched with the queue EMPTY: that call is exposed to its last
    microsecond (the burst's tail)."""
    steps = [(0.0, "start"),
             (1.0, "in", "prefill_chunk"), (1.0, "call", "chunk", "c"),
             (1.25, "ret"), (6.0, "ready", None, True), (6.0, "out"),
             (7.0, "in", "dispatch"), (7.0, "call", "block", "b"),
             (9.0, "ret"), (9.0, "out"),
             (10.0, "in", "harvest_wait"), (20.0, "out"),
             (20.0, "harvest", "b", True), (20.0, "stop")]
    want = {"device": {"chunk": 5.0, "block": 13.0},
            "runs": {"chunk": 1, "block": 1},
            "exposed": {"chunk": 0.25, "block": 2.0},
            "empty": {"other": 1.0 + 1.0}}
    return steps, want


def _last_chunk_block_behind(chunk_end):
    """A one-chunk join in the async tick: the block is dispatched behind
    the unread last chunk, then the hold lets go and ``int(tok)`` waits on
    the chunk, whose end it names by the chunk's ticket (the program
    dispatched last is the block by then). The block's call lay under the
    chunk: nothing of it is exposed, and the device is never empty between
    the two (``late_at_ret``: the chunk was found ended when the block's
    call came back, and the read's own ``ready`` changes nothing)."""
    steps = [(0.0, "start"),
             (1.0, "in", "prefill_chunk"), (1.0, "call", "chunk", "c"),
             (1.25, "ret"), (1.5, "out"),
             (2.0, "in", "dispatch"), (2.0, "call", "block", "b"), (4.0, "ret")]
    if chunk_end == "late_at_ret":
        steps += [(4.0, "ready", "c", False, "late")]
    steps += [(4.0, "out"),
              (4.5, "in", "first_token"), (6.0, "ready", "c", False), (6.5, "out"),
              (10.0, "in", "harvest_wait"), (20.0, "out"),
              (20.0, "harvest", "b", True), (20.0, "stop")]
    end = 4.0 if chunk_end == "late_at_ret" else 6.0
    want = {"device": {"chunk": end - 1.0, "block": 20.0 - end},
            "runs": {"chunk": 1, "block": 1},
            "late": {"chunk": int(chunk_end == "late_at_ret")},
            "exposed": {"chunk": 0.25, "block": 0.0},
            "empty": {"other": 1.0}}
    return steps, want


def _abandoned(then):
    """A block whose harvest failed (``_abandon``), a chunk and a block in
    front of a scheduler failure (``_fail_all``), a cancelled joiner's
    chunk at the idle wait: the queue is emptied, no run and no program's
    seconds are counted, and the time it stood is kept apart. (The old
    scheduler cleared its bit at the idle wait alone and left it set from a
    failure until then; the oracle clears it at every drop.)"""
    steps = [(0.0, "start"), (1.0, "in", "dispatch"),
             (1.0, "call", "block", "b"), (1.5, "ret"), (1.5, "out")]
    if then == "fail_all":
        steps = [(0.0, "start"), (0.5, "in", "prefill_chunk"),
                 (0.5, "call", "chunk", "c"), (0.75, "ret"), (0.75, "out")] + steps[1:]
    steps += [(4.0, "drop")]
    if then == "idle_wait":
        steps += [(4.0, "in", "idle_wait"), (9.0, "out")]
    steps += [(5.0 if then != "idle_wait" else 9.0, "ready", "b", False),  # closed already
              (10.0, "stop")]
    began = 0.5 if then == "fail_all" else 1.0
    want = {"device": {}, "runs": {}, "exposed": {}, "unread": 4.0 - began,
            "empty": ({"other": 1.0 + 1.0, "idle_wait": 5.0} if then == "idle_wait"
                      else {"other": began + 6.0})}
    return steps, want


def _idle_wait():
    """Nothing in any slot: the last block is read, the queue is empty, and
    the whole wait on the submit queue is empty time."""
    steps = [(0.0, "start"), (1.0, "in", "dispatch"),
             (1.0, "call", "block", "b"), (1.5, "ret"), (1.5, "out"),
             (2.0, "in", "harvest_wait"), (8.0, "out"), (8.0, "harvest", "b", True),
             (8.5, "drop"),  # the idle wait's: nothing is left, nothing moves
             (8.5, "in", "idle_wait"), (30.0, "out"), (31.0, "stop")]
    want = {"device": {"block": 7.0}, "runs": {"block": 1},
            "exposed": {"block": 0.5},
            "empty": {"other": 1.0 + 0.5 + 1.0, "idle_wait": 21.5}}
    return steps, want


def _draft_chunk_then_block():
    """Kinds whose end nobody waits for (``other``: a draft's chunk) are
    closed by the read that follows: taken to have ended when the next
    program was called."""
    steps = [(0.0, "start"),
             (1.0, "in", "prefill_chunk"), (1.0, "call", "other", "d"),
             (1.5, "ret"), (1.5, "out"),
             (2.0, "in", "dispatch"), (2.0, "call", "block", "b"),
             (2.5, "ret"), (2.5, "out"),
             (3.0, "in", "harvest_wait"), (12.0, "out"),
             (12.0, "harvest", "b", True), (12.0, "stop")]
    want = {"device": {"other": 1.0, "block": 10.0},
            "runs": {"other": 1, "block": 1},
            "exposed": {"other": 0.5}, "empty": {"other": 1.0}}
    return steps, want


SCRIPTS = {
    "full_pipeline": _full_pipeline,
    "join_chunk_end_seen": lambda: _join("seen"),
    "join_chunk_late_at_the_wait": lambda: _join("late_at_wait"),
    "join_chunk_late_at_the_blocks_return": lambda: _join("late_at_ret"),
    "two_chunks_nothing_decoding": _two_chunks_nothing_decoding,
    "last_chunk_read_by_int_tok": _last_chunk_then_block,
    "last_chunk_read_behind_its_block": lambda: _last_chunk_block_behind("seen"),
    "last_chunk_late_at_its_blocks_return": lambda: _last_chunk_block_behind("late_at_ret"),
    "abandoned_block": lambda: _abandoned("abandon"),
    "fail_all": lambda: _abandoned("fail_all"),
    "cancelled_chunk_at_idle_wait": lambda: _abandoned("idle_wait"),
    "idle_wait": _idle_wait,
    "unread_kind_then_block": _draft_chunk_then_block,
}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_the_account_on_a_scripted_clock(monkeypatch, name):
    steps, want = SCRIPTS[name]()
    script = _Script(monkeypatch)
    snap = script.run(steps)
    elapsed = steps[-1][0] - steps[0][0]

    def full(d, zero):
        return {k: d.get(k, zero) for k in KINDS}

    assert snap["device_seconds"] == pytest.approx(full(want["device"], 0.0))
    assert snap["exposed_seconds"] == pytest.approx(full(want["exposed"], 0.0))
    assert snap["runs"] == full(want["runs"], 0)
    assert snap["late"] == full(want.get("late", {}), 0)
    assert snap["unread_seconds"] == pytest.approx(want.get("unread", 0.0))
    # busy and empty partition the tick thread's clock
    assert not script.ph.busy
    assert (sum(snap["device_seconds"].values()) + snap["unread_seconds"]
            + sum(snap["empty_seconds"].values())) == pytest.approx(elapsed)
    assert sum(snap["seconds"].values()) == pytest.approx(elapsed)
    for kind in KINDS:
        assert 0.0 <= snap["exposed_seconds"][kind] <= snap["device_seconds"][kind]
    # the device's bit, to the float: what the one bit gave on this script
    assert snap["empty_seconds"] == script.bit.empty_seconds
    assert snap["seconds"] == script.bit.seconds
    nonzero = {k: v for k, v in snap["empty_seconds"].items() if v}
    assert nonzero == pytest.approx(want["empty"])


def test_a_snapshot_counts_closed_programs_only_and_the_queue_outlives_it(monkeypatch):
    """What is still queued has no seconds yet (a window's edge cuts it:
    the ``[device]`` line's remainder); a ticket closed already, or never
    given out, changes nothing; ``device()`` is gone."""
    script = _Script(monkeypatch)
    snap = script.run([(0.0, "start"), (1.0, "call", "block", "b1"), (1.5, "ret"),
                       (2.0, "call", "block", "b2"), (2.5, "ret"),
                       (6.0, "ready", "b1", False)])
    assert snap["runs"]["block"] == 1 and snap["device_seconds"]["block"] == 5.0
    assert script.ph.busy
    before = script.ph.snapshot()
    script.ph.ready(script.tickets["b1"])  # closed already
    assert script.ph.snapshot() == before and script.ph.busy
    assert not hasattr(script.ph, "device")
    with pytest.raises(AttributeError):
        script.ph.busy = False  # read off the queue, not kept beside it


# ---------------------------------------------- through a ContinuousBatcher
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
    tracing.configure("off")


def _join_a_decoding_stream(batcher):
    """A 20-token prompt (three chunks of 8: two middle ones, one last)
    joins while another stream decodes; both run to their end."""
    out, decoding = {0: [], 1: []}, threading.Event()

    def run(i, prompt, n):
        for t, _ in batcher.generate_step(prompt, max_tokens=n):
            out[i].append(t)
            if len(out[i]) == 3:
                decoding.set()

    first = threading.Thread(target=run, args=(0, [3, 17, 42], 38))
    first.start()
    assert decoding.wait(timeout=60)
    joiner = threading.Thread(target=run, args=(1, list(range(5, 25)), 9))
    joiner.start()
    for th in (first, joiner):
        th.join(timeout=120)
        assert not th.is_alive()
    assert len(out[0]) == 38 and len(out[1]) == 9
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and (
            batcher.stats()[1] or batcher._inflight is not None
            or batcher._phases.busy):
        time.sleep(0.02)
    time.sleep(0.05)  # into the idle wait


@hard_timeout(240)
@pytest.mark.parametrize("mode", ["on", "off"])
def test_every_chunk_and_block_is_closed_once_on_a_batcher(engine, mode, caplog):
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched=mode)
    try:
        _join_a_decoding_stream(batcher)
        s = batcher.tick_phase_stats()
        runs, secs = s["program_runs"], s["program_device_seconds"]
        exposed = s["program_dispatch_exposed_seconds"]
        assert set(runs) == set(secs) == set(exposed) == set(s["program_late"]) == set(KINDS)
        # 1 chunk for the first prompt, 3 for the joiner; every block read
        assert runs["chunk"] == s["join_programs"]["chunk"] == 4
        assert runs["block"] == s["blocks_harvested"] >= 10
        assert s["blocks_abandoned"] == 0 and runs["other"] == 0
        for kind in KINDS:
            assert 0.0 <= exposed[kind] <= secs[kind]
            assert 0 <= s["program_late"][kind] <= runs[kind]
        assert secs["block"] > 0.0 and secs["chunk"] > 0.0
        # the first prompt's chunk was dispatched into an empty device
        assert exposed["chunk"] > 0.0
        # busy and empty partition the thread's clock; nothing was dropped
        # with seconds on it, so the programs' seconds are the busy ones
        busy = sum(s["phase_seconds"].values()) - sum(s["device_empty_seconds"].values())
        assert sum(secs.values()) + s["program_unread_seconds"] == pytest.approx(busy, abs=2e-3)
        assert s["device_empty_seconds"]["harvest_wait"] == 0.0
        assert batcher._chunk_unread is None and not batcher._first_unread
        # both joins closed in front of a block: the async tick read their
        # first tokens behind it, so no block's call ever found the device
        # empty; the sync tick reads first and every block's call is exposed
        reads = s["join_first_reads"]
        assert reads == ({"behind_block": 2, "before_block": 0} if mode == "on"
                         else {"behind_block": 0, "before_block": 2})
        assert s["phase_entries"]["first_token"] == 2
        assert s["device_empty_seconds"]["first_token"] == 0.0 or mode == "off"
        assert (exposed["block"] == 0.0) == (mode == "on")
        text = ServingMetrics(batcher_fn=lambda: batcher).render()
        for family in FAMILIES:
            assert f"# HELP {family} " in text and f"# TYPE {family} counter" in text
            for kind in KINDS:
                assert f'\n{family}{{program="{kind}"}} ' in text
        assert f'mst_program_runs_total{{program="chunk"}} {runs["chunk"]}' in text
        fleet = ReplicaSet([batcher, batcher]).tick_phase_stats()  # the same account, twice
        assert fleet["program_runs"] == {k: 2 * v for k, v in runs.items()}
        assert fleet["program_device_seconds"]["block"] == pytest.approx(2 * secs["block"])
        assert fleet["program_dispatch_exposed_seconds"]["chunk"] == pytest.approx(
            2 * exposed["chunk"])
    finally:
        with caplog.at_level(logging.INFO, logger="mlx_sharding_tpu.scheduler"):
            batcher.close()
            batcher.close()  # one line a batcher, however often it is closed
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("tick account: ")]
    assert len(lines) == 1
    logged = json.loads(lines[0].split(": ", 1)[1])
    assert logged["program_runs"] == runs and logged["decode_block"] == BLOCK
    assert logged["path"] == ("async" if mode == "on" else "sync")
    assert set(logged["program_device_seconds"]) == {"block", "chunk"}
    assert logged["phase_seconds"]["harvest_wait"] > 0.0
    assert logged["blocks_harvested"] == runs["block"]


@hard_timeout(240)
def test_a_failed_harvest_empties_the_queue_and_counts_no_run(engine):
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        assert len(list(batcher.generate_step([3, 4, 5], max_tokens=6))) == 6
        before = batcher.tick_phase_stats()
        faults.arm("scheduler.harvest", exc=faults.FaultError, times=1)
        with pytest.raises(faults.FaultError):
            list(batcher.generate_step([3, 4, 5], max_tokens=30))
        faults.disarm()
        seen = batcher.tick_phase_stats()["ticks"]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and (
                batcher.tick_phase_stats()["ticks"] < seen + 2 or batcher._phases.busy):
            time.sleep(0.02)
        s = batcher.tick_phase_stats()
        assert s["blocks_abandoned"] >= 1 and not batcher._phases.busy
        harvested = s["blocks_harvested"] - before["blocks_harvested"]
        assert s["program_runs"]["block"] - before["program_runs"]["block"] == harvested
        assert s["program_unread_seconds"] > before["program_unread_seconds"]
    finally:
        batcher.close()


# ----------------------------------------------------- the profiler's clock
def test_mst_tick_carries_the_account_and_nothing_is_built_without_profile(monkeypatch):
    built = []
    monkeypatch.setattr(
        tracing, "profile_span",
        lambda name, **args: built.append((name, args)) or contextlib.nullcontext())
    off = tracing.TickPhases()
    off.start()
    with off.tick():
        off.dispatched("block")
        off.returned()
        off.ready()
    assert built == [] and off.ticks == 1
    ph = tracing.TickPhases(profile=True)
    ph.start()
    for kind in ("block", "chunk", "block"):
        with ph.tick():
            ph.dispatched(kind)
            time.sleep(0.002)
            ph.returned()
            ph.ready()
    ticks = [args for name, args in built if name == tracing.TICK_SPAN]
    assert len(ticks) == 3
    for args in ticks:
        assert set(args) == {"pc", "empty", "dev_block", "dev_chunk", "exposed"}
    snap = ph.snapshot()
    first, second, third = ticks
    assert first["dev_block"] == first["dev_chunk"] == first["exposed"] == 0.0
    assert second["dev_block"] > 0.0 == second["dev_chunk"]
    assert third["dev_chunk"] == snap["device_seconds"]["chunk"] > 0.0
    assert third["dev_block"] == second["dev_block"] < snap["device_seconds"]["block"]
    # every call here was made with the device empty: its call to its
    # return is exposed, which is all of its seconds but the read's own
    assert 0.004 <= third["exposed"] <= third["dev_block"] + third["dev_chunk"]


@hard_timeout(240)
def test_a_joins_closing_chunk_is_told_from_a_middle_one_under_profile(engine, monkeypatch):
    built = []
    monkeypatch.setattr(
        tracing, "profile_span",
        lambda name, **args: built.append((name, args)) or contextlib.nullcontext())
    tracing.configure("on", profile=True)
    batcher = ContinuousBatcher(engine, decode_block=BLOCK, async_sched="on")
    try:
        assert batcher._trace_profile
        _join_a_decoding_stream(batcher)
        s = batcher.tick_phase_stats()
    finally:
        batcher.close()
    chunks = [args for name, args in built if name == "mst.prefill_chunk"]
    assert [c["last"] for c in chunks] == [1, 0, 0, 1]
    assert [c["pos"] for c in chunks] == [0, 0, 8, 16]
    ticks = [args for name, args in built if name == tracing.TICK_SPAN]
    for key, total in (("dev_block", s["program_device_seconds"]["block"]),
                       ("dev_chunk", s["program_device_seconds"]["chunk"]),
                       ("exposed", sum(s["program_dispatch_exposed_seconds"].values()))):
        series = [t[key] for t in ticks]
        assert series == sorted(series) and series[0] == 0.0
        assert 0.0 < series[-1] <= total


# ------------------------------------------------- the per-layer readers
def _scrape(seconds, exposed, runs, late, empty, blocks, positions):
    out = {"mst_decode_blocks_dispatched_total": float(blocks),
           "mst_decode_positions_computed_total": float(positions)}
    for family, d in zip(FAMILIES, (seconds, exposed, runs, late)):
        for kind, v in zip(KINDS, d):
            out[f'{family}{{program="{kind}"}}'] = float(v)
    for phase, v in empty.items():
        out[f'mst_device_empty_seconds_total{{phase="{phase}"}}'] = v
    return out


def test_the_five_readers_over_a_pair_of_scrapes(capsys):
    from benchmarks.run import load_reader

    bench = json.loads((Path(__file__).resolve().parents[1] / "BENCHMARK.json").read_text())
    new = {"prefill_chunk_ms.window": ("ms", "lower", "engine"),
           "decode_step_ms.window": ("ms", "lower", "engine"),
           "chunk_device_share": ("%", "lower", "scheduler"),
           "dispatch_exposed_share": ("%", "lower", "scheduler"),
           "slots_decoding.mean": ("slots", "higher", "scheduler")}
    at = [m["name"] for m in bench["per_layer"]].index("prefill_chunk_ms.window")
    entries = bench["per_layer"][at:at + 5]  # (later PRs append behind them)
    assert [m["name"] for m in entries] == list(new)  # appended, in the issue's order
    for m in entries:
        unit, better, layer = new[m["name"]]
        # no ``workloads`` list: every cell reports it
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": "program_counter", "layer": layer, "moves": "out_tok_s"}
    before = _scrape((10.0, 1.0, 0.0), (0.5, 0.25, 0.0), (50, 40, 0), (0, 1, 0),
                     {"admit": 0.5, "idle_wait": 2.0}, 50, 50 * 8 * 24)
    after = _scrape((54.0, 6.0, 0.25), (1.0, 1.25, 0.0), (250, 240, 1), (0, 5, 0),
                    {"admit": 1.25, "idle_wait": 2.0}, 250, 50 * 8 * 24 + 200 * 8 * 20)
    ctx = {"w0": 100.0, "w1": 150.0, "before": before, "after": after}
    read = {name: load_reader("layer_metrics", name) for name in new}
    assert read["prefill_chunk_ms.window"](ctx) == pytest.approx(25.0)  # 5 s / 200
    assert read["decode_step_ms.window"](ctx) == pytest.approx(27.5)  # 44 s / (200 x 8)
    assert read["chunk_device_share"](ctx) == pytest.approx(10.0)
    assert read["dispatch_exposed_share"](ctx) == pytest.approx(3.0)  # (0.5 + 1.0) / 50
    assert read["slots_decoding.mean"](ctx) == pytest.approx(20.0)
    out = capsys.readouterr().out
    (line,) = [ln for ln in out.splitlines() if ln.startswith("[device]")]  # once a run
    assert "device seconds: block 44.000, chunk 5.000, other 0.250" in line
    assert "runs: block 200, chunk 200, other 1; late: block 0, chunk 4, other 0" in line
    assert "dispatch exposed seconds: block 0.500, chunk 1.000, other 0.000" in line
    # 50 - 49.25 - 0.75 = 0
    assert "empty seconds 0.750; remainder 0.000 s (0.00 % of the window)" in line
    # a window that closed no chunk has no chunk time; its share is 0
    quiet = dict(after)
    for family in FAMILIES:
        quiet[f'{family}{{program="chunk"}}'] = before[f'{family}{{program="chunk"}}']
    ctx2 = {"w0": 100.0, "w1": 150.0, "before": before, "after": quiet,
            "_device_account_printed": True}
    assert read["prefill_chunk_ms.window"](ctx2) is None
    assert read["chunk_device_share"](ctx2) == 0.0
    # a program from before the families (this PR's parent) exposes none of
    # them: four metrics are left out; the fifth's counters it does have
    old = {k: v for k, v in after.items() if "mst_program_" not in k}
    ctx3 = {"w0": 100.0, "w1": 150.0, "after": old,
            "before": {k: v for k, v in before.items() if "mst_program_" not in k}}
    for name in list(new)[:4]:
        assert read[name](ctx3) is None
    assert read["slots_decoding.mean"](ctx3) == pytest.approx(20.0)
    assert read["slots_decoding.mean"]({"before": None, "after": None}) is None
    assert "[device]" not in capsys.readouterr().out
