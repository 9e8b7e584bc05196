"""A tick that drains the pipeline for a joiner hands the drained block's
tokens to their streams only once the device has the joiner's chunk
(``scheduler._hand`` / ``_flush_held``, opened in ``_tick_async``).

A join's closing tick dispatches the decode block behind the last chunk
before anything of the join is read: the hold lets go after that dispatch,
then the first token is read and emitted (``_read_first_tokens``); where the
host must act on the token first, the read stays in front of the block.

The tick is driven by hand on the test's own thread wherever the order of
events matters (the scheduler thread never starts), with a tape of what it
ran: drains, slot claims, chunk dispatches, block and speculative
dispatches, the blocking read of a first token, and every ``put`` on a
request's queue. The plain reference for what a stream receives is the same
request served alone, no joiner interleaved: the hold and the read's place
move when an item is handed over, never which or in what order."""

import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.resilience import Deadlines, RequestTimeoutError
from mlx_sharding_tpu.sample import sampler_params_host
from mlx_sharding_tpu.scheduler import (
    ContinuousBatcher,
    _InflightSpec,
    _Request,
)
from mlx_sharding_tpu.testing import faults
from mlx_sharding_tpu.utils.observability import _render_tick_phases
from tests.helpers import hard_timeout

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)
PAGE = CHUNK = 8
BLOCK = 3
SAMPLED = dict(temperature=0.8, top_p=0.9, repetition_penalty=1.3)
SEED = (1 << 31) + 12345
END = "end"


@pytest.fixture(scope="module")
def engines():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    kw = dict(microbatches=3, max_seq=64, cache_dtype=jnp.float32,
              prefill_chunk=CHUNK)
    return {
        True: PipelineEngine(model, params, pipeline_mesh(1), pool_pages=24,
                             page_size=PAGE, **kw),
        False: PipelineEngine(model, params, pipeline_mesh(1), **kw),
    }


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.disarm()


# ------------------------------------------------------------------ the tape
class Tape:
    def __init__(self):
        self.events = []

    def add(self, *event):
        self.events.append(event)

    def ticks(self):
        """The events, one list a tick."""
        out = []
        for event in self.events:
            if event[0] == "tick":
                out.append([])
            elif out:
                out[-1].append(event)
        return out


class TapedQueue(queue.Queue):
    """A request's ``out`` that writes every put on the tape: ``("put",
    stream, token | END | the error)``."""

    def __init__(self, tape, name):
        super().__init__()
        self.tape, self.name = tape, name

    def put(self, item, *args, **kwargs):
        what = END if item is None else item
        self.tape.add("put", self.name, what[0] if isinstance(what, tuple) else what)
        super().put(item, *args, **kwargs)


class FirstToken:
    """``finish_join``'s token: its ``int()`` is the tick's blocking read."""

    def __init__(self, tok, tape, batcher):
        self.tok, self.tape, self.batcher = tok, tape, batcher

    def __int__(self):
        self.tape.add("read", self.batcher._held is None)
        return int(self.tok)


def tap(b, tape, monkeypatch):
    """Write on the tape what the tick thread runs."""
    quiesce, claim, finish, assign = (
        b._quiesce, b._claim_slot, b._finish_join, b._assign_slot)
    chunk = b.engine.prefill_slot()

    def taped_quiesce(reason):
        inf = b._inflight
        if inf is not None:
            tape.add("drain", reason, "spec" if isinstance(inf, _InflightSpec) else "block")
        quiesce(reason)

    def taped_assign(req, slot):
        tape.add("assign", req.out.name, slot)
        assign(req, slot)

    def taped_claim(*args):
        tape.add("claim")
        return claim(*args)

    def taped_chunk(*args):
        tape.add("chunk")
        return chunk(*args)

    def taped_finish(*args):
        tape.add("finish_join")
        tok, *rest = finish(*args)
        return (FirstToken(tok, tape, b), *rest)

    def taped_round():
        prefill_round()
        tape.add("round_end")

    def taped_block():
        tape.add("block")
        return dispatch_block()

    def taped_spec(*args):
        tape.add("spec")
        return dispatch_spec(*args)

    dispatch_block, b._dispatch_block = b._dispatch_block, taped_block
    dispatch_spec, b._dispatch_spec = b._dispatch_spec, taped_spec
    prefill_round, b._prefill_round = b._prefill_round, taped_round
    b._quiesce, b._assign_slot, b._claim_slot, b._finish_join = (
        taped_quiesce, taped_assign, taped_claim, taped_finish)
    monkeypatch.setattr(b.engine, "prefill_slot", lambda: taped_chunk)
    return b


def make_batcher(engines, paged, tape=None, monkeypatch=None, **kw):
    b = ContinuousBatcher(engines[paged], decode_block=BLOCK, **kw)
    assert b._async == (kw.get("async_sched") != "off")
    return b if tape is None else tap(b, tape, monkeypatch)


def request(b, tape, name, prompt, max_tokens, **sampler):
    width = b.sp.bias_indices.shape[1]
    return _Request(
        prompt=np.asarray(prompt, np.int32), seed=SEED, max_tokens=max_tokens,
        sp=sampler_params_host(**sampler, slots=width), rep_context=20,
        out=TapedQueue(tape, name), **sampler,
    )


def tick(b, tape):
    """One iteration of ``_loop``'s body, on this thread."""
    tape.add("tick")
    try:
        (b._tick_async if b._async else b._tick)()
    except Exception as exc:  # noqa: BLE001 — as _loop does
        b._fail_all(exc)
    assert b._held is None, "a hold outlived its tick"
    assert not b._first_unread, "a first token outlived its tick"


def ended(req):
    return any(e[:2] == ("put", req.out.name)
               and (e[2] is END or isinstance(e[2], BaseException))
               for e in req.out.tape.events)


def drive(b, tape, arrivals, admitted=(), limit=120):
    """Tick until every stream has ended; ``arrivals[n]`` join the waiting
    line before tick ``n``, ``admitted`` are in it or in a slot already."""
    reqs = [*admitted, *(r for batch in arrivals.values() for r in batch)]
    n = 0
    while not all(ended(r) for r in reqs):
        assert n < limit, "the streams did not end"
        b._waiting.extend(arrivals.get(n, ()))
        tick(b, tape)
        n += 1


def decoding(b, tape, req):
    """Two ticks: ``req`` joins, decodes one block, has the next in flight."""
    b._waiting.append(req)
    tick(b, tape)
    tick(b, tape)
    assert b._inflight is not None


def received(req):
    """What the stream's consumer finds: tokens, then END or the error."""
    out = []
    while True:
        try:
            item = req.out.get_nowait()
        except queue.Empty:
            return out
        out.append(END if item is None else
                   item if isinstance(item, BaseException) else int(item[0]))


def held_ticks(tape, behind=True):
    """The ticks that drained for a joiner and dispatched its chunk: for
    each, ``(events, drain index, chunk index)``; asserts the rule on every
    tick that drained for one — no put between the drain and the chunk's
    dispatch (``_prefill_round``'s return where it dispatched none), the
    hold closed before the blocking read, and every read behind the tick's
    decode block wherever the block leads (``behind``)."""
    found = []
    for events in tape.ticks():
        closing_tick(events, behind)
        drains = [i for i, e in enumerate(events)
                  if e[0] == "drain" and e[1] in ("admit", "prefilling")]
        chunks = [i for i, e in enumerate(events) if e[0] == "chunk"]
        if not drains:
            continue
        d = drains[0]
        c = chunks[0] if chunks else events.index(("round_end",))
        assert not [e for e in events[d:c] if e[0] == "put"], events
        if chunks:
            found.append((events, d, c))
    return found


def closing_tick(events, behind=True):
    """The rule of a tick in which joins closed (it ran ``finish_join``):
    every first token is read with no hold open, its ``put`` follows its
    read (and the stream's end, where that token was its last), and —
    ``behind`` — claim, chunk and ``finish_join`` of every such join, then
    the block's dispatch, then the held tokens' puts, then the reads; else
    every read lies in front of the tick's block or speculative round.
    Returns the joins closed."""
    events = [e for i, e in enumerate(events)  # a one-token stream's end
              if not (e[0] == "put" and e[2] is END and i > 1
                      and events[i - 2][0] == "read")]
    kinds = [e[0] for e in events]
    closed = kinds.count("finish_join")
    reads = [i for i, k in enumerate(kinds) if k == "read"]
    assert len(reads) == closed, events
    for i in reads:
        assert events[i][1], "a hold was open at the blocking read"
        assert kinds[i + 1] == "put", events  # the token just read
    if not closed:
        return 0
    dispatch = [i for i, k in enumerate(kinds) if k in ("block", "spec")]
    assert len(dispatch) == 1 or not behind, events
    last_finish = len(kinds) - 1 - kinds[::-1].index("finish_join")
    if behind:
        (blk,) = dispatch
        # nothing leaves and nothing is read until the device has the block
        assert last_finish < blk < reads[0], events
        assert "put" not in kinds[kinds.index("finish_join"):blk], events
        # then the held tokens, and after the first read first tokens only
        tail = kinds[blk + 1:]
        assert set(tail) <= {"put", "read"}, events
        assert tail[reads[0] - blk - 1:] == ["read", "put"] * closed, events
    else:
        assert dispatch and reads[-1] < dispatch[0], events
    return closed


# --------------------------------------------------------- the plain reference
@pytest.fixture(scope="module")
def alone(engines):
    """``alone(paged, sampled, prompt, max_tokens)``: the stream the request
    gives when it is served with nobody else, memoized."""
    memo, batchers = {}, {}

    def run(paged, sampled, prompt, max_tokens):
        key = (paged, sampled, tuple(prompt), max_tokens)
        if key not in memo:
            if (paged, sampled) not in batchers:
                batchers[paged, sampled] = make_batcher(engines, paged)
            b, tape = batchers[paged, sampled], Tape()
            req = request(b, tape, "alone", prompt, max_tokens,
                          **(SAMPLED if sampled else {}))
            drive(b, tape, {0: [req]})
            memo[key] = received(req)
            assert len(memo[key]) == max_tokens + 1 and memo[key][-1] is END
        return memo[key]

    yield run
    for b in batchers.values():
        b.close()


# ------------------------------------------------------------------ the rule
PROMPTS = {"A": [3, 17, 42, 5, 9], "C": [9, 1, 4, 7, 30, 2], "E": [5, 6, 2, 8]}
MAX_TOKENS = {"A": 20, "C": 5, "D": 9, "E": 7}


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("chunks", [1, 3])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
@hard_timeout(300)
def test_a_drains_tokens_leave_after_the_joiners_chunk_is_dispatched(
        engines, alone, monkeypatch, paged, chunks, sampled):
    """A and C decode; D (``chunks`` chunks) and E join before tick 2, whose
    drain harvests the block in which C reaches ``max_tokens``: C's slot is
    the first free one, and D takes it in the same tick."""
    tape = Tape()
    b = make_batcher(engines, paged, tape, monkeypatch)
    sampler = SAMPLED if sampled else {}
    prompts = dict(PROMPTS, D=list(range(40, 40 + chunks * CHUNK - 3)))
    try:
        reqs = {n: request(b, tape, n, prompts[n], MAX_TOKENS[n], **sampler)
                for n in "ACDE"}
        drive(b, tape, {0: [reqs["A"], reqs["C"]], 2: [reqs["D"], reqs["E"]]})
        stats = b.tick_phase_stats()
    finally:
        b.close()
    # every stream: the tokens it gives alone, in that order, its end last
    for n, req in reqs.items():
        assert received(req) == alone(paged, sampled, prompts[n], MAX_TOKENS[n]), n
    held = held_ticks(tape)
    # D's chunks and E's one, each under a drain of the block A decodes in
    assert len(held) == chunks + 1
    events, d, c = held[0]
    # the first of them is tick 2: one block of A (3 tokens) and of C, which
    # ends on the block's first token; C's slot goes to D before the chunk
    c_slot = next(e[2] for e in tape.events if e[:2] == ("assign", "C"))
    assert events[d] == ("drain", "admit", "block")
    assert ("assign", "D", c_slot) in events[d:c]
    assert ("assign", "E", 2) in events[d:c]
    assert [e[0] for e in events[d + 1:c]] == ["assign", "claim", "assign", "claim"]
    after = events[c + 1:]
    if after[0] == ("finish_join",):
        # the join's last chunk (E's, or D's one): the first token is
        # sampled on the device, the block behind the chunk is dispatched,
        # the hold lets go, and only then the blocking read and its put
        assert after[1:3] == [("round_end",), ("block",)]
        read = after.index(("read", True))
        assert after[read + 1][0] == "put" and after[read + 1][1] in "DE"
        assert after[read + 2:] == []  # the harvest is of nothing
        after = after[3:read]
    flushed = [e for e in after if e[0] == "put"]
    assert after[:len(flushed)] == flushed and len(flushed) == 5
    assert [e[1] for e in flushed].count("A") == 3
    assert [e for e in flushed if e[1] == "C"][-1] == ("put", "C", END)
    # the counters: what each of those ticks deferred, one hold each
    deferred = 0
    for events, d, c in held:
        rest = events[c + 1:]
        stop = next((i for i, e in enumerate(rest) if e[0] == "read"), len(rest))
        deferred += sum(e[0] == "put" for e in rest[:stop])
    assert stats["emit_held"] == {"chunk": deferred, "tick_end": 0, "fail": 0}
    assert stats["emit_holds"] == len(held)
    assert 0 < stats["emit_hold_seconds"] < 30
    # tick 0: A and C join with nothing decoding, both close in it, and one
    # block goes out in front of both reads; every join of the run read its
    # first token behind its block, and each stream got that token in the
    # closing tick and its block's tokens a tick later at the earliest
    first = tape.ticks()[0]
    assert [e[0] for e in first if e[0] != "assign"] == [
        "claim", "claim", "chunk", "finish_join", "chunk", "finish_join",
        "round_end", "block", "read", "put", "read", "put"]
    assert [e[1] for e in first if e[0] == "put"] == ["A", "C"]
    assert stats["join_first_reads"] == {"behind_block": 4, "before_block": 0}
    for events in tape.ticks():
        reads = [i for i, e in enumerate(events) if e[0] == "read"]
        for i in reads:
            joiner = events[i + 1][1]
            assert [e for e in events if e[:2] == ("put", joiner)] == [events[i + 1]]
    lines = []
    _render_tick_phases(lines, stats)
    assert 'mst_join_first_reads_total{order="behind_block"} 4' in lines
    assert f'mst_emit_held_total{{flush="chunk"}} {deferred}' in lines
    assert f"mst_emit_hold_seconds_count {len(held)}" in lines
    # every decode-block token a stream got was counted, and no other
    got = sum(len(received_) - 2 for received_ in (
        alone(paged, sampled, prompts[n], MAX_TOKENS[n]) for n in "ACDE"))
    assert stats["tokens_emitted"] == got


# ----------------------------------------------------------------- the exits
@pytest.mark.parametrize("exit_", ["head_does_not_fit", "cancelled", "shed"])
@hard_timeout(300)
def test_a_drain_that_dispatches_no_chunk_lets_go_at_the_ticks_end(
        engines, alone, monkeypatch, exit_):
    tape = Tape()
    b = make_batcher(engines, True, tape, monkeypatch)
    try:
        a = request(b, tape, "A", PROMPTS["A"], 20)
        d = request(b, tape, "D", PROMPTS["E"], 7)
        decoding(b, tape, a)
        stolen = []
        if exit_ == "head_does_not_fit":
            stolen = b.pool.take(b.pool.free)
        elif exit_ == "shed":
            d.deadlines = Deadlines(submitted_at=b._clock() - 2.0,
                                    ttft_deadline=b._clock() - 1.0)
        else:
            # the consumer walks away between the drain and the claim
            quiesce = b._quiesce

            def cancel_after(reason):
                quiesce(reason)
                d.cancelled = True
            b._quiesce = cancel_after
        b._waiting.append(d)
        tick(b, tape)
        events = tape.ticks()[-1]
        drain = events.index(("drain", "admit", "block"))
        assert not any(e[0] in ("chunk", "claim") for e in events)
        puts = [e for e in events[drain:] if e[0] == "put"]
        # let go at the round's end, in front of the tick's block
        assert puts + [("block",)] == events[events.index(("round_end",)) + 1:]
        assert [e[1] for e in puts[:BLOCK]] == ["A"] * BLOCK
        stats = b.tick_phase_stats()
        if exit_ == "head_does_not_fit":
            assert len(puts) == BLOCK and not ended(d)
        else:
            assert len(puts) == BLOCK + 1 and puts[-1][1] == "D"
            assert (puts[-1][2] is END) == (exit_ == "cancelled")
        assert stats["emit_held"] == {"chunk": 0, "tick_end": len(puts), "fail": 0}
        assert stats["emit_holds"] == 1
        # the line moves on once the pages are back
        b.pool.unref(stolen[::-1])  # the free list as it was
        drive(b, tape, {}, admitted=[a, d])
    finally:
        b.close()
    assert received(a) == alone(True, False, PROMPTS["A"], 20)
    got = received(d)
    if exit_ == "head_does_not_fit":
        assert got == alone(True, False, PROMPTS["E"], 7)
    elif exit_ == "cancelled":
        assert got == [END]
    else:
        assert len(got) == 1 and isinstance(got[0], RequestTimeoutError)
    held_ticks(tape)


@pytest.mark.parametrize("where", ["claim", "chunk", "block"])
@hard_timeout(300)
def test_a_failure_under_a_hold_sends_the_tokens_first_then_the_exception(
        engines, monkeypatch, where):
    """``block``: the dispatch between ``finish_join`` and the first
    token's read fails — the hold is still open, the token unread and
    counted nowhere: the joiner gets the exception alone."""
    tape = Tape()
    b = make_batcher(engines, True, tape, monkeypatch)
    boom = RuntimeError("the join broke")

    def broken(*args):
        raise boom
    try:
        a = request(b, tape, "A", PROMPTS["A"], 20)
        d = request(b, tape, "D", PROMPTS["E"], 7)
        decoding(b, tape, a)
        if where == "claim":
            b._claim_slot = broken
        elif where == "block":
            b._dispatch_block = broken
        else:
            monkeypatch.setattr(b.engine, "prefill_slot", lambda: broken)
        b._waiting.append(d)
        tick(b, tape)
        stats = b.tick_phase_stats()
    finally:
        b.close()
    got_a, got_d = received(a), received(d)
    # A: its first token, the block before the drain, the drained block,
    # THEN the exception; what was counted as emitted is what it received
    assert got_a[-1] is boom and all(isinstance(t, int) for t in got_a[:-1])
    assert len(got_a) - 2 == stats["tokens_emitted"] == 2 * BLOCK
    # (a request whose claim raised is in no list _fail_all walks, as on
    # the parent: its consumer's deadline ends it)
    assert got_d == ([] if where == "claim" else [boom])
    assert stats["join_first_reads"] == {"behind_block": 1, "before_block": 0}
    assert stats["emit_held"] == {"chunk": 0, "tick_end": 0, "fail": BLOCK}
    assert stats["emit_holds"] == 1
    events = tape.ticks()[-1]
    drain = events.index(("drain", "admit", "block"))
    puts = [e[1:] for e in events[drain:] if e[0] == "put"]
    assert puts[:BLOCK] == [("A", t) for t in got_a[-1 - BLOCK:-1]]
    assert puts[BLOCK:] == [("A", boom), ("D", boom)][:1 + (where != "claim")]
    assert (("finish_join",) in events) == (where == "block")
    assert not any(e[0] == "read" for e in events)


@hard_timeout(300)
def test_a_fault_at_the_ticks_start_finds_no_hold_and_loses_no_token(engines):
    """``scheduler.tick`` fires before anything is drained: the streams get
    every token counted as emitted, then the exception."""
    b = make_batcher(engines, True)
    got, decoding = [], threading.Event()

    def consume():
        try:
            for t, _ in b.generate_step(PROMPTS["A"], max_tokens=40):
                got.append(t)
                if len(got) == 4:
                    decoding.set()
        except Exception as e:  # noqa: BLE001 — the stream's end
            got.append(e)

    th = threading.Thread(target=consume)
    try:
        th.start()
        assert decoding.wait(timeout=120)
        faults.arm("scheduler.tick", exc=faults.FaultError, times=1,
                   match={"engine": id(b)})
        th.join(timeout=120)
        assert not th.is_alive()
        stats = b.tick_phase_stats()
    finally:
        b.close()
    assert isinstance(got[-1], faults.FaultError)
    assert len(got) - 2 == stats["tokens_emitted"]
    assert sum(stats["emit_held"].values()) == 0 and b._held is None


@hard_timeout(300)
def test_close_under_an_open_hold_ends_the_streams_after_their_tokens(engines):
    """``close()`` arrives while the tick is between the drain and the
    chunk: the tick runs on to its chunk, the held tokens go out, and only
    then do the shutdown's sentinels."""
    b = make_batcher(engines, True)
    claim = b._claim_slot
    in_hold, release = threading.Event(), threading.Event()
    got = {"A": [], "D": []}
    decoding = threading.Event()

    def gated_claim(*args):
        if decoding.is_set():  # the joiner's claim, under the hold
            assert b._held  # the drained block's tokens wait in it
            in_hold.set()
            assert release.wait(timeout=60)
        return claim(*args)

    def consume(name, prompt, n):
        for t, _ in b.generate_step(prompt, max_tokens=n):
            got[name].append(t)
            if name == "A" and len(got[name]) == 4:
                decoding.set()

    b._claim_slot = gated_claim
    threads = [threading.Thread(target=consume, args=("A", PROMPTS["A"], 50))]
    try:
        threads[0].start()
        assert decoding.wait(timeout=120)
        threads.append(threading.Thread(target=consume, args=("D", PROMPTS["E"], 9)))
        threads[1].start()
        assert in_hold.wait(timeout=120)
        time.sleep(0.3)  # A's consumer reads what was put before the hold
        seen = len(got["A"])
        closer = threading.Thread(target=b.close)
        closer.start()
        time.sleep(0.05)
        assert len(got["A"]) == seen  # nothing leaves while the hold is open
        release.set()
        for th in threads + [closer]:
            th.join(timeout=120)
            assert not th.is_alive()
        stats = b.tick_phase_stats()
    finally:
        release.set()
        b.close()
    assert b._held is None
    assert stats["emit_held"]["chunk"] >= BLOCK and stats["emit_holds"] >= 1
    # both streams ended cleanly, A with every token that was counted
    assert len(got["A"]) > seen and len(got["D"]) >= 1
    assert len(got["A"]) + len(got["D"]) - 2 == stats["tokens_emitted"]


@hard_timeout(120)
def test_the_loops_end_and_the_idle_wait_let_a_hold_go_first(engines):
    """Neither is reached with a hold open as the tick stands (it flushes
    when ``_prefill_round`` returns); the two calls keep that true whatever
    a later tick does: no hold spans a wait on the queue, and no sentinel
    overtakes a held token."""
    tape = Tape()
    b = make_batcher(engines, True)
    try:
        a, w = (request(b, tape, n, PROMPTS["A"], 5) for n in "AW")
        b._held = []
        b._hand(a, (7, None))
        b._idle_wait()
        assert b._held is None and received(a) == [7]
        b._held = []
        b._hand(w, (8, None))
        b._hand(w, (9, None))
        b._waiting.append(w)
        b._stop = True
        b._loop()  # no tick runs: the shutdown's hand-overs alone
        assert b._held is None and received(w) == [8, 9, END]
        stats = b.tick_phase_stats()
        assert stats["emit_held"] == {"chunk": 0, "tick_end": 3, "fail": 0}
        assert stats["emit_holds"] == 2
        b._flush_held("tick_end")  # idempotent: nothing open, nothing counted
        assert b.tick_phase_stats()["emit_holds"] == 2
    finally:
        b.close()


@hard_timeout(300)
def test_a_speculative_rounds_harvest_is_held_alike(engines, monkeypatch):
    """``draft="ngram"``: the lookahead slot holds a speculative round, its
    harvest emits through the same ``_emit``; streams as without a joiner."""
    def run(together):
        tape = Tape()
        b = tap(ContinuousBatcher(engines[True], decode_block=BLOCK,
                                  draft="ngram", spec_clock=lambda: 0.0),
                tape, monkeypatch)
        try:
            a = request(b, tape, "A", [1, 2, 3, 4] * 3, 30)
            d = request(b, tape, "D", [4, 3, 2, 1] * 2, 12)
            for arrivals in ([{0: [a], 3: [d]}] if together
                             else [{0: [a]}, {0: [d]}]):
                drive(b, tape, arrivals)
            return (tape, {"A": received(a), "D": received(d)},
                    b.spec_stats(), b.tick_phase_stats())
        finally:
            b.close()

    _, apart, _, _ = run(False)
    tape, together, spec, stats = run(True)
    assert together == apart
    assert [len(v) for v in together.values()] == [31, 13]
    assert spec["rounds"] > 0
    # the next round's guess is built from host history: every first token
    # is read in front of the round (or the plain block in its place)
    held = held_ticks(tape, behind=False)
    assert held and any(events[d][2] == "spec" for events, d, _ in held)
    assert any(e == ("spec",) for events, _, _ in held for e in events)
    assert stats["emit_held"]["chunk"] > 0 and stats["emit_held"]["fail"] == 0
    assert stats["join_first_reads"] == {"behind_block": 0, "before_block": 2}


# ------------------------------------------------ the parent's order, to the bit
def everything(req):
    """A stream as its consumer finds it, log-probabilities and all: the
    first token's lazy device row, a block token's summary, END."""
    out = []
    while True:
        try:
            item = req.out.get_nowait()
        except queue.Empty:
            return out
        if item is None or isinstance(item, BaseException):
            out.append(END if item is None else item)
            continue
        tok, lp = item
        if lp is not None and not isinstance(lp, jax.Array):
            lp = (lp.chosen, lp.top_indices, lp.top_values)
        out.append((int(tok), None if lp is None else jax.tree.map(np.asarray, lp)))


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("chunks", [1, 3])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
@hard_timeout(300)
def test_token_ids_and_log_probabilities_are_those_of_the_read_in_front(
        engines, monkeypatch, paged, chunks, sampled):
    """The same arrivals under both orders: the block in front of the read,
    and the parent's (every first token read, emitted and, where it ends
    its stream, finished before the block is dispatched), which
    ``_block_leads`` answering no gives back. A and C join in one tick with
    nothing decoding; D (``chunks`` chunks), E and F (``max_tokens`` 1)
    join a decoding batch. Every stream, item for item: token ids, the
    first token's log-probability row, each block token's summary."""
    sampler = SAMPLED if sampled else {}
    prompts = dict(PROMPTS, D=list(range(40, 40 + chunks * CHUNK - 3)), F=[8, 8, 1])
    max_tokens = dict(MAX_TOKENS, F=1)
    got = {}
    for order in ("behind_block", "before_block"):
        tape = Tape()
        b = make_batcher(engines, paged, tape, monkeypatch)
        if order == "before_block":
            b._block_leads = lambda: False
        try:
            reqs = {n: request(b, tape, n, prompts[n], max_tokens[n], **sampler)
                    for n in "ACDEF"}
            for req in reqs.values():
                req.want_logprobs = True
            drive(b, tape, {0: [reqs["A"], reqs["C"]],
                            2: [reqs["D"], reqs["E"], reqs["F"]]})
            stats = b.tick_phase_stats()
        finally:
            b.close()
        assert stats["join_first_reads"][order] == 5 == sum(
            stats["join_first_reads"].values())
        closed = [closing_tick(t, behind=order == "behind_block") for t in tape.ticks()]
        assert sum(closed) == 5 and closed[0] == 2
        got[order] = {n: everything(req) for n, req in reqs.items()}
    new, old = got["behind_block"], got["before_block"]
    for n in "ACDEF":
        assert len(new[n]) == len(old[n]) == max_tokens[n] + 1 and new[n][-1] is END, n
        for (tok, lp), (tok0, lp0) in zip(new[n][:-1], old[n][:-1]):
            assert tok == tok0, n
            assert jax.tree.structure(lp) == jax.tree.structure(lp0), n
            for x, x0 in zip(jax.tree.leaves(lp), jax.tree.leaves(lp0)):
                np.testing.assert_array_equal(x, x0, err_msg=n)
        assert new[n][0][1].shape[-1] == TINY["vocab_size"]  # the first token's row
        if len(new[n]) > 2:
            assert len(new[n][1][1]) == 3  # a block token's summary


# ------------------------------------------------- the read's place, and exits
@pytest.mark.parametrize("why", ["prefill_only", "sync_tick", "growth_may_preempt"])
@hard_timeout(300)
def test_a_read_the_host_must_act_on_stays_in_front_of_the_block(
        engines, alone, monkeypatch, why):
    """A decodes, D joins: D's first token is read before the tick's block
    where the slot must never enter one (``prefill_only``), where the tick
    is the synchronous one, and where page growth might preempt."""
    from mlx_sharding_tpu.scheduler import HandoffReadyError

    tape = Tape()
    kw = {"sync_tick": dict(async_sched="off"),
          "growth_may_preempt": dict(overcommit=True)}.get(why, {})
    b = make_batcher(engines, True, tape, monkeypatch, **kw)
    try:
        a = request(b, tape, "A", PROMPTS["A"], 20)
        d = request(b, tape, "D", PROMPTS["E"], 7)
        d.prefill_only = why == "prefill_only"
        b._waiting.append(a)
        tick(b, tape)
        tick(b, tape)
        if why == "growth_may_preempt":
            b._growth_fits = lambda: False  # what the tick observes
        b._waiting.append(d)
        tick(b, tape)
        events = tape.ticks()[-1]
        assert closing_tick(events, behind=False) == 1
        if why == "growth_may_preempt":
            del b._growth_fits
        drive(b, tape, {}, admitted=[a, d])
        stats = b.tick_phase_stats()
    finally:
        b.close()
    assert received(a) == alone(True, False, PROMPTS["A"], 20)
    got = received(d)
    want = alone(True, False, PROMPTS["E"], 7)
    if why == "prefill_only":
        # its first token, then the handoff: the slot never entered a block
        assert got[:1] == want[:1] and isinstance(got[1], HandoffReadyError)
        assert len(got) == 2
    else:
        assert got == want
    # A's own join, alone in tick 0, read behind its block but in the sync tick
    assert closing_tick(tape.ticks()[0], behind=why != "sync_tick") == 1
    assert stats["join_first_reads"] == {
        "behind_block": int(why != "sync_tick"),
        "before_block": 1 + (why == "sync_tick")}


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
@hard_timeout(300)
def test_a_first_token_that_ends_its_stream_finishes_it_behind_the_block(
        engines, alone, monkeypatch, paged, sampled):
    """``max_tokens`` 1: the slot is live in the block dispatched in front
    of the read, ``_finish`` runs after it, the block's positions for the
    slot are dropped as ``slot_finished`` and its pages are back at once."""
    tape = Tape()
    b = make_batcher(engines, paged, tape, monkeypatch)
    sampler = SAMPLED if sampled else {}
    try:
        a = request(b, tape, "A", PROMPTS["A"], 20, **sampler)
        d = request(b, tape, "D", PROMPTS["E"], 1, **sampler)
        e = request(b, tape, "E", PROMPTS["C"], 5, **sampler)
        decoding(b, tape, a)
        before = b.tick_phase_stats()
        free = b.pool.free if paged else 0
        b._waiting.append(d)
        tick(b, tape)
        events = tape.ticks()[-1]
        assert closing_tick(events) == 1
        assert events[-2:] == [("put", "D", events[-2][2]), ("put", "D", END)]
        assert d.slot == -1 and b._inflight is not None
        assert [slot for slot, r in b._inflight.live if r is d]  # live in it
        if paged:
            assert b.pool.free == free
        # the next joiner takes the slot while that block is still unread
        drive(b, tape, {0: [e]}, admitted=[a, d])
        stats = b.tick_phase_stats()
    finally:
        b.close()
    for req, n in ((a, "A"), (d, "E"), (e, "C")):
        assert received(req) == alone(paged, sampled, PROMPTS[n], req.max_tokens), n
    dropped = (stats["tokens_dropped"]["slot_finished"]
               - before["tokens_dropped"]["slot_finished"])
    assert dropped >= BLOCK and stats["tokens_dropped"]["cancelled"] == 0
    assert stats["join_first_reads"] == {"behind_block": 3, "before_block": 0}
    held_ticks(tape)


@pytest.mark.parametrize("chunks", [1, 3])
@hard_timeout(300)
def test_a_joiner_cancelled_between_the_dispatch_and_the_read_loses_no_token(
        engines, alone, monkeypatch, chunks):
    """The consumer walks away while the block is being dispatched: the
    first token was sampled and is emitted as on the parent, the next
    tick reaps the slot, and the block's positions for it are dropped."""
    tape = Tape()
    b = make_batcher(engines, True, tape, monkeypatch)
    prompt = list(range(40, 40 + chunks * CHUNK - 3))
    try:
        a = request(b, tape, "A", PROMPTS["A"], 20)
        d = request(b, tape, "D", prompt, 9)
        decoding(b, tape, a)
        dispatch = b._dispatch_block

        def cancel_behind():
            inf = dispatch()
            d.cancelled = d.cancelled or b._prefill_done(d)
            return inf
        b._dispatch_block = cancel_behind
        drive(b, tape, {0: [d]}, admitted=[a])
        stats = b.tick_phase_stats()
    finally:
        b.close()
    assert received(a) == alone(True, False, PROMPTS["A"], 20)
    assert received(d) == [alone(True, False, prompt, 9)[0], END]
    assert stats["tokens_dropped"]["cancelled"] >= BLOCK
    assert stats["join_first_reads"] == {"behind_block": 2, "before_block": 0}
    assert len(held_ticks(tape)) == chunks


@hard_timeout(300)
def test_a_fault_at_the_harvest_behind_a_join_sends_the_tokens_first(engines, monkeypatch):
    """``scheduler.harvest`` fires at the read of the block that was
    dispatched in front of the joiner's first token: both streams have
    every token counted (the joiner its first), then the exception."""
    tape = Tape()
    b = make_batcher(engines, True, tape, monkeypatch)
    try:
        a = request(b, tape, "A", PROMPTS["A"], 20)
        d = request(b, tape, "D", PROMPTS["E"], 7)
        decoding(b, tape, a)
        b._waiting.append(d)
        tick(b, tape)
        assert closing_tick(tape.ticks()[-1]) == 1
        faults.arm("scheduler.harvest", exc=faults.FaultError, times=1)
        tick(b, tape)
        stats = b.tick_phase_stats()
    finally:
        b.close()
    got_a, got_d = received(a), received(d)
    assert isinstance(got_a[-1], faults.FaultError) and got_d[-1] is got_a[-1]
    assert len(got_d) == 2 and len(got_a) - 2 == stats["tokens_emitted"] == 2 * BLOCK
    assert stats["blocks_abandoned"] == 2  # the one read, the one behind it
    assert stats["emit_held"] == {"chunk": BLOCK, "tick_end": 0, "fail": 0}
