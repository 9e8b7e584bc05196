"""A tick that drains the pipeline for a joiner hands the drained block's
tokens to their streams only once the device has the joiner's chunk
(``scheduler._hand`` / ``_flush_held``, opened in ``_tick_async``).

The tick is driven by hand on the test's own thread wherever the order of
events matters (the scheduler thread never starts), with a tape of what it
ran: drains, slot claims, chunk dispatches, the blocking read of a first
token, and every ``put`` on a request's queue. The plain reference for what
a stream receives is the same request served alone, no joiner interleaved:
the hold moves when an item is handed over, never which or in what order."""

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

    prefill_round, b._prefill_round = b._prefill_round, taped_round
    b._quiesce, b._assign_slot, b._claim_slot, b._finish_join = (
        taped_quiesce, taped_assign, taped_claim, taped_finish)
    monkeypatch.setattr(b.engine, "prefill_slot", lambda: taped_chunk)
    return b


def make_batcher(engines, paged, tape=None, monkeypatch=None, **kw):
    b = ContinuousBatcher(engines[paged], decode_block=BLOCK, **kw)
    assert b._async
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
        b._tick_async()
    except Exception as exc:  # noqa: BLE001 — as _loop does
        b._fail_all(exc)
    assert b._held is None, "a hold outlived its tick"


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


def held_ticks(tape):
    """The ticks that drained for a joiner and dispatched its chunk: for
    each, ``(events, drain index, chunk index)``; asserts the rule on every
    tick that drained for one — no put between the drain and the chunk's
    dispatch (``_prefill_round``'s return where it dispatched none), the
    hold closed before the blocking read."""
    found = []
    for events in tape.ticks():
        drains = [i for i, e in enumerate(events)
                  if e[0] == "drain" and e[1] in ("admit", "prefilling")]
        chunks = [i for i, e in enumerate(events) if e[0] == "chunk"]
        if not drains:
            continue
        d = drains[0]
        c = chunks[0] if chunks else events.index(("round_end",))
        assert not [e for e in events[d:c] if e[0] == "put"], events
        for e in events:
            if e[0] == "read":
                assert e[1], "a hold was open at the blocking read"
        if chunks:
            found.append((events, d, c))
    return found


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
        # dispatched too, the hold lets go, and only then the blocking read
        read = after.index(("read", True))
        assert after[read + 1][0] == "put" and after[read + 1][1] in "DE"
        after = after[1:read]
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
    lines = []
    _render_tick_phases(lines, stats)
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
            stolen, b._free_pages[:] = b._free_pages[:], []
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
        assert puts == events[events.index(("round_end",)) + 1:]
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
        b._free_pages[:] = stolen or b._free_pages
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


@pytest.mark.parametrize("where", ["claim", "chunk"])
@hard_timeout(300)
def test_a_failure_under_a_hold_sends_the_tokens_first_then_the_exception(
        engines, monkeypatch, where):
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
    assert got_d == ([boom] if where == "chunk" else [])
    assert stats["emit_held"] == {"chunk": 0, "tick_end": 0, "fail": BLOCK}
    assert stats["emit_holds"] == 1
    events = tape.ticks()[-1]
    drain = events.index(("drain", "admit", "block"))
    puts = [e[1:] for e in events[drain:] if e[0] == "put"]
    assert puts[:BLOCK] == [("A", t) for t in got_a[-1 - BLOCK:-1]]
    assert puts[BLOCK:] == [("A", boom), ("D", boom)][:1 + (where == "chunk")]


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
    held = held_ticks(tape)
    assert held and any(events[d][2] == "spec" for events, d, _ in held)
    assert stats["emit_held"]["chunk"] > 0 and stats["emit_held"]["fail"] == 0
