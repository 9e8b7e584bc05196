"""A join reaches the device as three programs — the slot claim, each
prefill chunk, the first token — with arguments made on the host
(``scheduler.py``: ``claim_slot``, ``prefill_chunk``, ``finish_join``).

The plain reference is the sequence of eager conversions and one-row
setters a join was before, written out below (``old_claim``,
``old_finish``): the device state a join leaves and its first token are
the same bit for bit; the key ``finish_join`` seeds is
``jax.random.PRNGKey(seed)``; a join runs exactly the programs
``mst_join_programs_total`` names and nothing eager on the tick thread; a
second join of the same shapes compiles nothing."""

import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.sample import (
    make_sampler_params,
    sampler_params_host,
    seed_key_row,
)
from mlx_sharding_tpu.scheduler import ContinuousBatcher, _pack_i32, _Request
from tests.helpers import hard_timeout

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)
PAGE = CHUNK = 8
SAMPLER = dict(temperature=0.8, top_p=0.9, repetition_penalty=1.3,
               logit_bias={5: 2.0, 7: -1.5, 200: 0.25})
SEED = (1 << 31) + 12345  # a client's seed above 31 bits


@pytest.fixture(scope="module")
def engines():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    mesh = pipeline_mesh(1)
    kw = dict(microbatches=3, max_seq=64, cache_dtype=jnp.float32,
              prefill_chunk=CHUNK)
    return {
        "paged": PipelineEngine(model, params, mesh, pool_pages=24,
                                page_size=PAGE, **kw),
        "dense": PipelineEngine(model, params, mesh, **kw),
        "draft": PipelineEngine(model, params, mesh, **kw),
    }


def make_batcher(engines, paged=True, draft=False, prefix=False, **kw):
    if draft:
        kw.update(draft_engine=engines["draft"], spec_k=3, async_sched="off")
    return ContinuousBatcher(engines["paged" if paged else "dense"],
                             decode_block=3, prefix_cache=prefix, **kw)


def request(batcher, prompt, seed=SEED, rep_context=20, resumed=None, **sampler):
    width = batcher.sp.bias_indices.shape[1]
    req = _Request(
        prompt=np.asarray(prompt, np.int32), seed=seed, max_tokens=8,
        sp=sampler_params_host(**sampler, slots=width),
        rep_context=rep_context, **sampler,
    )
    if resumed is not None:
        req.resume_keys, req.resume_recent = resumed
    return req


def join(batcher, req, slot):
    """One join on the calling thread (the scheduler thread never starts):
    claim, chunks, first token. Returns (token, log-probabilities)."""
    batcher._assign_slot(req, slot)
    while not batcher._prefill_done(req):
        batcher._prefill_one_chunk(req)
    batcher._read_first_tokens("before_block")  # the async tick's own call
    tok, logprobs = req.out.get_nowait()
    return tok, np.asarray(logprobs)


def device_state(b):
    state = {
        "offset": b.cache.offset, "rep_sizes": b.rep_sizes, "recent": b.recent,
        "keys": b.keys, "last_tok": b.last_tok, "active": b.active,
        **{f"sp.{k}": v for k, v in b.sp._asdict().items()},
    }
    if b.paged:
        state["table"] = b.table
    if b.draft is not None:
        state["draft_offset"] = b.dcache.offset
    return {k: np.asarray(v) for k, v in state.items()}


# ------------------------------------------------------ the plain reference
def install_old_sequence(b):
    """Make ``b`` join as a batcher did before: every value converted by an
    eager ``jnp`` call, every row written by a program of its own."""
    row_set = b._row_set
    sp_set = jax.jit(lambda batched, one, slot: jax.tree.map(
        lambda full, x: full.at[slot].set(x), batched, one))
    set_last = jax.jit(lambda lt, slot, tok: lt.at[slot, 0].set(tok))
    first_sample = jax.jit(b.first_sample)

    def old_claim(self, req, slot, pages, start):
        slot_arr = jnp.asarray(slot, jnp.int32)
        if self.paged:  # _write_table_row
            row = np.full((self.engine.slot_pages,), self.engine.pool_pages,
                          np.int32)
            row[: len(pages)] = pages
            self.table = row_set(self.table, jnp.asarray(slot, jnp.int32),
                                 jnp.asarray(row))
        self.cache = self.cache._replace(offset=row_set(
            self.cache.offset, slot_arr, jnp.asarray(start, jnp.int32)))
        # _write_sampler_row: the request's own narrow row, padded eagerly
        one = make_sampler_params(req.temperature, req.top_p,
                                  req.repetition_penalty, req.logit_bias)
        width = self.sp.bias_indices.shape[1]
        n_bias = one.bias_indices.shape[0]
        one = one._replace(
            bias_indices=jnp.pad(one.bias_indices, (0, width - n_bias)),
            bias_values=jnp.pad(one.bias_values, (0, width - n_bias)),
        )
        self.sp = sp_set(self.sp, one, slot_arr)
        self.rep_sizes = row_set(self.rep_sizes, slot_arr,
                                 jnp.asarray(req.rep_context, jnp.int32))
        if self.draft is not None:
            self.dcache = self.dcache._replace(offset=row_set(
                self.dcache.offset, slot_arr, jnp.asarray(0, jnp.int32)))

    def old_finish(logits, packed, keys, recent, sp, rep_sizes, last_tok,
                   active):
        slot = int(packed[0])
        req = b._slots[slot]
        slot_arr = jnp.asarray(slot, jnp.int32)
        if req._old_resume is not None:
            resume_keys, resume_recent = req._old_resume
            recent = row_set(recent, slot_arr, jnp.asarray(resume_recent))
            keys = row_set(keys, slot_arr, jnp.asarray(resume_keys))
        else:
            row = np.full((b.W,), -1, np.int32)
            tail = (req.prompt[-req.rep_context:] if req.rep_context
                    else req.prompt[:0])
            if tail.size:
                row[b.W - tail.size:] = tail
            recent = row_set(recent, slot_arr, jnp.asarray(row))
            keys = row_set(keys, slot_arr, jax.random.PRNGKey(req.seed))
        tok, logprobs, keys, recent = first_sample(
            logits, keys, sp, recent, rep_sizes, slot_arr)
        last_tok = set_last(last_tok, slot_arr, tok)
        active = row_set(active, slot_arr, jnp.asarray(True))
        return tok, logprobs, keys, recent, last_tok, active

    b._claim = types.MethodType(old_claim, b)
    b._finish_join = old_finish


CASES = [
    # (paged, chunks after reuse, prefix hit, draft, resumed)
    (True, 1, False, False, False),
    (True, 3, False, False, False),
    (True, 1, True, False, False),
    (True, 3, True, False, False),
    (True, 1, False, True, False),
    (True, 3, False, True, False),
    (True, 1, True, True, False),
    (True, 3, True, True, False),
    (False, 1, False, False, False),
    (False, 3, False, False, False),
    (False, 1, False, True, False),
    (False, 3, False, True, False),
    (True, 1, False, False, True),
    (False, 3, False, False, True),
]


@pytest.mark.parametrize(
    "paged,chunks,hit,draft,resumed", CASES,
    ids=[f"{'paged' if p else 'dense'}-{c}chunk{'-hit' if h else ''}"
         f"{'-draft' if d else ''}{'-resumed' if r else ''}"
         for p, c, h, d, r in CASES])
@hard_timeout(300)
def test_a_join_leaves_what_the_old_sequence_of_setters_left(
        engines, paged, chunks, hit, draft, resumed):
    rng = np.random.default_rng(chunks * 7 + hit)
    shared = rng.integers(1, 250, 2 * PAGE)  # two full pages a hit reuses
    tail = rng.integers(1, 250, CHUNK * (chunks - 1) + 5)
    prompt = np.concatenate([shared, tail]) if hit else tail
    stash = None
    if resumed:
        stash = (np.asarray([7, 0xDEADBEEF], np.uint32),
                 rng.integers(-1, 250, 64).astype(np.int32))
    got = {}
    for side in ("new", "old"):
        b = make_batcher(engines, paged=paged, draft=draft, prefix=hit)
        try:
            if side == "old":
                install_old_sequence(b)
            if hit:  # an earlier request registers the shared pages, leaves
                first = request(b, np.concatenate([shared, [9, 8, 7]]), seed=3)
                first._old_resume = None
                join(b, first, 0)
                b._finish(first)
            req = request(b, prompt, resumed=stash, **SAMPLER)
            req._old_resume = stash
            tok, logprobs = join(b, req, 1)
            assert req.prefill_pos == prompt.size
            if hit:
                assert b.prefix_hits == 1 and b.prefix_tokens_reused == 2 * PAGE
            got[side] = (tok, logprobs, device_state(b), dict(b._join_programs))
        finally:
            b.close()
    (tok, logprobs, state, counted), (tok0, logprobs0, state0, _) = got["new"], got["old"]
    assert tok == tok0
    np.testing.assert_array_equal(logprobs, logprobs0)
    assert state.keys() == state0.keys()
    for name in state:
        assert state[name].dtype == state0[name].dtype, name
        np.testing.assert_array_equal(state[name], state0[name], err_msg=name)
    assert state["active"][1] and state["last_tok"][1, 0] == tok
    joins = 2 if hit else 1
    n_chunks = chunks * (1 + draft) if not hit else None
    assert counted["claim"] == joins and counted["finish"] == joins
    assert counted["other"] == 0
    if n_chunks is not None:
        # with a draft the target's and the draft's chunks are each a dispatch
        assert counted["chunk"] == n_chunks


@pytest.mark.parametrize("seed", [0, 1, (1 << 31) - 1, (1 << 31) + 12345,
                                  (1 << 40) + 3, -1])
def test_the_host_made_key_is_prngkey(engines, seed):
    """``seed_key_row`` is ``jax.random.PRNGKey`` word for word, and the key a
    join leaves in the slot's row is that key split once, as ``first_sample``
    splits it."""
    want = jax.random.PRNGKey(seed)
    row = seed_key_row(seed)
    assert row.dtype == np.uint32 and row.shape == (2,)
    np.testing.assert_array_equal(row, np.asarray(want))
    b = make_batcher(engines, paged=False)
    try:
        join(b, request(b, [3, 4, 5], seed=seed, temperature=0.7), 2)
        np.testing.assert_array_equal(
            np.asarray(b.keys[2]), np.asarray(jax.random.split(want)[0]))
    finally:
        b.close()


def test_pack_i32_keeps_every_bit():
    parts = (np.int32(-7), np.asarray([0, 0xFFFFFFFF], np.uint32),
             np.asarray([-0.0, np.nan, 1e-42, 3.5], np.float32),
             np.asarray(2.5, np.float32))
    packed = _pack_i32(*parts)
    assert packed.dtype == np.int32 and packed.shape == (8,)
    assert packed[1:3].view(np.uint32).tolist() == [0, 0xFFFFFFFF]
    assert packed[3:7].view(np.float32).tobytes() == parts[2].tobytes()


class Log:
    """What the tick thread ran, in order: the batcher's jitted helpers by
    the counter's names, decode blocks, and eager ``jnp`` calls."""

    def __init__(self):
        self.events = []

    def wrap(self, name, fn):
        def wrapped(*args, **kwargs):
            if threading.current_thread().name == "continuous-batcher":
                self.events.append(name)
            return fn(*args, **kwargs)
        return wrapped


@pytest.mark.parametrize("paged,draft", [(True, False), (False, False), (True, True)],
                         ids=["paged", "dense", "paged-draft"])
@hard_timeout(300)
def test_a_join_runs_the_programs_the_counter_names_and_nothing_eager(
        engines, monkeypatch, paged, draft):
    b = make_batcher(engines, paged=paged, draft=draft)
    log = Log()
    try:
        # warm every program (tracing calls jnp.asarray on the tick thread)
        assert len(list(b.generate_step(list(range(1, 21)), max_tokens=7))) == 7
        for name in ("_claim_slot", "_finish_join", "_resume_slot", "_row_set"):
            setattr(b, name, log.wrap(name[1:], getattr(b, name)))
        b._dispatch_block = log.wrap("block", b._dispatch_block)
        if draft:
            b._spec_tick = log.wrap("block", b._spec_tick)
        for eng in {b.engine, b.draft} - {None}:
            chunk = log.wrap("prefill_chunk", eng.prefill_slot())
            monkeypatch.setattr(eng, "prefill_slot", lambda chunk=chunk: chunk)
        for mod, name in ((jnp, "asarray"), (jnp, "pad"), (jax.random, "PRNGKey")):
            monkeypatch.setattr(mod, name, log.wrap(f"eager {name}", getattr(mod, name)))
        before = dict(b._join_programs)
        # three chunks, sampled, biased: the same programs
        assert len(list(b.generate_step(list(range(1, 21)), max_tokens=7,
                                        seed=SEED, **SAMPLER))) == 7
    finally:
        b.close()
    events = log.events
    assert events[0] == "claim_slot"
    to_block = events[: events.index("block")]
    chunks = ["prefill_chunk"] * (3 * (1 + draft))
    assert to_block == ["claim_slot"] + chunks + ["finish_join"], events
    counted = {k: v - before[k] for k, v in b._join_programs.items()}
    assert counted == {"claim": 1, "chunk": len(chunks), "finish": 1, "other": 0}


@hard_timeout(300)
def test_a_second_join_of_the_same_shapes_compiles_nothing(engines):
    import jax.monitoring

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    rng = np.random.default_rng(5)
    for paged in (True, False):
        b = make_batcher(engines, paged=paged)
        try:
            join(b, request(b, rng.integers(1, 250, 13), seed=1), 0)  # warm-up
            assert compiles  # the listener hears this process's compiles
            del compiles[:]
            # what a request can bring: another slot, another length, a
            # sampled and biased row, a seed over 31 bits, a resumed stream
            join(b, request(b, rng.integers(1, 250, 21), **SAMPLER), 1)
            stash = (np.asarray([1, 2], np.uint32), np.full((64,), 9, np.int32))
            join(b, request(b, rng.integers(1, 250, 6), resumed=stash,
                            rep_context=0, temperature=0.5), 2)
            assert compiles == []
        finally:
            b.close()
