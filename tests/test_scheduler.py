"""Continuous batching: slot-level request interleaving on the fused engine.

The key properties (VERDICT r1 item 3): concurrent requests produce exactly
the tokens they would produce run serially (per-slot offsets, sampler state
and PRNG chains are fully independent), requests genuinely interleave in one
engine, slots are reclaimed and reused, and batched decode beats serial
throughput.
"""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher

TINY = dict(
    vocab_size=256,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=4,
    num_attention_heads=4,
    num_key_value_heads=2,
)


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    batcher = ContinuousBatcher(eng)
    ref_gen = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    yield batcher, ref_gen
    batcher.close()


def _run(gen, prompt, **kw):
    return [t for t, _ in gen.generate_step(prompt, **kw)]


def _concurrent(batcher, jobs):
    """Run several generate_step calls in parallel threads, recording each
    token's arrival time."""
    results = [None] * len(jobs)
    times = [None] * len(jobs)

    def worker(i, prompt, kw):
        toks, stamps = [], []
        for t, _ in batcher.generate_step(prompt, **kw):
            toks.append(t)
            stamps.append(time.monotonic())
        results[i] = toks
        times[i] = stamps

    threads = [
        threading.Thread(target=worker, args=(i, p, kw))
        for i, (p, kw) in enumerate(jobs)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
        assert not th.is_alive(), "generation thread hung"
    return results, times


def test_concurrent_greedy_matches_serial(setup):
    batcher, ref_gen = setup
    jobs = [
        ([3, 17, 42], dict(max_tokens=10)),
        ([9, 1, 4, 7], dict(max_tokens=10)),
    ]
    refs = [_run(ref_gen, p, **kw) for p, kw in jobs]
    got, times = _concurrent(batcher, jobs)
    assert got == refs
    # genuine interleaving: each request produced a token before the other
    # finished (they shared the engine, not took turns with it)
    assert times[0][0] < times[1][-1] and times[1][0] < times[0][-1]


def test_concurrent_seeded_sampling_matches_serial(setup):
    """Per-slot PRNG chains: a seeded stochastic request yields the same
    tokens alone or interleaved with a different request."""
    batcher, ref_gen = setup
    jobs = [
        ([5, 6, 2], dict(temperature=0.9, top_p=0.8, seed=11, max_tokens=8)),
        ([8, 8, 1], dict(temperature=1.3, top_p=0.95, seed=977, max_tokens=8)),
    ]
    refs = [_run(ref_gen, p, **kw) for p, kw in jobs]
    got, _ = _concurrent(batcher, jobs)
    assert got == refs


def test_repetition_penalty_context_matches_serial(setup):
    batcher, ref_gen = setup
    kw = dict(repetition_penalty=1.4, repetition_context_size=6, max_tokens=10)
    prompt = [3, 3, 7, 7, 2]
    ref = _run(ref_gen, prompt, **kw)
    got, _ = _concurrent(batcher, [(prompt, kw), ([1, 2], dict(max_tokens=10))])
    assert got[0] == ref


def test_more_requests_than_slots(setup):
    """3 requests on a 2-slot engine: the third waits for a free slot, then
    runs correctly (slot state fully reset between tenants)."""
    batcher, ref_gen = setup
    jobs = [
        ([3, 17, 42], dict(max_tokens=6)),
        ([9, 1, 4, 7], dict(max_tokens=6)),
        ([5, 5, 5], dict(max_tokens=6)),
    ]
    refs = [_run(ref_gen, p, **kw) for p, kw in jobs]
    got, _ = _concurrent(batcher, jobs)
    assert got == refs


def test_multichunk_prompt_admission(setup):
    """Prompts longer than one prefill chunk admit via chunked slot prefill
    while the other slot keeps decoding."""
    batcher, ref_gen = setup
    long_prompt = list(range(1, 20))  # chunk=8 -> 8+8+3
    jobs = [
        (long_prompt, dict(max_tokens=6)),
        ([2, 9], dict(max_tokens=12)),
    ]
    refs = [_run(ref_gen, p, **kw) for p, kw in jobs]
    got, _ = _concurrent(batcher, jobs)
    assert got == refs


def test_capacity_error(setup):
    batcher, _ = setup
    with pytest.raises(ValueError, match="exceeds KV capacity"):
        list(batcher.generate_step(list(range(30)), max_tokens=200))


def test_throughput_beats_serial(setup):
    """Two interleaved requests take fewer scheduler ticks than the same two
    run back-to-back through the batcher: the fused step advances both slots
    in S+M-1 ticks instead of 2x S ticks. A count, not a CPU timing."""
    batcher, _ = setup
    jobs = [
        ([3, 17, 42], dict(max_tokens=25)),
        ([9, 1, 4], dict(max_tokens=25)),
    ]
    # warmup (compile both programs)
    _concurrent(batcher, [(p, dict(max_tokens=3)) for p, _ in jobs])

    def ticks(run):
        before = batcher.tick_phase_stats()
        run()
        after = batcher.tick_phase_stats()
        # an idle tick only waits for the next submission: not the work's
        idle = after["phase_entries"]["idle_wait"] - before["phase_entries"]["idle_wait"]
        return after["ticks"] - before["ticks"] - idle

    serial = ticks(lambda: [_run(batcher, p, **kw) for p, kw in jobs])
    concurrent = ticks(lambda: _concurrent(batcher, jobs))
    assert concurrent < serial, (
        f"interleaved ({concurrent} ticks) not fewer than serial ({serial} ticks)"
    )


def test_oversized_logit_bias_rejected_on_submit(setup):
    """A >512-entry logit_bias raises on the submitting thread BEFORE the
    scheduler sees it — the scheduler thread must never die on bad input."""
    batcher, _ = setup
    bias = {i: 1.0 for i in range(600)}
    with pytest.raises(ValueError, match="bias width"):
        list(batcher.generate_step([1, 2], logit_bias=bias, max_tokens=2))
    # scheduler still healthy afterwards
    assert _run(batcher, [3, 4], max_tokens=3)


def test_close_unblocks_consumers():
    """close() during in-flight generation ends the stream instead of
    hanging the consumer thread (generator hot-swap path)."""
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    b = ContinuousBatcher(eng)
    got = []

    def worker():
        for t, _ in b.generate_step([3, 1], max_tokens=50):
            got.append(t)
            if len(got) == 3:
                b.close()

    th = threading.Thread(target=worker)
    th.start()
    th.join(timeout=120)
    assert not th.is_alive(), "consumer hung after close()"
    assert len(got) >= 3


def test_multichunk_seeded_admission_deterministic(setup):
    """Regression: decode ticks between a request's prefill chunks split ALL
    PRNG keys and shift ALL repetition windows — slot state must be seeded at
    prefill COMPLETION, or a multi-chunk seeded/penalized request diverges
    from its solo run when admitted next to an active stream."""
    batcher, ref_gen = setup
    long_prompt = list(range(1, 20))  # 3 chunks at prefill_chunk=8
    kw = dict(
        temperature=0.9, top_p=0.85, seed=123,
        repetition_penalty=1.3, repetition_context_size=8, max_tokens=8,
    )
    ref = _run(ref_gen, long_prompt, **kw)
    # busy neighbor decodes while the long prompt admits chunk by chunk
    got, _ = _concurrent(
        batcher, [([7, 7, 2], dict(max_tokens=14)), (long_prompt, kw)]
    )
    assert got[1] == ref


def test_oversized_repetition_context_rejected(setup):
    batcher, _ = setup
    with pytest.raises(ValueError, match="exceeds the scheduler's window"):
        list(
            batcher.generate_step(
                [1, 2], repetition_penalty=1.2, repetition_context_size=100,
                max_tokens=2,
            )
        )


def test_concurrent_logprobs_summaries(setup):
    """want_logprobs through the batcher: TokenLogprobs summaries from the
    decode block, a full lazy row for the first (prefill-sampled) token."""
    import numpy as np

    from mlx_sharding_tpu.generate import TokenLogprobs

    batcher, _ = setup
    out = list(
        batcher.generate_step([3, 1, 4], max_tokens=6, want_logprobs=True)
    )
    assert len(out) == 6
    first_tok, first_lp = out[0]
    assert first_lp is not None and not isinstance(first_lp, TokenLogprobs)
    for tok, lp in out[1:]:
        assert isinstance(lp, TokenLogprobs)
        vals = np.asarray(lp.top_values)
        assert (np.diff(vals) <= 1e-6).all()
        assert int(lp.top_indices[0]) == tok  # greedy -> argmax is chosen
        assert lp.chosen == pytest.approx(float(vals[0]), abs=1e-5)
    # parity with the default path's tokens
    plain = [t for t, _ in batcher.generate_step([3, 1, 4], max_tokens=6)]
    assert [t for t, _ in out] == plain


def test_single_stage_batched_step_parity():
    """pp=1 continuous batching takes the VECTORIZED engine body (one
    vmapped forward for all slots — the aggregate-throughput path on a
    single chip) instead of the tick rotation; streams must still match the
    serial generator exactly, greedy and seeded-sampled, interleaved."""
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=3, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    batcher = ContinuousBatcher(eng, decode_block=4)
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    try:
        jobs = [
            ([3, 17, 42], dict(max_tokens=9, seed=1)),
            ([9, 9, 31, 5], dict(max_tokens=7, temperature=0.8, seed=2)),
            ([1, 2], dict(max_tokens=11, temperature=0.5, top_p=0.9, seed=3,
                          repetition_penalty=1.2)),
        ]
        got = _concurrent(batcher, jobs)[0]
        for (prompt, kw), toks in zip(jobs, got):
            assert toks == _run(ref, prompt, **kw), (prompt, kw)
    finally:
        batcher.close()


# ----------------------------------------------------------------- over-commit
def _paged_batcher(pool_pages=8, microbatches=2, **kw):
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=microbatches,
        max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=pool_pages, page_size=8,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    return ContinuousBatcher(eng, decode_block=3, **kw), ref


@pytest.fixture(scope="module")
def oc_setup():
    """One 8-page pool where each test request's FULL need is 6 pages — two
    can never be co-resident under reserve admission, but over-commit admits
    both on current need and preempts under pressure."""
    batcher, ref = _paged_batcher(pool_pages=8, overcommit=True)
    yield batcher, ref
    batcher.close()


def test_overcommit_requires_paged(setup):
    batcher, _ = setup  # dense engine from the module fixture
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(batcher.engine, overcommit=True)


def test_overcommit_preempt_resume_seeded_exact(oc_setup):
    """A seeded stochastic request that gets preempted and resumed must
    continue its exact PRNG chain and repetition window: its stream matches
    the uninterrupted solo run token-for-token."""
    batcher, ref = oc_setup
    jobs = [
        ([7, 7, 2, 1], dict(max_tokens=40)),  # greedy hog, admitted first
        ([9, 4, 4, 6], dict(temperature=0.9, top_p=0.85, seed=321,
                            repetition_penalty=1.3, repetition_context_size=8,
                            max_tokens=36)),
    ]
    refs = [_run(ref, p, **kw) for p, kw in jobs]
    before = batcher.preemptions
    got, _ = _concurrent(batcher, jobs)
    assert got == refs
    assert batcher.preemptions > before
    # pool accounting intact after the churn: everything back on the free list
    total, in_use, _ = batcher.page_stats()
    assert in_use == 0 and batcher.pool.free == total


def test_growth_and_its_forecast_share_one_computation_of_the_want(oc_setup):
    """``_growth_fits`` must promise exactly what ``_grow_for_decode`` will
    take, so both ask ONE method for the pages a slot's next block wants:
    neither spells the arithmetic (``have``, ``offset``, ``cap``) itself."""
    batcher, ref = oc_setup
    cls = type(batcher)
    callers = set()
    wanted = batcher._growth_wanted

    def recording(slot, req):
        frame = sys._getframe(1)
        for _ in range(2):  # a caller, or the caller of its comprehension
            callers.add(frame.f_code.co_name)
            frame = frame.f_back
        return wanted(slot, req)

    batcher._growth_wanted = recording
    try:
        assert _run(batcher, [7, 7, 2, 1], max_tokens=20) == _run(
            ref, [7, 7, 2, 1], max_tokens=20)
    finally:
        del batcher._growth_wanted
    assert {"_grow_for_decode", "_growth_fits"} <= callers
    for fn in (cls._grow_for_decode, cls._growth_fits):
        names = set(fn.__code__.co_names)
        for const in fn.__code__.co_consts:  # a comprehension's own code
            names |= set(getattr(const, "co_names", ()))
        assert "_growth_wanted" in names
        assert not names & {"_pages_needed", "page_size", "_grow_ahead"}


# (Heavier over-commit / speculation composition cases — each building its
# own engines — live in tests/test_scheduler_heavy.py, outside the quick
# tier; the representatives here keep the tier's scheduler signal.)


# --------------------------------------------- speculative continuous batching
def _spec_batcher(microbatches=3, spec_k=3, pool_pages=None, draft_seed=7,
                  **kw):
    """Target + draft of the same tiny arch; ``draft_seed`` controls
    agreement (same seed → perfect draft, different → imperfect, so both
    the accept and the reject/correction paths run)."""
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    dparams = model.init_params(jax.random.PRNGKey(draft_seed), jnp.float32)
    mesh = pipeline_mesh(1)
    eng = PipelineEngine(
        model, params, mesh, microbatches=microbatches, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=pool_pages, page_size=8 if pool_pages else None,
    )
    deng = PipelineEngine(
        model, dparams, mesh, microbatches=microbatches, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    return (
        ContinuousBatcher(eng, decode_block=4, draft_engine=deng,
                          spec_k=spec_k, **kw),
        ref,
    )


@pytest.fixture(scope="module")
def spec_setup():
    batcher, ref = _spec_batcher()
    yield batcher, ref
    batcher.close()


@pytest.mark.slow  # spec greedy exactness also pinned quick by test_speculative
def test_spec_cb_greedy_token_exact(spec_setup):
    """Speculative continuous batching emits exactly the tokens plain
    (non-speculative) greedy decode would, for every interleaved request —
    whatever the draft proposes only throughput may change, never content."""
    batcher, ref = spec_setup
    jobs = [
        ([3, 17, 42], dict(max_tokens=12)),
        (list(range(1, 20)), dict(max_tokens=9)),  # multi-chunk admission
        ([9, 1, 4, 7], dict(max_tokens=11,
                            repetition_penalty=1.3,
                            repetition_context_size=8)),
    ]
    refs = [_run(ref, p, **kw) for p, kw in jobs]
    r0, a0 = batcher.rounds, batcher.accepted_tokens
    got, times = _concurrent(batcher, jobs)
    assert got == refs
    assert batcher.rounds > r0
    assert batcher.accepted_tokens - a0 >= batcher.rounds - r0  # >= 1/round
    # genuinely interleaved, not serialized
    assert times[0][0] < times[1][-1] and times[1][0] < times[0][-1]


def test_spec_cb_sampled_interleaving_independent(spec_setup):
    """Sampled requests under speculation: per-slot PRNG chains make a
    seeded request's stream identical run solo or interleaved with
    spec-compatible neighbors (both through the speculative path; matching
    NON-speculative streams is not promised — the PRNG is consumed
    differently — and a neighbor that pauses speculation shifts sampled
    chains too, per the scheduler docstring carve-out)."""
    batcher, _ = spec_setup
    jobs = [
        ([5, 6, 2], dict(temperature=0.9, top_p=0.8, seed=11, max_tokens=9)),
        ([8, 8, 1], dict(temperature=1.2, top_p=0.95, seed=97, max_tokens=8)),
        ([2, 4], dict(max_tokens=10)),  # greedy neighbor in the same rounds
    ]
    solo = [_run(batcher, p, **kw) for p, kw in jobs]
    got, _ = _concurrent(batcher, jobs)
    assert got == solo


def test_spec_cb_logprobs_falls_back_unspeculated(spec_setup):
    """A want_logprobs request pauses speculation (the verify computes no
    summaries): tokens still exact, summaries well-formed, rounds frozen."""
    from mlx_sharding_tpu.generate import TokenLogprobs

    batcher, ref = spec_setup
    r0 = batcher.rounds
    out = list(batcher.generate_step([3, 1, 4], max_tokens=6,
                                     want_logprobs=True))
    assert [t for t, _ in out] == _run(ref, [3, 1, 4], max_tokens=6)
    assert batcher.rounds == r0
    assert all(isinstance(lp, TokenLogprobs) for _, lp in out[1:])


def test_spec_cb_guards():
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng2 = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    deng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    with pytest.raises(ValueError, match="pp=1"):
        ContinuousBatcher(eng2, draft_engine=deng)


# ---------------------------------------------------------------- prefix cache
def _paged_cached_batcher(pool_pages=24, microbatches=2, **kw):
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=microbatches,
        max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=pool_pages, page_size=8,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    return ContinuousBatcher(eng, decode_block=3, prefix_cache=True, **kw), ref


def test_prefix_cache_requires_paged(setup):
    batcher, _ = setup  # dense engine from the module fixture
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatcher(batcher.engine, prefix_cache=True)


def test_prefix_cache_hit_token_exact():
    """A repeated prompt reuses its full prompt pages (minus the final
    token's page) and still matches the serial generator token-for-token."""
    batcher, ref = _paged_cached_batcher()
    try:
        prompt = [((7 * i) % 251) + 1 for i in range(20)]  # 2 full pages + 4
        want = _run(ref, prompt, max_tokens=8)
        first = _run(batcher, prompt, max_tokens=8)
        assert first == want
        q0, h0, reused0, _, cached0 = batcher.prefix_stats()
        assert h0 == 0 and cached0 >= 2  # cold query registered its pages
        second = _run(batcher, prompt, max_tokens=8)
        assert second == want
        q1, h1, reused1, _, _ = batcher.prefix_stats()
        assert (q1, h1) == (q0 + 1, 1)
        assert reused1 == 16  # two 8-token pages; the tail re-prefills
    finally:
        batcher.close()


def test_prefix_cache_interleaved_token_exact():
    """Two concurrent requests sharing a 16-token system prefix with
    different suffixes: token-exact vs the serial path, with a prefix hit
    recorded for whichever admits second."""
    batcher, ref = _paged_cached_batcher()
    try:
        system = [((11 * i) % 250) + 1 for i in range(16)]
        jobs = [
            (system + [61, 62, 63], dict(max_tokens=8, seed=5,
                                         temperature=0.7)),
            (system + [71, 72], dict(max_tokens=10)),
        ]
        # warm the cache with a third request sharing the prefix, so BOTH
        # concurrent requests hit regardless of admission order
        warm = _run(batcher, system + [99], max_tokens=2)
        assert len(warm) == 2
        got, _ = _concurrent(batcher, jobs)
        for (prompt, kw), toks in zip(jobs, got):
            assert toks == _run(ref, prompt, **kw), (prompt, kw)
        _, hits, reused, _, _ = batcher.prefix_stats()
        assert hits >= 2
        assert reused >= 2 * 16
    finally:
        batcher.close()


def test_prefix_cache_eviction_and_no_leaks():
    """Distinct prompts big enough to overflow the pool force LRU eviction
    of cached pages; accounting stays exact: after everything finishes,
    free + cached == pool."""
    batcher, ref = _paged_cached_batcher(pool_pages=8)
    try:
        prompts = [
            [((13 * i + s) % 250) + 1 for i in range(17)] for s in range(4)
        ]
        for p in prompts:
            assert _run(batcher, p, max_tokens=4) == _run(ref, p, max_tokens=4)
        _, _, _, evictions, cached = batcher.prefix_stats()
        assert evictions > 0
        total, in_use, _ = batcher.page_stats()
        assert in_use == cached  # only cache entries hold pages now
        assert batcher.pool.free + cached == total
        # and a cached prompt still hits after the shuffle
        hits_before = batcher.prefix_stats()[1]
        assert _run(batcher, prompts[-1], max_tokens=4) == _run(
            ref, prompts[-1], max_tokens=4
        )
        assert batcher.prefix_stats()[1] == hits_before + 1
    finally:
        batcher.close()


@pytest.mark.slow  # eviction-pressure sweep — the other prefix tests stay quick
def test_prefix_cache_own_chain_not_evicted_under_pressure():
    """Regression: when the only evictable cached pages ARE the incoming
    request's prefix chain, the request must wait for capacity, not evict
    its own chain out from under itself (which popped the page's refcount
    entry and KeyError'd the scheduler thread, failing every request)."""
    batcher, ref = _paged_cached_batcher(pool_pages=6)
    try:
        shared_prompt = [((7 * i) % 251) + 1 for i in range(17)]  # 2 cached pages
        assert _run(batcher, shared_prompt, max_tokens=4) == _run(
            ref, shared_prompt, max_tokens=4
        )
        assert batcher.prefix_stats()[4] == 2  # two pages cached

        # occupy 3 of the remaining pages with a long-running request, so
        # free=1 and the only other pages are the cached chain itself
        hog_prompt = [((5 * i) % 250) + 2 for i in range(9)]
        hog_done = threading.Event()
        hog_out = []

        def hog():
            hog_out.extend(_run(batcher, hog_prompt, max_tokens=20))
            hog_done.set()

        th = threading.Thread(target=hog)
        th.start()
        time.sleep(0.5)  # let the hog admit
        # chain=2 shared, needs 2 fresh pages, free=1, nothing else
        # evictable -> must WAIT (crash = _fail_all = exception here)
        toks = _run(batcher, shared_prompt, max_tokens=15)
        th.join(timeout=120)
        assert hog_done.is_set()
        assert hog_out == _run(ref, hog_prompt, max_tokens=20)
        assert toks == _run(ref, shared_prompt, max_tokens=15)
        assert batcher.prefix_stats()[1] >= 1  # the chain WAS reused
    finally:
        batcher.close()


# ------------------------------------------------ ragged paged decode (ISSUE 1)
def _ragged_batcher(paged_attention, pool_pages=10, **kw):
    """pp=1 paged engine: the only wiring the ragged in-place attention path
    supports (ops/paged_attention.py via the vectorized decode body)."""
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=3, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=pool_pages, page_size=8, paged_attention=paged_attention,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    return ContinuousBatcher(eng, decode_block=3, **kw), ref


def test_ragged_mixed_length_cb_matches_serial():
    """Mixed-length concurrent run on the ragged path (pool attended in
    place, per-slot lengths masked in-kernel): every stream token-exact vs
    its solo serial run, and the KV accounting reports the ragged path."""
    batcher, ref = _ragged_batcher("ragged")
    try:
        assert batcher.engine.paged_attention == "ragged"
        rng = np.random.default_rng(3)
        jobs = []
        for i, plen in enumerate([2, 8, 11, 19]):  # straddle page boundaries
            prompt = [int(t) for t in rng.integers(1, 256, size=plen)]
            jobs.append((prompt, dict(max_tokens=5 + 2 * i, seed=i,
                                      temperature=0.6)))
        want = [_run(ref, p, **kw) for p, kw in jobs]
        got, _ = _concurrent(batcher, jobs)
        assert got == want
        path, last_tick, total, claimed = batcher.kv_read_stats()
        assert path == "ragged" and total > 0
        # RESERVE admission: a stream's whole need is in its row from its
        # first token, so the rows name more than the slots hold
        assert claimed > total
    finally:
        batcher.close()


# ----------------------------- the pool in the layer scan's carry (ISSUE 40)
DSV2_TINY = dict(
    vocab_size=256, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
    q_lora_rank=None, qk_rope_head_dim=8, qk_nope_head_dim=16,
    v_head_dim=12, n_routed_experts=4, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1,
    mla_cache_mode="compressed",
)

# (family, engine keywords, mask the last layer): a dense + MoE stack with a
# latent pool, int8 {d, s} pools in the latent and in the heads-apart
# layout, and a padding layer
CARRIED = {
    "two-groups": ("dsv2", {}, False),
    "two-groups-int8-pages": ("dsv2", {"kv_dtype": "int8"}, False),
    "gqa-int8-pages": ("llama", {"kv_dtype": "int8"}, False),
    "two-groups-padding-layer": ("dsv2", {}, True),
}


def _carried_model(family, layers=None):
    from mlx_sharding_tpu.config import DeepseekV2Config
    from mlx_sharding_tpu.models.deepseek_v2 import DeepseekV2Model

    if family == "llama":
        model = LlamaModel(LlamaConfig(**TINY))
    else:
        model = DeepseekV2Model(DeepseekV2Config(
            **{**DSV2_TINY, "num_hidden_layers": layers or 3}
        ))
    return model, model.init_params(jax.random.PRNGKey(0), jnp.float32)


@pytest.mark.parametrize("case", list(CARRIED))
def test_ragged_carried_pool_matches_gather_and_serial(case):
    """The ragged body's layer scans CARRY the whole page pool (a layer is
    an offset into the page table): mixed-length concurrent streams are
    token-exact against the gather body, whose scans slice the pool per
    layer, and against every request's solo serial run. A masked-out
    padding layer changes neither the hidden state (the streams are those
    of the model without that layer) nor a live page of its pool rows."""
    family, kw, padded = CARRIED[case]
    model, params = _carried_model(family)
    rng = np.random.default_rng(5)
    jobs = []
    for i, plen in enumerate([2, 8, 11, 19]):  # straddle page boundaries
        prompt = [int(t) for t in rng.integers(1, 256, size=plen)]
        jobs.append((prompt, dict(max_tokens=5 + 2 * i, seed=i,
                                  temperature=0.6 if i % 2 else 0.0)))
    streams, pools = {}, {}
    for path in ("ragged", "gather"):
        eng = PipelineEngine(
            model, params, pipeline_mesh(1), microbatches=3, max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
            pool_pages=10, page_size=8, paged_attention=path, **kw,
        )
        assert eng.paged_attention == path
        if padded:  # the last MoE slot is padding: its parameters unused
            eng.layer_masks = {
                **eng.layer_masks,
                "moe": jax.device_put(
                    jnp.asarray([[True, False]]), eng.layer_masks["moe"].sharding
                ),
            }
        batcher = ContinuousBatcher(eng, decode_block=3)
        try:
            streams[path], _ = _concurrent(batcher, jobs)
            if path == "gather":  # one request at a time
                streams["solo"] = [_run(batcher, p, **k) for p, k in jobs]
            pools[path] = jax.tree.map(
                np.asarray, (batcher.cache.k, batcher.cache.v)
            )
        finally:
            batcher.close()
    assert streams["ragged"] == streams["gather"] == streams["solo"]
    if not kw:  # float pages: the single-request generator is the reference
        ref_model, ref_params = model, params
        if padded:  # the same stack without its last layer
            ref_model, _ = _carried_model(family, layers=2)
            ref_params = {**params, "layers": {
                "dense": params["layers"]["dense"],
                "moe": jax.tree.map(lambda x: x[:1], params["layers"]["moe"]),
            }}
        ref = Generator(
            ref_model, ref_params, max_seq=64, cache_dtype=jnp.float32,
            prefill_chunk=8,
        )
        assert streams["ragged"] == [_run(ref, p, **k) for p, k in jobs]
    # K, (S, L, P+1, 1, page, H, D): the last page of a layer is its scratch
    for leaf in jax.tree.leaves(pools["ragged"][0]):
        live = leaf[0, :, :-1]
        assert live[:-1].any()
        assert live[-1].any() != padded  # a padding layer wrote no live page


def test_kv_read_accounting_ragged_below_gather():
    """Same short run on both paths: the ragged analytic KV-bytes-read must
    come in strictly below gather's (gather always reads every slot's full
    slot_pages regardless of true length)."""
    totals, claimed, per_token = {}, {}, {}
    for path in ("ragged", "gather"):
        batcher, _ = _ragged_batcher(path)
        try:
            _run(batcher, [5, 3], max_tokens=8)
            totals[path], claimed[path] = batcher.kv_read_stats()[2:]
            per_token[path] = batcher.hbm_bytes_per_token_stats()
        finally:
            batcher.close()
    assert 0 < totals["ragged"] < totals["gather"]
    # beside it, the bytes of every page the slots' rows name: 2 + 8 tokens
    # claim 2 pages of 8 at admission and the row is 8 wide, so a ragged step
    # is charged 3 pages (the claim and the scratch entry) where the slot
    # holds 1 and then 2; gather reads the whole row, claimed or not
    assert totals["ragged"] < claimed["ragged"] < claimed["gather"]
    assert claimed["gather"] == totals["gather"]
    assert claimed["ragged"] * 8 == claimed["gather"] * 3
    # the per-token gauges /metrics exports: the same weights either way
    # (one live slot, so the whole stream is its token's), less KV when ragged
    assert per_token["ragged"]["weights"] == per_token["gather"]["weights"] > 0
    assert 0 < per_token["ragged"]["kv"] < per_token["gather"]["kv"]


def test_overcommit_pool_exhaustion_errors_not_wedges():
    """If the pool truly cannot cover a lone request's next decode block
    (only reachable through accounting drift), the request must FAIL with a
    loud error, not wedge against the scratch page emitting garbage. Drift
    is simulated by vanishing the free list mid-decode."""
    batcher, _ = _paged_batcher(pool_pages=4, overcommit=True)
    try:
        gen = batcher.generate_step([5, 9], max_tokens=24)  # 4-page full need
        next(gen)  # prefill done, decode under way
        batcher.pool.take(batcher.pool.free)  # simulate the drift: pool gone
        with pytest.raises(RuntimeError, match="pool exhausted"):
            for _ in gen:
                pass
    finally:
        batcher.close()


@pytest.fixture(scope="module")
def spec_perfect():
    """draft == target: every proposal verifies, so acceptance statistics
    become deterministic signal instead of noise."""
    batcher, ref = _spec_batcher(draft_seed=0)
    yield batcher, ref
    batcher.close()


def test_spec_accepted_counts_only_emitted(spec_perfect):
    """accepted_tokens is throughput telemetry: a final round whose accepted
    run overshoots the request's remaining budget must count only what was
    emitted, not the whole run."""
    batcher, ref = spec_perfect
    a0 = batcher.accepted_tokens
    out = _run(batcher, [4, 2], max_tokens=2)  # 1 prefill + 1 spec token
    assert out == _run(ref, [4, 2], max_tokens=2)
    assert batcher.accepted_tokens - a0 == max(0, len(out) - 1)


def test_spec_draft_replay_after_fallback_keeps_acceptance(spec_perfect):
    """A want_logprobs neighbor forces non-speculative ticks for EVERY live
    slot; the draft must be replayed through those emitted tokens or its KV
    desyncs and acceptance collapses once speculation resumes. With a
    perfect draft, post-fallback rounds must keep accepting multiple tokens
    per round."""
    batcher, ref = spec_perfect
    f0, p0 = batcher.fallback_ticks, batcher.replayed_tokens
    r0, a0 = batcher.rounds, batcher.accepted_tokens
    jobs = [
        ([3, 1, 4], dict(max_tokens=8, want_logprobs=True)),
        ([5, 2, 6], dict(max_tokens=24)),  # outlives the logprobs neighbor
    ]
    got, _ = _concurrent(batcher, jobs)
    assert got[0] == _run(ref, [3, 1, 4], max_tokens=8)
    assert got[1] == _run(ref, [5, 2, 6], max_tokens=24)
    assert batcher.fallback_ticks > f0  # the fallback ticks really happened
    assert batcher.replayed_tokens > p0  # and the draft replayed through them
    rounds = batcher.rounds - r0
    accepted = batcher.accepted_tokens - a0
    assert rounds > 0
    # a desynced draft degenerates to ~1 accepted/round; the replayed one
    # keeps the perfect draft's multi-token acceptance
    assert accepted >= 2 * rounds


# ------------------------------------------- async tick pipelining (ISSUE 4)
# The module fixtures above already run the async path (async_sched defaults
# to "auto" = on for plain single-host decode), so every stream-vs-serial
# assertion in this file doubles as async-correctness coverage — including
# overcommit preemption (oc_setup) and pool exhaustion. The tests below pin
# the explicit sync-vs-async contract: BIT-IDENTICAL token streams, clean
# one-tick-lag handling, and clean shedding when the in-flight block dies.


def test_async_sched_validation(setup, spec_setup):
    batcher, _ = setup
    spec, _ = spec_setup
    assert batcher._async  # auto -> on for plain single-host decode
    assert not spec._async  # auto -> off with a draft engine attached
    with pytest.raises(ValueError, match="async_sched"):
        ContinuousBatcher(batcher.engine, async_sched="sometimes")
    with pytest.raises(ValueError, match="draft"):
        ContinuousBatcher(
            spec.engine, draft_engine=spec.draft, async_sched="on"
        )
    off = ContinuousBatcher(batcher.engine, async_sched="off")
    try:
        assert not off._async
        assert off.tick_phase_stats()["path"] == "sync"
    finally:
        off.close()
    assert batcher.tick_phase_stats()["path"] == "async"


def test_async_matches_sync_token_exact_matrix():
    """The core contract: the double-buffered pipeline emits BIT-IDENTICAL
    streams to the classic loop across the request matrix — greedy, seeded
    sampling, multi-chunk admission, repetition penalty, and max_tokens
    boundaries (1-token streams and streams that run to their budget)."""
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)

    def make(mode):
        eng = PipelineEngine(
            model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
        )
        return ContinuousBatcher(eng, async_sched=mode)

    jobs = [
        ([3, 17, 42], dict(max_tokens=10)),  # greedy
        ([5, 6, 2], dict(temperature=0.9, top_p=0.8, seed=11,
                         max_tokens=8)),  # seeded sampled
        (list(range(1, 20)), dict(max_tokens=6)),  # multi-chunk admission
        ([9, 1, 4, 7], dict(max_tokens=1)),  # max_tokens boundary: one token
        ([3, 3, 7, 7, 2], dict(repetition_penalty=1.4,
                               repetition_context_size=6, max_tokens=12)),
    ]
    streams = {}
    for mode in ("off", "on"):
        batcher = make(mode)
        try:
            got, _ = _concurrent(batcher, jobs[:2])
            got += _concurrent(batcher, jobs[2:4])[0]
            got.append(_run(batcher, jobs[4][0], **jobs[4][1]))
            streams[mode] = got
        finally:
            batcher.close()
    assert streams["on"] == streams["off"]
    assert all(len(s) for s in streams["on"])


def test_async_mid_stream_cancellation_sheds_lookahead():
    """A client dropping its stream mid-generation under the async loop: the
    one-tick control lag means a lookahead block for the dead slot may still
    complete on device — its tokens must be dropped host-side, its pages
    returned, and the surviving stream must stay token-exact. (Server-side
    stop sequences cancel streams through this same path.)"""
    batcher, ref = _paged_batcher(pool_pages=8)
    try:
        assert batcher._async
        survivor_kw = dict(max_tokens=16)
        want = _run(ref, [9, 4, 4, 6], **survivor_kw)
        got = []
        cancelled_tokens = []

        def cancel_worker():
            gen = batcher.generate_step([7, 7, 2, 1], max_tokens=30)
            for t, _ in gen:
                cancelled_tokens.append(t)
                if len(cancelled_tokens) == 3:
                    gen.close()  # client walked away mid-stream
                    return

        def survivor_worker():
            got.extend(_run(batcher, [9, 4, 4, 6], **survivor_kw))

        threads = [
            threading.Thread(target=cancel_worker),
            threading.Thread(target=survivor_worker),
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
            assert not th.is_alive(), "generation thread hung"
        assert got == want
        assert len(cancelled_tokens) == 3
        # a follow-up request forces the loop through quiesce + admission;
        # after it the cancelled slot's pages must all be home
        assert _run(batcher, [1, 2], max_tokens=3) == _run(
            ref, [1, 2], max_tokens=3
        )
        total, in_use, _ = batcher.page_stats()
        assert in_use == 0 and batcher.pool.free == total
        assert all(r is None for r in batcher._slots)
    finally:
        batcher.close()


def test_async_harvest_fault_sheds_cleanly():
    """Kill the in-flight block at the harvest boundary (the new
    scheduler.harvest fault site): every consumer gets the error instead of
    hanging, no slot stays wedged, every page returns to the pool, and the
    batcher serves the next request normally."""
    from mlx_sharding_tpu.testing import faults

    batcher, ref = _paged_batcher(pool_pages=8)
    try:
        assert batcher._async
        f = faults.arm("scheduler.harvest", exc=RuntimeError("harvest kill"),
                       after=2, times=1)
        errors = []

        def worker(prompt):
            try:
                _run(batcher, prompt, max_tokens=24)
            except RuntimeError as e:
                errors.append(str(e))

        threads = [
            threading.Thread(target=worker, args=(p,))
            for p in ([7, 7, 2, 1], [9, 4, 4, 6])
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
            assert not th.is_alive(), "consumer hung after harvest fault"
        assert f.fired == 1
        assert len(errors) == 2 and all("harvest kill" in e for e in errors)
        # clean shed: no wedged slots, the whole pool back on the free list.
        # _fail_all surfaces the error to consumers BEFORE its pool reset,
        # so give the scheduler thread a beat to finish the reset.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            total, in_use, _ = batcher.page_stats()
            if in_use == 0 and batcher.pool.free == total:
                break
            time.sleep(0.01)
        assert all(r is None for r in batcher._slots)
        total, in_use, _ = batcher.page_stats()
        assert in_use == 0 and batcher.pool.free == total
        # and the scheduler thread survived to serve the next request
        assert _run(batcher, [3, 4], max_tokens=4) == _run(
            ref, [3, 4], max_tokens=4
        )
    finally:
        faults.disarm()
        batcher.close()


@pytest.mark.slow  # engine-pair sweep; the quick tier covers async prefix
def test_async_prefix_cache_hits_match_sync():
    """Prefix-cache hits through both run loops: identical streams and
    identical hit/reuse accounting (admission prefill quiesces the in-flight
    block, so a hit can never race the lookahead)."""
    stats = {}
    streams = {}
    prompt = [((7 * i) % 251) + 1 for i in range(20)]
    for mode in ("off", "on"):
        batcher, ref = _paged_cached_batcher(async_sched=mode)
        try:
            first = _run(batcher, prompt, max_tokens=8)
            second = _run(batcher, prompt, max_tokens=8)
            assert first == second == _run(ref, prompt, max_tokens=8)
            streams[mode] = (first, second)
            q, h, reused, _, cached = batcher.prefix_stats()
            stats[mode] = (q, h, reused, cached)
        finally:
            batcher.close()
    assert streams["on"] == streams["off"]
    assert stats["on"] == stats["off"]
    assert stats["on"][1] == 1  # the repeat really hit


@pytest.mark.slow  # engine-pair sweep; oc_setup covers async+overcommit
def test_async_overcommit_preemption_matches_sync():
    """Preemption under over-commit through both run loops: identical
    streams (quiesce-before-preempt keeps token accounting exact under the
    one-tick lag) and a fully-free pool afterwards."""
    streams = {}
    jobs = [
        ([7, 7, 2, 1], dict(max_tokens=40)),
        ([9, 4, 4, 6], dict(temperature=0.9, top_p=0.85, seed=321,
                            repetition_penalty=1.3, repetition_context_size=8,
                            max_tokens=36)),
    ]
    for mode in ("off", "on"):
        batcher, _ = _paged_batcher(pool_pages=8, overcommit=True,
                                    async_sched=mode)
        try:
            before = batcher.preemptions
            got, _ = _concurrent(batcher, jobs)
            assert batcher.preemptions > before
            total, in_use, _ = batcher.page_stats()
            assert in_use == 0 and batcher.pool.free == total
            streams[mode] = got
        finally:
            batcher.close()
    assert streams["on"] == streams["off"]


def test_async_tick_phase_stats_populated(setup):
    """The host / device-blocked split feeding /metrics: harvests
    counted, both sides timed, no import on this path."""
    batcher, _ = setup
    _run(batcher, [2, 9, 5], max_tokens=6)
    t = batcher.tick_phase_stats()
    secs = t["phase_seconds"]
    assert t["path"] == "async"
    assert t["phase_entries"]["harvest_wait"] > 0
    assert secs["harvest_wait"] >= 0.0
    assert sum(s for ph, s in secs.items()
               if ph not in ("harvest_wait", "idle_wait")) >= 0.0
    assert secs["kv_import"] == 0.0


def test_mixed_batch_sampler_classes_and_solo_tokens(setup):
    """Greedy streams beside one seeded sampled ``top_p < 1`` request that
    ends mid-run: each request gets the tokens of its solo run, and the
    decode blocks are counted by what their sampler had to run
    (``mst_decode_blocks_total{sampler}``): ``nucleus`` while the sampled
    request lives, ``greedy`` once its slot is freed — though the freed
    slot keeps the request's sampler row until the next claim (the
    stale-``sp`` case the device-side predicate masks by ``active``) —
    and ``draw`` for a sampled request at ``top_p = 1``."""
    batcher, ref_gen = setup

    def counts():
        return dict(batcher.tick_phase_stats()["blocks_by_sampler"])

    long_greedy = ([3, 17, 42], dict(max_tokens=56))
    nucleus = ([5, 6, 2], dict(temperature=1.1, top_p=0.7, seed=23, max_tokens=6))
    draw = ([8, 8, 1], dict(temperature=0.9, seed=5, max_tokens=12))
    refs = [_run(ref_gen, p, **kw) for p, kw in (long_greedy, nucleus, draw)]

    at_start, at_nucleus_end, got = counts(), {}, [None, None]

    def worker(i, prompt, kw):
        got[i] = _run(batcher, prompt, **kw)
        if i == 1:
            # the stream's end is put after the slot is freed: every block
            # dispatched from here on has only the greedy stream live
            at_nucleus_end.update(counts())

    threads = [
        threading.Thread(target=worker, args=(i, p, kw))
        for i, (p, kw) in enumerate((long_greedy, nucleus))
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
        assert not th.is_alive(), "generation thread hung"
    assert got == refs[:2]
    at_end = counts()
    # nobody has claimed the freed slot: its sampler row is still the
    # sampled request's, beside the greedy stream's
    temperature, top_p = np.asarray(batcher.sp.temperature), np.asarray(batcher.sp.top_p)
    assert sorted(zip(temperature.tolist(), top_p.tolist())) == [
        (0.0, 1.0), (pytest.approx(1.1), pytest.approx(0.7))
    ]
    assert not np.asarray(batcher.active).any()
    assert at_nucleus_end["nucleus"] > at_start["nucleus"]
    assert at_end["nucleus"] == at_nucleus_end["nucleus"]
    assert at_end["greedy"] >= at_nucleus_end["greedy"] + 2
    assert at_end["draw"] == at_start["draw"]

    assert _run(batcher, draw[0], **draw[1]) == refs[2]
    after_draw = counts()
    assert after_draw["draw"] > at_end["draw"]
    assert after_draw["nucleus"] == at_end["nucleus"]
    s = batcher.tick_phase_stats()
    assert sum(s["blocks_by_sampler"].values()) == s["blocks_dispatched"]
