"""Scheduler composition tests that each build their own engines (compile
cost ~40-70s apiece on CPU) — correctness-critical but excluded from the
quick tier, which keeps one representative per feature (see
tests/test_scheduler.py: over-commit preempt/resume exactness, spec greedy
exactness + sampled stability) and stays within its time budget."""

import jax
import jax.numpy as jnp
import pytest

from mlx_sharding_tpu.scheduler import ContinuousBatcher  # noqa: F401

from tests.test_scheduler import (  # noqa: F401 — shared tiny-model helpers
    _concurrent,
    _paged_batcher,
    _run,
    _spec_batcher,
)

pytestmark = pytest.mark.slow  # compile-bound combos; excluded from tier-1


def test_overcommit_interleaves_where_reserve_serializes():
    """Two requests whose reserved needs (6 pages each) exceed the 8-page
    pool: reserve admission runs them strictly one-after-another, over-commit
    runs them concurrently (higher slot occupancy) and stays token-exact
    through the preemption the pool pressure eventually forces."""
    jobs = [
        ([3, 17, 42, 9], dict(max_tokens=40)),   # full need ceil(44/8)=6
        ([5, 11, 2, 8], dict(max_tokens=40)),
    ]
    # reserve-mode control: same pool, no overcommit — strict serialization
    reserve, ref = _paged_batcher(pool_pages=8)
    try:
        refs = [_run(ref, p, **kw) for p, kw in jobs]
        got_r, times_r = _concurrent(reserve, jobs)
        assert got_r == refs
        # one request's stream finished entirely before the other started
        starts = [t[0] for t in times_r]
        ends = [t[-1] for t in times_r]
        assert min(ends) <= max(starts), (
            "reserve admission co-ran 2x6 pages in an 8-page pool"
        )
    finally:
        reserve.close()

    batcher, _ = _paged_batcher(pool_pages=8, overcommit=True)
    try:
        before = batcher.preemptions
        got, times = _concurrent(batcher, jobs)
        assert got == refs  # token-exact through preemption + resume
        # genuine interleaving: each produced a token before the other ended
        assert times[0][0] < times[1][-1] and times[1][0] < times[0][-1]
        assert batcher.preemptions > before  # pool pressure forced a preempt
    finally:
        batcher.close()


def test_overcommit_prefix_cache_compose():
    """Over-commit + prefix cache: a preempted request's registered prompt
    pages survive as cache entries and its resume re-prefill hits them;
    streams stay exact."""
    batcher, ref = _paged_batcher(
        pool_pages=8, overcommit=True, prefix_cache=True
    )
    try:
        shared = [((7 * i) % 251) + 1 for i in range(12)]  # 1 full page + 4
        jobs = [
            (shared + [61, 62], dict(max_tokens=30)),
            (shared + [71], dict(max_tokens=30)),
        ]
        refs = [_run(ref, p, **kw) for p, kw in jobs]
        got, _ = _concurrent(batcher, jobs)
        assert got == refs
        assert batcher.prefix_stats()[0] >= 2  # both queried the index
    finally:
        batcher.close()


def test_spec_cb_perfect_draft_accepts_k():
    """A draft identical to the target agrees at every position: every
    round emits the full window K (the acceptance gauge's upper bound)."""
    batcher, ref = _spec_batcher(microbatches=2, spec_k=3, draft_seed=0)
    try:
        jobs = [([3, 17, 42], dict(max_tokens=13)),
                ([5, 11, 2], dict(max_tokens=13))]
        refs = [_run(ref, p, **kw) for p, kw in jobs]
        got, _ = _concurrent(batcher, jobs)
        assert got == refs
        assert batcher.accepted_tokens == batcher.spec_k * batcher.rounds
    finally:
        batcher.close()


def test_spec_cb_paged_overcommit_compose():
    """Speculation x paged pool x over-commit: verify writes straddle page
    boundaries (multi-page writeback) and pool pressure preempts + resumes
    a request mid-speculation; greedy streams stay exact throughout."""
    batcher, ref = _spec_batcher(microbatches=2, spec_k=3, pool_pages=8,
                                 overcommit=True)
    try:
        jobs = [
            ([3, 17, 42, 9], dict(max_tokens=40)),  # full need 6 pages
            ([5, 11, 2, 8], dict(max_tokens=40)),
        ]
        refs = [_run(ref, p, **kw) for p, kw in jobs]
        before = batcher.preemptions
        got, _ = _concurrent(batcher, jobs)
        assert got == refs
        assert batcher.preemptions > before
        total, in_use, _ = batcher.page_stats()
        assert in_use == 0 and batcher.pool.free == total
    finally:
        batcher.close()


def test_spec_cb_prefix_cache_compose():
    """Speculation x prefix cache: a prefix hit skips TARGET prefill chunks
    while the draft — which has no page sharing — catches up from 0 on its
    own position; activation waits for both, streams stay token-exact and
    the hit is real."""
    from mlx_sharding_tpu.config import LlamaConfig
    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.models.llama import LlamaModel
    from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    from tests.test_scheduler import TINY

    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    dparams = model.init_params(jax.random.PRNGKey(7), jnp.float32)
    mesh = pipeline_mesh(1)
    eng = PipelineEngine(
        model, params, mesh, microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=16, page_size=8,
    )
    deng = PipelineEngine(
        model, dparams, mesh, microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    batcher = ContinuousBatcher(
        eng, decode_block=3, draft_engine=deng, spec_k=3, prefix_cache=True
    )
    try:
        shared = [((7 * i) % 251) + 1 for i in range(20)]  # 2 full pages + 4
        first = _run(batcher, shared + [61], max_tokens=8)
        assert first == _run(ref, shared + [61], max_tokens=8)
        # second request prefix-hits (16 reused tokens) while its draft
        # prefills all 3 chunks — token-exact vs the serial generator
        second = _run(batcher, shared + [71, 72], max_tokens=8)
        assert second == _run(ref, shared + [71, 72], max_tokens=8)
        _, hits, reused, _, _ = batcher.prefix_stats()
        assert hits >= 1 and reused >= 16
        assert batcher.rounds > 0  # speculation ran on the hit request too
    finally:
        batcher.close()
