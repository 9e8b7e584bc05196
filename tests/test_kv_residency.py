"""Proactive KV residency tiers (ISSUE 9): cold-slot spill with
PRESERVE-style overlapped prefetch.

The load-bearing properties: (1) a decode slot whose consumer stops
pulling tokens is spilled to the host tier and its pool pages freed, and
the stream still delivers EXACTLY the tokens the never-spilled run would;
(2) with prefetch on, scheduled resumes consume a device-staged block
(the overlapped path) — the demand-import fallback count stays ~0 in the
happy path; (3) every ``cache.prefetch`` fault degrades to demand import,
then to re-prefill, never a dropped stream.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.cache import KVCache
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.kv_transfer import KVSpillTier, export_block
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.resilience import RequestMigratedError
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.testing import faults
from tests.helpers import hard_timeout

TINY = dict(
    vocab_size=256,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
)


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    faults.disarm()


# ----------------------------------------------------- tier + block units
def _pool_cache(pool_pages=6, page=4):
    shape = (1, 2, pool_pages + 1, 1, page, 2, 4)
    vals = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    return KVCache(k=vals, v=vals + 1000.0, offset=jnp.zeros((), jnp.int32))


def _block(history=(5, 6, 7)):
    return export_block(
        _pool_cache(), [2, 4], page_size=4, n_tokens=6,
        prompt=[1, 2, 3], history=list(history), produced=len(history),
        resume_keys=None, resume_recent=None,
    )


def test_tier_hit_miss_and_reject_reason_counters():
    """take() counts hits/misses, put() splits rejects by reason, drop()
    counts neither, and hit_rate reflects the lookup history."""
    tier = KVSpillTier(1 << 20)
    assert tier.put("a", _block())
    assert tier.take("a") is not None
    assert tier.take("a") is None  # gone: a counted miss
    tier.put("b", _block())
    tier.drop("b")  # cancelled-stream cleanup: not a lookup
    s = tier.stats()
    assert (s["hits"], s["misses"]) == (1, 1)
    assert s["hit_rate"] == 0.5
    small = KVSpillTier(8)  # smaller than any block
    assert not small.put("c", _block())
    assert small.stats()["rejects_oversize"] == 1
    small.close()
    tier.close()
    assert not tier.put("d", _block())
    assert tier.stats()["rejects_closed"] == 1
    assert tier.stats()["rejects"] == 1  # aggregate stays in sync


def test_tier_touch_refreshes_lru_order():
    """touch() moves a block to the LRU tail so budget pressure evicts a
    colder one instead of the block about to be re-imported."""
    one = _block().to_host()
    tier = KVSpillTier(3 * one.nbytes + 8)
    for key in ("a", "b", "c"):
        assert tier.put(key, _block().to_host())
    tier.touch("a")  # now the hottest; "b" is the LRU head
    assert tier.put("d", _block().to_host())  # forces one eviction
    assert tier.contains("a") and not tier.contains("b")
    assert tier.stats()["evictions"] == 1
    tier.touch("zzz")  # absent key: a no-op, not an error


def test_block_prefetch_stage_and_payload():
    """prefetch() stages device copies of a host block exactly once,
    payload() prefers the stage, drop_prefetch() releases it, and a
    still-device block never stages (nothing to upload)."""
    dev = _block()
    assert not dev.is_prefetched
    dev.prefetch()
    assert not dev.is_prefetched  # not host-resident: no-op
    host = _block().to_host()
    calls = []

    def put(x):
        calls.append(1)
        return jnp.asarray(x)

    host.prefetch(put=put)
    assert host.is_prefetched and calls
    n = len(calls)
    host.prefetch(put=put)  # idempotent: already staged
    assert len(calls) == n
    k_pages, v_pages = host.payload()
    assert all(
        isinstance(leaf, jax.Array)
        for leaf in jax.tree.leaves((k_pages, v_pages))
    )
    host.drop_prefetch()
    assert not host.is_prefetched
    k_pages, _ = host.payload()
    assert isinstance(jax.tree.leaves(k_pages)[0], np.ndarray)


def test_block_prefetch_fault_site():
    """The cache.prefetch fault site fires before any staging happens."""
    host = _block().to_host()
    faults.arm("cache.prefetch", exc=faults.FaultError)
    with pytest.raises(faults.FaultError):
        host.prefetch()
    faults.disarm()
    assert not host.is_prefetched


def test_tier_stats_blocks_host_tracks_flusher():
    """blocks_host counts host-materialized entries — what tests (and the
    prefetcher) use to know the async flush landed."""
    tier = KVSpillTier(1 << 20)
    tier.put("a", _block())
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if tier.stats()["blocks_host"] == 1:
            break
        time.sleep(0.01)
    assert tier.stats()["blocks_host"] == 1
    tier.close()


# --------------------------------------------- engine-level happy/degraded
@pytest.fixture(scope="module")
def residency_env():
    """One shared pp=2 paged engine + solo reference; each test wraps it in
    its own batcher (the policy knobs differ per test, the engine doesn't)."""
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=8, page_size=8,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
    )
    return eng, ref


def _residency_batcher(eng, **kw):
    kw.setdefault("spill_bytes", 64 << 20)
    kw.setdefault("spill_cold_after", 2)
    kw.setdefault("kv_prefetch", "on")
    return ContinuousBatcher(eng, decode_block=3, overcommit=True, **kw)


JOB = ([7, 7, 2, 1], dict(max_tokens=40))


def _run_stalled(batcher, *, wait_host=True, prompt_kw=JOB, timeout=90.0):
    """Drive one stream with a consumer that stalls after the first token
    (backlog builds → the slot goes cold and parks), optionally waits for
    the flusher to host-materialize the block, then drains to completion.
    Returns the collected tokens."""
    prompt, kw = prompt_kw
    toks: list = []
    stall = threading.Event()

    def consume():
        for i, (t, _) in enumerate(batcher.generate_step(prompt, **kw)):
            toks.append(t)
            if i == 0:
                stall.wait()

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if batcher.spill_stats()["cold_spills"] > 0:
            break
        time.sleep(0.02)
    assert batcher.spill_stats()["cold_spills"] > 0, "slot never went cold"
    if wait_host:
        while time.monotonic() < deadline:
            if batcher.spill_stats()["blocks_host"] > 0:
                break
            time.sleep(0.02)
        assert batcher.spill_stats()["blocks_host"] > 0, "flusher never ran"
    stall.set()
    th.join(timeout=timeout)
    assert not th.is_alive(), "stream hung after wake"
    return toks


@pytest.mark.parametrize("async_sched", ["off", "on"])
@hard_timeout(420)
def test_cold_spill_prefetch_resume_exact(residency_env, async_sched):
    """Tentpole happy path, sync AND async sched: an idle-consumer slot is
    cold-spilled (pool pages freed), the wake stages the block ahead of
    admission, the resume takes the overlapped path (prefetch_hits, zero
    demand imports), and the greedy stream is bit-identical to the
    never-spilled solo run."""
    eng, ref = residency_env
    prompt, kw = JOB
    want = [t for t, _ in ref.generate_step(prompt, **kw)]
    batcher = _residency_batcher(eng, async_sched=async_sched)
    try:
        toks = _run_stalled(batcher)
        assert toks == want
        s = batcher.spill_stats()
        assert s["cold_spills"] > 0 and s["cold_wakes"] > 0
        assert s["prefetches"] > 0 and s["prefetch_hits"] > 0
        assert s["demand_imports"] == 0 and s["prefetch_faults"] == 0
        assert s["spill_fallbacks"] == 0 and s["parked"] == 0
        assert s["hit_rate"] > 0.0
        total, in_use, _ = batcher.page_stats()
        assert in_use == 0 and s["bytes_in_use"] == 0
        # the resume's import is a tick phase of its own: entered, timed
        phases = batcher.tick_phase_stats()
        assert phases["phase_entries"]["kv_import"] > 0
        assert phases["phase_seconds"]["kv_import"] > 0.0
        # ... with the pipeline drained: the device waits for every second
        assert phases["device_empty_seconds"]["kv_import"] == (
            phases["phase_seconds"]["kv_import"])
    finally:
        batcher.close()


@hard_timeout(420)
def test_prefetch_fault_degrades_to_demand_import_exact(residency_env):
    """cache.prefetch armed: every stage attempt fails, so the resume
    falls back to the counted demand import — stream still exact, nothing
    dropped."""
    eng, ref = residency_env
    prompt, kw = JOB
    want = [t for t, _ in ref.generate_step(prompt, **kw)]
    batcher = _residency_batcher(eng)
    faults.arm("cache.prefetch", exc=faults.FaultError)
    try:
        toks = _run_stalled(batcher)
        assert toks == want
        s = batcher.spill_stats()
        assert s["prefetch_faults"] > 0 and s["prefetch_hits"] == 0
        assert s["demand_imports"] > 0
        assert s["parked"] == 0
    finally:
        faults.disarm()
        batcher.close()


@hard_timeout(420)
def test_prefetch_and_import_faults_degrade_to_reprefill_exact(residency_env):
    """Both cache.prefetch and cache.import armed: the full degradation
    ladder lands on fold-and-re-prefill — stream still exact."""
    eng, ref = residency_env
    prompt, kw = JOB
    want = [t for t, _ in ref.generate_step(prompt, **kw)]
    batcher = _residency_batcher(eng)
    faults.arm("cache.prefetch", exc=faults.FaultError)
    faults.arm("cache.import", exc=faults.FaultError)
    try:
        toks = _run_stalled(batcher)
        assert toks == want
        s = batcher.spill_stats()
        assert s["spill_fallbacks"] > 0
        assert s["reprefill_tokens"] > 0
        assert s["prefetch_hits"] == 0
    finally:
        faults.disarm()
        batcher.close()


@hard_timeout(420)
def test_prefetch_off_counts_demand_imports(residency_env):
    """kv_prefetch='off': resumes demand-import (counted), never stage,
    and the stream is still exact — the fallback path is the whole path."""
    eng, ref = residency_env
    prompt, kw = JOB
    want = [t for t, _ in ref.generate_step(prompt, **kw)]
    batcher = _residency_batcher(eng, kv_prefetch="off")
    try:
        toks = _run_stalled(batcher)
        assert toks == want
        s = batcher.spill_stats()
        assert not s["prefetch_enabled"]
        assert s["prefetches"] == 0 and s["prefetch_hits"] == 0
        assert s["demand_imports"] > 0
    finally:
        batcher.close()


@hard_timeout(420)
def test_cancel_while_parked_reaps_cleanly(residency_env):
    """A consumer that abandons its stream while the slot is parked: the
    wake pass reaps the request, drops its tier block, and the tier
    drains — no wedge, no leak."""
    eng, _ = residency_env
    batcher = _residency_batcher(eng)
    try:
        gen = batcher.generate_step([9, 4, 4, 6], max_tokens=40)
        next(gen)  # first token, then stop pulling: the slot goes cold
        deadline = time.monotonic() + 90
        # wait until the request IS parked: cold_spills counts the decision,
        # a moment before the slot is suspended and the request is on the
        # parked list — a cancel in that moment is the slot reap's case, not
        # this test's (and "nothing parked, nothing in the tier" is then
        # true before the spill as well as after the reap)
        while time.monotonic() < deadline:
            if batcher.spill_stats()["parked"] > 0:
                break
            time.sleep(0.02)
        s = batcher.spill_stats()
        assert s["cold_spills"] > 0 and s["parked"] == 1
        gen.close()  # cancel the parked stream

        def drained():
            s = batcher.spill_stats()
            return (s["parked"] == 0 and s["bytes_in_use"] == 0
                    and batcher.page_stats()[1] == 0)

        while time.monotonic() < deadline and not drained():
            time.sleep(0.02)
        assert drained()
    finally:
        batcher.close()


@hard_timeout(420)
def test_migrate_out_covers_parked_requests(residency_env):
    """Replica drain while a cold session is parked: the parked request's
    stream ends with a RequestMigratedError whose ResumeState carries the
    tokens already emitted (block or fold) — migration never forgets a
    parked session."""
    eng, _ = residency_env
    batcher = _residency_batcher(eng)
    caught: list = []
    stall = threading.Event()

    def consume():
        try:
            for i, _ in enumerate(
                batcher.generate_step([3, 17, 42], max_tokens=40)
            ):
                if i == 0:
                    stall.wait()
        except RequestMigratedError as e:
            caught.append(e)

    th = threading.Thread(target=consume, daemon=True)
    th.start()
    try:
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            if batcher.spill_stats()["cold_spills"] > 0:
                break
            time.sleep(0.02)
        assert batcher.spill_stats()["cold_spills"] > 0
        moved = batcher.migrate_out(deadline=60)
        stall.set()
        th.join(timeout=60)
        assert not th.is_alive()
        assert moved >= 1 and caught
        state = caught[0].state
        assert state.produced > 0
        assert state.block is not None or state.history
    finally:
        batcher.close()


# -------------------------------------------------- slow parity sweeps
def _sweep_refs(eng_kw, prompt_kw):
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=16, page_size=8, **eng_kw,
    )
    batcher = ContinuousBatcher(eng, decode_block=3)
    try:
        prompt, kw = prompt_kw
        return [t for t, _ in batcher.generate_step(prompt, **kw)]
    finally:
        batcher.close()


@pytest.mark.slow
@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("async_sched", ["off", "on"])
@pytest.mark.parametrize("fault", [None, "cache.prefetch", "cache.import"])
def test_cold_spill_parity_sweep(kv_dtype, async_sched, fault):
    """Full matrix: {bf16, int8 pool} x {sync, async} x {happy, prefetch
    fault, import fault} — the cold-spilled stream is always bit-identical
    to the never-spilled run on the same pool dtype (the int8 pool's
    quantization drift makes the bf16 stream an invalid reference)."""
    eng_kw = dict(kv_dtype=kv_dtype) if kv_dtype else {}
    want = _sweep_refs(eng_kw, JOB)
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=8, page_size=8, **eng_kw,
    )
    batcher = _residency_batcher(eng, async_sched=async_sched)
    if fault:
        faults.arm(fault, exc=faults.FaultError)
    try:
        toks = _run_stalled(batcher, wait_host=(fault is None))
        assert toks == want
        s = batcher.spill_stats()
        assert s["cold_spills"] > 0 and s["parked"] == 0
        if fault is None:
            assert s["demand_imports"] == 0 and s["prefetch_hits"] > 0
    finally:
        faults.disarm()
        batcher.close()


def test_spill_cold_skips_candidate_unslotted_by_the_quiesce(residency_env):
    """Regression: the async tick scans cold candidates BEFORE quiescing,
    and the quiesce's harvest can finish a candidate (its max_tokens lands
    in the drained block), leaving ``req.slot == -1``. ``_spill_cold``
    must skip such a request — suspending it would release slot -1
    (clobbering ``self._slots[-1]``, i.e. whatever live stream holds the
    last slot) and park an already-finished request for ``_wake_parked``
    to re-admit. The window is harvest-timing dependent, so this pins the
    guard directly with an unslotted candidate."""
    from types import SimpleNamespace

    eng, _ = residency_env
    batcher = _residency_batcher(eng)
    try:
        finished = SimpleNamespace(slot=-1, _trace=None)
        before = batcher.spill_stats()
        batcher._spill_cold([finished])
        after = batcher.spill_stats()
        assert after["cold_spills"] == before["cold_spills"]
        assert after["parked"] == before["parked"]
    finally:
        batcher.close()
