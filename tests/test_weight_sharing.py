"""Cross-replica shared weights (weights.py): one resident packed tree per
host, refcount-leased to every replica.

Contracts pinned here:

- Store semantics: one build per key under concurrent acquires, aliasing
  returns the SAME resident object, last release frees the entry, unknown
  releases and double-released leases raise.
- Alias-fast engines: ``PipelineEngine(..., weights=...)`` executes against
  the same device arrays (leaf identity), greedy streams are bit-identical
  shared vs private, and fleet weight bytes stay ~W instead of N×W.
- Lifecycle: ``engine.close()`` (via drain / ReplicaSet.close / disagg
  teardown) releases exactly one ref; a faulted spawn releases its lease
  before the error propagates — refcounts are consistent either way.
- The spawn-path device-slice free list recycles drained replicas' slices
  (the old next-index factories leaked them).
"""

import threading

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.disagg import DisaggCoordinator
from mlx_sharding_tpu.fleet import FleetAutoscaler
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import make_mesh, mesh_fingerprint
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine, place_weights
from mlx_sharding_tpu.replicas import ReplicaSet
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.server.openai_api import _SliceAllocator
from mlx_sharding_tpu.utils.observability import ServingMetrics
from mlx_sharding_tpu.weights import (
    WeightKey,
    WeightStore,
    aliased_spawn,
    key_digest,
    weight_store,
)
from tests.helpers import run_concurrent
from tests.test_fleet import FakeClock, _LoadStub

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)

KEY = WeightKey(checkpoint="ck", stage_bounds=(("auto", 1),),
                dtype="float32", quant="tp1", placement="pp=1|0")


def _key(**kw):
    base = dict(checkpoint="ck", stage_bounds=(("auto", 1),),
                dtype="float32", quant="tp1", placement="pp=1|0")
    base.update(kw)
    return WeightKey(**base)


class _Tree:
    def __init__(self, nbytes=100):
        self.weight_bytes = nbytes


@pytest.fixture(scope="module")
def tiny_model():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return model, params


# ------------------------------------------------------------ store semantics
def test_acquire_builds_once_and_aliases():
    store, built = WeightStore(), []

    def build():
        built.append(1)
        return _Tree()

    a = store.acquire(KEY, build)
    b = store.acquire(KEY, build)
    assert len(built) == 1  # ONE placement, however many spawns
    assert a.weights is b.weights
    assert store.refs(KEY) == 2
    st = store.stats()
    assert st == {
        "trees": 1, "refs": 2, "bytes": 100,
        "entries": [{"checkpoint": "ck", "placement": "pp=1|0",
                     "refs": 2, "bytes": 100,
                     "digest": key_digest(KEY)}],
    }


def test_distinct_keys_build_distinct_trees():
    store = WeightStore()
    a = store.acquire(_key(dtype="float32"), _Tree)
    b = store.acquire(_key(dtype="bfloat16"), _Tree)
    assert a.weights is not b.weights
    assert store.stats()["trees"] == 2


def test_last_release_frees_and_errors_raise():
    store = WeightStore()
    a = store.acquire(KEY, _Tree)
    b = store.acquire(KEY, _Tree)
    assert a.release() is False  # a ref remains — tree stays resident
    assert store.refs(KEY) == 1
    assert b.release() is True  # last ref out frees the entry
    assert store.stats() == {"trees": 0, "refs": 0, "bytes": 0, "entries": []}
    with pytest.raises(RuntimeError, match="released twice"):
        b.release()
    with pytest.raises(RuntimeError, match="does not hold"):
        store.release(KEY)


def test_concurrent_acquires_build_once():
    store, built = WeightStore(), []

    def build():
        built.append(1)
        return _Tree()

    leases = [None] * 8

    def go(i):
        leases[i] = store.acquire(KEY, build)

    threads = [threading.Thread(target=go, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == 1 and store.refs(KEY) == 8
    assert all(ls.weights is leases[0].weights for ls in leases)


def test_aliased_spawn_fault_leaves_refcounts_consistent():
    store = WeightStore()
    holder = store.acquire(KEY, _Tree)  # a live replica's lease

    def boom(lease):
        raise RuntimeError("engine construction failed")

    with pytest.raises(RuntimeError, match="construction failed"):
        aliased_spawn(store, KEY, _Tree, boom)
    # the faulted spawn's ref is gone, the live replica's is intact —
    # nothing leaked, nothing freed in use
    assert store.refs(KEY) == 1
    assert holder.release() is True
    # and a first-spawn fault leaves the store empty (build not leaked)
    with pytest.raises(RuntimeError, match="construction failed"):
        aliased_spawn(store, KEY, _Tree, boom)
    assert store.stats()["trees"] == 0


def test_module_singleton_is_shared():
    assert weight_store() is weight_store()


# ------------------------------------------------- alias-fast engine builds
def test_engines_alias_one_resident_tree(tiny_model):
    """Two engines over one placed tree execute against the SAME device
    arrays (leaf identity), and greedy streams are bit-identical to a
    private-upload engine of the same geometry."""
    model, params = tiny_model
    devices = jax.devices()
    rw = place_weights(model, params, make_mesh(pp=1, devices=devices[:1]))
    kw = dict(max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8)
    shared = [
        PipelineEngine(model, None, rw.mesh, weights=rw, **kw)
        for _ in range(2)
    ]
    private = PipelineEngine(
        model, params, make_mesh(pp=1, devices=devices[1:2]), **kw
    )
    assert all(e.weights_shared for e in shared)
    assert not private.weights_shared
    a_leaves = jax.tree.leaves(shared[0].layer_params)
    b_leaves = jax.tree.leaves(shared[1].layer_params)
    assert all(x is y for x, y in zip(a_leaves, b_leaves))
    prompt = [3, 17, 42]
    want = [t for t, _ in private.generate_step(prompt, max_tokens=10)]
    for eng in shared:
        assert [t for t, _ in eng.generate_step(prompt, max_tokens=10)] == want


def test_fleet_weight_bytes_stay_flat(tiny_model):
    """The headline number: N aliased engines hold ~W resident weight
    bytes where N private engines hold N×W (unique-buffer accounting)."""
    model, params = tiny_model
    devices = jax.devices()

    def unique_bytes(engines):
        seen, total = set(), 0
        for e in engines:
            for leaf in jax.tree.leaves(
                (e.layer_params, e.vocab_parts, e.shared_params)
            ):
                if id(leaf) not in seen:
                    seen.add(id(leaf))
                    total += leaf.nbytes
        return total

    rw = place_weights(model, params, make_mesh(pp=1, devices=devices[:1]))
    kw = dict(max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8)
    shared = [PipelineEngine(model, None, rw.mesh, weights=rw, **kw)
              for _ in range(3)]
    private = [
        PipelineEngine(model, params,
                       make_mesh(pp=1, devices=devices[i:i + 1]), **kw)
        for i in range(3)
    ]
    w = unique_bytes(shared[:1])
    assert unique_bytes(shared) == w  # ~W, however many replicas alias it
    assert unique_bytes(private) == 3 * w  # N×W without the store
    assert rw.weight_bytes == w


def test_alias_rejects_foreign_mesh_and_bounds(tiny_model):
    model, params = tiny_model
    devices = jax.devices()
    rw = place_weights(model, params, make_mesh(pp=2, devices=devices[:2]))
    kw = dict(max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8)
    with pytest.raises(ValueError, match="different device grid"):
        PipelineEngine(model, None, make_mesh(pp=2, devices=devices[2:4]),
                       weights=rw, **kw)
    with pytest.raises(ValueError, match="disagree"):
        PipelineEngine(model, None, rw.mesh, weights=rw,
                       stage_bounds=[(0, 2), (2, 2)], **kw)


def test_close_hook_releases_exactly_once(tiny_model):
    model, params = tiny_model
    store = WeightStore()
    rw_key = _key(checkpoint="close-hook")
    mesh = make_mesh(pp=1, devices=jax.devices()[:1])
    lease = store.acquire(
        rw_key, lambda: place_weights(model, params, mesh)
    )
    eng = PipelineEngine(
        model, None, lease.weights.mesh, weights=lease.weights,
        max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
    )
    eng.on_close(lease.release)
    assert store.refs(rw_key) == 1
    eng.close()
    assert store.refs(rw_key) == 0 and lease.released
    eng.close()  # idempotent — the hook ran once, no double release


# --------------------------------------------- fleet lifecycle with real refs
def _shared_batcher(tiny_model, store, key, concurrent=2, **pool_kw):
    model, params = tiny_model
    mesh = make_mesh(pp=1, devices=jax.devices()[:1])
    lease = store.acquire(
        key, lambda: place_weights(model, params, mesh)
    )
    eng = PipelineEngine(
        model, None, lease.weights.mesh, weights=lease.weights,
        microbatches=concurrent, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, **pool_kw,
    )
    eng.on_close(lease.release)
    return ContinuousBatcher(eng, decode_block=3)


def test_drain_releases_ref_close_frees_tree(tiny_model):
    """ReplicaSet.drain → batcher.close → engine close hook → one ref out;
    ReplicaSet.close releases the rest and the LAST release frees the
    store's tree. Streams before/through are token-exact vs private."""
    model, params = tiny_model
    store, key = WeightStore(), _key(checkpoint="drain")
    rs = ReplicaSet([_shared_batcher(tiny_model, store, key)
                     for _ in range(3)])
    assert store.refs(key) == 3
    assert rs.fleet_stats()["weights_shared"] == 3
    private = PipelineEngine(
        model, params, make_mesh(pp=1, devices=jax.devices()[1:2]),
        max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
    )
    jobs = [([3, 17, 42], dict(max_tokens=8, seed=i + 1)) for i in range(3)]
    got = run_concurrent(rs, jobs)
    for (p, kw), toks in zip(jobs, got):
        assert toks == [t for t, _ in private.generate_step(p, **kw)]
    rs.drain(2, deadline=5.0)
    assert store.refs(key) == 2  # retirement released exactly one ref
    assert rs.fleet_stats()["weights_shared"] == 2
    rs.close()
    assert store.stats()["trees"] == 0  # last engine out freed the tree


def test_disagg_pools_share_one_tree_with_parity(tiny_model):
    """Prefill and decode pools alias the same resident tree; coordinated
    streams stay bit-identical to a private monolithic batcher; teardown
    frees the tree."""
    model, params = tiny_model
    store, key = WeightStore(), _key(checkpoint="disagg")
    pool_kw = dict(pool_pages=10, page_size=8)
    co = DisaggCoordinator(
        ReplicaSet([_shared_batcher(tiny_model, store, key, **pool_kw)],
                   role="prefill"),
        ReplicaSet([_shared_batcher(tiny_model, store, key, **pool_kw)],
                   role="decode"),
    )
    mono_eng = PipelineEngine(
        model, params, make_mesh(pp=1, devices=jax.devices()[1:2]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, **pool_kw,
    )
    mono = ContinuousBatcher(mono_eng, decode_block=3)
    try:
        assert store.refs(key) == 2
        assert co.fleet_stats()["weights_shared"] == 2
        jobs = [([3, 17, 42], dict(max_tokens=12)),
                ([9, 4, 4, 6], dict(max_tokens=10, seed=7, temperature=0.8))]
        got = run_concurrent(co, jobs)
        want = run_concurrent(mono, jobs)
        assert got == want
    finally:
        co.close()
        mono.close()
    assert store.stats()["trees"] == 0


@pytest.mark.slow
def test_async_batcher_parity_shared_vs_private(tiny_model):
    """Async tick pipelining over aliased weights stays token-exact vs a
    private synchronous batcher."""
    model, params = tiny_model
    store, key = WeightStore(), _key(checkpoint="async")
    eng_shared = _shared_batcher(tiny_model, store, key)
    mesh = make_mesh(pp=1, devices=jax.devices()[:1])
    lease = store.acquire(key, lambda: place_weights(model, params, mesh))
    async_eng = PipelineEngine(
        model, None, lease.weights.mesh, weights=lease.weights,
        microbatches=2, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
    )
    async_eng.on_close(lease.release)
    shared_async = ContinuousBatcher(async_eng, decode_block=3,
                                     async_sched="on")
    private = ContinuousBatcher(
        PipelineEngine(
            model, params, make_mesh(pp=1, devices=jax.devices()[1:2]),
            microbatches=2, max_seq=64, cache_dtype=jnp.float32,
            prefill_chunk=8,
        ),
        decode_block=3,
    )
    try:
        jobs = [([3, 17, 42], dict(max_tokens=10)),
                ([5, 5, 9], dict(max_tokens=8, seed=3, temperature=0.7))]
        want = run_concurrent(private, jobs)
        assert run_concurrent(eng_shared, jobs) == want
        assert run_concurrent(shared_async, jobs) == want
    finally:
        eng_shared.close()
        shared_async.close()
        private.close()
    assert store.stats()["trees"] == 0


def test_autoscaler_spawn_fault_keeps_store_consistent():
    """A replica.spawn fault through aliased_spawn degrades the controller
    to the static fleet with refcounts exactly as they were."""
    store, key = WeightStore(), _key(checkpoint="fleet")
    holder = store.acquire(key, _Tree)  # the static fleet's resident tree

    def factory():
        return aliased_spawn(
            store, key, _Tree,
            lambda lease: (_ for _ in ()).throw(RuntimeError("spawn boom")),
        )

    clk = FakeClock()
    reps = [_LoadStub() for _ in range(2)]
    rs = ReplicaSet(reps)
    ctrl = FleetAutoscaler(rs, factory, clock=clk, max_replicas=3,
                           scale_up_sustain_s=5.0, cooldown_s=20.0)
    for r in reps:
        r.load = (1, 1, 2)
    ctrl.tick()
    clk.advance(5.0)
    assert ctrl.tick()["action"] == "spawn_failed"
    assert ctrl.state()["degraded"]
    assert store.refs(key) == 1  # the fault neither leaked nor freed
    assert holder.release() is True


def test_autoscaler_spawn_records_latency():
    clk = FakeClock()
    reps = [_LoadStub() for _ in range(2)]
    rs = ReplicaSet(reps)
    ctrl = FleetAutoscaler(rs, _LoadStub, clock=clk, max_replicas=3,
                           scale_up_sustain_s=5.0, cooldown_s=20.0)
    assert ctrl.state()["last_spawn_s"] is None
    for r in reps:
        r.load = (1, 1, 2)
    ctrl.tick()
    clk.advance(5.0)
    assert ctrl.tick()["action"] == "spawn"
    # the spawn's seconds, as the autoscaler's state exports them
    assert ctrl.state()["last_spawn_s"] >= 0.0


# ------------------------------------------------- device-slice free list
def test_slice_allocator_recycles_lowest_first():
    alloc = _SliceAllocator(list("abcdef"), per=2)
    assert alloc.total == 3
    assert [alloc.take() for _ in range(3)] == [0, 1, 2]
    assert alloc.slice_for(1) == ["c", "d"]
    with pytest.raises(RuntimeError, match="no free device slice"):
        alloc.take()
    alloc.give(2)
    alloc.give(0)
    alloc.give(0)  # double-give must not hand one slice to two replicas
    assert alloc.free_count() == 2
    assert [alloc.take(), alloc.take()] == [0, 2]


def test_drain_recycles_slice_through_on_retire():
    """Regression for the spawn-factory device-slice leak: a drained
    replica's slice returns to the free list, so a later spawn reuses it
    instead of failing on a 'consumed' grid."""
    class _Rep:
        def generate_step(self, prompt_tokens, **kw):
            yield from ((t, None) for t in prompt_tokens)

        def close(self):
            pass

    alloc = _SliceAllocator([0, 1], per=1)
    reps = [_Rep(), _Rep()]
    for r in reps:
        r._mst_slice = alloc.take()
    with pytest.raises(RuntimeError, match="no free device slice"):
        alloc.take()  # the old factories were stuck here forever
    rs = ReplicaSet(reps)
    rs.on_retire = lambda rep: alloc.give(
        getattr(rep, "_mst_slice", None)
    ) if getattr(rep, "_mst_slice", None) is not None else None
    rs.drain(1, deadline=2.0)
    assert alloc.free_count() == 1
    assert alloc.take() == 1  # the drained replica's slice, reused


# ------------------------------------------------------------- observability
def test_metrics_weight_store_gauges():
    store = WeightStore()
    store.acquire(KEY, lambda: _Tree(nbytes=2048))
    store.acquire(KEY, lambda: _Tree(nbytes=2048))
    m = ServingMetrics(weight_store_fn=lambda: store)
    out = m.render()
    assert "mst_weight_store_trees 1" in out
    assert "mst_weight_store_refs 2" in out
    assert "mst_weight_store_bytes 2048" in out


def test_metrics_per_replica_shared_gauge():
    shared, private = _LoadStub(), _LoadStub()
    shared.weights_shared = True
    rs = ReplicaSet([shared, private])
    m = ServingMetrics(batcher_fn=lambda: rs,
                       weight_store_fn=lambda: WeightStore())
    out = m.render()
    assert 'mst_replica_weights_shared{replica="0"} 1' in out
    assert 'mst_replica_weights_shared{replica="1"} 0' in out
    assert "mst_weight_store_trees 0" in out


def test_provider_shared_weights_resolution():
    from mlx_sharding_tpu.server.openai_api import ModelProvider

    p = ModelProvider.__new__(ModelProvider)
    p.multihost = False
    for mode, replicas, disagg, want in (
        ("auto", 3, False, True),
        ("auto", 1, True, True),
        ("auto", 1, False, False),
        ("off", 3, False, False),
        ("on", 1, False, True),
    ):
        p.shared_weights, p.replicas, p.disagg = mode, replicas, disagg
        assert p._shared_weights_on() is want, (mode, replicas, disagg)


def test_weight_key_placement_is_identity():
    devices = jax.devices()
    a = mesh_fingerprint(make_mesh(pp=1, devices=devices[:1]))
    b = mesh_fingerprint(make_mesh(pp=1, devices=devices[1:2]))
    assert a != b  # same geometry, different devices → different trees
    assert _key(placement=a) != _key(placement=b)
