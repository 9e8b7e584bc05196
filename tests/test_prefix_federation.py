"""Pod-federated prefix store (ISSUE 19): pod-wide prefix reuse.

The load-bearing properties: (1) pod-wide, a hot prefix is prefilled
ONCE — a later same-prefix admission on ANY host pulls the owner's
exported ``KVPageBlock`` into its local host tier over the fabric, and
the fetch is counted (one blob, its bytes, its latency); (2) EVERY
federation failure — the ``pod.prefix_fetch`` fault site, a pod-wide
miss, a stale inventory, a dead owner, a silent owner, a corrupt or
geometry-mismatched blob, a host-tier budget reject — degrades to plain
prefill, counted by kind, never a wrong or dropped stream; (3) greedy
streams whose prefix rode the fabric are bit-identical to a monolithic
batcher's.

Unit tests drive :class:`PodPrefixFederation` directly over a fake
transport (the pod view is just ``peers()`` + ``send``); the end-to-end
test runs two real batchers over the :class:`LoopbackHub`.
"""

import pickle
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.cache import KVCache
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.kv_transfer import export_block
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.pod import (
    PREFIX_FETCH_TIMEOUT_S,
    LoopbackHub,
    PodFleet,
    PodPrefixFederation,
)
from mlx_sharding_tpu.prefix_store import PrefixStore
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.testing import faults
from tests.helpers import hard_timeout

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)

PAGE = 8
# one shared 2-page prefix, divergent tails: the hot-prefix traffic shape
BASE = [7, 7, 2, 1, 9, 4, 4, 6, 3, 17, 42, 5, 11, 2, 2, 8]


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    faults.disarm()


def _pure_prefix_block(tokens, pages=(0, 1), share_hash=None):
    shape = (1, 2, 4, 1, PAGE, 2, 4)
    vals = jnp.arange(np.prod(shape), dtype=jnp.float32).reshape(shape)
    cache = KVCache(k=vals, v=vals + 1000.0, offset=jnp.zeros((), jnp.int32))
    return export_block(
        cache, list(pages), page_size=PAGE, n_tokens=len(pages) * PAGE,
        prompt=list(tokens), history=[], produced=0,
        resume_keys=None, resume_recent=None, share_hash=share_hash,
    ).to_host()


class _FakeTransport:
    """The slice of the pod fabric the federation touches: a static
    ``peers()`` view plus ``send`` capture with an optional synchronous
    responder (replies land on the requester's queue before ``q.get``)."""

    def __init__(self, host_id=0, peers=None):
        self.host_id = host_id
        self._peers = dict(peers or {})
        self.sent = []
        self.respond = None  # (host, kind, payload) -> None

    def peers(self):
        return self._peers

    def send(self, host, kind, payload):
        self.sent.append((host, kind, payload))
        if self.respond is not None:
            self.respond(host, kind, payload)


def _peer_entry(keys, *, age_s=0.0, page_size=PAGE, share=None):
    return {"info": {"prefix": {"keys": list(keys),
                                "page_size": page_size,
                                "share": share}},
            "age_s": age_s}


def _mk(store=None, peers=None, **kw):
    store = store or PrefixStore(host_bytes=1 << 20)
    if store.page_size is None:
        store.bind_page_size(PAGE)
    t = _FakeTransport(peers=peers)
    kw.setdefault("fetch_timeout_s", 0.25)
    return PodPrefixFederation(0, t, store, **kw), t, store


# -------------------------------------------------------- heartbeat surface
def test_local_info_advertises_inventory_and_geometry():
    fed, _, store = _mk()
    digests = store.digests_for(BASE + [5])
    store.host_put(digests[-1], _pure_prefix_block(BASE))
    info = fed.local_info()
    assert info["keys"] == [digests[-1].hex()]
    assert info["page_size"] == PAGE
    assert info["share"] is None
    store.close()


def test_local_info_sick_store_advertises_nothing():
    fed, _, store = _mk()
    store.host_inventory = lambda *a, **k: 1 / 0
    assert fed.local_info() == {}
    store.close()


def test_stats_shape():
    fed, _, store = _mk()
    s = fed.stats()
    assert set(s) == {"inventory_keys", "hits", "fetches", "fetch_bytes",
                      "blobs_served", "bytes_served", "fallbacks",
                      "fetch_ms_p50", "fetch_ms_p99"}
    assert s["fallbacks"] == {} and s["fetch_ms_p50"] is None
    store.close()


# ------------------------------------------------------------------ routing
def test_owner_for_prefers_freshest_live_compatible_peer():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    hexd = store.digests_for(BASE + [5])[-1].hex()
    fed, t, _ = _mk(store=store, peers={
        1: _peer_entry([hexd], age_s=1.2),
        2: _peer_entry([hexd], age_s=0.1),
        3: _peer_entry([hexd], age_s=0.0, page_size=16),   # wrong geometry
        4: _peer_entry([hexd], age_s=0.0, share="deadbeef"),  # wrong layout
        5: _peer_entry([], age_s=0.0),                     # doesn't have it
    })
    assert fed._owner_for(hexd) == (2, None)
    store.close()


def test_owner_for_stale_only_and_pod_miss():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    hexd = store.digests_for(BASE + [5])[-1].hex()
    fed, t, _ = _mk(store=store, heartbeat_timeout_s=2.0,
                    peers={1: _peer_entry([hexd], age_s=60.0)})
    assert fed._owner_for(hexd) == (None, "stale_inventory")
    t._peers = {}
    assert fed._owner_for(hexd) == (None, "miss")
    store.close()


# --------------------------------------------- fetch degradations, by kind
def test_fetch_fault_site_degrades_before_the_wire():
    fed, t, store = _mk(peers={1: _peer_entry(["ab"])})
    faults.arm("pod.prefix_fetch", exc=faults.FaultError)
    assert fed.fetch(b"\xab") is False
    assert fed.stats()["fallbacks"] == {"fetch_fault": 1}
    assert t.sent == []  # degraded before touching the fabric
    store.close()


def test_pod_miss_is_negative_cached():
    fed, t, store = _mk(peers={})
    digest = store.digests_for(BASE + [5])[-1]
    assert fed.fetch(digest) is False
    assert fed.fetch(digest) is False  # second probe: neg cache, no route
    assert fed.stats()["fallbacks"] == {"miss": 1, "neg_cached": 1}
    store.close()


def test_neg_cache_expires_on_the_clock():
    now = [100.0]
    fed, t, store = _mk(peers={}, neg_cache_s=30.0, clock=lambda: now[0])
    digest = store.digests_for(BASE + [5])[-1]
    assert fed.fetch(digest) is False
    now[0] += 31.0
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"miss": 2}  # re-probed, no neg hit
    store.close()


def test_owner_dead_when_send_raises():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])})
    t.respond = lambda *a: 1 / 0
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"owner_dead": 1}
    assert fed.stats()["hits"] == 1  # the pod view DID name an owner
    store.close()


def test_timeout_when_owner_goes_silent():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])},
                    fetch_timeout_s=0.05)
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"timeout": 1}
    assert fed._waiters == {}  # the waiter never leaks
    store.close()


def _respond_with(fed, kind, data):
    """Synchronous owner stand-in: answer the fetch on the requester's
    own queue before it starts waiting."""
    def responder(host, msg_kind, payload):
        rid = pickle.loads(payload)["rid"]
        fed.handle(host, kind, pickle.dumps((rid, data)))
    return responder


def test_owner_eviction_between_gossip_and_fetch_is_stale_inventory():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])})
    t.respond = _respond_with(fed, "prefix.miss", b"")
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"stale_inventory": 1}
    assert fed.fetch(digest) is False  # and the digest is neg-cached now
    assert fed.stats()["fallbacks"]["neg_cached"] == 1
    store.close()


def test_corrupt_blob_fails_integrity():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    blob = bytearray(_pure_prefix_block(BASE).to_bytes())
    blob[-3] ^= 0xFF  # flip payload bits under the checksum
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])})
    t.respond = _respond_with(fed, "prefix.blob", bytes(blob))
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"integrity": 1}
    store.close()


def test_geometry_mismatched_blob_fails_integrity():
    """A lying inventory (advertised page_size matches, blob doesn't)
    still can't land a wrong-geometry block in the local tier."""
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(16)
    digest = store.digests_for(list(range(40)))[-1]
    fed, t, _ = _mk(store=store,
                    peers={1: _peer_entry([digest.hex()], page_size=16)})
    t.respond = _respond_with(
        fed, "prefix.blob", _pure_prefix_block(BASE).to_bytes())  # PAGE=8
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"integrity": 1}
    store.close()


def test_share_hash_mismatched_blob_fails_integrity():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    blob = _pure_prefix_block(BASE, share_hash="feedface").to_bytes()
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])})
    t.respond = _respond_with(fed, "prefix.blob", blob)
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"integrity": 1}
    store.close()


def test_host_tier_budget_reject_is_host_reject():
    store = PrefixStore(host_bytes=1)  # nothing fits
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])})
    t.respond = _respond_with(
        fed, "prefix.blob", _pure_prefix_block(BASE).to_bytes())
    assert fed.fetch(digest) is False
    assert fed.stats()["fallbacks"] == {"host_reject": 1}
    store.close()


# -------------------------------------------------------------- happy path
def test_fetch_roundtrip_imports_into_local_tier():
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    blob = _pure_prefix_block(BASE).to_bytes()
    fed, t, _ = _mk(store=store, peers={1: _peer_entry([digest.hex()])})
    t.respond = _respond_with(fed, "prefix.blob", blob)
    assert not store.host_contains(digest)
    assert fed.fetch(digest) is True
    assert store.host_contains(digest)  # the ordinary import path takes over
    s = fed.stats()
    assert s["hits"] == 1 and s["fetches"] == 1
    assert s["fetch_bytes"] == len(blob)
    assert s["fetch_ms_p50"] is not None and s["fallbacks"] == {}
    assert s["inventory_keys"] == 1
    store.close()


def test_serve_side_exports_blob_and_counts():
    """Owner side: a ``prefix.fetch`` message is consumed, served OFF the
    receive thread, and answered with the exported blob."""
    store = PrefixStore(host_bytes=1 << 20)
    store.bind_page_size(PAGE)
    digest = store.digests_for(BASE + [5])[-1]
    store.host_put(digest, _pure_prefix_block(BASE))
    fed, t, _ = _mk(store=store)
    req = pickle.dumps({"rid": "r1", "digest": digest})
    assert fed.handle(9, "prefix.fetch", req) is True
    deadline = time.monotonic() + 5.0
    while not t.sent and time.monotonic() < deadline:
        time.sleep(0.01)
    (host, kind, payload), = t.sent
    assert (host, kind) == (9, "prefix.blob")
    rid, data = pickle.loads(payload)
    assert rid == "r1" and len(data) > 0
    s = fed.stats()
    assert s["blobs_served"] == 1 and s["bytes_served"] == len(data)
    # a digest the tier doesn't hold answers prefix.miss
    t.sent.clear()
    other = store.digests_for(list(range(50, 67)))[-1]
    fed.handle(9, "prefix.fetch", pickle.dumps({"rid": "r2",
                                                "digest": other}))
    deadline = time.monotonic() + 5.0
    while not t.sent and time.monotonic() < deadline:
        time.sleep(0.01)
    assert t.sent[0][1] == "prefix.miss"
    assert fed.handle(9, "weights.have", b"x") is False  # not ours
    store.close()


# ----------------------------------------------------- end-to-end loopback
@pytest.fixture(scope="module")
def tiny_model():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return model, params


def _mk_host(tiny_model, dev_idx, *, with_store=True):
    model, params = tiny_model
    devices = jax.devices()
    eng = PipelineEngine(
        model, params, make_mesh(pp=1, devices=devices[dev_idx:dev_idx + 1]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, pool_pages=10, page_size=PAGE,
    )
    store = PrefixStore(host_bytes=1 << 20) if with_store else None
    return ContinuousBatcher(eng, decode_block=3, prefix_store=store), store


@hard_timeout(120)
def test_pod_federation_end_to_end_one_prefill_pod_wide(tiny_model):
    """The acceptance shape: a prefix made hot on host A is continued on
    host B with exactly one counted blob fetch, reused (not re-prefilled)
    tokens, and a greedy stream bit-identical to a monolithic batcher —
    then a faulted fetch degrades to plain prefill with the same tokens."""
    b_a, store_a = _mk_host(tiny_model, 0)
    b_b, store_b = _mk_host(tiny_model, 1)
    mono, _ = _mk_host(tiny_model, 2, with_store=False)
    hub = LoopbackHub()
    f_a = PodFleet(0, hub.register(0), b_a, prefix_store=store_a)
    f_b = PodFleet(1, hub.register(1), b_b, prefix_store=store_b)
    try:
        # warm the prefix on A: stream completion demotes the pure-
        # prefix block into A's host tier
        list(b_a.generate_step(BASE + [5], max_tokens=12))
        assert store_a.stats()["demotions"] >= 1
        f_a.tick()  # gossip A's inventory
        f_b.tick()
        assert f_b.prefix.stats()["fetches"] == 0
        # continue on B: local miss -> pod view -> one blob fetch
        got = [t for t, _ in b_b.generate_step(BASE + [9], max_tokens=12)]
        ref = [t for t, _ in mono.generate_step(BASE + [9], max_tokens=12)]
        assert got == ref
        sb = f_b.prefix.stats()
        assert sb["fetches"] == 1 and sb["fetch_bytes"] > 0
        assert f_a.prefix.stats()["blobs_served"] == 1
        assert store_b.stats()["tokens_reused"] >= 2 * PAGE
        # the same prefix again on B: local host tier, no second fetch
        got2 = [t for t, _ in b_b.generate_step(BASE + [3], max_tokens=8)]
        ref2 = [t for t, _ in mono.generate_step(BASE + [3], max_tokens=8)]
        assert got2 == ref2
        assert f_b.prefix.stats()["fetches"] == 1
        # fault leg: a fresh hot prefix on A, fetch faulted on B ->
        # plain prefill, stream still bit-identical, fault counted
        base2 = [11, 3, 3, 1, 2, 8, 8, 5, 9, 1, 40, 6, 12, 7, 7, 2]
        list(b_a.generate_step(base2 + [5], max_tokens=12))
        f_a.tick()
        f_b.tick()
        faults.arm("pod.prefix_fetch", exc=faults.FaultError, times=4)
        got3 = [t for t, _ in b_b.generate_step(base2 + [9], max_tokens=12)]
        ref3 = [t for t, _ in mono.generate_step(base2 + [9],
                                                 max_tokens=12)]
        assert got3 == ref3
        assert f_b.prefix.stats()["fallbacks"]["fetch_fault"] >= 1
        assert f_b.prefix.stats()["fetches"] == 1  # no new fetch
    finally:
        faults.disarm()
        f_a.close(close_local=False)
        f_b.close(close_local=False)
        b_a.close()
        b_b.close()
        mono.close()
