"""Pod-scale multihost fleet (pod.py): cross-host weight store gossip,
disagg handoff over the pod fabric, and the pod autoscaler.

Parity contract: every stream a client sees through a pod-attached
coordinator — including streams whose decode leg ran on a REMOTE host —
is bit-identical to the same request served by one monolithic batcher.
Every ``PodHandoffFallback`` kind (injected fault, unreachable remote,
serialization failure, transfer failure, remote pool error, and the
relay timeout that drains a dead host) must land back on the origin's
local plan, counted by kind, never a dropped stream.

The quick tier runs everything over the in-process :class:`LoopbackHub`;
the slow tier spawns two real processes over gloo collectives and
asserts the module's own acceptance demo (``python -m
mlx_sharding_tpu.pod``) reports ok."""

import json
import os
import socket
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.disagg import DisaggCoordinator
from mlx_sharding_tpu.kv_transfer import BlockIntegrityError, KVPageBlock
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.pod import (
    LoopbackHub,
    PodAutoscaler,
    PodFleet,
    PodHandoff,
    PodHandoffFallback,
    PodWeightRegistry,
)
from mlx_sharding_tpu.replicas import ReplicaSet
from mlx_sharding_tpu.resilience import ResumeState
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from mlx_sharding_tpu.testing import faults
from mlx_sharding_tpu.utils.observability import ServingMetrics
from mlx_sharding_tpu.weights import WeightKey, WeightStore, key_digest

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)

# greedy and seeded-stochastic: the remote decode host must reproduce
# both bit-for-bit (the kw whitelist carries the sampler config)
JOBS = [
    ([3, 17, 42], dict(max_tokens=24)),
    ([9, 4, 4, 6], dict(temperature=0.9, top_p=0.85, seed=321,
                        repetition_penalty=1.3, repetition_context_size=8,
                        max_tokens=20)),
]


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    faults.disarm()


@pytest.fixture(scope="module")
def tiny_model():
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    return model, params


def _mk_batcher(tiny_model, dev_idx):
    model, params = tiny_model
    devices = jax.devices()
    eng = PipelineEngine(
        model, params, make_mesh(pp=1, devices=devices[dev_idx:dev_idx + 1]),
        microbatches=2, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, pool_pages=10, page_size=8,
    )
    return ContinuousBatcher(eng, decode_block=3)


@pytest.fixture(scope="module")
def engines(tiny_model):
    """Host 0's coordinator (prefill + decode pools), host 1's decode
    batcher, and the monolithic parity reference — shared across the pod
    tests; each test builds its own fabric around them."""
    co = DisaggCoordinator(
        ReplicaSet([_mk_batcher(tiny_model, 0)], role="prefill"),
        ReplicaSet([_mk_batcher(tiny_model, 1)], role="decode"),
    )
    b1 = _mk_batcher(tiny_model, 2)
    mono = _mk_batcher(tiny_model, 3)
    refs = [[t for t, _ in mono.generate_step(p, **kw)] for p, kw in JOBS]
    yield SimpleNamespace(co=co, b1=b1, refs=refs)
    co.close()
    b1.close()
    mono.close()


@pytest.fixture
def pod(engines):
    """A fresh two-host loopback pod around the shared engines: host 0
    serves the coordinator (its decode pool priced as saturated so every
    handoff prefers the remote), host 1 serves the plain batcher."""
    hub = LoopbackHub()
    f0 = PodFleet(0, hub.register(0), engines.co)
    f1 = PodFleet(1, hub.register(1), engines.b1)
    f0.tick()
    f1.tick()
    f0.handoff.local_pressure = lambda: 1.0
    yield SimpleNamespace(hub=hub, f0=f0, f1=f1, co=engines.co,
                          refs=engines.refs)
    # the shared engines outlive this pod membership (module fixture)
    f0.close(close_local=False)
    f1.close(close_local=False)
    engines.co.pod = None  # detach so later fixtures start clean


# --------------------------------------------------------------- wire format


def _mk_block():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((2, 1, 3, 8, 4)).astype(np.float32)
    blk = KVPageBlock(
        k_pages=k, v_pages=k + 1.0, n_tokens=20, page_size=8,
        prompt=np.array([3, 17, 42], np.int32), history=[5, 6], produced=2,
        last_tok=6, resume_keys=None, resume_recent=None,
    )
    return blk.to_host()


def test_block_wire_roundtrip_bit_exact():
    blk = _mk_block()
    data = blk.to_bytes()
    back = KVPageBlock.from_bytes(data)
    np.testing.assert_array_equal(np.asarray(back.k_pages),
                                  np.asarray(blk.k_pages))
    np.testing.assert_array_equal(np.asarray(back.v_pages),
                                  np.asarray(blk.v_pages))
    assert back.n_tokens == blk.n_tokens
    assert back.history == blk.history
    assert back.last_tok == blk.last_tok
    assert back.checksum == blk.checksum


def test_block_wire_corruption_detected():
    data = _mk_block().to_bytes()
    with pytest.raises(BlockIntegrityError):
        KVPageBlock.from_bytes(data[: len(data) // 2])
    mid = len(data) // 2
    flipped = data[:mid] + bytes([data[mid] ^ 0xFF]) + data[mid + 1:]
    with pytest.raises(BlockIntegrityError):
        KVPageBlock.from_bytes(flipped)


# ------------------------------------------------------------ weight gossip


def test_registry_build_once_and_pod_view():
    key = WeightKey(checkpoint="ck", stage_bounds=(("auto", 1),),
                    dtype="float32", quant="none", placement="h0")
    store = WeightStore()
    builds = []

    def build():
        builds.append(1)
        return object()

    a = store.acquire(key, build)
    b = store.acquire(key, build)
    assert len(builds) == 1  # one packed tree, two local refs
    reg = PodWeightRegistry(store=store)
    info = reg.local_info()
    assert info["trees"] == 1 and info["refs"] == 2
    assert key_digest(key) in info["digests"]

    # the pod view aggregates gossiped peers into the {host=} source
    view = reg.pod_view({1: {"info": {"weights": {"trees": 1, "refs": 3,
                                                  "bytes": 17}}},
                         2: {"info": {}}})
    assert view == {1: {"trees": 1, "refs": 3, "bytes": 17}}

    # teardown broadcast maps a gossiped digest back onto the local key
    torn = []
    reg.set_teardown_handler(torn.append)
    assert reg.handle_teardown(key_digest(key)) == key
    assert torn == [key]
    assert reg.handle_teardown("ffffffffffffffff") is None
    b.release()
    a.release()


def test_registry_teardown_broadcast_over_fabric():
    hub = LoopbackHub()
    t0, t1 = hub.register(0), hub.register(1)
    key = WeightKey(checkpoint="ck", stage_bounds=(("auto", 1),),
                    dtype="float32", quant="none", placement="h1")
    s1 = WeightStore()
    lease = s1.acquire(key, object)
    r1 = PodWeightRegistry(store=s1)
    torn = []
    r1.set_teardown_handler(torn.append)
    t1.set_handler(
        lambda src, kind, payload: r1.handle_teardown(payload.decode()))
    t1.publish({})
    PodWeightRegistry(store=WeightStore()).request_teardown(
        t0, key_digest(key))
    assert torn == [key]
    lease.release()


# ----------------------------------------------------- cross-host handoff


def test_cross_host_handoff_parity(pod):
    for (prompt, kw), ref in zip(JOBS, pod.refs):
        # host 1 is alive: it heartbeats (PodFleet.start()'s thread in a
        # deployment). Without it a first job that compiles for more than
        # HEARTBEAT_TIMEOUT_S on a loaded CPU leaves the peer stale, and the
        # second job is rightly served locally
        pod.f1.tick()
        got = [t for t, _ in pod.co.generate_step(prompt, **kw)]
        assert got == ref
    h = pod.f0.handoff.stats()
    assert h["shipped"] == len(JOBS)
    assert h["bytes_shipped"] > 0
    assert h["relayed_tokens"] > 0
    assert h["fallbacks"] == {}
    assert h["ms_p50"] is not None
    assert pod.f1.handoff.stats()["received"] == len(JOBS)


def test_pick_remote_tie_serves_locally(pod):
    # an equally-loaded remote never wins: the wire is not free
    pod.f0.handoff.local_pressure = lambda: 0.0
    assert pod.f0.handoff.pick_remote() is None
    pod.f0.handoff.local_pressure = lambda: 1.0
    assert pod.f0.handoff.pick_remote() == 1
    assert pod.f0.handoff.stats()["fallbacks"] == {}


def test_fallback_remote_unavailable():
    hub = LoopbackHub()
    h = PodHandoff(0, hub.register(0), local_pressure=lambda: 1.0)
    state = ResumeState(prompt=np.array([1, 2], np.int32), history=[],
                        produced=0)
    with pytest.raises(PodHandoffFallback) as exc:
        next(h.serve_remote(state, {}))
    assert exc.value.kind == "remote_unavailable"
    assert exc.value.keep_block
    assert h.stats()["fallbacks"] == {"remote_unavailable": 1}


def test_fallback_injected_handoff_fault(pod):
    faults.arm("pod.handoff", exc=faults.FaultError, times=1)
    got = [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    assert got == pod.refs[0]
    h = pod.f0.handoff.stats()
    assert h["fallbacks"] == {"handoff_fault": 1}
    assert h["shipped"] == 0  # the fault fires before any wire work


def test_fallback_serialize_error(pod, monkeypatch):
    def boom(self):
        raise RuntimeError("unserializable")

    monkeypatch.setattr(KVPageBlock, "to_bytes", boom)
    got = [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    assert got == pod.refs[0]  # serve-in-place: local import of the block
    h = pod.f0.handoff.stats()
    assert h["fallbacks"] == {"serialize_error": 1}
    assert h["shipped"] == 0


def test_fallback_transfer_fault(pod):
    # the remote dies between pick and ship: the heartbeat is still
    # fresh, so the pick succeeds and the send itself bounces
    pod.hub.kill(1)
    got = [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    assert got == pod.refs[0]
    h = pod.f0.handoff.stats()
    assert h["fallbacks"] == {"transfer_fault": 1}
    assert h["shipped"] == 0


def test_fallback_remote_error(pod):
    class Broken:
        def generate_step(self, prompt, **kw):
            raise RuntimeError("remote pool down")
            yield  # pragma: no cover

    pod.f1.handoff.attach_local(Broken())
    got = [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    assert got == pod.refs[0]
    h = pod.f0.handoff.stats()
    assert h["fallbacks"] == {"remote_error": 1}
    assert h["shipped"] == 1  # the block made it over before the failure


def test_host_death_mid_relay_drains_token_exact(pod):
    """The host-death drain: the remote goes silent after 2 relayed
    tokens, the origin's relay times out and resumes locally AFTER the
    delivered tokens — the full stream stays bit-identical."""
    orig = pod.hub._handlers[0]
    seen = [0]

    def silent_death(src, kind, payload):
        if kind == "pod.tok":
            seen[0] += 1
            if seen[0] > 2:
                return
        elif kind == "pod.end":
            return
        orig(src, kind, payload)

    pod.hub._handlers[0] = silent_death
    pod.f0.handoff.relay_timeout_s = 2.0  # don't wait 30s on the corpse
    got = [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    assert got == pod.refs[0]  # zero dropped streams, token-exact
    h = pod.f0.handoff.stats()
    assert h["fallbacks"] == {"relay_timeout": 1}
    assert h["relayed_tokens"] == 2


def test_every_fallback_kind_is_counted(pod, monkeypatch):
    """One sweep over every degradation the ladder defines: each lands on
    the local plan with an identical stream and its own counter."""
    prompt, kw = JOBS[0]
    ref = pod.refs[0]

    faults.arm("pod.handoff", exc=faults.FaultError, times=1)
    assert [t for t, _ in pod.co.generate_step(prompt, **kw)] == ref

    with monkeypatch.context() as m:
        m.setattr(KVPageBlock, "to_bytes",
                  lambda self: (_ for _ in ()).throw(RuntimeError("x")))
        assert [t for t, _ in pod.co.generate_step(prompt, **kw)] == ref

    pod.f1.handoff.attach_local(
        type("B", (), {"generate_step": lambda self, p, **k:
                       (_ for _ in ()).throw(RuntimeError("down"))})())
    assert [t for t, _ in pod.co.generate_step(prompt, **kw)] == ref

    pod.hub.kill(1)
    assert [t for t, _ in pod.co.generate_step(prompt, **kw)] == ref

    assert pod.f0.handoff.stats()["fallbacks"] == {
        "handoff_fault": 1, "serialize_error": 1,
        "remote_error": 1, "transfer_fault": 1,
    }


# ------------------------------------------------------------ pod autoscaler


class _Ctrl:
    """Fake FleetAutoscaler: fixed pressure/headroom, records nudges."""

    def __init__(self, pressure=0.0, spawnable=0, drainable=0, slots=4):
        self._p = pressure
        self._spawnable = spawnable
        self._drainable = drainable
        self.actions = []
        self.rs = SimpleNamespace(stats=lambda: (slots, 0, 0))

    def pressure(self):
        return self._p

    def headroom(self):
        return {"live": 1, "spawnable": self._spawnable,
                "drainable": self._drainable}

    def spawn_one(self):
        self.actions.append("spawn")
        return "spawn"

    def drain_one(self):
        self.actions.append("drain")
        return "drain"


def _fleet_info(pressure, spawnable=0, drainable=0, slots=4):
    return {"pressure": pressure, "slots": slots, "live": 1,
            "spawnable": spawnable, "drainable": drainable}


def test_autoscaler_spawns_on_least_loaded_host():
    clk = [0.0]
    hub = LoopbackHub(clock=lambda: clk[0])
    t0, t1 = hub.register(0), hub.register(1)
    ctrl = _Ctrl(pressure=0.8, spawnable=1)
    a = PodAutoscaler(0, t0, [ctrl], heartbeat_timeout_s=5.0,
                      clock=lambda: clk[0])
    # the peer is hotter and has no headroom: WE are the spawn target
    t1.publish({"fleet": _fleet_info(0.95)})
    out = a.tick()
    assert out["action"] == "spawn" and ctrl.actions == ["spawn"]
    assert out["pod_pressure"] >= a.scale_up_pressure
    # a less-loaded peer WITH headroom takes the next one — not us
    t1.publish({"fleet": _fleet_info(0.76, spawnable=1)})
    ctrl._p = 0.95
    assert a.tick()["action"] is None and ctrl.actions == ["spawn"]


def test_autoscaler_drains_most_loaded_host():
    clk = [0.0]
    hub = LoopbackHub(clock=lambda: clk[0])
    t0, t1 = hub.register(0), hub.register(1)
    ctrl = _Ctrl(pressure=0.2, drainable=1)
    a = PodAutoscaler(0, t0, [ctrl], heartbeat_timeout_s=5.0,
                      clock=lambda: clk[0])
    # we are the most-loaded drainable host (the peer is idle, undrainable)
    t1.publish({"fleet": _fleet_info(0.05)})
    assert a.tick()["action"] == "drain" and ctrl.actions == ["drain"]
    # a hotter drainable peer sheds instead
    t1.publish({"fleet": _fleet_info(0.22, drainable=1)})
    assert a.tick()["action"] is None and ctrl.actions == ["drain"]


def test_autoscaler_declares_death_once():
    clk = [0.0]
    hub = LoopbackHub(clock=lambda: clk[0])
    t0, t1 = hub.register(0), hub.register(1)
    deaths = []
    a = PodAutoscaler(0, t0, [_Ctrl(pressure=0.5)], heartbeat_timeout_s=5.0,
                      on_host_death=deaths.append, clock=lambda: clk[0])
    t1.publish({"fleet": _fleet_info(0.5)})
    assert a.tick()["dead"] == []
    clk[0] += 6.0  # heartbeat goes stale past the timeout
    assert a.tick()["dead"] == [1]
    a.tick()
    assert deaths == [1]  # fired exactly once
    assert a.state()["deaths_detected"] == 1


def test_pod_fleet_death_reflected_in_pod_stats(engines):
    clk = [0.0]
    hub = LoopbackHub(clock=lambda: clk[0])
    f0 = PodFleet(0, hub.register(0), engines.co, heartbeat_timeout_s=5.0,
                  clock=lambda: clk[0])
    f1 = PodFleet(1, hub.register(1), engines.b1, heartbeat_timeout_s=5.0,
                  clock=lambda: clk[0])
    try:
        f0.tick()
        f1.tick()
        assert f0.pod_stats()["hosts"]["1"]["alive"]
        clk[0] += 6.0
        f0.tick()
        st = f0.pod_stats()
        assert not st["hosts"]["1"]["alive"]
        assert st["autoscaler"]["dead_hosts"] == [1]
        assert st["host_deaths"] == 1
    finally:
        f0.close(close_local=False)
        f1.close(close_local=False)
        engines.co.pod = None


# ------------------------------------------------------------- observability


def test_pod_metrics_render(pod):
    [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    faults.arm("pod.handoff", exc=faults.FaultError, times=1)
    [t for t, _ in pod.co.generate_step(JOBS[0][0], **JOBS[0][1])]
    text = ServingMetrics(pod_stats_fn=pod.f0.pod_stats).render()
    assert "mst_pod_hosts 2" in text
    assert 'mst_pod_host_alive{host="0"} 1' in text
    assert 'mst_pod_host_alive{host="1"} 1' in text
    assert 'mst_pod_heartbeat_age_seconds{host="1"}' in text
    assert 'mst_weight_store_trees{host="0"}' in text
    assert 'mst_fleet_size{host="0"}' in text
    assert "mst_pod_handoff_total 1" in text
    assert "mst_pod_handoff_bytes_total" in text
    assert 'mst_pod_handoff_fallbacks_total{kind="handoff_fault"} 1' in text
    assert 'mst_pod_handoff_ms{quantile="0.5"}' in text
    # each family is TYPEd exactly once — a duplicate breaks scrapers
    types = [ln for ln in text.splitlines() if ln.startswith("# TYPE ")]
    assert len(types) == len(set(types))


def test_pod_metrics_absent_on_single_host():
    assert "mst_pod_" not in ServingMetrics().render()
    assert "mst_pod_" not in ServingMetrics(
        pod_stats_fn=lambda: None).render()


def test_pod_metrics_never_500():
    def broken():
        raise RuntimeError("pod stats exploded")

    text = ServingMetrics(pod_stats_fn=broken).render()
    assert "mst_pod_" not in text  # the guard drops the partial block


def test_health_pod_block(pod):
    import http.client

    from mlx_sharding_tpu.server.openai_api import ModelProvider, make_server

    provider = ModelProvider.__new__(ModelProvider)
    provider.default_model = "tiny"
    provider.trust_remote_paths = False
    provider._key = None
    provider._load_lock = threading.Lock()
    provider.generator = SimpleNamespace()
    provider.pod_fleet = pod.f0
    srv = make_server(provider, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/health")
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert payload["pod"]["host_id"] == 0
        assert set(payload["pod"]["hosts"]) == {"0", "1"}
        # a broken pod surface must never take /health down
        provider.pod_fleet = SimpleNamespace(
            pod_stats=lambda: (_ for _ in ()).throw(RuntimeError("x")))
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/health")
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        assert "pod" not in payload
    finally:
        srv.shutdown()


# ------------------------------------------------- capacity-aware sharing


def _provider(replicas=2, disagg=False, multihost=False, mode="auto"):
    from mlx_sharding_tpu.server.openai_api import ModelProvider

    p = ModelProvider.__new__(ModelProvider)
    p.shared_weights = mode
    p.replicas = replicas
    p.disagg = disagg
    p.multihost = multihost
    return p


def test_shared_weights_auto_prices_kv_headroom(monkeypatch):
    W = 100 * 2**20
    # budget 500 MiB/slice, 3 replicas: W*(N+1)=400 MiB < 500 MiB — the
    # forfeited KV headroom outweighs the saved uploads, keep private
    monkeypatch.setenv("MST_DEVICE_MEMORY_BYTES", str(500 * 2**20))
    p = _provider(replicas=3)
    assert p._shared_weights_on(weight_bytes=W, want=3, per=1,
                                n_devices=8) is False
    # budget 300 MiB/slice: 400 MiB >= 300 MiB — sharing wins
    monkeypatch.setenv("MST_DEVICE_MEMORY_BYTES", str(300 * 2**20))
    assert p._shared_weights_on(weight_bytes=W, want=3, per=1,
                                n_devices=8) is True


def test_shared_weights_auto_edges(monkeypatch):
    W = 100 * 2**20
    monkeypatch.setenv("MST_DEVICE_MEMORY_BYTES", str(500 * 2**20))
    # a grid too small for want private slices forces sharing regardless
    assert _provider(replicas=4)._shared_weights_on(
        weight_bytes=W, want=4, per=4, n_devices=8) is True
    # unknown budget: auto keeps the legacy always-share-for-fleet rule
    monkeypatch.delenv("MST_DEVICE_MEMORY_BYTES", raising=False)
    assert _provider(replicas=3)._shared_weights_on(
        weight_bytes=W, want=3, per=1, n_devices=8) is True
    # explicit modes bypass the pricing entirely
    monkeypatch.setenv("MST_DEVICE_MEMORY_BYTES", str(500 * 2**20))
    assert _provider(mode="off")._shared_weights_on(
        weight_bytes=W, want=3, per=1, n_devices=8) is False
    assert _provider(mode="on")._shared_weights_on(
        weight_bytes=W, want=3, per=1, n_devices=8) is True
    # not a fleet / SPMD multihost: nothing to share
    assert _provider(replicas=1)._shared_weights_on(
        weight_bytes=W, want=1, per=1, n_devices=8) is False
    assert _provider(multihost=True)._shared_weights_on(
        weight_bytes=W, want=3, per=1, n_devices=8) is False


# ---------------------------------------------------------- gloo acceptance


@pytest.mark.slow
def test_gloo_two_process_acceptance():
    """The module's own acceptance demo over real gloo collectives: one
    packed tree per host aliased by two replicas, a cross-host handoff
    bit-identical to monolithic serving, and fault + host-death drains
    with zero dropped streams."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def spawn(rank):
        return subprocess.Popen(
            [sys.executable, "-m", "mlx_sharding_tpu.pod",
             "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", "2", "--process-id", str(rank)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        )

    r1 = spawn(1)
    r0 = spawn(0)
    try:
        out = r0.communicate(timeout=240)[0].decode()
    finally:
        r0.kill()
        r1.kill()
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    assert lines, f"rank0 printed no report:\n{out[-2000:]}"
    report = json.loads(lines[-1])
    assert report["ok"] is True, report
    assert r0.returncode == 0
    for host in ("0", "1"):
        w = report["hosts"][host]["weights"]
        assert w["trees"] == 1 and w["refs"] >= 2
    assert report["handoff"]["match"] and report["handoff"]["shipped"] >= 1
    assert report["fault_sweep"]["fallbacks"]["handoff_fault"] == 1
    assert report["host_death"]["match"]
    assert report["host_death"]["dropped_streams"] == 0
