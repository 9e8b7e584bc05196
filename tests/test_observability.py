import http.client
import json

import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.utils.observability import ServingMetrics, _Reservoir, profile_trace


def test_reservoir_percentiles():
    r = _Reservoir(capacity=100)
    for i in range(100):
        r.add(float(i))
    assert abs(r.percentile(50) - 50) <= 2
    assert abs(r.percentile(95) - 95) <= 2


def test_metrics_render():
    m = ServingMetrics()
    m.record_request(prompt_tokens=10, generation_tokens=20, ttft_s=0.5, decode_tps=40.0)
    m.record_failure()
    out = m.render()
    assert "mst_requests_total 2" in out
    assert "mst_requests_failed_total 1" in out
    assert "mst_generation_tokens_total 20" in out
    assert 'mst_decode_tokens_per_second{quantile="0.5"} 40.000' in out


def test_profile_trace_noop():
    with profile_trace(None):
        pass  # must not require jax


def test_metrics_endpoint(tmp_path):
    """/metrics live on the server after a request."""
    import threading

    import jax
    import jax.numpy as jnp

    from mlx_sharding_tpu.config import LlamaConfig
    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.models.llama import LlamaModel
    from mlx_sharding_tpu.server.openai_api import ModelProvider, make_server
    from tests.test_tokenizer_utils import ByteTokenizer

    model = LlamaModel(
        LlamaConfig(
            vocab_size=300, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        )
    )
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    gen = Generator(model, params, max_seq=128, cache_dtype=jnp.float32, prefill_chunk=16)
    provider = ModelProvider.__new__(ModelProvider)
    provider.default_model = "tiny"
    provider.trust_remote_paths = False
    provider._key = None
    provider._load_lock = threading.Lock()
    provider._set("tiny", gen, ByteTokenizer())
    srv = make_server(provider, "127.0.0.1", 0)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request(
            "POST", "/v1/completions",
            json.dumps({"prompt": "hi", "max_tokens": 5}),
            {"Content-Type": "application/json"},
        )
        conn.getresponse().read()
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200
        assert "mst_requests_total 1" in body
        assert "mst_generation_tokens_total 5" in body
        conn.close()
    finally:
        srv.shutdown()


def test_metrics_expose_batcher_slots():
    """/metrics reports slot occupancy and queue depth when the server runs
    a ContinuousBatcher (stats() contract; the real batcher integration is
    covered by the scheduler/server suites)."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    class _FakeBatcher:
        def stats(self):
            return (2, 1, 3)

    text = ServingMetrics(batcher_fn=lambda: _FakeBatcher()).render()
    assert "mst_batch_slots 2" in text
    assert "mst_batch_slots_active 1" in text
    assert "mst_batch_queue_depth 3" in text
    # and none of it when no batcher is live
    assert "mst_batch_slots" not in ServingMetrics().render()


def _fake_tick_phase_stats(path="async"):
    from mlx_sharding_tpu.tracing import TICK_PHASES

    seconds = dict.fromkeys(TICK_PHASES, 0.0)
    seconds.update(harvest_wait=9.5, emit=0.25, kv_import=2.125)
    empty = dict.fromkeys(TICK_PHASES, 0.0)
    empty.update(emit=0.125, kv_import=2.125)
    entries = dict.fromkeys(TICK_PHASES, 0)
    entries.update(harvest_wait=7, emit=7, kv_import=1)
    return {
        "path": path, "ticks": 9, "phase_seconds": seconds,
        "device_empty_seconds": empty, "phase_entries": entries,
        "blocks_dispatched": 8, "blocks_harvested": 7, "blocks_abandoned": 0,
        "positions_computed": 128, "tokens_emitted": 100,
        "tokens_dropped": {"slot_finished": 12, "abandoned_block": 0},
        "drains": {"admit": 2, "idle": 1},
        "blocks_by_sampler": {"greedy": 6, "draw": 0, "nucleus": 2},
        "join_programs": {"claim": 4, "chunk": 9, "finish": 4, "other": 0},
        "join_first_reads": {"behind_block": 3, "before_block": 1},
        "emit_held": {"chunk": 31, "tick_end": 2, "fail": 0},
        "emit_hold_seconds": 0.0075, "emit_holds": 5,
        "program_device_seconds": {"block": 9.25, "chunk": 0.5, "other": 0.0},
        "program_dispatch_exposed_seconds":
            {"block": 0.125, "chunk": 0.0625, "other": 0.0},
        "program_runs": {"block": 7, "chunk": 9, "other": 0},
        "program_late": {"block": 0, "chunk": 1, "other": 0},
        "program_unread_seconds": 0.0,
    }


def test_metrics_expose_tick_timing():
    """/metrics reports the scheduler path (sync vs async tick pipeline)
    and the tick's cumulative account: seconds and entries per phase,
    blocks dispatched and harvested, positions computed against tokens
    emitted and dropped, pipeline drains (tick_phase_stats() contract)."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    class _FakeBatcher:
        def stats(self):
            return (2, 1, 0)

        def tick_phase_stats(self):
            return _fake_tick_phase_stats()

    text = ServingMetrics(batcher_fn=lambda: _FakeBatcher()).render()
    assert "mst_sched_async 1" in text
    assert 'mst_tick_phase_seconds_total{phase="harvest_wait"} 9.500000' in text
    assert 'mst_device_empty_seconds_total{phase="harvest_wait"} 0.000000' in text
    assert 'mst_device_empty_seconds_total{phase="emit"} 0.125000' in text
    assert 'mst_tick_phase_total{phase="emit"} 7' in text
    assert "mst_ticks_total 9" in text
    assert "mst_decode_blocks_dispatched_total 8" in text
    assert "mst_decode_blocks_harvested_total 7" in text
    assert "mst_decode_positions_computed_total 128" in text
    assert "mst_decode_tokens_emitted_total 100" in text
    assert 'mst_decode_tokens_dropped_total{reason="slot_finished"} 12' in text
    assert 'mst_pipeline_drains_total{reason="admit"} 2' in text
    assert 'mst_decode_blocks_total{sampler="greedy"} 6' in text
    assert 'mst_decode_blocks_total{sampler="nucleus"} 2' in text
    assert 'mst_join_programs_total{program="chunk"} 9' in text
    assert 'mst_join_programs_total{program="other"} 0' in text
    # joins by where their first token's read fell: both labels, always
    assert "# TYPE mst_join_first_reads_total counter" in text
    assert "# HELP mst_join_first_reads_total Joins by where" in text
    assert 'mst_join_first_reads_total{order="behind_block"} 3' in text
    assert 'mst_join_first_reads_total{order="before_block"} 1' in text
    assert 'mst_emit_held_total{flush="chunk"} 31' in text
    assert 'mst_emit_held_total{flush="fail"} 0' in text
    assert "mst_emit_hold_seconds_sum 0.007500" in text
    assert "mst_emit_hold_seconds_count 5" in text
    # the one-tick gauges are gone: nothing could read them soundly
    assert "mst_tick_host_ms" not in text
    assert "mst_tick_device_blocked_ms" not in text

    class _SyncBatcher(_FakeBatcher):
        def tick_phase_stats(self):
            return _fake_tick_phase_stats(path="sync")

    text = ServingMetrics(batcher_fn=lambda: _SyncBatcher()).render()
    assert "mst_sched_async 0" in text

    class _NoTickBatcher:
        def stats(self):
            return (2, 1, 0)

    # a batcher without the accessors (or a plain fake) emits no tick families
    text = ServingMetrics(batcher_fn=lambda: _NoTickBatcher()).render()
    assert "mst_tick_phase" not in text
    assert "mst_sched_async" not in text

def test_metrics_expose_kv_residency_and_prefetch():
    """/metrics reports the proactive-residency split: cold-spill/wake
    activity, tier lookup quality, reject reasons, the prefetch-vs-demand
    resume counters, and the kv_import phase's stall seconds, all of them
    with the device empty (spill_stats() / tick_phase_stats() contracts)."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    class _FakeBatcher:
        def stats(self):
            return (2, 1, 0)

        def spill_stats(self):
            return {
                "enabled": True, "spills": 4, "spill_hits": 3,
                "spill_fallbacks": 1, "evictions": 0, "bytes_in_use": 1024,
                "budget_bytes": 4096, "migrations_out": 0,
                "migrations_in": 0, "reprefill_tokens": 7,
                "cold_spills": 5, "cold_wakes": 4, "parked": 2,
                "hit_rate": 0.875, "rejects_oversize": 1,
                "rejects_closed": 2, "prefetch_enabled": True,
                "prefetches": 4, "prefetch_hits": 3, "demand_imports": 1,
                "prefetch_faults": 1,
            }

        def tick_phase_stats(self):
            return _fake_tick_phase_stats()

    text = ServingMetrics(batcher_fn=lambda: _FakeBatcher()).render()
    assert "mst_kv_spill_cold_total 5" in text
    assert "mst_kv_spill_wakes_total 4" in text
    assert "mst_kv_spill_parked 2" in text
    assert "mst_kv_spill_hit_rate 0.8750" in text
    assert 'mst_kv_spill_rejects_total{reason="oversize"} 1' in text
    assert 'mst_kv_spill_rejects_total{reason="closed"} 2' in text
    assert "mst_kv_prefetch_enabled 1" in text
    assert "mst_kv_prefetch_total 4" in text
    assert "mst_kv_prefetch_hits_total 3" in text
    assert "mst_kv_prefetch_demand_total 1" in text
    assert "mst_kv_prefetch_faults_total 1" in text
    assert 'mst_tick_phase_seconds_total{phase="kv_import"} 2.125000' in text
    assert 'mst_device_empty_seconds_total{phase="kv_import"} 2.125000' in text

    class _LegacySpill(_FakeBatcher):
        # a ReplicaSet aggregation that predates the residency keys
        def spill_stats(self):
            s = _FakeBatcher.spill_stats(self)
            for k in ("cold_spills", "cold_wakes", "parked", "hit_rate",
                      "rejects_oversize", "rejects_closed",
                      "prefetch_enabled", "prefetches", "prefetch_hits",
                      "demand_imports", "prefetch_faults"):
                del s[k]
            return s

        def tick_phase_stats(self):  # ... and the tick's phase account
            return None

    text = ServingMetrics(batcher_fn=lambda: _LegacySpill()).render()
    assert "mst_kv_spill_cold_total 0" in text
    assert "mst_kv_prefetch_enabled 0" in text
    assert "mst_tick_phase_seconds_total" not in text


def _rich_metrics():
    """A ServingMetrics wired with every accessor the renderer reads,
    all returning data — the widest exposition we can produce offline."""
    from mlx_sharding_tpu.prefix_store import PrefixStore
    from mlx_sharding_tpu.utils.observability import (
        HANDOFF_BUCKETS_MS, ITL_BUCKETS_S, LATENCY_BUCKETS_S, Histogram,
        ServingMetrics,
    )

    itl = Histogram(ITL_BUCKETS_S)
    itl.observe(0.01)
    qw = Histogram(LATENCY_BUCKETS_S)
    qw.observe(0.2)
    hand = Histogram(HANDOFF_BUCKETS_MS)
    hand.observe(3.0)

    class _Batcher:
        def stats(self):
            return (2, 1, 3)

        def tick_phase_stats(self):
            return _fake_tick_phase_stats()

        def spill_stats(self):
            return {"enabled": True, "spills": 4, "spill_hits": 3,
                    "spill_fallbacks": 1, "evictions": 0,
                    "bytes_in_use": 1024, "budget_bytes": 4096,
                    "migrations_out": 1, "migrations_in": 1,
                    "reprefill_tokens": 7, "cold_spills": 5,
                    "cold_wakes": 4, "parked": 2, "hit_rate": 0.875,
                    "rejects_oversize": 1, "rejects_closed": 2,
                    "prefetch_enabled": True, "prefetches": 4,
                    "prefetch_hits": 3, "demand_imports": 1,
                    "prefetch_faults": 1}

        def latency_stats(self):
            return {"itl": itl.to_dict(), "queue_wait": qw.to_dict(),
                    "join": qw.to_dict()}

        def fleet_stats(self):
            return {"size": 2, "sticky_hits": 1, "affinity_hits": 2,
                    "store_hits": 3}

        def handoff_stats(self):
            return {"handoffs": 4, "bytes_total": 100, "ms_p50": 1.0,
                    "ms_p99": 2.0, "fallbacks": {"handoff_fault": 1},
                    "store_skips": 5, "ms_hist": hand.to_dict()}

    store = PrefixStore(host_bytes=1 << 20)
    m = ServingMetrics(batcher_fn=lambda: _Batcher(),
                       prefix_store_fn=lambda: store)
    m.record_request(prompt_tokens=10, generation_tokens=20, ttft_s=0.5,
                     decode_tps=40.0)
    m.record_failure()
    return m, store


def test_metrics_help_type():
    """Exposition coverage contract: EVERY sample family in the widest
    render carries ``# HELP`` and ``# TYPE`` ahead of its first sample,
    histogram suffixes (_bucket/_sum/_count) resolve to a family declared
    ``histogram``, and the latency families render as real cumulative
    histograms."""
    m, store = _rich_metrics()
    try:
        text = m.render()
    finally:
        store.close()
    helped, typed, hist = set(), {}, set()
    for ln in text.splitlines():
        if ln.startswith("# HELP "):
            helped.add(ln.split()[2])
            continue
        if ln.startswith("# TYPE "):
            fam = ln.split()[2]
            assert fam in helped, f"# TYPE {fam} without a preceding # HELP"
            assert fam not in typed, f"duplicate # TYPE for {fam}"
            typed[fam] = ln.split()[3]
            if typed[fam] in ("histogram", "summary"):
                hist.add(fam)
            continue
        if not ln or ln.startswith("#"):
            continue
        name = ln.split("{", 1)[0].split(" ", 1)[0]
        fam = name
        for sfx in ("_bucket", "_sum", "_count"):
            if name.endswith(sfx) and name[: -len(sfx)] in hist:
                fam = name[: -len(sfx)]
        assert fam in typed, f"sample {name} has no # TYPE"
        assert fam in helped, f"sample {name} has no # HELP"
    # the histogram-grade latency families are really histograms
    for fam in ("mst_ttft_seconds", "mst_itl_seconds",
                "mst_queue_wait_seconds", "mst_join_seconds",
                "mst_disagg_handoff_ms"):
        assert typed.get(fam) == "histogram", f"{fam} should be a histogram"
        assert f'{fam}_bucket{{le="+Inf"}}' in text
        assert f"{fam}_sum " in text and f"{fam}_count " in text
    # counters follow the Prometheus naming/type convention
    for fam, ty in typed.items():
        if fam.endswith("_total"):
            assert ty == "counter", f"{fam} typed {ty}, want counter"


def test_metrics_render_never_500():
    """Every accessor raising at scrape time still yields a parseable
    exposition with the core request counters — a sick engine must not
    take down the monitoring that would diagnose it."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    def _boom():
        raise RuntimeError("accessor gone")

    class _BrokenBatcher:
        def __getattr__(self, name):
            def method(*a, **kw):
                raise RuntimeError("batcher gone")
            return method

    for m in (
        ServingMetrics(batcher_fn=_boom, prefix_store_fn=_boom),
        ServingMetrics(batcher_fn=lambda: _BrokenBatcher()),
    ):
        m.record_request(prompt_tokens=1, generation_tokens=1, ttft_s=0.1,
                         decode_tps=1.0)
        text = m.render()
        assert "mst_requests_total 1" in text


def test_metrics_expose_itl_and_queue_wait_histograms():
    """The scheduler's latency_stats() contract flows to /metrics as
    cumulative bucketed histograms; a batcher without the accessor (or a
    fleet with nothing recorded) emits neither family."""
    from mlx_sharding_tpu.utils.observability import (
        ITL_BUCKETS_S, LATENCY_BUCKETS_S, Histogram, ServingMetrics,
    )

    itl = Histogram(ITL_BUCKETS_S)
    for v in (0.004, 0.009, 2.0):
        itl.observe(v)
    qw = Histogram(LATENCY_BUCKETS_S)
    qw.observe(0.03)

    class _B:
        def stats(self):
            return (2, 1, 0)

        def latency_stats(self):
            return {"itl": itl.to_dict(), "queue_wait": qw.to_dict()}

    text = ServingMetrics(batcher_fn=lambda: _B()).render()
    assert 'mst_itl_seconds_bucket{le="0.005"} 1' in text
    assert 'mst_itl_seconds_bucket{le="+Inf"} 3' in text
    assert "mst_itl_seconds_count 3" in text
    assert 'mst_queue_wait_seconds_bucket{le="' in text
    assert "mst_queue_wait_seconds_count 1" in text

    class _NoLat:
        def stats(self):
            return (2, 1, 0)

    text = ServingMetrics(batcher_fn=lambda: _NoLat()).render()
    assert "mst_itl_seconds" not in text
    assert "mst_queue_wait_seconds" not in text


def test_metrics_expose_prefix_store():
    """/metrics reports the fleet-wide prefix store family — residency by
    tier, lookup quality, COW forks, insertion damping, eviction reasons —
    against a REAL PrefixStore so the renderer's key reads stay in lock-step
    with stats(); plus the routing/disagg counters and the never-500 rule."""
    from mlx_sharding_tpu.prefix_store import PrefixStore
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    store = PrefixStore(host_bytes=1 << 20)
    try:
        text = ServingMetrics(prefix_store_fn=lambda: store).render()
        assert 'mst_prefix_store_blocks{tier="device"} 0' in text
        assert 'mst_prefix_store_blocks{tier="host"} 0' in text
        assert 'mst_prefix_store_bytes{tier="host"} 0' in text
        assert f"mst_prefix_store_budget_bytes {1 << 20}" in text
        assert 'mst_prefix_store_hits_total{tier="device"} 0' in text
        assert 'mst_prefix_store_hits_total{tier="host"} 0' in text
        assert "mst_prefix_store_misses_total 0" in text
        assert "mst_prefix_store_hit_rate 0.0000" in text
        assert "mst_prefix_store_tokens_reused_total 0" in text
        assert "mst_prefix_store_cow_forks_total 0" in text
        assert "mst_prefix_store_inserts_total 0" in text
        assert "mst_prefix_store_inserts_damped_total 0" in text
        assert "mst_prefix_store_inserts_paused 0" in text
        assert "mst_prefix_store_demotions_total 0" in text
        assert "mst_prefix_store_demote_drops_total 0" in text
        assert 'mst_prefix_store_evictions_total{reason="budget"} 0' in text
        assert 'mst_prefix_store_evictions_total{reason="oversize"} 0' in text
        assert 'mst_prefix_store_evictions_total{reason="reset"} 0' in text
        assert 'mst_prefix_store_imports_total{kind="staged"} 0' in text
        assert 'mst_prefix_store_imports_total{kind="demand"} 0' in text
        assert 'mst_prefix_store_faults_total{kind="lookup"} 0' in text
        assert 'mst_prefix_store_faults_total{kind="import"} 0' in text
    finally:
        store.close()

    # no store wired -> no family
    assert "mst_prefix_store_" not in ServingMetrics().render()

    # a broken accessor must not 500 the scrape
    def _boom():
        raise RuntimeError("store gone")

    text = ServingMetrics(prefix_store_fn=_boom).render()
    assert "mst_requests_total" in text
    assert "mst_prefix_store_" not in text

    # routing + disagg counters ride the existing fleet/handoff blocks
    class _FakeFleet:
        def stats(self):
            return (2, 1, 0)

        def fleet_stats(self):
            return {"size": 2, "sticky_hits": 1, "affinity_hits": 2,
                    "store_hits": 3}

        def handoff_stats(self):
            return {"handoffs": 4, "bytes_total": 100, "ms_p50": 1.0,
                    "ms_p99": 2.0, "fallbacks": {}, "store_skips": 5}

    text = ServingMetrics(batcher_fn=lambda: _FakeFleet()).render()
    assert "mst_route_store_hits_total 3" in text
    assert "mst_disagg_store_skips_total 5" in text

    class _OldFleet(_FakeFleet):
        # pre-store aggregations lack the new keys -> lines stay absent
        def fleet_stats(self):
            f = _FakeFleet.fleet_stats(self)
            del f["store_hits"]
            return f

        def handoff_stats(self):
            h = _FakeFleet.handoff_stats(self)
            del h["store_skips"]
            return h

    text = ServingMetrics(batcher_fn=lambda: _OldFleet()).render()
    assert "mst_route_store_hits_total" not in text
    assert "mst_disagg_store_skips_total" not in text
