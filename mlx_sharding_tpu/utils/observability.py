"""Tracing / profiling / metrics.

The reference has none of this — ad-hoc prints on the shard server and a
tok/s printout in the CLI are its entire observability story (SURVEY §5
"Tracing/profiling: None"). Here:

- :func:`profile_trace` wraps the JAX profiler (TensorBoard-loadable traces
  of XLA execution, including per-op TPU timing) around any generation call;
- :class:`ServingMetrics` is a lock-guarded counter set the API server
  exposes at ``/metrics`` — request counts, token throughput, TTFT and
  decode-rate summaries (p50/p95 from a bounded reservoir).
"""

from __future__ import annotations

import bisect
import contextlib
import random
import sys
import threading
from dataclasses import dataclass, field

from mlx_sharding_tpu.analysis.runtime import make_lock

# Shared bucket boundaries. Chosen to straddle both the CPU smoke rig
# (ms-scale ticks) and real-chip serving points; the +Inf bucket is
# implicit (the histogram's last slot).
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0)
ITL_BUCKETS_S = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                 0.25, 0.5, 1.0)
HANDOFF_BUCKETS_MS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0)


@contextlib.contextmanager
def profile_trace(log_dir: str | None):
    """JAX profiler trace context; no-op when log_dir is falsy."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield


class _Reservoir:
    """Bounded uniform sample for percentile summaries."""

    def __init__(self, capacity: int = 512, seed: int = 0):
        self.capacity = capacity
        self.values: list[float] = []
        self.count = 0
        self._rng = random.Random(seed)

    def add(self, value: float):
        self.count += 1
        if len(self.values) < self.capacity:
            self.values.append(value)
        else:
            j = self._rng.randrange(self.count)
            if j < self.capacity:
                self.values[j] = value

    def percentile(self, p: float) -> float:
        if not self.values:
            return 0.0
        s = sorted(self.values)
        idx = min(len(s) - 1, max(0, round(p / 100 * (len(s) - 1))))
        return s[idx]


class Histogram:
    """Cumulative bucketed histogram — the Prometheus ``_bucket{le=}`` /
    ``_sum`` / ``_count`` exposition shape. Unlike the reservoir summaries
    (whose quantiles cannot be combined), bucket counts aggregate exactly:
    merging replicas or successive scrapes is elementwise addition, which
    is why the latency families that matter (TTFT, ITL, queue wait,
    handoff) live here and not in :class:`_Reservoir`."""

    __slots__ = ("_bounds", "_counts", "_sum", "_count", "_lock")

    def __init__(self, bounds, lock_name: str = "Histogram._lock"):
        self._bounds = tuple(float(b) for b in bounds)
        self._counts = [0] * (len(self._bounds) + 1)  # last slot = +Inf
        self._sum = 0.0
        self._count = 0
        self._lock = make_lock(lock_name)

    def observe(self, value: float):
        v = float(value)
        with self._lock:
            # bisect_left: first bound >= v, i.e. the smallest le bucket
            # containing v; beyond every bound lands in the +Inf slot
            self._counts[bisect.bisect_left(self._bounds, v)] += 1
            self._sum += v
            self._count += 1

    def to_dict(self) -> dict:
        """Serializable snapshot — the cross-replica aggregation currency
        (``latency_stats()`` contracts pass these, never live objects)."""
        with self._lock:
            return {
                "bounds": list(self._bounds),
                "counts": list(self._counts),
                "sum": self._sum,
                "count": self._count,
            }

    @staticmethod
    def merge_dicts(dicts) -> dict | None:
        """Elementwise merge of :meth:`to_dict` snapshots. Snapshots with
        mismatched bounds are skipped (a mixed-version fleet must degrade,
        not crash a scrape)."""
        out = None
        for d in dicts:
            if not d or "counts" not in d:
                continue
            if out is None:
                out = {
                    "bounds": list(d["bounds"]),
                    "counts": list(d["counts"]),
                    "sum": float(d["sum"]),
                    "count": int(d["count"]),
                }
            elif list(d["bounds"]) == out["bounds"]:
                out["counts"] = [a + b for a, b in
                                 zip(out["counts"], d["counts"])]
                out["sum"] += float(d["sum"])
                out["count"] += int(d["count"])
        return out

    @staticmethod
    def render_into(lines: list, family: str, snap: dict | None,
                    help_text: str = ""):
        """Append one family's exposition block from a :meth:`to_dict`
        snapshot (no-op when the snapshot is absent/malformed)."""
        if not snap or "counts" not in snap:
            return
        if help_text:
            lines.append(f"# HELP {family} {help_text}")
        lines.append(f"# TYPE {family} histogram")
        acc = 0
        for bound, n in zip(snap["bounds"], snap["counts"]):
            acc += n
            lines.append(f'{family}_bucket{{le="{bound:g}"}} {acc}')
        acc += snap["counts"][-1]
        lines.append(f'{family}_bucket{{le="+Inf"}} {acc}')
        lines.append(f"{family}_sum {snap['sum']:.6f}")
        lines.append(f"{family}_count {snap['count']}")


def sum_counter_dicts(per: list) -> dict:
    """Key-wise sum of (nested) dicts of counters — how ReplicaSet and
    DisaggCoordinator fold their batchers' ``tick_phase_stats()``. A name
    among them (``path``) stays where all agree and reads ``mixed`` else."""
    out: dict = {}
    for d in per:
        for k, v in d.items():
            if isinstance(v, dict):
                out[k] = sum_counter_dicts([out.get(k, {}), v])
            elif isinstance(v, str):
                out[k] = v if out.get(k, v) == v else "mixed"
            else:
                out[k] = out.get(k, 0) + v
    return out


def _render_tick_phases(lines: list, t: dict):
    """The scheduler tick's cumulative account (``tick_phase_stats()``):
    which run-loop it is on, where its time went and how much of it the
    device had nothing to run, and what became of the decode blocks it
    dispatched. All but the first are counters: a reader takes the delta
    over its window."""

    def labelled(family: str, label: str, values: dict, fmt: str = "{}"):
        for k in sorted(values):
            lines.append(f'{family}{{{label}="{k}"}} ' + fmt.format(values[k]))

    lines += [
        # which run-loop the batcher is on (1 = double-buffered async
        # pipeline, 0 = classic dispatch-then-harvest)
        "# TYPE mst_sched_async gauge",
        f"mst_sched_async {int(t['path'] == 'async')}",
        "# TYPE mst_tick_phase_seconds_total counter",
    ]
    labelled("mst_tick_phase_seconds_total", "phase", t["phase_seconds"],
             "{:.6f}")
    lines.append("# TYPE mst_device_empty_seconds_total counter")
    labelled("mst_device_empty_seconds_total", "phase",
             t["device_empty_seconds"], "{:.6f}")
    lines.append("# TYPE mst_tick_phase_total counter")
    labelled("mst_tick_phase_total", "phase", t["phase_entries"])
    lines += [
        "# TYPE mst_ticks_total counter",
        f"mst_ticks_total {t['ticks']}",
        "# TYPE mst_decode_blocks_dispatched_total counter",
        f"mst_decode_blocks_dispatched_total {t['blocks_dispatched']}",
        "# TYPE mst_decode_blocks_harvested_total counter",
        f"mst_decode_blocks_harvested_total {t['blocks_harvested']}",
        "# TYPE mst_decode_positions_computed_total counter",
        f"mst_decode_positions_computed_total {t['positions_computed']}",
        "# TYPE mst_decode_tokens_emitted_total counter",
        f"mst_decode_tokens_emitted_total {t['tokens_emitted']}",
        "# TYPE mst_decode_tokens_dropped_total counter",
    ]
    labelled("mst_decode_tokens_dropped_total", "reason", t["tokens_dropped"])
    lines.append("# TYPE mst_pipeline_drains_total counter")
    labelled("mst_pipeline_drains_total", "reason", t["drains"])
    lines.append("# TYPE mst_decode_blocks_total counter")
    labelled("mst_decode_blocks_total", "sampler", t["blocks_by_sampler"])
    lines.append("# TYPE mst_join_programs_total counter")
    labelled("mst_join_programs_total", "program", t["join_programs"])
    lines.append("# TYPE mst_join_first_reads_total counter")
    labelled("mst_join_first_reads_total", "order", t["join_first_reads"])
    lines.append("# TYPE mst_emit_held_total counter")
    labelled("mst_emit_held_total", "flush", t["emit_held"])
    lines += [
        "# TYPE mst_emit_hold_seconds summary",
        f"mst_emit_hold_seconds_sum {t['emit_hold_seconds']:.6f}",
        f"mst_emit_hold_seconds_count {t['emit_holds']}",
    ]
    # the device's timeline as the tick thread's waits show it, by kind of
    # served program (tracing.TickPhases)
    lines.append("# TYPE mst_program_device_seconds_total counter")
    labelled("mst_program_device_seconds_total", "program",
             t["program_device_seconds"], "{:.6f}")
    lines.append("# TYPE mst_program_dispatch_exposed_seconds_total counter")
    labelled("mst_program_dispatch_exposed_seconds_total", "program",
             t["program_dispatch_exposed_seconds"], "{:.6f}")
    lines.append("# TYPE mst_program_runs_total counter")
    labelled("mst_program_runs_total", "program", t["program_runs"])
    lines.append("# TYPE mst_program_late_total counter")
    labelled("mst_program_late_total", "program", t["program_late"])
    d = t.get("diffusion")
    if d is not None:
        # a family that generates by diffusion over blocks (diffusion.py):
        # its forwards, finished blocks and transfers, counted at the harvest
        lines += [
            "# TYPE mst_diffusion_slot_forwards_total counter",
            f"mst_diffusion_slot_forwards_total {d['slot_forwards']}",
            "# TYPE mst_diffusion_blocks_committed_total counter",
            f"mst_diffusion_blocks_committed_total {d['blocks_committed']}",
            "# TYPE mst_diffusion_tokens_transferred_total counter",
        ]
        labelled("mst_diffusion_tokens_transferred_total", "by",
                 {"rank": d["by_rank"], "confidence": d["by_confidence"]})


def _render_spec_family(lines: list, spec: dict):
    """Append the adaptive-speculation gauge family from a ``spec_stats()``
    dict: whether this generator drafts at all, how wide, and whether it
    pays (accept_rate = accepted / drafted). Never rendered as zeros on a
    non-speculating host — callers gate on ``spec is not None``."""
    lines += [
        "# TYPE mst_spec_enabled gauge",
        f'mst_spec_enabled{{mode="{spec["mode"]}"}} 1',
        "# TYPE mst_spec_window gauge",
        f"mst_spec_window {spec.get('window_max', 0)}",
        "# TYPE mst_spec_accept_rate gauge",
        f"mst_spec_accept_rate "
        f"{spec.get('accept_rate', 0.0):.4f}",
        "# TYPE mst_spec_draft_tokens_total counter",
        f"mst_spec_draft_tokens_total "
        f"{spec.get('draft_tokens', 0)}",
        "# TYPE mst_spec_accepted_tokens_total counter",
        f"mst_spec_accepted_tokens_total "
        f"{spec.get('accepted_tokens', 0)}",
        "# TYPE mst_spec_rounds_total counter",
        f"mst_spec_rounds_total {spec.get('rounds', 0)}",
        "# TYPE mst_spec_fallback_ticks_total counter",
        f"mst_spec_fallback_ticks_total "
        f"{spec.get('fallback_ticks', 0)}",
        "# TYPE mst_spec_draft_faults_total counter",
        f"mst_spec_draft_faults_total "
        f"{spec.get('draft_faults', 0)}",
    ]
    if "disabled_slots" in spec:
        # per-slot adaptive control only (tracker-backed)
        lines += [
            "# TYPE mst_spec_disabled_slots gauge",
            f"mst_spec_disabled_slots "
            f"{spec['disabled_slots']}",
            "# TYPE mst_spec_shed_events_total counter",
            f"mst_spec_shed_events_total "
            f"{spec['shed_events']}",
        ]


@dataclass
class ServingMetrics:
    # named lock (ordering: ServingMetrics.lock is taken BEFORE any engine
    # lock — render() calls the engine's locked accessors while holding it)
    lock: threading.Lock = field(
        default_factory=lambda: make_lock("ServingMetrics.lock")
    )
    requests_total: int = 0
    requests_failed: int = 0
    prompt_tokens_total: int = 0
    generation_tokens_total: int = 0
    ttft_s: _Reservoir = field(default_factory=_Reservoir)
    decode_tps: _Reservoir = field(default_factory=_Reservoir)
    # bucketed TTFT (the reservoir stays for operator-facing quantiles in
    # logs; the histogram is what aggregates across replicas and scrapes)
    ttft_hist: Histogram = field(
        default_factory=lambda: Histogram(
            LATENCY_BUCKETS_S, "ServingMetrics.ttft_hist"
        )
    )
    # zero-arg callable returning the live ContinuousBatcher (or None) —
    # a callable so model hot-swaps can never leave a stale reference
    batcher_fn: object = None
    # zero-arg callable returning the live SpeculativeGenerator (or None)
    spec_fn: object = None
    # zero-arg callable returning the host's weights.WeightStore (or None);
    # defaults to the module singleton at render time so the shared-weights
    # gauges exist even for servers built without make_server
    weight_store_fn: object = None
    # zero-arg callable returning the live prefix_store.PrefixStore (or
    # None) — callable for the same hot-swap reason as batcher_fn
    prefix_store_fn: object = None
    # zero-arg callable returning pod.PodFleet.pod_stats() (or None) —
    # None on every single-host deployment, which keeps the single-host
    # exposition byte-identical (no host labels, no pod families)
    pod_stats_fn: object = None
    # zero-arg callable returning the layer-wise KV sharing summary
    # (kv_share.py; provider.kv_share_stats()) or None when no share map
    # is configured — unset keeps the exposition free of share families
    kv_share_fn: object = None
    # zero-arg callable returning the compressed-latent KV transport
    # summary (kv_compress.py; provider.kv_compress_stats()) or None when
    # no codec is active — unset keeps compress families absent
    kv_compress_fn: object = None

    def record_request(
        self,
        *,
        prompt_tokens: int,
        generation_tokens: int,
        ttft_s: float,
        decode_tps: float,
        failed: bool = False,
    ):
        with self.lock:
            self.requests_total += 1
            if failed:
                self.requests_failed += 1
            self.prompt_tokens_total += prompt_tokens
            self.generation_tokens_total += generation_tokens
            if ttft_s > 0:
                self.ttft_s.add(ttft_s)
                self.ttft_hist.observe(ttft_s)
            if decode_tps > 0:
                self.decode_tps.add(decode_tps)

    def record_failure(self):
        with self.lock:
            self.requests_total += 1
            self.requests_failed += 1

    def render(self) -> str:
        """Prometheus text exposition."""
        with self.lock:
            lines = [
                "# TYPE mst_requests_total counter",
                f"mst_requests_total {self.requests_total}",
                "# TYPE mst_requests_failed_total counter",
                f"mst_requests_failed_total {self.requests_failed}",
                "# TYPE mst_prompt_tokens_total counter",
                f"mst_prompt_tokens_total {self.prompt_tokens_total}",
                "# TYPE mst_generation_tokens_total counter",
                f"mst_generation_tokens_total {self.generation_tokens_total}",
                "# TYPE mst_decode_tokens_per_second summary",
                f'mst_decode_tokens_per_second{{quantile="0.5"}} {self.decode_tps.percentile(50):.3f}',
                f'mst_decode_tokens_per_second{{quantile="0.95"}} {self.decode_tps.percentile(95):.3f}',
            ]
            # TTFT as a cumulative histogram (was a two-point summary):
            # bucket counts sum across replicas; quantiles never did
            Histogram.render_into(
                lines, "mst_ttft_seconds", self.ttft_hist.to_dict()
            )
            # fault-harness visibility: a fault left ARMED in a live
            # deployment (forgotten MST_FAULTS, a chaos campaign that
            # didn't disarm) must show on every scrape, as must specs
            # dropped at parse time. Lazy import + never-500, same as the
            # engine sections below.
            fmark = len(lines)
            try:
                from mlx_sharding_tpu.testing import faults as _faults

                lines += [
                    "# TYPE mst_faults_malformed_total counter",
                    f"mst_faults_malformed_total {_faults.malformed_total()}",
                    "# TYPE mst_faults_armed gauge",
                ]
                armed = _faults.armed_sites()
                if armed:
                    lines += [
                        f'mst_faults_armed{{site="{site}"}} {n}'
                        for site, n in sorted(armed.items())
                    ]
                else:
                    # a bare # TYPE with no sample is invalid exposition —
                    # the disarmed steady state is an explicit zero
                    lines.append("mst_faults_armed 0")
            except Exception:  # noqa: BLE001 — scrape must not 500
                del lines[fmark:]
            # leak-ledger health: the bounded anomaly ring keeps only the
            # newest entries, this counter keeps the true total (zero when
            # no ledger is instrumented — the production steady state)
            lmark = len(lines)
            try:
                from mlx_sharding_tpu.analysis import runtime as _rt

                led = _rt._RESOURCES
                lines += [
                    "# TYPE mst_ledger_anomalies_total counter",
                    "mst_ledger_anomalies_total "
                    f"{led.anomalies_total if led is not None else 0}",
                ]
            except Exception:  # noqa: BLE001 — scrape must not 500
                del lines[lmark:]
            # which path each packed matmul, each ragged paged-attention call,
            # each routed-expert call and each decode step's Mamba-2
            # recurrence took while its program was traced.
            # Only where the ops are loaded: a process that never imported
            # them dispatched nothing, and a scrape imports no JAX.
            for type_line, module in (
                ("# TYPE mst_quant_dispatch_total counter", "quant"),
                ("# TYPE mst_paged_attention_dispatch_total counter",
                 "paged_attention"),
                ("# TYPE mst_moe_dispatch_total counter", "moe"),
                ("# TYPE mst_ssm_dispatch_total counter", "mamba2"),
                ("# TYPE mst_kda_dispatch_total counter", "kda"),
            ):
                ops = sys.modules.get(f"mlx_sharding_tpu.ops.{module}")
                if ops is not None:
                    family = type_line.split()[2]
                    lines.append(type_line)
                    lines += [
                        f'{family}{{path="{path}"}} {n}'
                        for path, n in sorted(ops.dispatch_counts().items())
                    ]
            # any engine accessor can die mid-scrape (replica torn
            # down, pool closing); drop the whole engine section
            # cleanly rather than 500 or emit a half-rendered family
            mark = len(lines)
            spec_rendered = False
            try:
                b = self.batcher_fn() if self.batcher_fn is not None else None
                if b is not None:
                    slots, active, queued = b.stats()
                    lines += [
                        "# TYPE mst_batch_slots gauge",
                        f"mst_batch_slots {slots}",
                        "# TYPE mst_batch_slots_active gauge",
                        f"mst_batch_slots_active {active}",
                        "# TYPE mst_batch_queue_depth gauge",
                        f"mst_batch_queue_depth {queued}",
                    ]
                    pages = getattr(b, "page_stats", lambda: None)()
                    if pages is not None:
                        total, in_use, high = pages
                        lines += [
                            "# TYPE mst_kv_pool_pages gauge",
                            f"mst_kv_pool_pages {total}",
                            "# TYPE mst_kv_pool_pages_in_use gauge",
                            f"mst_kv_pool_pages_in_use {in_use}",
                            "# TYPE mst_kv_pool_pages_high_water gauge",
                            f"mst_kv_pool_pages_high_water {high}",
                        ]
                    state = getattr(b, "state_stats", lambda: None)()
                    if state is not None:
                        lines += [
                            "# TYPE mst_state_slots_in_use gauge",
                            f"mst_state_slots_in_use {state['slots_in_use']}",
                            "# TYPE mst_state_bytes gauge",
                            f"mst_state_bytes {state['bytes']}",
                            "# TYPE mst_state_resets_total counter",
                            f"mst_state_resets_total {state['resets']}",
                        ]
                    win = getattr(b, "window_stats", lambda: None)()
                    if win is not None:
                        lines += [
                            "# TYPE mst_kv_window_bytes gauge",
                            f"mst_kv_window_bytes {win['bytes']}",
                            "# TYPE mst_kv_window_rows_live gauge",
                            f"mst_kv_window_rows_live {win['rows_live']}",
                            "# TYPE mst_kv_ring_wraps_total counter",
                            f"mst_kv_ring_wraps_total {win['ring_wraps']}",
                        ]
                    if pages is not None and getattr(b, "overcommit", False):
                        lines += [
                            "# TYPE mst_preemptions_total counter",
                            f"mst_preemptions_total {b.preemptions}",
                        ]
                    spill = getattr(b, "spill_stats", lambda: None)()
                    if spill is not None:
                        # KV migration story: how often memory pressure / drain
                        # moved page blocks instead of discarding them, and how
                        # much host DRAM the spill tier is holding
                        lines += [
                            "# TYPE mst_kv_spill_enabled gauge",
                            f"mst_kv_spill_enabled {int(bool(spill['enabled']))}",
                            "# TYPE mst_kv_spill_total counter",
                            f"mst_kv_spill_total {spill['spills']}",
                            "# TYPE mst_kv_spill_hits_total counter",
                            f"mst_kv_spill_hits_total {spill['spill_hits']}",
                            "# TYPE mst_kv_spill_fallbacks_total counter",
                            f"mst_kv_spill_fallbacks_total "
                            f"{spill['spill_fallbacks']}",
                            "# TYPE mst_kv_spill_evictions_total counter",
                            f"mst_kv_spill_evictions_total {spill['evictions']}",
                            "# TYPE mst_kv_spill_bytes gauge",
                            f"mst_kv_spill_bytes {spill['bytes_in_use']}",
                            "# TYPE mst_kv_spill_budget_bytes gauge",
                            f"mst_kv_spill_budget_bytes {spill['budget_bytes']}",
                            "# TYPE mst_kv_migration_out_total counter",
                            f"mst_kv_migration_out_total "
                            f"{spill['migrations_out']}",
                            "# TYPE mst_kv_migration_in_total counter",
                            f"mst_kv_migration_in_total {spill['migrations_in']}",
                            "# TYPE mst_kv_reprefill_tokens_total counter",
                            f"mst_kv_reprefill_tokens_total "
                            f"{spill['reprefill_tokens']}",
                            # proactive residency: cold-policy activity, tier
                            # lookup quality, and the overlapped-vs-demand
                            # resume split (.get: ReplicaSet aggregation may
                            # predate these keys)
                            "# TYPE mst_kv_spill_cold_total counter",
                            f"mst_kv_spill_cold_total "
                            f"{spill.get('cold_spills', 0)}",
                            "# TYPE mst_kv_spill_wakes_total counter",
                            f"mst_kv_spill_wakes_total "
                            f"{spill.get('cold_wakes', 0)}",
                            "# TYPE mst_kv_spill_parked gauge",
                            f"mst_kv_spill_parked {spill.get('parked', 0)}",
                            "# TYPE mst_kv_spill_hit_rate gauge",
                            f"mst_kv_spill_hit_rate "
                            f"{spill.get('hit_rate', 0.0):.4f}",
                            "# TYPE mst_kv_spill_rejects_total counter",
                            f'mst_kv_spill_rejects_total{{reason="oversize"}} '
                            f"{spill.get('rejects_oversize', 0)}",
                            f'mst_kv_spill_rejects_total{{reason="closed"}} '
                            f"{spill.get('rejects_closed', 0)}",
                            "# TYPE mst_kv_prefetch_enabled gauge",
                            f"mst_kv_prefetch_enabled "
                            f"{int(bool(spill.get('prefetch_enabled', False)))}",
                            "# TYPE mst_kv_prefetch_total counter",
                            f"mst_kv_prefetch_total "
                            f"{spill.get('prefetches', 0)}",
                            "# TYPE mst_kv_prefetch_hits_total counter",
                            f"mst_kv_prefetch_hits_total "
                            f"{spill.get('prefetch_hits', 0)}",
                            "# TYPE mst_kv_prefetch_demand_total counter",
                            f"mst_kv_prefetch_demand_total "
                            f"{spill.get('demand_imports', 0)}",
                            "# TYPE mst_kv_prefetch_faults_total counter",
                            f"mst_kv_prefetch_faults_total "
                            f"{spill.get('prefetch_faults', 0)}",
                        ]
                        if "migrated_streams" in spill:
                            # ReplicaSet-level: streams re-placed across
                            # replicas after a drain or mid-stream crash
                            lines += [
                                "# TYPE mst_kv_migration_streams_total counter",
                                f"mst_kv_migration_streams_total "
                                f"{spill['migrated_streams']}",
                            ]
                    kv = getattr(b, "kv_read_stats", lambda: None)()
                    if kv is not None:
                        path, _last_tick, total_bytes, claimed_bytes = kv
                        lines += [
                            # 1 = ragged in-place paged attention, 0 = the
                            # gather/scatter path — which kernel decode is on
                            "# TYPE mst_paged_attention_ragged gauge",
                            f"mst_paged_attention_ragged {int(path == 'ragged')}",
                            "# TYPE mst_kv_bytes_read_total counter",
                            f"mst_kv_bytes_read_total {total_bytes}",
                            "# TYPE mst_kv_bytes_claimed_total counter",
                            f"mst_kv_bytes_claimed_total {claimed_bytes}",
                        ]
                    hbm = getattr(b, "hbm_bytes_per_token_stats", lambda: None)()
                    if hbm is not None:
                        lines += [
                            "# TYPE mst_decode_hbm_bytes_per_token gauge",
                            'mst_decode_hbm_bytes_per_token{kind="weights"} '
                            f"{hbm['weights']:.1f}",
                            'mst_decode_hbm_bytes_per_token{kind="kv"} '
                            f"{hbm['kv']:.1f}",
                        ]
                    lat = getattr(b, "latency_stats", lambda: None)()
                    if lat is not None:
                        # scheduler-side per-token latency: inter-token gaps
                        # from the emit path, queue wait from submit→slot.
                        # Histograms so ReplicaSet/Disagg merges stay exact.
                        Histogram.render_into(
                            lines, "mst_itl_seconds", lat.get("itl")
                        )
                        Histogram.render_into(
                            lines, "mst_queue_wait_seconds",
                            lat.get("queue_wait")
                        )
                        Histogram.render_into(
                            lines, "mst_join_seconds", lat.get("join")
                        )
                    phases = getattr(b, "tick_phase_stats", lambda: None)()
                    if phases is not None:
                        _render_tick_phases(lines, phases)
                    spec = getattr(b, "spec_stats", lambda: None)()
                    if spec is not None:
                        _render_spec_family(lines, spec)
                        spec_rendered = True
                    res = getattr(b, "resilience_stats", lambda: None)()
                    if res is not None:
                        lines += [
                            "# TYPE mst_requests_timeout_total counter",
                            f"mst_requests_timeout_total {res['timeouts']}",
                            # shed = rejected before any engine work was spent:
                            # queue_full at admission (429), deadline while queued
                            "# TYPE mst_requests_shed_total counter",
                            f'mst_requests_shed_total{{reason="queue_full"}} '
                            f"{res['shed_queue_full']}",
                            f'mst_requests_shed_total{{reason="deadline"}} '
                            f"{res['shed_deadline']}",
                            "# TYPE mst_scheduler_thread_live gauge",
                            "mst_scheduler_thread_live "
                            f"{int(bool(res['scheduler_thread_live']))}",
                        ]
                        if res.get("max_queue") is not None:
                            lines += [
                                "# TYPE mst_max_queue gauge",
                                f"mst_max_queue {res['max_queue']}",
                            ]
                    health = getattr(b, "health", lambda: None)()
                    if health is not None and "replicas_total" in health:
                        lines += [
                            "# TYPE mst_replicas_total gauge",
                            f"mst_replicas_total {health['replicas_total']}",
                            "# TYPE mst_replicas_live gauge",
                            f"mst_replicas_live {health['replicas_live']}",
                        ]
                        lines.append("# TYPE mst_replica_breaker_open gauge")
                        for rep in health["replicas"]:
                            lines += [
                                f'mst_replica_breaker_open{{replica="{rep["replica"]}"}} '
                                f"{int(rep['breaker'] != 'closed')}",
                            ]
                        lines.append("# TYPE mst_replica_failures_total counter")
                        for rep in health["replicas"]:
                            lines += [
                                f'mst_replica_failures_total{{replica="{rep["replica"]}"}} '
                                f"{rep['failures']}",
                            ]
                    # per-replica routing load + fleet elasticity (replicas.py /
                    # fleet.py); breaker_state: 0 closed, 1 half-open, 2 open
                    per_rep = getattr(b, "replica_stats", lambda: None)()
                    if per_rep is not None:
                        # disaggregated pools tag entries with a role; indices
                        # repeat across pools, so the role label is what keeps
                        # the gauge lines distinct (monolithic sets stay
                        # unlabeled — role is None there)
                        def _rl(rep):
                            role = rep.get("role")
                            return (
                                f'replica="{rep["replica"]}",role="{role}"'
                                if role else f'replica="{rep["replica"]}"'
                            )
                        lines.append("# TYPE mst_replica_inflight gauge")
                        for rep in per_rep:
                            lines.append(
                                f"mst_replica_inflight{{{_rl(rep)}}} "
                                f"{rep['inflight']}"
                            )
                        lines.append("# TYPE mst_replica_queue_depth gauge")
                        for rep in per_rep:
                            lines.append(
                                f"mst_replica_queue_depth{{{_rl(rep)}}} "
                                f"{rep['queue_depth']}"
                            )
                        lines.append("# TYPE mst_replica_breaker_state gauge")
                        for rep in per_rep:
                            lines.append(
                                f"mst_replica_breaker_state{{{_rl(rep)}}} "
                                f"{rep['breaker_state']}"
                            )
                        # 1 = this replica aliases the host's resident weight
                        # tree (weights.WeightStore), 0 = private upload
                        lines.append("# TYPE mst_replica_weights_shared gauge")
                        for rep in per_rep:
                            lines.append(
                                f"mst_replica_weights_shared{{{_rl(rep)}}} "
                                f"{int(bool(rep.get('weights_shared')))}"
                            )
                    fleet = getattr(b, "fleet_stats", lambda: None)()
                    if fleet is not None:
                        lines += [
                            "# TYPE mst_fleet_size gauge",
                            f"mst_fleet_size {fleet['size']}",
                        ]
                        for pool in fleet.get("pools", []):
                            # per-role pool sizes under the disagg coordinator
                            if pool.get("role"):
                                lines.append(
                                    f'mst_fleet_size{{role="{pool["role"]}"}} '
                                    f"{pool['size']}"
                                )
                        lines += [
                            "# TYPE mst_autoscale_events_total counter",
                        ]
                        for kind in sorted(fleet.get("autoscale_events", {})):
                            lines.append(
                                f'mst_autoscale_events_total{{kind="{kind}"}} '
                                f"{fleet['autoscale_events'][kind]}"
                            )
                        if "sticky_hits" in fleet:
                            lines += [
                                "# TYPE mst_route_sticky_hits_total counter",
                                f"mst_route_sticky_hits_total "
                                f"{fleet['sticky_hits']}",
                                "# TYPE mst_route_affinity_hits_total counter",
                                f"mst_route_affinity_hits_total "
                                f"{fleet['affinity_hits']}",
                            ]
                        if "store_hits" in fleet:
                            # routed to the replica already holding the prefix
                            # resident in the fleet-wide store
                            lines += [
                                "# TYPE mst_route_store_hits_total counter",
                                f"mst_route_store_hits_total "
                                f"{fleet['store_hits']}",
                            ]
                    hand = getattr(b, "handoff_stats", lambda: None)()
                    if hand is not None:
                        # disaggregated serving: prefill→decode KV handoffs —
                        # volume, shipped bytes, DMA+control latency, and how
                        # often the degradation ladder fired (by kind)
                        lines += [
                            "# TYPE mst_disagg_handoff_total counter",
                            f"mst_disagg_handoff_total {hand['handoffs']}",
                            "# TYPE mst_disagg_handoff_bytes_total counter",
                            f"mst_disagg_handoff_bytes_total "
                            f"{hand['bytes_total']}",
                        ]
                        if hand.get("ms_hist"):
                            # handoff latency as a histogram (bucket counts
                            # aggregate across coordinators and scrapes)
                            Histogram.render_into(
                                lines, "mst_disagg_handoff_ms", hand["ms_hist"]
                            )
                        else:
                            # a pre-histogram aggregation: keep the summary
                            lines += [
                                "# TYPE mst_disagg_handoff_ms summary",
                                'mst_disagg_handoff_ms{quantile="0.5"} '
                                f"{hand.get('ms_p50') or 0.0:.3f}",
                                'mst_disagg_handoff_ms{quantile="0.99"} '
                                f"{hand.get('ms_p99') or 0.0:.3f}",
                            ]
                        lines += [
                            "# TYPE mst_disagg_fallbacks_total counter",
                        ]
                        for kind in sorted(hand.get("fallbacks", {})):
                            lines.append(
                                f'mst_disagg_fallbacks_total{{kind="{kind}"}} '
                                f"{hand['fallbacks'][kind]}"
                            )
                        if "store_skips" in hand:
                            # full-prefix store hits that skipped the prefill
                            # pool entirely (no phase-1 dispatch, no handoff)
                            lines += [
                                "# TYPE mst_disagg_store_skips_total counter",
                                f"mst_disagg_store_skips_total "
                                f"{hand['store_skips']}",
                            ]
                    bro = getattr(b, "brownout", None)
                    if bro is not None:
                        lines += [
                            "# TYPE mst_brownout_level gauge",
                            f"mst_brownout_level {bro.level()}",
                        ]
                    prefix = getattr(b, "prefix_stats", lambda: None)()
                    if prefix is not None:
                        queries, hits, reused, evictions, cached = prefix
                        lines += [
                            "# TYPE mst_prefix_cache_queries_total counter",
                            f"mst_prefix_cache_queries_total {queries}",
                            "# TYPE mst_prefix_cache_hits_total counter",
                            f"mst_prefix_cache_hits_total {hits}",
                            "# TYPE mst_prefix_cache_tokens_reused_total counter",
                            f"mst_prefix_cache_tokens_reused_total {reused}",
                            "# TYPE mst_prefix_cache_evictions_total counter",
                            f"mst_prefix_cache_evictions_total {evictions}",
                            "# TYPE mst_prefix_cache_pages gauge",
                            f"mst_prefix_cache_pages {cached}",
                        ]
            except Exception:  # noqa: BLE001 — scrapes must never 500
                del lines[mark:]
            spec = (
                self.spec_fn()
                if self.spec_fn is not None and not spec_rendered
                else None
            )
            if spec is not None and hasattr(spec, "spec_stats"):
                # new-protocol generator (n-gram single-stream) hosted
                # without a batcher: same family, same never-500 contract
                smark = len(lines)
                try:
                    st = spec.spec_stats()
                    if st is not None:
                        _render_spec_family(lines, st)
                except Exception:  # noqa: BLE001 — scrapes must never 500
                    del lines[smark:]
            elif spec is not None:
                # accepted/round ∈ [1, spec_k]: the draft-quality dial the
                # operator watches to size --spec-k
                lines += [
                    "# TYPE mst_spec_rounds_total counter",
                    f"mst_spec_rounds_total {spec.rounds}",
                    "# TYPE mst_spec_tokens_accepted_total counter",
                    f"mst_spec_tokens_accepted_total {spec.accepted_tokens}",
                ]
                rounds = max(1, spec.rounds)
                lines += [
                    # accepted/rounds collapsing toward 1.0 with fallbacks
                    # climbing = the draft is stale or mismatched
                    "# TYPE mst_spec_acceptance_rate gauge",
                    f"mst_spec_acceptance_rate "
                    f"{spec.accepted_tokens / rounds:.4f}",
                    "# TYPE mst_spec_fallback_ticks_total counter",
                    f"mst_spec_fallback_ticks_total "
                    f"{getattr(spec, 'fallback_ticks', 0)}",
                    "# TYPE mst_spec_tokens_replayed_total counter",
                    f"mst_spec_tokens_replayed_total "
                    f"{getattr(spec, 'replayed_tokens', 0)}",
                ]
            # cross-replica shared weights (weights.WeightStore): resident
            # tree count, engine refs aliasing them, and resident bytes —
            # with sharing on, bytes stays ~W while refs tracks fleet size;
            # always emitted (zeros mean every replica owns a private copy)
            try:
                if self.weight_store_fn is not None:
                    ws = self.weight_store_fn()
                else:
                    from mlx_sharding_tpu.weights import weight_store

                    ws = weight_store()
                store = ws.stats() if ws is not None else None
            except Exception:  # noqa: BLE001 — scrapes must never 500
                store = None
            if store is not None:
                lines += [
                    "# TYPE mst_weight_store_trees gauge",
                    f"mst_weight_store_trees {store['trees']}",
                    "# TYPE mst_weight_store_refs gauge",
                    f"mst_weight_store_refs {store['refs']}",
                    "# TYPE mst_weight_store_bytes gauge",
                    f"mst_weight_store_bytes {store['bytes']}",
                ]
            # fleet-wide content-addressed prefix KV store (prefix_store.py):
            # residency by tier, lookup quality, COW fork volume, insertion
            # damping, and eviction churn by reason
            try:
                ps = (
                    self.prefix_store_fn()
                    if self.prefix_store_fn is not None
                    else None
                )
                pstats = ps.stats() if ps is not None else None
            except Exception:  # noqa: BLE001 — scrapes must never 500
                pstats = None
            if pstats is not None:
                lines += [
                    "# TYPE mst_prefix_store_blocks gauge",
                    f'mst_prefix_store_blocks{{tier="device"}} '
                    f"{pstats['device_blocks']}",
                    f'mst_prefix_store_blocks{{tier="host"}} '
                    f"{pstats['host_blocks']}",
                    "# TYPE mst_prefix_store_bytes gauge",
                    f'mst_prefix_store_bytes{{tier="device"}} '
                    f"{pstats['device_bytes']}",
                    f'mst_prefix_store_bytes{{tier="host"}} '
                    f"{pstats['host_bytes']}",
                    "# TYPE mst_prefix_store_budget_bytes gauge",
                    f"mst_prefix_store_budget_bytes "
                    f"{pstats['host_budget_bytes']}",
                    "# TYPE mst_prefix_store_hits_total counter",
                    f'mst_prefix_store_hits_total{{tier="device"}} '
                    f"{pstats['hits_device']}",
                    f'mst_prefix_store_hits_total{{tier="host"}} '
                    f"{pstats['hits_host']}",
                    "# TYPE mst_prefix_store_misses_total counter",
                    f"mst_prefix_store_misses_total {pstats['misses']}",
                    "# TYPE mst_prefix_store_hit_rate gauge",
                    f"mst_prefix_store_hit_rate {pstats['hit_rate']:.4f}",
                    "# TYPE mst_prefix_store_tokens_reused_total counter",
                    f"mst_prefix_store_tokens_reused_total "
                    f"{pstats['tokens_reused']}",
                    "# TYPE mst_prefix_store_cow_forks_total counter",
                    f"mst_prefix_store_cow_forks_total "
                    f"{pstats['cow_forks']}",
                    "# TYPE mst_prefix_store_inserts_total counter",
                    f"mst_prefix_store_inserts_total {pstats['inserts']}",
                    "# TYPE mst_prefix_store_inserts_damped_total counter",
                    f"mst_prefix_store_inserts_damped_total "
                    f"{pstats['inserts_damped']}",
                    # 1 while brownout level >= 1 holds insertion closed
                    "# TYPE mst_prefix_store_inserts_paused gauge",
                    f"mst_prefix_store_inserts_paused "
                    f"{int(bool(pstats['inserts_paused']))}",
                    "# TYPE mst_prefix_store_demotions_total counter",
                    f"mst_prefix_store_demotions_total "
                    f"{pstats['demotions']}",
                    "# TYPE mst_prefix_store_demote_drops_total counter",
                    f"mst_prefix_store_demote_drops_total "
                    f"{pstats['demote_drops']}",
                    "# TYPE mst_prefix_store_evictions_total counter",
                    f'mst_prefix_store_evictions_total{{reason="budget"}} '
                    f"{pstats['evictions_budget']}",
                    f'mst_prefix_store_evictions_total{{reason="oversize"}} '
                    f"{pstats['evictions_oversize']}",
                    f'mst_prefix_store_evictions_total{{reason="reset"}} '
                    f"{pstats['evictions_reset']}",
                    "# TYPE mst_prefix_store_imports_total counter",
                    f'mst_prefix_store_imports_total{{kind="staged"}} '
                    f"{pstats['imports_staged']}",
                    f'mst_prefix_store_imports_total{{kind="demand"}} '
                    f"{pstats['imports_demand']}",
                    "# TYPE mst_prefix_store_faults_total counter",
                    f'mst_prefix_store_faults_total{{kind="lookup"}} '
                    f"{pstats['lookup_faults']}",
                    f'mst_prefix_store_faults_total{{kind="import"}} '
                    f"{pstats['import_faults']}",
                ]
            # layer-wise KV sharing (kv_share.py, KVSharer): share-group
            # geometry and the pool bytes the calibrated map removed —
            # only when a share map is configured (kv_share_fn unset keeps
            # the exposition free of the families)
            try:
                share = (
                    self.kv_share_fn()
                    if self.kv_share_fn is not None
                    else None
                )
            except Exception:  # noqa: BLE001 — scrapes must never 500
                share = None
            if share is not None:
                lines += [
                    "# TYPE mst_kv_share_enabled gauge",
                    f"mst_kv_share_enabled "
                    f"{int(bool(share.get('enabled')))}",
                    "# TYPE mst_kv_share_groups gauge",
                    f"mst_kv_share_groups {share.get('groups', 0)}",
                    "# TYPE mst_kv_share_bytes_saved gauge",
                    f"mst_kv_share_bytes_saved "
                    f"{share.get('bytes_saved', 0)}",
                ]
            # compressed-latent KV transport (kv_compress.py): blocks and
            # bytes moved compressed vs raw plus the counted degradation
            # legs — only when a codec is active (MLA-native or a loaded
            # low-rank map; kv_compress_fn returning None keeps the
            # exposition free of the families)
            try:
                comp = (
                    self.kv_compress_fn()
                    if self.kv_compress_fn is not None
                    else None
                )
            except Exception:  # noqa: BLE001 — scrapes must never 500
                comp = None
            if comp is not None:
                mode = str(comp.get("mode", "latent"))
                lines += [
                    "# TYPE mst_kv_compress_enabled gauge",
                    f'mst_kv_compress_enabled{{mode="{mode}"}} 1',
                    "# TYPE mst_kv_compress_blocks_total counter",
                    f'mst_kv_compress_blocks_total{{op="compress"}} '
                    f"{comp.get('blocks_compressed', 0)}",
                    f'mst_kv_compress_blocks_total{{op="reconstruct"}} '
                    f"{comp.get('blocks_reconstructed', 0)}",
                    "# TYPE mst_kv_compress_faults_total counter",
                    f'mst_kv_compress_faults_total{{op="encode"}} '
                    f"{comp.get('compress_faults', 0)}",
                    f'mst_kv_compress_faults_total{{op="decode"}} '
                    f"{comp.get('reconstruct_faults', 0)}",
                    "# TYPE mst_kv_compress_bytes_total counter",
                    f'mst_kv_compress_bytes_total{{kind="raw"}} '
                    f"{comp.get('bytes_raw_total', 0)}",
                    f'mst_kv_compress_bytes_total{{kind="wire"}} '
                    f"{comp.get('bytes_wire_total', 0)}",
                    "# TYPE mst_kv_compress_bytes_saved gauge",
                    f"mst_kv_compress_bytes_saved "
                    f"{comp.get('bytes_saved_total', 0)}",
                ]
            # pod fleet (pod.py): host-labeled size/weights/heartbeat from
            # the gossip view plus handoff and autoscaler counters — only
            # on --pod deployments (pod_stats_fn unset keeps single-host
            # exposition label-free); the gossip snapshot can race a host
            # death mid-render, so the whole section drops on any error
            pmark = len(lines)
            try:
                pod = (
                    self.pod_stats_fn()
                    if self.pod_stats_fn is not None
                    else None
                )
                if pod is not None:
                    lines += [
                        "# TYPE mst_pod_hosts gauge",
                        f"mst_pod_hosts {len(pod['hosts'])}",
                        "# TYPE mst_pod_host_deaths_total counter",
                        f"mst_pod_host_deaths_total "
                        f"{pod['autoscaler']['deaths_detected']}",
                    ]
                    hosts = sorted(pod["hosts"])
                    # one # TYPE per family (invalid exposition otherwise),
                    # then every host's sample; mst_fleet_size and the
                    # mst_weight_store_* families were already declared by
                    # the single-host sections above, so the host-labeled
                    # samples ride the existing declarations
                    lines.append("# TYPE mst_pod_host_alive gauge")
                    lines += [
                        f'mst_pod_host_alive{{host="{h}"}} '
                        f"{int(bool(pod['hosts'][h].get('alive')))}"
                        for h in hosts
                    ]
                    ages = [
                        (h, pod["hosts"][h].get("heartbeat_age_s"))
                        for h in hosts
                    ]
                    if any(a is not None for _, a in ages):
                        lines.append(
                            "# TYPE mst_pod_heartbeat_age_seconds gauge"
                        )
                        lines += [
                            f'mst_pod_heartbeat_age_seconds{{host="{h}"}} '
                            f"{a:.3f}"
                            for h, a in ages if a is not None
                        ]
                    lines += [
                        f'mst_fleet_size{{host="{h}"}} '
                        f"{(pod['hosts'][h].get('fleet') or {}).get('live', 0)}"
                        for h in hosts if pod["hosts"][h].get("fleet")
                    ]
                    for fam, key in (("trees", "trees"), ("refs", "refs"),
                                     ("bytes", "bytes")):
                        lines += [
                            f'mst_weight_store_{fam}{{host="{h}"}} '
                            f"{(pod['hosts'][h].get('weights') or {}).get(key, 0)}"
                            for h in hosts if pod["hosts"][h].get("weights")
                        ]
                    ho = pod["handoff"]
                    lines += [
                        "# TYPE mst_pod_handoff_total counter",
                        f"mst_pod_handoff_total {ho['shipped']}",
                        "# TYPE mst_pod_handoff_bytes_total counter",
                        f"mst_pod_handoff_bytes_total {ho['bytes_shipped']}",
                        "# TYPE mst_pod_handoff_received_total counter",
                        f"mst_pod_handoff_received_total {ho['received']}",
                        "# TYPE mst_pod_handoff_fallbacks_total counter",
                    ]
                    fb = ho.get("fallbacks") or {}
                    if fb:
                        lines += [
                            f'mst_pod_handoff_fallbacks_total'
                            f'{{kind="{kind}"}} {fb[kind]}'
                            for kind in sorted(fb)
                        ]
                    else:
                        # a bare # TYPE with no samples is invalid
                        # exposition — emit the zero explicitly
                        lines.append("mst_pod_handoff_fallbacks_total 0")
                    if ho.get("ms_p50") is not None:
                        lines += [
                            "# TYPE mst_pod_handoff_ms summary",
                            f'mst_pod_handoff_ms{{quantile="0.5"}} '
                            f"{ho['ms_p50']:.3f}",
                            f'mst_pod_handoff_ms{{quantile="0.99"}} '
                            f"{ho['ms_p99']:.3f}",
                        ]
                    # pod-federated prefix store (PodPrefixFederation):
                    # gossiped inventory size, remote-hit fetch traffic,
                    # and the by-kind degradations to plain prefill — only
                    # when the pod federates a store
                    pp = pod.get("prefix")
                    if pp is not None:
                        lines += [
                            "# TYPE mst_prefix_pod_inventory_keys gauge",
                            f"mst_prefix_pod_inventory_keys "
                            f"{pp.get('inventory_keys', 0)}",
                            "# TYPE mst_prefix_pod_hits_total counter",
                            f"mst_prefix_pod_hits_total "
                            f"{pp.get('hits', 0)}",
                            "# TYPE mst_prefix_pod_fetches_total counter",
                            f"mst_prefix_pod_fetches_total "
                            f"{pp.get('fetches', 0)}",
                            "# TYPE mst_prefix_pod_fetch_bytes_total "
                            "counter",
                            f"mst_prefix_pod_fetch_bytes_total "
                            f"{pp.get('fetch_bytes', 0)}",
                            "# TYPE mst_prefix_pod_fallbacks_total counter",
                        ]
                        pfb = pp.get("fallbacks") or {}
                        if pfb:
                            lines += [
                                f'mst_prefix_pod_fallbacks_total'
                                f'{{kind="{kind}"}} {pfb[kind]}'
                                for kind in sorted(pfb)
                            ]
                        else:
                            # a bare # TYPE with no samples is invalid
                            # exposition — emit the zero explicitly
                            lines.append("mst_prefix_pod_fallbacks_total 0")
                        if pp.get("fetch_ms_p50") is not None:
                            lines += [
                                "# TYPE mst_prefix_pod_fetch_ms summary",
                                f'mst_prefix_pod_fetch_ms{{quantile="0.5"}} '
                                f"{pp['fetch_ms_p50']:.3f}",
                                f'mst_prefix_pod_fetch_ms{{quantile="0.99"}} '
                                f"{pp['fetch_ms_p99']:.3f}",
                            ]
            except Exception:  # noqa: BLE001 — scrapes must never 500
                del lines[pmark:]
        return "\n".join(_finalize(lines)) + "\n"


# explicit HELP strings for the families whose meaning is not readable off
# the name; everything else gets a generated one-liner (coverage contract:
# EVERY emitted family carries # HELP and # TYPE — test_metrics_help_type)
_HELP = {
    "mst_kv_bytes_read_total":
        "K/V bytes the decode steps' attention reads, analytic: the "
        "page-rounded rows the live slots hold (ragged) or every slot's "
        "whole table row (gather).",
    "mst_kv_bytes_claimed_total":
        "K/V bytes of every page in the live slots' table rows, claimed "
        "and scratch entries included: what a walk that names its table "
        "row would read. read / claimed is the share the ragged walk "
        "fetches.",
    "mst_requests_total": "Requests served (including failures).",
    "mst_requests_failed_total": "Requests that ended in an error.",
    "mst_ttft_seconds": "Time to first token, seconds (histogram).",
    "mst_itl_seconds":
        "Inter-token latency from the scheduler emit path, seconds.",
    "mst_queue_wait_seconds":
        "Admission queue wait, submit to slot assignment, seconds.",
    "mst_disagg_handoff_ms":
        "Prefill-to-decode KV handoff latency, milliseconds.",
    "mst_decode_tokens_per_second": "Per-request decode rate summary.",
    "mst_tick_phase_seconds_total":
        "Scheduler tick thread wall time by phase, seconds; the phases "
        "partition the thread's time (harvest_wait = blocked on the device).",
    "mst_device_empty_seconds_total":
        "The part of each tick phase's seconds with no served program "
        "dispatched and unread on the device: host time the chip idles for.",
    "mst_join_seconds":
        "Slot assignment to the slot decoding (last prefill chunk's first "
        "token, or a block import's end), seconds; one a join that got there.",
    "mst_tick_phase_total": "Times the tick entered each phase.",
    "mst_ticks_total": "Scheduler loop iterations.",
    "mst_decode_blocks_dispatched_total": "Plain decode blocks dispatched.",
    "mst_decode_blocks_harvested_total":
        "Plain decode blocks whose tokens reached the host.",
    "mst_decode_positions_computed_total":
        "Token positions dispatched decode blocks compute (steps x rows).",
    "mst_decode_tokens_emitted_total":
        "Decode-block tokens delivered to their streams.",
    "mst_decode_tokens_dropped_total":
        "Computed positions no stream received: slot_finished (past the "
        "last token of a stream that reached max_tokens), cancelled (the "
        "slot was given up earlier: consumer gone, preempted), "
        "abandoned_block (futures dropped) or, where the family generates by "
        "diffusion over blocks, denoise (a forward's rows that were not a "
        "finished block's new tokens).",
    "mst_diffusion_slot_forwards_total":
        "Diffusion over blocks: forwards x live slots of harvested decode "
        "programs. A slot-forward is one lane (a block of positions, "
        "denoising) or two (a finished block's commit and the next block's "
        "first denoise behind it); one a slot stood still for (it held a "
        "finished block at a narrow forward) is counted too.",
    "mst_diffusion_blocks_committed_total":
        "Diffusion over blocks: blocks finished and handed to their "
        "streams, each at the forward that transferred its last masked "
        "position; its K/V is stored by its slot's next wide forward, a "
        "stream's last block's never. Two slot-forwards a block at 2 "
        "denoising steps under a rank order.",
    "mst_diffusion_tokens_transferred_total":
        "Diffusion over blocks: masked positions that took their sampled "
        "token, by what chose them: rank (the strategy's n a forward) or "
        "confidence (above the threshold).",
    "mst_pipeline_drains_total":
        "Pipeline drains (a block was in flight at a quiesce), by call site.",
    "mst_decode_blocks_total":
        "Plain decode blocks dispatched, by what their sampler had to run "
        "for the live requests: greedy (argmax only), draw (a sampled row "
        "at top_p = 1), nucleus (a sampled row at top_p < 1: the sort).",
    "mst_join_programs_total":
        "Dispatches joins made between their drain and the slot decoding, "
        "by program: claim (the slot claim), chunk (a prefill chunk, the "
        "draft's too), finish (the first token), other (a block import's "
        "resume). Over mst_join_seconds_count: 3 for a one-chunk join.",
    "mst_join_first_reads_total":
        "Joins by where the host's read of their first token fell: "
        "behind_block (the decode block behind the last chunk was dispatched "
        "first: the device went from chunk to block with no gap) or "
        "before_block (the host had to act on the token first: a "
        "prefill_only request, a speculating batcher, growth that might "
        "preempt, the sync tick).",
    "mst_emit_held_total":
        "Queue items (tokens, stream ends, errors) of a tick that drained "
        "the pipeline for a joiner, handed to their streams late, by what "
        "let them go: chunk (the joiner's prefill chunk was dispatched), "
        "tick_end (the tick dispatched none), fail (a scheduler failure).",
    "mst_emit_hold_seconds":
        "First deferred item to the flush, seconds; one observation a hold: "
        "what the deferral adds to a token's latency.",
    "mst_program_device_seconds_total":
        "Seconds the device had each kind of served program (block: a decode "
        "block; chunk: a prefill chunk, with the first-token program behind a "
        "join's last; other: a speculative round, a draft's programs, a block "
        "import's resume): the blocking read that learned its end, less the "
        "later of its dispatch call and the end before it. An upper bound by "
        "mst_program_dispatch_exposed_seconds_total.",
    "mst_program_dispatch_exposed_seconds_total":
        "The part of each kind's dispatch calls (call to return) that lay "
        "after the end of the program before: a dispatch made with the "
        "device empty, which it waits for.",
    "mst_program_runs_total":
        "Served programs whose end the tick thread learned, by kind.",
    "mst_program_late_total":
        "Of mst_program_runs_total, those found ended already when asked: "
        "their end, and so their seconds, are an upper bound.",
    "mst_state_slots_in_use":
        "Slots whose recurrent state (Mamba-2 SSM state and convolution "
        "tail) belongs to an admitted request.",
    "mst_state_bytes": "Bytes of the per-slot recurrent state pool.",
    "mst_state_resets_total":
        "First prefill chunks dispatched: each starts its slot's recurrent "
        "state from zero, inside the chunk's program.",
    "mst_kv_window_bytes":
        "Bytes of the window layers' per-slot K/V rings: window + one "
        "prefill chunk + one page of rows a slot and layer, whatever the "
        "contexts hold.",
    "mst_kv_window_rows_live":
        "Rows inside the windows of the slots in use: per slot min(positions, "
        "sliding_window), times the window layers.",
    "mst_kv_ring_wraps_total":
        "Ring pages overwritten: pages a slot opened past its ring's first "
        "lap (one count stands for every window layer's page).",
    "mst_quant_dispatch_total":
        "Packed 4-bit matmuls by the path ops/quant chose, one count per "
        "traced call: matmul is the Pallas kernel; xla "
        "dequantizes the whole weight in HBM every step (0 on a chip).",
    "mst_paged_attention_dispatch_total":
        "Ragged paged-attention calls by the path ops/paged_attention "
        "chose, one count per traced call: kernel walks each slot's live "
        "pages in place; xla gathers every slot's whole table row (0 on a "
        "chip unless a layer has a softcap or a window).",
    "mst_moe_dispatch_total":
        "Routed-expert calls by the path ops/moe chose, one count per traced "
        "call: kernel is the expert-indexed 4-bit kernel on a decode step's "
        "rows, grouped the same kernel on a chunk's rows sorted by expert "
        "(each expert multiplies the rows that picked it), dense_kernel "
        "walks the distinct held experts a decode step's rows picked as one "
        "expert-indexed kernel over dense bf16 stacks (a resident range, "
        "expert parallelism, on a TPU), scan walks them as a loop with all "
        "rows against each (a chunk's rows there, packed or float32 stacks, "
        "off the chip); gather_packed and gather copy every pick's "
        "whole expert out of the stacks first (0 on a chip where the decode "
        "step is packed and inside the kernel's contract).",
    "mst_ssm_dispatch_total":
        "Decode steps' Mamba-2 recurrences by the path ops/mamba2 chose, one "
        "count per traced call: kernel updates the layer's rows of the state "
        "pool where they lie in one pass; xla slices them out, passes over "
        "them twice and writes them back (0 on a chip).",
    "mst_kda_dispatch_total":
        "Gated delta-rule recurrences by the path ops/kda chose, one count "
        "per traced call. A decode step: kernel updates the layer's rows of "
        "the state pool where they lie in one pass; xla slices them out, "
        "passes over them for k^T S, again for the update and the output, and "
        "writes them back (0 on a chip). A prefill chunk's chunked form: "
        "chunk_kernel runs each block of 64 positions as one Pallas pass in "
        "VMEM (pairwise sums, the unit lower triangular system by "
        "substitution, the products with the carried state); chunk_xla makes "
        "them float32 array operations and a triangular-solve custom call "
        "(0 on a chip).",
    "mst_faults_armed":
        "Currently armed fault-injection sites (should be 0 in prod).",
    "mst_faults_malformed_total":
        "MST_FAULTS entries dropped as malformed at parse time.",
    "mst_ledger_anomalies_total":
        "Resource-ledger anomalies (double acquire/release); the log is "
        "a bounded ring but this counter never loses an increment.",
}


def _help_text(family: str) -> str:
    return _HELP.get(
        family, family.removeprefix("mst_").replace("_", " ") + "."
    )


def _infer_type(family: str) -> str:
    return "counter" if family.endswith("_total") else "gauge"


def _family_of(sample: str, histograms: set) -> str:
    name = sample.split("{", 1)[0].split(" ", 1)[0]
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix) and name[: -len(suffix)] in histograms:
            return name[: -len(suffix)]
    return name


def _finalize(lines: list) -> list:
    """Exposition post-pass: every family gets a ``# HELP`` ahead of its
    ``# TYPE``, and any sample whose family never declared a ``# TYPE``
    (ad-hoc gauges added over ten PRs) gets both synthesized in front of
    its first sample. Keeps the per-block rendering code append-only."""
    typed = set()
    histograms = set()
    for ln in lines:
        if ln.startswith("# TYPE "):
            parts = ln.split()
            typed.add(parts[2])
            if parts[3] in ("histogram", "summary"):  # _sum / _count
                histograms.add(parts[2])
    out: list = []
    helped: set = set()
    for ln in lines:
        if ln.startswith("# HELP "):
            helped.add(ln.split()[2])
            out.append(ln)
            continue
        if ln.startswith("# TYPE "):
            fam = ln.split()[2]
            if fam not in helped:
                out.append(f"# HELP {fam} {_help_text(fam)}")
                helped.add(fam)
            out.append(ln)
            continue
        if not ln or ln.startswith("#"):
            out.append(ln)
            continue
        fam = _family_of(ln, histograms)
        if fam not in typed:
            out.append(f"# HELP {fam} {_help_text(fam)}")
            out.append(f"# TYPE {fam} {_infer_type(fam)}")
            helped.add(fam)
            typed.add(fam)
        out.append(ln)
    return out
