"""OpenAI-compatible HTTP server with SSE streaming.

Behavior-parity target is the reference's API front end
(ref: shard/openai_api.py): ``POST /v1/completions`` and
``POST /v1/chat/completions`` (routing ref :182-186), CORS headers
(ref :137-141), static web-UI serving on GET (ref :157-176), request
parameter validation (ref :252-294), chat-template prompt building with a
plain role-mapped fallback (ref convert_chat :46-67), non-streaming
responses with usage + token logprobs (ref :357-434), SSE streaming that
buffers partial stop-sequences so a half-emitted stop word never reaches the
client (ref :436-505), and a model provider that caches the loaded model and
can hot-swap on request (ref ModelProvider :70-127).

The execution engine underneath is the TPU stack: one resident
``Generator``/``PipelineEngine`` whose compiled step programs are reused
across requests — a request costs zero compiles. Generation is serialized by
a lock (the honest version of the reference's single-threaded-HTTP-server
concurrency story, SURVEY §5 "race detection"; here it is explicit instead
of accidental).
"""

from __future__ import annotations

import heapq
import json
import logging
import os
import signal
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Optional

import numpy as np

from mlx_sharding_tpu import diffusion, tracing
from mlx_sharding_tpu.analysis.runtime import make_lock
from mlx_sharding_tpu.cache import refuse_recurrent
from mlx_sharding_tpu.generate import TokenLogprobs
from mlx_sharding_tpu.kv_compress import load_compress_map
from mlx_sharding_tpu.kv_share import load_share_map
from mlx_sharding_tpu.resilience import (
    QueueFullError,
    ReplicasUnavailableError,
    RequestTimeoutError,
)
from mlx_sharding_tpu.testing.faults import inject
from mlx_sharding_tpu.tokenizer_utils import (
    StreamingDetokenizer,
    sequence_overlap,
    stopping_criteria,
)
from mlx_sharding_tpu.utils.observability import ServingMetrics, profile_trace
from mlx_sharding_tpu.weights import weight_store

logger = logging.getLogger(__name__)

STATIC_DIR = Path(__file__).parent / "static"
CONTENT_TYPES = {
    ".html": "text/html",
    ".js": "application/javascript",
    ".css": "text/css",
    ".json": "application/json",
}


def _encode_plain(tokenizer, text: str) -> list[int]:
    """Encode without special tokens (stop sequences must match raw ids)."""
    try:
        return list(tokenizer.encode(text, add_special_tokens=False))
    except TypeError:
        return list(tokenizer.encode(text))


def convert_chat(messages: list, role_mapping: Optional[dict] = None) -> str:
    """Plain-text fallback prompt when the tokenizer has no chat template
    (semantics of ref shard/openai_api.py:46-67)."""
    default = {
        "system_prompt": "A chat between a curious user and an artificial "
        "intelligence assistant. The assistant follows the given rules no "
        "matter what.",
        "system": "ASSISTANT's RULE: ",
        "user": "USER: ",
        "assistant": "ASSISTANT: ",
        "stop": "\n",
    }
    role_mapping = role_mapping or default
    prompt = role_mapping.get("system_prompt", "")
    for m in messages:
        role = m["role"]
        prefix = role_mapping.get(role, "")
        stop = role_mapping.get("stop", "")
        prompt += f"{prefix}{m['content']}{stop}"
    prompt += role_mapping.get("assistant", "")
    return prompt.rstrip()


class _SliceAllocator:
    """Free-list of per-replica device slices. The spawn factories used to
    burn a fresh slice index per spawn (``spawn_state["next"] += 1``), so a
    few spawn/drain cycles exhausted the grid while drained replicas'
    devices sat idle — a device-slice leak. Retired slices now come back
    through ``ReplicaSet.on_retire`` and are handed out lowest-index-first
    (heap), so the fleet reuses hardware instead of failing spawns."""

    def __init__(self, devices, per: int):
        self.devices = devices
        self.per = per
        self.total = len(devices) // per
        self._free = list(range(self.total))
        heapq.heapify(self._free)
        self._lock = make_lock("_SliceAllocator._lock")

    def slice_for(self, i: int):
        return self.devices[i * self.per : (i + 1) * self.per]

    def take(self) -> int:
        with self._lock:
            if not self._free:
                raise RuntimeError(
                    f"no free device slice: all {self.total} slices of "
                    f"{self.per} device(s) are held by live replicas"
                )
            return heapq.heappop(self._free)

    def give(self, i: int):
        with self._lock:
            # a double-give is an upstream bug, but corrupting the heap
            # with a duplicate entry would hand one slice to two replicas
            if 0 <= i < self.total and i not in self._free:
                heapq.heappush(self._free, i)

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)


class ModelProvider:
    """Loads and caches one model+tokenizer, swapping when a request names a
    different one (ref shard/openai_api.py:70-127). Paths are validated to
    stay under the working directory, as the reference does."""

    def __init__(
        self,
        default_model: Optional[str] = None,
        *,
        start_layer: Optional[int] = None,
        end_layer: Optional[int] = None,
        num_stages: Optional[int] = None,
        stage_bounds: Optional[list[tuple[int, int]]] = None,
        engine: str = "fused",
        concurrent: int = 1,
        multihost: bool = False,
        tp: int = 1,
        ep: int = 1,
        max_seq: int = 4096,
        prefill_chunk: int = 256,
        cache_dtype=None,
        trust_remote_paths: bool = False,
        chat_template: Optional[str] = None,
        keep_quantized: bool = False,
        decode_block: int = 16,
        paged_pool: Optional[int] = None,
        page_size: Optional[int] = None,
        paged_attention: str = "auto",
        kv_dtype: Optional[str] = None,
        kv_share_map: Optional[str] = None,
        kv_compress_map: Optional[str] = None,
        kv_compress_rank: Optional[int] = None,
        admission_policy: str = "fifo",
        overcommit: bool = False,
        spill_bytes: Optional[int] = None,
        spill_cold_after: Optional[int] = None,
        kv_prefetch: str = "auto",
        draft_model: Optional[str] = None,
        spec_k: int = 4,
        draft: str = "auto",
        spec_window_max: Optional[int] = None,
        prompt_cache: bool = False,
        prefix_store: bool = False,
        prefix_store_bytes: Optional[int] = None,
        prefix_insert_min_hits: int = 1,
        replicas: int = 1,
        max_queue: Optional[int] = None,
        async_sched: str = "auto",
        autoscale: bool = False,
        autoscale_min: Optional[int] = None,
        autoscale_max: Optional[int] = None,
        autoscale_interval: float = 2.0,
        autoscale_cooldown: float = 15.0,
        brownout: bool = True,
        disagg: bool = False,
        prefill_replicas: int = 1,
        decode_replicas: int = 1,
        shared_weights: str = "auto",
        pod: bool = False,
    ):
        # admission control: per-batcher bound on queued requests; a full
        # queue rejects with QueueFullError (HTTP 429 + Retry-After)
        self.max_queue = max_queue
        # async tick pipelining in the continuous batcher: dispatch decode
        # block t+1 before harvesting block t ("auto" = on for plain
        # single-host decode, off when speculating/multi-host)
        self.async_sched = async_sched
        # data-parallel serving: R independent engine replicas, each on its
        # own slice of jax.devices(), score-based request routing
        self.replicas = max(1, replicas)
        # elastic fleet (fleet.py): autoscaler loop spawning/draining
        # replicas under queue pressure, brownout degradation ladder
        self.autoscale = bool(autoscale)
        self.autoscale_min = autoscale_min
        self.autoscale_max = autoscale_max
        self.autoscale_interval = autoscale_interval
        self.autoscale_cooldown = autoscale_cooldown
        self.brownout = bool(brownout)
        self.fleet = None  # FleetAutoscaler once a ReplicaSet is loaded
        # disaggregated prefill/decode serving (disagg.py): two role-split
        # replica pools bridged by KVPageBlock handoff; with --autoscale,
        # self.fleet becomes a (prefill, decode) controller tuple
        self.disagg = bool(disagg)
        self.prefill_replicas = max(1, prefill_replicas)
        self.decode_replicas = max(1, decode_replicas)
        # pod-scale serving (pod.py): N independent host-local fleets (one
        # per process, engines on local devices only) stitched by the pod
        # gossip plane — NOT the SPMD mirror plane (the two are mutually
        # exclusive, so only one collective plane ever exists)
        self.pod = bool(pod)
        self.pod_fleet = None  # PodFleet once a generator is loaded
        # cross-replica shared weights (weights.WeightStore): one resident
        # packed tree per host, every replica co-located on one model-
        # parallel slice and aliasing it — fleet weight bytes ~W, not N×W.
        # "auto" turns it on exactly when a fleet would otherwise hold N
        # copies: multiple replicas (or disagg pools), single-host, on the
        # fused-engine path.
        self.shared_weights = shared_weights
        self.shared_weights_active = False
        # speculative decoding: --draft selects the proposal source
        # ("auto" keeps the legacy contract — engine iff --draft-model,
        # else off; "ngram" drafts from the stream's own history, no
        # second checkpoint), --spec-window-max bounds the per-slot
        # adaptive window ladder
        self.draft_model = draft_model
        self.spec_k = spec_k
        self.draft_mode = draft
        self.spec_window_max = spec_window_max
        # prompt-prefix KV reuse across requests (single-chip generator)
        self.prompt_cache = prompt_cache
        # fleet-wide content-addressed prefix KV store (prefix_store.py):
        # ONE store shared by every batcher this provider builds — device
        # entries leased copy-on-write within a replica, host-tier blocks
        # imported across replicas. Subsumes --prompt-cache (main()
        # rejects the combination).
        self.prefix_store = bool(prefix_store)
        self.prefix_store_bytes = prefix_store_bytes
        self.prefix_insert_min_hits = prefix_insert_min_hits
        self.prefix_store_obj = None  # built once per load()
        self.chat_template = chat_template
        self.keep_quantized = keep_quantized
        # decode steps fused per program launch: 16 amortizes a network-
        # attached chip's per-pull round trip; 1 restores strict per-token
        # streaming granularity for a locally-attached device
        self.decode_block = max(1, decode_block)
        # paged KV pool (continuous batching): pages shared across slots,
        # reservation admission — see scheduler.ContinuousBatcher
        self.paged_pool = paged_pool
        self.page_size = page_size
        # decode-attention path over the pool: "ragged" attends in place
        # (ops/paged_attention.py), "gather" materializes the contiguous
        # per-slot view, "auto" picks ragged where the engine supports it
        self.paged_attention = paged_attention
        # KV-pool storage: "int8" stores {codes, per-row-per-head scale}
        # pools at ~half the bytes of bf16 (see cache.quantize_kv_rows)
        self.kv_dtype = kv_dtype
        # layer-wise KV sharing (kv_share.py, KVSharer): path to a
        # calibrated share-map artifact; pools allocate one physical
        # buffer per share GROUP. Loaded once here — a bad artifact fails
        # at startup, not per-engine-build
        self.kv_share_map_path = kv_share_map
        self.kv_share_map = load_share_map(kv_share_map)
        # compressed-latent KV transport (kv_compress.py): path to a
        # calibrated low-rank artifact (GQA models) — MLA-native models
        # compress without one. Loaded once here, same startup-failure
        # contract as the share map; --kv-compress-rank truncates the
        # nested SVD basis to a cheaper operating point
        self.kv_compress_map_path = kv_compress_map
        self.kv_compress_map = load_compress_map(
            kv_compress_map, kv_compress_rank)
        self.admission_policy = admission_policy
        self.overcommit = overcommit
        # host-DRAM spill tier for preempted requests' KV page blocks
        # (kv_transfer.KVSpillTier): resume re-imports instead of
        # re-prefilling; None = legacy discard preemption
        self.spill_bytes = spill_bytes
        # proactive residency: spill slots whose consumer stopped pulling
        # for N ticks, and stage re-imports ahead of the resume tick
        self.spill_cold_after = spill_cold_after
        self.kv_prefetch = kv_prefetch
        self.default_model = default_model
        self.start_layer = start_layer
        self.end_layer = end_layer
        self.num_stages = num_stages
        self.stage_bounds = stage_bounds
        self.engine = engine
        self.concurrent = max(1, concurrent)
        self.multihost = multihost
        self.tp = max(1, tp)
        self.ep = max(1, ep)
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        self.cache_dtype = cache_dtype
        self.trust_remote_paths = trust_remote_paths
        self._key: Optional[str] = None
        # hot-swap loads must be serialized: two concurrent requests naming
        # different models would otherwise race _key/generator mutation and
        # double-load onto the device
        self._load_lock = make_lock("ModelProvider._load_lock")
        self.generator = None
        self.tokenizer = None
        if default_model:
            self.load("default_model")

    @property
    def prefix_cache_enabled(self) -> bool:
        """--prompt-cache with a paged pool. The ONE definition every
        consumer (rank-0 batcher, multi-host batcher, worker mirror) must
        share: the cache changes the page-allocation sequence, so a
        rank-divergent answer here is a multi-host desync."""
        return bool(self.prompt_cache and self.paged_pool is not None)

    def kv_share_stats(self) -> Optional[dict]:
        """Layer-wise KV sharing summary for /metrics and /health: the
        configured map's geometry plus the first live engine's measured
        pool-bytes saving (every engine binds the same artifact, so one
        engine's view is the fleet's per-engine view). None when no
        --kv-share-map is configured — the metric families stay absent."""
        m = self.kv_share_map
        if m is None:
            return None
        out = {
            "enabled": not m.is_identity,
            "groups": m.num_groups,
            "layers": m.num_layers,
            "share_hash": m.share_hash,
            "bytes_saved": 0,
        }
        try:
            eng = getattr(getattr(self, "generator", None), "engine", None)
            fn = getattr(eng, "kv_share_stats", None)
            if fn is not None:
                out["bytes_saved"] = int(fn().get("bytes_saved", 0))
        except Exception:  # noqa: BLE001 — geometry still renders
            pass
        return out

    def kv_compress_stats(self) -> Optional[dict]:
        """Compressed-latent KV transport summary for /metrics and
        /health: the live engine codec's counters (blocks, faults, bytes
        raw vs wire) when one is bound — which covers MLA-native models
        that compress WITHOUT a configured map — else the configured
        artifact's geometry, else None (metric families stay absent)."""
        try:
            eng = getattr(getattr(self, "generator", None), "engine", None)
            fn = getattr(eng, "kv_compress_stats", None)
            live = fn() if fn is not None else None
        except Exception:  # noqa: BLE001 — fall back to map geometry
            live = None
        if live is not None:
            return live
        m = self.kv_compress_map
        if m is None:
            return None
        return {
            "mode": "lowrank",
            "compress_hash": m.compress_hash,
            "rank": m.rank,
            "blocks_compressed": 0,
            "blocks_reconstructed": 0,
            "compress_faults": 0,
            "reconstruct_faults": 0,
            "bytes_raw_total": 0,
            "bytes_wire_total": 0,
            "bytes_saved_total": 0,
        }

    def _shared_weights_on(self, *, weight_bytes: int = 0, want: int = 0,
                           per: int = 0, n_devices: int = 0) -> bool:
        """Resolve --shared-weights. ``on`` forces (main() already rejected
        the incompatible multihost/chained configs); ``auto`` prices the
        trade capacity-aware when the caller passes the fleet shape.

        Sharing co-locates all ``want`` replicas on ONE slice: it saves
        ``(want-1)*W`` of weight uploads but squeezes every replica's KV
        headroom into the single slice's budget ``B`` instead of spreading
        the fleet over ``want`` private slices. Equating the two — bytes
        saved ``(N-1)W`` against per-slice KV headroom forfeited
        ``(B-W)(N-1)/N`` — sharing wins exactly when ``W*(N+1) >= B``.
        ``B`` comes from ``MST_DEVICE_MEMORY_BYTES`` (per device, scaled by
        the slice width); unset means the budget is unknown and ``auto``
        keeps the legacy rule (a fleet always shares). A grid too small
        for ``want`` private slices forces sharing regardless: co-location
        is then the only way the fleet fits at all."""
        mode = (self.shared_weights or "auto").lower()
        if mode == "off":
            return False
        if mode == "on":
            return True
        if not ((self.replicas > 1 or self.disagg) and not self.multihost):
            return False
        if not (weight_bytes and want > 1 and per):
            return True
        if n_devices and want * per > n_devices:
            logger.info(
                "shared-weights auto: forced ON — %d private slices of %d "
                "devices exceed the %d-device grid", want, per, n_devices,
            )
            return True
        per_device = int(os.environ.get("MST_DEVICE_MEMORY_BYTES", 0) or 0)
        if per_device <= 0:
            return True
        budget = per_device * per
        share = weight_bytes * (want + 1) >= budget
        logger.info(
            "shared-weights auto: %s — weights %.1f MiB x (%d replicas + 1) "
            "%s slice budget %.1f MiB (saved upload %.1f MiB vs KV headroom "
            "%.1f MiB/replica private)",
            "ON" if share else "OFF", weight_bytes / 2**20, want,
            ">=" if share else "<", budget / 2**20,
            (want - 1) * weight_bytes / 2**20,
            max(0, budget - weight_bytes) / 2**20,
        )
        return share

    def _load_draft(self, cache_dtype):
        """Load the draft model pair for speculative decoding. The draft
        rides the packed path only if IT is a quantized checkpoint — a
        dense draft next to a quantized target is a legitimate pairing."""
        from mlx_sharding_tpu.loading import (
            get_model_path,
            load_config,
            load_model,
        )

        draft_quant = (
            load_config(get_model_path(self.draft_model))
            .get("quantization") is not None
        )
        return load_model(
            self.draft_model, dtype=cache_dtype,
            keep_quantized=self.keep_quantized and draft_quant,
        )

    def _validate(self, name: str) -> str:
        if name == "default_model":
            if not self.default_model:
                raise ValueError(
                    "no default model configured; request must name a model"
                )
            return self.default_model
        # Only allow local paths inside CWD unless explicitly trusted
        # (ref shard/openai_api.py:96-104 cwd-relative validation).
        p = Path(name)
        if not self.trust_remote_paths:
            # Proper containment check — a plain str.startswith would let a
            # sibling like /root/repo-evil pass for cwd /root/repo.
            if not p.resolve().is_relative_to(Path.cwd().resolve()):
                raise ValueError(f"model path {name!r} escapes the working directory")
        return name

    def load(self, name: str):
        target = self._validate(name)
        with self._load_lock:
            if self._key == target:
                return self.generator, self.tokenizer
            if self.multihost and self._key is not None:
                # workers mirror only the step sequence, not model swaps
                raise ValueError(
                    "model hot-swap is not supported in multi-host serving"
                )
            logger.info("loading model %s", target)
            import jax.numpy as jnp

            from mlx_sharding_tpu.generate import Generator
            from mlx_sharding_tpu.loading import get_model_path, load_model

            cache_dtype = self.cache_dtype or jnp.bfloat16
            pstore = None  # built below iff --prefix-store applies
            if self.stage_bounds and self.engine == "chained":
                from mlx_sharding_tpu.parallel.chained import load_chained_pipeline

                generator = load_chained_pipeline(
                    target, self.stage_bounds, dtype=cache_dtype,
                    max_seq=self.max_seq, cache_dtype=cache_dtype,
                    prefill_chunk=self.prefill_chunk,
                    keep_quantized=self.keep_quantized,
                )
            else:
                model, params = load_model(
                    target, self.start_layer, self.end_layer, dtype=cache_dtype,
                    keep_quantized=self.keep_quantized,
                )
                stages = (
                    len(self.stage_bounds) if self.stage_bounds
                    else (self.num_stages or 1)
                )
                if self.disagg:
                    refuse_recurrent(
                        model, "--disagg",
                        "the prefill-to-decode hand-off moves pages of K/V only",
                    )
                    diffusion.refuse(model, "--disagg")
                if self.concurrent > 1 and not self.paged_pool:
                    diffusion.refuse(model, "--paged-pool")
                if (
                    stages > 1 or self.concurrent > 1 or self.tp > 1
                    or self.ep > 1 or self.replicas > 1 or self.disagg
                ):
                    import jax as _jax

                    from mlx_sharding_tpu.parallel.mesh import make_mesh
                    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

                    draft_pair = (
                        self._load_draft(cache_dtype)
                        if self.draft_model and self.concurrent > 1 else None
                    )

                    if (self.prefix_store and self.concurrent > 1
                            and self.paged_pool and not self.multihost):
                        from mlx_sharding_tpu.prefix_store import PrefixStore

                        # ONE store for the whole fleet: every batcher
                        # (all replicas, both disagg pools, autoscaler
                        # spawns) binds to it — device entries are
                        # per-engine (page ids are pool-local) but the
                        # host tier and the digest index span the fleet
                        pstore = PrefixStore(
                            host_bytes=self.prefix_store_bytes or (256 << 20),
                            insert_min_hits=self.prefix_insert_min_hits,
                        )

                    per = stages * self.tp * self.ep
                    # a pod host's fleet lives on ITS devices only — local
                    # meshes are process-addressable, so each host builds
                    # engines without any cross-host program
                    devices = (
                        _jax.local_devices() if self.pod else _jax.devices()
                    )
                    want = (
                        self.prefill_replicas + self.decode_replicas
                        if self.disagg else self.replicas
                    )
                    shared = self._shared_weights_on(
                        weight_bytes=sum(
                            getattr(leaf, "nbytes", 0)
                            for leaf in _jax.tree.leaves(params)
                        ),
                        want=want, per=per, n_devices=len(devices),
                    ) and not self.multihost
                    self.shared_weights_active = shared
                    if shared:
                        # shared-weights replicas all co-locate on ONE
                        # model-parallel slice and alias one resident tree
                        # (jit rejects arrays committed to a different
                        # device set, so sharing REQUIRES co-location) —
                        # fleet size is bounded by KV memory, not by how
                        # many weight copies the grid can hold
                        if per > len(devices):
                            raise ValueError(
                                f"shared-weights serving needs one slice "
                                f"of {per} devices, have {len(devices)}"
                            )
                    elif want * per > len(devices):
                        raise ValueError(
                            f"{want} replicas x {per} devices each "
                            f"needs {want * per} devices, have "
                            f"{len(devices)}"
                        )

                    alloc = _SliceAllocator(devices, per)
                    store = key = build_weights = None
                    if shared:
                        from mlx_sharding_tpu.loading import (
                            checkpoint_signature,
                        )
                        from mlx_sharding_tpu.parallel.mesh import (
                            mesh_fingerprint,
                        )
                        from mlx_sharding_tpu.parallel.pipeline import (
                            place_weights,
                        )
                        from mlx_sharding_tpu.weights import (
                            WeightKey,
                            aliased_spawn,
                            weight_store,
                        )

                        base_mesh = make_mesh(
                            pp=stages, tp=self.tp, ep=self.ep,
                            devices=devices[:per],
                        )
                        store = weight_store()
                        key = WeightKey(
                            checkpoint=checkpoint_signature(
                                target, keep_quantized=self.keep_quantized
                            ),
                            stage_bounds=(
                                tuple(tuple(b) for b in self.stage_bounds)
                                if self.stage_bounds else ("auto", stages)
                            ),
                            dtype=jnp.dtype(cache_dtype).name,
                            quant=f"tp{self.tp}:ep{self.ep}",
                            placement=mesh_fingerprint(base_mesh),
                        )

                        def build_weights():
                            return place_weights(
                                model, params, base_mesh,
                                stage_bounds=self.stage_bounds,
                            )

                        if draft_pair is not None:
                            # the draft checkpoint is a WeightStore tree
                            # exactly like the base: keyed by its own
                            # checkpoint signature + placement, aliased by
                            # every replica on this host, digest gossiped
                            # over the pod heartbeat by the same registry
                            draft_mesh = make_mesh(
                                pp=1, tp=1, ep=1, devices=devices[:per]
                            )
                            draft_key = WeightKey(
                                checkpoint=checkpoint_signature(
                                    self.draft_model,
                                    keep_quantized=self.keep_quantized,
                                ),
                                stage_bounds=("auto", 1),
                                dtype=jnp.dtype(cache_dtype).name,
                                quant="draft",
                                placement=mesh_fingerprint(draft_mesh),
                            )

                            def build_draft_weights():
                                dm, dp = draft_pair
                                return place_weights(dm, dp, draft_mesh)

                    def build_engine(dev_slice, *, weights_lease=None,
                                     speculate=True):
                        nonlocal params
                        if weights_lease is not None:
                            engine = PipelineEngine(
                                model, None, weights_lease.weights.mesh,
                                weights=weights_lease.weights,
                                stage_bounds=self.stage_bounds,
                                microbatches=self.concurrent,
                                max_seq=self.max_seq,
                                cache_dtype=cache_dtype,
                                prefill_chunk=self.prefill_chunk,
                                decode_block=self.decode_block,
                                pool_pages=self.paged_pool
                                if self.concurrent > 1 else None,
                                page_size=self.page_size,
                                paged_attention=self.paged_attention,
                                kv_dtype=self.kv_dtype,
                                kv_share_map=self.kv_share_map
                                if self.paged_pool and self.concurrent > 1
                                else None,
                                kv_compress_map=self.kv_compress_map
                                if self.paged_pool and self.concurrent > 1
                                else None,
                            )
                            # retirement releases the ref; the LAST engine
                            # to close frees the store's tree
                            engine.on_close(weights_lease.release)
                        else:
                            engine = PipelineEngine(
                                model, params,
                                make_mesh(pp=stages, tp=self.tp, ep=self.ep,
                                          devices=dev_slice),
                                stage_bounds=self.stage_bounds,
                                microbatches=self.concurrent,
                                max_seq=self.max_seq,
                                cache_dtype=cache_dtype,
                                prefill_chunk=self.prefill_chunk,
                                decode_block=self.decode_block,
                                pool_pages=self.paged_pool
                                if self.concurrent > 1 else None,
                                page_size=self.page_size,
                                paged_attention=self.paged_attention,
                                kv_dtype=self.kv_dtype,
                                kv_share_map=self.kv_share_map
                                if self.paged_pool and self.concurrent > 1
                                else None,
                                kv_compress_map=self.kv_compress_map
                                if self.paged_pool and self.concurrent > 1
                                else None,
                            )
                            if want == 1:
                                # the one engine holds its own placed copy
                                # of the weights and no fleet will spawn a
                                # second: drop the loader's tree BEFORE the
                                # batcher allocates the KV pool, or the
                                # model is resident twice beside it
                                params = None
                        if self.concurrent > 1 and not self.multihost:
                            from mlx_sharding_tpu.scheduler import (
                                ContinuousBatcher,
                            )

                            draft_eng = None
                            if draft_pair is not None and speculate:
                                dmodel, dparams = draft_pair
                                if shared:
                                    # alias the store's resident draft
                                    # tree; the ref drops when the batcher
                                    # closes this engine. Same spawn
                                    # contract as the base tree: a faulted
                                    # build releases before re-raising.
                                    def make_draft(dlease):
                                        deng = PipelineEngine(
                                            dmodel, None,
                                            dlease.weights.mesh,
                                            weights=dlease.weights,
                                            microbatches=self.concurrent,
                                            max_seq=self.max_seq,
                                            cache_dtype=cache_dtype,
                                            prefill_chunk=self.prefill_chunk,
                                        )
                                        deng.on_close(dlease.release)
                                        return deng

                                    draft_eng = aliased_spawn(
                                        store, draft_key,
                                        build_draft_weights, make_draft,
                                    )
                                else:
                                    draft_eng = PipelineEngine(
                                        dmodel, dparams,
                                        make_mesh(pp=1, tp=1, ep=1,
                                                  devices=dev_slice),
                                        microbatches=self.concurrent,
                                        max_seq=self.max_seq,
                                        cache_dtype=cache_dtype,
                                        prefill_chunk=self.prefill_chunk,
                                    )
                            engine = ContinuousBatcher(
                                engine,
                                decode_block=min(8, self.decode_block),
                                policy=self.admission_policy,
                                prefix_cache=self.prefix_cache_enabled,
                                overcommit=self.overcommit,
                                spill_bytes=self.spill_bytes,
                                spill_cold_after=self.spill_cold_after,
                                kv_prefetch=self.kv_prefetch,
                                draft_engine=draft_eng,
                                spec_k=self.spec_k,
                                draft=self.draft_mode if speculate else "off",
                                spec_window_max=(
                                    self.spec_window_max if speculate
                                    else None
                                ),
                                max_queue=self.max_queue,
                                async_sched=self.async_sched,
                                prefix_store=pstore,
                            )
                        return engine

                    def spawn_replica(speculate=True):
                        """One replica by either strategy: alias the
                        store's resident tree (shared) or take a private
                        device slice and upload a full copy. Both paths
                        leave state consistent when the build faults — the
                        lease is released / the slice returned before the
                        error propagates, so the autoscaler degrades to
                        the static fleet with nothing leaked and nothing
                        freed in use. ``speculate=False`` builds a
                        non-drafting replica (disagg prefill pools: a
                        prefill replica emits one token per request, so
                        draft windows there are pure ballast)."""
                        if shared:
                            return aliased_spawn(
                                store, key, build_weights,
                                lambda lease: build_engine(
                                    devices[:per], weights_lease=lease,
                                    speculate=speculate,
                                ),
                            )
                        i = alloc.take()
                        try:
                            eng = build_engine(
                                alloc.slice_for(i), speculate=speculate
                            )
                        except BaseException:
                            alloc.give(i)
                            raise
                        eng._mst_slice = i
                        return eng

                    def recycle_slice(rep):
                        # ReplicaSet.on_retire: a drained-and-closed
                        # replica's device slice goes back on the free list
                        # (shared replicas carry no slice tag — their
                        # release rides the engine close hook)
                        i = getattr(rep, "_mst_slice", None)
                        if i is not None:
                            alloc.give(i)

                    if self.disagg:
                        from mlx_sharding_tpu.disagg import DisaggCoordinator
                        from mlx_sharding_tpu.replicas import ReplicaSet

                        if self.concurrent <= 1:
                            raise ValueError(
                                "disagg serving requires concurrent > 1: "
                                "only the continuous batcher can park a "
                                "prefill-only request and resume it from a "
                                "KV page block"
                            )
                        n_pf = self.prefill_replicas
                        n_dc = self.decode_replicas
                        # role-aware spawns: decode replicas speculate
                        # (adaptive windows per stream), prefill replicas
                        # never do — and their autoscaler factories below
                        # inherit the same role
                        import functools

                        spawn_prefill = functools.partial(
                            spawn_replica, speculate=False
                        )
                        prefill = ReplicaSet([
                            spawn_prefill() for _ in range(n_pf)
                        ], role="prefill", prefix_store=pstore)
                        decode = ReplicaSet([
                            spawn_replica() for _ in range(n_dc)
                        ], role="decode", prefix_store=pstore)
                        prefill.on_retire = recycle_slice
                        decode.on_retire = recycle_slice
                        generator = DisaggCoordinator(
                            prefill, decode, prefix_store=pstore
                        )
                        if self.autoscale:
                            from mlx_sharding_tpu.fleet import FleetAutoscaler

                            # Two controllers, one per role pool — each
                            # reads only its own pool's pressure
                            # (fleet.pool_pressure), so a prefill storm
                            # can't spawn decode replicas and vice versa.
                            # Private spawns draw device slices from the
                            # shared free list: the pools compete for
                            # leftover (and recycled) hardware first-come,
                            # and an empty list fails the next spawn —
                            # which degrades to the static pool, by design.
                            # Shared spawns consume no slice, so each pool
                            # keeps at least one elastic spawn even on a
                            # fully-consumed grid.
                            spare = alloc.total - (n_pf + n_dc)
                            self.fleet = tuple(
                                FleetAutoscaler(
                                    pool,
                                    spawn_prefill if pool is prefill
                                    else spawn_replica,
                                    min_replicas=base,
                                    max_replicas=base + (
                                        max(1, spare) if shared
                                        else max(0, spare)
                                    ),
                                    interval_s=self.autoscale_interval,
                                    cooldown_s=self.autoscale_cooldown,
                                    enable_brownout=self.brownout,
                                )
                                for pool, base in (
                                    (prefill, n_pf), (decode, n_dc)
                                )
                            )
                            for ctrl in self.fleet:
                                ctrl.start()
                    elif self.replicas > 1:
                        from mlx_sharding_tpu.replicas import ReplicaSet

                        generator = ReplicaSet([
                            spawn_replica() for _ in range(self.replicas)
                        ], prefix_store=pstore)
                        generator.on_retire = recycle_slice
                        if self.autoscale:
                            from mlx_sharding_tpu.fleet import FleetAutoscaler

                            hw_max = alloc.total
                            self.fleet = FleetAutoscaler(
                                generator, spawn_replica,
                                min_replicas=self.autoscale_min or 1,
                                # shared replicas don't consume device
                                # slices, so the grid doesn't cap the fleet
                                # — KV memory does; private spawns stay
                                # clamped to the slice count (now a true
                                # bound on LIVE replicas, since drains
                                # recycle slices through the free list)
                                max_replicas=(
                                    (self.autoscale_max or hw_max) if shared
                                    else min(
                                        self.autoscale_max or hw_max, hw_max
                                    )
                                ),
                                interval_s=self.autoscale_interval,
                                cooldown_s=self.autoscale_cooldown,
                                enable_brownout=self.brownout,
                            )
                            self.fleet.start()
                    else:
                        generator = spawn_replica()
                    if self.multihost:
                        # (--replicas is rejected with --coordinator, so
                        # `generator` here is the raw single engine)
                        if _jax.process_index() > 0:
                            # raw engine: serve_worker / serve_worker_batched
                            # wraps it in its own mirror state
                            pass
                        elif self.concurrent > 1:
                            from mlx_sharding_tpu.parallel.multihost import (
                                make_multihost_batcher,
                            )

                            generator = make_multihost_batcher(
                                generator,
                                decode_block=min(8, self.decode_block),
                                policy=self.admission_policy,
                                prefix_cache=self.prefix_cache_enabled,
                                max_queue=self.max_queue,
                            )
                        else:
                            from mlx_sharding_tpu.parallel.multihost import (
                                MultiHostPipeline,
                            )

                            generator = MultiHostPipeline(generator)
                elif self.draft_mode == "ngram":
                    # single-stream prompt-lookup speculation: drafts from
                    # the stream's own history, no second checkpoint
                    from mlx_sharding_tpu.speculative import (
                        NgramSpeculativeGenerator,
                    )

                    generator = NgramSpeculativeGenerator(
                        model, params,
                        spec_window_max=self.spec_window_max or 8,
                        max_seq=self.max_seq, cache_dtype=cache_dtype,
                        prefill_chunk=self.prefill_chunk,
                        decode_block=self.decode_block,
                    )
                elif self.draft_model:
                    from mlx_sharding_tpu.speculative import (
                        SpeculativeGenerator,
                    )

                    dmodel, dparams = self._load_draft(cache_dtype)
                    generator = SpeculativeGenerator(
                        model, params, dmodel, dparams, spec_k=self.spec_k,
                        max_seq=self.max_seq, cache_dtype=cache_dtype,
                        prefill_chunk=self.prefill_chunk,
                        decode_block=self.decode_block,
                    )
                else:
                    generator = Generator(
                        model, params, max_seq=self.max_seq,
                        cache_dtype=cache_dtype,
                        prefill_chunk=self.prefill_chunk,
                        decode_block=self.decode_block,
                        prompt_cache=self.prompt_cache,
                    )
            if self.pod:
                # stitch this host's fleet into the pod: gossip transport
                # over the PodControlPlane, weight-registry + handoff +
                # pod-autoscaler front door wrapping the local generator
                # (DisaggCoordinator gets the cross-host decode leg via
                # attach_pod inside PodFleet)
                from mlx_sharding_tpu.pod import CollectiveTransport, PodFleet

                ctrls = (
                    self.fleet if isinstance(self.fleet, tuple)
                    else (self.fleet,) if self.fleet is not None else ()
                )
                transport = CollectiveTransport()
                pf = PodFleet(
                    transport.host_id, transport, generator,
                    controllers=list(ctrls),
                    # federate the prefix store's host tier over the pod:
                    # its digest inventory rides the heartbeat and a local
                    # miss can pull the owner's exported block instead of
                    # re-prefilling (pod.PodPrefixFederation)
                    prefix_store=pstore,
                )
                pf.start()
                self.pod_fleet = pf
                generator = pf
            from transformers import AutoTokenizer

            tokenizer = AutoTokenizer.from_pretrained(str(get_model_path(target)))
            # swap the fleet store with the generator: _set closes the old
            # generator first (its close() drops its owner entries), so
            # the old store drains cleanly before its host tier is freed
            old_store, self.prefix_store_obj = self.prefix_store_obj, pstore
            self._set(target, generator, tokenizer)
            if old_store is not None:
                old_store.close()
            return self.generator, self.tokenizer

    def _set(self, key, generator, tokenizer):
        # operator-supplied chat template wins over the checkpoint's
        # (ref shard/openai_api.py --chat-template flag behavior)
        if getattr(self, "chat_template", None):
            tokenizer.chat_template = self.chat_template
        old = getattr(self, "generator", None)
        self._key = key
        self.generator = generator
        self.tokenizer = tokenizer
        if old is not None and hasattr(old, "close"):
            old.close()  # stop a replaced batcher's scheduler thread
            # a fleet controller bound to the replaced generator died with
            # it (rs.close() stopped the loop) — drop the stale handle;
            # disagg stores a (prefill, decode) controller tuple whose
            # pools hang off the replaced coordinator
            fleet = getattr(self, "fleet", None)
            ctrls = fleet if isinstance(fleet, tuple) else (fleet,)
            owned = {id(o) for o in (old, getattr(old, "prefill", None),
                                     getattr(old, "decode", None))
                     if o is not None}
            if any(c is not None and getattr(c, "rs", None) is not None
                   and id(c.rs) in owned for c in ctrls):
                self.fleet = None


class APIHandler(BaseHTTPRequestHandler):
    """One handler class per server instance, bound to its provider via a
    factory (class attributes), as stdlib requires."""

    provider: ModelProvider = None
    gen_lock: threading.Lock = None
    metrics: ServingMetrics = None
    profile_dir: Optional[str] = None
    api_key: Optional[str] = None
    # server-wide deadline defaults (--request-timeout / --ttft-timeout);
    # per-request body fields override them
    request_timeout: Optional[float] = None
    ttft_timeout: Optional[float] = None
    protocol_version = "HTTP/1.1"

    # ------------------------------------------------------------- helpers
    def log_message(self, fmt, *args):
        logger.debug("%s - %s", self.address_string(), fmt % args)

    def _cors(self):
        # ref shard/openai_api.py:137-141
        self.send_header("Access-Control-Allow-Origin", "*")
        self.send_header("Access-Control-Allow-Methods", "GET, POST, OPTIONS")
        self.send_header("Access-Control-Allow-Headers", "Content-Type, Authorization")

    def _json(self, code: int, payload: dict,
              extra_headers: Optional[dict] = None):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        # per-request headers accumulated during handling (brownout level,
        # caps) ride along on whatever response finally goes out
        headers = dict(getattr(self, "_resp_headers", None) or {})
        headers.update(extra_headers or {})
        for k, v in headers.items():
            self.send_header(k, str(v))
        self._cors()
        self.end_headers()
        self.wfile.write(body)

    def _error(self, code: int, message: str,
               extra_headers: Optional[dict] = None):
        # OpenAI error envelope with a type that reflects the status class,
        # so clients can distinguish bad requests from engine failures.
        kind = (
            "invalid_request_error" if code == 400
            else "not_found_error" if code == 404
            else "overloaded_error" if code == 429
            else "service_unavailable_error" if code == 503
            else "timeout_error" if code == 504
            else "server_error"
        )
        self._json(
            code, {"error": {"message": message, "type": kind, "code": code}},
            extra_headers=extra_headers,
        )

    # ------------------------------------------------------------- routing
    def do_OPTIONS(self):
        self.send_response(204)
        self._cors()
        self.end_headers()

    def do_GET(self):
        # static web UI (ref shard/openai_api.py:157-176)
        path = self.path.split("?")[0]
        if path in ("/", "/index.html"):
            path = "/index.html"
        elif path == "/health":
            # Layered health: the generator's own view (scheduler thread
            # liveness / per-replica circuit state — ok, degraded, draining)
            # plus multi-host control-plane liveness. ``serving`` decides the
            # status code: partial capacity (some replicas circuit-broken,
            # ≥1 alive) is degraded WITH a 200 — degraded, not dead; a
            # wedged scheduler, drained server, or dead control plane is a
            # 503.
            gen = self.provider.generator
            payload, serving = {"status": "ok"}, True
            if hasattr(gen, "health"):
                payload = dict(gen.health())
                serving = bool(payload.pop("serving", True))
            # resident weight-tree occupancy (weights.WeightStore): how many
            # trees this host holds, how many engine refs alias them, and
            # the resident bytes — the N×W → ~W number, live
            try:
                st = weight_store().stats()
                payload["weight_store"] = {
                    "shared_weights": bool(
                        getattr(self.provider, "shared_weights_active",
                                False)
                    ),
                    "trees": st["trees"],
                    "refs": st["refs"],
                    "bytes": st["bytes"],
                }
            except Exception:  # noqa: BLE001 — health must render anyway
                pass
            # fleet prefix store: residency split, hit rate, insertion-
            # policy counters — the block operators watch to size
            # --prefix-store-bytes and tune --prefix-insert-min-hits
            store = getattr(self.provider, "prefix_store_obj", None)
            if store is not None:
                try:
                    payload["prefix_store"] = store.stats()
                except Exception:  # noqa: BLE001 — health must render anyway
                    pass
            # pod fleet: per-host liveness/weights from the gossip view,
            # handoff + autoscaler counters — absent on every single-host
            # deployment (shape contract: no pod key, no host labels)
            pod = getattr(self.provider, "pod_fleet", None)
            if pod is not None:
                try:
                    payload["pod"] = pod.pod_stats()
                except Exception:  # noqa: BLE001 — health must render anyway
                    pass
            if getattr(self.provider, "kv_share_map", None) is not None:
                try:
                    payload["kv_share"] = self.provider.kv_share_stats()
                except Exception:  # noqa: BLE001 — health must render anyway
                    pass
            try:
                kc = self.provider.kv_compress_stats()
                if kc is not None:
                    payload["kv_compress"] = kc
            except Exception:  # noqa: BLE001 — health must render anyway
                pass
            ctrl = getattr(gen, "ctrl", None)
            if ctrl is not None:
                # a timed-out collective marks the plane dead (multihost.py
                # ControlPlane); every completed one proves all ranks alive
                import time as _time

                last = getattr(ctrl, "last_ok", None)
                payload["multihost"] = {
                    "workers_responsive": not getattr(ctrl, "dead", False),
                    "last_exchange_s_ago": (
                        None if last is None
                        else round(_time.monotonic() - last, 1)
                    ),
                }
                if getattr(ctrl, "dead", False):
                    payload["status"] = "degraded"
                    serving = False
            return self._json(200 if serving else 503, payload)
        elif path == "/admin/trace" or path.startswith("/admin/trace/"):
            # flight-recorder readout: /admin/trace/dump is the whole ring
            # (+ incident snapshots) as ONE chrome://tracing JSON document;
            # /admin/trace/<request_id> is one request's timeline (live,
            # retired, or preserved in a snapshot)
            tracer = tracing.get_tracer()
            if tracer is None or not tracer.enabled:
                return self._error(
                    404, "tracing is off — start the server with "
                         "--trace sample|on"
                )
            rest = path[len("/admin/trace"):].strip("/")
            if rest in ("", "dump"):
                return self._json(200, tracer.export_dump())
            payload = tracer.export_request(rest)
            if payload is None:
                return self._error(404, f"no trace recorded for {rest!r}")
            return self._json(200, payload)
        elif path == "/metrics":
            body = self.metrics.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain; version=0.0.4")
            self.send_header("Content-Length", str(len(body)))
            self._cors()
            self.end_headers()
            self.wfile.write(body)
            return
        target = (STATIC_DIR / path.lstrip("/")).resolve()
        if not str(target).startswith(str(STATIC_DIR.resolve())) or not target.is_file():
            return self._error(404, f"not found: {self.path}")
        body = target.read_bytes()
        self.send_response(200)
        self.send_header("Content-Type", CONTENT_TYPES.get(target.suffix, "application/octet-stream"))
        self.send_header("Content-Length", str(len(body)))
        self._cors()
        self.end_headers()
        self.wfile.write(body)

    # request bodies above this are rejected before being read — an
    # unauthenticated client must not be able to buffer arbitrary bytes or
    # pin a handler thread with a huge/negative Content-Length
    MAX_BODY = 8 << 20

    ADMIN_ROUTES = ("/admin/drain", "/admin/autoscaler")

    def do_POST(self):
        route = self.path.split("?")[0]
        self._resp_headers: dict = {}  # reset per request (handler reuse)
        handlers = {
            "/v1/completions": self._handle_text_completion,
            "/v1/chat/completions": self._handle_chat_completion,
        }
        try:
            length = int(self.headers.get("Content-Length", 0))
        except ValueError:
            length = -1
        if not 0 <= length <= self.MAX_BODY:
            self.close_connection = True  # can't safely drain; don't reuse
            return self._error(413, "invalid or oversized request body")
        try:
            raw = self.rfile.read(length)  # always drain — before ANY reply,
            # including 404/401: replying with the body unread desyncs
            # HTTP/1.1 keep-alive (the leftover bytes would parse as the
            # next request line)
        except OSError:
            return self._error(400, "unreadable request body")
        if route not in handlers and route not in self.ADMIN_ROUTES:
            return self._error(404, f"unknown route {route}")
        if self.api_key:
            # the reference UI sends Authorization: Bearer <key>
            # (ref shard/static/app.js:151) but its server never checks it;
            # here --api-key makes the check real. Static/health/metrics
            # stay open — only the generation endpoints are gated.
            # bytes compare: compare_digest rejects non-ASCII str, and
            # header bytes are remotely controlled
            import hmac

            auth = self.headers.get("Authorization", "").encode(
                "utf-8", "surrogateescape"
            )
            want = f"Bearer {self.api_key}".encode()
            if not hmac.compare_digest(auth, want):
                return self._json(401, {"error": {
                    "message": "invalid or missing API key",
                    "type": "authentication_error", "code": 401,
                }})
        try:
            body = json.loads(raw or b"{}")
        except json.JSONDecodeError:
            return self._error(400, "invalid JSON body")
        if route == "/admin/drain":
            # operator surface, not a generation request: no sampler params
            # to validate and no model hot-swap — but it IS key-gated above
            return self._handle_drain(body)
        if route == "/admin/autoscaler":
            return self._handle_autoscaler(body)
        try:
            params = self._validate_params(body)
        except ValueError as e:
            return self._error(400, str(e))
        try:
            generator, tokenizer = self.provider.load(body.get("model", "default_model"))
        except ValueError as e:
            return self._error(400, str(e))
        try:
            handlers[route](body, params, generator, tokenizer)
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away; _generate's close already cancelled
            # the in-flight request (the scheduler reclaims its slot/pages)
        except QueueFullError as e:
            # load shed at admission: the queue bound was hit before any
            # work was spent; tell the client when to come back
            try:
                self._error(429, str(e), extra_headers={
                    "Retry-After": str(max(1, round(e.retry_after_s))),
                })
            except Exception:
                pass
        except RequestTimeoutError as e:
            try:
                self._error(504, str(e))
            except Exception:
                pass
        except ReplicasUnavailableError as e:
            # every replica circuit-broken: the error carries the earliest
            # half-open probe ETA, so tell the client when a retry could
            # actually be admitted instead of inviting an instant hammer
            ra = getattr(e, "retry_after_s", None)
            hdrs = (
                {"Retry-After": str(max(1, round(ra)))}
                if isinstance(ra, (int, float)) and not isinstance(ra, bool)
                else None
            )
            try:
                self._error(503, str(e), extra_headers=hdrs)
            except Exception:
                pass
        except ValueError as e:  # bad request discovered late (e.g. KV capacity)
            try:
                self._error(400, str(e))
            except Exception:
                pass
        except Exception as e:  # return a structured error, don't kill the conn
            logger.exception("request failed")
            try:
                self._error(500, f"{type(e).__name__}: {e}")
            except Exception:
                pass

    def _handle_drain(self, body: dict):
        """POST /admin/drain ``{"replica": i, "deadline": s}`` — gracefully
        retire one replica. Its admitted requests migrate to the remaining
        replicas (their clients' streams continue seamlessly) and /health
        reports ``draining`` for the duration. 400 without --replicas
        serving; a mid-migration failure leaves the replica quarantined
        (500, retryable) with nothing dropped."""
        gen = self.provider.generator
        drain = getattr(gen, "drain", None)
        if drain is None:
            return self._error(400, "drain requires --replicas serving "
                                    "(a ReplicaSet generator)")
        if "replica" not in body:
            return self._error(400, "missing 'replica' index")
        try:
            replica = int(body["replica"])
            deadline = float(body.get("deadline", 30.0))
            if deadline <= 0:
                raise ValueError
        except (TypeError, ValueError):
            return self._error(400, "'replica' must be an integer and "
                                    "'deadline' a positive number of seconds")
        try:
            result = drain(replica, deadline=deadline)
        except ValueError as e:
            return self._error(400, str(e))
        except Exception as e:
            logger.exception("replica drain failed")
            return self._error(500, f"{type(e).__name__}: {e}")
        return self._json(200, result)

    def _handle_autoscaler(self, body: dict):
        """POST /admin/autoscaler ``{"enabled": bool}`` — start/stop the
        fleet autoscaler loop (omit ``enabled`` to just inspect it).
        Returns the controller's counters plus the brownout ladder state.
        400 when the server wasn't launched with --autoscale."""
        fleet = getattr(self.provider, "fleet", None)
        if fleet is None:
            return self._error(400, "autoscaler requires --autoscale "
                                    "(and --replicas > 1 or --disagg) "
                                    "serving")
        # --disagg runs one controller per role pool; start/stop applies
        # to both, and the response carries a per-pool state list
        ctrls = fleet if isinstance(fleet, tuple) else (fleet,)
        enabled = body.get("enabled")
        if enabled is not None and not isinstance(enabled, bool):
            return self._error(400, "'enabled' must be a boolean")
        try:
            for ctrl in ctrls:
                if enabled is True:
                    ctrl.start()
                elif enabled is False:
                    ctrl.stop()
        except Exception as e:
            logger.exception("autoscaler control failed")
            return self._error(500, f"{type(e).__name__}: {e}")

        def _state(ctrl):
            out = dict(ctrl.state())
            bro = getattr(ctrl, "brownout", None)
            if bro is not None:
                out["brownout"] = bro.state()
            return out

        if len(ctrls) == 1:
            return self._json(200, _state(ctrls[0]))
        return self._json(200, {"pools": [_state(c) for c in ctrls]})

    # ---------------------------------------------------------- validation
    def _validate_params(self, body: dict) -> dict:
        """Parameter extraction + validation (ref shard/openai_api.py:206-294,
        same bounds)."""
        p = {}
        p["stream"] = bool(body.get("stream", False))
        p["max_tokens"] = body.get("max_tokens", 100)
        if not isinstance(p["max_tokens"], int) or p["max_tokens"] < 0:
            raise ValueError("max_tokens must be a non-negative integer")
        p["temperature"] = body.get("temperature", 0.0)
        if not isinstance(p["temperature"], (int, float)) or p["temperature"] < 0:
            raise ValueError("temperature must be a non-negative float")
        p["top_p"] = body.get("top_p", 1.0)
        if not isinstance(p["top_p"], (int, float)) or not 0 < p["top_p"] <= 1:
            raise ValueError("top_p must be in (0, 1]")
        rp = body.get("repetition_penalty")
        if rp is not None and (not isinstance(rp, (int, float)) or rp <= 0):
            raise ValueError("repetition_penalty must be a positive float")
        p["repetition_penalty"] = rp
        rcs = body.get("repetition_context_size", 20)
        if not isinstance(rcs, int) or rcs < 1:
            raise ValueError("repetition_context_size must be a positive integer")
        p["repetition_context_size"] = rcs
        logprobs = body.get("logprobs", -1)
        if logprobs != -1 and not (0 < logprobs <= 10):
            raise ValueError("logprobs must be between 1 and 10")
        p["logprobs"] = logprobs
        bias = body.get("logit_bias")
        if bias is not None:
            if not isinstance(bias, dict):
                raise ValueError("logit_bias must be a token_id -> bias map")
            try:
                bias = {int(k): float(v) for k, v in bias.items()}
            except (ValueError, TypeError):
                raise ValueError("logit_bias keys must be token ids")
            # one cap for every serving path (solo / scheduler slots /
            # multi-host control plane all size their buffers to 512) so a
            # request never succeeds on one deployment and 500s on another;
            # OpenAI's documented cap is 300
            if len(bias) > 512:
                raise ValueError("logit_bias supports at most 512 entries")
        p["logit_bias"] = bias
        stop = body.get("stop", [])
        if isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list) or not all(isinstance(s, str) for s in stop):
            raise ValueError("stop must be a string or list of strings")
        p["stop_words"] = stop
        p["seed"] = body.get("seed")
        # per-request deadline overrides; None falls back to the server-wide
        # --request-timeout / --ttft-timeout defaults
        for key in ("request_timeout", "ttft_timeout"):
            v = body.get(key)
            if v is not None and (
                isinstance(v, bool) or not isinstance(v, (int, float)) or v <= 0
            ):
                raise ValueError(f"{key} must be a positive number of seconds")
            p[key] = v
        return p

    # ------------------------------------------------------------- prompts
    def _chat_prompt(self, body: dict, tokenizer) -> list[int]:
        messages = body.get("messages")
        if not isinstance(messages, list) or not messages:
            raise ValueError("messages must be a non-empty list")
        if getattr(tokenizer, "chat_template", None):
            return tokenizer.apply_chat_template(
                messages, tokenize=True, add_generation_prompt=True
            )
        return tokenizer.encode(convert_chat(messages, body.get("role_mapping")))

    # ----------------------------------------------------------- responses
    @staticmethod
    def _response_id() -> str:
        return f"cmpl-{uuid.uuid4().hex[:24]}"

    def _make_response(
        self, *, rid, object_type, model, text=None, delta=None,
        finish_reason=None, usage=None, logprobs=None,
    ) -> dict:
        # OpenAI schema builder (ref generate_response shard/openai_api.py:296-355)
        choice = {"index": 0, "finish_reason": finish_reason, "logprobs": logprobs}
        if object_type.startswith("chat"):
            if delta is not None:
                choice["delta"] = delta
            else:
                choice["message"] = {"role": "assistant", "content": text}
        else:
            choice["text"] = text if text is not None else ""
        resp = {
            "id": rid,
            "object": object_type,
            "created": int(time.time()),
            "model": model,
            "system_fingerprint": f"fp_{uuid.uuid4().hex[:10]}",
            "choices": [choice],
        }
        if usage:
            resp["usage"] = usage
        return resp

    # ----------------------------------------------------------- execution
    def _run(self, body, params, generator, tokenizer, prompt_ids, chat: bool):
        rid = self._response_id()
        # the trace key the operator curls /admin/trace/<id> with — echoed
        # on EVERY response (traced or not) so clients can always correlate
        self._resp_headers["X-MST-Request-Id"] = rid
        model_name = body.get("model", "default_model")
        stop_id_sequences = [_encode_plain(tokenizer, s) for s in params["stop_words"]]
        eos = getattr(tokenizer, "eos_token_id", None)
        obj = "chat.completion" if chat else "text_completion"

        gen_kwargs = dict(
            temperature=params["temperature"],
            top_p=params["top_p"],
            repetition_penalty=params["repetition_penalty"],
            repetition_context_size=params["repetition_context_size"],
            logit_bias=params["logit_bias"],
            seed=params["seed"],
            max_tokens=params["max_tokens"],
        )
        if not params["stream"] and params["logprobs"] > 0:
            # streaming discards logprobs (ref shard/openai_api.py:454-455),
            # so only the non-streaming path asks the engine to compute them
            gen_kwargs["want_logprobs"] = True

        # Brownout: under sustained overload the ladder trades per-request
        # cost for admission — cap max_tokens before shedding anything. The
        # applied level is surfaced in a response header so load generators
        # and clients can observe degradation without parsing /health.
        fleet = getattr(self.provider, "fleet", None)
        if isinstance(fleet, tuple):
            # disagg: the decode pool's ladder governs generation caps
            # (max_tokens is decode-side cost; prefill overload sheds at
            # that pool's own admission instead)
            fleet = fleet[-1]
        bro = getattr(fleet, "brownout", None) if fleet is not None else None
        if bro is not None:
            bstate = bro.state()
            level = bstate.get("level", 0)
            if level > 0:
                self._resp_headers["X-MST-Brownout-Level"] = level
                cap = bstate.get("max_tokens_cap")
                if cap is not None and gen_kwargs["max_tokens"] > cap:
                    gen_kwargs["max_tokens"] = cap
                    self._resp_headers["X-MST-Max-Tokens-Capped"] = cap

        # Session stickiness: an explicit session_id (or OpenAI's `user`
        # field) lets the fleet router keep a conversation on the replica
        # that holds its prefix cache.
        sess = body.get("session_id") or body.get("user")
        if (
            isinstance(sess, str) and sess
            and getattr(generator, "supports_sessions", False)
        ):
            gen_kwargs["_session"] = sess

        # Deadlines: per-request override beats the server-wide flag. A
        # scheduler-backed generator enforces them itself (bounded out-queue
        # waits that survive a wedged engine); anything else gets a coarse
        # between-tokens check in _generate — it can't interrupt a stuck
        # step, but it bounds total generation.
        req_to = params.get("request_timeout")
        if req_to is None:
            req_to = self.request_timeout
        ttft_to = params.get("ttft_timeout")
        if ttft_to is None:
            ttft_to = self.ttft_timeout
        soft_timeout = None
        if getattr(generator, "supports_deadlines", False):
            if req_to is not None:
                gen_kwargs["request_timeout"] = req_to
            if ttft_to is not None:
                gen_kwargs["ttft_timeout"] = ttft_to
        else:
            soft_timeout = req_to

        # a concurrency-safe generator (ContinuousBatcher) interleaves
        # requests itself; everything else is serialized by the lock, which
        # is the reference's single-request behavior (shard/openai_api.py:543-563)
        import contextlib

        lock = (
            contextlib.nullcontext()
            if getattr(generator, "concurrent", False)
            else self.gen_lock
        )
        # request-lifecycle tracing: begin a timeline under the client-
        # visible request id and hand it down the stack — the scheduler,
        # disagg coordinator, replica router and KV paths all stamp spans
        # onto it. The server owns the handle, so it (not the scheduler)
        # retires it into the flight-recorder ring when the response ends.
        trace = (
            tracing.begin(rid)
            if getattr(generator, "supports_trace", False) else None
        )
        if trace is not None:
            gen_kwargs["_trace"] = trace
        try:
            with lock:
                if params["stream"]:
                    self._stream(
                        rid, obj + ".chunk", model_name, generator, tokenizer,
                        prompt_ids, stop_id_sequences, eos, chat, gen_kwargs,
                        soft_timeout, trace=trace,
                    )
                else:
                    self._complete(
                        rid, obj, model_name, generator, tokenizer, prompt_ids,
                        stop_id_sequences, eos, chat, params["logprobs"],
                        gen_kwargs, soft_timeout, trace=trace,
                    )
        finally:
            tracing.finish(trace)

    def _complete(
        self, rid, obj, model_name, generator, tokenizer, prompt_ids,
        stop_id_sequences, eos, chat, want_logprobs, gen_kwargs,
        soft_timeout=None, trace=None,
    ):
        # non-streaming path (ref handle_completion shard/openai_api.py:357-434)
        tokens: list[int] = []
        token_logprobs: list[float] = []
        top_logprobs: list[dict] = []
        finish_reason = "length"
        t_start = time.perf_counter()
        t_first = None
        it = self._generate(generator, prompt_ids, gen_kwargs, soft_timeout)
        try:
            for token, logprobs in it:
                if t_first is None:
                    t_first = time.perf_counter()
                if eos is not None and token == eos:
                    finish_reason = "stop"
                    break
                tokens.append(token)
                if want_logprobs > 0:
                    if isinstance(logprobs, TokenLogprobs):
                        # computed on device in the decode block (lax.top_k);
                        # nothing vocab-sized ever reaches the host
                        token_logprobs.append(logprobs.chosen)
                        top_logprobs.append(
                            {
                                int(i): float(v)
                                for i, v in zip(
                                    logprobs.top_indices[:want_logprobs],
                                    logprobs.top_values[:want_logprobs],
                                )
                            }
                        )
                    else:  # engines still yielding the full (B, V) row
                        row = np.asarray(logprobs[0])
                        token_logprobs.append(float(row[token]))
                        top_idx = np.argsort(row)[::-1][:want_logprobs]
                        top_logprobs.append({int(i): float(row[i]) for i in top_idx})
                stop = stopping_criteria(tokens, stop_id_sequences, None)
                if stop.stop_met:
                    if stop.trim_length:
                        tokens = tokens[: -stop.trim_length]
                        if want_logprobs > 0:
                            token_logprobs = token_logprobs[: -stop.trim_length]
                            top_logprobs = top_logprobs[: -stop.trim_length]
                    finish_reason = "stop"
                    break
        finally:
            # deterministic cancellation (stop-word / eos early exit, or an
            # exception): closing the generator flips the scheduler
            # request's cancelled flag NOW, not at some later GC, so the
            # slot and its KV pages are reclaimed within a tick
            it.close()
        self._record(len(prompt_ids), len(tokens), t_start, t_first)
        text = tokenizer.decode(tokens)
        logprobs_payload = None
        if want_logprobs > 0:
            logprobs_payload = {
                "token_logprobs": token_logprobs,
                "top_logprobs": top_logprobs,
                "tokens": tokens,
            }
        usage = {
            "prompt_tokens": len(prompt_ids),
            "completion_tokens": len(tokens),
            "total_tokens": len(prompt_ids) + len(tokens),
        }
        self._json(
            200,
            self._make_response(
                rid=rid, object_type=obj, model=model_name, text=text,
                finish_reason=finish_reason, usage=usage, logprobs=logprobs_payload,
            ),
        )

    def _stream(
        self, rid, obj, model_name, generator, tokenizer, prompt_ids,
        stop_id_sequences, eos, chat, gen_kwargs, soft_timeout=None,
        trace=None,
    ):
        # SSE with partial-stop-word buffering (ref handle_stream
        # shard/openai_api.py:436-505): if the current token tail could still
        # grow into a stop sequence, hold the text back.
        t_start = time.perf_counter()
        it = self._generate(generator, prompt_ids, gen_kwargs, soft_timeout)
        # Prime the FIRST token before committing to a 200/SSE response:
        # instant failures — queue full (429), TTFT timeout (504), bad
        # request discovered at admission (400), every replica down (503) —
        # surface as proper status codes instead of a broken event stream.
        try:
            head = next(it)
        except StopIteration:
            head = None
        except BaseException:
            it.close()
            raise
        t_first = time.perf_counter() if head is not None else None

        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        # SSE has no Content-Length; end-of-stream is signalled by closing
        # the connection after [DONE].
        self.send_header("Connection", "close")
        for k, v in (getattr(self, "_resp_headers", None) or {}).items():
            self.send_header(k, str(v))
        self._cors()
        self.end_headers()

        def emit(payload: dict):
            with tracing.bind(trace):
                inject("server.sse_write")  # fault harness: kill a live
                # stream (record_fault stamps the bound timeline first)
            buf = f"data: {json.dumps(payload)}\n\n".encode()
            if trace is not None:
                t0 = time.perf_counter()
                self.wfile.write(buf)
                self.wfile.flush()
                trace.add("sse_write", t0, time.perf_counter(),
                          bytes=len(buf))
            else:
                self.wfile.write(buf)
                self.wfile.flush()

        if chat:
            emit(
                self._make_response(
                    rid=rid, object_type=obj, model=model_name,
                    delta={"role": "assistant", "content": ""},
                )
            )

        def token_stream():
            if head is not None:
                yield head
            yield from it

        detok = StreamingDetokenizer(tokenizer)
        tokens: list[int] = []
        in_flight: list[int] = []  # tokens withheld due to stop-prefix overlap
        finish_reason = "length"
        timed_out: Optional[RequestTimeoutError] = None
        try:
            for token, _ in token_stream():
                if eos is not None and token == eos:
                    finish_reason = "stop"
                    break
                tokens.append(token)
                stop = stopping_criteria(tokens, stop_id_sequences, None)
                if stop.stop_met:
                    finish_reason = "stop"
                    in_flight.clear()
                    break
                if any(sequence_overlap(tokens, s) for s in stop_id_sequences):
                    in_flight.append(token)
                    continue
                for t in in_flight:
                    detok.add_token(t)
                in_flight.clear()
                detok.add_token(token)
                if detok.last_segment:
                    delta = {"content": detok.last_segment}
                    emit(
                        self._make_response(
                            rid=rid, object_type=obj, model=model_name,
                            **({"delta": delta} if chat else {"text": detok.last_segment}),
                        )
                    )
        except RequestTimeoutError as e:
            # headers are gone — close the stream with a final error event
            # instead of a raw connection drop
            timed_out = e
            in_flight.clear()
        finally:
            # deterministic cancellation: whatever path leaves this loop
            # (stop word, eos, timeout, BrokenPipeError from a vanished
            # client), the scheduler request's cancelled flag flips NOW and
            # its slot/KV pages are reclaimed within a tick
            it.close()
        self._record(len(prompt_ids), len(tokens), t_start, t_first)
        if timed_out is not None:
            emit({"error": {"message": str(timed_out), "type": "timeout_error",
                            "code": 504}})
            self.wfile.write(b"data: [DONE]\n\n")
            self.wfile.flush()
            self.close_connection = True
            return
        # a length-finished run that was still buffering emits the buffered
        # tokens — they never completed a stop sequence
        for t in in_flight:
            detok.add_token(t)
        detok.finalize()
        if detok.last_segment:
            emit(
                self._make_response(
                    rid=rid, object_type=obj, model=model_name,
                    **(
                        {"delta": {"content": detok.last_segment}}
                        if chat
                        else {"text": detok.last_segment}
                    ),
                )
            )
        emit(
            self._make_response(
                rid=rid, object_type=obj, model=model_name,
                **({"delta": {}} if chat else {"text": ""}),
                finish_reason=finish_reason,
            )
        )
        self.wfile.write(b"data: [DONE]\n\n")
        self.wfile.flush()
        self.close_connection = True

    # -------------------------------------------------------- observability
    def _generate(self, generator, prompt_ids, gen_kwargs, soft_timeout=None):
        """Generation wrapped in a JAX profiler trace when --profile-dir is
        set (SURVEY §5: the profiling layer the reference lacks).

        ``soft_timeout`` is the fallback total-generation bound for engines
        without scheduler-side deadline support: checked between tokens, so
        it bounds a long generation but cannot interrupt a wedged step."""
        with profile_trace(self.profile_dir):
            it = generator.generate_step(prompt_ids, **gen_kwargs)
            if soft_timeout is None:
                yield from it
                return
            t0 = time.monotonic()
            try:
                for item in it:
                    yield item
                    if time.monotonic() - t0 > soft_timeout:
                        raise RequestTimeoutError(
                            "total", time.monotonic() - t0, soft_timeout
                        )
            finally:
                it.close()

    def _record(self, n_prompt, n_gen, t_start, t_first):
        end = time.perf_counter()
        ttft = (t_first - t_start) if t_first else 0.0
        decode_time = (end - t_first) if t_first else 0.0
        self.metrics.record_request(
            prompt_tokens=n_prompt,
            generation_tokens=n_gen,
            ttft_s=ttft,
            decode_tps=(max(n_gen - 1, 0) / decode_time) if decode_time > 0 else 0.0,
        )

    # ------------------------------------------------------------ handlers
    def _handle_chat_completion(self, body, params, generator, tokenizer):
        prompt_ids = self._chat_prompt(body, tokenizer)
        self._run(body, params, generator, tokenizer, list(prompt_ids), chat=True)

    def _handle_text_completion(self, body, params, generator, tokenizer):
        prompt = body.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            return self._error(400, "prompt must be a non-empty string")
        prompt_ids = tokenizer.encode(prompt)
        self._run(body, params, generator, tokenizer, list(prompt_ids), chat=False)


def make_server(
    provider: ModelProvider,
    host: str = "127.0.0.1",
    port: int = 8080,
    profile_dir: Optional[str] = None,
    api_key: Optional[str] = None,
    request_timeout: Optional[float] = None,
    ttft_timeout: Optional[float] = None,
):
    handler = type(
        "BoundAPIHandler",
        (APIHandler,),
        {
            "provider": provider,
            "gen_lock": make_lock("APIHandler.gen_lock"),
            "metrics": ServingMetrics(
                batcher_fn=lambda: provider.generator
                if getattr(provider.generator, "concurrent", False)
                else None,
                spec_fn=lambda: provider.generator
                if hasattr(provider.generator, "accepted_tokens")
                else None,
                weight_store_fn=weight_store,
                prefix_store_fn=lambda: getattr(
                    provider, "prefix_store_obj", None
                ),
                pod_stats_fn=lambda: (
                    provider.pod_fleet.pod_stats()
                    if getattr(provider, "pod_fleet", None) is not None
                    else None
                ),
                kv_share_fn=lambda: (
                    provider.kv_share_stats()
                    if getattr(provider, "kv_share_map", None) is not None
                    else None
                ),
                kv_compress_fn=lambda: provider.kv_compress_stats(),
            ),
            "profile_dir": profile_dir,
            "api_key": api_key,
            "request_timeout": request_timeout,
            "ttft_timeout": ttft_timeout,
        },
    )
    return ThreadingHTTPServer((host, port), handler)


def main(argv=None):
    import argparse

    from mlx_sharding_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = argparse.ArgumentParser(description="OpenAI-compatible API server")
    parser.add_argument("--model", default=None, help="default model path/repo")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8080)
    parser.add_argument("--start-layer", type=int, default=None)
    parser.add_argument("--end-layer", type=int, default=None)
    parser.add_argument("--num-stages", type=int, default=None,
                        help="pipeline stages on the local mesh (fused SPMD engine)")
    parser.add_argument("--stage-bounds", default=None,
                        help="pipeline stage bounds, e.g. '0-14,14-27' "
                        "(uneven splits and MoE/dense mixes allowed)")
    parser.add_argument("--engine", choices=("fused", "chained"), default="fused",
                        help="pipeline engine for --stage-bounds: fused SPMD "
                        "(one program per token, default) or chained per-stage "
                        "programs")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel width within each pipeline "
                        "stage")
    parser.add_argument("--keep-quantized", action="store_true",
                        help="keep 4-bit checkpoint weights packed in HBM "
                        "(fused dequant-matmul) instead of dequantizing on "
                        "load — 4x decode weight bandwidth")
    parser.add_argument("--ep", type=int, default=1,
                        help="expert-parallel width within each pipeline "
                        "stage (MoE models)")
    parser.add_argument("--concurrent", type=int, default=1,
                        help="continuous-batching slots: serve up to N "
                        "requests interleaved in one fused engine (N>1 "
                        "replaces the per-request generation lock)")
    parser.add_argument("--paged-pool", type=int, default=None,
                        help="with --concurrent: share a KV pool of N pages "
                             "across slots (reservation admission) instead "
                             "of dense per-slot max-seq allocations")
    parser.add_argument("--page-size", type=int, default=None,
                        help="KV page size in tokens (default: the prefill "
                             "chunk); must be a chunk multiple")
    parser.add_argument("--paged-attention",
                        choices=("auto", "ragged", "gather"), default="auto",
                        help="with --paged-pool: decode-attention path over "
                             "the page pool. 'ragged' attends in place via "
                             "the slot page tables (no per-tick gather/"
                             "scatter of the cache), 'gather' keeps the "
                             "contiguous per-slot view, 'auto' (default) "
                             "picks ragged where the engine supports it "
                             "(pp=1, tp=ep=1)")
    parser.add_argument("--kv-dtype", choices=("bf16", "int8"), default=None,
                        help="with --paged-pool: KV-pool storage. 'int8' "
                             "stores quantized codes plus a per-row-per-head "
                             "float32 scale (~2x the tokens per page of "
                             "bf16); default keeps the cache dtype")
    parser.add_argument("--kv-share-map", default=None, metavar="PATH",
                        help="with --paged-pool: layer-wise KV sharing "
                             "(KVSharer) — path to a calibrated share-map "
                             "artifact from cli/kv_share_calibrate.py. "
                             "Pools allocate one physical (k,v) buffer per "
                             "share GROUP (~25-50%% fewer KV bytes at the "
                             "calibrated sharing ratio); exported blocks "
                             "carry the map's hash so mismatched layouts "
                             "fail closed at import. Composes with "
                             "--kv-dtype int8, --spill-bytes and "
                             "--prefix-store")
    parser.add_argument("--kv-compress-map", default=None, metavar="PATH",
                        help="with --paged-pool: compressed-latent KV "
                             "transport (kv_compress.py) — path to a "
                             "calibrated low-rank artifact from "
                             "cli/kv_compress_calibrate.py. Exported KV "
                             "page blocks (spill, prefix demotion, disagg "
                             "handoff, pod federation) ship rank-r latent "
                             "coefficients instead of full per-head pages; "
                             "bounded-error, opt-in. MLA-native models "
                             "(DeepSeek-v2 compressed cache mode) compress "
                             "exactly WITHOUT this flag. Requires "
                             "float/bf16 pools (not --kv-dtype int8)")
    parser.add_argument("--kv-compress-rank", type=int, default=None,
                        metavar="R",
                        help="with --kv-compress-map: truncate the "
                             "artifact's nested SVD basis to rank R (a "
                             "cheaper operating point than the calibrated "
                             "rank; more reconstruction error)")
    parser.add_argument("--admission-policy", choices=("fifo", "first_fit"),
                        default="fifo",
                        help="waiting-line policy when a request doesn't fit "
                             "the page pool: strict order vs let smaller "
                             "requests jump a blocked head")
    parser.add_argument("--overcommit", action="store_true",
                        help="with --paged-pool: admit on current page need "
                             "(prompt + one decode block) and grow per "
                             "block, preempting the newest-admitted request "
                             "on pool exhaustion (token-exact resume) — "
                             "higher slot occupancy than reserving every "
                             "request's full prompt+max_tokens need")
    parser.add_argument("--spill-bytes", type=int, default=None,
                        help="with --overcommit or --spill-cold-after: "
                             "host-DRAM budget (bytes) for spilled KV page "
                             "blocks. Preemption/cold-spill exports the "
                             "victim's pages to host memory and resume "
                             "re-imports them — one page scatter instead of "
                             "a full re-prefill; LRU-evicted past the "
                             "budget, falling back to re-prefill")
    parser.add_argument("--spill-cold-after", type=int, default=None,
                        help="with --spill-bytes: proactively spill a "
                             "decode slot whose consumer stopped pulling "
                             "tokens for N scheduler ticks (idle streaming "
                             "session) — its pool pages free up for "
                             "admission and the session resumes "
                             "token-exactly when the consumer catches up")
    parser.add_argument("--kv-prefetch", choices=["on", "off", "auto"],
                        default="auto",
                        help="stage spilled KV blocks host→device BEFORE "
                             "the resume tick (overlapped with decode "
                             "compute), demoting demand import to a counted "
                             "fallback; auto = on whenever --spill-bytes is "
                             "set (default)")
    parser.add_argument("--draft-model", default=None,
                        help="speculative decoding: a small draft model "
                             "proposes --spec-k tokens per round (greedy "
                             "token-exact, sampled distribution-exact). "
                             "Single-chip generator path only.")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="speculation window (with --draft-model)")
    parser.add_argument("--draft", choices=("auto", "off", "ngram", "engine"),
                        default="auto",
                        help="speculative proposal source. 'ngram' drafts "
                             "by prompt-lookup against the stream's own "
                             "prompt+history — no second checkpoint, no "
                             "draft KV, free to enable on every decode "
                             "host; 'engine' uses --draft-model; 'auto' "
                             "(default) keeps the legacy contract: engine "
                             "iff --draft-model, else off")
    parser.add_argument("--spec-window-max", type=int, default=None,
                        help="per-slot ADAPTIVE speculation windows, "
                             "resized each round on an acceptance EWMA "
                             "over the ladder {0,2,4,8} capped here "
                             "(losing slots disable and re-probe). Always "
                             "on for --draft ngram (default cap 8); opt-in "
                             "for --draft engine (without it the engine "
                             "path keeps fixed --spec-k rounds)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="data-parallel serving: N independent engine "
                             "replicas, each on its own devices (stages x tp "
                             "x ep each), least-loaded request routing — "
                             "aggregate throughput scales with N")
    parser.add_argument("--disagg", action="store_true",
                        help="disaggregated prefill/decode serving: split "
                             "the fleet into a prefill pool and a decode "
                             "pool. Each request prefills (and emits its "
                             "first token) on a prefill replica, then its "
                             "KV page block is handed to the least-loaded "
                             "decode replica, which owns the rest of the "
                             "stream — long prefills stop stalling decode "
                             "steady-state. Requires --concurrent; "
                             "--paged-pool makes the handoff a block "
                             "import instead of a re-prefill; handoff "
                             "failures degrade to serve-in-place (never a "
                             "dropped stream)")
    parser.add_argument("--shared-weights", choices=("on", "off", "auto"),
                        default="auto",
                        help="cross-replica shared weights: place ONE "
                             "resident packed param tree per host and have "
                             "every replica (and both disagg pools) alias "
                             "it — fleet weight bytes ~W instead of N*W, "
                             "and an autoscaler spawn costs slot/cache "
                             "setup instead of a checkpoint re-upload. "
                             "Replicas co-locate on one model-parallel "
                             "slice (capacity is then bounded by KV "
                             "memory, not weight copies). auto: on when "
                             "--replicas > 1 or --disagg on a single-host "
                             "fused-engine config; off: always private "
                             "per-replica copies")
    parser.add_argument("--prefill-replicas", type=int, default=1,
                        help="with --disagg: replicas in the prefill pool")
    parser.add_argument("--decode-replicas", type=int, default=1,
                        help="with --disagg: replicas in the decode pool")
    parser.add_argument("--autoscale", action="store_true",
                        help="with --replicas: run the elastic fleet "
                             "controller — spawn extra replicas onto unused "
                             "device slices under sustained queue pressure, "
                             "drain idle ones back down; spawn/drain "
                             "failures degrade to the static fleet (never a "
                             "dropped stream). Control at runtime via POST "
                             "/admin/autoscaler")
    parser.add_argument("--autoscale-min", type=int, default=None,
                        help="autoscaler floor: never drain below this many "
                             "replicas (default: 1)")
    parser.add_argument("--autoscale-max", type=int, default=None,
                        help="autoscaler ceiling (default: every replica the "
                             "device count can hold)")
    parser.add_argument("--autoscale-interval", type=float, default=2.0,
                        help="seconds between autoscaler control ticks")
    parser.add_argument("--autoscale-cooldown", type=float, default=15.0,
                        help="seconds after any scale event (or failed "
                             "attempt) before the next one")
    parser.add_argument("--brownout", choices=("on", "off"), default="on",
                        help="overload brownout ladder: under sustained "
                             "pressure cap max_tokens, shed speculation "
                             "(per-slot lowest-acceptance-first under "
                             "adaptive windows, globally in fixed-K engine "
                             "mode) and tighten admission BEFORE shedding "
                             "with 429; "
                             "level surfaced in /health and the "
                             "X-MST-Brownout-Level response header")
    parser.add_argument("--prompt-cache", action="store_true",
                        help="reuse KV for shared prompt prefixes (chat turns "
                             "re-send their whole history: TTFT becomes "
                             "O(new tokens)). Single-chip generator path, or "
                             "with --concurrent --paged-pool: content-"
                             "addressed page sharing across interleaved "
                             "requests (composes with --coordinator — the "
                             "worker mirrors rebuild the same index from the "
                             "op stream — and with --replicas, one cache per "
                             "replica)")
    parser.add_argument("--prefix-store", action="store_true",
                        help="fleet-wide content-addressed prefix KV store "
                             "(with --concurrent --paged-pool): completed "
                             "prefills register their page-aligned prompt "
                             "prefix under chained chunk digests; later "
                             "requests sharing the prefix lease the pages "
                             "copy-on-write (zero-copy within a replica) or "
                             "import them from the host tier (across "
                             "replicas / after demotion) and prefill only "
                             "the uncovered tail. Subsumes --prompt-cache "
                             "(the two are mutually exclusive); with "
                             "--disagg a full-prefix hit skips the prefill "
                             "pool entirely")
    parser.add_argument("--prefix-store-bytes", type=int, default=None,
                        help="with --prefix-store: host-DRAM budget (bytes) "
                             "for the demoted-prefix tier (default 256 MiB); "
                             "LRU-evicted past the budget, falling back to "
                             "plain prefill")
    parser.add_argument("--prefix-insert-min-hits", type=int, default=1,
                        help="with --prefix-store: a prefix must MISS this "
                             "many times before a completed prefill inserts "
                             "it (damps one-shot prompts; default 1)")
    parser.add_argument("--decode-block", type=int, default=16,
                        help="decode steps fused per program launch (token "
                             "pulls amortize over this many tokens; set 1 "
                             "for strict per-token streaming on a local chip)")
    parser.add_argument("--async-sched", choices=("on", "off", "auto"),
                        default="auto",
                        help="with --concurrent: async tick pipelining — "
                             "dispatch decode block t+1 before harvesting "
                             "block t, overlapping host-side emit/stop/"
                             "admission work with device compute (token "
                             "streams stay bit-identical to sync). 'auto' "
                             "(default) enables it for plain decode AND "
                             "--draft ngram (host-built drafts chain pure "
                             "device-side) and falls back to sync with "
                             "--draft-model or multi-host — the resolution "
                             "reason is logged at startup; 'off' forces "
                             "the sequential tick")
    parser.add_argument("--max-seq", type=int, default=4096)
    parser.add_argument("--prefill-chunk", type=int, default=256)
    parser.add_argument("--request-timeout", type=float, default=None,
                        help="total-generation deadline in seconds (submit "
                             "to last token); expiry cancels the request, "
                             "frees its slot/KV pages and returns HTTP 504 "
                             "(or a final SSE error event). Per-request "
                             "'request_timeout' in the body overrides it")
    parser.add_argument("--ttft-timeout", type=float, default=None,
                        help="time-to-first-token deadline in seconds "
                             "(queue wait + prefill + compile); also the "
                             "default inter-token stall watchdog. Requests "
                             "still queued past it are shed before prefill. "
                             "Per-request 'ttft_timeout' overrides it")
    parser.add_argument("--max-queue", type=int, default=None,
                        help="with --concurrent: admission bound on queued "
                             "requests (per replica); a full queue rejects "
                             "with 429 + Retry-After instead of growing "
                             "without limit under overload")
    parser.add_argument("--api-key", default=None,
                        help="require 'Authorization: Bearer <key>' on the "
                             "/v1/* endpoints (the web UI's API key setting)")
    parser.add_argument("--log-level", default="INFO")
    parser.add_argument("--profile-dir", default=None,
                        help="write JAX profiler traces per request here")
    parser.add_argument("--trace", choices=("off", "sample", "on"),
                        default="off",
                        help="request-lifecycle tracing: record per-request "
                             "span timelines (queue wait, prefill, handoff, "
                             "decode ticks, spill/wake, SSE writes) into a "
                             "bounded flight-recorder ring, exported as "
                             "chrome://tracing JSON via GET /admin/trace/"
                             "{request_id} and /admin/trace/dump. 'sample' "
                             "traces every --trace-sample-th request; 'on' "
                             "traces all; 'off' (default) compiles to "
                             "None-check no-ops on the hot paths")
    parser.add_argument("--trace-buffer", type=int, default=256,
                        help="flight-recorder capacity: completed request "
                             "timelines kept in the ring (oldest evicted); "
                             "incident snapshots (breaker trip, wedge, "
                             "injected fault) preserve theirs separately")
    parser.add_argument("--trace-sample", type=int, default=8,
                        help="with --trace sample: trace every Nth request")
    parser.add_argument("--trace-profile", action="store_true",
                        help="with --trace: open the scheduler tick and "
                             "its phases (mst.tick, mst.<phase>, "
                             "mst.decode_block) as "
                             "jax.profiler.TraceAnnotation, so the host's "
                             "spans sit on the XLA timeline's clock in a "
                             "profiler capture")
    parser.add_argument("--chat-template", default=None,
                        help="jinja chat template (inline, or @/path/to/file) "
                        "overriding the tokenizer's")
    # multi-host (DCN) bring-up — the jax.distributed control plane
    parser.add_argument("--coordinator", default=None,
                        help="host:port of jax.distributed coordinator")
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--pod", action="store_true",
                        help="pod-scale serving: each process runs its own "
                             "host-local fleet on its local devices, "
                             "stitched by the pod gossip plane (weight "
                             "registry, cross-host disagg handoff, pod "
                             "autoscaler) instead of the SPMD mirror — "
                             "requires --coordinator and --num-processes")
    args = parser.parse_args(argv)

    if args.engine == "chained" and not args.stage_bounds:
        parser.error("--engine chained requires --stage-bounds")
    if args.concurrent > 1 and args.engine == "chained":
        parser.error("--concurrent requires the fused engine")
    if (args.tp > 1 or args.ep > 1) and args.engine == "chained":
        parser.error("--tp/--ep require the fused engine")
    if args.pod:
        if not (args.coordinator and (args.num_processes or 1) > 1):
            parser.error("--pod requires --coordinator and --num-processes "
                         "> 1 (the pod gossip plane rides "
                         "jax.distributed)")
        if not args.model:
            parser.error("--pod serving requires --model (every host loads "
                         "its fleet at startup)")
    if args.coordinator and (args.num_processes or 1) > 1 and not args.pod:
        if not args.model:
            parser.error("multi-host serving requires --model (workers load "
                         "the model at startup)")
        if not args.stage_bounds and (args.num_stages or 1) <= 1:
            parser.error("multi-host serving requires a pipeline "
                         "(--num-stages > 1 or --stage-bounds)")
    if args.trace_buffer < 1:
        parser.error("--trace-buffer must be >= 1")
    if args.trace_sample < 1:
        parser.error("--trace-sample must be >= 1")
    if args.trace_profile and args.trace == "off":
        parser.error("--trace-profile requires --trace sample|on")
    logging.basicConfig(level=args.log_level.upper())
    # before the provider builds any engine: batchers resolve the profile
    # bridge once at construction, so the tracer must exist first
    tracing.configure(args.trace, buffer=args.trace_buffer,
                      sample_n=args.trace_sample,
                      profile=args.trace_profile)
    if args.coordinator:
        import jax

        if os.environ.get("JAX_PLATFORMS", "") == "cpu":
            # CPU ranks (the multi-host tests, or a smoke deployment) need
            # an explicit cross-process collectives implementation
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            args.coordinator, num_processes=args.num_processes,
            process_id=args.process_id,
        )
    stage_bounds = None
    if args.stage_bounds:
        stage_bounds = [
            tuple(int(x) for x in part.split("-"))
            for part in args.stage_bounds.split(",")
        ]
    chat_template = args.chat_template
    if chat_template and chat_template.startswith("@"):
        chat_template = Path(chat_template[1:]).read_text()
    if args.draft_model and (
        args.coordinator or args.tp > 1
        or args.ep > 1 or args.stage_bounds or (args.num_stages or 1) > 1
        or args.engine == "chained"
        or args.start_layer is not None or args.end_layer is not None
    ):
        parser.error("--draft-model applies to the single-chip full-model "
                     "generator or to --concurrent serving "
                     "(no --coordinator/--tp/--ep/stage or "
                     "layer-range flags)")
    if args.draft == "engine" and not args.draft_model:
        parser.error("--draft engine needs --draft-model")
    if args.draft_model and args.draft in ("off", "ngram"):
        parser.error(f"--draft {args.draft} conflicts with --draft-model: "
                     "drop one (--draft-model implies the engine proposer)")
    if args.draft == "ngram" and (
        (args.coordinator and (args.num_processes or 1) > 1
         and not args.pod)
        or args.tp > 1 or args.ep > 1 or args.stage_bounds
        or (args.num_stages or 1) > 1 or args.engine == "chained"
        or args.start_layer is not None or args.end_layer is not None
    ):
        parser.error("--draft ngram applies to the single-chip full-model "
                     "generator or to --concurrent serving (the verify "
                     "needs the pp=1 vectorized body; multi-host worker "
                     "mirrors replay plain decode ticks only — run it on "
                     "single-host replicas or --pod hosts instead)")
    if args.spec_window_max is not None:
        if args.spec_window_max < 2:
            parser.error("--spec-window-max must be >= 2")
        if args.draft == "off" or (
            args.draft == "auto" and not args.draft_model
        ):
            parser.error("--spec-window-max needs a speculating server: "
                         "--draft ngram or --draft-model")
    # ---- prompt-prefix reuse flags. --prefix-store (the fleet-wide
    # content-addressed store) SUBSUMES --prompt-cache (engine-local page
    # index): running both would put two owners over the same pool pages,
    # so the pair is rejected outright with a migration hint.
    if args.prefix_store:
        if args.prompt_cache:
            parser.error(
                "--prompt-cache is subsumed by --prefix-store: the fleet-"
                "wide store covers the slot-local prefix cache's reuse and "
                "adds cross-replica sharing and a host tier — drop "
                "--prompt-cache (see README: migrating from --prompt-cache)"
            )
        if args.concurrent <= 1 or not args.paged_pool:
            parser.error("--prefix-store requires --concurrent N (N > 1) "
                         "with --paged-pool (prefix reuse is page-granular)")
        if args.draft_model:
            parser.error("--prefix-store is incompatible with --draft-model "
                         "(the draft cache cannot alias shared prefix pages)")
        if args.coordinator and (args.num_processes or 1) > 1:
            parser.error("--prefix-store is single-host only: store "
                         "admissions rewrite page tables host-side, outside "
                         "the op stream worker ranks mirror")
    elif (args.prefix_store_bytes is not None
          or args.prefix_insert_min_hits != 1):
        parser.error("--prefix-store-bytes/--prefix-insert-min-hits require "
                     "--prefix-store")
    if args.prefix_store_bytes is not None and args.prefix_store_bytes < 1:
        parser.error("--prefix-store-bytes must be a positive byte count")
    if args.prefix_insert_min_hits < 1:
        parser.error("--prefix-insert-min-hits must be >= 1")
    if args.prompt_cache:
        # ONE home for every --prompt-cache rule (this used to be three
        # overlapping conditionals, each re-encoding part of the story —
        # the replicas check below no longer mentions --prompt-cache):
        # concurrent serving needs the paged pool; otherwise the flag
        # means the single-chip full-model generator path, nothing else.
        if args.concurrent > 1:
            if not args.paged_pool:
                parser.error("--prompt-cache with --concurrent requires "
                             "--paged-pool (prefix sharing is "
                             "page-granular)")
        elif (args.coordinator or args.tp > 1 or args.ep > 1
              or args.stage_bounds or (args.num_stages or 1) > 1
              or args.engine == "chained" or args.draft_model
              or args.replicas > 1 or args.disagg
              or args.start_layer is not None
              or args.end_layer is not None):
            parser.error("--prompt-cache applies to the single-chip "
                         "full-model generator path or to --concurrent "
                         "--paged-pool serving (no --coordinator/--tp/--ep/"
                         "stage, layer-range, --draft-model, or fleet "
                         "flags)")
    if args.replicas > 1 and (
        (args.coordinator and not args.pod) or args.engine == "chained"
        or (args.draft_model and args.concurrent <= 1)
        or (args.draft == "ngram" and args.concurrent <= 1)
        or args.start_layer is not None or args.end_layer is not None
    ):
        parser.error("--replicas requires the fused full-model engine path "
                     "(no --coordinator/--engine chained/layer-range flags "
                     "unless --pod; --draft-model/--draft ngram only with "
                     "--concurrent)")
    if args.paged_pool and args.concurrent <= 1:
        parser.error("--paged-pool requires --concurrent N (N > 1)")
    if args.paged_pool and args.engine == "chained":
        parser.error("--paged-pool requires the fused engine")
    if args.page_size and not args.paged_pool:
        parser.error("--page-size requires --paged-pool")
    if args.paged_attention != "auto" and not args.paged_pool:
        parser.error("--paged-attention requires --paged-pool")
    if args.kv_dtype and not args.paged_pool:
        parser.error("--kv-dtype requires --paged-pool")
    if args.kv_share_map:
        if not args.paged_pool:
            parser.error("--kv-share-map requires --paged-pool (sharing "
                         "deduplicates the paged KV pool's layer axis)")
        if args.stage_bounds or (args.num_stages or 1) > 1:
            parser.error("--kv-share-map requires a single-stage engine: "
                         "share groups span the full layer stack, which a "
                         "pipeline stage split cuts")
    if args.kv_compress_map:
        if not args.paged_pool:
            parser.error("--kv-compress-map requires --paged-pool "
                         "(compression rides the paged KV transport path)")
        if args.kv_dtype == "int8":
            parser.error("--kv-compress-map is incompatible with "
                         "--kv-dtype int8: dequantize->project->requantize "
                         "compounds quantization error past the artifact's "
                         "calibrated bound")
        if args.stage_bounds or (args.num_stages or 1) > 1:
            parser.error("--kv-compress-map requires a single-stage "
                         "engine: the calibration spans the full layer "
                         "stack, which a pipeline stage split cuts")
    if args.kv_compress_rank is not None and not args.kv_compress_map:
        parser.error("--kv-compress-rank requires --kv-compress-map")
    if args.admission_policy != "fifo" and not args.paged_pool:
        parser.error("--admission-policy requires --paged-pool")
    if args.overcommit and not args.paged_pool:
        parser.error("--overcommit requires --paged-pool")
    if (args.overcommit and args.coordinator
            and (args.num_processes or 1) > 1 and not args.pod):
        # the sampler-state stash is no longer the blocker (it travels in
        # KVPageBlock / ResumeState now); what remains is that preemption
        # and resume rewrite page tables and free lists host-side, outside
        # the op stream the worker ranks mirror — their page accounting
        # would silently diverge from rank 0's
        parser.error(
            "--overcommit is not supported in multi-host serving: "
            "preemption/resume rewrites page tables and free lists "
            "host-side, outside the op stream worker ranks mirror; run "
            "overcommit on single-host replicas (e.g. behind --replicas) "
            "instead"
        )
    if args.spill_bytes is not None:
        if args.spill_bytes < 1:
            parser.error("--spill-bytes must be a positive byte count")
        if not args.overcommit and args.spill_cold_after is None:
            parser.error("--spill-bytes requires --overcommit or "
                         "--spill-cold-after: the spill tier holds "
                         "preempted or cold-spilled requests' KV page "
                         "blocks")
        if args.draft_model:
            parser.error("--spill-bytes is incompatible with --draft-model "
                         "(speculative slots re-prefill on preemption)")
    if args.spill_cold_after is not None:
        if args.spill_cold_after < 1:
            parser.error("--spill-cold-after must be >= 1 (scheduler ticks)")
        if args.spill_bytes is None:
            parser.error("--spill-cold-after needs a spill tier to spill "
                         "into: set --spill-bytes")
        if args.concurrent <= 1:
            parser.error("--spill-cold-after requires --concurrent N "
                         "(N > 1): cold-slot residency is a continuous-"
                         "batching policy")
    if args.kv_prefetch == "on" and args.spill_bytes is None:
        parser.error("--kv-prefetch on needs a spill tier to prefetch "
                     "from: set --spill-bytes")
    if args.disagg:
        if args.concurrent <= 1:
            parser.error("--disagg requires --concurrent N (N > 1): only "
                         "the continuous batcher can park a prefill-only "
                         "request and resume it from a KV page block")
        if args.replicas > 1:
            parser.error("--disagg replaces --replicas: size the pools "
                         "with --prefill-replicas/--decode-replicas")
        if (args.coordinator and not args.pod) or args.engine == "chained":
            parser.error("--disagg requires the single-host fused engine "
                         "path (no --coordinator/--engine chained) — or "
                         "--pod, where each host runs its own disagg pools")
        if args.draft_model:
            parser.error("--disagg is incompatible with --draft-model: a "
                         "resumed stream's draft KV cannot be rebuilt from "
                         "the handed-off block (only the target's pages "
                         "travel). Use --draft ngram — prompt-lookup "
                         "drafts need no draft KV, so decode replicas "
                         "speculate on resumed streams too")
        if args.prefill_replicas < 1 or args.decode_replicas < 1:
            parser.error("--prefill-replicas/--decode-replicas must be "
                         "positive integers")
        if args.autoscale and (args.autoscale_min is not None
                               or args.autoscale_max is not None):
            parser.error("--autoscale-min/--autoscale-max do not apply to "
                         "--disagg: each pool's floor is its initial size "
                         "and its ceiling is the free device slices")
    elif args.prefill_replicas != 1 or args.decode_replicas != 1:
        parser.error("--prefill-replicas/--decode-replicas require "
                     "--disagg")
    if args.autoscale and args.replicas <= 1 and not args.disagg:
        parser.error("--autoscale requires --replicas N (N > 1) or "
                     "--disagg: only a ReplicaSet fleet can grow or shrink")
    if not args.autoscale and (
        args.autoscale_min is not None or args.autoscale_max is not None
    ):
        parser.error("--autoscale-min/--autoscale-max require --autoscale")
    if args.autoscale_min is not None and args.autoscale_min < 1:
        parser.error("--autoscale-min must be a positive integer")
    if (
        args.autoscale_min is not None and args.autoscale_max is not None
        and args.autoscale_max < args.autoscale_min
    ):
        parser.error("--autoscale-max must be >= --autoscale-min")
    if args.autoscale_interval <= 0 or args.autoscale_cooldown < 0:
        parser.error("--autoscale-interval must be > 0 and "
                     "--autoscale-cooldown >= 0")
    if args.shared_weights == "on":
        if (args.coordinator or (args.num_processes or 1) > 1) \
                and not args.pod:
            parser.error("--shared-weights on is single-host only: worker "
                         "ranks hold their own device grids, there is no "
                         "one resident tree for them to alias (--pod hosts "
                         "each alias their own local tree)")
        if args.engine == "chained":
            parser.error("--shared-weights on requires the fused engine "
                         "path (chained stage processes each own their "
                         "stage's weights)")
        if args.replicas <= 1 and not args.disagg:
            parser.error("--shared-weights on requires --replicas N "
                         "(N > 1) or --disagg: with one engine there is "
                         "nothing to alias")
    if args.max_queue is not None:
        if args.max_queue < 1:
            parser.error("--max-queue must be a positive integer")
        if args.concurrent <= 1:
            parser.error("--max-queue requires --concurrent N (N > 1): only "
                         "the continuous batcher has a submit queue to bound")
    if args.async_sched != "auto" and args.concurrent <= 1:
        parser.error("--async-sched requires --concurrent N (N > 1): only "
                     "the continuous batcher has a tick loop to pipeline")
    if args.async_sched == "on" and args.draft_model:
        parser.error("--async-sched on is incompatible with --draft-model "
                     "(speculative rounds harvest per-round accept counts); "
                     "use 'auto'")
    if args.async_sched == "on" and args.coordinator and (
        args.num_processes or 1
    ) > 1 and not args.pod:
        parser.error("--async-sched on is not supported in multi-host "
                     "serving (worker mirrors replay the op stream per "
                     "broadcast tick); use 'auto'")
    for flag, val in (("--request-timeout", args.request_timeout),
                      ("--ttft-timeout", args.ttft_timeout)):
        if val is not None and val <= 0:
            parser.error(f"{flag} must be a positive number of seconds")
    multihost = (bool(args.coordinator) and (args.num_processes or 1) > 1
                 and not args.pod)
    provider = ModelProvider(
        args.model, start_layer=args.start_layer, end_layer=args.end_layer,
        num_stages=args.num_stages, stage_bounds=stage_bounds,
        engine=args.engine, concurrent=args.concurrent, multihost=multihost,
        tp=args.tp, ep=args.ep,
        max_seq=args.max_seq, prefill_chunk=args.prefill_chunk,
        chat_template=chat_template, keep_quantized=args.keep_quantized,
        decode_block=args.decode_block, paged_pool=args.paged_pool,
        page_size=args.page_size, paged_attention=args.paged_attention,
        kv_dtype=args.kv_dtype,
        kv_share_map=args.kv_share_map,
        kv_compress_map=args.kv_compress_map,
        kv_compress_rank=args.kv_compress_rank,
        admission_policy=args.admission_policy,
        overcommit=args.overcommit,
        spill_bytes=args.spill_bytes,
        spill_cold_after=args.spill_cold_after,
        kv_prefetch=args.kv_prefetch,
        draft_model=args.draft_model, spec_k=args.spec_k,
        draft=args.draft, spec_window_max=args.spec_window_max,
        prompt_cache=args.prompt_cache, replicas=args.replicas,
        prefix_store=args.prefix_store,
        prefix_store_bytes=args.prefix_store_bytes,
        prefix_insert_min_hits=args.prefix_insert_min_hits,
        max_queue=args.max_queue,
        async_sched=args.async_sched,
        autoscale=args.autoscale,
        autoscale_min=args.autoscale_min,
        autoscale_max=args.autoscale_max,
        autoscale_interval=args.autoscale_interval,
        autoscale_cooldown=args.autoscale_cooldown,
        brownout=args.brownout == "on",
        disagg=args.disagg,
        prefill_replicas=args.prefill_replicas,
        decode_replicas=args.decode_replicas,
        shared_weights=args.shared_weights,
        pod=args.pod,
    )
    if multihost:
        import jax

        if jax.process_index() > 0:
            # worker rank: no HTTP — mirror rank 0's step sequence until
            # shutdown (the reference's per-machine shard server,
            # /root/reference/shard/main.py:4-14, without the RPC surface)
            logger.info("worker rank %d serving", jax.process_index())
            if args.concurrent > 1:
                from mlx_sharding_tpu.parallel.multihost import (
                    serve_worker_batched,
                )

                serve_worker_batched(
                    provider.generator,
                    decode_block=min(8, args.decode_block),
                    prefix_cache=provider.prefix_cache_enabled,
                )
            else:
                from mlx_sharding_tpu.parallel.multihost import serve_worker

                serve_worker(provider.generator)
            return
    server = make_server(provider, args.host, args.port,
                         profile_dir=args.profile_dir, api_key=args.api_key,
                         request_timeout=args.request_timeout,
                         ttft_timeout=args.ttft_timeout)
    logger.info("serving on http://%s:%d", args.host, args.port)

    def _stop(signum, frame):
        # shutdown() waits for serve_forever to return, which it cannot do
        # from the thread serve_forever runs on — hand it to another one
        threading.Thread(
            target=server.shutdown, name="mst-server-stop", daemon=True
        ).start()

    # SIGTERM/SIGINT end the serve loop and close the generator (scheduler
    # thread, device state): the process exits 0 and the next one can have
    # the chip
    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    try:
        server.serve_forever()
    finally:
        server.server_close()
        if hasattr(provider.generator, "close"):
            provider.generator.close()
    logger.info("server stopped")


if __name__ == "__main__":
    main()
