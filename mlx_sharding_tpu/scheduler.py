"""Continuous batching scheduler over the fused engine's microbatch axis.

The reference serializes requests entirely — one request owns the whole
pipeline until it finishes (single-threaded stdlib HTTP front end,
ref: shard/openai_api.py:543-563). Round 1 of this repo kept that behavior
(a generation lock). This module replaces it with slot-level continuous
batching, the thing the fused engine's ``M`` axis was designed for:

- every microbatch slot holds an independent request with its own KV-cache
  offset, sampler params, PRNG key and repetition window;
- a single scheduler thread owns the engine and loops: admit pending
  requests into free slots (chunked prefill that leaves other slots'
  state untouched), then run ONE fused decode step advancing every active
  slot by one token;
- tokens stream out through per-request queues; a slot is reclaimed when
  its request hits max_tokens or its consumer disappears (client
  disconnect / stop sequence matched by the server layer).

Determinism: each slot samples with its own PRNG-key chain seeded from the
request's seed, so a request's token stream is identical whether it ran
alone or interleaved with others (tested in tests/test_scheduler.py).
One carve-out: with a draft engine attached, a SAMPLED request's key chain
advances per speculative round (3 splits) vs per plain step (1 split), and
a neighbor that pauses speculation for a tick (want_logprobs, or within K
of max_seq — see _spec_ok) shifts where those rounds fall — so a sampled
stream is replay-stable only among spec-compatible neighbors. Every stream
remains distribution-exact regardless, and GREEDY streams never consume
keys, so their token-exactness holds unconditionally.

Async tick pipelining (``async_sched``): the decode loop can run
double-buffered — dispatch decode block t+1 (a pure device-side state
chain; last_tok/cache/recent/keys/active never round-trip through the
host) BEFORE harvesting block t's tokens, so the blocking ``device_get``
of an already-finished block overlaps the next block's compute and all
host-side work (emit, stop/cancel, admission bookkeeping) runs while the
device is busy. Token streams are bit-identical to sync mode: the same
jitted block programs consume the same device state chain in the same
order, and per-slot PRNG/repetition state is untouched by neighbors. The
cost is a one-tick control lag — a slot that finishes during block t
still participates in the in-flight block t+1 (its lookahead tokens are
dropped host-side, its pages are retired only at t+1's harvest, and its
paged-KV overrun is bounded to one decode block by the doubled
``_grow_ahead``) — and every host-visible state transition (admission
prefill, preemption, pool-pressure growth, shutdown) must quiesce the
in-flight block first.
"""

from __future__ import annotations

import hashlib
import json
import logging
import math
import queue
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu import diffusion, tracing
from mlx_sharding_tpu.analysis.runtime import (
    make_lock,
    note_acquire,
    note_release,
)
from mlx_sharding_tpu.cache import (
    KVCache,
    export_pool_pages,
    import_pool_pages,
    has_recurrent_state,
    has_slot_state,
    refuse_recurrent,
    rewind_slot_offset,
)
from mlx_sharding_tpu.generate import (
    TokenLogprobs,
    block_lp_outputs,
    block_token_logprobs,
)
from mlx_sharding_tpu.kv_transfer import KVSpillTier, export_block, import_block
from mlx_sharding_tpu.page_pool import PagePool
from mlx_sharding_tpu.resilience import (
    Deadlines,
    HandoffReadyError,
    QueueFullError,
    ReplicaDrainingError,
    RequestMigratedError,
    RequestTimeoutError,
    ResumeState,
)
from mlx_sharding_tpu.testing.faults import inject
from mlx_sharding_tpu.utils.clock import MONOTONIC, WALL_SLEEP, Clock, SleepFn
from mlx_sharding_tpu.utils.observability import (
    Histogram,
    ITL_BUCKETS_S,
    LATENCY_BUCKETS_S,
)
from mlx_sharding_tpu.sample import (
    SamplerParams,
    make_sampler_params,
    sampler_params_host,
    seed_key_row,
    sample_token_batched,
    stack_sampler_params,
)
from mlx_sharding_tpu.speculative import (
    AcceptanceTracker,
    NgramDraftProposer,
)


@dataclass(eq=False)  # identity semantics: requests key the spill tier
class _Request:
    prompt: np.ndarray  # (T,) int32
    sp: SamplerParams
    seed: int
    max_tokens: int
    rep_context: int
    want_logprobs: bool = False
    out: queue.Queue = field(default_factory=lambda: queue.Queue())
    cancelled: bool = False
    # per-request deadlines (resilience.Deadlines) — None = unbounded, the
    # seed behavior; host-side only, never broadcast to worker mirrors
    deadlines: Optional[Deadlines] = None
    slot: int = -1
    produced: int = 0
    prefill_pos: int = 0  # next prompt index to prefill; admission is chunked
    # draft-engine prefill position (speculative CB): tracked separately —
    # a prefix-cache hit starts the TARGET past the reused pages while the
    # draft, which has no page sharing, prefills the whole prompt from 0
    draft_pos: int = 0
    # target prefill logits stashed while the draft catches up
    _last_logits: Optional[object] = None
    # raw sampler request, kept so multi-host serving can broadcast the
    # request verbatim and workers rebuild an identical SamplerParams
    temperature: float = 0.0
    top_p: float = 1.0
    repetition_penalty: Optional[float] = None
    logit_bias: Optional[dict] = None
    # prefix-cache scratch: rolling page keys (memoized for the request's
    # lifetime) and the chain _fits matched, consumed by _assign_slot in the
    # same admission pass
    _pkeys: Optional[list] = None
    _chain: Optional[list] = None
    # prefix-STORE scratch (fleet-wide content-addressed reuse): the chained
    # chunk digests (memoized like _pkeys), the ("device"|"host", cover)
    # plan _fits resolved, and the held PrefixLease while the slot maps
    # shared store pages — released exactly once on every exit path
    _sdigests: Optional[list] = None
    _splan: Optional[tuple] = None
    _please: Optional[object] = None
    # pod-federated prefix fetch state: None = never consulted, "pending" =
    # a background fetch is in flight (admission holds the request so the
    # prefix isn't redundantly prefilled), "done" = resolved either way.
    # _fits only READS the flag — every federation call runs off the tick
    # path in _pod_fetch_waiting (MST115)
    _podfetch: Optional[str] = None
    # over-commit admission state: order ticket (oldest admitted request is
    # never preempted), tokens emitted since the last (re)admission (folded
    # into the prompt on preemption so resume re-prefills them), and the
    # stashed device-side sampler state for token-exact resume
    admit_seq: Optional[int] = None
    history: list = field(default_factory=list)
    resume_keys: Optional[np.ndarray] = None
    resume_recent: Optional[np.ndarray] = None
    # KV migration state: ``spilled`` marks a KVPageBlock waiting in the
    # batcher's spill tier (preemption), ``_block`` carries a block handed
    # in directly (cross-replica migration via generate_step(_resume=…))
    spilled: bool = False
    _block: Optional[object] = None
    # disaggregated serving: emit the first token, then end the stream
    # with HandoffReadyError(ResumeState) instead of entering decode
    prefill_only: bool = False
    # cold-slot detection scratch: consumed tokens observed at the last
    # recency scan (produced - out.qsize()) and ticks the count has been
    # stagnant with a backlog — the consumer stopped pulling, the engine
    # keeps decoding for nobody
    _consumed_seen: int = 0
    _cold_ticks: int = 0
    # request-lifecycle tracing (tracing.py): the bound RequestTrace (None
    # when tracing is off or the request is unsampled — every hot-path site
    # guards on that), whether THIS batcher began the trace (and so must
    # retire it into the flight recorder at finish), and perf_counter
    # stamps feeding the queue-wait / inter-token histograms
    _trace: Optional[object] = None
    _trace_own: bool = False
    _t_submit: float = 0.0
    _t_join: float = 0.0  # slot claimed (mst_join_seconds runs from here)
    _t_last_emit: float = 0.0
    # diffusion over blocks: positions of the slot's NEXT committed block
    # that are prompt (the P mod L tokens no whole block held; 0 after it)
    _block_skip: int = 0


def _pack_i32(*parts) -> np.ndarray:
    """A program's host-made values as ONE int32 array: every part is a
    numpy value of a 32-bit dtype, laid end to end by its bits (a float32
    or uint32 part is read back with ``_Unpack.take(..., dtype)`` bit for
    bit). One array because each array argument of a dispatch is one more
    point at which the calling thread lets go of the interpreter lock."""
    parts = [np.ravel(p) for p in parts]
    if any(p.dtype.itemsize != 4 for p in parts):
        raise TypeError(f"32-bit parts only: {[p.dtype for p in parts]}")
    return np.concatenate([p.view(np.int32) for p in parts])


class _Unpack:
    """Reads a ``_pack_i32`` argument back inside the jitted program, part
    by part in the order it was packed."""

    def __init__(self, packed):
        self.packed, self.at = packed, 0

    def take(self, shape=(), dtype=jnp.int32):
        n = math.prod(shape)
        part = self.packed[self.at:self.at + n]
        self.at += n
        return jax.lax.bitcast_convert_type(part, dtype).reshape(shape)


@dataclass
class _InflightBlock:
    """A dispatched-but-unharvested decode block: the device-side output
    futures plus the host-side snapshot needed to emit its tokens later."""

    outs: object                     # block output futures (tokens [+ lp])
    live: list                       # [(slot, req)] snapshot at dispatch
    want_lp: bool
    prev_tok: Optional[object] = None  # block's first input (draft replay)
    seq: int = 0                     # block number (mst.decode_block's seq)
    positions: int = 0               # steps x live rows: tokens it computes
    ticket: int = 0                  # the tick account's name for it


@dataclass
class _InflightSpec:
    """A dispatched-but-unharvested speculative round: the (count, gs)
    output futures plus the host-side plan needed to emit, account and
    train the acceptance tracker at harvest. The async ngram tick keeps at
    most one of these in flight (same double-buffer slot as
    :class:`_InflightBlock`)."""

    outs: object                     # (count (M,), gs (K, M)) futures
    live: list                       # [(slot, req)] snapshot at dispatch
    wins: dict                       # slot → policy window used this round
    wcaps: object                    # np (M,) effective per-slot caps
    K: int                           # round width (max live window)
    # optimistic continuation per slot (the proposals, assumed accepted):
    # while THIS round is in flight, the next async dispatch appends these
    # to the slot's host-visible history so its n-gram lookup sees an
    # up-to-date tail. A wrong guess only costs that round's acceptance —
    # the verify never trusts proposals, so exactness is unaffected.
    guess: dict = field(default_factory=dict)
    ticket: int = 0                  # the tick account's name for it


# Retry-After clamps for 429 sheds: the estimate comes from the OBSERVED
# completion rate (below), not a fixed constant, bounded so a mis-sampled
# rate can neither tell clients "come back now" nor park them for minutes.
RETRY_AFTER_FLOOR_S = 1.0
RETRY_AFTER_CEIL_S = 30.0
RETRY_AFTER_WINDOW_S = 30.0


def estimate_retry_after(
    backlog: int,
    finish_times,
    now: float,
    *,
    window_s: float = RETRY_AFTER_WINDOW_S,
    floor_s: float = RETRY_AFTER_FLOOR_S,
    ceil_s: float = RETRY_AFTER_CEIL_S,
) -> float:
    """When should a shed client retry? ``backlog`` is how many requests
    must finish before the queue has room again; ``finish_times`` are
    monotonic completion stamps (any iterable, typically the batcher's
    bounded deque). The drain rate is completions-in-window / window-span;
    the estimate is ``backlog / rate``, clamped to [floor_s, ceil_s].

    Zero-drain edge: with no completion inside the window the queue is not
    draining at all — the honest answer is the ceiling, not the floor (a
    constant 1s would tell every shed client to hammer a wedged server)."""
    recent = [t for t in finish_times if now - t <= window_s]
    if not recent:
        return ceil_s
    span = max(now - min(recent), 1e-3)
    rate = len(recent) / span
    return min(ceil_s, max(floor_s, backlog / rate))


class ContinuousBatcher:
    """Drives a :class:`PipelineEngine` (built with ``microbatches=M``,
    ``batch=1``) as an M-slot continuous-batching server backend.

    ``generate_step`` has the same contract as ``Generator.generate_step`` /
    ``PipelineEngine.generate_step`` — the API server uses it unchanged, but
    without the global generation lock (``concurrent = True``).
    """

    concurrent = True
    # generate_step accepts request_timeout/ttft_timeout/stall_timeout and
    # enforces them scheduler-side; the server checks this attr before
    # forwarding deadline kwargs (plain Generator/PipelineEngine lack them)
    supports_deadlines = True
    # generate_step accepts _resume=ResumeState — the dispatcher only
    # re-places migrated/crashed streams onto engines that advertise this
    supports_resume = True
    # generate_step accepts _prefill_only=True (disaggregated serving):
    # the stream delivers the first token, then ends with
    # HandoffReadyError carrying the request's ResumeState
    supports_prefill_only = True
    # generate_step accepts _trace=RequestTrace (tracing.py): the server
    # (or disagg coordinator) binds one span timeline through the whole
    # request path; without one the scheduler self-begins on the process
    # tracer when tracing is enabled
    supports_trace = True

    def __init__(self, engine, *, repetition_window: int = 64, decode_block: int = 8,
                 policy: str = "fifo", prefix_cache: bool = False,
                 overcommit: bool = False, draft_engine=None, spec_k: int = 4,
                 draft: str = "auto", spec_window_max: Optional[int] = None,
                 spec_clock=None,
                 max_queue: Optional[int] = None, async_sched: str = "auto",
                 spill_bytes: Optional[int] = None,
                 spill_cold_after: Optional[int] = None,
                 kv_prefetch: str = "auto",
                 prefix_store=None, clock: Clock = MONOTONIC,
                 sleep: SleepFn = WALL_SLEEP):
        if engine.batch != 1:
            raise ValueError("continuous batching expects engine batch=1")
        # A model with recurrent state beside its K/V pages (cache.py): a
        # slot that starts over at position 0 reads its state as zero inside
        # the programs (chained after any block in flight), chunk k+1 starts
        # from chunk k's, an inactive slot's is not touched — so admission,
        # a freed slot's offset rewind, discard preemption (fold and
        # re-prefill from 0) and a blockless migration all carry it. What
        # re-enters a sequence at a LATER position from pages alone cannot.
        self._recurrent = has_recurrent_state(engine.model)
        # ... and so does one whose window layers keep their K/V in per-slot
        # rings (cache.py): a ring is addressed by position, so it needs no
        # reset, and nothing that moves full-length pages carries it either
        self._slot_state = has_slot_state(engine.model)
        self._ring_pages = getattr(engine, "ring_rows", 0) // engine.page_size
        self.ring_wraps = 0  # ring pages overwritten (one per slot and page)
        # A model that generates by diffusion over blocks (diffusion.py): a
        # decode step is a forward over every slot's whole block, the carry
        # between blocks' programs is the block and its mask where it was
        # the last token, a prefill yields no token, and a harvest hands a
        # slot the blocks its forwards committed.
        self._diffusion = diffusion.block_of(engine.model)
        for flag, on in (
            ("--prompt-cache", prefix_cache),
            ("--prefix-store", prefix_store is not None),
            ("--spill-bytes", spill_bytes is not None),
            ("--overcommit", overcommit),
            ("--draft", draft not in ("auto", "off") or draft_engine is not None),
        ):
            if on:
                diffusion.refuse(engine.model, flag)
        for flag, on, why in (
            ("--prompt-cache", prefix_cache,
             "a prefix hit starts a slot past pages whose state nobody kept"),
            ("--prefix-store", prefix_store is not None,
             "a store hit starts a slot past pages whose state nobody kept"),
            ("--spill-bytes", spill_bytes is not None,
             "a spilled block holds pages of K/V only"),
            ("--draft", draft not in ("auto", "off") or draft_engine is not None,
             "a rejected draft is undone by lowering the slot's offset"),
        ):
            if on:
                refuse_recurrent(engine.model, flag, why)
                if flag == "--draft" and draft_engine is not None:  # either side
                    refuse_recurrent(draft_engine.model, flag, why)
        if max_queue is not None and (not isinstance(max_queue, int) or max_queue < 1):
            raise ValueError(f"max_queue must be a positive int, got {max_queue!r}")
        if draft not in ("auto", "off", "ngram", "engine"):
            raise ValueError(
                f"draft must be 'auto', 'off', 'ngram' or 'engine', got "
                f"{draft!r}"
            )
        # the draft MODE: 'auto' keeps the legacy contract — engine iff a
        # draft engine was handed in, otherwise no speculation
        spec_mode = draft
        if spec_mode == "auto":
            spec_mode = "engine" if draft_engine is not None else "off"
        if spec_mode == "engine" and draft_engine is None:
            raise ValueError(
                "draft='engine' needs a draft engine (--draft-model)"
            )
        if spec_mode != "engine" and draft_engine is not None:
            raise ValueError(
                f"a draft engine was given but draft={draft!r} — drop the "
                "draft engine or select draft='engine'/'auto'"
            )
        if spec_mode == "ngram":
            if engine.num_stages != 1:
                raise ValueError(
                    "speculative continuous batching needs a pp=1 engine "
                    "(the verify wants the keep_all vectorized body)"
                )
            if jax.process_count() > 1:
                # the worker-mirror protocol (multihost.serve_worker_batched)
                # has no speculative op: a controller-local spec round would
                # desync the mirrored op streams
                raise ValueError(
                    "--draft ngram is not supported in multi-host serving: "
                    "worker mirrors replay plain decode ticks only; run it "
                    "on single-host replicas (e.g. behind --replicas) instead"
                )
        if spec_window_max is not None:
            if isinstance(spec_window_max, bool) \
                    or not isinstance(spec_window_max, int) \
                    or spec_window_max < 2:
                raise ValueError(
                    f"spec_window_max must be an int >= 2, got "
                    f"{spec_window_max!r}"
                )
            if spec_mode == "off":
                raise ValueError(
                    "spec_window_max needs a draft mode — select "
                    "--draft ngram or --draft engine"
                )
        if draft_engine is not None:
            # speculative x continuous batching: the draft engine mirrors the
            # target's slot structure (same M, same chunking) with its own
            # dense KV cache; pp=1 only (the verify needs the keep_all
            # vectorized body)
            if engine.num_stages != 1 or draft_engine.num_stages != 1:
                raise ValueError(
                    "speculative continuous batching needs pp=1 engines"
                )
            tv = getattr(engine.model.config, "vocab_size", None)
            dv = getattr(draft_engine.model.config, "vocab_size", None)
            if tv != dv:
                # a mismatched pair would silently emit clamped-index
                # garbage: draft token ids index the target's embedding and
                # logprob rows (speculative.py:131-139 enforces the same)
                raise ValueError(
                    f"draft vocab ({dv}) must match target vocab ({tv}) — "
                    "speculation exchanges raw token ids between the models"
                )
            if getattr(draft_engine, "paged", False):
                raise ValueError("the draft engine must be dense (no pool_pages)")
            if draft_engine.microbatches != engine.microbatches:
                raise ValueError("draft engine must match the target's slots")
            if draft_engine.prefill_chunk != engine.prefill_chunk:
                raise ValueError("draft engine must match the target's "
                                 "prefill chunk")
            if draft_engine.max_seq < engine.max_seq:
                raise ValueError("draft engine max_seq must cover the target's")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        if policy not in ("fifo", "first_fit"):
            raise ValueError(f"unknown admission policy {policy!r}")
        if prefix_cache and not getattr(engine, "paged", False):
            raise ValueError(
                "prefix_cache requires a paged engine (pool_pages): sharing "
                "is page-granular"
            )
        if prefix_store is not None:
            if not getattr(engine, "paged", False):
                raise ValueError(
                    "the prefix store requires a paged engine (pool_pages): "
                    "prefix reuse is page-granular"
                )
            if prefix_cache:
                raise ValueError(
                    "prefix_cache and prefix_store are mutually exclusive — "
                    "the fleet-wide store subsumes the slot-local prefix "
                    "cache (--prompt-cache); drop --prompt-cache"
                )
            if draft_engine is not None:
                raise ValueError(
                    "the prefix store is incompatible with a draft engine: "
                    "the draft's dense KV has no shareable pages, so a "
                    "store hit would leave it attending to unprefilled state"
                )
            if jax.process_count() > 1:
                # same class of problem as overcommit: lookup/lease/import
                # are host-side page-table decisions outside the op stream
                # worker ranks mirror
                raise ValueError(
                    "the prefix store is not supported in multi-host "
                    "serving: store hits rewrite page tables host-side, "
                    "outside the mirrored op stream; run it on single-host "
                    "replicas (e.g. behind --replicas) instead"
                )
        if overcommit and not getattr(engine, "paged", False):
            raise ValueError(
                "overcommit admission requires a paged engine (pool_pages)"
            )
        if overcommit and jax.process_count() > 1:
            # The sampler-state stash itself is no longer the blocker (it
            # rides a KVPageBlock now, a pure device-side gather every rank
            # could mirror). What remains genuinely unsupported: preemption
            # and block re-import are HOST-side scheduling decisions that
            # rewrite page-table/active rows and pop the rank-local free
            # list outside the mirrored multihost op stream — worker ranks
            # can't observe the controller's choice of victim/pages, so
            # their mirrored jitted programs would consume diverged inputs
            # and desync into a collective hang.
            raise ValueError(
                "overcommit admission is not supported in multi-host "
                "serving: preemption/resume rewrites page tables and free "
                "lists host-side, outside the op stream worker ranks "
                "mirror; run overcommit on single-host replicas (e.g. "
                "behind --replicas) instead"
            )
        if spill_bytes is not None:
            if isinstance(spill_bytes, bool) or not isinstance(spill_bytes, int) \
                    or spill_bytes <= 0:
                raise ValueError(
                    f"spill_bytes must be a positive byte count, got "
                    f"{spill_bytes!r}"
                )
            if not getattr(engine, "paged", False):
                raise ValueError(
                    "KV spill (--spill-bytes) requires a paged engine "
                    "(pool_pages): spilling moves pool pages"
                )
            if draft_engine is not None:
                # the draft's dense KV has no page chain to export; a spilled
                # target block would resume against a stale draft cache
                raise ValueError(
                    "KV spill is incompatible with a draft engine — "
                    "speculative slots re-prefill on preemption"
                )
        if spill_cold_after is not None:
            if isinstance(spill_cold_after, bool) \
                    or not isinstance(spill_cold_after, int) \
                    or spill_cold_after < 1:
                raise ValueError(
                    f"spill_cold_after must be an int >= 1 (ticks), got "
                    f"{spill_cold_after!r}"
                )
            if spill_bytes is None:
                raise ValueError(
                    "spill_cold_after needs a spill tier to spill into — "
                    "set spill_bytes (--spill-bytes)"
                )
            if jax.process_count() > 1:
                # same host-side page-table rewrite problem as overcommit:
                # a rank-local cold spill would desync mirrored op streams
                raise ValueError(
                    "cold-slot spill is not supported in multi-host serving"
                )
        if kv_prefetch not in ("on", "off", "auto"):
            raise ValueError(
                f"kv_prefetch must be 'on', 'off' or 'auto', got "
                f"{kv_prefetch!r}"
            )
        if kv_prefetch == "on" and spill_bytes is None:
            raise ValueError(
                "kv_prefetch='on' needs a spill tier to prefetch from — "
                "set spill_bytes (--spill-bytes)"
            )
        if async_sched not in ("on", "off", "auto"):
            raise ValueError(
                f"async_sched must be 'on', 'off' or 'auto', got {async_sched!r}"
            )
        if async_sched == "on" and draft_engine is not None:
            # speculative rounds already harvest per-round accept counts —
            # the next round's proposals depend on them, so there is no
            # device-side chain to run ahead on
            raise ValueError(
                "async_sched='on' is incompatible with a draft engine; use "
                "'auto' (resolves to sync when speculating)"
            )
        if async_sched == "on" and jax.process_count() > 1:
            # worker mirrors replay the op stream per broadcast tick; a
            # rank-local lookahead block would desync the mirrored streams
            raise ValueError(
                "async_sched='on' is not supported in multi-host serving"
            )
        self.engine = engine
        self.M = engine.microbatches
        self.W = repetition_window
        # injectable time source + wait primitive (utils/clock.py): every
        # deadline/TTFT/retry-after computation below reads this clock, so
        # tests and the fleet simulator can drive admission, timeout expiry
        # and migrate_out unwinding in virtual time. spec_clock defaults to
        # the same source (it predates the general slot; kept for callers
        # that pin the speculative controller to its own clock).
        self._clock = clock
        self._sleep = sleep
        if spec_clock is None:
            spec_clock = clock
        # Admission: "fifo" is strict arrival order (a request that doesn't
        # fit blocks everything behind it — predictable, starvation-free);
        # "first_fit" lets later requests that DO fit (free slot + enough
        # pages) jump a blocked head. Only meaningful with a paged pool.
        self.policy = policy
        self._waiting: list[_Request] = []
        # decode steps fused per scheduler tick: the host pulls tokens once
        # per block (the per-pull round trip otherwise gates every slot —
        # see generate.Generator). Tradeoff: admission/cancel latency grows
        # to a block boundary, so the serving default (8) stays below the
        # Generator's 16.
        self.decode_block = max(1, decode_block)
        self._decode_block_progs: dict = {}  # want_lp → jitted block
        self._submit: queue.Queue = queue.Queue()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        self._start_lock = make_lock("ContinuousBatcher._start_lock")
        # Admission control: generate_step rejects (QueueFullError → HTTP
        # 429) when queued requests reach max_queue, instead of letting the
        # unbounded submit queue grow without limit under overload. The lock
        # makes check-then-enqueue atomic across HTTP handler threads (and
        # the shed counter exact). The scheduler thread moves requests from
        # _submit to _waiting outside this lock, so a request mid-drain can
        # be momentarily invisible to the depth read — the bound is exact
        # across submitters and soft by at most that one in-flight drain.
        self.max_queue = max_queue
        self._admission_lock = make_lock("ContinuousBatcher._admission_lock")
        # resilience counters (read by /metrics via resilience_stats)
        self.timeouts = 0        # consumer-side deadline expiries
        self.shed_queue_full = 0  # rejected at admission (429)
        self.shed_deadline = 0   # shed while queued: TTFT budget already gone
        # monotonic completion stamps (bounded) feeding the drain-rate
        # Retry-After estimate on 429s; appended under _admission_lock
        self._finish_times: deque = deque(maxlen=256)
        # brownout ladder level from the fleet controller (fleet.py), set
        # via set_pressure(): >=2 pauses speculation, >=3 halves the
        # effective admission bound. Hot-path reads are racy by design
        # (gauge-grade) — the level changes at autoscaler-tick cadence.
        self._pressure = 0
        # close() flips this when the scheduler thread fails to join —
        # /health reports degraded and the thread-live gauge drops to 0
        self.thread_wedged = False

        # Multi-controller discipline (multi-host serving mirrors this
        # scheduler on every rank): host-built inputs must be committed as
        # REPLICATED global arrays before entering a jitted program over the
        # global mesh, and state transitions must run inside jit — eager ops
        # on process-spanning arrays are not executable. Single-host, _put is
        # the identity and the jitted setters behave exactly like the eager
        # .at[].set they replace.
        from jax.sharding import NamedSharding, PartitionSpec as P

        from mlx_sharding_tpu.parallel.pipeline import put_global

        rep = NamedSharding(engine.mesh, P())

        # the per-slot arrays start where every program hands them back —
        # committed, replicated over the engine's mesh — so the first call
        # of a program that takes them is its only compile (an array that
        # starts uncommitted makes the second call another)
        def place(x):
            return put_global(x, rep)

        # every rank mirrors the same op stream, so the host value being
        # committed is identical by construction — put_global skips
        # device_put's cross-host assert broadcast
        self._put = place if jax.process_count() > 1 else (lambda x: x)
        # every jitted helper is a named function: the name is the program's
        # in a profile (``jit_<name>``) and in a compile log, and no two
        # served programs share one (tests/test_program_names.py)
        def row_set(arr, slot, val):
            return arr.at[slot].set(val)

        # A join reaches the device as three programs — the claim, each
        # prefill chunk, the first token — and what each takes from the
        # host is ONE numpy array (_pack_i32): after a drain the streams'
        # threads have tokens to write, and every eager operation, every
        # dispatch and every array argument of a dispatch is one more point
        # at which this thread lets go of the interpreter lock and waits to
        # get it back. None of the three takes the cache: a jitted program
        # hands back what passes through it as a copy unless donated, so
        # they take the small per-slot arrays alone and donate the ones
        # they rewrite. A batcher without a page table or without a draft
        # passes None there.
        def claim_slot(packed, table, offset, sp, rep_sizes, doffset):
            u = _Unpack(packed)
            slot, start, rep_context = u.take(), u.take(), u.take()
            if table is not None:
                table = table.at[slot].set(u.take(table.shape[1:]))
            offset = offset.at[slot].set(start)
            sp = jax.tree.map(  # leaf by leaf in the order they were packed
                lambda full: full.at[slot].set(
                    u.take(full.shape[1:], full.dtype)
                ),
                sp,
            )
            rep_sizes = rep_sizes.at[slot].set(rep_context)
            if doffset is not None:  # the draft mirrors the slot from 0
                doffset = doffset.at[slot].set(0)
            return table, offset, sp, rep_sizes, doffset

        def seed_rows(u, keys, recent):
            slot = u.take()
            keys = keys.at[slot].set(u.take(keys.shape[1:], keys.dtype))
            recent = recent.at[slot].set(u.take(recent.shape[1:]))
            return slot, keys, recent

        def finish_join(logits, packed, keys, recent, sp, rep_sizes,
                        last_tok, active):
            slot, keys, recent = seed_rows(_Unpack(packed), keys, recent)
            tok, logprobs, keys, recent = self.first_sample(
                logits, keys, sp, recent, rep_sizes, slot
            )
            return (tok, logprobs, keys, recent,
                    last_tok.at[slot, 0].set(tok), active.at[slot].set(True))

        def finish_join_block(logits, packed, keys, recent, sp, rep_sizes,
                              blk, active):
            """``finish_join`` of a family whose prefill yields no token:
            the slot's first decode block [prompt tail | masks] takes the
            last token's place. What it hands back as the "token" is no
            token (negative): its read is the wait for the join's last
            chunk, as a first token's is."""
            u = _Unpack(packed)
            slot, keys, recent = seed_rows(u, keys, recent)
            length = blk["ids"].shape[1]
            blk = dict(blk)
            blk["ids"] = blk["ids"].at[slot].set(u.take((length,)))
            blk["masked"] = blk["masked"].at[slot].set(u.take((length,)) > 0)
            # (the stream that left the slot never had its last block stored)
            blk["pending"] = blk["pending"].at[slot].set(False)
            none = jnp.asarray(-1, jnp.int32) if logits is None else jnp.where(
                jnp.isnan(logits.reshape(-1)[0]), -2, -1
            ).astype(jnp.int32)
            return none, None, keys, recent, blk, active.at[slot].set(True)

        def resume_slot(packed, keys, recent, last_tok, active):
            u = _Unpack(packed)
            slot, keys, recent = seed_rows(u, keys, recent)
            return (keys, recent, last_tok.at[slot, 0].set(u.take()),
                    active.at[slot].set(True))

        self._row_set = jax.jit(row_set)
        self._claim_slot = jax.jit(claim_slot, donate_argnums=(1, 2, 3, 4, 5))
        self._finish_join = jax.jit(
            finish_join_block if self._diffusion else finish_join,
            donate_argnums=(2, 3, 6, 7),
        )
        self._resume_slot = jax.jit(resume_slot, donate_argnums=(1, 2, 3, 4))
        self._zeros_like = jax.jit(jnp.zeros_like)
        self._rewind_offset = jax.jit(rewind_slot_offset)

        # device-side per-slot state. Paged engines share a page pool across
        # slots — packing mixed-length requests into far less HBM than M
        # dense max_seq allocations; the admission accounting mode below
        # decides how much of a request's need is claimed up front.
        self.paged = getattr(engine, "paged", False)
        self.prefix_cache = bool(prefix_cache)
        # Fleet-wide content-addressed prefix KV store (prefix_store.py):
        # admission LPM-matches the prompt's chained chunk digests against
        # device entries (zero-copy COW page share) and the host tier
        # (block import), and completed prefills register their prefix
        # back. One store is shared by every batcher in the process — the
        # subsystem the slot-local _prefix_index cannot grow into.
        # the engine's KV share-map layout hash (kv_share.py; None ==
        # unshared/identity) — stamped into every exported block and
        # demanded of every imported one, so a layout mismatch fails
        # closed at the edge instead of scattering wrong-geometry KV
        self._share_hash = getattr(engine, "kv_share_hash", None)
        # the engine's compressed-latent codec + layout hash
        # (kv_compress.py; None == raw transport) — every export carries
        # the codec so host-boundary flushes compress, every import
        # reconstructs under the matching layout or fails closed
        self._kv_codec = getattr(engine, "kv_codec", None)
        self._compress_hash = getattr(engine, "kv_compress_hash", None)
        self.prefix_store = prefix_store
        if prefix_store is not None:
            prefix_store.bind_page_size(engine.page_size)
            prefix_store.bind_share_hash(self._share_hash)
            prefix_store.bind_compress_hash(self._compress_hash)
        # Admission accounting mode. "reserve" (default) claims a request's
        # whole page need (prompt + max_tokens) up front: deadlock-free by
        # construction, but a request that asks for max_tokens=4096 and emits
        # 20 holds ~64x its real need. Over-commit admits on CURRENT need
        # (prompt + one decode block), grows per block, and on pool
        # exhaustion preempts the newest-admitted slot back to the waiting
        # line (its emitted tokens fold into its prompt; device sampler
        # state is stashed, so resume is token-exact). The oldest admitted
        # request is never preempted, so progress is guaranteed: worst case
        # the pool drains to one request, which the absolute capacity check
        # in generate_step proves fits alone.
        self.overcommit = bool(overcommit)
        self.preemptions = 0
        self._admit_counter = 0
        # KV migration (kv_transfer.py): spill-don't-discard preemption and
        # request migration. The tier holds preempted requests' page blocks
        # in host DRAM under an LRU budget; export is a dispatched device
        # gather (the blocking device→host copy runs on the tier's flusher
        # thread, never the tick path — MST106), import is one page scatter
        # instead of a re-prefill. All counters below are written under
        # _admission_lock (racy reads are gauge-grade, like preemptions).
        self.spill_bytes = spill_bytes
        self.spill = KVSpillTier(spill_bytes) if spill_bytes else None
        self.spills = 0            # blocks exported to the tier at preempt
        self.spill_hits = 0        # resumes served by a block import
        self.spill_fallbacks = 0   # export/import/budget failures → re-prefill
        self.migrations_out = 0    # requests exported by migrate_out (drain)
        self.migrations_in = 0     # resumed requests accepted via _resume
        self.handoffs_out = 0      # prefill-only requests handed to decode
        self.reprefill_tokens = 0  # tokens re-prefilled after discard paths
        # Proactive KV residency (cold-slot spill + PRESERVE-style
        # prefetch). A slot whose consumer stopped pulling tokens for
        # spill_cold_after ticks (backlog stagnant — the engine keeps
        # decoding, nobody reads) is suspended: its block spills to the
        # tier, its pool pages free up for admission, and the request
        # parks off the waiting line until the consumer catches up. Wake
        # re-queues it at the head; with prefetch on, the host→device
        # stage is dispatched while it waits its turn, so the re-import
        # scatter consumes device-resident pages instead of demand-paging
        # host numpy on the resume tick (the stall MST109 polices).
        self.spill_cold_after = spill_cold_after
        self.kv_prefetch = kv_prefetch
        self._prefetch_on = kv_prefetch == "on" or (
            kv_prefetch == "auto" and self.spill is not None
        )
        self._parked: list[_Request] = []  # cold-spilled, off the waiting line
        self.cold_spills = 0      # slots suspended by the cold policy
        self.cold_wakes = 0       # parked requests re-queued on consumer pull
        self.prefetches = 0       # host→device stages dispatched
        self.prefetch_hits = 0    # imports that consumed a staged block
        self.demand_imports = 0   # imports that marshaled host numpy (fallback)
        self.prefetch_faults = 0  # cache.prefetch faults absorbed → demand path
        # prefill-only requests whose first token was emitted this tick;
        # _handoff_out exports them before the tick's decode dispatch
        self._handoff_ready: list = []
        self._export_pages = jax.jit(export_pool_pages) if self.paged else None
        self._import_pages = jax.jit(import_pool_pages) if self.paged else None
        # drain flag: migrate_out() sets it (under _start_lock, like _stop);
        # the scheduler thread notices at the next tick, quiesces, and ends
        # every stream with a RequestMigratedError carrying its ResumeState
        self._migrate_requested = False
        # speculative decoding across slots: per tick, the draft proposes K
        # tokens for every active slot and the target verifies all of them
        # in one T=K forward; each slot emits its accepted prefix + one
        # correction/resample token. Greedy slots stay token-exact vs plain
        # decode; sampled slots are distribution-exact (the PRNG is consumed
        # differently than non-speculative decode, as in speculative.py).
        self.draft = draft_engine
        self.spec_k = spec_k
        self._spec_mode = spec_mode  # "off" | "ngram" | "engine"
        # async tick pipelining: resolved mode. "auto" turns it on for any
        # tick whose in-flight work is a pure device-side chain — plain
        # single-host decode AND n-gram speculation (host-built drafts, no
        # draft KV); a draft ENGINE forces sync (the round harvests accept
        # counts the next proposals depend on), multi-host forces sync
        # (worker mirrors replay per broadcast tick). The reason is kept on
        # the instance and logged so `--async-sched auto` says WHY.
        self.async_sched = async_sched
        if async_sched == "on":
            self._async = True
            reason = "async ticks: async_sched='on'"
        elif async_sched == "off":
            self._async = False
            reason = "sync ticks: async_sched='off'"
        elif draft_engine is not None:
            self._async = False
            reason = (
                "sync ticks: auto resolved to sync — the draft engine's "
                "speculative rounds harvest per-round accept counts that "
                "the next round's proposals depend on, so there is no "
                "device-side chain to run ahead on"
            )
        elif jax.process_count() > 1:
            self._async = False
            reason = (
                "sync ticks: auto resolved to sync — multi-host worker "
                "mirrors replay the op stream per broadcast tick; a "
                "rank-local lookahead block would desync them"
            )
        elif spec_mode == "ngram":
            self._async = True
            reason = (
                "async ticks: auto resolved to async — n-gram drafts are "
                "host-built (no draft engine, no draft KV), so the "
                "speculative round chains pure device-side like a plain "
                "decode block"
            )
        else:
            self._async = True
            reason = (
                "async ticks: auto resolved to async — plain single-host "
                "decode is a pure device-side chain"
            )
        self.async_reason = reason
        logging.getLogger(__name__).info("%s", reason)
        # the work in flight (dispatched, not harvested): a plain decode
        # block or, in async ngram mode, a speculative round. Owned by the
        # scheduler thread, always None in sync mode outside _decode_once
        self._inflight: Optional[object] = None  # _InflightBlock | _InflightSpec
        # --trace-profile resolved once at construction (serving configures
        # tracing before building engines): True also opens every tick
        # phase as a jax.profiler.TraceAnnotation (mst.tick, mst.<phase>,
        # mst.decode_block) so the host's spans sit on the device trace's
        # clock in a profiler capture
        self._trace_profile = tracing.profile_enabled()
        # where the tick thread's time goes (always on, cumulative): every
        # part of _tick/_tick_async runs inside one phase of
        # tracing.TICK_PHASES; harvest_wait is the harvest device_get (what
        # the async path overlaps), idle_wait the blocking submission wait,
        # the rest is host work. It also keeps the device's timeline as
        # this thread sees it: _phases.dispatched(kind) stands right before
        # the dispatch call of every served program (decode block,
        # speculative round, prefill chunk; its arguments are made first:
        # they are host work) and _phases.returned() right after it,
        # _phases.ready(ticket) follows the blocking read that waited on
        # it, _phases.drop() stands where nobody will read what is left —
        # and nothing else touches the queue. From it come, per phase, the
        # seconds the device had nothing to run, and per kind of program
        # the seconds the device had it
        self._phases = tracing.TickPhases(profile=self._trace_profile)
        # (ticket, logits) of the target's prefill chunk dispatched last,
        # while nobody has read it: a join's middle chunk ends unseen, so
        # the harvest behind it waits on its logits first (_chunk_ended)
        self._chunk_unread = None
        self._account_logged = False  # close() logs the account once
        # plain decode blocks, counted where they happen (tick thread only).
        # Identities: dispatched = harvested + abandoned + in flight, and
        # positions computed = tokens emitted + dropped + positions in
        # flight. Speculative rounds keep spec_stats() as their account.
        self._blocks_dispatched = 0
        self._blocks_harvested = 0
        self._blocks_abandoned = 0
        self._positions_computed = 0  # steps x live rows, at dispatch
        self._tokens_emitted = 0      # decode-block tokens handed to _emit
        # slot_finished: positions past the last token of a stream that ran
        # to its max_tokens (the rest of its block, and its whole lookahead
        # block); cancelled: the same for a slot given up earlier — the
        # consumer stopped reading (stop sequence, disconnect, timeout) or
        # the slot was preempted; abandoned_block: a block whose futures
        # were dropped unharvested (shutdown, _fail_all)
        self._tokens_dropped = {
            "slot_finished": 0, "cancelled": 0, "abandoned_block": 0,
        }
        if self._diffusion:
            # ... and denoise: a forward's rows that were not a commit's new
            # tokens. The family's own account, counted at the harvest: live
            # slots x forwards, blocks committed, positions transferred by
            # rank (the strategy's n a forward) or by passing the threshold
            self._tokens_dropped["denoise"] = 0
            self._diffusion_stats = dict.fromkeys(
                ("slot_forwards", "blocks_committed", "by_rank",
                 "by_confidence"), 0
            )
        # first prefill chunks dispatched for a model with recurrent state:
        # each starts its slot's state from zero (inside the chunk's program)
        self.state_resets = 0
        # _quiesce calls that found a block in flight, by call site: each is
        # one lost overlap of the double-buffered pipeline
        self._drains = dict.fromkeys(
            ("admit", "prefilling", "cold", "growth", "migrate", "idle"), 0
        )
        # plain decode blocks by what their sampler had to run, classed at
        # dispatch from the live requests' own settings: greedy (argmax),
        # draw (a live row samples at top_p = 1), nucleus (a live sampled
        # row cuts at top_p < 1: the vocabulary is sorted). The program
        # decides the same on the device (sample.sample_token_batched)
        self._blocks_by_sampler = dict.fromkeys(
            ("greedy", "draw", "nucleus"), 0
        )
        # dispatches a join made between its drain and the slot decoding,
        # by program: the claim, each prefill chunk (the draft's too), the
        # first token; other: a block import's resume_slot
        self._join_programs = dict.fromkeys(
            ("claim", "chunk", "finish", "other"), 0
        )
        # first tokens sampled on the device and not yet read: (request,
        # token, log-probabilities, ticket of the program the read waits on)
        # of every join whose last chunk was dispatched, oldest first. The
        # async tick reads them once the decode block behind the chunk is
        # dispatched (_tick_async); joins by where that read fell
        self._first_unread: list = []
        self._join_first_reads = dict.fromkeys(
            ("behind_block", "before_block"), 0
        )
        # a drain's hand-over to the streams, held back until the device has
        # the joiner's chunk (_tick_async): the (queue, item) pairs in the
        # order they would have been put, None while no hold is open; when
        # the first was deferred; items held by the point that let them go,
        # and the holds' seconds and count (first deferred item to flush)
        self._held: Optional[list] = None
        self._held_since = 0.0
        self._emit_held = dict.fromkeys(("chunk", "tick_end", "fail"), 0)
        self._emit_hold_seconds = 0.0
        self._emit_holds = 0
        # always-on latency histograms (/metrics): inter-token latency at
        # the emit path, admission queue wait at slot assignment. These are
        # the metric itself (a lock + bisect per observation, same grade as
        # the tick-timing counters), distinct from per-request tracing —
        # which stays behind the `if tr is not None` no-op guard (MST112)
        self._h_itl = Histogram(ITL_BUCKETS_S, "ContinuousBatcher._h_itl")
        self._h_queue_wait = Histogram(
            LATENCY_BUCKETS_S, "ContinuousBatcher._h_queue_wait"
        )
        # one observation a slot claim that reached decode: where the queue
        # wait ends (_assign_slot's stamp) to the slot turning active (its
        # last prefill chunk's first token, or the end of a block import)
        self._h_join = Histogram(LATENCY_BUCKETS_S, "ContinuousBatcher._h_join")
        # adaptive window control: an AcceptanceTracker drives per-slot
        # windows for ngram mode always, and for engine mode when the
        # operator opts in with spec_window_max (without it the engine path
        # keeps the legacy fixed-K contract: every round is exactly spec_k
        # wide). The tracker's clock is injectable for deterministic tests.
        if spec_mode == "ngram" or (
            spec_mode == "engine" and spec_window_max is not None
        ):
            self.spec_tracker: Optional[AcceptanceTracker] = AcceptanceTracker(
                self.M, w_max=spec_window_max or 8, clock=spec_clock
            )
            self._w_max = self.spec_tracker.rungs[-1]
        else:
            self.spec_tracker = None
            self._w_max = spec_k if spec_mode == "engine" else 0
        self._ngram = NgramDraftProposer() if spec_mode == "ngram" else None
        # over-commit page growth must cover whichever step writes furthest
        # ahead: a decode block (1 write/step), a T=K speculative verify,
        # and DOUBLE that when the pipeline runs a block/round ahead of the
        # host's emitted counts (at dispatch of t+1 the host has harvested
        # only through t-1)
        reach = self.decode_block
        if spec_mode == "engine":
            reach = max(reach, spec_k, self._w_max)
        elif spec_mode == "ngram":
            reach = max(reach, self._w_max)
        self._grow_ahead = (2 if self._async else 1) * reach
        if spec_mode != "off":
            self.rounds = 0          # spec telemetry: verify rounds x slots
            self.accepted_tokens = 0  # tokens EMITTED by speculating slots
            self.draft_tokens = 0    # proposal tokens offered to verifies
            # ticks that fell back to plain decode (spec paused) and the
            # tokens replayed through the draft to keep its KV in sync
            self.fallback_ticks = 0
            self.replayed_tokens = 0
            # spec.draft faults absorbed → that tick ran plain decode
            self.spec_draft_faults = 0
        if draft_engine is not None:
            self.dcache = draft_engine.init_cache()
            def split3(ks):
                return jax.vmap(lambda k: jax.random.split(k, 3))(ks)

            self._split3 = jax.jit(split3)
            # draft consumed [t0, d1..d_{K-1}] = K rows; keep the verified
            # prefix (the accepted tokens ARE the draft's inputs there).
            # k is the ROUND's width — adaptive rounds can run narrower
            # than spec_k
            def draft_rewind(off, count, act, k):
                return off + jnp.where(act, count - k, 0)

            self._drewind = jax.jit(draft_rewind)
        elif spec_mode == "ngram":
            # sampled ngram rounds split each slot's key once for the
            # verify (no draft-side key, unlike the engine path's 3-way)
            def split2(ks):
                return jax.vmap(lambda k: jax.random.split(k, 2))(ks)

            self._split2 = jax.jit(split2)
        if self.paged:
            self.cache, self.table = engine.init_cache_paged()
            # analytic per-tick KV-read accounting (the HBM story behind the
            # ragged-vs-gather paths): bytes of K+V per token position,
            # summed over every layer stack — leaf shape is
            # (S, L, pool+1, B, page, H, D), so S*L*H*D*itemsize per row
            self.kv_path = getattr(engine, "paged_attention", "gather")
            self.kv_bytes_read_last_tick = 0
            self.kv_bytes_read_total = 0
            self.kv_bytes_claimed_total = 0
            # per-decoded-token HBM traffic, split by side (weights vs KV):
            # the headline numbers of the quantized memory hierarchy
            self.weight_bytes_per_token_last = 0.0
            self.kv_bytes_per_token_last = 0.0
            self._kv_row_bytes = sum(
                leaf.shape[0] * leaf.shape[1] * leaf.shape[-2]
                * leaf.shape[-1] * leaf.dtype.itemsize
                for leaf in (
                    jax.tree.leaves(self.cache.k) + jax.tree.leaves(self.cache.v)
                )
            )
            self.pool = PagePool(engine.pool_pages, engine.slot_pages)
            # Prompt-prefix sharing (vLLM-style content-addressed pages):
            # a FULL page of prompt KV is registered under the hash of the
            # whole token prefix it closes; a later request whose prompt
            # matches a chain of registered pages maps them read-only and
            # prefills only the suffix (its slot offset starts past them).
            # A page's holders = #slots mapping it + 1 if the index holds it;
            # index-only pages are "cached": not free, evictable LRU when
            # admission runs short. The reference resets remote caches per
            # request (ref: shard/utils.py:122-124) — this is the beaten
            # semantics; Generator._pc is the single-stream analogue.
            self._prefix_index: "OrderedDict[bytes, int]" = OrderedDict()
            self.prefix_queries = 0
            self.prefix_hits = 0
            self.prefix_tokens_reused = 0
            self.prefix_evictions = 0
        else:
            self.cache = engine.init_cache()
            # dummy for the step arg
            self.table = place(jnp.zeros((1, 1), jnp.int32))
        self.recent = place(jnp.full((self.M, self.W), -1, jnp.int32))
        self.keys = place(jnp.stack([jax.random.PRNGKey(0)] * self.M))
        # bias width 512 covers OpenAI's documented logit_bias cap (300);
        # larger requests are rejected on the submitting thread
        self.sp = place(stack_sampler_params(
            [make_sampler_params(min_bias_slots=512) for _ in range(self.M)]
        ))
        self.rep_sizes = place(jnp.full((self.M,), self.W, jnp.int32))
        self.active = place(jnp.zeros((self.M,), bool))
        # a decode block's first input: every slot's last token — or, for a
        # family that generates by diffusion over blocks, its block
        self.last_tok = place(
            diffusion.init_block(self.M, self._diffusion) if self._diffusion
            else jnp.zeros((self.M, 1), jnp.int32)
        )

        # host-side slot table
        self._slots: list[Optional[_Request]] = [None] * self.M
        self._prefill_rr = 0  # round-robin cursor for admission fairness

    # ------------------------------------------------------------- public
    def generate_step(
        self,
        prompt_tokens,
        *,
        temperature: float = 0.0,
        top_p: float = 1.0,
        repetition_penalty: Optional[float] = None,
        repetition_context_size: int = 20,
        logit_bias: Optional[dict[int, float]] = None,
        seed: Optional[int] = None,
        max_tokens: int = 256,
        want_logprobs: bool = False,  # yields TokenLogprobs summaries
        request_timeout: Optional[float] = None,  # submit → last token budget
        ttft_timeout: Optional[float] = None,     # submit → first token budget
        stall_timeout: Optional[float] = None,    # inter-token watchdog
        _resume: Optional[ResumeState] = None,    # dispatcher-internal
        _prefill_only: bool = False,              # disagg-coordinator-internal
        _trace=None,                              # tracing.RequestTrace or None
    ):
        # Eager validation/admission, lazy consumption: every rejection
        # (bad params, queue full) raises on the CALLING thread before any
        # request state exists — the server can answer 400/429 before it has
        # committed to a streaming response. Only the token loop is deferred.
        with self._start_lock:
            draining = self._migrate_requested
        if draining:
            # draining/retired: reject up front so the dispatcher re-places
            # on a healthy replica (QueueFullError subtype → retry, no strike)
            raise ReplicaDrainingError()
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        # Re-placement of a partially generated stream (replica drain /
        # crash failover): continue from the migrated state instead of
        # starting over. Preferred path imports the shipped KVPageBlock;
        # without one (or when this engine can't host it) the emitted
        # history folds into the prompt and re-prefills — slower but
        # token-exact, since the sampler PRNG row and repetition window
        # travel in the state when the source captured them.
        produced0 = 0
        hist: list = []
        block = None
        resume_keys = resume_recent = None
        if _resume is not None:
            produced0 = int(_resume.produced)
            if produced0 >= max_tokens:
                raise ValueError(
                    f"resumed request already produced {produced0} of "
                    f"{max_tokens} tokens"
                )
            hist = [int(t) for t in (_resume.history or [])]
            if len(hist) > produced0:
                # history is "tokens emitted since the last fold" — always a
                # suffix of what the client saw, so it can be SHORTER than
                # produced (the rest already folded into the prompt) but
                # never longer: that would re-emit tokens the accounting
                # says were never delivered
                raise ValueError(
                    f"resume state inconsistent: produced={produced0} but "
                    f"history carries {len(hist)} tokens"
                )
            block = _resume.block
            if block is not None and (
                not self.paged or self.draft is not None or self._diffusion
            ):
                block = None  # no pool to import into; fall back to fold
            # Capture the stashed sampler rows even when a block rides along:
            # if its import fails on this engine the admission path degrades
            # to fold + re-prefill, and the re-seeded PRNG chain must be the
            # exported one — a fresh PRNGKey(seed) would replay the stream
            # from token zero and double-emit what the client already saw.
            resume_keys = _resume.resume_keys
            resume_recent = _resume.resume_recent
            if block is None and hist:
                prompt = np.concatenate([prompt, np.asarray(hist, np.int32)])
                hist = []
        budget = max_tokens - produced0
        total = (block.n_tokens if block is not None else prompt.size) + budget
        if total > self.engine.max_seq:
            raise ValueError(
                f"prompt ({prompt.size}) + max_tokens ({max_tokens}) exceeds "
                f"KV capacity {self.engine.max_seq}"
            )
        if self.paged and -(-total // self.engine.page_size) > self.engine.pool_pages:
            raise ValueError(
                f"request needs {-(-total // self.engine.page_size)} "
                f"pages, pool has {self.engine.pool_pages} — it could never "
                "be admitted"
            )
        width = self.sp.bias_indices.shape[1]
        if logit_bias and len(logit_bias) > width:
            raise ValueError(
                f"logit_bias with {len(logit_bias)} entries exceeds the "
                f"scheduler's per-slot bias width {width}"
            )
        # the request's sampler row, as wide as the batch's and made on
        # the host: the slot claim hands it to its program as it is
        sp = sampler_params_host(
            temperature, top_p, repetition_penalty, logit_bias, slots=width
        )
        if repetition_penalty is not None and repetition_context_size > self.W:
            # silently shrinking the window would make --concurrent output
            # diverge from the serial path for the same request
            raise ValueError(
                f"repetition_context_size {repetition_context_size} exceeds "
                f"the scheduler's window {self.W}"
            )
        deadlines = (
            Deadlines.start(
                ttft_timeout=ttft_timeout,
                request_timeout=request_timeout,
                stall_timeout=stall_timeout,
            )
            if any(v is not None
                   for v in (ttft_timeout, request_timeout, stall_timeout))
            else None
        )
        req = _Request(
            prompt=prompt,
            sp=sp,
            seed=int(time.time_ns()) & 0x7FFFFFFF if seed is None else seed,
            max_tokens=max_tokens,
            rep_context=min(repetition_context_size, self.W),
            want_logprobs=want_logprobs,
            deadlines=deadlines,
            temperature=temperature,
            top_p=top_p,
            repetition_penalty=repetition_penalty,
            logit_bias=logit_bias,
            prefill_only=bool(_prefill_only),
        )
        if _prefill_only:
            diffusion.refuse(self.engine.model, "--disagg")
            refuse_recurrent(
                self.engine.model, "--disagg",
                "the prefill-to-decode hand-off moves pages of K/V only",
            )
        if _resume is not None:
            req.produced = produced0
            req.history = hist
            req._block = block
            if resume_keys is not None:
                req.resume_keys = np.asarray(resume_keys, np.uint32)
            if resume_recent is not None:
                req.resume_recent = np.asarray(resume_recent, np.int32)
            with self._admission_lock:
                self.migrations_in += 1
        # Bind (or self-begin) the request's span timeline. The server and
        # disagg coordinator pass _trace so one timeline spans the whole
        # path; direct scheduler users (tests) get a trace from the
        # process tracer when one is configured — begin() returns None when
        # tracing is off or this request falls outside the sample.
        tr = _trace
        if tr is None:
            tr = tracing.begin()
            req._trace_own = tr is not None
        req._trace = tr
        req._t_submit = time.perf_counter()
        if tr is not None:
            tr.note(
                prompt_tokens=int(prompt.size), max_tokens=int(max_tokens),
                prefill_only=bool(_prefill_only), resumed=_resume is not None,
            )
            tr.point("submit")
        self._ensure_running()
        if self.max_queue is not None:
            with self._admission_lock:
                depth = self._submit.qsize() + len(self._waiting)
                bound = self.max_queue
                if self._pressure >= 3:
                    # brownout level 3: tightened admission — shed at half
                    # the configured bound so queue-wait stays bounded
                    # while the fleet is saturated
                    bound = max(1, bound // 2)
                if depth >= bound:
                    self.shed_queue_full += 1
                    if tr is not None:
                        # the shed is the request's whole story: stamp it
                        # and retire a self-begun trace so it can't leak
                        # in the recorder's live table
                        tr.point("shed", depth=depth, bound=bound)
                        if req._trace_own:
                            tracing.finish(tr)
                    raise QueueFullError(
                        depth, bound,
                        retry_after_s=estimate_retry_after(
                            max(1, depth - bound + 1),
                            self._finish_times, self._clock(),
                        ),
                    )
                self._submit.put(req)
        else:
            # mst: allow(MST201): no admission bound to keep atomic with
            self._submit.put(req)
        return self._consume(req)

    def _consume(self, req: _Request):
        """Token stream for a submitted request. Waits are bounded by the
        request's deadlines: TTFT before the first token, the inter-token
        watchdog after it, and the total budget throughout — whichever
        expires first. Expiry flips ``cancelled`` (the scheduler reclaims
        the slot/pages on its next tick, even a wedged one once it revives)
        and raises the structured error immediately, so a consumer never
        blocks forever on a dead engine."""
        dl = req.deadlines
        first = True
        try:
            while True:
                kind, timeout = None, None
                if dl is not None:
                    now = self._clock()
                    cands = []
                    if first and dl.ttft_deadline is not None:
                        cands.append(("ttft", dl.ttft_deadline - now))
                    if dl.total_deadline is not None:
                        cands.append(("total", dl.total_deadline - now))
                    if dl.stall_timeout is not None and (
                        not first or dl.ttft_deadline is None
                    ):
                        # inter-token watchdog; with no TTFT budget it also
                        # bounds the FIRST token, so a caller who set only
                        # stall_timeout still can't block forever on a
                        # wedged engine
                        cands.append(("stall", dl.stall_timeout))
                    if cands:
                        kind, timeout = min(cands, key=lambda t: t[1])
                        timeout = max(0.0, timeout)
                try:
                    item = (
                        req.out.get(timeout=timeout)
                        if timeout is not None
                        else req.out.get()
                    )
                except queue.Empty:
                    req.cancelled = True
                    with self._admission_lock:  # exact under concurrency
                        self.timeouts += 1
                    now = self._clock()
                    budget = (
                        dl.stall_timeout if kind == "stall"
                        else (dl.ttft_deadline if kind == "ttft"
                              else dl.total_deadline) - dl.submitted_at
                    )
                    raise RequestTimeoutError(
                        kind, now - dl.submitted_at, budget
                    ) from None
                if item is None:
                    return
                if isinstance(item, BaseException):
                    raise item
                first = False
                yield item
        finally:
            req.cancelled = True  # scheduler reclaims the slot next tick

    @property
    def weights_shared(self) -> bool:
        """True when this batcher's engine aliases a WeightStore-resident
        tree instead of owning a private upload — the per-replica
        ``mst_replica_weights_shared`` gauge reads this through the
        ReplicaSet."""
        return bool(getattr(self.engine, "weights_shared", False))

    def stats(self) -> tuple[int, int, int]:
        """(total slots, active slots, queued requests) — the /metrics
        contract, kept here so scheduler internals can change freely."""
        with self._admission_lock:
            queued = self._submit.qsize() + len(self._waiting)
        # _slots is owned by the scheduler thread; this is a racy snapshot
        # by design (a metric, not a decision input)
        return (self.M, sum(1 for r in self._slots if r is not None), queued)

    def _live_locked(self) -> bool:
        """scheduler_thread_live body; caller holds ``_start_lock``."""
        if self.thread_wedged:
            return False
        t = self._thread
        return t is None or t.is_alive() or self._stop

    def scheduler_thread_live(self) -> bool:
        """True while the scheduler thread is healthy: running, cleanly
        stopped, or not yet started. False only after close() observed a
        join timeout (a tick wedged mid-device-op)."""
        with self._start_lock:
            return self._live_locked()

    def set_pressure(self, level: int):
        """Brownout ladder input from the fleet controller (fleet.py):
        level >= 1 pauses prefix-store INSERTION (serving hits stays on —
        reuse sheds prefill work exactly when the fleet needs it), level
        >= 2 sheds speculation — globally in legacy fixed-K mode, per-slot
        lowest-acceptance-first with an AcceptanceTracker — and level >= 3
        halves the effective admission bound (and sheds speculation
        everywhere). Idempotent; levels outside [0, 3] are clamped."""
        lvl = max(0, min(3, int(level)))
        with self._admission_lock:
            self._pressure = lvl
        store = self.prefix_store
        if store is not None:
            store.pause_inserts(lvl >= 1)

    def resilience_stats(self) -> dict:
        """Deadline/shedding counters + queue bound for /metrics."""
        live = self.scheduler_thread_live()  # own lock; taken before ours
        with self._admission_lock:
            return {
                "timeouts": self.timeouts,
                "brownout_level": self._pressure,
                "shed_queue_full": self.shed_queue_full,
                "shed_deadline": self.shed_deadline,
                "max_queue": self.max_queue,
                "scheduler_thread_live": live,
                "preemptions": self.preemptions,
                "spills": self.spills,
                "spill_hits": self.spill_hits,
                "spill_fallbacks": self.spill_fallbacks,
                "migrations_out": self.migrations_out,
                "migrations_in": self.migrations_in,
                "handoffs_out": self.handoffs_out,
            }

    def spec_stats(self) -> Optional[dict]:
        """Speculation telemetry for /metrics (``mst_spec_*``); None when
        the batcher never speculates, so a non-speculating host's exposition
        stays label-free. Racy counter snapshot by design — gauges, not
        decision inputs."""
        if self._spec_mode == "off":
            return None
        out = {
            "mode": self._spec_mode,
            "window_max": self._w_max,
            "rounds": self.rounds,
            "draft_tokens": self.draft_tokens,
            "accepted_tokens": self.accepted_tokens,
            "accept_rate": self.accepted_tokens / max(1, self.draft_tokens),
            "fallback_ticks": self.fallback_ticks,
            "replayed_tokens": self.replayed_tokens,
            "draft_faults": self.spec_draft_faults,
        }
        if self.spec_tracker is not None:
            out.update(self.spec_tracker.stats())
        return out

    def spill_stats(self) -> Optional[dict]:
        """KV spill/migration counters + tier occupancy for /metrics
        (``mst_kv_spill_*`` / ``mst_kv_migration_*``); None on dense
        engines, which have no page pool to export blocks from. The tier's
        own stats are read before taking the admission lock so the two
        locks never nest."""
        if not self.paged:
            return None
        spill = self.spill
        tier = spill.stats() if spill is not None else {}
        with self._admission_lock:
            out = {
                "enabled": spill is not None,
                "spills": self.spills,
                "spill_hits": self.spill_hits,
                "spill_fallbacks": self.spill_fallbacks,
                "migrations_out": self.migrations_out,
                "migrations_in": self.migrations_in,
                "reprefill_tokens": self.reprefill_tokens,
                "preemptions": self.preemptions,
                # proactive residency: cold policy + prefetch counters
                "cold_spills": self.cold_spills,
                "cold_wakes": self.cold_wakes,
                "parked": len(self._parked),
                "prefetch_enabled": self._prefetch_on,
                "prefetches": self.prefetches,
                "prefetch_hits": self.prefetch_hits,
                "demand_imports": self.demand_imports,
                "prefetch_faults": self.prefetch_faults,
            }
        out["budget_bytes"] = tier.get("budget_bytes", 0)
        out["bytes_in_use"] = tier.get("bytes_in_use", 0)
        out["blocks"] = tier.get("blocks", 0)
        out["blocks_host"] = tier.get("blocks_host", 0)
        out["evictions"] = tier.get("evictions", 0)
        out["rejects"] = tier.get("rejects", 0)
        out["rejects_oversize"] = tier.get("rejects_oversize", 0)
        out["rejects_closed"] = tier.get("rejects_closed", 0)
        out["tier_hits"] = tier.get("hits", 0)
        out["tier_misses"] = tier.get("misses", 0)
        out["hit_rate"] = tier.get("hit_rate", 0.0)
        return out

    def health(self) -> dict:
        """Serving health for the /health endpoint: ``status`` in
        ok/degraded/draining, ``serving`` decides 200 vs 503."""
        with self._start_lock:
            live = self._live_locked()
            draining = self._stop or self._migrate_requested
        if not live:
            # a wedged thread (even one noticed during close) beats draining:
            # the operator needs to see the leak, not a polite shutdown
            return {"status": "degraded", "serving": False,
                    "scheduler_thread_live": False}
        if draining:
            return {"status": "draining", "serving": False,
                    "scheduler_thread_live": live}
        return {"status": "ok", "serving": True,
                "scheduler_thread_live": live}

    def page_stats(self) -> Optional[tuple[int, int, int]]:
        """(pool pages, pages in use, high-water mark) for /metrics — the
        KV-HBM story of a paged pool; None on dense engines."""
        if not self.paged:
            return None
        return (self.pool.total, self.pool.in_use, self.pool.high_water)

    def _pages_needed(self, n_prompt: int, max_tokens: int) -> int:
        page = self.engine.page_size
        total = n_prompt + max_tokens
        if self._diffusion:
            # a slot writes whole blocks: rows up to the end of the block
            # that holds its last token
            total = -(-total // self._diffusion) * self._diffusion
        return -(-total // page)

    def kv_read_stats(self) -> Optional[tuple[str, int, int, int]]:
        """(attention path, KV bytes read last tick, total, CLAIMED total)
        for /metrics; None on dense engines. Analytic, not measured: ragged
        counts the page-rounded rows each live slot HOLDS — what the kernel's
        walk fetches, since it follows the lengths (``ops/paged_attention.
        walk_page``) — and gather the full slot_pages-wide contiguous view
        `_paged_read` materializes per slot per step; the gap between the
        two is the traffic the ragged kernel deletes. CLAIMED is the same
        sum over every page in the slots' table rows (under the default
        RESERVE admission a stream's whole prompt + max_tokens need from
        its first token, and the scratch entry where the row is wider):
        what a walk that names its table row would fetch. read / claimed
        is the share of that the kernel's walk fetches."""
        if not self.paged:
            return None
        return (
            self.kv_path, self.kv_bytes_read_last_tick,
            self.kv_bytes_read_total, self.kv_bytes_claimed_total,
        )

    def tick_phase_stats(self) -> dict:
        """The tick's cumulative account for /metrics (every value a
        counter, so ReplicaSet/DisaggCoordinator sum them): seconds, the
        part of them the device had nothing to run, and entries per phase,
        ticks, and the decode blocks' and tokens' fate; ``path`` names the
        run-loop (``mst_sched_async``). Racy snapshot of tick-thread-owned
        counters by design."""
        snap = self._phases.snapshot()
        return {
            "path": "async" if self._async else "sync",
            "ticks": snap["ticks"],
            "phase_seconds": snap["seconds"],
            "device_empty_seconds": snap["empty_seconds"],
            "phase_entries": snap["entries"],
            "blocks_dispatched": self._blocks_dispatched,
            "blocks_harvested": self._blocks_harvested,
            "blocks_abandoned": self._blocks_abandoned,
            "positions_computed": self._positions_computed,
            "tokens_emitted": self._tokens_emitted,
            "tokens_dropped": dict(self._tokens_dropped),
            "drains": dict(self._drains),
            "blocks_by_sampler": dict(self._blocks_by_sampler),
            "join_programs": dict(self._join_programs),
            "join_first_reads": dict(self._join_first_reads),
            "emit_held": dict(self._emit_held),
            "emit_hold_seconds": self._emit_hold_seconds,
            "emit_holds": self._emit_holds,
            "program_device_seconds": snap["device_seconds"],
            "program_dispatch_exposed_seconds": snap["exposed_seconds"],
            "program_runs": snap["runs"],
            "program_late": snap["late"],
            "program_unread_seconds": snap["unread_seconds"],
            **({"diffusion": dict(self._diffusion_stats)}
               if self._diffusion else {}),
        }

    def _tick_account(self) -> dict:
        """What :meth:`close` logs, once: the tick thread's seconds and
        device-empty seconds by phase and the device's programs by kind,
        with what turns them into a decode step (a block's steps, blocks
        and positions) — microseconds, phases nobody entered left out —
        and the joins by where their first token was read."""
        s = self.tick_phase_stats()

        def us(d):
            return {k: round(v, 6) for k, v in d.items() if v}

        return {
            "path": s["path"], "ticks": s["ticks"],
            "decode_block": self.decode_block,
            "blocks_harvested": s["blocks_harvested"],
            "positions_computed": s["positions_computed"],
            "phase_seconds": us(s["phase_seconds"]),
            "device_empty_seconds": us(s["device_empty_seconds"]),
            "program_device_seconds": us(s["program_device_seconds"]),
            "program_dispatch_exposed_seconds":
                us(s["program_dispatch_exposed_seconds"]),
            "program_runs": s["program_runs"],
            "program_late": s["program_late"],
            "program_unread_seconds": round(s["program_unread_seconds"], 6),
            "join_first_reads": s["join_first_reads"],
        }

    def state_stats(self) -> Optional[dict]:
        """The recurrent state pool for /metrics (None for a model without
        one): slots whose state belongs to a request, the pool's bytes, and
        how many times a slot's state was started from zero."""
        if not self._recurrent:
            return None
        return {
            "slots_in_use": sum(r is not None for r in self._slots),
            "bytes": self.engine.state_bytes(),
            "resets": self.state_resets,
        }

    def window_stats(self) -> Optional[dict]:
        """The window layers' rings for /metrics (None for a model without
        window layers): the rings' bytes, which no request changes; the rows
        inside the windows of the slots in use (per slot ``min(positions,
        window)``, times the window layers); ring pages overwritten so far."""
        if not self._ring_pages:
            return None
        eng = self.engine
        window = eng.model.config.sliding_window
        rows = sum(
            min(r.prefill_pos + max(0, r.produced - 1), window)
            for r in self._slots if r is not None
        )
        return {
            "bytes": eng.state_bytes(),
            "rows_live": rows * eng.state_layers,
            "ring_wraps": self.ring_wraps,
        }

    def _note_ring_page(self, position: int) -> None:
        """``position`` is about to be written: where it opens a page past
        the ring's first lap, that page overwrites one."""
        if self._ring_pages and position % self.engine.page_size == 0 and (
            position // self.engine.page_size >= self._ring_pages
        ):
            self.ring_wraps += 1

    def latency_stats(self) -> dict:
        """Bucketed latency snapshots for /metrics: inter-token latency
        (observed at the emit path), admission queue wait (submit → slot
        assignment) and the join (slot assignment → the slot decoding), as
        :meth:`Histogram.to_dict` snapshots — the mergeable currency
        ReplicaSet/DisaggCoordinator aggregate across replicas with
        :meth:`Histogram.merge_dicts`."""
        return {
            "itl": self._h_itl.to_dict(),
            "queue_wait": self._h_queue_wait.to_dict(),
            "join": self._h_join.to_dict(),
        }

    def _account_kv_read(self, live, steps: int, path: Optional[str] = None):
        if not self.paged or not live:
            return
        page = self.engine.page_size
        width = self.engine.slot_pages
        if (path or self.kv_path) == "ragged":
            rows = claimed = 0
            for slot, req in live:
                length = req.prompt.size + max(0, req.produced - 1) + 1
                rows += -(-length // page) * page
                # the row's distinct entries: the claim, and the scratch
                # page where the row is wider
                claimed += min(len(self.pool.pages(slot)) + 1, width)
            claimed *= page
        else:
            rows = claimed = len(live) * width * page
        b = rows * self._kv_row_bytes * steps
        self.kv_bytes_read_last_tick = b
        self.kv_bytes_read_total += b
        self.kv_bytes_claimed_total += claimed * self._kv_row_bytes * steps
        # weights stream once per step regardless of slot count, so per
        # token they amortize over the live slots; KV does not amortize
        tokens = len(live) * steps
        self.kv_bytes_per_token_last = b / tokens
        self.weight_bytes_per_token_last = (
            getattr(self.engine, "weight_stream_bytes", 0) / len(live)
        )

    def hbm_bytes_per_token_stats(self) -> Optional[dict]:
        """{"weights": bytes, "kv": bytes} streamed per decoded token on the
        last decode tick (analytic, from the same model as kv_read_stats);
        None on dense engines. Exported as
        ``mst_decode_hbm_bytes_per_token{kind=}``."""
        if not self.paged:
            return None
        return {
            "weights": self.weight_bytes_per_token_last,
            "kv": self.kv_bytes_per_token_last,
        }

    def prefix_stats(self) -> Optional[tuple[int, int, int, int, int]]:
        """(queries, hits, tokens reused, evictions, cached pages) for
        /metrics; None unless the prefix cache is on."""
        if not (self.paged and self.prefix_cache):
            return None
        return (
            self.prefix_queries, self.prefix_hits, self.prefix_tokens_reused,
            self.prefix_evictions, len(self._prefix_index),
        )

    def _prefix_keys(self, req: _Request) -> list[bytes]:
        """Rolling content-addressed key per FULL prompt page (the vLLM
        block-hash scheme): key_i = blake2b over pages 0..i, chained so the
        whole prompt is hashed once — O(prompt) total, 16 bytes retained per
        page. Memoized on the request (recomputing per _fits poll would make
        a blocked fifo head quadratic)."""
        if req._pkeys is None:
            page = self.engine.page_size
            h = hashlib.blake2b(digest_size=16)
            keys = []
            for i in range(int(req.prompt.size) // page):
                h.update(req.prompt[i * page : (i + 1) * page].tobytes())
                keys.append(h.digest())
            req._pkeys = keys
        return req._pkeys

    def _prefix_lookup(self, req: _Request) -> list[tuple[bytes, int]]:
        """Longest chain of registered pages covering a page-aligned prefix
        of the request's prompt. Capped one token short of the full prompt:
        the last prompt token must go through prefill to produce the logits
        the first sample needs."""
        if not self.prefix_cache:
            return []
        page = self.engine.page_size
        keys = self._prefix_keys(req)
        chain: list[tuple[bytes, int]] = []
        for i in range((int(req.prompt.size) - 1) // page):
            p = self._prefix_index.get(keys[i])
            if p is None:
                break
            chain.append((keys[i], p))
        return chain

    def _evictable_pages(self, exclude: tuple = ()) -> int:
        ex = set(exclude)
        return sum(
            1 for p in self._prefix_index.values()
            if self.pool.refs(p) == 1 and p not in ex
        )

    def _pool_covers(self, need: int, exclude: tuple = ()) -> bool:
        """Can ``need`` pages come from the free list and eviction?"""
        return need <= self.pool.free + self._evictable_pages(exclude)

    def _evict_for(self, n_needed: int):
        """Drop LRU index entries whose page no live slot maps until the
        free list can cover ``n_needed`` pages."""
        while self.pool.free < n_needed:
            victim = next(
                (k for k, p in self._prefix_index.items()
                 if self.pool.refs(p) == 1),
                None,
            )
            if victim is None:
                return
            self.pool.unref((self._prefix_index.pop(victim),))
            self.prefix_evictions += 1

    def _write_table_row(self, slot: int):
        """Publish a decoding slot's grown mapping (a claim writes its row
        in its own program)."""
        self.table = self._row_set(
            self.table, self._put(np.int32(slot)),
            self._put(self.pool.row(self.pool.pages(slot))),
        )

    # ------------------------------------------ prefix store (fleet-wide)
    def _store_digests(self, req: _Request) -> list:
        """The request's chained chunk digests for store keying, memoized
        like ``_pkeys`` (recomputing per _fits poll would make a blocked
        fifo head quadratic). Cleared whenever the prompt changes (fold)."""
        if req._sdigests is None:
            req._sdigests = self.prefix_store.digests_for(req.prompt)
        return req._sdigests

    def _store_lookup(self, req: _Request) -> Optional[tuple]:
        """Poll-safe store LPM for ``req``; absorbs the
        ``cache.prefix_lookup`` fault site into a counted no-hit — the
        stream degrades to plain prefill, never drops."""
        digests = self._store_digests(req)
        if not digests:
            return None
        try:
            # bind the request's trace for the store's self-instrumented
            # prefix_lookup span (tracing.current() inside the store)
            with tracing.bind(req._trace):
                return self.prefix_store.lookup(self, digests)
        except Exception as e:
            self.prefix_store.count_lookup_fault()
            logging.getLogger(__name__).debug(
                "prefix-store lookup failed (plain prefill): %s", e
            )
            return None

    def _store_admit(self, req: _Request, plan: tuple,
                     n: int) -> Optional[tuple]:
        """Admission-side half of a store hit: returns ``(pages,
        reused_tokens)`` for the slot, or None to fall back to plain
        prefill admission (the plan went stale between _fits and here).

        Device plan: lease the entry's shared pages copy-on-write — the
        slot maps them read-only (its own +1 per page on top of the
        entry's claim) and allocates only the uncovered tail; decode and
        tail-prefill write past ``reused_tokens``, so a fork never touches
        the shared prefix. Host plan: allocate the full need fresh and
        scatter the tier block into the prefix pages (prefetch-staged when
        the waiting-line pass got to it, counted demand import otherwise),
        then re-register the imported pages as a device entry so the next
        same-pool admission shares them zero-copy. An import failure keeps
        the already-mapped pages and prefills from token 0 — token-exact
        either way."""
        store = self.prefix_store
        kind, cover = plan
        digests = self._store_digests(req)
        if len(digests) < cover:
            return None  # prompt changed since the plan was computed
        if kind == "device":
            # the tail BEFORE the lease: if the headroom _fits saw has
            # evaporated, take raises with nothing taken and no lease out
            # (a lease never released is an entry that can never demote)
            self._evict_for(n - cover)
            tail = self.pool.take(n - cover)
            lease = store.acquire(self, digests, cover)
            if lease is None:
                self.pool.unref(tail[::-1])  # the free list as it was
                store.count_lookup("miss", digests)
                return None  # entry demoted since _fits; plain prefill
            store.count_lookup("device")
            # the slot's own claim on each shared page, released with its
            # mapping like any mapped page; the entry's claim (+1 at
            # registration) outlives the slot
            req._please = lease
            self.pool.share(lease.pages)
            return list(lease.pages) + tail, lease.n_tokens
        block = store.host_block(digests[cover - 1])
        if block is None:
            store.count_lookup("miss", digests)
            return None  # evicted since _fits; plain prefill
        store.count_lookup("host")
        self._evict_for(n)
        pages = self.pool.take(n)
        page = self.engine.page_size
        try:
            was_staged = block.is_prefetched
            t0 = time.perf_counter()
            with self._phases.span("kv_import"), tracing.bind(req._trace):
                self.cache = import_block(
                    self.cache, block, pages[:cover],
                    share_hash=self._share_hash, codec=self._kv_codec,
                    scatter=self._import_pages, put=self._put,
                )
            dt = time.perf_counter() - t0
            tr = req._trace
            if tr is not None:
                tr.add("handoff_import", t0, t0 + dt, kind="prefix_store",
                       pages=cover, staged=was_staged)
            store.count_import(staged=was_staged, n_tokens=cover * page)
            with self._admission_lock:
                if was_staged:
                    self.prefetch_hits += 1
                else:
                    self.demand_imports += 1
        except Exception as e:
            # the pages are already this slot's — keep them and prefill
            # the whole prompt into them; nothing reached the consumer,
            # so the stream stays token-exact
            store.count_import_fault()
            logging.getLogger(__name__).debug(
                "prefix-store block import failed (re-prefill): %s", e
            )
            return pages, 0
        block.drop_prefetch()  # staged copies served their one import
        lease = store.register(
            self, digests[:cover], pages[:cover],
            req.prompt[: cover * page], cover * page * self._kv_row_bytes,
            force=True,
        )
        if lease is not None:
            req._please = lease
            self.pool.share(lease.pages)  # the promoted entry's own claim
        return pages, cover * page

    def _store_insert(self, req: _Request):
        """Register a freshly prefilled prompt's page-aligned prefix in the
        store, under its insertion policy. Bookkeeping only — dict entries
        and refcounts, no device work — which is what keeps this legal in
        the tick-hot prefill-completion path (MST111 polices the opposite:
        store traffic that marshals host bytes in tick-hot code). The
        request itself holds the entry's first lease; pages it registered
        become shared the moment a same-prefix admission leases them."""
        store = self.prefix_store
        digests = self._store_digests(req)
        if not digests:
            return
        k = len(digests)
        pages = self.pool.pages(req.slot)[:k]
        if len(pages) < k:
            return
        page = self.engine.page_size
        lease = store.register(
            self, digests, pages, req.prompt[: k * page],
            k * page * self._kv_row_bytes,
        )
        if lease is None:
            return
        req._please = lease
        self.pool.share(lease.pages)  # the entry's own claim on each page

    def _drop_prefix_lease(self, req: _Request):
        """Release ``req``'s prefix lease exactly once (idempotent via the
        None swap; a true double release raises inside the store). On the
        LAST release the entry comes back for demotion: its pages leave
        the device for the host tier and return to the free list."""
        lease, req._please = req._please, None
        if lease is None:
            return
        entry = lease.release()
        if entry is not None:
            self._demote_prefix_entry(entry)

    def _demote_prefix_entry(self, entry):
        """Last-release demotion: export the entry's pages as a pure-prefix
        ``KVPageBlock`` (dispatch-only gather; the device→host copy runs on
        the host tier's flusher) keyed by the full-chain digest, then
        return the pages to the pool. Skips the export when the host tier
        already holds the digest (a re-imported prefix demoting again);
        any failure — injected ``cache.export``, tier budget reject —
        just drops the prefix (re-prefilled on next use), never an error
        the stream can see."""
        store = self.prefix_store
        digest = entry.digests[-1]
        try:
            if not store.host_contains(digest):
                block = export_block(
                    self.cache, entry.pages,
                    page_size=self.engine.page_size,
                    n_tokens=len(entry.pages) * self.engine.page_size,
                    prompt=entry.tokens, history=[], produced=0,
                    resume_keys=None, resume_recent=None,
                    share_hash=self._share_hash, codec=self._kv_codec,
                    gather=self._export_pages, put=self._put,
                )
                store.host_put(digest, block)
        except Exception as e:
            store.count_demote_drop()
            logging.getLogger(__name__).debug(
                "prefix demotion export failed (prefix dropped): %s", e
            )
        self.pool.unref(entry.pages)

    def _pod_fetch_waiting(self):
        """Consult the pod view for head-of-line waiting requests whose
        prefix missed the LOCAL store (pod.PodPrefixFederation): when a
        live peer's gossiped inventory advertises the digest, a background
        worker pulls the owner's exported block into the local host tier
        — pod-wide, the prefix prefills ONCE — while ``_fits`` holds the
        request on the ``_podfetch`` flag. Every failure (fault, stale
        inventory, owner death, timeout, integrity) resolves the flag and
        the request prefills plain: degraded, never dropped. All
        federation traffic lives here and in the worker thread, off the
        tick-hot functions (MST115)."""
        store = self.prefix_store
        fed = getattr(store, "federation", None) if store is not None \
            else None
        if fed is None or not self._waiting:
            return
        for req in self._waiting[:4]:
            if req.cancelled or req.spilled or req._block is not None \
                    or req._podfetch is not None:
                continue
            digests = self._store_digests(req)
            if not digests or self._store_lookup(req) is not None:
                req._podfetch = "done"  # nothing to federate / local hit
                continue
            req._podfetch = "pending"
            threading.Thread(
                target=self._pod_fetch_one, args=(req, digests[-1]),
                name="mst-pod-prefix-fetch", daemon=True,
            ).start()

    def _pod_fetch_one(self, req: _Request, digest: bytes):
        """Background federation fetch for one waiting request. The
        federation counts every outcome by kind; this worker only flips
        the admission gate — on success the next ``_store_lookup`` poll
        sees the host-tier hit and admission imports it via the ordinary
        staged-prefetch/demand path."""
        try:
            self.prefix_store.federation.fetch(digest)
        except Exception as e:  # noqa: BLE001 — degrade to plain prefill
            logging.getLogger(__name__).debug(
                "pod prefix fetch failed (plain prefill): %s", e
            )
        req._podfetch = "done"

    def _prefetch_store_waiting(self):
        """Stage host-tier prefix blocks for head-of-line waiting requests
        (the same PRESERVE-style overlap as the spill prefetch): a
        dispatch-only ``device_put`` here means the admission scatter a few
        ticks later consumes device-resident arrays instead of
        demand-marshaling host numpy. Bounded like _prefetch_waiting so a
        deep queue can't turn the pass into a copy storm."""
        store = self.prefix_store
        if store is None or not self._waiting:
            return
        budget = 2
        for req in self._waiting[:4]:
            if budget == 0:
                break
            if req.cancelled or req.spilled or req._block is not None:
                continue
            plan = self._store_lookup(req)
            if plan is None or plan[0] != "host":
                continue
            digests = self._store_digests(req)
            block = store.host_block(digests[plan[1] - 1])
            if block is None or not block.is_host or block.is_prefetched:
                continue
            budget -= 1
            try:
                block.prefetch(put=self._put, codec=self._kv_codec)
                with self._admission_lock:
                    self.prefetches += 1
            except Exception as e:
                with self._admission_lock:
                    self.prefetch_faults += 1
                logging.getLogger(__name__).debug(
                    "prefix block prefetch failed (demand import): %s", e
                )

    def stage_resume(self, state) -> bool:
        """Dispatch-only host→device staging of an incoming resume block —
        the pod receiving host calls this BEFORE submitting the shipped
        request (``generate_step(..., _resume=state)``), so the block's
        host→device DMA rides alongside the decode block already in
        flight and the admission scatter consumes device-resident arrays
        (the same PRESERVE-style overlap as the spill/store prefetch
        passes). Returns True when a stage was dispatched; any failure is
        absorbed into the counted demand-import path."""
        block = getattr(state, "block", None)
        if block is None or not getattr(block, "is_host", False) \
                or block.is_prefetched:
            return False
        try:
            block.prefetch(put=self._put, codec=self._kv_codec)
            with self._admission_lock:
                self.prefetches += 1
            return True
        except Exception as e:  # noqa: BLE001 — degrade to demand import
            with self._admission_lock:
                self.prefetch_faults += 1
            logging.getLogger(__name__).debug(
                "resume block prefetch failed (demand import): %s", e
            )
            return False

    def close(self, timeout: float = 10.0):
        with self._start_lock:
            self._stop = True
            t = self._thread
        if t is not None:
            if t.is_alive():
                # a sentinel for a dead thread would sit in _submit forever,
                # inflating the queued gauge (and the pod-gossiped pressure)
                # by one per repeated close
                # mst: allow(MST201): wake sentinel; Queue locks internally
                self._submit.put(None)  # wake the idle wait
            t.join(timeout=timeout)
            if t.is_alive():
                # a tick is wedged (stuck device op / injected fault): the
                # daemon thread can't be reclaimed, so record the leak —
                # /health flips to degraded and mst_scheduler_thread_live
                # drops to 0 instead of pretending the close succeeded
                with self._start_lock:
                    self.thread_wedged = True
                # post-mortem: freeze the flight recorder so the wedged
                # tick's victims keep their timelines after the ring cycles
                tracing.auto_snapshot("wedge:scheduler")
                logging.getLogger(__name__).error(
                    "scheduler thread failed to exit within %.0fs — a tick "
                    "is wedged; the thread is abandoned (daemon) and /health "
                    "now reports degraded", timeout,
                )
            elif not self._account_logged:
                # the run nobody traced leaves its whole account in the log
                self._account_logged = True
                logging.getLogger(__name__).info(
                    "tick account: %s", json.dumps(self._tick_account())
                )
        spill = self.spill
        if spill is not None:
            spill.close()
        store = self.prefix_store
        if store is not None:
            # drop this engine's device entries from the fleet store: the
            # pool backing those pages is going away with the engine, so
            # any index entry pointing at them would be a use-after-free
            # for the next admission. Host-tier blocks survive (they're
            # self-contained numpy) and keep serving other replicas.
            store.drop_owner(self)
        # the page pool dies with the engine: index-resident prefix pages
        # (legitimately out of the free list while the batcher lives) are
        # discarded wholesale, so retire them from the leak ledger too
        if self.paged:
            self.pool.forget()
        # release engine-held resources (a shared-weight store lease drops
        # its ref here — drain/retire/hot-swap all funnel through close())
        eng_close = getattr(self.engine, "close", None)
        if eng_close is not None:
            eng_close()
        draft = self.draft
        if draft is not None and hasattr(draft, "close"):
            draft.close()

    # ------------------------------------------------------------ internals
    def _ensure_running(self):
        with self._start_lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop = False
                self._thread = threading.Thread(
                    target=self._loop, name="continuous-batcher", daemon=True
                )
                self._thread.start()

    def first_sample(self, logits, keys, sp, recent, rep_sizes, slot):
        """Sample the first token of the request in ``slot`` from its prefill
        logits, using the same split-then-sample key chain as the decode
        step, leaving other slots' keys untouched. ``logits`` is the (1, V)
        prefill output; the returned logprobs keep that shape (indexing a
        global array must stay inside this jit)."""
        split = jax.random.split(keys[slot])
        key_new, sub = split[0], split[1]
        row = jnp.arange(self.W) >= self.W - rep_sizes[slot]
        masked = jnp.where(row, recent[slot], -1)
        tok, logprobs = sample_token_batched(
            sub[None],
            logits.reshape(1, -1),
            jax.tree.map(lambda x: x[slot][None], sp),
            masked[None],
            jnp.ones((1,), bool),
        )
        keys = keys.at[slot].set(key_new)
        recent = recent.at[slot].set(
            jnp.concatenate([recent[slot, 1:], tok.astype(jnp.int32)])
        )
        return tok[0], logprobs, keys, recent

    def _assign_slot(self, req: _Request, slot: int):
        """Claim ``slot`` for ``req``: the host's bookkeeping (pages,
        references, eviction, prefix chain or store plan), then the slot's
        device-side state in one program (``_claim``). The PRNG key and the
        repetition window are seeded with the first token, not here.
        Prefill happens incrementally in the loop — one chunk per scheduler
        tick — so active slots keep decoding during admission."""
        # queue wait ends here: submit (or re-queue after preempt/wake) →
        # slot assignment. Histogram always; span only when traced.
        now = time.perf_counter()
        if req._t_submit:
            self._h_queue_wait.observe(max(0.0, now - req._t_submit))
        req._t_join = now
        tr = req._trace
        if tr is not None:
            tr.add("queue_wait", req._t_submit or now, now, slot=slot)
        reused_tokens = 0
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        block = self._take_block(req)
        if block is not None and self._import_block(req, slot, block):
            return
        pages = None
        if self.paged:
            n = self._need_pages(req)
            got = None
            if self.prefix_store is not None:
                # one admitted request == one token of insert budget (the
                # deterministic damping clock — no wall time on this path)
                self.prefix_store.note_admission()
                splan, req._splan = req._splan, None
                got = self._store_admit(req, splan, n) if splan else None
                if got is None and splan is None:
                    self.prefix_store.count_lookup(
                        "miss", self._store_digests(req) or None
                    )
            if got is not None:
                # the shared (or imported) prefix KV is already mapped
                pages, reused_tokens = got
            else:
                chain = (req._chain if req._chain is not None
                         else self._prefix_lookup(req))
                req._chain = None
                if self.prefix_cache:
                    self.prefix_queries += 1
                    if chain:
                        self.prefix_hits += 1
                        reused_tokens = len(chain) * self.engine.page_size
                        self.prefix_tokens_reused += reused_tokens
                    for key, _ in chain:
                        self._prefix_index.move_to_end(key)
                shared = [p for _, p in chain]
                # claim the chain BEFORE evicting: at ref 2 its pages are
                # invisible to _evict_for, which must only reclaim OTHER
                # index-only pages (matching the _fits exclude accounting)
                self.pool.share(shared)
                self._evict_for(n - len(shared))
                pages = shared + self.pool.take(n - len(shared))
            self.pool.bind(slot, pages)
        self._claim(req, slot, pages, reused_tokens)
        if self.spec_tracker is not None:
            # new stream in the slot: window back to the probe rung, no
            # carried-over acceptance history from the previous occupant
            self.spec_tracker.reset(slot)
        self._slots[slot] = req
        note_acquire("scheduler.slot", (id(self), slot))
        req.slot = slot
        # prefill starts past the reused prefix — its KV is already mapped
        req.prefill_pos = reused_tokens

    def _claim(self, req: _Request, slot: int, pages: Optional[list],
               start: int):
        """The claim's device writes, one dispatch with one host-made
        argument: the slot's table row (paged), its offset ``start``, the
        request's sampler row and repetition context, the draft's offset 0
        (with a draft)."""
        draft = self.draft is not None
        table, offset, self.sp, self.rep_sizes, doffset = self._claim_slot(
            self._put(_pack_i32(
                np.int32(slot), np.int32(start), np.int32(req.rep_context),
                *([self.pool.row(pages)] if self.paged else []),
                *req.sp,
            )),
            self.table if self.paged else None,
            self.cache.offset, self.sp, self.rep_sizes,
            self.dcache.offset if draft else None,
        )
        self._join_programs["claim"] += 1
        if self.paged:
            self.table = table
        self.cache = self.cache._replace(offset=offset)
        if draft:
            self.dcache = self.dcache._replace(offset=doffset)

    def _take_block(self, req: _Request) -> Optional[object]:
        """Resolve the request's pending KVPageBlock, if any: one handed in
        by the dispatcher (cross-replica migration) or one parked in the
        spill tier at preemption. A tier entry that was LRU-evicted since
        the preemption degrades here to the discard path — fold and
        re-prefill, still token-exact via the stashed sampler rows."""
        if req._block is not None:
            block, req._block = req._block, None
            return block
        if not req.spilled:
            return None
        req.spilled = False
        block = self.spill.take(req) if self.spill is not None else None
        if block is None:
            self._fold_history(req)
            with self._admission_lock:
                self.spill_fallbacks += 1
        return block

    def _import_block(self, req: _Request, slot: int, block) -> bool:
        """Admission via page import: allocate the request's pages and
        scatter the block's payload into them instead of re-prefilling,
        then restore the sampler state the block carries — offset, PRNG
        row, repetition window, and the pending last token — so the next
        decode step emits exactly what the uninterrupted run would have.
        Any failure (fault-injected ``cache.import``, corrupt block, pool
        exhausted mid-import, geometry mismatch) releases what was claimed
        and returns False: the caller falls back to normal re-prefill
        admission, which can never double-emit because nothing was queued
        to the consumer here."""
        if not self.paged or self.draft is not None or self._slot_state:
            # (a block holds full-length pages of K/V only: no recurrent
            # state, no window layer's ring)
            self._fold_history(req)
            return False
        page = self.engine.page_size
        pages: list = []
        try:
            if block.page_size != page:
                raise ValueError(
                    f"block page_size {block.page_size} != pool page {page}"
                )
            data_pages = block.n_pages
            need = max(self._need_pages(req, block=block), data_pages)
            self._evict_for(need)
            if self.pool.free < need:
                raise RuntimeError(
                    f"target pool exhausted mid-import: need {need} pages, "
                    f"{self.pool.free} free"
                )
            pages = self.pool.take(need)
            # residency accounting, read BEFORE the import consumes the
            # stage: a host block with device-staged pages is the overlapped
            # path (prefetch hit); host without a stage is the demand import
            # this PR demotes to a counted fallback; a still-device block
            # (flusher hasn't run) is neither
            was_host = block.is_host
            was_staged = block.is_prefetched
            t0 = time.perf_counter()
            with self._phases.span("kv_import"), tracing.bind(req._trace):
                self.cache = import_block(
                    self.cache, block, pages[:data_pages],
                    share_hash=self._share_hash, codec=self._kv_codec,
                    scatter=self._import_pages, put=self._put,
                )
            dt = time.perf_counter() - t0
            tr = req._trace
            if tr is not None:
                tr.add("handoff_import", t0, t0 + dt, pages=data_pages,
                       staged=was_staged)
            if was_host:
                with self._admission_lock:
                    if was_staged:
                        self.prefetch_hits += 1
                    else:
                        self.demand_imports += 1
        except Exception as e:
            logging.getLogger(__name__).debug(
                "KV block import failed (falling back to re-prefill): %s", e
            )
            self.pool.unref(pages)
            self._fold_history(req)
            with self._admission_lock:
                self.spill_fallbacks += 1
            return False
        self.pool.bind(slot, pages)
        # offset = valid KV rows; the next decode step writes row n_tokens
        self._claim(req, slot, pages, block.n_tokens)
        resume = self._put(_pack_i32(
            np.int32(slot),
            np.asarray(block.resume_keys, np.uint32),
            np.asarray(block.resume_recent, np.int32),
            np.int32(block.last_tok),
        ))
        self._phases.dispatched("other")
        self.keys, self.recent, self.last_tok, self.active = self._resume_slot(
            resume, self.keys, self.recent, self.last_tok, self.active,
        )
        self._phases.returned()
        self._join_programs["other"] += 1
        req.resume_keys = None
        req.resume_recent = None
        req.history = [int(t) for t in block.history]
        self._slots[slot] = req
        note_acquire("scheduler.slot", (id(self), slot))
        req.slot = slot
        req.prefill_pos = req.prompt.size
        req.draft_pos = req.prompt.size
        with self._admission_lock:
            self.spill_hits += 1
        self._h_join.observe(time.perf_counter() - req._t_join)
        return True

    @staticmethod
    def _chunk_at(prompt: np.ndarray, pos: int, c: int):
        """Slice one right-padded prefill chunk at ``pos``; returns
        (chunk (c,), n_valid) — shared by the target and draft branches so
        their padding semantics can never diverge."""
        chunk = prompt[pos : pos + c]
        n_valid = chunk.size
        if n_valid < c:
            chunk = np.pad(chunk, (0, c - n_valid))
        return chunk, n_valid

    def _prefill_rows(self, req: _Request) -> int:
        """Prompt tokens the join's chunks prefill: all of them — or, for a
        family that generates by diffusion over blocks, the prompt's whole
        blocks (the ``P mod L`` tokens left start the first decode block:
        ``finish_join`` puts them there and moves ``prefill_pos`` to the
        prompt's end)."""
        n = req.prompt.size
        return n - n % self._diffusion if self._diffusion else n

    def _prefill_done(self, req: _Request) -> bool:
        """Admission prefill complete on EVERY engine: the target (which may
        start past a reused prefix) and, when speculating, the draft (which
        always prefills from 0)."""
        return req.prefill_pos >= req.prompt.size and (
            self.draft is None or req.draft_pos >= req.prompt.size
        )

    def _prefill_one_chunk(self, req: _Request):
        """Run ONE prefill chunk for a mid-admission request — on the target
        and, when speculating, the draft, each at its own position (a prefix
        hit advances only the target's start). On the last chunk of BOTH,
        sample the first token and activate the slot for decode, in one
        program (``finish_join``); the target's final-chunk logits are
        stashed while the draft catches up. The chunk's tokens, count and
        slot are numpy arrays of fixed dtypes: nothing eager runs here.

        Nothing of a last chunk is read here either: the first token stays
        on the device, noted in ``_first_unread``, and the drain's held
        tokens stay held. The async tick dispatches the decode block behind
        the chunk and only then lets the hold go and reads
        (``_read_first_tokens``); the sync tick, whose mirrors replay this
        very call, reads before it returns."""
        eng = self.engine
        c = eng.prefill_chunk
        put = self._put
        slot_arr = put(np.int32(req.slot))
        tr = req._trace
        t0 = time.perf_counter() if tr is not None else 0.0
        ticket = None  # of the program dispatched last
        rows = self._prefill_rows(req)
        if req.prefill_pos < rows:
            chunk, n_valid = self._chunk_at(req.prompt[:rows], req.prefill_pos, c)
            if self._recurrent and req.prefill_pos == 0:
                self.state_resets += 1
            self._note_ring_page(req.prefill_pos)
            tokens = put(chunk[None])
            valid = put(np.int32(n_valid))
            # from here the device has the chunk
            ticket = self._phases.dispatched("chunk")
            logits, self.cache = eng.prefill_slot()(
                eng.layer_params, eng.layer_masks, eng.vocab_parts,
                eng.shared_params, tokens, slot_arr, self.cache, valid,
                self.table if self.paged else None,
            )
            self._phases.returned()
            self._chunk_unread = (ticket, logits)
            self._join_programs["chunk"] += 1
            req.prefill_pos += n_valid
            if req.prefill_pos >= rows:
                req._last_logits = logits
        if self.draft is not None and req.draft_pos < req.prompt.size:
            d = self.draft
            chunk, n_valid = self._chunk_at(req.prompt, req.draft_pos, c)
            tokens = put(chunk[None])
            valid = put(np.int32(n_valid))
            ticket = self._phases.dispatched("other")
            _, self.dcache = d.prefill_slot()(
                d.layer_params, d.layer_masks, d.vocab_parts, d.shared_params,
                tokens, slot_arr, self.dcache, valid, None,
            )
            self._phases.returned()
            self._join_programs["chunk"] += 1
            req.draft_pos += n_valid
        if tr is not None:
            tr.add("prefill", t0, time.perf_counter(), slot=req.slot,
                   pos=req.prefill_pos, chunk=c)
        if req.prefill_pos < rows or (
            self.draft is not None and req.draft_pos < req.prompt.size
        ):
            # the device has the chunk: the drain's tokens leave now, and
            # the streams write them out while it computes
            self._flush_held("chunk")
            return
        logits = req._last_logits
        req._last_logits = None

        if self.prefix_cache:
            # Register every FULL prompt page under its whole-prefix content
            # key. Decode writes start at prompt.size, past all of them, so a
            # registered page is immutable for its pool lifetime. Pages a
            # concurrent identical prompt registered first just get touched.
            pages = self.pool.pages(req.slot)
            for i, key in enumerate(self._prefix_keys(req)):
                if key in self._prefix_index:
                    self._prefix_index.move_to_end(key)
                    continue
                self._prefix_index[key] = pages[i]
                self.pool.share((pages[i],))  # the index's own claim
        elif self.prefix_store is not None and req._please is None:
            # fleet-store insertion (bookkeeping only — refcounts and dict
            # entries, no device work on this hot path): the freshly
            # prefilled full prompt pages become a shareable device entry,
            # subject to the store's min-hits / burst / brownout damping.
            # A slot that ADMITTED via the store (req._please set) already
            # holds its lease — re-registering would double-claim pages.
            self._store_insert(req)

        # Seed the PRNG key and repetition window only NOW: decode ticks for
        # other slots ran between this request's chunks and they split/shift
        # ALL M rows — setting these at assignment would leave the slot with
        # mangled state by prefill completion and break the deterministic
        # serial-parity guarantee for multi-chunk prompts.
        if req.resume_keys is not None:
            # resuming a preempted request: restore the stashed sampler state
            # so the sample below continues the request's exact PRNG chain
            # and repetition window — the token it emits is the one the
            # uninterrupted run would have produced next
            key_row, recent_row = req.resume_keys, req.resume_recent
            req.resume_keys = None
            req.resume_recent = None
        else:
            key_row = seed_key_row(req.seed)  # jax.random.PRNGKey's words
            recent_row = np.full((self.W,), -1, np.int32)
            seen = req.prompt[:rows]  # a first block's prompt tail enters
            # the window with its block, at the commit
            tail = seen[-req.rep_context:] if req.rep_context else seen[:0]
            if tail.size:
                recent_row[self.W - tail.size:] = tail
        first = ()
        if self._diffusion:
            # the first decode block: the prompt's tail, then masks
            first = diffusion.first_block(
                req.prompt[rows:], self._diffusion,
                self.engine.model.config.mask_token_id,
            )
            req._block_skip = req.prompt.size - rows
            req.prefill_pos = req.prompt.size  # the slot decodes from here
        (tok, logprobs, self.keys, self.recent, self.last_tok,
         self.active) = self._finish_join(
            logits,
            put(_pack_i32(np.int32(req.slot), key_row, recent_row, *first)),
            self.keys, self.recent, self.sp, self.rep_sizes,
            self.last_tok, self.active,
        )
        self._join_programs["finish"] += 1
        # chunk and first token are dispatched and nothing is read: the
        # slot decodes from the next block on, whoever reads the token when
        self._first_unread.append((req, tok, logprobs, ticket))
        if not self._async:
            self._read_first_tokens("before_block")

    def _block_leads(self) -> bool:
        """Whether the decode block this tick dispatches may go in front of
        the read of the first tokens its joins left on the device. Nothing
        on the device needs the host's copy of them (``finish_join`` leaves
        ``last_tok``, ``active``, ``keys`` and ``recent`` there, and
        ``_dispatch_block`` reads none of it back), so it may unless the
        host has to act on a token first: a ``prefill_only`` joiner's slot
        is handed off and never enters a block here; a batcher that
        speculates builds its next round's guess from host history; page
        growth that might preempt would fold a history the token is not in
        yet."""
        return (
            self._spec_mode == "off"
            and not any(f[0].prefill_only for f in self._first_unread)
            and self._growth_fits()
        )

    def _read_first_tokens(self, order: str):
        """Read and emit, oldest first, the first tokens that joins closed
        since the last call left on the device; ``order`` says on which side
        of the tick's decode block the read fell
        (``mst_join_first_reads_total``). The drain's held tokens leave
        first, ahead of any joiner's first, and before the blocking read: no
        hold spans a wait on the device. Each read returns at the end of
        that join's last chunk and first-token program, which the account
        learns here, by the chunk's ticket: a block dispatched behind it
        stays queued."""
        firsts, self._first_unread = self._first_unread, []
        if not firsts:
            return
        with self._phases.span("first_token"):
            self._flush_held("chunk")
            for req, tok, logprobs, ticket in firsts:
                # the blocking read of the chunk and its sample
                tok = int(tok)
                if ticket is not None:  # (None: a join that ran no chunk)
                    unread = self._chunk_unread
                    if unread is not None and unread[0] <= ticket:
                        self._chunk_unread = None
                    self._phases.ready(ticket)
                self._join_first_reads[order] += 1
                if tok >= 0:  # (negative: this family's prefill yields none)
                    self._emit(req, tok, logprobs)
                self._h_join.observe(time.perf_counter() - req._t_join)
                if req.prefill_only and req.slot >= 0:
                    # disaggregated handoff: the first token is the prefill
                    # replica's whole deliverable — park the request; the
                    # tick exports its block (off this hot path) before
                    # dispatching decode, so the slot never enters a decode
                    # block here
                    self._handoff_ready.append(req)

    def _hand(self, req: _Request, item):
        """The scheduler thread's one way onto a request's ``out`` queue: a
        token, the ``None`` that ends a stream, an error. While a hold is
        open (a tick that drained for a joiner, until the device has the
        joiner's chunk: ``_tick_async``) the item waits its turn in the
        hold, behind everything handed before it — one list for all
        streams, so each stream's order is the order of the calls."""
        held = self._held
        if held is None:
            req.out.put(item)
            return
        if not held:
            self._held_since = time.perf_counter()
        held.append((req.out, item))

    def _flush_held(self, point: str):
        """Close the hold and put what it kept, in order; ``point`` names
        the call site for ``mst_emit_held_total``. With no hold open, or an
        empty one, nothing happens and nothing is counted."""
        held, self._held = self._held, None
        if not held:
            return
        for out, item in held:
            out.put(item)
        self._emit_held[point] += len(held)
        self._emit_hold_seconds += time.perf_counter() - self._held_since
        self._emit_holds += 1

    def _emit(self, req: _Request, token: int, logprobs):
        now = time.perf_counter()
        if req.produced == 0:
            # first token leaves the scheduler: the TTFT stamp on a traced
            # timeline (the TTFT histogram itself is recorded server-side,
            # where the client-visible first write happens)
            tr = req._trace
            if tr is not None:
                tr.point("first_token", slot=req.slot)
        elif req._t_last_emit:
            # inter-token latency: the gap between consecutive emits of one
            # stream — always-on metric, same grade as the tick counters
            self._h_itl.observe(now - req._t_last_emit)
        req._t_last_emit = now
        req.produced += 1
        if req.produced < req.max_tokens:  # the token goes back in as a row
            self._note_ring_page(req.prefill_pos + req.produced - 1)
        # history is the tokens emitted since the last prompt fold — the
        # overcommit preempt/resume bookkeeping, and (always, since drain
        # can migrate any request) the payload a ResumeState ships so the
        # target replica can continue this exact stream
        req.history.append(int(token))
        # decode blocks emit TokenLogprobs summaries (or None); the first
        # token of a request still carries a lazy (1, V) device row from its
        # prefill sample — the server handles both forms
        self._hand(req, (token, logprobs))
        if req.produced >= req.max_tokens:
            self._finish(req)

    def _finish(self, req: _Request):
        if req.slot >= 0:
            self.active = self._row_set(
                self.active, self._put(jnp.asarray(req.slot, jnp.int32)),
                self._put(jnp.asarray(False)),
            )
            if self.paged:
                # The slot is inactive from the next DISPATCH on (garbage
                # ticks route to the scratch table row), so its pages go
                # back to the pool immediately — even when an async
                # lookahead block is still writing them. Safe because the
                # only later writers of a recycled page (growth for another
                # slot's NEXT dispatch; admission prefill, which quiesces
                # first) are blocks the in-flight one strictly precedes on
                # the device stream, and both attention paths mask rows past
                # each owner's frontier — the same property that makes
                # dirty-page recycling sound in sync mode. Decode-region
                # garbage can never reach an index-registered prompt page
                # (registration covers only full PROMPT pages; decode
                # writes start past them). Index-registered pages survive
                # as cache entries until LRU eviction needs them back.
                self.pool.release(req.slot)
                # the slot's claim on any store-shared prefix pages is gone
                # with pool.release; the lease is the ENTRY's lifetime —
                # last release demotes the prefix to the host tier
                # (dispatch-only export; the flusher does the host copy)
                self._drop_prefix_lease(req)
                if self._inflight is not None and not self._diffusion:
                    # (a diffusion slot's offset is only ever read through
                    # the ragged body, which sends a dead slot's rows to
                    # the scratch page whatever its offset says, and the
                    # next claim sets it)
                    # the in-flight block's frozen active mask advances this
                    # dead slot's offset one block past its true end; queue
                    # a rewind CHAINED AFTER it (self.cache is its output
                    # future) so the reclaimed slot's offset never points
                    # past the pages just returned — no host sync involved.
                    # A speculative round advances by its data-dependent
                    # accepted count, not decode_block: rewind by the same
                    # device-side value (still future-chained, still async)
                    if isinstance(self._inflight, _InflightSpec):
                        amount = self._inflight.outs[0][req.slot]
                    else:
                        amount = self._put(
                            jnp.asarray(self.decode_block, jnp.int32)
                        )
                    self.cache = self.cache._replace(
                        offset=self._rewind_offset(
                            self.cache.offset,
                            self._put(jnp.asarray(req.slot, jnp.int32)),
                            amount,
                        )
                    )
            self._slots[req.slot] = None
            note_release("scheduler.slot", (id(self), req.slot))
            req.slot = -1
        # completion stamp for the drain-rate Retry-After estimate; cancelled
        # reaps count too — they free queue capacity all the same
        with self._admission_lock:
            self._finish_times.append(self._clock())
        tr = req._trace
        if tr is not None:
            tr.point("finish", produced=req.produced)
            if req._trace_own:
                # a self-begun trace retires here; a server-owned one is
                # finished by the server after its last SSE write
                tracing.finish(tr)
        self._hand(req, None)

    def _reap_cancelled(self):
        for req in list(self._slots):
            if req is not None and req.cancelled:
                self._finish(req)

    def _decode_block_prog(self, want_lp: bool):
        """``decode_block`` continuous-batching steps scanned into one
        program; the active mask is frozen for the block (a slot finishing
        mid-block keeps computing — its extra tokens are clamp-written into
        its own cache region and discarded host-side, so other slots'
        streams are unaffected and serial parity holds)."""
        if want_lp not in self._decode_block_progs:
            eng = self.engine
            M = self.M
            if self._diffusion:
                # a step is one forward over every slot's block — wide (a
                # finished block's commit, the next block's denoise behind
                # it) then narrow, by turns; ``tok`` is the blocks and their
                # masks, and what the host reads of a forward is a tree
                # (diffusion.block_forward)
                steps = [eng.diffusion_cb(want_lp, wide) for wide in (True, False)]
            else:
                step = eng.decode_cb()

            def block(layer_params, masks, vparts, shared, tok, cache, active,
                      recent, keys, sp, rep_sizes, table):
                def forwards(carry, steps):
                    outs = []
                    for step in steps:
                        tok, cache, recent, keys = carry
                        out, *carry = step(
                            layer_params, masks, vparts, shared, tok, cache,
                            active, recent, keys, sp, rep_sizes, table,
                        )
                        outs.append(out)
                    return tuple(carry), outs

                def body(carry, _):
                    tok, cache, recent, keys = carry
                    tok, logprobs, cache, recent, keys = step(
                        layer_params, masks, vparts, shared, tok, cache,
                        active, recent, keys, sp, rep_sizes, table,
                    )
                    if want_lp:
                        out = (tok, *block_lp_outputs(tok.reshape(M), logprobs))
                    else:
                        out = (tok,)
                    return (tok, cache, recent, keys), out

                carry = (tok, cache, recent, keys)
                if self._diffusion:
                    # pairs of a wide and a narrow forward, scanned; an odd
                    # block ends on one more wide forward
                    pairs, odd = divmod(self.decode_block, 2)
                    carry, outs = jax.lax.scan(
                        lambda c, _: forwards(c, steps), carry, None,
                        length=pairs,
                    )
                    # (pairs, …) twice → (2 * pairs, …), forward by forward
                    outs = jax.tree.map(
                        lambda a, b: jnp.stack([a, b], axis=1).reshape(
                            -1, *a.shape[1:]
                        ),
                        *outs,
                    )
                    if odd:
                        carry, (last,) = forwards(carry, steps[:1])
                        outs = jax.tree.map(
                            lambda a, b: jnp.concatenate([a, b[None]]),
                            outs, last,
                        )
                else:
                    carry, outs = jax.lax.scan(
                        body, carry, None, length=self.decode_block
                    )
                return (outs, *carry)

            # The CPU client executes donated computations inline at
            # dispatch (no async stream to alias on), which would serialize
            # the async pipeline: block t+1's dispatch would block for its
            # own execution. Donation only pays on accelerator backends —
            # there it aliases the cache buffers without blocking; on CPU
            # skip it so dispatch stays async and the overlap is real.
            donate = () if jax.default_backend() == "cpu" else (5, 7, 8)
            # two programs, two names: a profile's ``jit_block`` is the
            # streamed block, ``jit_block_lp`` the one with log-probabilities
            if want_lp:
                block.__name__ = block.__qualname__ = "block_lp"
            self._decode_block_progs[want_lp] = jax.jit(
                block, donate_argnums=donate
            )
        return self._decode_block_progs[want_lp]

    def _fold_history(self, req: _Request):
        """Legacy discard-preemption bookkeeping: fold the emitted tokens
        into the prompt so resume re-prefills them (the recompute strategy —
        the KV is gone). Clears any stale migration state; counts the
        re-prefill work (``reprefill_tokens``)."""
        req.spilled = False
        req._block = None
        if req.history:
            with self._admission_lock:
                self.reprefill_tokens += req.prompt.size + len(req.history)
            req.prompt = np.concatenate(
                [req.prompt, np.asarray(req.history, np.int32)]
            )
            req.history = []
            req._pkeys = None  # prompt changed: content keys are stale
            req._sdigests = None  # and so are the store digests
        req._splan = None  # any admission plan predates the fold

    def _spill_block(self, req: _Request) -> bool:
        """Export ``req``'s KV page chain into the spill tier. Device-side
        this only DISPATCHES a page gather (the jitted export program); the
        blocking device→host copy happens on the tier's flusher thread, so
        the tick never stalls on the transfer (MST106). Returns False —
        caller falls back to discard — on any failure: tier disabled, over
        budget, accounting drift, or an injected ``cache.export`` fault."""
        if self.spill is None or not req.history:
            return False
        slot = req.slot
        page = self.engine.page_size
        # valid KV rows: the last emitted token's KV is unwritten (its id
        # is last_tok / history[-1], fed as the next decode input)
        n_tokens = req.prompt.size + max(0, len(req.history) - 1)
        n_pages = -(-max(1, n_tokens) // page)
        pages = self.pool.pages(slot)[:n_pages]
        ok = False
        if len(pages) == n_pages:
            try:
                block = export_block(
                    self.cache, pages, page_size=page, n_tokens=n_tokens,
                    prompt=req.prompt, history=req.history,
                    produced=req.produced, resume_keys=req.resume_keys,
                    resume_recent=req.resume_recent,
                    share_hash=self._share_hash, codec=self._kv_codec,
                    gather=self._export_pages, put=self._put,
                )
                ok = self.spill.put(req, block)
            except Exception as e:
                logging.getLogger(__name__).debug(
                    "KV spill export failed for slot %d: %s", slot, e
                )
        req.spilled = ok
        with self._admission_lock:
            if ok:
                self.spills += 1
            else:
                self.spill_fallbacks += 1
        return ok

    def _suspend_slot(self, req: _Request):
        """Vacate ``req``'s slot, preserving everything a token-exact
        resume needs. Mid-decode, its page chain is exported to the spill
        tier when one is configured (resume re-imports it — one page
        scatter instead of a re-prefill); otherwise, or on export failure,
        its emitted tokens fold into its prompt and resume re-prefills
        them. Either way the device-side sampler state is stashed so the
        next sampled token continues the exact PRNG/repetition chain.
        Mid-prefill there is nothing to stash; the prefill restarts.
        Shared by overcommit preemption and cold-slot spill; the caller
        decides where the request goes (waiting line vs parked list)."""
        slot = req.slot
        tr = req._trace
        t0 = time.perf_counter() if tr is not None else 0.0
        if self._prefill_done(req):
            # one transfer for both sampler rows; runs only quiesced (no
            # in-flight block) in async mode, so this sync is off the
            # steady-state decode path
            keys_h, recent_h = jax.device_get((self.keys, self.recent))
            req.resume_keys = np.asarray(keys_h[slot])
            req.resume_recent = np.asarray(recent_h[slot])
            with tracing.bind(tr):  # kv_transfer export self-instruments
                if not self._spill_block(req):
                    self._fold_history(req)
        req._chain = None
        req._splan = None
        req._last_logits = None
        req.prefill_pos = 0
        req.draft_pos = 0
        self.active = self._row_set(
            self.active, self._put(jnp.asarray(slot, jnp.int32)),
            self._put(jnp.asarray(False)),
        )
        self.pool.release(slot)
        # suspend runs quiesced, so a last-release demotion's export
        # dispatch is safe here; re-admission re-plans against the store
        self._drop_prefix_lease(req)
        self._slots[slot] = None
        note_release("scheduler.slot", (id(self), slot))
        req.slot = -1
        if tr is not None:
            tr.add("spill", t0, time.perf_counter(), slot=slot,
                   spilled=req.spilled)

    def _preempt(self, req: _Request):
        """Evict an admitted request back to the head of the waiting line,
        releasing its pages (over-commit pool exhaustion)."""
        with self._admission_lock:
            self.preemptions += 1
        tr = req._trace
        if tr is not None:
            tr.point("preempt", slot=req.slot)
        self._suspend_slot(req)
        # back on the line: the queue-wait clock restarts for re-admission
        req._t_submit = time.perf_counter()
        # head of the waiting line: preemption goes newest-first, so
        # repeated inserts at 0 restore admission order among the victims
        self._waiting.insert(0, req)

    # -------------------------------------------- proactive KV residency
    def _cold_candidates(self) -> list:
        """Recency scan: admitted decode slots whose consumer stopped
        pulling. ``produced - out.qsize()`` is the consumed-token count; a
        slot with a standing backlog whose count has not moved for
        ``spill_cold_after`` consecutive scans is cold — the engine is
        decoding tokens nobody reads, holding pool pages hotter streams
        (or the waiting line) could use. Cheap host-only bookkeeping; runs
        every tick from the (non-hot) policy helpers."""
        if self.spill_cold_after is None or self.spill is None:
            return []
        cold = []
        for req in self._slots:
            if req is None or req.cancelled or req.prefill_only:
                continue
            if not self._prefill_done(req) or not req.history:
                continue  # mid-prefill slots have nothing to spill
            backlog = req.out.qsize()
            consumed = req.produced - backlog
            if backlog > 0 and consumed == req._consumed_seen:
                req._cold_ticks += 1
            else:
                req._cold_ticks = 0
            req._consumed_seen = consumed
            if req._cold_ticks >= self.spill_cold_after:
                cold.append(req)
        return cold

    def _spill_cold(self, cold: list):
        """Suspend cold slots and park them off the waiting line. Parked
        requests hold no pool pages and don't count against admission —
        their spilled bytes are reclaimed capacity until the consumer
        catches up and :meth:`_wake_parked` re-queues them. Callers on the
        async path quiesce first: suspension device_gets sampler rows and
        rewrites page tables, which must not race an in-flight block."""
        for req in cold:
            if req.slot < 0:
                # the async caller's quiesce drains the in-flight block
                # AFTER the candidate scan, and that harvest can finish a
                # cold slot (max_tokens landed). Suspending it then would
                # release slot -1 — i.e. clobber self._slots[-1], dropping
                # whichever live stream holds the last slot — and park a
                # finished request for _wake_parked to re-admit.
                continue
            with self._admission_lock:
                self.cold_spills += 1
            tr = req._trace
            if tr is not None:
                tr.point("cold_spill", slot=req.slot)
            self._suspend_slot(req)
            req._cold_ticks = 0
            self._parked.append(req)

    def _wake_parked(self):
        """Re-queue parked requests whose consumer caught up (backlog
        drained). Woken requests go to the HEAD of the waiting line — their
        TTFT is long past, making them the oldest claim on capacity — and,
        with prefetch on, their host→device stage is dispatched here so
        the copy overlaps the decode blocks that run while they wait for a
        slot. Cancelled parked requests are reaped in place."""
        if not self._parked:
            return
        keep, woken = [], []
        for req in self._parked:
            if req.cancelled:
                self._drop_spill(req)
                self._hand(req, None)
                continue
            if req.out.qsize() == 0:
                woken.append(req)
            else:
                keep.append(req)
        self._parked = keep
        if not woken:
            return
        now = time.perf_counter()
        for req in woken:
            req._cold_ticks = 0
            # re-queued at the head: the queue-wait clock restarts, and a
            # traced timeline gets its wake point
            req._t_submit = now
            tr = req._trace
            if tr is not None:
                tr.point("wake")
            self._prefetch_block(req)
            with self._admission_lock:
                self.cold_wakes += 1
        self._waiting[:0] = woken

    def _prefetch_block(self, req: _Request):
        """Dispatch the host→device stage for ``req``'s spilled block (the
        PRESERVE-style overlap): ``KVPageBlock.prefetch`` device_puts the
        page arrays without blocking on them, so by the time admission
        imports the block the scatter consumes device-resident pages. A
        still-device block (flusher hasn't copied it out) needs no stage.
        Faults on ``cache.prefetch`` are absorbed here — the block stays
        host-resident and import falls back to the counted demand path."""
        if not self._prefetch_on or self.spill is None or not req.spilled:
            return
        block = self.spill.peek(req)
        if block is None:
            return
        self.spill.touch(req)  # about to re-import: don't LRU-evict it
        if not block.is_host or block.is_prefetched:
            return
        try:
            tr = req._trace
            t0 = time.perf_counter() if tr is not None else 0.0
            block.prefetch(put=self._put, codec=self._kv_codec)
            if tr is not None:
                tr.add("prefetch", t0, time.perf_counter(),
                       pages=block.n_pages)
            with self._admission_lock:
                self.prefetches += 1
        except Exception as e:
            with self._admission_lock:
                self.prefetch_faults += 1
            logging.getLogger(__name__).debug(
                "KV prefetch failed (degrading to demand import): %s", e
            )

    def _prefetch_waiting(self):
        """Stage blocks for spilled requests near the head of the waiting
        line (preemption victims about to be re-admitted), bounded so a
        deep queue can't turn the policy pass into a copy storm. The
        prefix-store pass rides the same policy slot: host-tier prefix
        blocks for soon-to-be-admitted prompts get their stage started
        here so admission's import scatters device-resident arrays."""
        if self._prefetch_on and self.spill is not None:
            budget = 2
            for req in self._waiting[:4]:
                if budget == 0:
                    break
                if req.spilled and not req.cancelled:
                    self._prefetch_block(req)
                    budget -= 1
        self._pod_fetch_waiting()
        self._prefetch_store_waiting()

    def migrate_out(self, deadline: float = 30.0) -> int:
        """Gracefully evacuate every request (replica drain): the scheduler
        thread quiesces at its next tick and ends each stream with a
        ``RequestMigratedError`` carrying a :class:`ResumeState` — a
        host-materialized ``KVPageBlock`` when the page export succeeds,
        otherwise prompt+history for a token-exact re-prefill elsewhere.
        New submissions are rejected with ``ReplicaDrainingError`` from the
        moment this is called; the flag is permanent (retirement), so the
        caller should ``close()`` afterwards. Returns the number of
        requests migrated before ``deadline`` expired; stragglers (e.g. a
        wedged tick) keep migrating if the thread ever revives."""
        with self._admission_lock:
            base = self.migrations_out
        with self._start_lock:
            self._migrate_requested = True
            t = self._thread
        if t is None or not t.is_alive():
            # never started (no requests yet) or already stopped: nothing
            # admitted to migrate; the flag alone retires the batcher
            return 0
        # mst: allow(MST201): wake sentinel; Queue locks internally
        self._submit.put(None)  # wake the idle wait
        t0 = self._clock()
        while self._clock() - t0 < deadline:
            if not t.is_alive():
                break
            with self._admission_lock:
                queued = self._submit.qsize() + len(self._waiting)
            if queued == 0 and not any(r is not None for r in self._slots):
                break
            self._sleep(0.01)
        with self._admission_lock:
            return self.migrations_out - base

    def _migrate_all_out(self):
        """Scheduler-thread half of :meth:`migrate_out`. Runs quiesced (no
        in-flight block), so the one sampler-state ``device_get`` and the
        per-slot block exports are off the steady-state decode path — this
        is a teardown, not a tick, which is why the host copies here are
        synchronous rather than routed through the spill tier's flusher."""
        admitted = [
            (slot, req) for slot, req in enumerate(self._slots)
            if req is not None
        ]
        keys_h = recent_h = None
        if any(self._prefill_done(r) for _, r in admitted):
            # one transfer for every slot's sampler rows (PRNG chain +
            # repetition window) — what makes the resumed stream exact
            keys_h, recent_h = jax.device_get((self.keys, self.recent))
        for slot, req in admitted:
            self._slots[slot] = None
            note_release("scheduler.slot", (id(self), slot))
            req.slot = -1
            if req.cancelled:
                self.pool.release(slot)
                self._drop_prefix_lease(req)
                self._drop_spill(req)
                self._hand(req, None)
                continue
            tr = req._trace
            t0 = time.perf_counter() if tr is not None else 0.0
            with tracing.bind(tr):
                state = self._export_resume_state(req, slot, keys_h, recent_h)
            if tr is not None:
                tr.add("migration", t0, time.perf_counter(), slot=slot,
                       block=state.block is not None)
            self.pool.release(slot)
            self._drop_prefix_lease(req)
            self._hand(req, RequestMigratedError(state))
            with self._admission_lock:
                self.migrations_out += 1
        if admitted:
            self.active = self._zeros_like(self.active)
        self._drain_submissions()
        # parked cold-spilled sessions migrate too: their tier blocks (or
        # fold-history fallback) travel in the ResumeState like any
        # spill-preempted waiter's
        for req in self._waiting + self._parked:
            if req.cancelled:
                self._drop_spill(req)
                self._hand(req, None)
                continue
            tr = req._trace
            t0 = time.perf_counter() if tr is not None else 0.0
            with tracing.bind(tr):
                state = self._export_resume_state(req, -1, None, None)
            if tr is not None:
                tr.add("migration", t0, time.perf_counter(), queued=True)
            self._hand(req, RequestMigratedError(state))
            with self._admission_lock:
                self.migrations_out += 1
        self._waiting.clear()
        self._parked.clear()

    def _export_resume_state(self, req: _Request, slot: int,
                             keys_h, recent_h, *,
                             host: bool = True) -> ResumeState:
        """Build a request's portable :class:`ResumeState`. Admitted
        mid-decode requests get their page chain exported and host-
        materialized; a waiting request that was spill-preempted hands over
        its tier block. Any export failure (injected ``cache.export``
        fault, accounting drift, integrity error) degrades to a blockless
        state — the target folds history into the prompt and re-prefills,
        token-exact because the sampler rows still travel."""
        if slot >= 0 and self._prefill_done(req) and keys_h is not None:
            req.resume_keys = np.asarray(keys_h[slot])
            req.resume_recent = np.asarray(recent_h[slot])
        block = req._block  # un-imported block from a previous migration
        req._block = None
        if block is None and req.spilled:
            req.spilled = False
            block = self.spill.take(req) if self.spill is not None else None
        if (block is None and slot >= 0 and self.paged
                and self.draft is None and not self._slot_state
                and not self._diffusion  # (blockless: the fold re-prefills)
                and self._prefill_done(req) and req.history):
            page = self.engine.page_size
            n_tokens = req.prompt.size + max(0, len(req.history) - 1)
            n_pages = -(-max(1, n_tokens) // page)
            pages = self.pool.pages(slot)[:n_pages]
            if len(pages) == n_pages:
                try:
                    block = export_block(
                        self.cache, pages, page_size=page, n_tokens=n_tokens,
                        prompt=req.prompt, history=req.history,
                        produced=req.produced, resume_keys=req.resume_keys,
                        resume_recent=req.resume_recent,
                        share_hash=self._share_hash, codec=self._kv_codec,
                        gather=self._export_pages, put=self._put,
                    )
                except Exception as e:
                    block = None
                    with self._admission_lock:
                        self.spill_fallbacks += 1
                    logging.getLogger(__name__).debug(
                        "drain export failed for slot %d: %s", slot, e
                    )
        if block is not None and host:
            try:
                # staged prefetch copies pin THIS engine's device buffers;
                # a block leaving the replica must not carry them
                block.drop_prefetch()
                block.to_host()  # the block must outlive this engine
            except Exception as e:
                block = None
                with self._admission_lock:
                    self.spill_fallbacks += 1
                logging.getLogger(__name__).debug(
                    "drain host copy failed for slot %d: %s", slot, e
                )
        return ResumeState(
            prompt=np.asarray(req.prompt, np.int32),
            history=[int(t) for t in req.history],
            produced=req.produced,
            block=block,
            resume_keys=req.resume_keys,
            resume_recent=req.resume_recent,
        )

    def _drop_spill(self, req: _Request):
        req.spilled = False
        if self.spill is not None:
            self.spill.drop(req)

    def _handoff_out(self):
        """Finish this tick's prefill-only requests: export each parked
        request's page block (dispatch-only gather) and end its stream with
        :class:`HandoffReadyError` carrying the ResumeState. Runs from the
        tick right after the prefill section — the pipeline is still
        quiesced from admission, so the one sampler-row ``device_get`` here
        is off the steady-state decode path, and the slot is released
        before the tick's decode dispatch so a handoff request never rides
        a decode block. The block is deliberately NOT host-materialized
        here (``host=False``): the consumer thread — the disagg
        coordinator's handoff step — pulls it with ``to_host()``, so the
        device→host DMA drains while this replica's next prefills and
        decode ticks proceed."""
        ready, self._handoff_ready = self._handoff_ready, []
        live = [r for r in ready if r.slot >= 0]
        keys_h = recent_h = None
        if any(not r.cancelled for r in live):
            # one transfer for every parked request's sampler rows (PRNG
            # chain + repetition window) — what keeps the resumed decode
            # stream token-exact on the target replica
            keys_h, recent_h = jax.device_get((self.keys, self.recent))
        for req in live:
            slot = req.slot
            if req.cancelled:
                self._finish(req)
                continue
            tr = req._trace
            t0 = time.perf_counter() if tr is not None else 0.0
            with tracing.bind(tr):
                state = self._export_resume_state(
                    req, slot, keys_h, recent_h, host=False
                )
            if tr is not None:
                # phase 1 of the disagg handoff (export dispatch on the
                # prefill replica); the coordinator records transfer/import
                tr.add("handoff_export", t0, time.perf_counter(), slot=slot)
            self.active = self._row_set(
                self.active, self._put(jnp.asarray(slot, jnp.int32)),
                self._put(jnp.asarray(False)),
            )
            self.pool.release(slot)
            # a prefill-only request's insertion lease drops HERE: last
            # release demotes the freshly prefilled prefix to the host
            # tier, which is exactly what lets the disagg coordinator skip
            # the prefill pool next time this prefix arrives
            self._drop_prefix_lease(req)
            self._slots[slot] = None
            note_release("scheduler.slot", (id(self), slot))
            req.slot = -1
            self._hand(req, HandoffReadyError(state))
            with self._admission_lock:
                self.handoffs_out += 1
                self._finish_times.append(self._clock())

    def _growth_wanted(self, slot: int, req: _Request) -> int:
        """Pages a decoding slot lacks for its next block's KV writes
        (<= 0: it holds them all)."""
        emitted = len(req.history)
        # next KV write lands at prompt + emitted - 1 (the first sampled
        # token writes no KV; each block step writes one)
        offset = req.prompt.size + max(0, emitted - 1)
        # total pages this request can ever touch — same quantity
        # generate_step bounded by the pool size at submission
        cap = self._pages_needed(
            req.prompt.size, emitted + (req.max_tokens - req.produced)
        )
        want = min(-(-(offset + self._grow_ahead) // self.engine.page_size),
                   cap)
        return want - len(self.pool.pages(slot))

    def _grow_for_decode(self):
        """Over-commit page growth: before a decode block runs, every
        decoding slot must have pages covering the block's KV writes. Grow
        oldest-first from the free list (evicting cached prefix pages as
        needed); on pool exhaustion preempt the newest-admitted request.
        The oldest admitted request is never preempted, and generate_step's
        absolute capacity check proves a lone request's full need fits the
        pool, so it can always grow to completion — progress is guaranteed."""
        decoding = sorted(
            (
                (slot, req)
                for slot, req in enumerate(self._slots)
                if req is not None and self._prefill_done(req)
            ),
            key=lambda t: t[1].admit_seq,
        )
        for slot, req in decoding:
            while self._slots[slot] is req:  # a victim skips its own growth
                n_more = self._growth_wanted(slot, req)
                if n_more <= 0:
                    break
                self._evict_for(n_more)
                if self.pool.free >= n_more:
                    self.pool.extend(slot, self.pool.take(n_more))
                    self._write_table_row(slot)
                    break
                victims = [r for r in self._slots if r is not None]
                if len(victims) <= 1:
                    # Only this request is left and the pool STILL can't
                    # cover its next block. cap ≤ pool (generate_step's
                    # capacity check) makes this unreachable absent
                    # accounting drift — but silently continuing would
                    # wedge the request against its scratch-page tail and
                    # emit garbage forever. Fail it loudly instead.
                    self._hand(req, RuntimeError(
                        f"KV page pool exhausted: slot {slot} needs "
                        f"{n_more} more page(s) for its next decode block "
                        f"but only {self.pool.free} are free and no "
                        "other request remains to preempt"
                    ))
                    self._finish(req)
                    break
                self._preempt(max(victims, key=lambda r: r.admit_seq))

    def _dispatch_block(self) -> Optional[_InflightBlock]:
        """Dispatch one decode block on the device and return its handle
        WITHOUT waiting for it: pure device-side state chain (last_tok /
        cache / recent / keys rebind to output futures), no host reads.
        The paired :meth:`_harvest` pulls the tokens; the async tick runs
        them a block apart so the device never waits on host work."""
        eng = self.engine
        if self.paged and self.overcommit:
            with self._phases.span("housekeeping"):
                self._grow_for_decode()
        # snapshot of slots active for this block, in slot order
        live = [
            (slot, req) for slot, req in enumerate(self._slots)
            if req is not None and self._prefill_done(req)
        ]
        if not live:
            return None
        want_lp, sampler = False, "greedy"
        for _, req in live:
            want_lp |= req.want_logprobs
            if req.temperature > 0:
                if req.top_p < 1.0:
                    sampler = "nucleus"
                elif sampler == "greedy":
                    sampler = "draw"
        self._blocks_by_sampler[sampler] += 1
        # analytic gauge; in async mode the lengths are one block stale
        self._account_kv_read(live, self.decode_block)
        # the block's first input token, kept so a draft engine can replay
        # the exact chain the target consumed (sync/spec fallback only)
        prev_tok = self.last_tok if self.draft is not None else None
        block = self._decode_block_prog(want_lp)
        seq = self._blocks_dispatched
        self._blocks_dispatched += 1
        # a forward computes a position a live row — a block of them where
        # the family generates by diffusion over blocks
        positions = self.decode_block * len(live) * (self._diffusion or 1)
        self._positions_computed += positions
        args = {}
        if self._trace_profile:
            # what could tell two executions of one program apart: rows
            # active, the log-probability variant, the longest page chain
            args = dict(
                seq=seq, live=len(live), want_lp=int(want_lp),
                pages=max(
                    len(self.pool.pages(slot)) for slot, _ in live
                ) if self.paged else 0,
            )
        with self._phases.span("dispatch", **args):  # mst.decode_block
            ticket = self._phases.dispatched("block")
            outs, self.last_tok, self.cache, self.recent, self.keys = block(
                eng.layer_params, eng.layer_masks, eng.vocab_parts,
                eng.shared_params, self.last_tok, self.cache, self.active,
                self.recent, self.keys, self.sp, self.rep_sizes, self.table,
            )
            ret = self._phases.returned()
            unread = self._chunk_unread
            if unread is not None and unread[1].is_ready():
                # the chunk in front ended before this call came back: its
                # end is this stamp at the latest
                self._chunk_unread = None
                self._phases.ready(unread[0], at=ret, late=True)
        return _InflightBlock(outs=outs, live=live, want_lp=want_lp,
                              prev_tok=prev_tok, seq=seq, positions=positions,
                              ticket=ticket)

    def _abandon(self, inf):
        """A decode block's futures are dropped unharvested: its positions
        were computed for nobody."""
        self._forget_dispatched()
        if isinstance(inf, _InflightBlock):
            self._blocks_abandoned += 1
            self._tokens_dropped["abandoned_block"] += inf.positions

    def _forget_dispatched(self):
        """Whatever is still dispatched (an abandoned block, a cancelled
        joiner's chunk) nobody will read, so nobody will learn when it
        ends: the account counts the device empty from here."""
        self._chunk_unread = None
        self._phases.drop()

    def _harvest(self, inf: Optional[_InflightBlock], **drain):
        """Pull a dispatched block's tokens to the host and run all of its
        host-side consequences: emit per slot (lookahead tokens of a slot
        that finished after dispatch are dropped by the ``req.slot != slot``
        skip), draft replay, finish/reclaim. The ONE ``device_get`` here is
        the tick sync — the async loop must never grow a second harvest
        point (MST104)."""
        if inf is None:
            return
        try:
            inject("scheduler.harvest")  # fault harness: kill the harvest
            with self._phases.span("harvest_wait", seq=inf.seq, **drain):
                self._chunk_ended()
                # mst: allow(MST102): THE tick sync — tokens must reach the host
                outs, prev = jax.device_get((inf.outs, inf.prev_tok))
        except BaseException:
            self._abandon(inf)
            raise
        # the wait's return is the block's end; nobody waits on an empty
        # device, so the span closed first. With no lookahead block behind
        # it — every quiesce, every harvest of the sync tick — the device
        # has nothing to run until the next dispatch
        self._phases.ready(inf.ticket, at=self._phases.last[1])
        self._blocks_harvested += 1
        with self._phases.span("emit"):
            self._emit_block(inf, outs, prev, *self._phases.last)

    def _chunk_ended(self):
        """Inside a harvest's wait, before its read: a join's middle chunk
        was dispatched in front of the block and nobody has seen it end.
        Wait on its logits, so the account has the boundary between the
        chunk's seconds and the block's. The chunk ends before the block
        behind it: the device loses nothing, and this thread spends here
        what it would spend in the read a moment later. A chunk found ended
        already is counted late, its end this moment at the latest."""
        unread, self._chunk_unread = self._chunk_unread, None
        if unread is None:
            return
        ticket, logits = unread
        late = logits.is_ready()
        if not late:
            # mst: allow(MST102): ends before the block the harvest below waits on: no wait is added, only split in two
            logits.block_until_ready()
        self._phases.ready(ticket, late=late)

    def _emit_block(self, inf: _InflightBlock, outs, prev, t0, t1):
        """The host-side consequences of a harvested block. ``t0``/``t1``
        are the harvest wait's own stamps, reused for the traced requests'
        spans — no extra clock reads on this path."""
        if self._diffusion:
            self._emit_committed(inf, outs, t0, t1)
            return
        toks = outs[0]  # (K, M, 1)
        live = inf.live
        for _, _req in live:
            _tr = _req._trace
            if _tr is not None:
                _tr.add("decode_tick", t0, t1, slot=_req.slot,
                        block=self.decode_block)
        if self.draft is not None and live:
            # This tick fell back to plain decode (spec paused — logprobs
            # wanted, or a slot within K of max_seq): the target just
            # advanced decode_block positions, so the draft must ingest the
            # same token chain or its next proposals attend to stale KV and
            # acceptance silently collapses. Step j of the block consumed
            # toks[j-1] (step 0 consumed prev_tok), so the replay chain is
            # [prev_tok, toks[:-1]]. Deterministic device ops only — every
            # multi-host mirror computes the identical replay in lockstep.
            chain = self._put(
                jnp.asarray(np.concatenate([prev[None], toks[:-1]], 0))
            )
            self._phases.dispatched("other")
            self.dcache = self.draft.spec_replay_cb(self.decode_block)(
                self.draft.layer_params, self.draft.layer_masks,
                self.draft.vocab_parts, self.draft.shared_params,
                chain, self.dcache, self.active,
            )
            self._phases.returned()
            self.fallback_ticks += 1
            self.replayed_tokens += self.decode_block * len(live)
        # every position of the block is emitted or dropped, counted here
        left, emitted, finished, cancelled = inf.positions, 0, 0, 0
        try:
            for j in range(toks.shape[0]):
                for slot, req in live:
                    left -= 1
                    if req.slot != slot:  # the slot was left earlier
                        # (a consumer's exit sets req.cancelled on every
                        # stream, finished ones too: go by the count)
                        if req.produced >= req.max_tokens:
                            finished += 1
                        else:
                            cancelled += 1
                        continue
                    lp = None
                    if inf.want_lp and req.want_logprobs:
                        lp = block_token_logprobs(outs, j, slot)
                    emitted += 1
                    self._emit(req, int(toks[j, slot, 0]), lp)
        finally:
            self._tokens_emitted += emitted
            self._tokens_dropped["slot_finished"] += finished
            self._tokens_dropped["cancelled"] += cancelled
            # an emit that raised leaves the rest of the block undelivered
            self._tokens_dropped["abandoned_block"] += left

    def _emit_committed(self, inf: _InflightBlock, outs, t0, t1):
        """``_emit_block`` for a family that generates by diffusion over
        blocks: ``outs`` is ``diffusion.block_forward``'s tree over the
        program's forwards, and a slot is handed the blocks its forwards
        finished — none, or a few of ``L`` tokens each — in order, each at
        the forward that transferred its last masked position (its K/V is
        stored by a later forward, or never: a stream's last block). A
        position's log-probabilities are those of the forward that
        transferred it. Every position a forward gave logits for is emitted
        or dropped, counted here: ``denoise`` is a forward's rows that were
        not a finished block's new tokens (a forward that left positions
        masked, a slot that stood still for a narrow forward, a first
        block's prompt tail), the other reasons as ever."""
        L = self._diffusion
        done, ids = outs["done"], outs["ids"]  # (K, M), (K, M, L)
        live = inf.live
        stats = self._diffusion_stats
        stats["slot_forwards"] += done.shape[0] * len(live)
        slots = [slot for slot, _ in live]
        stats["by_rank"] += int(outs["by_rank"][:, slots].sum())
        stats["by_confidence"] += int(outs["by_confidence"][:, slots].sum())
        left, emitted, denoise, finished, cancelled = inf.positions, 0, 0, 0, 0
        blocks = dict.fromkeys(slots, 0)
        try:
            for j in range(done.shape[0]):
                for slot, req in live:
                    left -= L
                    if req.slot != slot:  # the slot was left earlier
                        if req.produced >= req.max_tokens:
                            finished += L
                        else:
                            cancelled += L
                        continue
                    if not done[j, slot]:
                        denoise += L
                        continue
                    stats["blocks_committed"] += 1
                    blocks[slot] += 1
                    skip, req._block_skip = req._block_skip, 0
                    denoise += skip
                    for i in range(skip, L):
                        if req.slot != slot:  # max_tokens fell inside the block
                            finished += 1
                            continue
                        lp = None
                        if inf.want_lp and req.want_logprobs:
                            lp = TokenLogprobs(
                                float(outs["lp_chosen"][j, slot, i]),
                                outs["lp_top_i"][j, slot, i],
                                outs["lp_top_v"][j, slot, i],
                            )
                        emitted += 1
                        self._emit(req, int(ids[j, slot, i]), lp)
        finally:
            self._tokens_emitted += emitted
            self._tokens_dropped["denoise"] += denoise
            self._tokens_dropped["slot_finished"] += finished
            self._tokens_dropped["cancelled"] += cancelled
            self._tokens_dropped["abandoned_block"] += left
        for slot, req in live:
            tr = req._trace
            if tr is not None:
                tr.add("denoise", t0, t1, slot=slot,
                       forwards=int(done.shape[0]), blocks=blocks[slot])

    def _decode_once(self):
        # the sync composition point — MultiHostBatcher overrides THIS to
        # broadcast the tick before the mirrored dispatch+harvest
        self._harvest(self._dispatch_block())

    def _need_pages(self, req: _Request, block=None) -> int:
        """Pages to map at admission. Reserve mode (default) claims the whole
        prompt+max_tokens need up front; over-commit claims only the CURRENT
        need — prompt plus one decode block (capped by what's left to emit) —
        and grows per block in _grow_for_decode. A request resuming via a
        KVPageBlock (``block``, or its entry still parked in the spill tier)
        sizes from the block's KV rows instead of the prompt: at least the
        block's own pages, plus decode headroom in the same mode."""
        remaining = max(1, req.max_tokens - req.produced)
        if req.prefill_only:
            # a prefill-only request emits exactly one token on this
            # replica before its block hands off to the decode pool —
            # reserving its full decode budget here would starve the
            # prefill pool's admission for capacity it never uses
            remaining = 1
        if block is None:
            block = req._block
        if block is None and req.spilled and self.spill is not None:
            block = self.spill.peek(req)
        if block is not None:
            ahead = min(self._grow_ahead, remaining) if self.overcommit \
                else remaining
            return max(
                block.n_pages,
                -(-(block.n_tokens + ahead) // self.engine.page_size),
            )
        if self.overcommit:
            return self._pages_needed(
                req.prompt.size, min(self._grow_ahead, remaining)
            )
        return self._pages_needed(req.prompt.size, remaining)

    def _spec_ok(self) -> bool:
        """A tick can take the speculative round iff no decoding slot wants
        logprob summaries (the verify doesn't compute them) and every
        decoding slot has window-max rows of KV headroom — the verify
        writes up to that many positions speculatively, and past max_seq
        the dynamic-slice clamp would corrupt valid rows. Async ngram ticks
        double the margin: at dispatch of round t+1 the host has harvested
        only through t-1, so the true frontier can be a full round ahead of
        ``history``. Ticks that fail the check run a plain decode block
        (all slots still advance, just unspeculated)."""
        if self._pressure >= 2 and self.spec_tracker is None:
            # legacy fixed-K engine mode: brownout level 2+ pauses
            # speculation globally — draft compute is ballast under
            # overload (racy gauge-grade read; the fallback tick path
            # handles the draft-KV replay). With a tracker the shed is
            # per-slot, lowest-acceptance-first (effective_windows).
            return False
        K = (2 if self._async else 1) * self._w_max
        ms = self.engine.max_seq
        for req in self._slots:
            if req is None or not self._prefill_done(req):
                continue
            if req.want_logprobs:
                return False
            # history counts tokens since the last prompt fold, so
            # prompt + history is the slot's true KV frontier even for a
            # resumed request whose ``produced`` spans an earlier replica
            since = len(req.history)
            if req.prompt.size + max(0, since - 1) + K > ms:
                return False
        return True

    def _spec_draft_ok(self) -> bool:
        """``spec.draft`` fault site, checked before each speculative
        round's proposals: a faulted draft degrades THAT tick to plain
        decode — counted, never a wrong or dropped stream (the fallback
        path replays the block through a draft engine's KV as usual)."""
        try:
            inject("spec.draft", engine=id(self))
        except Exception:
            self.spec_draft_faults += 1
            return False
        return True

    def _spec_plan(self, live):
        """Per-round window plan: ``(K, wins)`` where K is the round width
        (max live window) and wins maps slot → policy window, or None when
        no live slot speculates this round (the tick runs plain decode).
        Without a tracker (legacy fixed-K engine mode) every slot gets
        spec_k. With one, windows come from the per-slot controller after
        brownout shedding (level 2 sheds lowest-acceptance-first, level 3+
        sheds all — see AcceptanceTracker.effective_windows)."""
        if self.spec_tracker is None:
            return self.spec_k, {slot: self.spec_k for slot, _ in live}
        wins = self.spec_tracker.effective_windows(
            [slot for slot, _ in live], self._pressure
        )
        K = max(wins.values(), default=0)
        if K < 2:
            return None
        return K, wins

    def _dispatch_spec(self, prev_guess=None) -> Optional[_InflightSpec]:
        """Dispatch one speculative round for every decoding slot and
        return its handle WITHOUT waiting: proposals (host-built n-gram
        lookups, or K batched draft-engine steps), one T=K target verify
        with per-slot window caps, all device outputs left as futures.
        Slots whose window is 0 (disabled/shed) ride along with wcap=1 —
        they emit exactly the correction token, i.e. a plain decode step.
        ``prev_guess`` is the in-flight round's optimistic continuation per
        slot (async: host history is one round stale at dispatch). Returns
        None when no slot speculates — the caller runs a plain tick."""
        eng = self.engine
        if self.paged and self.overcommit:
            self._grow_for_decode()
        live = [
            (slot, req) for slot, req in enumerate(self._slots)
            if req is not None and self._prefill_done(req)
        ]
        if not live:
            return None
        plan = self._spec_plan(live)
        if plan is None:
            return None
        K, wins = plan
        # the T=K verify always takes the gather path (chunked writes want
        # the contiguous buffer), whatever the decode tick uses
        self._account_kv_read(live, 1, path="gather")
        wcaps = np.ones((self.M,), np.int32)
        guess: dict = {}
        if self._spec_mode == "ngram":
            prev_guess = prev_guess or {}
            drafts_np = np.zeros((K, self.M), np.int32)
            for slot, req in live:
                w = wins.get(slot, 0)
                if w < 2:
                    continue
                toks = np.concatenate(
                    [req.prompt, np.asarray(req.history, np.int32)]
                )
                tail = prev_guess.get(slot)
                if tail is not None and tail.size:
                    toks = np.concatenate([toks, tail])
                d, n_valid = self._ngram.propose(toks, w)
                drafts_np[:w, slot] = d
                wcaps[slot] = min(w, max(1, n_valid))
                guess[slot] = d[: wcaps[slot]]
            keys2 = self._split2(self.keys)
            self.keys, vkeys = keys2[:, 0], keys2[:, 1]
            drafts = self._put(jnp.asarray(drafts_np))
            caps = self._put(jnp.asarray(wcaps))
            ticket = self._phases.dispatched("other")
            gs, count, self.last_tok, self.cache, self.recent = \
                eng.spec_verify_ngram_cb(K)(
                    eng.layer_params, eng.layer_masks, eng.vocab_parts,
                    eng.shared_params, self.last_tok, drafts, self.cache,
                    self.active, self.recent, vkeys, self.sp,
                    self.rep_sizes, caps, self.table,
                )
            self._phases.returned()
        else:
            d = self.draft
            for slot, _req in live:
                wcaps[slot] = max(1, wins.get(slot, 0))
            keys3 = self._split3(self.keys)
            self.keys, dkeys, vkeys = keys3[:, 0], keys3[:, 1], keys3[:, 2]
            # the proposals and the verify behind them: one entry
            ticket = self._phases.dispatched("other")
            drafts, qlps, self.dcache = d.spec_propose_cb(K)(
                d.layer_params, d.layer_masks, d.vocab_parts, d.shared_params,
                self.last_tok, self.dcache, self.active, self.recent, dkeys,
                self.sp, self.rep_sizes,
            )
            gs, count, self.last_tok, self.cache, self.recent = \
                eng.spec_verify_cb(K)(
                    eng.layer_params, eng.layer_masks, eng.vocab_parts,
                    eng.shared_params, self.last_tok, drafts, qlps,
                    self.cache, self.active, self.recent, vkeys, self.sp,
                    self.rep_sizes, self._put(jnp.asarray(wcaps)),
                    self.table,
                )
            self._phases.returned()
            self.dcache = self.dcache._replace(
                offset=self._drewind(
                    self.dcache.offset, count, self.active,
                    jnp.asarray(K, jnp.int32),
                )
            )
        return _InflightSpec(outs=(count, gs), live=live, wins=wins,
                             wcaps=wcaps, K=K, guess=guess, ticket=ticket)

    def _harvest_spec(self, inf: Optional[_InflightSpec], **drain):
        """Pull a dispatched speculative round's (counts, tokens) to the
        host and run its host-side consequences: per-slot emit of the
        accepted prefix + correction token, acceptance accounting, and the
        tracker update that resizes each slot's next window. The ONE
        ``device_get`` here is the round's tick sync (MST104's single
        harvest point, spec flavor)."""
        if inf is None:
            return
        with self._phases.span("harvest_wait", **drain):
            self._chunk_ended()
            # mst: allow(MST102): the spec round's one consolidated harvest
            counts, gs_h = jax.device_get(inf.outs)
        self._phases.ready(inf.ticket, at=self._phases.last[1])
        with self._phases.span("emit"):
            self._emit_spec(inf, counts, gs_h, *self._phases.last)

    def _emit_spec(self, inf: _InflightSpec, counts, gs_h, t0, t1):
        self.rounds += len(inf.live)
        for _, _req in inf.live:
            _tr = _req._trace
            if _tr is not None:
                _tr.add("spec_round", t0, t1, slot=_req.slot, window=inf.K)
        for slot, req in inf.live:
            emitted = 0
            for j in range(int(counts[slot])):
                if req.slot != slot:
                    break  # finished (max_tokens) earlier in this round
                self._emit(req, int(gs_h[j, slot]), None)
                emitted += 1
            w = inf.wins.get(slot, 0)
            if w >= 2:
                # count what actually reached the consumer: a slot that
                # hits max_tokens mid-round drops the rest of its accepted
                # prefix, and counting those would overstate the acceptance
                # rate. Disabled/shed slots ride along as plain decode
                # (wcap=1) — counting their correction token as "accepted"
                # with no draft spend would push accept_rate past 1.0.
                self.accepted_tokens += emitted
                self.draft_tokens += int(inf.wcaps[slot])
                if self.spec_tracker is not None and req.slot == slot:
                    # train on the verify's verdict (the full accepted
                    # count), not the max_tokens-truncated emission
                    self.spec_tracker.observe(slot, w, int(counts[slot]))

    def _spec_once(self):
        """One synchronous speculative round: dispatch + immediate harvest
        (the sync composition point, like _decode_once for plain ticks)."""
        with self._phases.span("dispatch"):
            inf = self._dispatch_spec()
        self._harvest_spec(inf)

    def _spec_tick(self) -> bool:
        """Try to make this sync tick a speculative round. False means the
        caller must run a plain decode block instead — speculation is off,
        gated (_spec_ok), fault-degraded (spec.draft), or the per-slot plan
        came up empty (every window 0/disabled)."""
        if self._spec_mode == "off":
            return False
        if not (self._spec_ok() and self._spec_draft_ok()):
            return False
        with self._phases.span("dispatch"):
            inf = self._dispatch_spec()
        if inf is None:
            return False
        self._harvest_spec(inf)
        return True

    def _harvest_any(self, inf, **drain):
        """Harvest whichever flavor of in-flight work ``inf`` is — the
        async tick's lookahead slot can hold a plain decode block or a
        speculative round (ngram mode) interchangeably. ``drain=<reason>``
        (a quiesce's) goes on the wait's profiler span."""
        if isinstance(inf, _InflightSpec):
            self._harvest_spec(inf, **drain)
        else:
            self._harvest(inf, **drain)

    def _fits(self, req: _Request) -> bool:
        if not self.paged:
            return True
        if req.spilled and (self.spill is None or not self.spill.contains(req)):
            # the tier evicted this block under budget pressure since the
            # preemption: resolve to the discard path NOW so the page math
            # below sizes the folded prompt, not a phantom block. (No race
            # with _take_block: evictions only happen on this thread's own
            # puts, never concurrently.)
            req.spilled = False
            self._fold_history(req)
            with self._admission_lock:
                self.spill_fallbacks += 1
        need = self._need_pages(req)
        if req._block is not None or req.spilled:
            if req.spilled:
                # in the resume path: LRU-refresh the tier entry so budget
                # pressure evicts a genuinely-cold block instead
                self.spill.touch(req)
            # block import allocates its whole need fresh (no page sharing
            # with the prefix index), so the chain doesn't discount it
            req._chain = None
            return self._pool_covers(need)
        if self.prefix_store is not None:
            # fleet-store LPM instead of the slot-local chain (mutually
            # exclusive by construction): a device hit discounts the
            # covered pages — the slot leases them instead of allocating.
            # A host hit discounts nothing (the import scatters into fresh
            # pages), it just records the plan for _assign_slot. Pure
            # probe: counters resolve once, at admission.
            if getattr(self.prefix_store, "federation", None) is not None \
                    and req._podfetch != "done":
                # pod federation attached: hold the request until the
                # waiting-queue pass has classified it (None) or its
                # in-flight fetch lands (pending) so the prefix isn't
                # redundantly prefilled — the fetch worker flips the flag
                # on every outcome, and a failed fetch just prefills
                # plain. Flag read only: the federation itself is never
                # touched here
                return False
            req._splan = None
            plan = self._store_lookup(req)
            discount = plan[1] if plan is not None and plan[0] == "device" else 0
            ok = self._pool_covers(need - discount)
            if ok and plan is not None:
                # only a fitting request carries its plan into _assign_slot
                # (same admission pass, same thread — no staleness window
                # beyond the store's own acquire re-check)
                req._splan = plan
            return ok
        chain = self._prefix_lookup(req)
        # the chain's own pages must not double as eviction fodder: they're
        # about to be mapped, so only OTHER cached pages can be reclaimed
        ok = self._pool_covers(need - len(chain), [p for _, p in chain])
        # only a fitting request hands its chain to _assign_slot (same
        # admission pass); a stale chain could reference since-evicted pages
        req._chain = chain if ok else None
        return ok

    def _admit_waiting(self):
        """Admit from the waiting line into free slots under the admission
        policy. fifo: strict order, a non-fitting head blocks the line.
        first_fit: scan past non-fitting requests (they keep their place)."""
        # Shed queued requests whose TTFT budget is already gone: prefilling
        # them would be wasted work (the consumer has timed out or is about
        # to). Host-local decision — nothing was broadcast for an unassigned
        # request, so worker mirrors never knew it existed.
        if self._waiting:
            now = self._clock()
            # produced == 0 guard: a woken cold-spilled request is back on
            # the line long after its first token was delivered — its TTFT
            # budget is history, not a shed signal; dropping it here would
            # kill a mid-stream session
            for req in [
                r for r in self._waiting
                if not r.cancelled and r.produced == 0
                and r.deadlines is not None
                and r.deadlines.ttft_deadline is not None
                and now > r.deadlines.ttft_deadline
            ]:
                self._waiting.remove(req)
                with self._admission_lock:  # read by resilience_stats()
                    self.shed_deadline += 1
                req.cancelled = True
                self._hand(req, RequestTimeoutError(
                    "queue", now - req.deadlines.submitted_at,
                    req.deadlines.ttft_deadline - req.deadlines.submitted_at,
                ))
        # reap dead waiters first — under fifo a non-fitting head would
        # otherwise shadow a cancelled request behind it forever
        for req in [r for r in self._waiting if r.cancelled]:
            self._waiting.remove(req)
            self._drop_spill(req)  # its tier block frees with the stream
            self._hand(req, None)
        while None in self._slots and self._waiting:
            pick = None
            for i, req in enumerate(self._waiting):
                if self._fits(req):
                    pick = i
                    break
                if self.policy == "fifo":
                    return  # head of line doesn't fit; hold the line
            if pick is None:
                return  # first_fit: nothing waiting fits right now
            req, slot = self._waiting.pop(pick), self._slots.index(None)
            args = {}
            if self._trace_profile:
                # the identifier the join's prefill chunks carry, and what
                # the claim is sized for: pages to map, and how many of them
                # the admission pass found mapped already (prefix chain or
                # store plan), in tokens
                tr = req._trace
                args = dict(rid=slot if tr is None else tr.request_id,
                            slot=slot, pages=0, reused=0)
                if self.paged:
                    held = len(req._chain or ()) or (req._splan or (0, 0))[1]
                    args.update(pages=self._need_pages(req),
                                reused=held * self.engine.page_size)
            with self._phases.span("assign_slot", **args):
                self._assign_slot(req, slot)

    def _drain_submissions(self, block: bool = False):
        try:
            while True:
                req = self._submit.get(timeout=0.2) if block else self._submit.get_nowait()
                block = False
                if req is not None:
                    self._waiting.append(req)
        except queue.Empty:
            pass

    def _decoding(self) -> bool:
        """Host mirror of the device ``active`` mask: a slot is decoding iff
        it holds a request whose prefill completed. Exact by construction —
        ``active[slot]`` flips True only at prefill completion and False
        only in _finish/_preempt/_fail_all, each of which also clears
        ``_slots[slot]`` — so the branch gates on host state instead of a
        per-tick device round-trip."""
        return any(
            r is not None and self._prefill_done(r) for r in self._slots
        )

    def _quiesce(self, reason: str):
        """Drain the pipeline: harvest the in-flight block (if any) so every
        host-visible consequence of it — emitted tokens, finishes, freed
        pages — has landed and the device is idle. Required before anything
        that reads device state or host token counts the lookahead block is
        still mutating: admission prefill, preemption, pool-pressure growth
        that might preempt, shutdown. ``reason`` names the call site; a
        call that finds something in flight counts as one drain under it
        (``mst_pipeline_drains_total``)."""
        inf, self._inflight = self._inflight, None
        if inf is not None:
            self._drains[reason] += 1
        self._harvest_any(inf, drain=reason)

    def _growth_fits(self) -> bool:
        """True iff the next ``_grow_for_decode`` is guaranteed to cover
        every decoding slot's block from free + evictable pages alone, i.e.
        growth cannot preempt. The aggregate bound is exact because
        evictions only free index-only pages (never counted in any slot's
        mapping) and nothing else allocates between the check and the
        growth. The emitted/produced counts are one block stale in async
        mode — which the doubled ``_grow_ahead`` already covers — and the
        cap in ``_growth_wanted`` is staleness-invariant (history and
        produced increment together)."""
        if not (self.paged and self.overcommit):
            return True
        return self._pool_covers(sum(
            max(0, self._growth_wanted(slot, req))
            for slot, req in enumerate(self._slots)
            if req is not None and self._prefill_done(req)
        ))

    def _tick_async(self):
        """One double-buffered scheduler iteration: dispatch decode block
        t+1 BEFORE harvesting block t, so the harvest's device_get waits
        only on the already-finished block while the device computes ahead,
        and the host-side emit/stop/admission work below runs concurrently
        with it. Admission prefill, growth that could preempt, and the
        idle path quiesce the pipeline first (one-block drain), then the
        double-buffering resumes on the next tick.

        Where a block's tokens leave for their streams: in a tick with no
        joiner, in the harvest, which runs under block t+1 — the streams'
        threads wake, encode and write while the device works. In a tick
        that drains for a joiner the harvest runs with the device EMPTY,
        and every ``out.put`` wakes a thread that then competes with this
        one for the interpreter lock just as it makes the claim and the
        chunk. So such a tick holds the drain's hand-overs (``_hand``:
        tokens, the ``None`` of a stream that ended, errors; everything
        else ``_emit`` and ``_finish`` do happens where it did) and lets
        them go, in order, once the device has the joiner's chunk — or at
        the first of: ``_prefill_round`` returning without one, a failure
        (``_fail_all``), the loop's end, the idle wait. No hold spans a
        wait on the device or on the submit queue
        (``mst_emit_held_total{flush}``, ``mst_emit_hold_seconds``).

        A join's closing tick runs quiesce, claim, last chunk,
        ``finish_join``, the decode block, and only then lets the hold go,
        reads the joiner's first token and emits it
        (``_read_first_tokens``): the device goes from the chunk to the
        block with nothing of the host between them, and the streams'
        burst and the read's wake-up run under both. The slot is live in
        that block as any slot is in a lookahead block: a first token that
        ends its stream (``max_tokens`` 1) finishes it after the dispatch,
        and the block's positions for it are dropped as ``slot_finished``.
        Where the host must act on the token first the read stays in front
        of the block (``_block_leads``;
        ``mst_join_first_reads_total{order}`` counts both)."""
        inject("scheduler.tick", engine=id(self))  # fault harness: wedge/delay/fail a tick (match engine= to target one batcher)
        phase = self._phases.span  # every part of the tick runs in a phase
        if self._migrate_requested:
            # drain: finish the in-flight block, then end every stream with
            # its ResumeState; the idle wait keeps the loop from spinning
            # while the dispatcher re-places the migrated requests
            with phase("housekeeping"):
                self._quiesce("migrate")
                self._migrate_all_out()
            self._idle_wait()
            return
        with phase("housekeeping"):
            self._reap_cancelled()
            self._drain_submissions()
            cold = self._cold_candidates()
            if cold:
                # suspension device_gets sampler rows and rewrites page
                # tables: drain the lookahead block first
                self._quiesce("cold")
                self._spill_cold(cold)
            self._wake_parked()
            self._prefetch_waiting()
        with phase("admit"):
            admitting = bool(self._waiting) and None in self._slots
            if admitting or any(
                r is not None and not self._prefill_done(r)
                for r in self._slots
            ):
                # prefill (admission or mid-admission chunks) samples the
                # first token host-side and rewrites slot state: drain the
                # lookahead block before touching the engine. What the
                # drain hands its streams is held from here
                self._held = []
                self._quiesce("admit" if admitting else "prefilling")
            self._admit_waiting()
        self._prefill_round()
        if self._first_unread and not self._block_leads():
            self._read_first_tokens("before_block")
        if not self._first_unread:
            # a tick that drained and dispatched no chunk (the fifo head
            # does not fit, the joiner was cancelled or shed, a block
            # import); with a first token to read behind the block the
            # hold stays until the block is dispatched
            self._flush_held("tick_end")
        if self._handoff_ready:
            # prefill-only completions: export + end those streams BEFORE
            # dispatch (pipeline still quiesced from the prefill above)
            with phase("handoff"):
                self._handoff_out()
        if self._decoding():
            if self.paged and self.overcommit and not self._growth_fits():
                # growth might preempt (device_get of sampler rows + page
                # reshuffle): only safe against a drained pipeline
                self._quiesce("growth")
            prev, self._inflight = self._inflight, None
            nxt = None
            if (
                self._spec_mode == "ngram"
                and self._spec_ok()
                and self._spec_draft_ok()
            ):
                # host history is one round stale here (prev not harvested
                # yet): extend it with prev's optimistic guess so the
                # n-gram match sees the tokens prev is about to emit. A
                # wrong guess only costs acceptance, never exactness.
                with phase("dispatch"):
                    nxt = self._dispatch_spec(
                        prev.guess if isinstance(prev, _InflightSpec) else None
                    )
            if nxt is None:
                nxt = self._dispatch_block()
            self._inflight = nxt
            # a join closed in this tick: the device has its chunk and the
            # block behind it, and this thread nothing more to hand it
            self._read_first_tokens("behind_block")
            self._harvest_any(prev)
        else:
            # leftover lookahead block of finished slots
            self._quiesce("idle")
            if not any(self._slots):
                self._idle()

    def _idle_wait(self):
        self._flush_held("tick_end")  # no hold spans a wait on the queue
        # the device is as good as empty for as long as this thread blocks
        # on the queue
        self._forget_dispatched()
        with self._phases.span("idle_wait"):
            self._drain_submissions(block=True)

    def _idle(self):
        """Nothing holds a slot: block until the next request arrives
        (bounded wait, so parked cold sessions still get their wake poll)."""
        self._idle_wait()
        with self._phases.span("housekeeping"):
            self._wake_parked()
        with self._phases.span("admit"):
            self._admit_waiting()

    def _prefill_round(self):
        """This tick's share of admission prefill: every prefill chunk
        stalls every decoding slot for its duration, so while anything is
        decoding at most ONE chunk runs per tick (round-robin across
        admitting requests); with nothing decoding, all of them advance."""
        prefilling = [
            r for r in self._slots
            if r is not None and not self._prefill_done(r)
        ]
        if not prefilling:
            return
        if self._decoding():
            self._prefill_rr += 1
            prefilling = [prefilling[self._prefill_rr % len(prefilling)]]
        for req in prefilling:
            args = {}
            if self._trace_profile:
                tr = req._trace
                args = dict(
                    rid=req.slot if tr is None else tr.request_id,
                    pos=req.prefill_pos,
                    n_valid=max(0, min(self.engine.prefill_chunk,
                                       self._prefill_rows(req)
                                       - req.prefill_pos)),
                    # the join's closing chunk (the first token's read
                    # waits on it) or a middle one
                    last=int(
                        req.prefill_pos + self.engine.prefill_chunk
                        >= self._prefill_rows(req)
                        and (self.draft is None or req.draft_pos
                             + self.engine.prefill_chunk >= req.prompt.size)
                    ),
                )
            with self._phases.span("prefill_chunk", **args):
                self._prefill_one_chunk(req)

    def _tick(self):
        """One scheduler iteration: reap, admit waiting requests into free
        slots (policy + page-reservation gated), prefill mid-admission
        requests, one decode block for active slots.

        Prefill fairness: every prefill chunk stalls every decoding slot
        for its duration, so while anything is decoding, at most ONE chunk
        runs per tick (round-robin across admitting requests) — admission
        latency for long prompts trades against decode jitter bounded at
        one chunk per block. With nothing decoding, all admitting requests
        advance at full rate."""
        inject("scheduler.tick", engine=id(self))  # fault harness: wedge/delay/fail a tick (match engine= to target one batcher)
        phase = self._phases.span  # every part of the tick runs in a phase
        if self._migrate_requested:
            with phase("housekeeping"):
                self._quiesce("migrate")  # sync mode: nothing in flight
                self._migrate_all_out()
            self._idle_wait()
            return
        with phase("housekeeping"):
            self._reap_cancelled()
            self._drain_submissions()
            cold = self._cold_candidates()
            if cold:
                self._spill_cold(cold)  # sync mode: nothing in flight to drain
            self._wake_parked()
            self._prefetch_waiting()
        with phase("admit"):
            self._admit_waiting()
        self._prefill_round()
        if self._handoff_ready:
            # prefill-only completions leave before the decode block
            with phase("handoff"):
                self._handoff_out()
        if self._decoding():
            if not self._spec_tick():
                self._decode_once()
        elif not any(self._slots):
            self._idle()

    def _fail_all(self, exc: BaseException):
        # tokens already counted as emitted reach their streams before the
        # exception does, never the exception in their place; a first token
        # nobody read was counted nowhere, and its joiner is in a slot
        self._flush_held("fail")
        self._first_unread.clear()
        # a scheduler-thread failure is an incident: snapshot the flight
        # recorder before the streams die so their timelines survive
        tracing.auto_snapshot("scheduler_fail")
        # drop the lookahead block's futures (host-side); the wholesale
        # pool reset below reclaims whatever it was still writing
        inf, self._inflight = self._inflight, None
        self._abandon(inf)
        failed: list = []
        for slot, req in enumerate(self._slots):
            if req is not None:
                req.slot = -1
                self._slots[slot] = None
                note_release("scheduler.slot", (id(self), slot))
                failed.append(req)
                self._hand(req, exc)
        self.active = self._zeros_like(self.active)
        if self.paged:
            # cache contents are unreliable after a failure: reset the pool
            # wholesale (all pages free, index dropped)
            self.pool.reset()
            self._prefix_index.clear()
            if self.prefix_store is not None:
                # the fleet store's device entries for THIS engine point at
                # pages the wholesale reset just freed — drop them (marking
                # any outstanding leases dead so late releases are no-ops);
                # host-tier blocks are self-contained and stay valid
                self.prefix_store.drop_owner(self)
        for req in failed:
            # the drop above orphaned the dead slots' entries; retire their
            # leases through the normal idempotent path so the exactly-once
            # contract (and the leak ledger) sees every lease come back.
            # No demotion fires: a dropped entry's release returns None.
            self._drop_prefix_lease(req)
        if self.spill is not None:
            # spilled blocks reference requests whose streams just died;
            # host DRAM back to the budget
            self.spill.clear()
        for req in self._waiting:
            self._hand(req, exc)
        self._waiting.clear()
        for req in self._parked:  # cold-spilled sessions die with the rest
            self._hand(req, exc)
        self._parked.clear()
        while True:
            try:
                req = self._submit.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                self._hand(req, exc)

    def _loop(self):
        tick = self._tick_async if self._async else self._tick
        self._phases.start()
        while not self._stop:
            try:
                with self._phases.tick():  # mst.tick
                    tick()
            except Exception as exc:  # noqa: BLE001 — a dead scheduler thread
                # would hang every consumer; surface the error to them instead
                self._fail_all(exc)
        self._forget_dispatched()
        self._phases.stop()
        self._flush_held("tick_end")  # held tokens first, then the sentinels
        # graceful shutdown: end every in-flight and queued request's stream.
        # Host-side only — no device ops here: the engine is being dropped,
        # and in multi-host serving a device op after the final broadcast
        # would be a one-rank collective entry (a hang, not a cleanup).
        # abandon the lookahead block's futures
        inf, self._inflight = self._inflight, None
        self._abandon(inf)
        for slot, req in enumerate(self._slots):
            if req is not None:
                self._slots[slot] = None
                note_release("scheduler.slot", (id(self), slot))
                req.slot = -1
                # retire the slot's COW lease host-side: release WITHOUT
                # demotion (an export here would be a device op, and in
                # multi-host serving a one-rank collective entry). The
                # returned last-ref entry is dropped — close() is about to
                # drop_owner() the whole pool anyway.
                lease, req._please = req._please, None
                if lease is not None:
                    lease.release()
                self._hand(req, None)
        for req in self._waiting:
            self._hand(req, None)
        self._waiting.clear()
        for req in self._parked:  # parked streams end, like waiting ones
            self._hand(req, None)
        self._parked.clear()
        while True:
            try:
                req = self._submit.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                self._hand(req, None)
