"""KV page-block migration: the transferable unit of decode state.

The serving stack's two pressure valves used to be destructive: overcommit
preemption discarded the victim's KV and re-prefilled from the folded-back
prompt, and a replica could only leave the fleet via ``close()``, killing
its in-flight work. This module turns both into *moves* instead of
*deletes* by extracting the piece of state they both need to relocate —
a request's KV pages plus the sampler state that makes its continuation
bit-exact — into a serializable :class:`KVPageBlock`:

- **Spill-don't-discard preemption** — ``ContinuousBatcher._preempt``
  exports the victim's page chain into a :class:`KVSpillTier` (host-DRAM
  LRU, budgeted by ``--spill-bytes``). Resume re-imports the pages into
  freshly allocated pool pages instead of re-prefilling: preemption cost
  becomes one page-gather + one page-scatter rather than a full prefill.
- **Graceful replica drain** — ``ReplicaSet.drain(i)`` asks replica *i*'s
  batcher to export every admitted request as a host-resident block and
  end its stream with ``RequestMigratedError``; the dispatcher re-places
  each one on a healthy replica, which imports the block (same pool
  geometry) or re-prefills (different geometry / import failure).
- **Crash-safe re-placement** — when a replica dies mid-stream, the
  dispatcher rebuilds a blockless ``ResumeState`` from its own record of
  delivered tokens; the failover replica folds the history into the
  prompt and continues from the last emitted token.

Asynchrony discipline (PRESERVE-style overlap, arXiv:2501.08192): the
tick-hot path only ever *dispatches* the device-side page gather — the
device→host copy happens on the tier's
background flusher thread via :meth:`KVPageBlock.to_host`. A synchronous
full-block ``device_get`` in a tick-hot function is an mstcheck violation
(MST106). Drain is the one exception: it runs quiesced, off the decode
loop, where a blocking copy is shutdown-grade work.

The return trip is symmetric: when the scheduler knows a spilled block is
about to rejoin decode (a cold slot's consumer caught up, a preempted
request reached the head of the waiting line), it calls
:meth:`KVPageBlock.prefetch` — a dispatch-only ``jax.device_put`` of the
host payload, so the host→device DMA overlaps the decode block already in
flight and the admission-time page scatter consumes device-resident
arrays. Without the prefetch, the scatter marshals host numpy at import
time — the demand-paged resume stall mstcheck's MST109 polices in
tick-hot code.

Failure degradation: every consumer treats a failed export/import (fault
sites ``cache.export`` / ``cache.import``, corrupt block checksum, budget
or pool exhaustion) as "fall back to yesterday's behavior" — fold the
emitted history into the prompt and re-prefill. Token streams stay exact
either way because the sampler PRNG row and repetition window
(``resume_keys`` / ``resume_recent``) ride along in both paths.
"""

from __future__ import annotations

import hashlib
import logging
import queue
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from mlx_sharding_tpu import tracing
from mlx_sharding_tpu.analysis.runtime import (
    make_lock,
    note_acquire,
    note_release,
    note_reset,
)
from mlx_sharding_tpu.cache import export_pool_pages, import_pool_pages
from mlx_sharding_tpu.kv_compress import ZeroLeaf
from mlx_sharding_tpu.testing.faults import inject

logger = logging.getLogger(__name__)


class BlockIntegrityError(RuntimeError):
    """A host-materialized block failed its checksum or structural
    validation — treat as corrupt and fall back to re-prefill."""


def _leaves(tree) -> list:
    return jax.tree.leaves(tree)


@dataclass(eq=False)
class KVPageBlock:
    """One request's relocatable decode state: its KV page payloads (codes
    *and* scales for int8 pools) plus everything the sampler needs to
    continue the exact token stream on any engine with the same pool
    geometry.

    ``k_pages`` / ``v_pages`` mirror the paged pool's leaf structure with
    the pool axis (2) narrowed to this request's page chain, in chain
    order. They start as device arrays (the export gather is dispatched,
    not waited on) and become numpy after :meth:`to_host`, which also
    stamps ``checksum`` so a later :meth:`verify` catches corruption
    before the pages are scattered into a pool.

    KV-row accounting (matches the batcher's decode-write semantics): a
    request that has emitted ``len(history)`` tokens has
    ``prompt.size + len(history) - 1`` valid KV rows — the last emitted
    token's KV is unwritten; its id is ``last_tok`` and it is fed as the
    next decode step's input."""

    k_pages: object
    v_pages: object
    n_tokens: int            # valid KV rows covered by the pages
    page_size: int
    prompt: np.ndarray       # original prompt ids (pre-fold)
    history: list            # tokens emitted since admission/fold
    produced: int            # total tokens delivered to the client
    last_tok: int            # next decode input (== history[-1])
    resume_keys: object      # sampler PRNG key row at export
    resume_recent: object    # repetition-penalty recent window at export
    # KV share-map layout identity (kv_share.KVShareMap.share_hash) of the
    # pool the pages were lifted from; None == unshared/identity layout.
    # Joins the fingerprint and is re-checked at import so a block can
    # never scatter into a pool with a different layer→group layout.
    share_hash: Optional[str] = None
    # Compressed-latent wire form (kv_compress.KVCompressCodec): when
    # set, k_pages/v_pages hold the WIRE payload — the MLA latent with
    # ZeroLeaf stubs for the dummy V ("latent", exact) or rank-r float16
    # coefficients ("lowrank", calibrated) — and compress_hash names the
    # codec geometry that can reconstruct it. Both join the fingerprint;
    # import re-checks them so a block can never reconstruct under a
    # different layout.
    compress_kind: Optional[str] = None
    compress_hash: Optional[str] = None
    checksum: Optional[str] = None
    _host: bool = False
    # device-resident (k_pages, v_pages) staged by prefetch(); consumed by
    # payload() at import so the scatter never marshals host numpy. For a
    # compressed block the staged tuple is the RECONSTRUCTED pool form —
    # prefetch pays the up-projection off-tick so import never does.
    _staged: Optional[tuple] = None
    # the exporting engine's codec (kv_compress.KVCompressCodec); rides
    # the in-process block so the flusher's to_host can compress, never
    # serialized — from_bytes receivers pass their own codec at import
    _codec: object = None
    _lock: object = field(default_factory=lambda: make_lock("KVPageBlock._lock"), repr=False)

    @property
    def n_pages(self) -> int:
        return _leaves(self.k_pages)[0].shape[2]  # mst: allow(MST201): shape is invariant across the to_host swap

    @property
    def nbytes(self) -> int:
        """Payload size used against the spill budget (KV pages dominate;
        the sampler rows are a few hundred bytes and are not counted)."""
        return int(sum(
            0 if isinstance(leaf, ZeroLeaf)
            else int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            for leaf in _leaves((self.k_pages, self.v_pages))  # mst: allow(MST201): shapes/dtypes invariant across the to_host swap
        ))

    @property
    def is_host(self) -> bool:
        return self._host  # mst: allow(MST201): monotonic flag; to_host is idempotent on a racy False

    @property
    def is_prefetched(self) -> bool:
        return self._staged is not None  # mst: allow(MST201): racy read is gauge-grade; importers re-read under payload()'s lock

    def prefetch(self, put=None, codec=None) -> "KVPageBlock":
        """Stage the host-resident page payload back onto the device ahead
        of a scheduled import (the PRESERVE-style overlap, arXiv:2501.08192):
        ``jax.device_put`` only DISPATCHES the host→device DMA, so the copy
        rides alongside the decode block in flight and the admission-time
        page scatter consumes already-device-resident arrays. Idempotent; a
        block the flusher hasn't copied to host yet needs no staging (its
        payload never left the device). A compressed block reconstructs its
        pool-form payload here — off the tick path — so the import scatter
        never materializes an up-projection (MST116). Fault site
        ``cache.prefetch`` models a failed/refused stage — callers catch,
        count, and degrade to the demand import (then to re-prefill),
        never a dropped stream."""
        inject("cache.prefetch", n_bytes=self.nbytes)
        putfn = put if put is not None else jax.device_put
        with self._lock:
            if not self._host or self._staged is not None:
                return self
            if self.compress_kind is not None:
                dec = codec if codec is not None else self._codec
                if dec is None:
                    # nothing local can reconstruct it; the demand import
                    # (which carries the pool's codec) will
                    return self
                # a reconstruct fault propagates: the caller counts a
                # prefetch fault and the demand path retries at import
                k_pages, v_pages = dec.reconstruct_block(self)
            else:
                k_pages, v_pages = self.k_pages, self.v_pages
            self._staged = (
                jax.tree.map(putfn, k_pages),
                jax.tree.map(putfn, v_pages),
            )
        return self

    def payload(self) -> tuple:
        """``(k_pages, v_pages)`` for the import scatter: the prefetch-staged
        device copies when present, else the raw payload (host numpy after a
        flush — the demand path — or still-device arrays before one)."""
        with self._lock:
            if self._staged is not None:
                return self._staged
            return self.k_pages, self.v_pages

    def drop_prefetch(self) -> None:
        """Release staged device copies — a block leaving this engine
        (cross-replica migration) must not pin another mesh's buffers."""
        with self._lock:
            self._staged = None

    def to_host(self) -> "KVPageBlock":
        """Materialize the page payloads in host DRAM and stamp the
        checksum. Idempotent and thread-safe: the tier's flusher thread
        and a drain both may race to flush the same block. This is the
        only place the export's device→host copy blocks — never call it
        from a tick-hot function (MST106)."""
        # the one blocking device→host copy: span it when the caller bound
        # a trace (disagg handoff, drain); the tier's flusher thread has no
        # binding, so steady-state spills record nothing here
        tr = tracing.current()
        t0 = time.perf_counter() if tr is not None else 0.0
        with self._lock:
            if self._host:
                return self
            k, v = jax.device_get((self.k_pages, self.v_pages))
            self.k_pages = jax.tree.map(np.asarray, k)
            self.v_pages = jax.tree.map(np.asarray, v)
            if self._codec is not None:
                # compress at the host boundary — every downstream mover
                # (spill tier, prefix demotion, federation blob, handoff
                # wire) sees the wire form. A fault/codec failure leaves
                # the block raw: counted degradation, the bytes still move
                try:
                    kind, kw, vw = self._codec.compress_pages(
                        self.k_pages, self.v_pages
                    )
                    self.k_pages, self.v_pages = kw, vw
                    self.compress_kind = kind
                    self.compress_hash = self._codec.compress_hash
                except Exception:  # noqa: BLE001 — degrade to raw, never lose the block
                    self._codec.note_fault("encode")
                    logger.warning(
                        "KV compress failed; block ships raw", exc_info=True
                    )
            if self.resume_keys is not None:
                self.resume_keys = np.asarray(self.resume_keys)
            if self.resume_recent is not None:
                self.resume_recent = np.asarray(self.resume_recent)
            self.checksum = self._fingerprint()
            self._host = True
        if tr is not None:
            tr.add("kv_to_host", t0, time.perf_counter(), bytes=self.nbytes)
        return self

    def _fingerprint(self) -> str:
        h = hashlib.blake2b(digest_size=16)
        head = f"{self.n_tokens}:{self.page_size}:{self.last_tok}"
        if self.share_hash:
            # unshared blocks keep the legacy header so their checksums
            # (and the pod-federated digests derived from them) are stable
            head += f":share={self.share_hash}"
        if self.compress_kind:
            # compressed blocks fingerprint their WIRE payload, so the
            # checksum verifies on arrival without a codec; the kind and
            # codec geometry are bound in so a relabeled payload fails
            head += f":compress={self.compress_kind}:{self.compress_hash}"
        h.update(head.encode())
        for leaf in _leaves((self.k_pages, self.v_pages)):
            if isinstance(leaf, ZeroLeaf):
                h.update(repr(leaf).encode())
            else:
                h.update(np.ascontiguousarray(leaf).tobytes())
        return h.hexdigest()

    def verify(self) -> None:
        """Structural checks always; checksum when host-materialized.
        Raises :class:`BlockIntegrityError` on any mismatch — importers
        catch it and fall back to re-prefill."""
        if self.page_size < 1 or self.n_tokens < 1:
            raise BlockIntegrityError(
                f"degenerate block: page_size={self.page_size} "
                f"n_tokens={self.n_tokens}"
            )
        if self.n_tokens > self.n_pages * self.page_size:
            raise BlockIntegrityError(
                f"block claims {self.n_tokens} KV rows but carries only "
                f"{self.n_pages} pages of {self.page_size}"
            )
        if not self.history and self.produced != 0:
            # resume blocks always carry history; only a pure-prefix block
            # (prefix_store demotion: prompt KV, nothing emitted) may be
            # history-less, and it must claim zero produced tokens
            raise BlockIntegrityError("block without emitted history")
        # hold the block lock so the fingerprint reads a consistent
        # (payload, checksum) pair against a racing flusher to_host()
        with self._lock:
            if self._host and self.checksum is not None:
                if self._fingerprint() != self.checksum:
                    raise BlockIntegrityError(
                        "KV page payload checksum mismatch (corrupt block)"
                    )

    def to_bytes(self) -> bytes:
        """Wire format for cross-host shipment (the pod handoff): the
        host-materialized payload trees plus every resume field, one
        pickled dict. Host-materialization is the caller's job (``ship``
        runs off-tick, so the blocking :meth:`to_host` is legal there);
        the stamped checksum rides along and :meth:`from_bytes` re-verifies
        it on arrival, so transport corruption surfaces as
        :class:`BlockIntegrityError` — the importer's re-prefill fallback —
        never as wrong KV rows."""
        with self._lock:
            if not self._host:
                raise BlockIntegrityError(
                    "to_bytes() needs a host-materialized block — "
                    "call to_host() first (off the tick path)"
                )
            payload = {
                "k_pages": self.k_pages,
                "v_pages": self.v_pages,
                "n_tokens": self.n_tokens,
                "page_size": self.page_size,
                "prompt": self.prompt,
                "history": list(self.history),
                "produced": self.produced,
                "last_tok": self.last_tok,
                "resume_keys": self.resume_keys,
                "resume_recent": self.resume_recent,
                "share_hash": self.share_hash,
                "compress_kind": self.compress_kind,
                "compress_hash": self.compress_hash,
                "checksum": self.checksum,
            }
        import pickle

        return pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)

    @staticmethod
    def from_bytes(data: bytes) -> "KVPageBlock":
        """Rebuild a shipped block on the receiving host and verify it.
        Raises :class:`BlockIntegrityError` on any truncation, unpickle
        failure, or checksum mismatch — the caller counts the fallback and
        re-prefills from the resume history instead."""
        import pickle

        try:
            payload = pickle.loads(data)
            blk = KVPageBlock(
                k_pages=payload["k_pages"],
                v_pages=payload["v_pages"],
                n_tokens=int(payload["n_tokens"]),
                page_size=int(payload["page_size"]),
                prompt=np.asarray(payload["prompt"], np.int32),
                history=[int(t) for t in payload["history"]],
                produced=int(payload["produced"]),
                last_tok=int(payload["last_tok"]),
                resume_keys=payload["resume_keys"],
                resume_recent=payload["resume_recent"],
                share_hash=payload.get("share_hash"),
                compress_kind=payload.get("compress_kind"),
                compress_hash=payload.get("compress_hash"),
                checksum=payload["checksum"],
                _host=True,
            )
        except BlockIntegrityError:
            raise
        except Exception as e:  # noqa: BLE001 — any decode failure is corruption
            raise BlockIntegrityError(
                f"undecodable shipped block: {e!r}"
            ) from e
        blk.verify()
        return blk

    def compatible_with(self, cache) -> Optional[str]:
        """``None`` if this block's pages can be scattered into ``cache``'s
        pool; else a reason string. Catches cross-mode imports (int8 block
        into a bf16 pool and vice versa — the leaf trees differ) and any
        per-leaf geometry mismatch outside the pool axis. Compressed
        blocks are judged on their RECONSTRUCTED payload — import decodes
        first and calls :func:`pages_compatible` directly."""
        with self._lock:  # consistent payload view vs a racing to_host()
            return pages_compatible(self.k_pages, self.v_pages, cache)


def pages_compatible(k_pages, v_pages, cache, check_dtype=True) -> Optional[str]:
    """``None`` if the payload trees can be scattered into ``cache``'s
    pool; else a reason string. ``check_dtype=False`` is the lossy-lowrank
    import path: reconstruction yields float32 rows that the scatter casts
    into the pool dtype (the payload was never bit-exact to begin with)."""
    ours = jax.tree.structure((k_pages, v_pages))
    theirs = jax.tree.structure((cache.k, cache.v))
    if ours != theirs:
        return (
            f"KV storage mode mismatch: block {ours} vs pool {theirs}"
        )
    for blk, pool in zip(
        _leaves((k_pages, v_pages)),
        _leaves((cache.k, cache.v)),
    ):
        bs, ps = tuple(blk.shape), tuple(pool.shape)
        if len(bs) != len(ps) or bs[:2] != ps[:2] or bs[3:] != ps[3:]:
            return (
                f"page geometry mismatch: block leaf {bs} vs pool {ps}"
            )
        if check_dtype and np.dtype(blk.dtype) != np.dtype(pool.dtype):
            return (
                f"dtype mismatch: block {blk.dtype} vs pool {pool.dtype}"
            )
    return None


def export_block(
    cache,
    page_ids,
    *,
    page_size: int,
    n_tokens: int,
    prompt,
    history,
    produced: int,
    resume_keys,
    resume_recent,
    share_hash: Optional[str] = None,
    codec=None,
    gather=None,
    put=None,
) -> KVPageBlock:
    """Lift a request's page chain out of a paged cache as a
    :class:`KVPageBlock`. Dispatch-only on the device side: the returned
    block holds device arrays until someone calls :meth:`to_host`.

    ``gather`` lets the batcher pass its jitted ``export_pool_pages``;
    ``put`` its device-placement hook; ``codec`` the pool's
    ``kv_compress.KVCompressCodec`` — the block carries it so whoever
    flushes it to host (the spill tier's flusher, drain, a handoff)
    compresses the payload at that boundary. Fault site ``cache.export``
    fires before any device work so an injected failure leaves the cache
    untouched."""
    inject("cache.export", n_pages=len(page_ids), n_tokens=n_tokens)
    ids = np.asarray(list(page_ids), np.int32)
    if put is not None:
        ids = put(ids)
    fn = gather if gather is not None else export_pool_pages
    # self-instrumentation on the caller-bound trace (tracing.bind in the
    # scheduler/coordinator): the gather DISPATCH cost, not the DMA — the
    # copy itself lands in to_host on whoever pulls the block
    tr = tracing.current()
    if tr is not None:
        with tr.timed("kv_export", pages=len(page_ids), tokens=n_tokens):
            k_pages, v_pages = fn(cache, ids)
    else:
        k_pages, v_pages = fn(cache, ids)
    history = [int(t) for t in history]
    return KVPageBlock(
        k_pages=k_pages,
        v_pages=v_pages,
        n_tokens=int(n_tokens),
        page_size=int(page_size),
        prompt=np.array(prompt, np.int32, copy=True),
        history=history,
        produced=int(produced),
        # a pure-prefix export (prefix_store demotion) has emitted nothing:
        # there is no next decode input, so last_tok is a sentinel
        last_tok=int(history[-1]) if history else -1,
        resume_keys=resume_keys,
        resume_recent=resume_recent,
        share_hash=share_hash,
        _codec=codec,
    )


def import_block(cache, block: KVPageBlock, page_ids, *, share_hash=None,
                 codec=None, scatter=None, put=None):
    """Scatter ``block``'s page payloads into pool pages ``page_ids`` of
    ``cache`` and return the updated cache. Validates the block first
    (checksum + geometry + share-map and compress layout identities
    against the pool's ``share_hash``/``codec``); raises on any problem
    so the caller can release the pages and fall back to re-prefill.
    A compressed block reconstructs here (or consumes the prefetch-staged
    reconstruction); fault sites ``cache.import`` / ``cache.compress``
    model mid-import and mid-reconstruct failure."""
    inject("cache.import", n_pages=len(page_ids), n_tokens=block.n_tokens)
    block.verify()
    if block.share_hash != share_hash:
        # the geometry check below can't see this (a 2-layer-pair share
        # map halves the pool's layer axis, but two DIFFERENT maps with
        # the same group count are byte-compatible and silently wrong)
        raise BlockIntegrityError(
            f"KV share-map layout mismatch: block was exported under "
            f"share_hash={block.share_hash!r} but this pool runs "
            f"{share_hash!r} — re-prefill, or serve both hosts with the "
            f"same --kv-share-map artifact"
        )
    if block.compress_kind is not None:
        want = codec.compress_hash if codec is not None else None
        if block.compress_hash != want:
            raise BlockIntegrityError(
                f"KV compress layout mismatch: block carries a "
                f"{block.compress_kind!r} payload under compress_hash="
                f"{block.compress_hash!r} but this pool's codec is "
                f"{want!r} — re-prefill, or serve both hosts with the "
                f"same model/--kv-compress-map geometry"
            )
    if len(page_ids) != block.n_pages:
        raise BlockIntegrityError(
            f"import wants {len(page_ids)} pages for a {block.n_pages}-page block"
        )
    # prefetch-staged device copies when present (the overlapped path —
    # already reconstructed for compressed blocks); otherwise the raw
    # payload, reconstructed here — host numpy here IS the demand import
    if block.compress_kind is not None and not block.is_prefetched:
        try:
            k_pages, v_pages = codec.reconstruct_block(block)
        except Exception as e:  # noqa: BLE001 — fault or codec failure, same fallback
            codec.note_fault("decode")
            raise BlockIntegrityError(
                f"compressed block reconstruction failed: {e}"
            ) from e
        reason = pages_compatible(
            k_pages, v_pages, cache,
            check_dtype=block.compress_kind == "latent",
        )
    elif block.compress_kind is not None:
        k_pages, v_pages = block.payload()
        reason = pages_compatible(
            k_pages, v_pages, cache, check_dtype=False,
        )
    else:
        reason = block.compatible_with(cache)
        k_pages, v_pages = block.payload()
    if reason is not None:
        raise BlockIntegrityError(reason)
    ids = np.asarray(list(page_ids), np.int32)
    if put is not None:
        ids = put(ids)
    fn = scatter if scatter is not None else import_pool_pages
    tr = tracing.current()
    if tr is not None:
        with tr.timed("kv_import", pages=len(page_ids),
                      tokens=block.n_tokens):
            return fn(cache, k_pages, v_pages, ids)
    return fn(cache, k_pages, v_pages, ids)


class KVSpillTier:
    """Host-DRAM LRU spill tier for preempted requests' KV blocks.

    ``put`` is cheap on the caller (scheduler) thread: it only links the
    block into the LRU map and enqueues it for the background flusher
    thread, which performs the blocking device→host copy off the tick
    path. Eviction is strict LRU by insertion/refresh order; a block
    larger than the whole budget is rejected outright (the caller falls
    back to discard-and-re-prefill, exactly the pre-spill behavior).

    Keys are the owning request objects (identity), so a tier entry dies
    with its request and two requests can never collide."""

    def __init__(self, budget_bytes: int, flush_async: bool = True):
        if not isinstance(budget_bytes, int) or isinstance(budget_bytes, bool) \
                or budget_bytes <= 0:
            raise ValueError("spill budget must be a positive byte count")
        self.budget_bytes = budget_bytes
        self._blocks: "OrderedDict[object, KVPageBlock]" = OrderedDict()
        # bytes each resident block is currently charged against the
        # budget. A block's nbytes SHRINKS when the flusher's to_host
        # compresses it (kv_compress), so accounting must remember what
        # was charged at insert and re-charge after the flush — reading
        # blk.nbytes at pop time would leak the difference forever.
        self._sizes: dict = {}
        self._bytes = 0
        self.bytes_compress_saved = 0
        self._lock = make_lock("KVSpillTier._lock")
        self.evictions = 0
        # rejects split by reason (the aggregate stays for back-compat):
        # oversize = the block alone exceeds the whole budget; closed = a
        # put raced the tier's shutdown
        self.rejects = 0
        self.rejects_oversize = 0
        self.rejects_closed = 0
        # take() outcomes: a hit hands the resume its block (one scatter
        # instead of a re-prefill), a miss means LRU pressure evicted it
        # since the spill — the caller re-prefills. hit_rate in stats() is
        # hits / (hits + misses).
        self.hits = 0
        self.misses = 0
        self.bytes_spilled_total = 0
        self._flush_async = flush_async
        self._flush_q: "queue.Queue" = queue.Queue()
        self._flusher: Optional[threading.Thread] = None
        self._stopped = False

    # ------------------------------------------------------------- flusher
    def _ensure_flusher(self):
        # caller holds self._lock
        if self._flusher is None or not self._flusher.is_alive():
            self._flusher = threading.Thread(
                target=self._flush_loop, name="kv-spill-flusher", daemon=True
            )
            self._flusher.start()

    def _flush_loop(self):
        while True:
            item = self._flush_q.get()
            if item is None:
                return
            key, blk = item
            try:
                blk.to_host()
            except Exception:
                # a failed flush leaves the block device-resident; take()
                # still works while the arrays are alive, and verify() has
                # no checksum to mismatch — degraded, not broken
                logger.exception("KV spill flush failed; block stays on device")
            else:
                self._reaccount(key, blk)

    def _reaccount(self, key, blk: KVPageBlock) -> None:
        """Re-charge a flushed block at its post-compression size — the
        compressed-latent wire form counts fewer bytes against the budget,
        so the tier holds proportionally more blocks (the transfer
        multiplier doubles as a capacity multiplier)."""
        nb = blk.nbytes
        with self._lock:
            if self._blocks.get(key) is not blk:
                return  # dropped/replaced while flushing
            old = self._sizes.get(key, nb)
            if nb != old:
                self._sizes[key] = nb
                self._bytes += nb - old
                if nb < old:
                    self.bytes_compress_saved += old - nb

    # ------------------------------------------------------------- LRU map
    def put(self, key, block: KVPageBlock) -> bool:
        """Admit ``block`` under the budget, evicting LRU entries as
        needed. Returns False (and counts a reject) when the block alone
        exceeds the budget or the tier is closed."""
        nb = block.nbytes
        with self._lock:
            if self._stopped:
                self.rejects += 1
                self.rejects_closed += 1
                return False
            if nb > self.budget_bytes:
                self.rejects += 1
                self.rejects_oversize += 1
                return False
            old = self._blocks.pop(key, None)
            if old is not None:
                self._bytes -= self._sizes.pop(key, old.nbytes)
                note_release("tier.block", (id(self), key))
            while self._bytes + nb > self.budget_bytes and self._blocks:
                ek, evicted = self._blocks.popitem(last=False)
                self._bytes -= self._sizes.pop(ek, evicted.nbytes)
                self.evictions += 1
                note_release("tier.block", (id(self), ek))
            self._blocks[key] = block
            self._sizes[key] = nb
            self._bytes += nb
            note_acquire("tier.block", (id(self), key), nbytes=nb)
            self.bytes_spilled_total += nb
            if self._flush_async:
                self._ensure_flusher()
        if self._flush_async:
            self._flush_q.put((key, block))
        else:
            block.to_host()
            self._reaccount(key, block)
        return True

    def _pop(self, key) -> Optional[KVPageBlock]:
        # caller-agnostic removal: no hit/miss accounting (drop() uses it
        # for cancelled streams, which are neither)
        with self._lock:
            blk = self._blocks.pop(key, None)
            if blk is not None:
                self._bytes -= self._sizes.pop(key, blk.nbytes)
                note_release("tier.block", (id(self), key))
            return blk

    def take(self, key) -> Optional[KVPageBlock]:
        """Remove and return ``key``'s block for a resume, or None if LRU
        pressure evicted it since the spill; counts the hit/miss."""
        blk = self._pop(key)
        with self._lock:
            if blk is not None:
                self.hits += 1
            else:
                self.misses += 1
        return blk

    def peek(self, key) -> Optional[KVPageBlock]:
        with self._lock:
            return self._blocks.get(key)

    def touch(self, key) -> None:
        """LRU refresh without removal — the scheduler calls this when a
        spilled request is back in the resume path (head of the waiting
        line, a cold slot's consumer caught up), so budget pressure evicts
        some genuinely-cold block instead of the one about to re-import."""
        with self._lock:
            if key in self._blocks:
                self._blocks.move_to_end(key)

    def contains(self, key) -> bool:
        with self._lock:
            return key in self._blocks

    def keys(self) -> list:
        """Snapshot of resident keys, MRU-first — the prefix store's pod
        inventory reads this to gossip what this host can serve."""
        with self._lock:
            return list(reversed(self._blocks.keys()))

    def share_hashes(self) -> set:
        """Distinct ``share_hash`` values across resident blocks — the
        prefix store's share-map bind check reads this to reject a layout
        change over blocks exported under another one."""
        with self._lock:
            return {b.share_hash for b in self._blocks.values()}

    def compress_hashes(self) -> set:
        """Distinct ``compress_hash`` values across resident blocks — the
        prefix store's compress bind check reads this the same way. A
        still-raw block (flusher hasn't compressed it yet, or no codec)
        contributes None, which is always bind-compatible: raw payloads
        import anywhere their geometry fits."""
        with self._lock:
            return {b.compress_hash for b in self._blocks.values()}

    def drop(self, key) -> None:
        self._pop(key)

    def clear(self) -> None:
        with self._lock:
            self._blocks.clear()
            self._sizes.clear()
            self._bytes = 0
            tid = id(self)
            note_reset("tier.block", lambda k: k[0] == tid)

    def stats(self) -> dict:
        with self._lock:
            lookups = self.hits + self.misses
            return {
                "budget_bytes": self.budget_bytes,
                "bytes_in_use": self._bytes,
                "blocks": len(self._blocks),
                # blocks the flusher has host-materialized so far — the
                # prefetchable population (a still-device block needs no
                # staging); also what lets tests wait out the async flush
                "blocks_host": sum(
                    1 for b in self._blocks.values() if b.is_host
                ),
                "evictions": self.evictions,
                "rejects": self.rejects,
                "rejects_oversize": self.rejects_oversize,
                "rejects_closed": self.rejects_closed,
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": (self.hits / lookups) if lookups else 0.0,
                "bytes_spilled_total": self.bytes_spilled_total,
                # budget headroom reclaimed by compressed-latent flushes
                "bytes_compress_saved": self.bytes_compress_saved,
            }

    def close(self) -> None:
        with self._lock:
            self._stopped = True
            flusher = self._flusher
        self._flush_q.put(None)
        if flusher is not None and flusher.is_alive():
            flusher.join(timeout=5)
        self.clear()
