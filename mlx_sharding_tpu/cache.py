"""Functional, preallocated KV cache.

TPU-native replacement for the reference's growable per-layer ``KVCache``
objects (ref: shard/server/server.py:9-10,22; shard/utils.py:142-150). The
reference mutates a Python-global list of caches per RPC; on TPU that would
force re-compilation and host round-trips, so instead the cache is a pytree of
fixed-capacity HBM buffers carried through the jitted step function and
updated with ``lax.dynamic_update_slice`` — donated each step so XLA updates
in place.

Layout: keys/values are stacked across the stage's local layers:
    k, v : (num_layers, batch, max_seq, n_kv_heads, head_dim)
plus a scalar ``offset`` (the reference's ``KVCache.offset``, used for the
causal-mask shift at shard/server/model/llama.py:48-53).

MLA models cache differently-shaped tensors (tuple head dims,
ref: shard/server/model/deepseek_v2.py:120-125); they use the same structure
with their own head dims per tensor.

A model with recurrent layers (``models/nemotron_h.py``: Mamba-2) keeps a
second kind of per-sequence state beside the K/V rows: ``state``, a pytree
of per-layer, per-slot arrays the model defines (an SSM state in float32, a
convolution's last inputs) — donated and carried exactly as ``k``/``v`` are.
It is not addressed by ``offset``: lowering an offset rewinds K/V rows and
NOT the state, so every path that puts a sequence back that way either
starts over from position 0 (where the programs take the state as zero) or
refuses such a model at start-up (:func:`refuse_recurrent`).

A model with sliding-window layers (``models/afmoe.py``) keeps those layers'
K/V in ``state`` too: per window layer and slot a RING of ``ring_rows``
rows (:func:`window_ring_rows`: the window, one prefill chunk and one page,
in whole pages) in which position ``p`` lives at row ``p % ring_rows``, so
a window layer's bytes do not grow with the context; only the full-attention
layers have pages in ``k``/``v``. A row is overwritten ``ring_rows``
positions later, when no query can see it any more. The same refusals hold:
what re-enters or moves a sequence as full-length pages only cannot serve
such a model.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class KVCache(NamedTuple):
    k: jax.Array  # (L, B, S, H_kv, D_k) — or {"d": int8, "s": f32} (paged int8)
    v: jax.Array  # (L, B, S, H_kv, D_v) — same
    offset: jax.Array  # scalar int32 — number of valid positions
    # recurrent per-sequence state ({name: (L_state, slots, …)}) of models
    # that have any; None — no leaves, the same programs as before — otherwise
    state: Optional[dict] = None

    @property
    def max_seq(self) -> int:
        return kv_data(self.k).shape[2]

    @property
    def num_layers(self) -> int:
        return kv_data(self.k).shape[0]


def has_recurrent_state(model) -> bool:
    return bool(getattr(model, "has_recurrent_state", False))


def has_window_layers(model) -> bool:
    return bool(getattr(model, "has_window_layers", False))


def has_slot_state(model) -> bool:
    """Whether engines carry ``KVCache.state`` for this model: recurrent
    state, or window layers' rings."""
    return has_recurrent_state(model) or has_window_layers(model)


def window_ring_rows(window: int, chunk: int, page: int, max_seq: int) -> int:
    """Rows of one slot's ring in one window layer: the window and one
    prefill chunk (a chunk's queries reach ``window - 1`` rows behind its
    first row while its last row is already written) rounded up to pages,
    plus one page; never more than the context."""
    pages = -(-(window + chunk) // page) + 1
    return min(pages * page, max_seq)


def refuse_recurrent(model, flag: str, why: str) -> None:
    """The one start-up error of every feature that moves or rewinds a
    sequence as full-length pages of K/V only: it names the flag and says
    "recurrent state" or "window layers". No-op for a model with neither."""
    if has_recurrent_state(model):
        kind = "recurrent state"
    elif has_window_layers(model):
        kind = "window layers whose K/V is a ring"
    else:
        return
    raise ValueError(
        f"{flag} cannot serve {type(model).__name__}: it has {kind} beside "
        f"its K/V pages, and {why}"
    )


def is_quantized_kv(buf) -> bool:
    """True for an int8 KV buffer: ``{"d": int8 data, "s": float scales}``
    with the scale's trailing dim 1 broadcasting over head_dim."""
    return isinstance(buf, dict) and "d" in buf


def kv_data(buf) -> jax.Array:
    """The data leaf of a KV buffer — the int8 payload for quantized pools,
    the array itself otherwise. Shape-only bookkeeping (page counts, slot
    geometry) reads this so it never cares about the storage mode."""
    return buf["d"] if is_quantized_kv(buf) else buf


def quantize_kv_rows(rows: jax.Array) -> dict:
    """(…, H, D) float rows → ``{"d": int8, "s": f32 (…, H, 1)}`` with a
    per-row-per-head symmetric scale ``max|x| / 127``.

    Per-ROW scales (not per-page) are deliberate: ragged decode writes one
    row into a page per tick, and a per-page scale would force a read-
    modify-write rescale of the other rows on every write. Rows are
    independent — writeback, scatter, and rewind all stay pure writes."""
    x = rows.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    d = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    return {"d": d, "s": s.astype(jnp.float32)}


def dequantize_kv(buf, dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv_rows`; passes dense buffers through
    (after a dtype cast) so call sites handle both storage modes."""
    if not is_quantized_kv(buf):
        return buf.astype(dtype)
    return (buf["d"].astype(jnp.float32) * buf["s"]).astype(dtype)


def init_cache(
    num_layers: int,
    batch: int,
    max_seq: int,
    n_kv_heads: int,
    head_dim,
    dtype=jnp.bfloat16,
) -> KVCache:
    """Allocate an empty cache. ``head_dim`` may be an int or a
    ``(k_dim, v_dim)`` tuple for MLA (ref: deepseek_v2.py:120-125)."""
    if isinstance(head_dim, (tuple, list)):
        k_dim, v_dim = head_dim
    else:
        k_dim = v_dim = head_dim
    return KVCache(
        k=jnp.zeros((num_layers, batch, max_seq, n_kv_heads, k_dim), dtype),
        v=jnp.zeros((num_layers, batch, max_seq, n_kv_heads, v_dim), dtype),
        offset=jnp.zeros((), jnp.int32),
    )


@jax.named_scope("mst.attn.kv_write")
def write_layer_kv(
    k_buf: jax.Array,
    v_buf: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    offset: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Write ``k_new``/``v_new`` (B, T, H_kv, D) into one layer's
    full-capacity buffers (B, S, H_kv, D) at position ``offset``.

    Used inside the per-layer body of the ``lax.scan`` over stacked layers:
    the scan consumes ``cache.k``/``cache.v`` as per-layer xs and re-stacks
    the returned buffers as ys, so no dynamic indexing on the layer axis is
    ever needed. The shared ``offset`` counter is advanced once per step by
    :func:`advance` (as in the reference, every layer's cache grows in
    lockstep)."""
    zero = jnp.zeros((), jnp.int32)
    k = jax.lax.dynamic_update_slice(k_buf, k_new.astype(k_buf.dtype), (zero, offset, zero, zero))
    v = jax.lax.dynamic_update_slice(v_buf, v_new.astype(v_buf.dtype), (zero, offset, zero, zero))
    return k, v


def advance(cache: KVCache, n_tokens) -> KVCache:
    return cache._replace(offset=cache.offset + jnp.asarray(n_tokens, jnp.int32))


def check_capacity(cache: KVCache, n_new: int) -> None:
    """Host-side guard: ``dynamic_update_slice`` clamps out-of-range starts,
    which would silently overwrite valid entries rather than error. Call this
    outside jit (the generate loop does) before writing ``n_new`` tokens."""
    offset = int(cache.offset)
    if offset + n_new > cache.max_seq:
        raise ValueError(
            f"KV cache overflow: offset {offset} + {n_new} new tokens exceeds "
            f"capacity {cache.max_seq}. Allocate a larger max_seq."
        )


def reset(cache: KVCache) -> KVCache:
    """Equivalent of the reference's ResetCache RPC (shard/server/server.py:59-71):
    invalidate without reallocating."""
    return cache._replace(offset=jnp.zeros((), jnp.int32))


def export_pool_pages(cache: KVCache, page_ids: jax.Array):
    """Gather pool pages out of a paged cache's k/v buffers.

    ``page_ids`` is an int32 vector of pool-page indices; the paged pool
    layout puts the pool axis at position 2 of every leaf
    ``(S, L, pool_pages+1, B, page, H, D)``, so a ``take`` along axis 2
    lifts a request's page chain out of the pool in one gather per leaf —
    int8 pools (``{"d", "s"}`` dicts) come through ``jax.tree`` with their
    scales attached, which is what makes the exported block a faithful
    copy of the quantized codes rather than a lossy dequant/requant trip.

    Pure and jittable: callers jit it once and reuse the program per page
    count. Returns ``(k_pages, v_pages)`` pytrees shaped like the pool
    leaves with the pool axis narrowed to ``len(page_ids)``."""
    take = lambda leaf: jnp.take(leaf, page_ids, axis=2)  # noqa: E731
    return jax.tree.map(take, cache.k), jax.tree.map(take, cache.v)


def import_pool_pages(
    cache: KVCache, k_pages, v_pages, page_ids: jax.Array
) -> KVCache:
    """Scatter previously exported page payloads into pool pages
    ``page_ids`` of a paged cache — the inverse of
    :func:`export_pool_pages`. The payload leaves may be host (numpy)
    arrays from a spilled block or device arrays from a live one; dtypes
    are cast to the pool's (a bf16→bf16 or int8→int8 identity in
    practice — cross-mode imports are rejected before this call by
    ``KVPageBlock.compatible_with``).

    Residency note: when the leaves are host numpy, the ``jnp.asarray``
    below IS the demand-paged host→device marshal — the stall the
    scheduler's prefetch path avoids by handing this function
    ``KVPageBlock.payload()`` device arrays staged ahead of the resume
    tick (then the asarray is an identity and the jitted scatter runs
    against buffers already on device)."""

    def put(pool, blk):
        return pool.at[:, :, page_ids].set(jnp.asarray(blk).astype(pool.dtype))

    return cache._replace(
        k=jax.tree.map(put, cache.k, k_pages),
        v=jax.tree.map(put, cache.v, v_pages),
    )


def rewind_slot_offset(offset: jax.Array, slot, steps) -> jax.Array:
    """Roll one slot's write offset back by ``steps`` positions (floored at
    0). ``offset`` is the per-slot ``(M,)`` vector of the batched engines,
    not the scalar single-stream layout.

    Used by the async continuous batcher when reclaiming a slot that
    retired while a lookahead decode block was still in flight: the block's
    frozen active mask advanced the dead slot's offset up to one block past
    its true end, and the offset must not point past the pages being
    returned to the pool.

    It takes the offsets and not the cache: a jitted program hands back
    what passes through it unchanged as a COPY, so one that took the cache
    allocated a second page pool (and state pool) at every such reclaim —
    4.9 GB beside a 10 GB server, 6 GB where every layer keeps pages."""
    steps = jnp.asarray(steps, jnp.int32)
    return offset.at[slot].set(jnp.maximum(offset[slot] - steps, 0))
