"""Ragged paged decode attention: attend over the KV page pool in place.

The paged continuous-batching decode path used to gather every slot's pages
into a dense (B, max_seq, H, D) view per tick (`_paged_read`) and scatter the
dirty page back (`_paged_writeback`) — the entire KV cache through HBM twice
per T=1 step, then attention over max_seq padding regardless of each slot's
true length. This module is the TPU-native fix (Ragged Paged Attention,
arxiv 2604.15464): a Pallas kernel that walks each slot's page-table row
directly, streaming only the pages a slot actually occupies and masking
FLOPs past its offset. No contiguous copy of the cache ever exists.

Two paths, selected the same way ops/flash_attention.py picks its path:

- the Pallas kernel (`_paged_attention_kernel`): grid (slot, page); the
  page to fetch is data-dependent, so the page table and lengths ride in as
  scalar-prefetch operands and the K/V BlockSpec index maps read them —
  Pallas double-buffers exactly the pages named by the table. A block is
  one whole pool page, every KV head side by side on the lane axis (the
  pool viewed as (P+1, page, Hkv·D), a free reshape): Mosaic takes a block
  whose last two dims are tile-aligned or whole, and one head out of Hkv in
  the pool's native (page, Hkv, D) layout is neither. The kernel walks the
  heads as static 128-aligned lane slices. The walk's block indices follow
  the slots' LENGTHS, not their table rows (``walk_page``): consecutive
  grid steps with an identical block index skip the DMA, and a step past a
  slot's last page names the NEXT slot's first visible page, so it fetches
  nothing of its own and the next slot finds its first page in the buffer —
  fetched under this slot's last live page's arithmetic (the pipeline
  issues step k+1's fetch when step k starts). What lies in the row past
  the length is never read, be it the scratch id or, under the default
  RESERVE admission, the pages a stream has claimed and not yet filled
  (1.2-3 times the pages a slot holds, in the benchmark's cells). A call
  fetches ``max(1, live pages)`` blocks a slot. Online-softmax state
  (running max, normalizer, fp32 accumulator, per head) lives in VMEM
  scratch across the page walk. MLA's latent-as-values
  (``values_from_k``) is the same kernel with one operand fewer: the value block is the first ``values_from_k``
  lanes of the key block already in VMEM, and the latent pool's dummy
  (…, 1, 1) V is never fetched. A static ``sliding_window`` w masks keys at
  ``k_pos <= length - 1 - w``; a grid step whose page lies wholly behind
  the window or past the length computes nothing: a step behind the window
  names the slot's first visible page, as the step past the length names
  the next slot's, so it repeats a neighbour's block index and fetches
  nothing new. The table may map positions to a ring of pages
  (``cache.window_ring_rows``): logical page ``j`` is then
  ring page ``j % R`` of the slot, and the clamp keeps the walk off the
  ring pages that hold positions outside the window. An optional second
  length a slot (``lead_lengths``, one more scalar-prefetch operand) bounds
  the leading ``lead_rows`` rows of every K/V head's query group: two
  blocks of a sequence in one call, the earlier blind to the later's keys
  (``diffusion.py``'s wide forward); absent, the body, the operands and
  the grid are what they were.
- a fused-XLA fallback for CPU / odd shapes / softcap / a traced window,
  mirroring ops/attention.py's masking semantics. It gathers every slot's
  WHOLE table row (slot_pages × page rows, i.e. max_seq) and masks it, so
  its cost follows max_seq and not the caches' lengths: 75 MB a layer at
  16 slots × 16 pages of 256 × 576 bf16, whatever the slots hold. A row
  past the length is weighted by a probability of exactly 0, which only a
  finite value survives: the pool holds numbers everywhere (it does: zeros
  or a finished stream's rows), where the kernel never reads those pages.

Both are token-exact vs the gather path; tests/test_paged_attention.py holds
the parity matrix (uneven lengths, page-boundary offsets, empty slots, GQA/
MQA head counts, the latent layout and windows over plain and ring tables,
kernel-in-interpret vs XLA, two lengths a query group, rows whose tails are
claimed pages full of NaN) and the walk replayed on the host under the
pipeline's rule.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlx_sharding_tpu.ops.dispatch import DispatchCounter

NEG_INF = -1e30
#: what Mosaic lets a kernel use of VMEM unasked, on every generation so far
_DEFAULT_SCOPED_VMEM = 16 << 20

# Which path paged_attention chose, once per traced call (ops/dispatch.py).
# /metrics shows it as ``mst_paged_attention_dispatch_total{path}``: "xla"
# above 0 on a chip says some layer gathers its slots' whole table rows
# every step.
_DISPATCHED = DispatchCounter("kernel", "xla")
dispatch_counts = _DISPATCHED.counts
_count_dispatch = _DISPATCHED.count


def kernel_eligible(
    dk: int,
    dv: int,
    logit_softcap,
    sliding_window,
    values_from_k,
    interpret: bool,
    hkv: int = 1,
) -> bool:
    """Pallas path: TPU backend (or interpret mode on any backend), GQA and
    MLA's latent-as-values, with or without a window known at trace time —
    softcap and a traced window (one model's layers alternating inside one
    scan) stay on the XLA path. Head dims need 64-alignment on real
    hardware (Mosaic pads sub-128 lane tails); with ``values_from_k`` the
    values are the first lanes of each head's key slice, so their width is
    a multiple of 128 inside ``dk`` (``dv``, the dummy V pool's, is not
    asked) and several heads need a 128-aligned ``dk`` to start each slice
    on a lane tile. int8 pools follow the same rules in both layouts.
    No count of K/V heads and no query group is refused: the body walks
    the heads, each a ``(G, Dk)`` product from a query group of one up
    (multi-head attention, ``models/olmo_hybrid.py``: 30 heads of one row),
    and a call whose double-buffered page blocks pass Mosaic's default 16
    MiB of VMEM (30 heads of 128 merged on a 512-row page: 15.7 MB) states
    its own limit. Interpret mode takes any shape so CPU tests exercise the
    kernel logic itself."""
    if logit_softcap is not None:
        return False
    if sliding_window is not None and not isinstance(sliding_window, int):
        return False
    if interpret:
        return True
    if jax.default_backend() != "tpu" or dk % 64:
        return False
    if values_from_k is None:
        return dv % 64 == 0
    return (
        values_from_k % 128 == 0
        and values_from_k <= dk
        and (hkv == 1 or dk % 128 == 0)
    )


def walk_page(mi, ji, t, ln, *, page_size: int, window=None):
    """The pool page grid step ``(mi, ji)`` names: the K/V BlockSpecs' index
    map, a plain function of the prefetched table ``t`` (M, SPG) and lengths
    ``ln`` (M,) so that a test can replay the walk on the host (scalars or
    index arrays alike). With ``first`` / ``last`` the slot's first and last
    visible page: a step in ``[first, last]`` names its own table entry, a
    step before ``first`` the ``first`` page, and a step past ``last`` the
    NEXT slot's ``first`` page (the last slot's: its own ``last``) — the
    same block index as its neighbour's, so no fetch of its own, and the
    next slot starts on a page already in the buffer. An empty slot is its
    entry 0 alone."""

    def span(x):
        first = 0
        if window is not None:
            first = jnp.maximum(ln[x] - window, 0) // page_size
        return first, jnp.maximum(ln[x] - 1, 0) // page_size

    first, last = span(mi)
    nxt = jnp.minimum(mi + 1, t.shape[0] - 1)
    ahead = (ji > last) & (mi < nxt)
    return t[
        jnp.where(ahead, nxt, mi),
        jnp.where(ahead, span(nxt)[0], jnp.clip(ji, first, last)),
    ]


def _kernel(
    tables_ref,  # (M, SPG) int32 — scalar-prefetch
    lens_ref,  # (M,) int32 — scalar-prefetch
    *refs,
    # lead_ref (M,) int32 — scalar-prefetch, only with ``lead_rows``
    # q_ref (1, Hkv, G, Dk) block — one slot's query heads
    # k_ref (1, page, Hkv*Dk) block — the page named by tables[m, j]
    # v_ref (1, page, Hkv*Dv) block — absent where ``latent``
    # ks_ref, vs_ref (1, page, Hkv) per-row scales — int8 pools only, and
    #   vs_ref only beside v_ref
    # o_ref (1, Hkv, G, Dv) block
    # m_scr, l_scr (Hkv, G, 128) f32 VMEM — running max and normalizer,
    #   lane-replicated
    # acc_scr (Hkv, G, Dv) f32 VMEM — unnormalized output accumulator
    scale: float,
    page_size: int,
    pages_per_slot: int,
    hkv: int,
    dk: int,
    dv: int,
    quant: bool,
    latent: bool,
    window=None,
    lead_rows: int = 0,
):
    lead_ref = None
    if lead_rows:
        lead_ref, *refs = refs
    q_ref, *pages, o_ref, m_scr, l_scr, acc_scr = refs
    pages = iter(pages)
    k_ref = next(pages)
    v_ref = None if latent else next(pages)
    ks_ref = next(pages) if quant else None
    vs_ref = next(pages) if quant and not latent else None
    m = pl.program_id(0)
    j = pl.program_id(1)
    length = lens_ref[m]

    def row_bound(shape, axis):
        # the leading ``lead_rows`` rows (along ``axis``) of every K/V
        # head's query group see keys below ``lead``, the others below
        # ``length``
        row = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        return jnp.where(row < lead_rows, lead_ref[m], length)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a step past this slot's length or wholly behind the window (the first
    # visible key is ``length - window``) computes nothing; ``walk_page``
    # gave it a neighbour's block index, so it fetched nothing either
    live = j * page_size < length
    if window is not None:
        live &= (j + 1) * page_size > length - window

    @pl.when(live)
    def _attend():
        k_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        if window is not None:
            visible = (k_pos < length) & (k_pos >= length - window)
        elif lead_ref is not None:
            visible = k_pos < row_bound((q_ref.shape[2], page_size), 0)
        # the page block carries every KV head side by side on the lane
        # axis; head h is the static lane slice [h*D, (h+1)*D)
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32)  # (G, Dk)
            kblk = k_ref[0, :, h * dk:(h + 1) * dk].astype(jnp.float32)
            if ks_ref is not None:
                # int8 pool: dequant fused into the page read — the pool's
                # HBM→VMEM traffic is the int8 bytes; the (page, 1) scale
                # broadcasts over the head dim in registers
                kblk = kblk * ks_ref[0, :, h:h + 1]
            if latent:
                # MLA: the values are the latent prefix of the key rows
                # already in VMEM — no second page is fetched
                vblk = kblk[:, :dv]
            else:
                vblk = v_ref[0, :, h * dv:(h + 1) * dv].astype(jnp.float32)
                if vs_ref is not None:
                    vblk = vblk * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (G, page)
            s = jnp.where(
                k_pos < length if window is None and lead_ref is None
                else visible, s, NEG_INF,
            )
            m_prev = m_scr[h, :, :1]  # (G, 1)
            l_prev = l_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == pages_per_slot - 1)
    def _finish():
        # empty slot (length 0, the garbage lane): l stays 0 → zeros out
        l = jnp.maximum(l_scr[:, :, :1], 1e-30)
        out = acc_scr[...] / l
        if lead_ref is not None:
            # a row bounded at 0 beside a live longer length saw whole pages
            # of masked keys with no maximum yet: zeros, as an empty slot's
            out = jnp.where(row_bound(out.shape, 1) > 0, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def _paged_attention_kernel(
    q, k_pool, v_pool, tables, lengths, scale, interpret,
    k_scale=None, v_scale=None, values_from_k=None, window=None,
    kv_heads=None, lead_lengths=None, lead_rows=0,
):
    m, hq, dk = q.shape
    pages, page_size, hkv = k_pool.shape[:3]
    latent = values_from_k is not None
    dv = values_from_k if latent else v_pool.shape[-1]
    if kv_heads is not None:  # (pages, page, 1, Hkv * D): already the block's layout
        hkv, dv = kv_heads, dv // kv_heads
    spg = tables.shape[1]
    g = hq // hkv
    qg = q.reshape(m, hkv, g, dk)
    quant = k_scale is not None

    page_id = functools.partial(walk_page, page_size=page_size, window=window)

    def page_spec(width):
        # data-dependent page fetch: the block index comes from the
        # prefetched table and lengths — this is the whole point of the
        # kernel.
        # The block is one whole pool page with (Hkv, D) flattened onto the
        # lane axis (a free reshape of the contiguous pool): Mosaic wants
        # the last two block dims tile-aligned or whole, which a
        # one-head-of-Hkv block in the pool's native layout is not.
        return pl.BlockSpec(
            (1, page_size, width),
            lambda mi, ji, t, ln, *_: (page_id(mi, ji, t, ln), 0, 0),
        )

    # every operand after q is a pool fetched page by page through the
    # table. The latent layout's V pool is a (…, 1, 1) dummy: it is no
    # operand, so no page of it is fetched
    kv = [(k_pool, dk)] if latent else [(k_pool, dk), (v_pool, dv)]
    if quant:  # the scale planes ride the same table-indexed fetch
        scales = (k_scale,) if latent else (k_scale, v_scale)
        kv += [(s.astype(jnp.float32), 1) for s in scales]
    in_specs = [
        pl.BlockSpec((1, hkv, g, dk), lambda mi, ji, *_: (mi, 0, 0, 0)),
        *(page_spec(hkv * width) for _, width in kv),
    ]
    operands = [
        qg, *(x.reshape(pages, page_size, hkv * width) for x, width in kv)
    ]

    # the page blocks, double-buffered, are nearly all a call holds in VMEM:
    # 15.7 MB at 30 K/V heads of 128 merged on a page of 512 rows (3.9 MB a
    # block), where the largest before were 2 MB. Only a call whose blocks
    # leave no room under the default states a limit: every other compiles
    # to what it did.
    blocks = 2 * sum(
        page_size * hkv * width * x.dtype.itemsize for x, width in kv
    )
    params = {}
    if blocks > _DEFAULT_SCOPED_VMEM - (4 << 20):
        params["compiler_params"] = pltpu.CompilerParams(
            # + a head's values and the scores in float32, the scratch
            vmem_limit_bytes=blocks + (16 << 20)
        )
    # scalar-prefetch operands: the table, the lengths, and with
    # ``lead_rows`` the shorter bound of each group's leading rows
    scalars = [tables, lengths] + ([lead_lengths] if lead_rows else [])
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(m, spg),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hkv, g, dv), lambda mi, ji, *_: (mi, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, 128), jnp.float32),
            pltpu.VMEM((hkv, g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale, page_size=page_size, pages_per_slot=spg,
            hkv=hkv, dk=dk, dv=dv, quant=quant, latent=latent, window=window,
            lead_rows=lead_rows,
        ),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((m, hkv, g, dv), q.dtype),
        **params,
        interpret=interpret,
        name="paged_attention",
    )(*(x.astype(jnp.int32) for x in scalars), *operands)
    return out.reshape(m, hq, dv)


def _paged_attention_xla(
    q, k_pool, v_pool, tables, lengths, scale,
    logit_softcap, sliding_window, values_from_k,
    k_scale=None, v_scale=None, kv_heads=None, lead_lengths=None, lead_rows=0,
):
    if kv_heads is not None:  # heads merged on the lane axis: split them
        split = lambda x: x.reshape(*x.shape[:2], kv_heads, -1)  # noqa: E731
        k_pool, v_pool = split(k_pool), split(v_pool)
    m, hq, dk = q.shape
    page_size, hkv = k_pool.shape[1], k_pool.shape[2]
    spg = tables.shape[1]
    g = hq // hkv

    def gathered(pool, scl):
        x = jnp.take(pool, tables, axis=0)  # (M, SPG, page, Hkv, D)
        x = x.reshape(m, spg * page_size, hkv, -1)
        if scl is not None:  # int8 pool: dequant the gathered rows only
            s = jnp.take(scl, tables, axis=0).reshape(
                m, spg * page_size, hkv, 1
            )
            x = x.astype(jnp.float32) * s
        return x

    k = gathered(k_pool, k_scale)
    if values_from_k is not None:
        v = k[..., :values_from_k]  # MLA: values are the latent prefix of k
    else:
        v = gathered(v_pool, v_scale)
    qg = q.reshape(m, hkv, g, dk)
    scores = jnp.einsum(
        "mhgd,mshd->mhgs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if logit_softcap is not None:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)
    k_pos = jnp.arange(spg * page_size)[None, :]  # (1, S_virt)
    allowed = k_pos < lengths[:, None]
    if sliding_window is not None:
        # the single query sits at position lengths-1
        allowed &= k_pos > (lengths[:, None] - 1) - sliding_window
    allowed = allowed[:, None, None, :]
    if lead_rows:  # (M, 1, G, S_virt): a group's leading rows see less
        allowed = jnp.where(
            (jnp.arange(g) < lead_rows)[None, None, :, None],
            (k_pos < lead_lengths[:, None])[:, None, None, :], allowed,
        )
    scores = jnp.where(allowed, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # an all-masked row (length 0, an inactive slot) softmaxes to uniform
    # garbage, not zeros — clamp it so the contract matches the kernel
    probs = probs * allowed
    out = jnp.einsum(
        "mhgs,mshd->mhgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(m, hq, -1).astype(q.dtype)


def paged_attention(
    q: jax.Array,  # (M, Hq, Dk) — one query token per slot
    k_pool: jax.Array,  # (P+1, page, Hkv, Dk) — one layer's pool, scratch last
    v_pool: jax.Array,  # (P+1, page, Hkv, Dv)
    tables: jax.Array,  # (M, SPG) int32 pool-page ids (past length: never read)
    lengths: jax.Array,  # (M,) int32 — valid positions incl. the new token
    scale: float,
    *,
    logit_softcap: Optional[float] = None,
    sliding_window=None,  # int (either path) or traced scalar (XLA)
    values_from_k: Optional[int] = None,  # MLA latent-as-values
    k_scale: Optional[jax.Array] = None,  # (P+1, page, Hkv, 1) int8-pool scales
    v_scale: Optional[jax.Array] = None,
    kv_heads: Optional[int] = None,  # pools are (P+1, page, 1, Hkv * D)
    lead_lengths: Optional[jax.Array] = None,  # (M,) int32, <= lengths
    lead_rows: int = 0,  # leading rows of a query group under lead_lengths
    interpret: bool = False,
) -> jax.Array:
    """Ragged decode attention over one layer's page pool. Returns
    (M, Hq, Dv). Row m attends to positions 0..lengths[m] of its own pages;
    lengths[m] == 0 (an inactive slot) yields zeros. The new token's K/V
    must already be written into the pool (the engine scatters the single
    row before calling this). With ``k_scale``/``v_scale`` the pools are
    int8 codes and dequant (code × per-row-per-head scale) fuses into the
    page reads — both paths stream the int8 bytes, never a dense bf16 copy
    of the pages. ``kv_heads``: the pools keep their heads MERGED on the
    lane axis, ``(P+1, page, 1, Hkv * D)`` — the layout of the kernel's
    page block. On a TPU an array is tiled over its two minor dimensions,
    so the ``(page, Hkv, D)`` pool's view as ``(page, Hkv * D)`` blocks is
    a relayout of the whole pool, every call, once ``Hkv > 1``; a model
    that stores its rows merged pays none (bf16 pools only).
    ``lead_lengths`` with ``lead_rows``: the first ``lead_rows`` query rows
    of every K/V head's group see keys ``[0, lead_lengths[m])`` and the rest
    ``[0, lengths[m])`` — two blocks of a sequence in one call, the earlier
    blind to the later's rows (``parallel/pipeline.py``: the queries are
    folded into the group by block, then by query, then by head). The page
    walk follows ``lengths``, the longer; a row bounded at 0 yields zeros.
    No window beside it."""
    dk, dv = q.shape[-1], v_pool.shape[-1]
    hkv = k_pool.shape[2]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if kv_heads is not None:
        if k_scale is not None or values_from_k is not None or hkv != 1:
            raise ValueError(
                "kv_heads wants bf16 (P+1, page, 1, Hkv * D) pools, no "
                "int8 scales and no values_from_k"
            )
        hkv, dv = kv_heads, dv // kv_heads
    if bool(lead_rows) != (lead_lengths is not None) or (
        lead_rows and sliding_window is not None
    ):
        raise ValueError(
            "lead_lengths and lead_rows (above 0) go together, and without "
            "a sliding_window"
        )
    if kernel_eligible(
        dk, dv, logit_softcap, sliding_window, values_from_k, interpret,
        hkv=hkv,
    ):
        _count_dispatch("kernel")
        return _paged_attention_kernel(
            q, k_pool, v_pool, tables, lengths, scale, interpret,
            k_scale, v_scale, values_from_k, sliding_window, kv_heads,
            lead_lengths, lead_rows,
        )
    _count_dispatch("xla")
    return _paged_attention_xla(
        q, k_pool, v_pool, tables, lengths, scale,
        logit_softcap, sliding_window, values_from_k, k_scale, v_scale,
        kv_heads, lead_lengths, lead_rows,
    )
