"""Ragged paged-attention parity matrix (ISSUE 1 tentpole). Op level: the
Pallas kernel (interpret mode) and the fused-XLA fallback must both match a
straight-line numpy reference over uneven lengths, page-boundary offsets,
empty slots, and GQA/MQA head layouts; the kernel in MLA's latent layout
(values = the first lanes of the key rows, no V operand) must match the
fallback at the published widths. Engine level: a mixed-length
continuous-batching run on the ragged path must be token-exact vs the
gather path and vs the serial generator, for a GQA model and for a
compressed-MLA one whose ragged body goes through the kernel."""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.cache import quantize_kv_rows
from mlx_sharding_tpu.config import DeepseekV2Config, LlamaConfig
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models.deepseek_v2 import DeepseekV2Model
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.ops import paged_attention as paged_ops
from mlx_sharding_tpu.ops.paged_attention import (
    _paged_attention_xla,
    kernel_eligible,
    paged_attention,
)
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher

PAGE = 8
SPG = 4  # slot pages — virtual max of 32 positions per slot


def _own_pages(lengths, page, spg, claimed=False):
    """A page table as the scheduler lays a row out (``_table_row``): slot
    ``i`` owns the distinct pages ``i * spg + j``; past them the row holds
    the scratch page (last pool id). ``claimed=False``: a slot owns the
    pages of its live prefix alone, as ``--overcommit`` admission and an
    idle slot leave a row. ``claimed=True``: the default RESERVE admission,
    which claims a stream's whole prompt + max_tokens need at its first
    token — distinct real ids PAST the length (every other slot's claim one
    page short of the row, so the row is wider than the claim)."""
    n_pages = len(lengths) * spg
    tables = np.full((len(lengths), spg), n_pages, np.int32)
    for i, ln in enumerate(lengths):
        used = max(spg - i % 2, -(-ln // page)) if claimed else -(-ln // page)
        tables[i, :used] = np.arange(i * spg, i * spg + used)
    return tables


def _poison(lengths, page, spg, *pools):
    """NaN in every page no query may read: a slot's pages wholly past its
    length (claimed, not yet written) and the scratch page."""
    for pool in pools:
        pool[-1] = np.nan
        for i, ln in enumerate(lengths):
            pool[i * spg + -(-ln // page):(i + 1) * spg] = np.nan


def _numbers(*pools):
    """The pools as the XLA fallback may be handed them: it gathers a slot's
    whole row and weights a dead row by a probability of exactly 0, which
    only a finite value survives — a dead page holds 1e30 where the
    kernel's holds NaN."""
    return [jax.tree.map(lambda x: jnp.nan_to_num(x, nan=1e30), p) for p in pools]


TAILS = pytest.mark.parametrize(
    "claimed", [False, True], ids=["scratch-tail", "claimed-tail"]
)


def _make_case(rng, lengths, hq, hkv, dk, dv, claimed=False):
    """Build a pool where each slot owns distinct pages (``_own_pages``);
    ``claimed``: the rows name claimed pages past the lengths, and those
    pages and the scratch page hold NaN. Returns arrays plus a dense
    per-slot (S, Hkv, D) view for the reference."""
    m = len(lengths)
    n_pages = m * SPG
    k_pool = rng.standard_normal((n_pages + 1, PAGE, hkv, dk), np.float32)
    v_pool = rng.standard_normal((n_pages + 1, PAGE, hkv, dv), np.float32)
    if claimed:
        _poison(lengths, PAGE, SPG, k_pool, v_pool)
    tables = _own_pages(lengths, PAGE, SPG, claimed)
    q = rng.standard_normal((m, hq, dk), np.float32)
    own = np.arange(n_pages).reshape(m, SPG)
    dense_k = k_pool[own].reshape(m, SPG * PAGE, hkv, dk)
    dense_v = v_pool[own].reshape(m, SPG * PAGE, hkv, dv)
    return q, k_pool, v_pool, tables, dense_k, dense_v


def _same_as_scratch_tail(got, attend, lengths, page=PAGE, spg=SPG):
    """What lies in a row past the length changes no bit of the result:
    ``attend(tables)`` over the scratch-tail table equals ``got``."""
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(attend(jnp.asarray(_own_pages(lengths, page, spg)))),
    )


def _ref(q, dense_k, dense_v, lengths, scale, window=None):
    """Per-slot numpy softmax attention over the first length rows (the
    last ``window`` of them, with a window)."""
    m, hq, dk = q.shape
    hkv, dv = dense_k.shape[2], dense_v.shape[3]
    g = hq // hkv
    out = np.zeros((m, hq, dv), np.float32)
    for i, ln in enumerate(lengths):
        if ln == 0:
            continue  # inactive slot: contract is zeros
        lo = 0 if window is None else max(0, ln - window)
        for h in range(hq):
            k = dense_k[i, lo:ln, h // g]  # (ln - lo, dk)
            v = dense_v[i, lo:ln, h // g]
            s = (k @ q[i, h]) * scale
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ v
    return out


# lengths hit: mid-page, exact one-page boundary, exact two-page boundary,
# empty slot between live ones, uneven multi-page, completely full slot,
# empty slot last
LENGTHS = [5, PAGE, 2 * PAGE, 0, 27, SPG * PAGE, 0]


@pytest.mark.parametrize(
    "hq,hkv", [(4, 4), (4, 2), (4, 1)], ids=["mha", "gqa", "mqa"]
)
@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
# no window; shorter than most lengths with its edge inside a page (5, 11),
# a whole page, equal to the longest length, longer than every length
@pytest.mark.parametrize(
    "window", [None, 5, PAGE, 11, SPG * PAGE, 100],
    ids=["full", "w5", "w-page", "w11", "w-longest", "w-longer"],
)
@TAILS
def test_op_parity_matrix(hq, hkv, interpret, window, claimed):
    rng = np.random.default_rng(0)
    dk = dv = 16
    scale = dk ** -0.5
    q, k_pool, v_pool, tables, dense_k, dense_v = _make_case(
        rng, LENGTHS, hq, hkv, dk, dv, claimed
    )
    want = _ref(q, dense_k, dense_v, LENGTHS, scale, window)
    pools = [jnp.asarray(k_pool), jnp.asarray(v_pool)]
    if not interpret:
        pools = _numbers(*pools)

    def attend(tables):
        return paged_attention(
            jnp.asarray(q), *pools, tables, jnp.asarray(LENGTHS, jnp.int32),
            scale, sliding_window=window, interpret=interpret,
        )

    got = attend(jnp.asarray(tables))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[np.array(LENGTHS) == 0].any()  # the empty slots
    if claimed:
        _same_as_scratch_tail(got, attend, LENGTHS)


@pytest.mark.parametrize("merged", [False, True], ids=["heads-apart", "heads-merged"])
@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
@TAILS
def test_thirty_heads_at_a_query_group_of_one(interpret, merged, claimed):
    """Multi-head attention as ``olmo_hybrid`` serves it: 30 K/V heads under
    30 queries, a row's heads apart or merged on the lane axis, uneven
    lengths (mid-page, page borders, an empty slot, a full one): the body's
    walk over heads at one query row each (scratch ``(30, 1, 128)``) gives
    the numbers of the per-head reference."""
    rng = np.random.default_rng(4)
    hq = hkv = 30
    dk = dv = 16
    scale = dk ** -0.5
    q, k_pool, v_pool, tables, dense_k, dense_v = _make_case(
        rng, LENGTHS, hq, hkv, dk, dv, claimed
    )
    want = _ref(q, dense_k, dense_v, LENGTHS, scale)
    pools = [jnp.asarray(x) for x in (k_pool, v_pool)]
    if not interpret:
        pools = _numbers(*pools)
    layout = {}
    if merged:
        pools = [x.reshape(*x.shape[:2], 1, -1) for x in pools]
        layout = dict(kv_heads=hkv)

    def attend(tables):
        return paged_attention(
            jnp.asarray(q), *pools, tables, jnp.asarray(LENGTHS, jnp.int32),
            scale, interpret=interpret, **layout,
        )

    got = attend(jnp.asarray(tables))
    assert got.shape == (len(LENGTHS), hq, dv)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    assert not np.asarray(got)[np.array(LENGTHS) == 0].any()  # the empty slots
    if claimed:
        _same_as_scratch_tail(got, attend, LENGTHS)


# (lengths, lead lengths) of a call with two bounds a slot: the leading rows of
# every K/V head's query group see ``[0, lead)``, the rest ``[0, length)`` —
# a wide forward of a family that generates by diffusion over blocks of 4
# (``parallel/pipeline.py``): lane 1 a block whose K/V is being committed,
# lane 2 the next block, denoising
TWO_LENGTHS = {
    # both lanes live, a block apart, mid-page and across pages
    "uneven": ([9 + 4, 20 + 4, 31 + 1, 4 + 4], [9, 20, 31, 4]),
    # lane 1 ends on a page border, lane 2 lies in the next page
    "straddles-a-page": ([PAGE + 4, 2 * PAGE + 4, 3 * PAGE + 4], [PAGE, 2 * PAGE, 3 * PAGE]),
    # lane 1 sees nothing (a bound of 0 beside a live length): zeros
    "lane-1-inactive": ([4, 12, 27], [0, 0, 0]),
    # lane 2 is not computed: both bounds are lane 1's
    "lane-2-inactive": ([8, 13, SPG * PAGE], [8, 13, SPG * PAGE]),
    # empty slots between live ones, and last
    "length-0": ([12, 0, 0, 24, 0], [8, 0, 0, 20, 0]),
}


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("merged", [False, True], ids=["heads-apart", "heads-merged"])
@pytest.mark.parametrize("case", list(TWO_LENGTHS))
@TAILS
def test_two_lengths_a_query_group(case, merged, interpret, claimed):
    """``lead_lengths`` / ``lead_rows``: both paths against plain attention
    a row, each row under the bound of its place in its group (4 K/V heads'
    groups of 2 lanes x 2 queries x 2 heads: the leading 4 rows are lane 1);
    rows bounded at 0 are zeros whatever the slot's other bound."""
    lengths, lead = TWO_LENGTHS[case]
    rng = np.random.default_rng(4)
    hkv, g, lead_rows, d = 2, 8, 4, 16
    q, k_pool, v_pool, tables, dense_k, dense_v = _make_case(
        rng, lengths, hkv * g, hkv, d, d, claimed
    )
    first = (np.arange(hkv * g) % g) < lead_rows  # (Hq,): a lane-1 row
    want = np.where(
        first[None, :, None],
        _ref(q, dense_k, dense_v, lead, d ** -0.5),
        _ref(q, dense_k, dense_v, lengths, d ** -0.5),
    )
    pools = [jnp.asarray(x) for x in (k_pool, v_pool)]
    if not interpret:
        pools = _numbers(*pools)
    if merged:
        pools = [x.reshape(*x.shape[:2], 1, -1) for x in pools]

    def attend(tables):
        return paged_attention(
            jnp.asarray(q), *pools, tables,
            jnp.asarray(lengths, jnp.int32), d ** -0.5,
            kv_heads=hkv if merged else None,
            lead_lengths=jnp.asarray(lead, jnp.int32), lead_rows=lead_rows,
            interpret=interpret,
        )

    got = attend(jnp.asarray(tables))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)
    for i, (ln, ld) in enumerate(zip(lengths, lead)):
        assert ld or not np.asarray(got)[i][first].any()
        assert ln or not np.asarray(got)[i].any()
    if claimed:
        _same_as_scratch_tail(got, attend, lengths)


def test_two_lengths_go_together_and_without_a_window():
    q, k, v = jnp.zeros((1, 4, 16)), jnp.zeros((5, PAGE, 2, 16)), jnp.zeros((5, PAGE, 2, 16))
    tables, lens = jnp.zeros((1, SPG), jnp.int32), jnp.ones((1,), jnp.int32)
    for kw in ({"lead_rows": 2}, {"lead_lengths": lens},
               {"lead_rows": 2, "lead_lengths": lens, "sliding_window": 4}):
        with pytest.raises(ValueError, match="lead_lengths and lead_rows"):
            paged_attention(q, k, v, tables, lens, 0.25, **kw)


# (ring pages R, window, lengths): position p of slot m lives at ring page
# m * R + (p // PAGE) % R, as the engine lays a window layer out; the table
# is the ring repeated over the slot's logical pages. Lengths that wrap the
# ring more than twice, a window edge inside a page, an empty slot.
RINGS = {
    "wrapped-twice": (3, 12, [70, 0, 53, 24, 9]),
    "window-is-a-page": (3, PAGE, [64, 0, 17, 8, 3]),
    "never-wrapped": (4, 20, [32, 0, 21, 8, 1]),
}


@pytest.mark.parametrize("case", list(RINGS))
@pytest.mark.parametrize("merged", [False, True], ids=["heads-apart", "heads-merged"])
@pytest.mark.parametrize("hq,hkv", [(6, 1), (12, 2)], ids=["gqa6", "gqa6x2"])
@TAILS
def test_window_kernel_over_a_ring_matches_xla(case, hq, hkv, merged, claimed):
    """The kernel (interpret mode) with a window over a RING table equals
    the fallback over the same table, and both equal plain attention over
    the last ``window`` positions of a dense history: a ring page that has
    been overwritten holds positions no query can see. ``merged``: the
    pools keep a row's heads on the lane axis, ``(pages, page, 1, Hkv *
    D)``, and ``kv_heads`` says how many. ``claimed``: the ring pages no
    position has reached yet, and the scratch page, hold NaN."""
    ring, window, lengths = RINGS[case]
    rng = np.random.default_rng(5)
    m, d, spg = len(lengths), 16, 9
    assert ring * PAGE >= window + PAGE  # what the engine guarantees
    hist_k = rng.standard_normal((m, spg * PAGE, hkv, d), np.float32)
    hist_v = rng.standard_normal((m, spg * PAGE, hkv, d), np.float32)
    k_pool = np.zeros((m * ring + 1, PAGE, hkv, d), np.float32)
    v_pool = np.zeros_like(k_pool)
    if claimed:
        for pool in (k_pool, v_pool):
            pool[-1] = np.nan
            for i, ln in enumerate(lengths):
                pool[i * ring + -(-ln // PAGE):(i + 1) * ring] = np.nan
    for i, ln in enumerate(lengths):
        for p in range(ln):  # later positions overwrite earlier ones
            page = i * ring + (p // PAGE) % ring
            k_pool[page, p % PAGE] = hist_k[i, p]
            v_pool[page, p % PAGE] = hist_v[i, p]
    tables = (np.arange(m)[:, None] * ring + np.arange(spg)[None, :] % ring)
    q = rng.standard_normal((m, hq, d), np.float32)
    scale = d ** -0.5
    layout = {}
    if merged:
        k_pool, v_pool = (x.reshape(-1, PAGE, 1, hkv * d) for x in (k_pool, v_pool))
        layout = {"kv_heads": hkv}
    args = (
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32), scale,
    )
    before = paged_ops.dispatch_counts()
    got = paged_attention(*args, sliding_window=window, interpret=True, **layout)
    assert paged_ops.dispatch_counts()["kernel"] == before["kernel"] + 1
    xla = _paged_attention_xla(
        args[0], *_numbers(*args[1:3]), *args[3:], None, window, None, **layout
    )
    want = _ref(q, hist_k, hist_v, lengths, scale, window)
    np.testing.assert_allclose(np.asarray(xla), want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_op_parity_uneven_head_dims_xla():
    """dv != dk (MLA-shaped) rides the XLA path on CPU."""
    rng = np.random.default_rng(1)
    lengths = [3, 11, 0]
    q, k_pool, v_pool, tables, dense_k, dense_v = _make_case(
        rng, lengths, hq=2, hkv=2, dk=24, dv=12
    )
    scale = 24 ** -0.5
    want = _ref(q, dense_k, dense_v, lengths, scale)
    got = paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), scale,
    )
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_op_softcap_and_traced_window_stay_xla():
    """Softcap and a window that is a traced scalar force the fallback
    (kernel_eligible says no; a window known at trace time is the
    kernel's) and the window semantics match a masked reference."""
    assert not kernel_eligible(64, 64, 30.0, None, None, interpret=True)
    assert kernel_eligible(64, 64, None, 4, None, interpret=True)
    assert not kernel_eligible(64, 64, None, jnp.asarray(4), None, interpret=True)
    rng = np.random.default_rng(2)
    lengths = [13, 7]
    window = 4
    q, k_pool, v_pool, tables, dense_k, dense_v = _make_case(
        rng, lengths, hq=2, hkv=1, dk=16, dv=16
    )
    scale = 0.25
    # reference: only the last `window` positions before the query survive
    clipped = []
    for i, ln in enumerate(lengths):
        lo = max(0, ln - window)
        dk_i = np.zeros_like(dense_k[i])
        dk_i[lo:ln] = dense_k[i, lo:ln]
        clipped.append((lo, ln))
    want = np.zeros((2, 2, 16), np.float32)
    for i, (lo, ln) in enumerate(clipped):
        for h in range(2):
            k = dense_k[i, lo:ln, 0]
            v = dense_v[i, lo:ln, 0]
            s = (k @ q[i, h]) * scale
            p = np.exp(s - s.max())
            want[i, h] = (p / p.sum()) @ v
    got = paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), scale,
        sliding_window=window,
    )
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- latent ---


def _latent_case(rng, lengths, hq, hkv, dk, page, spg, dtype, claimed=False):
    """An MLA-shaped pool: ``hkv`` latent heads of width ``dk`` in K, the
    dummy ``(…, 1, 1)`` V the compressed cache mode allocates; ``claimed``
    as in ``_make_case``."""
    m = len(lengths)
    n_pages = m * spg
    k_pool = rng.standard_normal((n_pages + 1, page, hkv, dk), np.float32)
    if claimed:
        _poison(lengths, page, spg, k_pool)
    q = rng.standard_normal((m, hq, dk), np.float32)
    return (
        jnp.asarray(q, dtype), jnp.asarray(k_pool, dtype),
        jnp.zeros((n_pages + 1, page, 1, 1), dtype),
        jnp.asarray(_own_pages(lengths, page, spg, claimed)),
        jnp.asarray(lengths, jnp.int32),
    )


# lengths: empty, one row, a page boundary, mid third page / 770 (the
# cell's longest cache), a full table row, empty again
LATENT = {
    # DeepSeek-V2-Lite's latent head as published: 16 query heads on one
    # latent head, 512 + 64 rope lanes, values the first 512, 256-token pages
    "published-f32": (16, 1, 576, 512, 256, 4, [0, 1, 256, 770, 1024, 0], jnp.float32, 1e-5),
    "published-bf16": (16, 1, 576, 512, 256, 4, [0, 1, 256, 770, 1024, 0], jnp.bfloat16, 2e-2),
    "small": (4, 1, 24, 16, 8, 4, [0, 1, 8, 19, 32, 0], jnp.float32, 1e-5),
    "small-two-heads": (4, 2, 24, 16, 8, 4, [0, 1, 8, 19, 32, 0], jnp.float32, 1e-5),
}


@pytest.mark.parametrize("int8", [False, True], ids=["pool", "int8-pool"])
@pytest.mark.parametrize("case", list(LATENT))
@TAILS
def test_latent_kernel_matches_xla(case, int8, claimed):
    """``values_from_k``: the kernel (interpret mode) takes its value block
    from the key block's first lanes and is handed no V pool; it must equal
    the fallback, which slices the gathered keys. int8 pools take the same
    kernel: the key block is scaled, then sliced (``claimed``: a dead
    page's codes are whatever NaN casts to, its scales NaN)."""
    hq, hkv, dk, vfk, page, spg, lengths, dtype, tol = LATENT[case]
    q, k_pool, v_pool, tables, lens = _latent_case(
        np.random.default_rng(3), lengths, hq, hkv, dk, page, spg, dtype,
        claimed,
    )
    ks = vs = None
    if int8:
        kq, vq = quantize_kv_rows(k_pool), quantize_kv_rows(v_pool)
        k_pool, ks, v_pool, vs = kq["d"], kq["s"], vq["d"], vq["s"]
        assert not claimed or np.isnan(np.asarray(ks[-1])).all()
    scale = dk ** -0.5

    def attend(tables):
        return paged_attention(
            q, k_pool, v_pool, tables, lens, scale, values_from_k=vfk,
            k_scale=ks, v_scale=vs, interpret=True,
        )

    before = paged_ops.dispatch_counts()
    got = attend(tables)
    after = paged_ops.dispatch_counts()
    assert after == {**before, "kernel": before["kernel"] + 1}
    want = _paged_attention_xla(
        q, *_numbers(k_pool, v_pool), tables, lens, scale, None, None, vfk,
        *_numbers(ks, vs),
    )
    assert got.shape == want.shape == (len(lengths), hq, vfk)
    if claimed:
        _same_as_scratch_tail(got, attend, lengths, page, spg)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    assert not got[[0, -1]].any()  # the empty slots: zeros, on both paths
    assert np.abs(want[1:-1]).max() > 0.5


# (hq, hkv, dk, dv, values_from_k, int8): every layer's pool in ONE array
POOLED = {
    "latent": (4, 1, 24, 1, 16, False),
    "latent-int8": (4, 1, 24, 1, 16, True),
    "gqa-heads-apart": (4, 2, 16, 16, None, False),
    "gqa-heads-apart-int8": (4, 2, 16, 16, None, True),
}


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("case", list(POOLED))
@TAILS
def test_whole_pool_as_pages_with_a_traced_layer_offset(case, interpret, claimed):
    """The ragged decode body's form: an ``(L, P+1, page, H, D)`` pool viewed
    as ``L * (P+1)`` pages, layer ``l`` the page table offset by a TRACED
    ``l * (P+1)`` (a scan index), equals the call on that layer's own slice
    with the table as it is — a slot at a page boundary, an empty slot,
    int8 ``{d, s}`` pools viewed leaf by leaf; ``claimed``: every layer's
    claimed pages and scratch page hold NaN (an int8 pool's scales)."""
    hq, hkv, dk, dv, vfk, int8 = POOLED[case]
    layers, lengths = 3, [0, 1, PAGE, 19, SPG * PAGE]
    m = len(lengths)
    n_pages = m * SPG + 1
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((m, hq, dk), np.float32))
    k = rng.standard_normal((layers, n_pages, PAGE, hkv, dk), np.float32)
    v = (
        np.zeros((layers, n_pages, PAGE, 1, 1), np.float32) if vfk
        else rng.standard_normal((layers, n_pages, PAGE, hkv, dv), np.float32)
    )
    if claimed:
        _poison(lengths, PAGE, SPG, *k, *([] if vfk else v))
    k, v = jnp.asarray(k), jnp.asarray(v)
    if int8:
        k, v = quantize_kv_rows(k), quantize_kv_rows(v)
    if not interpret:
        k, v = _numbers(k, v)
    tables = jnp.asarray(_own_pages(lengths, PAGE, SPG, claimed))
    lens = jnp.asarray(lengths, jnp.int32)

    def attend(k, v, tables):
        scales = dict(k_scale=k["s"], v_scale=v["s"]) if int8 else {}
        return paged_attention(
            q, k["d"] if int8 else k, v["d"] if int8 else v, tables, lens,
            dk ** -0.5, values_from_k=vfk, interpret=interpret, **scales,
        )

    as_pages = lambda x: x.reshape(-1, *x.shape[2:])  # noqa: E731
    kp, vp = jax.tree.map(as_pages, (k, v))
    got = jax.jit(lambda: jax.lax.map(
        lambda l: attend(kp, vp, tables + l * n_pages), jnp.arange(layers)
    ))()
    assert got.shape == (layers, m, hq, vfk or dv)
    for l in range(layers):
        want = attend(*jax.tree.map(lambda x: x[l], (k, v)), tables)
        np.testing.assert_allclose(
            np.asarray(got[l]), np.asarray(want), atol=1e-6, rtol=1e-6
        )
        assert not np.asarray(got[l])[0].any()  # the empty slot
    assert np.abs(np.asarray(got[0]) - np.asarray(got[1])).max() > 0.1
    if claimed:
        _same_as_scratch_tail(
            got[0], lambda t: attend(*jax.tree.map(lambda x: x[0], (k, v)), t),
            lengths,
        )


# ------------------------------------------------------------ the walk ---


def _replay_walk(tables, lengths, page, window=None):
    """The grid of one call replayed on the host under the pipeline's rule:
    a step fetches where its block index differs from the step's before it,
    and that fetch is issued when the step before it starts. Returns the
    steps that fetch, and by step its slot and whether it has arithmetic
    (the body's ``live``)."""
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    mi, ji = np.divmod(np.arange(tables.size), tables.shape[1])
    pages = np.asarray(paged_ops.walk_page(
        mi, ji, tables, lengths, page_size=page, window=window
    ))
    live = ji * page < lengths[mi]
    if window is not None:
        live &= (ji + 1) * page > lengths[mi] - window
    fetches = [0] + [k for k in range(1, len(mi)) if pages[k] != pages[k - 1]]
    return fetches, mi, live, pages


# (table, window, lengths): rows whose tails are CLAIMED pages, every id
# distinct; lengths mid-page, on a page border, 0 (between live slots, twice
# in a row, first and last) and full
WALKS = {
    "plain": ("plain", None, [5, PAGE, 0, 27, SPG * PAGE, 0, 0, 2 * PAGE + 1, 0]),
    "plain-empty-first": ("plain", None, [0, 12, SPG * PAGE, 1]),
    "plain-last-slot-short": ("plain", None, [SPG * PAGE, 3]),
    "window": ("plain", 11, [5, PAGE, 0, 27, SPG * PAGE, 0, 2 * PAGE + 1]),
    "window-is-a-page": ("plain", PAGE, [30, 0, 0, 17, PAGE, 3]),
    "ring": ("ring", 12, [70, 0, 53, 24, 9, 0]),
    "ring-never-wrapped": ("ring", 20, [32, 0, 21, 8, 1]),
}


@pytest.mark.parametrize("case", list(WALKS))
def test_walk_fetches_the_pages_a_slot_holds_under_arithmetic(case):
    """What no parity test can see (a dead step computes nothing, whatever
    it fetched): (a) a call fetches ``max(1, live pages)`` blocks a slot —
    not the pages its row names past the length; (b) every fetch but the
    call's first and the one issued from an EMPTY slot's single step is
    issued from a step with arithmetic to hide it under: a step past the
    length names the next slot's first visible page, which that slot then
    finds in the buffer. The walk that named its table row failed (a) on
    claimed tails; one that clamps a dead step to the slot's OWN last page
    fails (b) once a slot."""
    kind, window, lengths = WALKS[case]
    m, spg = len(lengths), (9 if kind == "ring" else SPG)
    if kind == "ring":
        ring = 4
        tables = np.arange(m)[:, None] * ring + np.arange(spg)[None, :] % ring
    else:
        tables = np.arange(m * spg).reshape(m, spg)  # the whole row claimed
    fetches, slot, live, pages = _replay_walk(tables, lengths, PAGE, window)
    live_pages = np.bincount(slot[live], minlength=m)
    assert len(fetches) == np.maximum(live_pages, 1).sum()
    # and they are each slot's visible pages in turn, an empty slot's entry 0
    visible = [
        tables[i, j]
        for i, ln in enumerate(lengths)
        for j in range(
            max(ln - (window or ln), 0) // PAGE, max(ln - 1, 0) // PAGE + 1
        )
    ]
    assert pages[fetches].tolist() == visible
    for k in fetches[1:]:
        issued_from = k - 1
        assert live[issued_from] or lengths[slot[issued_from]] == 0, (
            f"step {k}'s fetch is issued from step {issued_from}, which "
            f"computes nothing (slot {slot[issued_from]})"
        )


def test_walk_replay_tells_the_old_walks_apart(monkeypatch):
    """The replay itself, held against the two walks it was written to
    catch: the table row as it stands (every claimed page fetched) and the
    backward clamp (a dead step repeats its slot's own last page: the next
    slot's first page is then issued from a step with no arithmetic)."""
    lengths = [5, PAGE, 17, SPG * PAGE, 3]
    m = len(lengths)
    tables = np.arange(m * SPG).reshape(m, SPG)

    def row_as_it_stands(mi, ji, t, ln, **_):
        return t[mi, ji]

    def backward(mi, ji, t, ln, *, page_size, **_):
        return t[mi, np.minimum(ji, np.maximum(ln[mi] - 1, 0) // page_size)]

    must = sum(-(-ln // PAGE) for ln in lengths)
    monkeypatch.setattr(paged_ops, "walk_page", row_as_it_stands)
    assert len(_replay_walk(tables, lengths, PAGE)[0]) == m * SPG > must
    monkeypatch.setattr(paged_ops, "walk_page", backward)
    fetches, slot, live, _ = _replay_walk(tables, lengths, PAGE)
    assert len(fetches) == must
    from_dead = [k for k in fetches[1:] if not live[k - 1]]
    assert [int(slot[k]) for k in from_dead] == [1, 2, 3]  # behind each short slot


# (dk, dv, softcap, window, values_from_k, hkv) -> the kernel on a chip?
ELIGIBLE = {
    "gqa-128": ((128, 128, None, None, None, 8), True),
    "gqa-dv-1": ((576, 1, None, None, None, 1), False),
    "latent-published": ((576, 1, None, None, 512, 1), True),
    "latent-whole-key": ((512, 1, None, None, 512, 1), True),
    "latent-two-heads-aligned": ((256, 1, None, None, 128, 2), True),
    "latent-two-heads-unaligned-dk": ((576, 1, None, None, 512, 2), False),
    "latent-values-unaligned": ((576, 1, None, None, 500, 1), False),
    "latent-values-wider-than-key": ((576, 1, None, None, 640, 1), False),
    "latent-dk-unaligned": ((552, 1, None, None, 512, 1), False),
    "latent-softcap": ((576, 1, 30.0, None, 512, 1), False),
    "latent-window": ((576, 1, None, 128, 512, 1), True),
    "gqa-window": ((128, 128, None, 4096, None, 8), True),
    "gqa-window-softcap": ((128, 128, 50.0, 4096, None, 8), False),
    "mha-30-heads-group-1": ((128, 128, None, None, None, 30), True),
}


@pytest.mark.parametrize("case", list(ELIGIBLE))
def test_kernel_eligible_table(case, monkeypatch):
    """The predicate on a chip: the latent layout is admitted where its
    lane slices are tile-aligned; a window known at trace time is the
    kernel's in either layout, softcap stays on the XLA path. An int8 pool changes nothing in it (the case above
    runs int8 + latent through the kernel)."""
    (dk, dv, softcap, window, vfk, hkv), want = ELIGIBLE[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernel_eligible(
        dk, dv, softcap, window, vfk, interpret=False, hkv=hkv
    ) is want
    # off the chip nothing reaches the kernel but in interpret mode
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not kernel_eligible(
        dk, dv, softcap, window, vfk, interpret=False, hkv=hkv
    )


def test_dispatch_is_counted_once_a_traced_call_and_shown_on_metrics():
    """``mst_paged_attention_dispatch_total{path}`` counts where
    ``paged_attention`` chooses: once per traced call, not once per run of
    the compiled program. Off the chip that is the XLA path."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    args = _latent_case(
        np.random.default_rng(4), [3, 0, 9], 4, 1, 24, 8, 4, jnp.float32
    )
    fn = jax.jit(functools.partial(paged_attention, scale=0.2, values_from_k=16))
    before = paged_ops.dispatch_counts()
    for _ in range(3):
        fn(*args).block_until_ready()
    after = paged_ops.dispatch_counts()
    assert after == {**before, "xla": before["xla"] + 1}
    text = ServingMetrics().render()
    assert "# TYPE mst_paged_attention_dispatch_total counter" in text
    assert "# HELP mst_paged_attention_dispatch_total" in text
    for path, n in after.items():
        assert f'mst_paged_attention_dispatch_total{{path="{path}"}} {n}' in text


# ---------------------------------------------------------------- engine ---

TINY = dict(
    vocab_size=300,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
)


def _make_engine(paged_attention):
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
        pool_pages=10, page_size=8, paged_attention=paged_attention,
    )
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    return eng, ref


def _concurrent(batcher, jobs):
    results = [None] * len(jobs)

    def work(i, prompt, kw):
        results[i] = [t for t, _ in batcher.generate_step(prompt, **kw)]

    threads = [
        threading.Thread(target=work, args=(i, p, kw))
        for i, (p, kw) in enumerate(jobs)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(r is not None for r in results)
    return results


def test_engine_auto_resolves_ragged():
    eng, _ = _make_engine("auto")
    assert eng.paged_attention == "ragged"


def test_engine_ragged_requires_supported_wiring():
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    with pytest.raises(ValueError, match="pp=1"):
        PipelineEngine(
            model, params, pipeline_mesh(2), microbatches=2, max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
            pool_pages=10, page_size=8, paged_attention="ragged",
        )


def test_engine_mixed_length_cb_parity_ragged_vs_gather():
    """The acceptance criterion: identical token streams from the ragged and
    gather paths on a mixed-length concurrent run, both matching the serial
    generator. Lengths straddle page boundaries on purpose."""
    rng = np.random.default_rng(7)
    jobs = []
    for i, plen in enumerate([3, 8, 13, 17]):  # mid/boundary/multi-page
        prompt = [int(t) for t in rng.integers(1, 300, size=plen)]
        jobs.append(
            (prompt, dict(max_tokens=int(6 + 3 * i), seed=i, temperature=0.5))
        )

    streams = {}
    for path in ("ragged", "gather"):
        eng, ref = _make_engine(path)
        assert eng.paged_attention == path
        batcher = ContinuousBatcher(eng, decode_block=3)
        try:
            streams[path] = _concurrent(batcher, jobs)
            stats = batcher.kv_read_stats()
            assert stats is not None and stats[0] == path
            assert stats[2] > 0  # bytes-read accounting registered ticks
        finally:
            batcher.close()
        if path == "ragged":
            want = [
                [t for t, _ in ref.generate_step(p, **kw)] for p, kw in jobs
            ]
            assert streams[path] == want

    assert streams["ragged"] == streams["gather"]


DSV2_TINY = dict(
    vocab_size=300, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
    q_lora_rank=None, qk_rope_head_dim=8, qk_nope_head_dim=16,
    v_head_dim=12, n_routed_experts=4, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1,
    mla_cache_mode="compressed",
)


def test_engine_mixed_length_cb_parity_latent_kernel_vs_gather(monkeypatch):
    """The twin of the test above for a compressed-MLA model: the served
    ragged body, its attention through the KERNEL (interpret mode: the
    engine's calls are handed ``interpret=True``), gives the token streams
    of the gather body and of the serial generator. Every ragged-attention
    call of the run is a latent one and none takes the XLA path."""
    monkeypatch.setattr(
        paged_ops, "paged_attention",
        functools.partial(paged_attention, interpret=True),
    )
    model = DeepseekV2Model(DeepseekV2Config(**DSV2_TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(11)
    jobs = []
    for i, plen in enumerate([3, 8, 13, 17]):  # mid/boundary/multi-page
        prompt = [int(t) for t in rng.integers(1, 300, size=plen)]
        jobs.append((prompt, dict(max_tokens=int(6 + 3 * i), seed=i)))

    streams = {}
    for path in ("ragged", "gather"):
        eng = PipelineEngine(
            model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
            pool_pages=10, page_size=8, paged_attention=path,
        )
        before = paged_ops.dispatch_counts()
        batcher = ContinuousBatcher(eng, decode_block=3)
        try:
            streams[path] = _concurrent(batcher, jobs)
        finally:
            batcher.close()
        after = paged_ops.dispatch_counts()
        assert after["xla"] == before["xla"]
        assert (after["kernel"] > before["kernel"]) == (path == "ragged")

    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    want = [[t for t, _ in ref.generate_step(p, **kw)] for p, kw in jobs]
    assert streams["ragged"] == want
    assert streams["ragged"] == streams["gather"]
