"""A chunk's packed experts multiply only the rows that picked them.

``ops.moe._apply_grouped_kernel`` sorts a chunk's (row, pick) pairs by
expert, pads each expert's run to whole tiles of rows and runs every tile
through the expert-indexed 4-bit kernel (``quant_matmul_experts``, one row
block per table entry) — here in interpret mode, against ``_apply_scan``
(every distinct expert against ALL rows, the path it replaces on a chip) and
against the experts dequantized one pair at a time in float64. The grouping
helper, ``group_rows``, is held to its contract on its own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.ops.moe import (
    _apply_grouped_kernel,
    _apply_scan,
    group_rows,
)
from mlx_sharding_tpu.ops.quant import dequantize
from tests.test_quant_moe import _packed_stack, _routing


def _pair_by_pair(x, weights, idx, w_gate, w_up, w_down, gs):
    """Float64, one (row, pick) pair at a time over the dequantized experts."""
    def dense(w):
        return np.asarray(
            dequantize(w["q"], w["scales"], w["biases"], gs, 4, jnp.float32),
            np.float64,
        )

    gate = None if w_gate is None else dense(w_gate)
    up, down = dense(w_up), dense(w_down)
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for n, (picks, mass) in enumerate(zip(np.asarray(idx), np.asarray(weights, np.float64))):
        for e, w in zip(picks, mass):
            u = up[e] @ x[n]
            if gate is None:
                h = np.square(np.maximum(u, 0.0))
            else:
                g = gate[e] @ x[n]
                h = g / (1.0 + np.exp(-g)) * u
            out[n] += w * (down[e] @ h)
    return out


def _stacks(rng, e, h, mi, gs, gated, layers=None):
    """(w_gate | None, w_up, w_down), ``(E, …)`` or ``(layers, E, …)``."""
    def stack(out_d, in_d):
        if layers is None:
            return _packed_stack(rng, e, out_d, in_d, gs)
        per = [_packed_stack(rng, e, out_d, in_d, gs) for _ in range(layers)]
        return jax.tree.map(lambda *a: jnp.stack(a), *per)

    return stack(mi, h) if gated else None, stack(mi, h), stack(h, mi)


@pytest.mark.parametrize("layered", [False, True], ids=["one-layer", "in-place"])
@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("e,k", [(64, 6), (8, 2)], ids=["top6of64", "top2of8"])
@pytest.mark.parametrize("n", [17, 64, 256])
def test_grouped_matches_the_scan_and_a_float_reference(n, e, k, gated, layered):
    """float32 rows: the grouped path equals the scan to float32 rounding
    and the pair-by-pair float64 reference tighter than the scan does
    (a row's K terms are summed in float32 and cast once); with ``layer=``
    the stacks are ``(L, E, …)`` and the answer is that layer's, bit for
    bit."""
    h, mi, gs, tile = 64, 32, 16, 8
    rng = np.random.default_rng(1000 * n + 10 * e + 2 * gated + layered)
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    weights, idx = _routing(rng, n, e, k, "random")
    layers, layer = (3, 1) if layered else (None, None)
    wg, wu, wd = _stacks(rng, e, h, mi, gs, gated, layers)
    got = _apply_grouped_kernel(
        x, weights, idx, wg, wu, wd, gs, 4, interpret=True, layer=layer, tile=tile
    )
    assert got.shape == x.shape and got.dtype == x.dtype
    one = (wg, wu, wd)
    if layered:
        one = jax.tree.map(lambda a: a[layer], one)
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(_apply_grouped_kernel(
                x, weights, idx, *one, gs, 4, interpret=True, tile=tile)),
        )
    want = _apply_scan(x, weights, idx, wg, wu, wd, gs, 4, layer=layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-5)
    exact = _pair_by_pair(x, weights, idx, *one, gs)
    scale = np.abs(exact).max()
    assert np.abs(np.asarray(got) - exact).max() <= 4e-6 * scale


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "case", ["an-expert-nobody-picked", "more-than-one-tile-an-expert",
             "all-rows-pick-the-same"],
)
def test_grouped_corner_routings(case, dtype):
    """Routings that stress the tiling: an expert no row picked (its leaves
    NaN: it must not be read into the result), experts whose rows fill
    several tiles, and every row picking the same K experts (few slots
    live, most of the static bound idle). bf16 rows, as served, round each
    dequantized plane to bf16 like the decode kernel: no worse than the scan
    against the float64 reference, up to bf16's own step."""
    from mlx_sharding_tpu.ops.quant_matmul import experts_blocks

    h, mi, gs, tile = 64, 32, 16, 8
    rng = np.random.default_rng(sum(map(ord, case)))
    n, e, k, picks = {
        "an-expert-nobody-picked": (40, 8, 2, "random"),
        "more-than-one-tile-an-expert": (64, 4, 2, "random"),
        "all-rows-pick-the-same": (64, 8, 2, "same"),
    }[case]
    x = jnp.asarray(rng.normal(size=(n, h)), dtype)
    weights, idx = _routing(rng, n, e, k, picks)
    wg, wu, wd = _stacks(rng, e, h, mi, gs, True)
    clean = (wg, wu, wd)
    if case == "an-expert-nobody-picked":
        weights, idx = _routing(rng, n, e - 1, k, picks)
        idx = jnp.where(idx >= 3, idx + 1, idx)  # nobody picks expert 3
        wg, wu, wd = jax.tree.map(
            lambda a: a.at[3].set(jnp.nan) if jnp.issubdtype(a.dtype, jnp.floating) else a,
            clean,
        )
    for out_d, in_d in ((mi, h), (h, mi)):
        assert experts_blocks(tile, out_d, in_d, gs, 4, hardware=False) is not None
    ids, live, rows, dest = group_rows(idx, e, tile)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=e)
    assert int(live[0]) == int(np.ceil(counts / tile).sum())
    assert ids.shape[0] == (n * k + min(e, n * k) * (tile - 1)) // tile
    if case == "more-than-one-tile-an-expert":
        assert (counts > tile).all()
    if case == "all-rows-pick-the-same":
        assert int(live[0]) == k * (n // tile) and int(live[0]) < ids.shape[0]

    got = _apply_grouped_kernel(x, weights, idx, wg, wu, wd, gs, 4, interpret=True, tile=tile)
    assert got.dtype == dtype and bool(jnp.isfinite(got).all())
    exact = _pair_by_pair(x, weights, idx, *clean, gs)
    scale = np.abs(exact).max()

    def err(a):
        return np.abs(np.asarray(a, np.float64) - exact).max() / scale

    if dtype == jnp.float32:
        assert err(got) <= 4e-6
    else:
        want = _apply_scan(x, weights, idx, *clean, gs, 4)
        assert err(got) <= max(err(want), 2.0 ** -7)


GROUPINGS = {
    # name -> (rows, top-k, experts, tile, picks)
    "chunk-top6of64-tile32": (256, 6, 64, 32, "random"),
    "chunk-top6of64-tile16": (256, 6, 64, 16, "random"),
    "top2of8-tile8": (17, 2, 8, 8, "random"),
    "top1-tile8": (33, 1, 16, 8, "random"),
    "every-pair-its-own-expert": (4, 2, 8, 8, "distinct"),
    "same-experts-every-row": (50, 3, 8, 16, "same"),
    "one-expert-tile1": (5, 1, 1, 1, "same"),
    "tile-wider-than-the-chunk": (17, 2, 4, 64, "random"),
}


@pytest.mark.parametrize("case", list(GROUPINGS))
def test_group_rows_places_every_pair_once(case):
    """The grouping helper alone: every (row, pick) pair has exactly one
    tile row, in a slot of its expert, holding its row; slots are ascending
    by expert and those past ``live`` repeat the last real id; a padding row
    is taken back by no pair — with NaN in every padding row of the
    gathered input, what the pairs take back is finite and theirs."""
    n, k, e, tile, picks = GROUPINGS[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    _, idx = _routing(rng, n, e, k, picks)
    ids, live, rows, dest = jax.jit(group_rows, static_argnums=(1, 2))(idx, e, tile)
    ids, rows, dest, idx = (np.asarray(a) for a in (ids, rows, dest, idx))
    live = int(live[0])
    slots = (n * k + min(e, n * k) * (tile - 1)) // tile
    assert ids.shape == (slots,) and rows.shape == (slots, tile) and dest.shape == (n, k)
    counts = np.bincount(idx.ravel(), minlength=e)
    assert live == int(np.ceil(counts / tile).sum()) and 1 <= live <= slots
    # ascending, each expert as many slots as its rows need, then the filler
    np.testing.assert_array_equal(
        ids[:live], np.repeat(np.arange(e), np.ceil(counts / tile).astype(int)))
    assert (ids[live:] == ids[live - 1]).all()
    # one tile row a pair, in a slot of the pair's expert, holding its row
    assert len(set(dest.ravel().tolist())) == n * k
    assert dest.min() >= 0 and dest.max() < live * tile
    np.testing.assert_array_equal(ids[dest // tile], idx)
    np.testing.assert_array_equal(
        rows.ravel()[dest], np.broadcast_to(np.arange(n)[:, None], (n, k)))
    assert rows.min() >= 0 and rows.max() < n
    # within an expert the pairs keep the rows' order (a stable sort)
    for ex in np.unique(idx):
        at = dest[idx == ex]
        assert (np.diff(at) > 0).all()
    # padding never reaches a result
    x = rng.normal(size=(n, 3))
    real = np.zeros(slots * tile, bool)
    real[dest.ravel()] = True
    gathered = np.where(real[:, None], x[rows.ravel()], np.nan)
    y = gathered * (ids.repeat(tile)[:, None] + 1.0)  # "expert e multiplies by e + 1"
    back = y[dest]  # (n, k, 3)
    assert np.isfinite(back).all()
    np.testing.assert_array_equal(back, x[:, None, :] * (idx[..., None] + 1.0))
