"""A decode step's dense bf16 experts as one expert-indexed kernel.

``ops.dense_experts.dense_experts`` is what ``ops.moe._distinct_walk``'s
walk becomes for the rows and stacks ``dense_experts_block`` serves — here
in interpret mode — against the loop it replaces (one expert an iteration,
terms added in the rows' dtype) and against the experts applied one (row,
pick) pair at a time in float64. The block function's refusals fall to the
loop and give the loop's result, lanes of a ``jax.vmap`` are folded into
rows before the kernel is reached, and ``mst_moe_dispatch_total`` names the
choice.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.ops import moe
from mlx_sharding_tpu.ops.dense_experts import (
    MAX_ROWS,
    TILES_VMEM_BYTES,
    VMEM_LIMIT_BYTES,
    dense_experts,
    dense_experts_block,
)

BF16, F32 = jnp.bfloat16, jnp.float32


def _inputs(rng, n, k, held, routed, h, i, gated, dtype, layers=None, base=0):
    """Rows, a routing over ``routed`` experts of which the stacks hold
    ``base .. base + held`` (picks already local: below 0 and at or above
    ``held`` name absent experts), and the stacks ``(E, …)`` or ``(L, E, …)``."""
    lead = () if layers is None else (layers,)
    draw = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.1, dtype)  # noqa: E731
    wg = draw(held, h, i) if gated else None
    wu, wd = draw(held, h, i), draw(held, i, h)
    x = jnp.asarray(rng.normal(size=(n, h)), dtype)
    idx = np.stack([rng.permutation(routed)[:k] for _ in range(n)]).astype(np.int32)
    weights = rng.uniform(0.1, 1.0, size=(n, k)).astype(np.float32)
    return x, jnp.asarray(weights), jnp.asarray(idx - base), (wg, wu, wd)


def _walks(x, weights, idx, stacks, layer=None):
    """``(kernel, loop)`` results of the one walk over the same arguments."""
    held = stacks[1].shape[0 if layer is None else 1]
    first = 0
    if layer is not None:
        stacks, first = moe._flat_layers(*stacks), layer * held
    args = (x, weights, idx, tuple(stacks), jnp.asarray(first, jnp.int32))
    assert moe.dense_kernel_block(x, *stacks, True) is not None
    return (moe._distinct_walk(held, 64, 4, True)(*args),
            moe._distinct_walk(held, 64, 4)(*args))


def _pair_by_pair(x, weights, idx, stacks, layer=None):
    """Float64, one (row, pick) pair at a time; absent experts add nothing."""
    wg, wu, wd = (None if w is None else np.asarray(
        w if layer is None else w[layer], np.float64) for w in stacks)
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for n, (picks, mass) in enumerate(zip(np.asarray(idx), np.asarray(weights, np.float64))):
        for e, w in zip(picks, mass):
            if not 0 <= e < wu.shape[0]:
                continue
            u = x[n] @ wu[e]
            if wg is None:
                h = np.square(np.maximum(u, 0.0))
            else:
                g = x[n] @ wg[e]
                h = g / (1.0 + np.exp(-g)) * u
            out[n] += w * (h @ wd[e])
    return out


#: the five served cells' experts cut to a few tiles each: name ->
#: (rows, top-k, held, routed, hidden, width, gated); the width keeps the
#: cell's factorization (21 x 128 in nemotron3)
CELLS = {
    "qwen3-next-80b": (32, 10, 16, 64, 128, 128, True),
    "nemotron3-21x128": (32, 22, 16, 64, 128, 21 * 128, False),
    "kimi-linear-48b": (40, 8, 16, 256, 256, 384, True),
    "zaya1-8b": (24, 1, 8, 16, 128, 256, True),
    "trinity-large": (32, 4, 16, 256, 384, 384, True),
}


@pytest.mark.parametrize("layered", [False, True], ids=["one-layer", "in-place"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_kernel_matches_the_loop_at_bf16(cell, layered, monkeypatch):
    """bf16 rows and stacks at each cell's rows, top-k and share of held
    experts: the kernel stands closer to the float64 pair-by-pair answer
    than the loop does (float32 activation, a row's terms summed in float32
    and cast once) and within the loop's own distance of the loop. Tiles of
    one 128-lane column each, so every width runs several grid steps an
    expert."""
    n, k, held, routed, h, i, gated = CELLS[cell]
    monkeypatch.setattr("mlx_sharding_tpu.ops.dense_experts.TILES_VMEM_BYTES",
                        2 * (3 if gated else 2) * h * 128 * 2)
    rng = np.random.default_rng(sum(map(ord, cell)) + layered)
    layers, layer = (3, 1) if layered else (None, None)
    x, weights, idx, stacks = _inputs(rng, n, k, held, routed, h, i, gated, BF16, layers)
    assert dense_experts_block(n, h, i, BF16, gated, hardware=False) == 128
    got, loop = _walks(x, weights, idx, stacks, layer)
    assert got.shape == x.shape and got.dtype == x.dtype
    want = _pair_by_pair(x, weights, idx, stacks, layer)
    scale = np.abs(want).max()
    err_kernel = np.abs(np.asarray(got, np.float64) - want).max()
    err_loop = np.abs(np.asarray(loop, np.float64) - want).max()
    assert err_kernel <= max(err_loop, scale * 2**-8), (err_kernel, err_loop, scale)
    assert np.abs(np.asarray(got, np.float64) - np.asarray(loop, np.float64)).max() \
        <= err_loop + scale * 2**-7


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("n", [24, 32, 40, 48])
def test_kernel_is_the_loops_arithmetic_on_exact_operands(n, gated):
    """Small integers over powers of two: every product and sum is exact in
    bf16 and float32 but the activation, which both round to bf16 — so the
    two walks differ by that rounding alone (the loop rounds gate and up to
    bf16 BEFORE the activation, the kernel after), far inside what bf16
    noise would hide: a wrong expert, tile, mass or a dropped term is whole
    units off."""
    rng = np.random.default_rng(n + gated)
    held, routed, k, h, i = 8, 16, 3, 128, 256
    ints = lambda *s: jnp.asarray(rng.integers(-2, 3, size=s) / 4, BF16)  # noqa: E731
    stacks = (ints(held, h, i) if gated else None, ints(held, h, i), ints(held, i, h))
    x = ints(n, h)
    idx = jnp.asarray(np.stack([rng.permutation(routed)[:k] for _ in range(n)]) - 4, jnp.int32)
    weights = jnp.asarray(rng.integers(1, 4, size=(n, k)) / 4, F32)
    got, _ = _walks(x, weights, idx, stacks)
    want = _pair_by_pair(x, weights, idx, stacks)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2**-6,
                               atol=np.abs(want).max() * 2**-7)


@pytest.mark.parametrize("case", ["none-held", "all-absent-below", "one-expert",
                                  "every-expert", "capped-by-rows"])
def test_kernel_corner_routings(case):
    """``live == 0`` (no row picked a held expert) is zeros; one distinct
    expert, every held expert (``T == E``: no dead entry) and a table capped
    by the picks (``T == N * K < E``) each equal the pair-by-pair answer."""
    rng = np.random.default_rng(len(case))
    held, h, i, n, k = 8, 128, 128, 4, 2
    x, weights, idx, stacks = _inputs(rng, n, k, held, 3 * held, h, i, True, BF16)
    if case == "none-held":
        idx = jnp.full((n, k), held + 3, jnp.int32)
    elif case == "all-absent-below":
        idx = jnp.full((n, k), -2, jnp.int32)
    elif case == "one-expert":
        idx = jnp.full((n, k), 5, jnp.int32)
    elif case == "every-expert":
        idx = jnp.arange(n * k, dtype=jnp.int32).reshape(n, k) % held
    else:  # 2 rows x top-2 over 8 held: the table has 4 entries
        x, weights, idx = x[:2], weights[:2], jnp.asarray([[7, 1], [1, 3]], jnp.int32)
    ids, live = moe.distinct_experts(idx, held)
    assert ids.shape[0] == min(held, idx.size)
    got, loop = _walks(x, weights, idx, stacks)
    want = _pair_by_pair(x, weights, idx, stacks)
    if case in ("none-held", "all-absent-below"):
        assert int(live[0]) == 0 and not np.asarray(got, np.float32).any()
        assert not np.asarray(loop, np.float32).any()
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2**-6,
                               atol=max(np.abs(want).max(), 1e-3) * 2**-7)


def test_kernel_reads_only_the_listed_experts():
    """Entries past ``live`` repeat the last real id and are skipped: an
    expert NO row picked may hold anything (here NaN) and the result does
    not see it; nor does another layer of the same stacks."""
    rng = np.random.default_rng(5)
    held, h, i, n, k = 8, 128, 256, 24, 2
    x, weights, _, stacks = _inputs(rng, n, k, held, held, h, i, True, BF16, layers=3)
    idx = jnp.asarray(rng.choice([1, 2, 6], size=(n, k)), jnp.int32)
    picked = np.isin(np.arange(held), np.asarray(idx))
    keep = (np.arange(3) == 1)[:, None] & picked[None]
    poisoned = tuple(jnp.where(keep[:, :, None, None], w, jnp.nan) for w in stacks)
    got, _ = _walks(x, weights, idx, stacks, layer=1)
    again, _ = _walks(x, weights, idx, poisoned, layer=1)
    assert np.isfinite(np.asarray(again, np.float32)).all()
    assert np.array_equal(np.asarray(got), np.asarray(again))


def test_kernel_direct_call_sums_the_first_live_entries():
    """The kernel's own contract, without the walk: the table names rows of
    the stacks in any order, ``coef`` weighs them, entries past ``live``
    add nothing whatever their mass."""
    rng = np.random.default_rng(9)
    e, h, i, n = 6, 128, 256, 8
    x, _, _, (wg, wu, wd) = _inputs(rng, n, 1, e, e, h, i, True, BF16)
    ids = jnp.asarray([4, 0, 3, 3, 3], jnp.int32)
    coef = jnp.asarray(rng.uniform(0.1, 1.0, size=(5, n)), F32)
    got = dense_experts(x, ids, jnp.asarray([3], jnp.int32), coef, wg, wu, wd,
                        block_i=128, interpret=True)
    want = _pair_by_pair(x, np.asarray(coef[:3]).T, np.tile([4, 0, 3], (n, 1)), (wg, wu, wd))
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=2**-6,
                               atol=np.abs(want).max() * 2**-7)


#: what the block function refuses, and so the loop keeps: name ->
#: (rows, hidden, width, dtype)
REFUSED = {
    "float32-stacks": (32, 128, 256, F32),
    "width-no-multiple-of-128": (32, 128, 192, BF16),
    "hidden-off-the-lanes": (32, 192, 256, BF16),
    "rows-over-the-bound": (MAX_ROWS + 1, 128, 256, BF16),
    "a-chunk": (256, 128, 256, BF16),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_refused_shapes_walk_the_loop(case, monkeypatch):
    """Where ``dense_experts_block`` says None the dispatcher counts
    ``scan`` — on a TPU backend too — and the walk IS the loop: the same
    jaxpr, a ``while`` and no kernel, and so the loop's result."""
    n, h, i, dtype = REFUSED[case]
    assert dense_experts_block(n, h, i, dtype, True) is None
    rng = np.random.default_rng(len(case))
    x, weights, idx, stacks = _inputs(rng, n, 2, 4, 8, h, i, True, dtype)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = moe.dispatch_counts()
    jaxpr = str(jax.make_jaxpr(
        lambda *a: moe.apply_experts(*a, *stacks, expert_base=0))(x, weights, idx))
    assert moe.dispatch_counts() == {**before, "scan": before["scan"] + 1}
    assert "while" in jaxpr and "pallas_call" not in jaxpr
    monkeypatch.undo()
    got = moe.apply_experts(x, weights, idx, *stacks, expert_base=0)
    want = _pair_by_pair(x, weights, idx, stacks)
    tol = 1e-5 if dtype == F32 else 2**-5
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=tol,
                               atol=np.abs(want).max() * tol)


def test_rows_of_another_dtype_than_the_stacks_walk_the_loop(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stacks = tuple(jnp.zeros(s, BF16) for s in ((4, 128, 256),) * 2 + ((4, 256, 128),))
    assert moe.dense_kernel_block(jnp.zeros((8, 128), F32), *stacks) is None
    assert moe.dense_kernel_block(jnp.zeros((8, 128), BF16), *stacks) == 256
    packed = {"q": jnp.zeros((4, 256, 16), jnp.uint32)}
    assert moe.dense_kernel_block(jnp.zeros((8, 128), BF16), packed, packed, packed) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert moe.dense_kernel_block(jnp.zeros((8, 128), BF16), *stacks) is None


#: the served cells' real shapes: name -> (rows, hidden, width, gated, block)
SERVED = {
    "qwen3-next-80b": (32, 2048, 512, True, 512),
    "nemotron3": (32, 1024, 2688, False, 2688),
    "kimi-linear-48b": (40, 2304, 1024, True, 1024),
    "zaya1-8b": (24, 2048, 2048, True, 1024),
    "trinity-large": (32, 3072, 3072, True, 768),
}


@pytest.mark.parametrize("cell", list(SERVED))
def test_block_of_the_served_shapes(cell):
    """The tile each cell's experts run with: the widest divisor of the
    width in whole 128-lane columns whose double-buffered tiles fit their
    share of VMEM, and with the rows' own buffers the limit the call states."""
    n, h, i, gated, block = SERVED[cell]
    assert dense_experts_block(n, h, i, BF16, gated) == block
    mats = 3 if gated else 2
    assert 2 * mats * h * block * 2 <= TILES_VMEM_BYTES < VMEM_LIMIT_BYTES
    wider = [b for b in range(block + 128, i + 1, 128) if i % b == 0]
    assert all(2 * mats * h * b * 2 > TILES_VMEM_BYTES for b in wider)
    assert dense_experts_block(MAX_ROWS, h, i, BF16, gated) == block
    assert dense_experts_block(MAX_ROWS + 1, h, i, BF16, gated) is None


@pytest.mark.parametrize("rows_a_lane", [1, 2])
def test_lanes_of_a_vmap_are_folded_into_the_kernels_rows(rows_a_lane):
    """The engine's vectorized decode step calls the walk under ``jax.vmap``
    over its lanes: ``walk_lanes`` folds them into rows first, so the kernel
    is ONE call over one list of experts (not a call a lane), and gives what
    the rows give unfolded."""
    rng = np.random.default_rng(17 + rows_a_lane)
    m, held, h, i, k = 6, 8, 128, 256, 2
    x, weights, idx, stacks = _inputs(rng, m * rows_a_lane, k, held, 16, h, i, True, BF16)
    walk = moe._distinct_walk(held, 64, 4, True)
    first = jnp.asarray(0, jnp.int32)
    lanes = lambda *rows: jax.vmap(  # noqa: E731
        lambda *lane: walk(*lane, stacks, first)
    )(*(a.reshape(m, rows_a_lane, -1) for a in rows))
    jaxpr = str(jax.make_jaxpr(lanes)(x, weights, idx))
    assert jaxpr.count("pallas_call") == 1 and "while" not in jaxpr
    got = jax.jit(lanes)(x, weights, idx).reshape(x.shape)
    assert np.array_equal(np.asarray(got), np.asarray(walk(x, weights, idx, stacks, first)))


def test_lanes_that_fold_to_more_rows_than_the_kernel_takes_walk_the_loop():
    rng = np.random.default_rng(23)
    m, held, h, i, k = MAX_ROWS + 8, 4, 128, 128, 2
    x, weights, idx, stacks = _inputs(rng, m, k, held, 8, h, i, True, BF16)
    walk = moe._distinct_walk(held, 64, 4, True)
    first = jnp.asarray(0, jnp.int32)
    lanes = jax.vmap(lambda *lane: walk(*lane, stacks, first))
    jaxpr = str(jax.make_jaxpr(lanes)(x[:, None], weights[:, None], idx[:, None]))
    assert "while" in jaxpr


DISPATCH = {
    "decode-32-rows-resident-range": (32, {"expert_base": 4}, "dense_kernel"),
    "decode-in-place": (32, {"expert_base": 4, "layer": 1}, "dense_kernel"),
    "decode-1-row": (1, {"expert_base": 0}, "dense_kernel"),
    "chunk-256-rows": (256, {"expert_base": 4}, "scan"),
    "chunk-no-range": (256, {}, "scan"),
    "decode-no-range-gathers": (8, {}, "gather"),
}


@pytest.mark.parametrize("case", list(DISPATCH))
def test_dispatch_is_counted_once_a_traced_call_and_shown_on_metrics(case, monkeypatch):
    """``mst_moe_dispatch_total{path="dense_kernel"}``: a decode step's rows
    over dense bf16 stacks under a resident range on a TPU, counted where
    the choice is made, once per traced call; a chunk's rows stay ``scan``
    and a decode step without a range the gather. Off the chip every one of
    them is what it was."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    n, kw, want = DISPATCH[case]
    rng = np.random.default_rng(len(case))
    layers = 2 if "layer" in kw else None
    x, weights, idx, stacks = _inputs(rng, n, 2, 8, 16, 128, 256, True, BF16, layers)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = moe.dispatch_counts()
    assert set(before) == {"kernel", "grouped", "dense_kernel", "scan",
                           "gather_packed", "gather"}
    traced = str(jax.make_jaxpr(lambda *a: moe.apply_experts(*a, **kw))(
        x, weights, idx, *stacks))
    assert moe.dispatch_counts() == {**before, want: before[want] + 1}
    assert ("dense_experts" in traced) == (want == "dense_kernel")
    monkeypatch.undo()
    off_chip = "gather" if want == "gather" else "scan"
    fn = jax.jit(lambda *a: moe.apply_experts(*a, **kw))
    for _ in range(2):  # the compiled program's runs add nothing
        fn(x, weights, idx, *stacks).block_until_ready()
    after = moe.dispatch_counts()
    assert after[off_chip] == before[off_chip] + 1 + (want == off_chip)
    text = ServingMetrics().render()
    assert "dense_kernel" in text.split("# HELP mst_moe_dispatch_total")[1].split("\n")[0]
    for path, count in after.items():
        assert f'mst_moe_dispatch_total{{path="{path}"}} {count}' in text


def test_ep_axis_takes_the_kernel_per_device_and_the_psum_follows(monkeypatch):
    """Under ``ep_axis`` the same choice runs on each device's share of the
    stacks; the devices' parts meet in the ``psum``."""
    from jax.sharding import PartitionSpec as P

    from mlx_sharding_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(31)
    x, weights, idx, stacks = _inputs(rng, 32, 2, 8, 8, 128, 256, True, BF16)
    rep, split = P(), P("ep")
    fn = jax.shard_map(
        lambda *a: moe.apply_experts(*a, ep_axis="ep"), mesh=make_mesh(pp=1, ep=2),
        in_specs=(rep, rep, rep, split, split, split), out_specs=rep, check_vma=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = moe.dispatch_counts()
    jaxpr = str(jax.make_jaxpr(fn)(x, weights, idx, *stacks))
    assert moe.dispatch_counts() == {**before, "dense_kernel": before["dense_kernel"] + 1}
    assert jaxpr.count("pallas_call") == 1 and "psum" in jaxpr and "while" not in jaxpr
