"""ops/mamba2.py's one-pass decode step (:func:`ssm_pool_step`), the Pallas
kernel in interpret mode against the recurrence it replaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.ops import mamba2
from mlx_sharding_tpu.ops.mamba2 import ssm_pool_step, ssm_sequential

# (heads, head width, groups, state): granite-like — one group, the whole
# slot one block — and nemotron-like — several groups of several heads;
# ``block``: the bytes of S a grid step may move, None for the module's own
# (at these sizes every head of a slot), or small enough to split the heads
# into blocks of whole groups / blocks inside one group
# ``heads``: the heads of a grid step that follow
SHAPES = {
    "granite-like": dict(dims=(8, 16, 1, 128), block=None, heads=8),
    "nemotron-like": dict(dims=(16, 8, 4, 128), block=None, heads=16),
    "nemotron-like-blocks-of-2-groups": dict(
        dims=(16, 8, 4, 128), block=8 * 8 * 128 * 4, heads=8),
    "nemotron-like-blocks-inside-a-group": dict(
        dims=(16, 8, 4, 128), block=2 * 8 * 128 * 4, heads=2),
}
LAYERS, ROWS, BATCH, RANK = 3, 6, 5, 1  # one scratch row past the batch


def _operands(dims, seed=0):
    h, p, g, n = dims
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    f32 = jnp.float32
    return dict(
        pool=jax.random.normal(ks[0], (LAYERS, ROWS, h, p, n), f32),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (BATCH, h), f32) - 1.0),
        x=jax.random.normal(ks[2], (BATCH, h, p), f32),
        b_mat=jax.random.normal(ks[3], (BATCH, g, n), f32),
        c_mat=jax.random.normal(ks[4], (BATCH, g, n), f32),
        a_head=-jnp.exp(jax.random.uniform(ks[5], (h,), f32, 0.0, 2.0)),
        active=jnp.asarray([True, False, True, True, False]),
    )


def _counting_heads(seen):
    """The kernel body, noting the heads of the block it is traced for."""
    body = mamba2._ssm_step_kernel

    def kernel(rank_ref, active_ref, decay_ref, dtx_ref, bc_ref, s_ref, *refs, **kw):
        seen.append(s_ref.shape[0])
        return body(rank_ref, active_ref, decay_ref, dtx_ref, bc_ref, s_ref, *refs, **kw)

    return kernel


def _close(got, want):
    """Relative 1e-6: of the largest entry, as a float32 sum's order moves
    its last bits."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))


def _one_step(o, rank):
    """``ssm_sequential`` over one position from the layer's rows."""
    rep = o["x"].shape[1] // o["b_mat"].shape[1]
    heads = lambda z: jnp.repeat(z, rep, axis=1)[:, None]  # noqa: E731
    y, s = ssm_sequential(
        o["x"][:, None], o["dt"][:, None], o["a_head"], heads(o["b_mat"]),
        heads(o["c_mat"]), o["pool"][rank, :BATCH],
    )
    return y[:, 0], s


@pytest.fixture
def fresh_traces():
    """The kernel is jitted: a trace of the same shapes at another block size
    must not serve a case, nor a case's the tests after it."""
    ssm_pool_step.clear_cache()
    yield
    ssm_pool_step.clear_cache()


@pytest.mark.parametrize("case", list(SHAPES))
def test_pool_step_is_the_recurrence_on_the_layers_rows_alone(case, monkeypatch, fresh_traces):
    dims, block = SHAPES[case]["dims"], SHAPES[case]["block"]
    if block is not None:
        monkeypatch.setattr(mamba2, "_STEP_BLOCK_BYTES", block)
    monkeypatch.setattr(mamba2, "_ssm_step_kernel", _counting_heads(seen := []))
    o = _operands(dims)
    pool0 = np.asarray(o["pool"])
    want_y, want_s = _one_step(o, RANK)
    step = jax.jit(lambda o, rank: ssm_pool_step(
        o["pool"], rank, o["dt"], o["x"], o["b_mat"], o["c_mat"], o["a_head"],
        o["active"], interpret=True,
    ))
    y, pool = step(o, RANK)
    assert seen == [SHAPES[case]["heads"]]
    act = np.asarray(o["active"])
    # y and S' of the active slots are the recurrence's
    _close(y, want_y)
    _close(np.asarray(pool)[RANK, :BATCH][act], np.asarray(want_s)[act])
    # an inactive slot keeps its S; the scratch row past the batch and every
    # other layer's rows are what they were, bit for bit
    keep = np.ones(pool0.shape[:2], bool)
    keep[RANK, :BATCH] = ~act
    np.testing.assert_array_equal(np.asarray(pool)[keep], pool0[keep])
    # no mask: every slot advances
    y_all, pool_all = ssm_pool_step(
        o["pool"], RANK, o["dt"], o["x"], o["b_mat"], o["c_mat"], o["a_head"],
        interpret=True,
    )
    _close(y_all, y)
    _close(pool_all[RANK, :BATCH], want_s)

    # a traced rank inside a scan gives what static ones do
    def walk(pool, rank):
        y, pool = ssm_pool_step(
            pool, rank, o["dt"], o["x"], o["b_mat"], o["c_mat"], o["a_head"],
            o["active"], interpret=True,
        )
        return pool, y

    scanned, ys = jax.jit(lambda p: jax.lax.scan(walk, p, jnp.arange(LAYERS)))(o["pool"])
    static = o["pool"]
    for rank in range(LAYERS):
        static, y_r = walk(static, rank)
        _close(ys[rank], y_r)
    _close(scanned, static)
    _close(ys[RANK], y)


# ------------------------------------------------- the mixer's decode step


def _mixer(dims, seed=1, hidden=32, taps=4):
    """A layer's leaves at ``dims``, a pool and the tails of ``BATCH``
    sequences, and the mixer closed over them: ``run(u, pool, tail, **kw)``."""
    h, p, g, n = dims
    di, conv_dim = h * p, h * p + 2 * g * n
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    f32 = jnp.float32
    leaves = {
        "in_proj": jax.random.normal(ks[0], (hidden, di + conv_dim + h), f32) * hidden ** -0.5,
        "conv_w": jax.random.uniform(ks[1], (conv_dim, taps), f32, -0.5, 0.5),
        "conv_b": jax.random.normal(ks[2], (conv_dim,), f32) * 0.1,
        "dt_bias": jax.random.normal(ks[3], (h,), f32) - 2.0,
        "A_log": jax.random.uniform(ks[4], (h,), f32, 0.0, 2.0),
        "D": jnp.ones((h,), f32),
        "ssm_norm": jnp.ones((di,), f32),
        "out_proj": jax.random.normal(ks[5], (di, hidden), f32) * di ** -0.5,
    }
    pool = jax.random.normal(ks[6], (LAYERS, ROWS, h, p, n), f32)
    tail = jax.random.normal(ks[7], (BATCH, taps - 1, conv_dim), f32)
    active = jnp.asarray([True, False, True, True, False])

    def run(u, pool, tail, **kw):
        return mamba2.mamba2_mixer(
            lambda x, w: x @ w, leaves, u, pool, RANK, tail, None, active,
            heads=h, head_dim=p, groups=g, state=n, taps=taps, chunk=8,
            eps=1e-5, **kw,
        )

    u = lambda t: jax.random.normal(ks[8], (BATCH, t, hidden), f32)  # noqa: E731
    return run, u, pool, tail


@pytest.mark.parametrize("case", ["granite-like", "nemotron-like"])
def test_mixer_decode_step_through_the_kernel_is_the_formulas(case):
    """``mamba2_mixer`` at ``T == 1``: what the kernel path returns — the
    output, the whole pool, the tails — is what the XLA formula's path does."""
    run, u, pool, tail = _mixer(SHAPES[case]["dims"])
    want = jax.jit(run)(u(1), pool, tail)
    got = jax.jit(lambda *a: run(*a, interpret=True))(u(1), pool, tail)
    for g, w in zip(got, want):
        _close(g, w)
    frozen = ~np.asarray([True, False, True, True, False])
    np.testing.assert_array_equal(
        np.asarray(got[1])[RANK, :BATCH][frozen], np.asarray(pool)[RANK, :BATCH][frozen]
    )
    np.testing.assert_array_equal(np.asarray(got[2])[frozen], np.asarray(tail)[frozen])


def test_mixer_decode_step_under_vmap_takes_the_formula_lane_by_lane():
    """An engine's vectorized decode step runs the mixer under ``jax.vmap``,
    one sequence a lane with a copy of its own rows: the kernel's batching
    rule is the XLA formula (pallas would loop over the lanes and copy each
    lane's pool), so every lane gives what it gives alone and the dispatch
    is counted as ``xla`` too."""
    run, u, pool, tail = _mixer(SHAPES["nemotron-like"]["dims"])
    lanes = 3
    us = jnp.stack([u(1) * (i + 1) for i in range(lanes)])
    pools = jnp.stack([pool * (i + 1) for i in range(lanes)])
    tails = jnp.stack([tail] * lanes)
    before = mamba2.dispatch_counts()
    got = jax.jit(jax.vmap(lambda *a: run(*a, interpret=True)))(us, pools, tails)
    after = mamba2.dispatch_counts()
    assert after == {"kernel": before["kernel"] + 1, "xla": before["xla"] + 1}
    for i in range(lanes):
        want = jax.jit(run)(us[i], pools[i], tails[i])
        for g, w in zip(got, want):
            _close(g[i], w)


def test_ssm_dispatch_is_counted_once_a_traced_call_and_shown_on_metrics():
    """``mst_ssm_dispatch_total{path}`` counts where ``mamba2_mixer`` chooses
    a decode step's recurrence: once per traced call, not once per run of the
    compiled program; a chunk is no decode step and counts nothing. Off the
    chip the step is ``xla``, in interpret mode ``kernel``."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    run, u, pool, tail = _mixer(SHAPES["granite-like"]["dims"])
    before = mamba2.dispatch_counts()
    assert set(before) == {"kernel", "xla"}
    for fn, t in (
        (jax.jit(run), 1),
        (jax.jit(lambda *a: run(*a, interpret=True)), 1),
        (jax.jit(run), 5),
    ):
        for _ in range(3):
            jax.block_until_ready(fn(u(t), pool, tail))
    after = mamba2.dispatch_counts()
    assert after == {"kernel": before["kernel"] + 1, "xla": before["xla"] + 1}
    text = ServingMetrics().render()
    assert "# TYPE mst_ssm_dispatch_total counter" in text
    assert "# HELP mst_ssm_dispatch_total" in text
    for path, n in after.items():
        assert f'mst_ssm_dispatch_total{{path="{path}"}} {n}' in text
