"""``olmo_hybrid`` (Olmo Hybrid: a gated delta rule with negative eigenvalues
on a rectangular state, or NoPE multi-head attention behind a full-width QK
norm, then a dense SwiGLU MLP, each sub-layer's OUTPUT normed) on the served
path, against its plain reference (``benchmarks/reference/olmo_hybrid.py``) at
tiny widths on the CPU, with the benchmark's seeded weights on both sides.

Sizes: pages and chunks of 8; two periods of ``G G G A``; 6 linear heads, keys
16 wide and values 64 (``Dk != Dv``; two heads side by side fill a 128-lane
tile, so the state pool keeps them PACKED as at the published 96 x 192) behind
4 taps; 3 attention heads of 32 on 3 K/V heads (an odd count at a query group
of ONE). Prompts end inside a chunk, one row into a chunk (the convolutions'
three taps behind it lie in the chunk before) and on a chunk border.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so what
separates them is the order of sums: chunks and the chunked (WY) form against
one position at a time, pages against whole rows. Log-probabilities agree to
~5e-5; ``LP_TOL`` = 4e-4 leaves several times that and is hundreds of times
under the SMALLEST of the reference's faults (``test_each_fault_moves…``).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import olmo_hybrid as ref
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.models.base import LayerRow
from mlx_sharding_tpu.ops import kda
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout
from tests.test_afmoe import served  # [(token, {id: log-probability})] of one greedy request

LP_TOL = 4e-4
SEED = 11
PAGE, MAX_SEQ = 8, 64
TINY = dict(
    model_type="olmo_hybrid", vocab_size=256, hidden_size=96, num_hidden_layers=8,
    num_attention_heads=3, num_key_value_heads=3, intermediate_size=128,
    layer_types=["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    linear_num_key_heads=6, linear_num_value_heads=6, linear_key_head_dim=16,
    linear_value_head_dim=64, linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": None}, rms_norm_eps=1e-6, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False, max_position_embeddings=65536,
)
rng = np.random.default_rng(3)
PROMPTS = {
    "inside": rng.integers(1, 256, 13).tolist(),  # chunks of 8 and 5
    "one-past": rng.integers(1, 256, 17).tolist(),  # 8, 8 and ONE row: every tap behind a border
    "border": rng.integers(1, 256, 24).tolist(),  # ends on a border: decode starts on one
}


def seeded_params(cfg: dict, seed: int = SEED):
    """The benchmark's seeded tree for ``cfg``, materialized and widened:
    bf16 VALUES in float32 leaves, so that every served path computes in
    float32 as the reference does."""
    lazy = lambda x: isinstance(x, W.LazyStack)  # noqa: E731
    return jax.tree.map(
        lambda x: (x[:] if lazy(x) else x).astype(jnp.float32),
        ref.program_params(cfg, "bf16", seed), is_leaf=lazy,
    )


def reference_at(cfg, ids, rows, wanted, fault=None, seed=SEED):
    return ref.forward(cfg, "bf16", seed, ids, rows, wanted, fault=fault)[2]


def differences(cfg, prompt, got, fault=None):
    """Served minus reference log-probability at the served top ids, the
    reference teacher-forced on the served tokens."""
    toks = [t for t, _ in got]
    seq = list(prompt) + toks[:-1]
    rows = [len(prompt) - 1 + j for j in range(len(toks))]
    wanted = [sorted(top)[:8] for _, top in got]
    want = reference_at(cfg, seq, rows, wanted, fault)
    have = np.asarray([[top[i] for i in w] for (_, top), w in zip(got, wanted)])
    return have - want


def make_engine(model, params, *, slots=2, paged=True, **kw):
    return PipelineEngine(
        model, params, make_mesh(pp=1, tp=1, ep=1, devices=jax.devices()[:1]),
        microbatches=slots, max_seq=MAX_SEQ, cache_dtype=jnp.float32,
        prefill_chunk=PAGE, decode_block=4,
        pool_pages=8 * slots if paged else None, page_size=PAGE if paged else None,
        **kw,
    )


@pytest.fixture(scope="module")
def tiny():
    model, _ = build_model(TINY)
    return model, seeded_params(TINY)


@pytest.fixture(scope="module")
def batcher(tiny):
    b = ContinuousBatcher(make_engine(*tiny), decode_block=4)
    assert b.engine.paged_attention == "ragged" and b._async
    yield b
    b.close()


# ------------------------------------------------------------ the model


@hard_timeout(300)
def test_full_forward_matches_the_reference(tiny):
    model, params = tiny
    assert model.walk == ([], ["gdn", "gdn", "gdn", "attn"], 2, [])
    assert model.state_pack == 2 and model.beta_scale == 2.0
    ids = PROMPTS["border"]
    cache = model.make_cache(1, MAX_SEQ, jnp.float32)
    # a K/V row's three heads of 32 merged on the lane axis
    assert cache.k.shape == cache.v.shape == (2, 1, MAX_SEQ, 1, 96)
    # two heads' (16, 64) tiles side by side: 128 lanes, no padded byte
    assert {k: (v.shape, v.dtype) for k, v in cache.state.items()} == {
        "gdn": ((6, 1, 3, 16, 128), jnp.float32),
        "conv": ((6, 1, 3 * (96 + 96 + 384)), jnp.float32),
    }
    logits, _ = model(params, jnp.asarray(ids)[None], cache)
    have = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    rows = list(range(len(ids)))
    wanted = np.argsort(-have, axis=-1)[:, :8]
    want = reference_at(TINY, ids, rows, wanted)
    np.testing.assert_allclose(np.take_along_axis(have, wanted, -1), want, atol=LP_TOL)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_moves_the_reference_far_past_the_tolerance(fault):
    """A lost state, ``beta`` left in (0, 1), a per-head QK norm, a pre-norm
    linear mixer, rotary on the NoPE layers, bf16 state, fp8 weights: each
    would fail the comparisons of this file by two orders of magnitude and
    more."""
    ids = PROMPTS["border"]
    rows, wanted = list(range(8, len(ids))), [list(range(1, 9))] * (len(ids) - 8)
    clean = reference_at(TINY, ids, rows, wanted)
    moved = np.abs(reference_at(TINY, ids, rows, wanted, fault) - clean).max()
    assert moved > 100 * LP_TOL, (fault, moved)


# --------------------------------------------------- the recurrence's forms


def _inputs(b, t, h, dk, dv, a_max, beta_scale=2.0, seed=0):
    """``h`` heads, keys ``dk`` wide and values ``dv``, one decay a head,
    ``beta`` in ``(0, beta_scale)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2norm(jax.random.normal(ks[0], (b, t, h, dk))) * dk**-0.5
    k = kda._l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    # head 0 decays at exp(A_log) = a_max, the strongest; the last hardly
    g = -jnp.linspace(a_max, 0.01, h) * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = beta_scale * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, dk, dv))


def test_the_chunked_form_takes_a_rectangular_state():
    """Three blocks of 64 and a ragged one of 8 at keys 24 wide and values 48,
    ``beta`` in (0, 2): the sequential form's numbers to 2e-5 (float32 sums
    in another order; outputs are O(1))."""
    q, k, v, g, beta, s0 = _inputs(2, 200, 3, 24, 48, 16.0)
    o_seq, s_seq = kda.kda_sequential(q, k, v, g, beta, s0)
    o, s = jax.jit(kda.kda_chunked)(q, k, v, g, beta, s0)
    assert o.shape == (2, 200, 3, 48) and s.shape == (2, 3, 24, 48)
    np.testing.assert_allclose(o, o_seq, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, s_seq, atol=2e-5, rtol=2e-5)


@hard_timeout(600)
def test_beta_near_two_stays_bounded_over_two_thousand_positions():
    """``beta`` in (1.96, 2) — ``I - beta k k^T`` nearly a reflection, the
    eigenvalue ``1 - beta`` near -1 — and hardly any decay on the last head,
    2048 positions: the state and the outputs stay bounded in both forms (no
    eigenvalue leaves the unit disc) and the forms agree."""
    q, k, v, g, _, s0 = _inputs(1, 2048, 2, 16, 32, 1.0, seed=5)
    beta = 2.0 - 0.04 * jax.random.uniform(jax.random.PRNGKey(9), g.shape)
    o_seq, s_seq = jax.jit(kda.kda_sequential)(q, k, v, g, beta, s0)
    o, s = jax.jit(kda.kda_chunked)(q, k, v, g, beta, s0)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    # each step adds at most |beta v| to a state a reflection keeps the norm of
    assert float(jnp.abs(s_seq).max()) < 50 and float(jnp.abs(o_seq).max()) < 50
    np.testing.assert_allclose(o, o_seq, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(s, s_seq, atol=2e-3, rtol=2e-3)


_CHUNK_SHAPES = {
    # name: (heads, Dk, Dv, a decay a key channel?, beta's scale)
    "scalar-24x48-beta2": (3, 24, 48, False, 2.0),
    "scalar-square": (2, 32, 32, False, 1.0),
    "per-channel": (2, 32, 32, True, 1.0),
}
_CHUNK_ROWS = {
    # name: (T, n_valid, the sequences that advance)
    "whole-blocks": (128, None, None),
    "ragged-last-block": (136, None, None),
    "n_valid-inside-a-block": (192, 100, None),
    "whole-blocks-past-n_valid": (256, 70, None),
    "an-inactive-sequence": (128, 128, [True, False]),
}


@hard_timeout(600)
@pytest.mark.parametrize("rows", list(_CHUNK_ROWS))
@pytest.mark.parametrize("shape", list(_CHUNK_SHAPES))
def test_the_chunk_kernel_is_the_sequential_recurrence(shape, rows):
    """``ops.kda._advance`` over a chunk with the Pallas pass in interpret
    mode (``kda_chunk_call``: a block's pairwise sums, its unit lower
    triangular system by substitution and the three products with the carried
    state in one kernel) against the definition run on the valid rows alone:
    2e-5 on the valid rows of ``o`` and on the state, the array form's
    tolerance. Rows past ``n_valid`` advance nothing (whole blocks past it
    are not even computed), and an inactive sequence keeps its state."""
    h, dk, dv, per_channel, beta_scale = _CHUNK_SHAPES[shape]
    t, n_valid, active = _CHUNK_ROWS[rows]
    q, k, v, g, beta, s0 = _inputs(2, t, h, dk, dv, 16.0, beta_scale, seed=7)
    if per_channel:
        g = g[..., None] * jax.random.uniform(jax.random.PRNGKey(8), k.shape, minval=0.2)
    pool = jnp.stack([jnp.zeros_like(s0), s0])  # layer 1 of two, heads apart
    tail = jnp.zeros((2, 3, 8))
    before = kda.dispatch_counts()
    o, new, _ = kda._advance(
        pool, jnp.asarray(1), q, k, v, g, beta, tail, tail + 1.0,
        None if n_valid is None else jnp.asarray(n_valid),
        None if active is None else jnp.asarray(active), kda.CHUNK, True,
    )
    after = kda.dispatch_counts()
    assert after["chunk_kernel"] == before["chunk_kernel"] + 1
    assert after["chunk_xla"] == before["chunk_xla"]
    n = t if n_valid is None else n_valid
    o_seq, s_seq = kda.kda_sequential(q[:, :n], k[:, :n], v[:, :n], g[:, :n], beta[:, :n], s0)
    assert o.shape == (2, t, h, dv) and np.isfinite(o).all()
    np.testing.assert_allclose(o[:, :n], o_seq, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(new[0], pool[0])
    if active is None:
        np.testing.assert_allclose(new[1], s_seq, atol=2e-5, rtol=2e-5)
    else:
        np.testing.assert_allclose(new[1, 0], s_seq[0], atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(new[1, 1], s0[1])


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_a_block_of_one_repeated_key_at_beta_near_two(interpret):
    """The block only substitution survives: 64 keys that are ONE unit
    vector, ``beta`` in (1.96, 2), almost no decay. ``I + A`` is then 1 on the
    diagonal and nearly 2 everywhere below it: its inverse has entries of
    about 2 in alternating signs, while ``A``'s powers grow by binomials
    (``C(63, 31) 2^32``) before they cancel, so a Neumann or repeated-squaring
    product loses every float32 digit (shown below on the block's own
    matrix). Both forms solve row after row (the kernel inside 16 x 16
    diagonal blocks, then block after block) and give the sequential form's
    numbers, outputs up to 6 in magnitude, to 1e-4, over a second block of
    ordinary keys as well."""
    q, k, v, g, _, s0 = _inputs(1, 128, 2, 24, 48, 1.0, seed=11)
    k = k.at[:, :64].set(jnp.broadcast_to(k[:, :1], k[:, :64].shape))
    g = 1e-4 * g
    beta = 2.0 - 0.04 * jax.random.uniform(jax.random.PRNGKey(12), g.shape)
    o_seq, s_seq = kda.kda_sequential(q, k, v, g, beta, s0)
    assert float(jnp.abs(o_seq).max()) < 50 and float(jnp.abs(s_seq).max()) < 50
    o, s = kda.kda_chunked(q, k, v, g, beta, s0, kda.CHUNK, None, interpret)
    np.testing.assert_allclose(o, o_seq, atol=1e-4, rtol=2e-5)
    np.testing.assert_allclose(s, s_seq, atol=1e-4, rtol=2e-5)
    # the same system by repeated squaring, (I - A)(I + A^2)(I + A^4)...:
    # exact in exact arithmetic (A^64 = 0), useless in float32
    a = np.tril(np.asarray(beta[0, :64, 0])[:, None] * np.ones((64, 64), np.float32), -1)
    eye = np.eye(64, dtype=np.float32)
    series, power = eye - a, a @ a
    for _ in range(5):
        series, power = series @ (eye + power), power @ power
    exact = np.linalg.inv((eye + a).astype(np.float64))
    assert np.abs(exact).max() < 2.5
    assert not np.abs(series - exact).max() < 1.0  # off by more than the answer, or not finite


def test_the_chunked_form_counts_its_path_and_the_kernel_holds_no_solve():
    """One count a traced call of the chunked form, ``chunk_kernel`` or
    ``chunk_xla`` (a jitted call traced once counts once however often it
    runs); ``/metrics`` carries both paths; and the kernel path's program
    holds no ``triangular_solve`` (nor the ``custom_call`` XLA makes of it),
    where the array form's does."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    q, k, v, g, beta, s0 = _inputs(1, 128, 2, 24, 48, 4.0, seed=13)
    jaxprs = {}
    for interpret, path, other in ((False, "chunk_xla", "chunk_kernel"),
                                   (True, "chunk_kernel", "chunk_xla")):
        form = jax.jit(lambda *a, i=interpret: kda.kda_chunked(*a, kda.CHUNK, None, i))
        before = kda.dispatch_counts()
        form(q, k, v, g, beta, s0)
        form(q, k, v, g, beta, s0)
        after = kda.dispatch_counts()
        assert after[path] == before[path] + 1 and after[other] == before[other]
        assert after["kernel"] == before["kernel"] and after["xla"] == before["xla"]
        jaxprs[path] = str(jax.make_jaxpr(form)(q, k, v, g, beta, s0))
    assert "triangular_solve" in jaxprs["chunk_xla"]
    assert "triangular_solve" not in jaxprs["chunk_kernel"]
    assert "kda_chunk_local" in jaxprs["chunk_kernel"]
    # the lanes of a ``jax.vmap`` (an engine's micro-batches) take the array
    # form, bit for bit, as the step's do
    lanes = jax.vmap(lambda *a: kda.kda_chunked(
        *(z[None] for z in a), kda.CHUNK, jnp.asarray(128), True))
    before = kda.dispatch_counts()
    two = _inputs(2, 128, 2, 24, 48, 4.0, seed=14)
    o, s = lanes(*two)
    assert kda.dispatch_counts()["chunk_xla"] == before["chunk_xla"] + 1
    o_xla, s_xla = kda.kda_chunked(*two)
    np.testing.assert_array_equal(o[:, 0], o_xla)
    np.testing.assert_array_equal(s[:, 0], s_xla)
    text = ServingMetrics().render()
    counts = kda.dispatch_counts()
    for path in ("chunk_kernel", "chunk_xla"):
        assert f'mst_kda_dispatch_total{{path="{path}"}} {counts[path]}' in text


def test_lane_pack_and_its_two_views():
    assert kda.lane_pack(30, 192) == 2  # 384 lanes: three whole tiles
    assert kda.lane_pack(32, 128) == 1 and kda.lane_pack(6, 64) == 2
    assert kda.lane_pack(3, 64) == 1  # no count divides 3 and fills a tile
    s = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 16, 64))
    packed = kda.pack_heads(s, 2)
    assert packed.shape == (2, 3, 16, 128)
    # heads 2j and 2j + 1 side by side on the lanes
    np.testing.assert_array_equal(packed[:, 1, :, :64], s[:, 2])
    np.testing.assert_array_equal(packed[:, 1, :, 64:], s[:, 3])
    np.testing.assert_array_equal(kda.unpack_heads(packed, 2), s)
    assert kda.pack_heads(s, 1) is s and kda.unpack_heads(s, 1) is s


@pytest.mark.parametrize("pack", [1, 2], ids=["apart", "packed"])
@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_the_one_step_form_on_a_rectangular_tile(interpret, pack):
    """Keys 96 wide, values 192, six heads, ``beta`` in (0, 2): layer 1 of a
    pool of two, two sequences and a scratch row, heads apart ``(6, 96, 192)``
    or two side by side ``(3, 96, 384)`` as the published configuration keeps
    them. The active sequence gets the sequential form's step and the chunked
    form's (float32, 1e-5), the inactive one keeps its state, and nothing else
    of the pool moves."""
    q, k, v, g, beta, _ = _inputs(2, 1, 6, 96, 192, 16.0, seed=1)
    apart = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 6, 96, 192))
    pool = kda.pack_heads(apart, pack)
    before = kda.dispatch_counts()
    o, new = kda.kda_step(
        pool, jnp.asarray(1), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
        jnp.asarray([True, False]), interpret,
    )
    after = kda.dispatch_counts()
    path = "kernel" if interpret else "xla"
    assert after[path] == before[path] + 1
    assert new.shape == pool.shape
    new = kda.unpack_heads(new, pack)
    o_seq, s_seq = kda.kda_sequential(q, k, v, g, beta, apart[1, :2])
    np.testing.assert_allclose(o[0], o_seq[0, 0], atol=1e-5)
    np.testing.assert_allclose(new[1, 0], s_seq[0], atol=1e-5)
    o_chk, s_chk = kda.kda_chunked(q, k, v, g, beta, apart[1, :2])
    np.testing.assert_allclose(o[0], o_chk[0, 0], atol=1e-5)
    np.testing.assert_allclose(new[1, 0], s_chk[0], atol=1e-5)
    np.testing.assert_array_equal(new[1, 1:], apart[1, 1:])
    np.testing.assert_array_equal(new[0], apart[0])


def test_the_kernel_walks_a_packed_pool_in_head_blocks(monkeypatch):
    """Blocks of fewer lane groups than the pool has (the published shape
    walks 15 groups in blocks of 5): every block gets its own heads' columns."""
    monkeypatch.setattr(kda, "_STEP_BLOCK_BYTES", 2 * 16 * 128 * 4)
    q, k, v, g, beta, _ = _inputs(3, 1, 12, 16, 64, 8.0, seed=3)
    apart = jax.random.normal(jax.random.PRNGKey(4), (1, 3, 12, 16, 64))
    wide = jnp.broadcast_to(g[:, 0, :, None], k[:, 0].shape)
    # not jitted anew per patch: call the body under its own trace
    o, new = kda.kda_pool_step.__wrapped__(
        kda.pack_heads(apart, 2), 0, q[:, 0], k[:, 0], v[:, 0], wide, beta[:, 0],
        interpret=True)
    o_seq, s_seq = kda.kda_sequential(q, k, v, g, beta, apart[0])
    np.testing.assert_allclose(o, o_seq[:, 0], atol=1e-5)
    np.testing.assert_allclose(kda.unpack_heads(new, 2)[0], s_seq, atol=1e-5)


def _mixer_args(tiny, t, b=2, seed=4):
    model, params = tiny
    p = LayerRow(params["layers"]["gdn"], 1)
    u = jax.random.normal(jax.random.PRNGKey(seed), (b, t, 96), jnp.float32)
    pool = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, b + 1, 3, 16, 128))
    tail = jax.random.normal(jax.random.PRNGKey(seed + 2), (b, 3, model.conv_dim))
    kw = dict(key_heads=6, value_heads=6, head_dim=16, value_dim=64, beta_scale=2.0,
              taps=4, eps=1e-6)
    return model, p, u, pool, tail, kw


def test_rows_past_n_valid_and_inactive_slots_advance_nothing(tiny):
    """A chunk of 16 of which 13 rows are valid, the second sequence inactive,
    on the packed pool: the first sequence's state, tail and valid outputs are
    those of the 13 rows alone; the second's state and tail stay; no other
    layer's rows and not the scratch row move."""
    model, p, u, pool, tail, kw = _mixer_args(tiny, 16)
    linear = model._linear
    out, new, new_tail = kda.gdn_mixer(
        linear, p, u, pool, jnp.asarray(1), tail, jnp.asarray(13),
        jnp.asarray([True, False]), **kw)
    want, want_pool, want_tail = kda.gdn_mixer(
        linear, p, u[:1, :13], pool, jnp.asarray(1), tail[:1], None, None, **kw)
    np.testing.assert_allclose(out[0, :13], want[0], atol=1e-5)
    np.testing.assert_allclose(new[1, 0], want_pool[1, 0], atol=1e-5)
    np.testing.assert_allclose(new_tail[0], want_tail[0], atol=1e-6)
    np.testing.assert_array_equal(new[1, 1:], pool[1, 1:])
    np.testing.assert_array_equal(new_tail[1], tail[1])
    np.testing.assert_array_equal(new[jnp.asarray([0, 2])], pool[jnp.asarray([0, 2])])


def test_a_chunk_then_steps_equal_one_chunk_on_the_packed_pool(tiny):
    """Ten rows as one chunk, and as a chunk of 7 then three decode steps (the
    kernel in interpret mode): the same outputs and the same packed state."""
    model, p, u, pool, tail, kw = _mixer_args(tiny, 10, b=1)
    whole, pool_w, _ = kda.gdn_mixer(model._linear, p, u, pool, 1, tail, None, None, **kw)
    out, pool_s, tail_s = kda.gdn_mixer(model._linear, p, u[:, :7], pool, 1, tail, None, None, **kw)
    outs = [out]
    for t in range(7, 10):
        out, pool_s, tail_s = kda.gdn_mixer(
            model._linear, p, u[:, t:t + 1], pool_s, 1, tail_s, None, None,
            interpret=True, **kw)
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole, atol=1e-5)
    np.testing.assert_allclose(pool_s, pool_w, atol=1e-5)


# ---------------------------------------------- through pool and state


def test_the_state_pool_sits_beside_the_k_v_pages(batcher):
    eng = batcher.engine
    assert eng.has_state and eng.has_recurrent and not eng.ring_rows
    assert eng.layers_per_stage == 2 and eng.state_layers == 6
    cache, _ = eng.init_cache_paged()
    # 16 pages + scratch in the two attention layers; a row is all three heads
    assert cache.k.shape == cache.v.shape == (1, 2, 17, 1, PAGE, 1, 96)
    # 2 slots + the scratch row in the six linear layers, two heads a lane group
    assert cache.state["gdn"].shape == (1, 6, 3, 3, 16, 128)
    assert cache.state["conv"].shape == (1, 6, 3, 3 * 576)
    assert eng.state_bytes() == 6 * 3 * (6 * 16 * 64 + 3 * 576) * 4


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks whose borders fall inside and between the
    convolutions' taps (the chunked form on a rectangular state, ``beta`` in
    (0, 2)), then decode through the K/V pages (the ragged path at three
    merged heads, a query group of one) and the packed state pool:
    log-probabilities against the reference's one full-sequence pass."""
    got = served(batcher, PROMPTS[name], 14)
    np.testing.assert_allclose(differences(TINY, PROMPTS[name], got), 0, atol=LP_TOL)


@hard_timeout(900)
@pytest.mark.parametrize("fault", [
    "gdn_state_bf16", "beta_unscaled", "qk_norm_per_head", "gdn_state_reset",
    "linear_prenorm", "rope_on",
])
def test_the_served_path_with_a_fault_is_not_the_reference(batcher, fault):
    """The float32 state, ``beta``'s factor of two, the norm over the WHOLE
    query and key projections, the state carried from prefill into decode, the
    reordered norm on the linear layers and the absence of rotary: the served
    path is tens of tolerances from each wrong variant."""
    got = served(batcher, PROMPTS["one-past"], 12)
    assert np.abs(differences(TINY, PROMPTS["one-past"], got, fault)).max() > 30 * LP_TOL


@hard_timeout(900)
def test_slots_join_and_leave_mid_run_and_a_reused_slot_starts_from_zero(batcher):
    """Three requests on two slots, each against the reference's full pass
    over its own sequence, LOGITS not tokens: the third joins while another
    decodes (its chunks run between the other's decode blocks, which must
    leave its state rows alone) and takes a slot whose state its last
    occupant left behind."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    jobs = {"inside": 9, "border": 14, "one-past": 11}
    resets0 = batcher.state_stats()["resets"]
    outs: dict = {}

    def run(name, n):
        try:
            outs[name] = served(batcher, PROMPTS[name], n)
        except Exception as e:  # noqa: BLE001 — surfaced below
            outs[name] = e

    threads = [threading.Thread(target=run, args=job, daemon=True) for job in jobs.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "generation thread hung"
    for name, n in jobs.items():
        assert not isinstance(outs[name], Exception), outs[name]
        assert len(outs[name]) == n
        np.testing.assert_allclose(
            differences(TINY, PROMPTS[name], outs[name]), 0, atol=LP_TOL, err_msg=name)
    assert batcher.state_stats()["resets"] - resets0 == 3
    text = ServingMetrics(batcher_fn=lambda: batcher).render()
    assert f"mst_state_bytes {batcher.engine.state_bytes()}" in text
    assert 'mst_kda_dispatch_total{path="xla"}' in text


@hard_timeout(900)
def test_the_gather_body_the_dense_cache_and_the_solo_generator_agree(tiny, batcher):
    model, params = tiny
    want = [t for t, _ in served(batcher, PROMPTS["one-past"], 10)]
    for kw in (dict(paged_attention="gather"), dict(paged=False)):
        other = ContinuousBatcher(make_engine(model, params, **kw), decode_block=4)
        try:
            assert [t for t, _ in other.generate_step(PROMPTS["one-past"], max_tokens=10)] == want
        finally:
            other.close()
    gen = Generator(model, params, max_seq=MAX_SEQ, cache_dtype=jnp.float32,
                    prefill_chunk=PAGE, decode_block=4)
    assert [t for t, _ in gen.generate_step(PROMPTS["one-past"], max_tokens=10)] == want


# ------------------------------------------------------------ refusals


@pytest.mark.parametrize("flag,build", [
    ("--prompt-cache", lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_cache=True)),
    ("--draft", lambda m, p: ContinuousBatcher(make_engine(m, p), draft="ngram")),
    ("--kv-share-map", lambda m, p: make_engine(m, p, kv_share_map=object())),
], ids=["prompt-cache", "draft", "kv-share-map"])
def test_what_re_enters_a_sequence_from_pages_alone_is_refused_by_name(tiny, flag, build):
    with pytest.raises(ValueError, match="recurrent state") as err:
        build(*tiny)
    assert flag in str(err.value) and "OlmoHybridModel" in str(err.value)


@pytest.mark.parametrize("kw,what", [
    (dict(pp=2), r"not wired for olmo_hybrid.*--num-stages 1"),
    (dict(tp=2), "tensor parallelism is not wired for OlmoHybridModel"),
], ids=["num-stages", "tp"])
def test_other_layouts_refuse_by_name(tiny, kw, what):
    model, params = tiny
    mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=what):
        PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)


def test_unwired_configurations_refuse_by_name():
    with pytest.raises(ValueError, match=r"olmo_hybrid.*--num-stages 1"):
        build_model({**TINY, "start_layer": 0, "end_layer": 4})
    for bad in (dict(attention_bias=True), dict(hidden_act="gelu"),
                dict(tie_word_embeddings=True), dict(rope_scaling={"type": "linear", "factor": 2}),
                dict(rope_parameters={"rope_theta": 500000.0})):  # NoPE: no rotary is wired
        with pytest.raises(ValueError, match="olmo_hybrid is wired for"):
            build_model({**TINY, **bad})
    with pytest.raises(ValueError, match="must divide linear_num_value_heads"):
        build_model({**TINY, "linear_num_key_heads": 4})
    with pytest.raises(ValueError, match="must name num_hidden_layers"):
        build_model({**TINY, "num_hidden_layers": 4})
    with pytest.raises(ValueError, match="unknown layer_types"):
        build_model({**TINY, "layer_types": ["sliding_attention"] * 8})
    with pytest.raises(ValueError, match="norms q and k over hidden_size"):
        build_model({**TINY, "head_dim": 16})
    # no layer_types: the published period
    model, _ = build_model({k: v for k, v in TINY.items() if k != "layer_types"})
    assert model.layer_groups == ["gdn", "gdn", "gdn", "attn"] * 2


# ------------------------------------------------- weights and the tables


def test_map_weights_joins_the_published_tensors(tiny):
    """A checkpoint under the family's tensor names (torch orientation: a
    linear is ``(out, in)``, a convolution ``(C, 1, k)``; ``q/k/v/g_proj``,
    ``b_proj`` / ``a_proj`` and the three convolutions apart) loads into the
    tree the model runs: the parts joined in column order."""
    model, params = tiny
    t = lambda w: np.asarray(w).T  # noqa: E731
    hf = {"model.embed_tokens.weight": params["embed"]["weight"],
          "model.norm.weight": params["final_norm"]["weight"],
          "lm_head.weight": t(params["lm_head"]["weight"])}
    widths = {"qkvz_proj": (96, 96, 384, 384), "ba_proj": (6, 6), "conv_w": (96, 96, 384)}
    for g, idxs in model.layer_group_layers().items():
        stack = params["layers"][g]
        for rank, i in enumerate(idxs):
            pre = f"model.layers.{i}."
            for suffix, (our, transposed) in model.NAMES[g].items():
                w = stack[our][rank]
                hf[pre + suffix] = t(w) if transposed else w
            if g != "gdn":
                continue
            for our, parts in model.JOINED.items():
                w = np.asarray(stack[our][rank])
                cuts = np.cumsum((0, *widths[our]))
                for part, lo, hi in zip(parts, cuts, cuts[1:]):
                    hf[pre + f"linear_attn.{part}.weight"] = (
                        w[lo:hi, None, :] if our == "conv_w" else t(w[:, lo:hi]))
    hf = {k: np.asarray(v) for k, v in hf.items()}
    assert hf["model.layers.0.linear_attn.v_conv1d.weight"].shape == (384, 1, 4)
    assert hf["model.layers.0.linear_attn.g_proj.weight"].shape == (384, 96)
    jax.tree.map(np.testing.assert_array_equal, model.map_weights(hf, jnp.float32), params)


def _published():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    path = Path(__file__).parents[1] / "benchmarks/configs/olmo-hybrid-7b-bf16-pp2.json"
    return published_config(json.loads(path.read_text()))


def test_step_bytes_of_the_published_configuration():
    """ISSUE 58's arithmetic: a linear layer 215.4 M parameters, a full layer
    185.8 M, 16 layers 3328 M, with embedding and head 8.20 GB; 2,211,840 +
    69,120 B of state a slot and linear layer, no padded byte; 15,360 B of
    K/V a token a full layer."""
    cfg = _published()
    assert ref.layer_groups(cfg) == ["gdn", "gdn", "gdn", "attn"] * 4
    units = ref.model_units(cfg)
    params = {g: sum(u.out * u.inn for u in units[g].values()) for g in ref.GROUPS}
    assert params["gdn"] == 3840 * (2880 + 2880 + 5760 + 5760 + 60) + 5760 * 3840 + 3 * 3840 * 11008
    assert round(params["gdn"] / 1e6, 1) == 215.5  # ISSUE 58's 215.4 and the 60 columns of b and a
    assert round(params["attn"] / 1e6, 1) == 185.8
    held = 2 * (12 * params["gdn"] + 4 * params["attn"] + 2 * 100352 * 3840)
    assert 8.19e9 < held < 8.22e9
    need = ref.decode_step_bytes(cfg, "bf16", 48, 48 * 512)
    # every layer's matrices and the head: the embedding's rows are not read
    assert 7.42e9 < need["fixed_weights"] < 7.45e9
    assert need["recurrent_state"] == ref.kda_state_step_bytes(cfg, 48)
    assert need["recurrent_state"] == 2 * 48 * 12 * (30 * 96 * 192 * 4 + 11520 * 3 * 2)
    assert 30 * 96 * 192 * 4 == 2211840 and 11520 * 3 * 2 == 69120
    assert ref.kv_row_bytes(cfg) == 15360
    assert need["kv_pages"] == 48 * 512 * 4 * 15360 == ref.paged_attn_step_bytes(cfg, 48, 512)
    assert need["total"] == sum(v for k, v in need.items() if k != "total")
    # the program's own shapes at the published widths: 15 lane groups of 384
    model, _ = build_model(cfg)
    shapes = model.state_shapes(48)
    assert shapes["gdn"][0] == (48, 15, 96, 384) and shapes["conv"][0] == (48, 3 * 11520)
    assert kda.step_kernel_eligible(jax.ShapeDtypeStruct((12, 49, 15, 96, 384), jnp.float32), True)


def test_the_seeded_tree_is_the_program_s(tiny):
    model, params = tiny
    made = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params)
    # a norm's weight is the seeded vector itself: plain, not zero-centred
    unit = ref.model_units(TINY)["attn"]["q_norm"]
    np.testing.assert_array_equal(
        params["layers"]["attn"]["q_norm"][1], W.logical_norm(W.seed_key(SEED), unit, 1))
    assert params["layers"]["attn"]["q_norm"].shape == (2, 96)  # the whole projection's
