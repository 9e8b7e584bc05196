"""Quantized memory hierarchy (ISSUE 5 tentpole): bit-exact parity for the
4-bit kernel at decode rows and the build-time fused projections against
the golden dequant reference, and the int8 paged-KV contracts — greedy streams
token-identical to the bf16 pool on both paged-attention paths, a bounded
per-element quantization error, and code-exact requantize-on-writeback.

Bit-exactness strategy: every operand is constructed integer-valued
(scales 1.0, biases -2^(bits-1), integer activations), so all float32
sub-dot accumulations are exact regardless of summation order and any
kernel/XLA/fused variant of the same math must agree to the last bit.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.cache import dequantize_kv, quantize_kv_rows
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.models.base import apply_projection_fusion
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.ops.paged_attention import paged_attention
from mlx_sharding_tpu.ops.quant import dequantize, fuse_packed, linear
from mlx_sharding_tpu.ops.quant_matmul import quant_matmul_pallas
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher

GS = 64


def _exact_packed(rng, out_dim, in_dim, bits):
    """A packed triple whose dequantized values are exact small integers:
    random codes, scale 1.0, bias -2^(bits-1) → values in [-2^(b-1), 2^(b-1))."""
    words = in_dim * bits // 32
    q = rng.integers(0, 2 ** 32, size=(out_dim, words), dtype=np.uint32)
    scales = np.ones((out_dim, in_dim // GS), np.float32)
    biases = np.full(
        (out_dim, in_dim // GS), -float(2 ** (bits - 1)), np.float32
    )
    return q, scales, biases


def _bitexact_case(rng, m, in_dim, out_dim, bits):
    q, s, b = _exact_packed(rng, out_dim, in_dim, bits)
    x = rng.integers(-4, 4, size=(m, in_dim)).astype(np.float32)
    dq = np.asarray(
        dequantize(jnp.asarray(q), jnp.asarray(s), jnp.asarray(b),
                   group_size=GS, bits=bits, dtype=jnp.float32)
    )
    # exact integer reference; fits fp32 exactly (|sum| << 2^24)
    want = (x.astype(np.int64) @ dq.astype(np.int64).T).astype(np.float32)
    return x, q, s, b, want


@pytest.mark.parametrize("m", [1, 8])
def test_matmul_bitexact_vs_golden_at_decode_rows(m):
    """The one projection kernel at a single stream's row and at 8 slots'
    must reproduce the golden dequant matmul to the last bit (2 IN blocks →
    the accumulator carries across grid steps)."""
    rng = np.random.default_rng(20)
    x, q, s, b, want = _bitexact_case(rng, m, in_dim=512, out_dim=256, bits=4)
    got = quant_matmul_pallas(
        jnp.asarray(x), jnp.asarray(q), jnp.asarray(s), jnp.asarray(b),
        group_size=GS, bits=4, block_out=128, block_in=256, interpret=True,
    )
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.slow
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("m", [1, 2, 4, 8])
@pytest.mark.parametrize("in_dim,out_dim", [(512, 128), (1024, 256)])
def test_matmul_parity_matrix_at_decode_rows(bits, m, in_dim, out_dim):
    """Full sweep: the 3-D-grid kernel at every decode M, both packed
    widths — all bit-exact vs the golden reference."""
    rng = np.random.default_rng(21)
    x, q, s, b, want = _bitexact_case(rng, m, in_dim, out_dim, bits)
    ops = [jnp.asarray(a) for a in (x, q, s, b)]
    grid = quant_matmul_pallas(
        *ops, group_size=GS, bits=bits, block_m=8, block_out=128,
        block_in=in_dim // 2, interpret=True,
    )
    assert np.array_equal(np.asarray(grid), want)


def test_linear_one_row_dispatches_to_the_kernel(monkeypatch):
    """On a TPU ops.quant.linear sends a single stream's one row to the
    Pallas kernel, counted once. Traced, not run: a TPU kernel does not run
    on the CPU."""
    from mlx_sharding_tpu.ops import quant

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(22)
    x, q, s, b, _ = _bitexact_case(rng, 1, in_dim=512, out_dim=256, bits=4)
    packed = {"q": jnp.asarray(q), "scales": jnp.asarray(s),
              "biases": jnp.asarray(b)}
    before = quant.dispatch_counts()
    text = str(jax.make_jaxpr(lambda x: linear(x, packed, GS, 4))(jnp.asarray(x)))
    after = quant.dispatch_counts()
    assert "quant_matmul" in text
    assert after == {"matmul": before["matmul"] + 1, "xla": before["xla"]}


def test_fused_projection_bitexact():
    """fuse_packed concatenates triples along OUT: the fused weight must
    dequantize to exactly the concatenation, and one fused matmul must be
    bit-identical to the separate projections it replaces (each fused
    output row runs the identical sub-dot sequence)."""
    rng = np.random.default_rng(23)
    in_dim = 256
    parts, denses = [], []
    for out_dim in (128, 64, 64):  # qkv-shaped GQA split
        q, s, b = _exact_packed(rng, out_dim, in_dim, bits=4)
        parts.append({"q": jnp.asarray(q), "scales": jnp.asarray(s),
                      "biases": jnp.asarray(b)})
        denses.append(np.asarray(dequantize(
            parts[-1]["q"], parts[-1]["scales"], parts[-1]["biases"],
            group_size=GS, bits=4, dtype=jnp.float32)))
    fused = fuse_packed(parts)
    assert np.array_equal(
        np.asarray(dequantize(fused["q"], fused["scales"], fused["biases"],
                              group_size=GS, bits=4, dtype=jnp.float32)),
        np.concatenate(denses, axis=0),
    )
    x = jnp.asarray(
        rng.integers(-4, 4, size=(1, in_dim)).astype(np.float32)
    )
    want = np.concatenate(
        [np.asarray(linear(x, p, GS, 4)) for p in parts], axis=-1
    )
    assert np.array_equal(np.asarray(linear(x, fused, GS, 4)), want)


def test_apply_projection_fusion_rewrites_packed_stacks():
    """The build-time rewrite: packed q/k/v and gate/up triples collapse to
    qkv_proj / gate_up_proj, originals removed; dense stacks are left
    alone (fusion is a packed-checkpoint optimization only)."""
    model = LlamaModel(LlamaConfig(**TINY))
    rng = np.random.default_rng(24)

    def triple(out_dim, in_dim):
        q, s, b = _exact_packed(rng, out_dim, in_dim, 4)
        return {"q": jnp.asarray(q), "scales": jnp.asarray(s),
                "biases": jnp.asarray(b)}

    stack = {
        "q_proj": triple(128, 64), "k_proj": triple(64, 64),
        "v_proj": triple(64, 64), "o_proj": triple(64, 128),
        "gate_proj": triple(64, 64), "up_proj": triple(64, 64),
        "down_proj": triple(64, 64),
        "input_norm": jnp.ones((64,)),
    }
    fused = apply_projection_fusion(model, stack)
    assert sorted(fused) == ["gate_up_proj", "qkv_proj"]
    assert "q_proj" not in stack and "gate_proj" not in stack
    assert stack["qkv_proj"]["q"].shape[0] == 128 + 64 + 64
    assert stack["gate_up_proj"]["q"].shape[0] == 128

    dense_stack = {"q_proj": jnp.ones((4, 8)), "k_proj": jnp.ones((4, 8)),
                   "v_proj": jnp.ones((4, 8))}
    assert apply_projection_fusion(model, dense_stack) == []
    assert "qkv_proj" not in dense_stack


# --------------------------------------------------------------- int8 KV
TINY = dict(
    vocab_size=300, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
)


def _paged_pair(kv_dtype, pp=2, attention="auto"):
    cfg = LlamaConfig(**TINY)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(pp), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
        paged_attention=attention, kv_dtype=kv_dtype,
    )
    return ContinuousBatcher(eng, decode_block=3)


def _streams(batcher, jobs):
    # close on exit: a leaked scheduler thread skews the wedge-timing
    # tests that run after this module
    out = [None] * len(jobs)

    def work(i, prompt, kw):
        out[i] = [t for t, _ in batcher.generate_step(prompt, **kw)]

    try:
        threads = [threading.Thread(target=work, args=(i, p, kw))
                   for i, (p, kw) in enumerate(jobs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        batcher.close()
    assert all(r is not None for r in out)
    return out


JOBS = [
    ([3, 17, 42], dict(max_tokens=12)),
    ([9, 1, 5, 8, 2, 250, 11], dict(max_tokens=10)),
]


@pytest.mark.parametrize(
    "pp,attention",
    # gather rides the slow tier: ragged is the serving default and pins the
    # same quantize-on-writeback path; the pp=2 gather sweep is the heavy leg
    [pytest.param(2, "gather", marks=pytest.mark.slow), (1, "ragged")],
    ids=["gather", "ragged"],
)
def test_int8_kv_greedy_token_identical(pp, attention):
    """Greedy decode through the int8 pool must emit the exact token
    stream of the bf16 pool on both paged-attention paths — multi-block
    decode (block 3, 10-12 tokens) exercises quantize-on-writeback /
    scatter several times per stream. Per-element KV error is bounded by
    max|row|/254 (half an int8 step); at the tiny model's logit margins
    that perturbation never flips an argmax."""
    want, got = (
        _streams(_paged_pair(kv, pp=pp, attention=attention), JOBS)
        for kv in (None, "int8")
    )
    assert got == want


def test_int8_writeback_reuse_roundtrip():
    """Pages freed by a finished int8 stream are reused by the next one
    (quantize → scatter → dequant-read → free → reallocate): back-to-back
    serial runs through one batcher must reproduce their own streams."""
    batcher = _paged_pair("int8")
    try:
        # what an int8 page costs, counted on the pool's own leaves: D codes
        # and one f32 scale a row-head (D + 4 bytes where bf16 holds 2D, so
        # equal bytes hold 2D / (D + 4) times the tokens)
        codes, scales = batcher.cache.k["d"], batcher.cache.k["s"]
        d = codes.shape[-1]
        assert codes.dtype == jnp.int8 and scales.dtype == jnp.float32
        assert scales.shape == codes.shape[:-1] + (1,)
        pool_bytes = sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((batcher.cache.k, batcher.cache.v))
        )
        assert pool_bytes == 2 * (codes.size // d) * (d + 4)
        first = [
            [t for t, _ in batcher.generate_step(p, **kw)] for p, kw in JOBS
        ]
        again = [
            [t for t, _ in batcher.generate_step(p, **kw)] for p, kw in JOBS
        ]
        assert again == first
    finally:
        batcher.close()


def test_quantize_kv_rows_error_bound_and_requant_idempotence():
    """The two numeric contracts the engine relies on: (1) per-element
    round-trip error ≤ half an int8 step = max|row-head|/254 — the
    documented tolerance behind the greedy-identical tests; (2) re-
    quantizing a dequantized row reproduces the codes exactly (the stored
    max element sits at ±127, pinning the recomputed scale), which is what
    makes the gather path's writeback of untouched rows a no-op."""
    rng = np.random.default_rng(25)
    x = (rng.standard_normal((5, 3, 4, 32)) *
         rng.uniform(0.01, 10, (5, 3, 4, 1))).astype(np.float32)
    packed = quantize_kv_rows(jnp.asarray(x))
    dq = np.asarray(dequantize_kv(packed, jnp.float32))
    amax = np.abs(x).max(axis=-1, keepdims=True)
    assert np.all(np.abs(dq - x) <= amax / 254 + 1e-8)

    repacked = quantize_kv_rows(jnp.asarray(dq))
    assert np.array_equal(np.asarray(repacked["d"]), np.asarray(packed["d"]))
    np.testing.assert_allclose(
        np.asarray(repacked["s"]), np.asarray(packed["s"]), rtol=1e-6
    )


def test_paged_attention_int8_scales_atol():
    """Op level: the fused dequant (codes × per-row scale inside the page
    read) must match attention over the explicitly dequantized pool almost
    exactly (same numbers, different fusion point), and sit within the
    quantization-noise envelope of the original f32 pool — atol 2e-2 for
    unit-variance data, documented here as the int8-KV logits tolerance."""
    rng = np.random.default_rng(26)
    m, spg, page, hkv, d = 3, 4, 8, 2, 16
    lengths = [5, 17, 32]
    n_pages = m * spg
    k_pool = rng.standard_normal((n_pages + 1, page, hkv, d)).astype(np.float32)
    v_pool = rng.standard_normal((n_pages + 1, page, hkv, d)).astype(np.float32)
    tables = np.full((m, spg), n_pages, np.int32)
    for i, ln in enumerate(lengths):
        used = -(-ln // page)
        tables[i, :used] = np.arange(i * spg, i * spg + used)
    q = rng.standard_normal((m, 4, d)).astype(np.float32)
    scale = d ** -0.5

    kq = quantize_kv_rows(jnp.asarray(k_pool))
    vq = quantize_kv_rows(jnp.asarray(v_pool))
    args = (jnp.asarray(q),)
    common = dict(interpret=False)
    fused = paged_attention(
        *args, kq["d"], vq["d"], jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), scale,
        k_scale=kq["s"], v_scale=vq["s"], **common,
    )
    explicit = paged_attention(
        *args, dequantize_kv(kq), dequantize_kv(vq), jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), scale, **common,
    )
    original = paged_attention(
        *args, jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), scale, **common,
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(explicit), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(original), atol=2e-2, rtol=0
    )
