"""Packed 4-bit residency for the MoE models (VERDICT r2 item 3).

The BASELINE primary checkpoint (DeepSeek-Coder-V2-Lite-4bit) must load with
--keep-quantized: MLA projections and the (E, …) expert stacks stay packed
in HBM and dequantize inside the matmuls; the router (fp32 routing einsum)
and — in compressed cache mode — kv_b (absorbed into einsums as a tensor)
load dense via packed_keep_dense_re. Reference quant predicate:
shard/utils.py:54-65. Parity contract: packed load produces the exact token
stream of the dequantize-at-load path, solo and on every engine/mesh.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.ops.quant import is_quantized, quantize


def _write_quantized(tmp_path: Path, cfg: dict, spec, gs: int):
    """spec: iterable of (name, shape, quantized?) — quantized entries write
    MLX triples, including the routers/kv_b (the loader must decide what
    stays packed, not the checkpoint)."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(11)
    tensors = {}
    for name, shape, quant in spec:
        w = (rng.normal(size=shape) * 0.05).astype(np.float32)
        if quant:
            q, s, b = quantize(w, group_size=gs, bits=4)
            tensors[name] = q
            tensors[name.replace(".weight", ".scales")] = s
            tensors[name.replace(".weight", ".biases")] = b
        else:
            tensors[name] = w
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    return tmp_path


def _quantized_tiny_deepseek(tmp_path: Path, gs: int = 16, cache_mode="compressed"):
    hd, rank, heads = 64, 32, 4
    nope, rope, v_d = 16, 8, 16
    inter, mi, n_exp = 64, 32, 4
    cfg = dict(
        model_type="deepseek_v2", vocab_size=128, hidden_size=hd,
        intermediate_size=inter, moe_intermediate_size=mi,
        num_hidden_layers=3, num_attention_heads=heads,
        num_key_value_heads=heads, kv_lora_rank=rank, q_lora_rank=None,
        qk_rope_head_dim=rope, qk_nope_head_dim=nope, v_head_dim=v_d,
        n_routed_experts=n_exp, n_shared_experts=1, num_experts_per_tok=2,
        first_k_dense_replace=1, max_position_embeddings=256,
        rms_norm_eps=1e-5, rope_theta=10000.0,
        mla_cache_mode=cache_mode,
        quantization={"group_size": gs, "bits": 4},
    )
    spec = [
        ("model.embed_tokens.weight", (128, hd), False),
        ("model.norm.weight", (hd,), False),
        ("lm_head.weight", (128, hd), False),
    ]
    for i in range(3):
        p = f"model.layers.{i}"
        spec += [
            (f"{p}.input_layernorm.weight", (hd,), False),
            (f"{p}.post_attention_layernorm.weight", (hd,), False),
            (f"{p}.self_attn.kv_a_layernorm.weight", (rank,), False),
            (f"{p}.self_attn.q_proj.weight", (heads * (nope + rope), hd), True),
            (f"{p}.self_attn.kv_a_proj_with_mqa.weight", (rank + rope, hd), True),
            (f"{p}.self_attn.kv_b_proj.weight", (heads * (nope + v_d), rank), True),
            (f"{p}.self_attn.o_proj.weight", (hd, heads * v_d), True),
        ]
        if i < 1:  # dense layer
            spec += [
                (f"{p}.mlp.gate_proj.weight", (inter, hd), True),
                (f"{p}.mlp.up_proj.weight", (inter, hd), True),
                (f"{p}.mlp.down_proj.weight", (hd, inter), True),
            ]
        else:  # moe layer — router is quantized in the checkpoint too;
            # the loader must dequantize it (packed_keep_dense_re)
            spec += [
                (f"{p}.mlp.gate.weight", (n_exp, hd), True),
                (f"{p}.mlp.shared_experts.gate_proj.weight", (mi, hd), True),
                (f"{p}.mlp.shared_experts.up_proj.weight", (mi, hd), True),
                (f"{p}.mlp.shared_experts.down_proj.weight", (hd, mi), True),
            ]
            for e in range(n_exp):
                spec += [
                    (f"{p}.mlp.experts.{e}.gate_proj.weight", (mi, hd), True),
                    (f"{p}.mlp.experts.{e}.up_proj.weight", (mi, hd), True),
                    (f"{p}.mlp.experts.{e}.down_proj.weight", (hd, mi), True),
                ]
    return _write_quantized(tmp_path, cfg, spec, gs)


def _quantized_tiny_mixtral(tmp_path: Path, gs: int = 32):
    hd, inter, heads, hkv, d, n_exp = 64, 64, 4, 2, 16, 4
    cfg = dict(
        model_type="mixtral", vocab_size=128, hidden_size=hd,
        intermediate_size=inter, num_hidden_layers=2,
        num_attention_heads=heads, num_key_value_heads=hkv,
        num_local_experts=n_exp, num_experts_per_tok=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        quantization={"group_size": gs, "bits": 4},
    )
    spec = [
        ("model.embed_tokens.weight", (128, hd), False),
        ("model.norm.weight", (hd,), False),
        ("lm_head.weight", (128, hd), False),
    ]
    for i in range(2):
        p = f"model.layers.{i}"
        spec += [
            (f"{p}.input_layernorm.weight", (hd,), False),
            (f"{p}.post_attention_layernorm.weight", (hd,), False),
            (f"{p}.self_attn.q_proj.weight", (heads * d, hd), True),
            (f"{p}.self_attn.k_proj.weight", (hkv * d, hd), True),
            (f"{p}.self_attn.v_proj.weight", (hkv * d, hd), True),
            (f"{p}.self_attn.o_proj.weight", (hd, heads * d), True),
            (f"{p}.block_sparse_moe.gate.weight", (n_exp, hd), True),
        ]
        for e in range(n_exp):
            spec += [
                (f"{p}.block_sparse_moe.experts.{e}.w1.weight", (inter, hd), True),
                (f"{p}.block_sparse_moe.experts.{e}.w2.weight", (hd, inter), True),
                (f"{p}.block_sparse_moe.experts.{e}.w3.weight", (inter, hd), True),
            ]
    return _write_quantized(tmp_path, cfg, spec, gs)


def _tokens(model, params, prompt, max_tokens=8):
    from mlx_sharding_tpu.generate import Generator

    gen = Generator(model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8)
    return [t for t, _ in gen.generate_step(prompt, max_tokens=max_tokens)]


@pytest.mark.parametrize(
    "cache_mode",
    # decompressed rides the slow tier; compressed is the deployed MLA mode
    # and exercises the same packed MoE dispatch
    ["compressed", pytest.param("decompressed", marks=pytest.mark.slow)],
)
def test_deepseek_keep_quantized_matches_dense(tmp_path, cache_mode):
    from mlx_sharding_tpu.loading import load_model

    path = _quantized_tiny_deepseek(tmp_path, cache_mode=cache_mode)
    model_d, params_d = load_model(str(path), dtype=jnp.float32)
    model_p, params_p = load_model(str(path), dtype=jnp.float32, keep_quantized=True)

    moe = params_p["layers"]["moe"]
    assert is_quantized(moe["w_gate"])  # expert stacks stay packed
    assert moe["w_gate"]["q"].shape[:2] == (2, 4)  # (L_moe, E) leading dims
    assert not is_quantized(moe["router"])  # router forced dense
    kv_b = moe["kv_b_proj"]
    if cache_mode == "compressed":
        assert not is_quantized(kv_b)  # consumed as a tensor → dense
    else:
        assert is_quantized(kv_b)

    prompt = [3, 17, 42, 9]
    assert _tokens(model_p, params_p, prompt) == _tokens(model_d, params_d, prompt)


@pytest.mark.slow  # ~15s arch-matrix combo (packed x pipeline x EP)
def test_deepseek_packed_fused_pipeline_and_ep(tmp_path):
    """Packed grouped stacks through the fused SPMD engine: pp2 (uneven
    dense/moe split) and pp1 x ep2 (packed expert stacks sharded on their E
    axis) — exact parity with the solo packed run."""
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    path = _quantized_tiny_deepseek(tmp_path)
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    prompt = [5, 9, 2, 61]
    want = _tokens(model, params, prompt)

    for mesh_kw in (dict(pp=2), dict(pp=1, ep=2)):
        eng = PipelineEngine(
            model, params, make_mesh(**mesh_kw), max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
        )
        got = [t for t, _ in eng.generate_step(prompt, max_tokens=8)]
        assert got == want, f"{mesh_kw} diverged"
    # in the ep engine the packed E axis is the sharded one
    wq = eng.layer_params["moe"]["w_gate"]["q"]
    assert wq.sharding.shard_shape(wq.shape)[2] == wq.shape[2] // 2


@pytest.mark.slow  # ~12s arch-matrix combo (packed x TP)
def test_deepseek_packed_tensor_parallel(tmp_path):
    """TP x packed for MLA + experts: kv_b/q column-parallel (whole heads),
    o row-parallel, expert stacks split their intermediate dim — gs=16 keeps
    every row-split on a quant-group boundary."""
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    path = _quantized_tiny_deepseek(tmp_path, gs=16, cache_mode="decompressed")
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    prompt = [7, 3, 99, 12]
    want = _tokens(model, params, prompt)
    eng = PipelineEngine(
        model, params, make_mesh(pp=1, tp=2), max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    assert [t for t, _ in eng.generate_step(prompt, max_tokens=8)] == want
    # column-parallel packed expert gate: out (= mi) dim sharded
    wq = eng.layer_params["moe"]["w_gate"]["q"]
    assert wq.sharding.shard_shape(wq.shape)[3] == wq.shape[3] // 2


@pytest.mark.slow  # ~11s all-engine sweep; dense-parity gates stay tier-1
def test_mixtral_keep_quantized_all_engines(tmp_path):
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    path = _quantized_tiny_mixtral(tmp_path)
    model_d, params_d = load_model(str(path), dtype=jnp.float32)
    model_p, params_p = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    assert is_quantized(params_p["layers"]["w_gate"])
    assert not is_quantized(params_p["layers"]["router"])

    prompt = [9, 4, 120, 33]
    want = _tokens(model_d, params_d, prompt)
    assert _tokens(model_p, params_p, prompt) == want

    for mesh_kw in (dict(pp=2), dict(pp=1, ep=2)):
        eng = PipelineEngine(
            model_p, params_p, make_mesh(**mesh_kw), max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
        )
        got = [t for t, _ in eng.generate_step(prompt, max_tokens=8)]
        assert got == want, f"{mesh_kw} diverged"


def test_packed_gather_and_scan_paths_agree(tmp_path):
    """Decode (gather over packed leaves) and prefill (scan with fused
    dequant linears) must produce identical expert outputs."""
    from mlx_sharding_tpu.ops.moe import (
        GATHER_PATH_MAX_TOKENS,
        _apply_gather_packed,
        _apply_scan,
        mixtral_routing,
    )

    rng = np.random.default_rng(5)
    n, h, mi, e, k, gs = 8, 64, 32, 4, 2, 16
    assert n <= GATHER_PATH_MAX_TOKENS
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(h, e)), jnp.float32)

    wg, wu = _packed_stack(rng, e, mi, h, gs), _packed_stack(rng, e, mi, h, gs)
    wd = _packed_stack(rng, e, h, mi, gs)
    weights, idx = mixtral_routing(x, router, k)
    got_g = _apply_gather_packed(x, weights, idx, wg, wu, wd, gs, 4)
    got_s = _apply_scan(x, weights, idx, wg, wu, wd, gs, 4)
    np.testing.assert_allclose(np.asarray(got_g), np.asarray(got_s), rtol=1e-4, atol=1e-5)


def _packed_stack(rng, e, out_d, in_d, gs, zero=False):
    """(E, out, in) random weights as packed MLX-orientation leaves (f32
    scales and biases, as the loader widens them); ``zero``: a padded layer
    slot of the fused engine, every leaf all zeros."""
    ws = [
        quantize((rng.normal(size=(out_d, in_d)) * 0.1).astype(np.float32), gs, 4)
        for _ in range(e)
    ]
    stack = {
        "q": jnp.stack([jnp.asarray(w[0]) for w in ws]),
        "scales": jnp.stack([jnp.asarray(w[1], jnp.float32) for w in ws]),
        "biases": jnp.stack([jnp.asarray(w[2], jnp.float32) for w in ws]),
    }
    return jax.tree.map(jnp.zeros_like, stack) if zero else stack


def _routing(rng, n, e, k, picks):
    """(weights (N, K) f32, idx (N, K) int32) for a named pattern of picks."""
    if picks == "random":
        idx = np.stack([rng.permutation(e)[:k] for _ in range(n)])
    elif picks == "same":  # every row chooses the same experts
        idx = np.tile(rng.permutation(e)[:k], (n, 1))
    elif picks == "distinct":  # no expert is picked twice in the step
        assert n * k <= e
        idx = rng.permutation(e)[: n * k].reshape(n, k)
    elif picks == "neighbours":  # row r and row r+1 share one expert
        idx = np.stack([(np.arange(k) + r * (k - 1)) % e for r in range(n)])
    else:
        raise ValueError(picks)
    w = rng.uniform(0.1, 1.0, size=(n, k)).astype(np.float32)
    return jnp.asarray(w / w.sum(-1, keepdims=True)), jnp.asarray(idx, jnp.int32)


#: name → (N, hidden, expert width, E, K, group size, picks, zero params)
KERNEL_CASES = {
    **{f"n{n}-e4k2": (n, 64, 32, 4, 2, 16, "random", False) for n in (1, 2, 8, 16)},
    "n16-e64k6": (16, 128, 64, 64, 6, 16, "random", False),
    "n2-e64k6": (2, 128, 64, 64, 6, 16, "random", False),
    "same-expert": (8, 64, 32, 4, 2, 16, "same", False),
    "all-picks-distinct": (8, 128, 64, 64, 6, 16, "distinct", False),
    "neighbours-share-one": (8, 64, 32, 8, 2, 16, "neighbours", False),
    # expert width 1408 -> 176 words in small: 176 words of 8 inputs a row,
    # not a multiple of 128, which the down projection reads transposed
    "words-not-128": (16, 256, 1408, 4, 2, 64, "random", False),
    "zero-params": (16, 64, 32, 4, 2, 16, "random", True),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_expert_kernel_matches_gather_and_scan(case):
    """The expert-indexed 4-bit kernel (interpret mode) against the gather
    fallback and the prefill scan on the same packed stacks."""
    from mlx_sharding_tpu.ops.moe import (
        _apply_gather_packed,
        _apply_packed_kernel,
        _apply_scan,
        distinct_experts,
    )
    from mlx_sharding_tpu.ops.quant_matmul import experts_blocks

    n, h, mi, e, k, gs, picks, zero = KERNEL_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wg, wu = (_packed_stack(rng, e, mi, h, gs, zero) for _ in range(2))
    wd = _packed_stack(rng, e, h, mi, gs, zero)
    weights, idx = _routing(rng, n, e, k, picks)
    for out_d, in_d in ((mi, h), (h, mi)):
        assert experts_blocks(n, out_d, in_d, gs, 4, hardware=False) is not None

    ids, live = distinct_experts(idx, e)
    want_ids = np.unique(np.asarray(idx))
    assert int(live[0]) == len(want_ids) and ids.shape == (min(e, n * k),)
    np.testing.assert_array_equal(np.asarray(ids)[: len(want_ids)], want_ids)
    assert (np.asarray(ids)[len(want_ids):] == want_ids[-1]).all()

    got = _apply_packed_kernel(x, weights, idx, wg, wu, wd, gs, 4, interpret=True)
    assert got.shape == x.shape and got.dtype == x.dtype
    assert bool(jnp.isfinite(got).all())
    if zero:
        assert not np.asarray(got).any()
    for ref in (_apply_gather_packed, _apply_scan):
        want = ref(x, weights, idx, wg, wu, wd, gs, 4)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-5
        )


#: one case per shape family of KERNEL_CASES, gated and un-gated
LAYER_CASES = [
    (case, gated)
    for case in ("n1-e4k2", "n16-e4k2", "n2-e64k6", "same-expert",
                 "neighbours-share-one", "words-not-128", "zero-params")
    for gated in (True, False)
]


@pytest.mark.parametrize(
    "case,gated", LAYER_CASES,
    ids=[f"{c}-{'gated' if g else 'ungated'}" for c, g in LAYER_CASES],
)
def test_expert_kernel_reads_a_layer_in_place(case, gated):
    """``layer=i`` over whole ``(L, E, …)`` stacks against the same call on
    layer i's slice: the kernel sees the same bytes through another index,
    so the answers are equal bit for bit — for every layer in turn, with a
    Python ``layer``, a traced one under ``jax.jit`` and a ``lax.scan``
    counter."""
    from mlx_sharding_tpu.ops.moe import _apply_packed_kernel

    n, h, mi, e, k, gs, picks, zero = KERNEL_CASES[case]
    n_layers = 3
    rng = np.random.default_rng(sum(map(ord, case)) + gated)
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)

    def stacks(out_d, in_d):  # (L, E, out, in) packed; layer 1 the padded slot
        per_layer = [
            _packed_stack(rng, e, out_d, in_d, gs, zero or i == 1)
            for i in range(n_layers)
        ]
        return jax.tree.map(lambda *a: jnp.stack(a), *per_layer)

    wg = stacks(mi, h) if gated else None
    wu, wd = stacks(mi, h), stacks(h, mi)
    weights, idx = _routing(rng, n, e, k, picks)

    def at(w, i):
        return jax.tree.map(lambda a: a[i], w)

    def in_place(i):
        return _apply_packed_kernel(
            x, weights, idx, wg, wu, wd, gs, 4, interpret=True, layer=i
        )

    want = [
        np.asarray(_apply_packed_kernel(
            x, weights, idx, at(wg, i), at(wu, i), at(wd, i), gs, 4,
            interpret=True,
        ))
        for i in range(n_layers)
    ]
    assert not want[1].any() and (zero or want[0].any() and want[2].any())
    traced = jax.jit(in_place)
    _, scanned = jax.lax.scan(
        lambda c, i: (c, in_place(i)), 0, jnp.arange(n_layers)
    )
    for i in range(n_layers):
        np.testing.assert_array_equal(np.asarray(in_place(i)), want[i])
        np.testing.assert_array_equal(
            np.asarray(traced(jnp.asarray(i, jnp.int32))), want[i]
        )
        np.testing.assert_array_equal(np.asarray(scanned[i]), want[i])


def test_expert_kernel_bf16_rows_round_as_the_gather_path_does():
    """bf16 activations, as served: the kernel rounds each dequantized plane
    to bf16 before its sub-dot like ``dequantize(..., bf16)``, accumulates in
    f32, and casts once."""
    from mlx_sharding_tpu.ops.moe import _apply_gather_packed, _apply_packed_kernel

    rng = np.random.default_rng(3)
    n, h, mi, e, k, gs = 16, 128, 64, 8, 2, 64
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.bfloat16)
    wg, wu = (_packed_stack(rng, e, mi, h, gs) for _ in range(2))
    wd = _packed_stack(rng, e, h, mi, gs)
    weights, idx = _routing(rng, n, e, k, "random")
    got = _apply_packed_kernel(x, weights, idx, wg, wu, wd, gs, 4, interpret=True)
    want = _apply_gather_packed(x, weights, idx, wg, wu, wd, gs, 4)
    exact = _apply_gather_packed(x.astype(jnp.float32), weights, idx, wg, wu, wd, gs, 4)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.abs(exact).max())
    err = lambda a: float(jnp.abs(a.astype(jnp.float32) - exact).max()) / scale  # noqa: E731
    assert err(got) <= max(err(want), 2 ** -7)


def _only(path: str) -> dict:
    return {p: p == path for p in ("kernel", "grouped", "gather", "scan")}


def _paths_taken(rng, n, e, k, h, stacks, dtype=jnp.float32, **kw) -> dict:
    """Which of its paths ``apply_experts`` traced for ``n`` rows, read off
    the jaxpr: the expert kernel behind a sort is the grouped path, without
    one the decode step's; a loop is the scan; a gather and neither, one of
    the two gather fallbacks."""
    from mlx_sharding_tpu.ops import moe

    x = jnp.ones((n, h), dtype)
    weights, idx = _routing(rng, n, e, k, "random")
    text = str(jax.make_jaxpr(
        lambda *a: moe.apply_experts(*a, **kw)
    )(x, weights, idx, *stacks))
    kernel = "quant_matmul_experts" in text
    grouped = kernel and "argsort" in text
    return {
        "kernel": kernel and not grouped,
        "grouped": grouped,
        "gather": "gather" in text and not grouped,
        "scan": "scan" in text or "while" in text,
    }


def test_apply_experts_dispatch(monkeypatch):
    """What ``apply_experts`` chooses from what it can observe: on the CPU
    the gather fallback; with the backend answered as ``tpu`` (as
    tests/test_tpu_compile.py does) the kernel; 17 rows, a shape
    outside the kernel's contract and ``ep_axis`` keep the paths they had;
    17 rows over packed stacks the kernel serves are the grouped path's (the
    next test)."""
    from mlx_sharding_tpu.ops import moe

    rng = np.random.default_rng(9)
    e, k, h, mi, gs = 4, 2, 256, 128, 64
    wg, wu = (_packed_stack(rng, e, mi, h, gs) for _ in range(2))
    wd = _packed_stack(rng, e, h, mi, gs)

    def paths(n, **kw):
        return _paths_taken(rng, n, e, k, h, (wg, wu, wd), group_size=gs, **kw)

    assert paths(16) == _only("gather")
    assert paths(17) == _only("scan")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe.packed_kernel_ok(16, wg, wu, wd, gs, 4)
    assert paths(16) == _only("kernel")
    assert paths(1)["kernel"]
    assert paths(17) == _only("grouped")
    # whole (L, E, …) stacks and a layer's index: the same choices, and the
    # kernel's operand is the (L*E, …) view of the stack, not a layer's slice
    one = (wg, wu, wd)
    wg, wu, wd = jax.tree.map(lambda a: jnp.stack([a, a, a]), one)
    assert paths(16, layer=1) == _only("kernel")
    assert paths(17, layer=1) == _only("grouped")
    assert paths(16, layer=1, expert_base=0) == _only("scan")
    text = str(jax.make_jaxpr(
        lambda *a: moe.apply_experts(*a[:-1], group_size=gs, layer=a[-1])
    )(jnp.ones((16, h), jnp.float32), *_routing(rng, 16, e, k, "random"),
      wg, wu, wd, jnp.asarray(2, jnp.int32)))
    assert f"u32[{3 * e},{mi},{h // 8}]" in text
    assert not re.search(r":u32\[[^\]]*\] = (dynamic_slice|gather)", text)
    monkeypatch.undo()  # off the chip: the layer's slice, then the gather
    assert paths(16, layer=1) == _only("gather")
    x16 = jnp.asarray(rng.normal(size=(16, h)), jnp.float32)
    weights, idx = _routing(rng, 16, e, k, "random")
    np.testing.assert_array_equal(
        np.asarray(moe.apply_experts(x16, weights, idx, wg, wu, wd, group_size=gs, layer=2)),
        np.asarray(moe.apply_experts(x16, weights, idx, *one, group_size=gs)),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wg, wu, wd = one
    # expert-parallel: each device scans its residents, whatever the rows
    from jax.sharding import PartitionSpec as P

    from mlx_sharding_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(pp=1, ep=2)
    rep, split = P(), jax.tree.map(lambda _: P("ep"), wg)
    x = jnp.ones((4, h), jnp.float32)
    weights, idx = _routing(rng, 4, e, k, "random")
    text = str(jax.make_jaxpr(jax.shard_map(
        lambda *a: moe.apply_experts(*a, ep_axis="ep", group_size=gs),
        mesh=mesh, in_specs=(rep, rep, rep, split, split, split), out_specs=rep,
        check_vma=False,
    ))(x, weights, idx, wg, wu, wd))
    assert "quant_matmul_experts" not in text and "psum" in text
    # an OUT of 10944 rows has no 128-row tiling the pickers accept
    odd = _packed_stack(rng, 2, 96, 64, 64)
    assert not moe.packed_kernel_ok(16, odd, odd, _packed_stack(rng, 2, 64, 96, 32), 64, 4)
    # dense stacks gather as before
    dense = jnp.ones((e, h, mi), jnp.float32)
    x = jnp.ones((4, h), jnp.float32)
    weights, idx = _routing(rng, 4, e, k, "random")
    text = str(jax.make_jaxpr(moe.apply_experts)(
        x, weights, idx, dense, dense, jnp.ones((e, mi, h), jnp.float32)))
    assert "quant_matmul_experts" not in text


def test_moe_dispatch_is_counted_once_a_traced_call_and_shown_on_metrics(monkeypatch):
    """``mst_moe_dispatch_total{path}`` counts where ``apply_experts``
    chooses: once per traced call, not once per run of the compiled program.
    Off the chip a packed decode step is ``gather_packed`` (``kernel`` with
    the backend answered as ``tpu``), a dense one ``gather``, 17 rows or a
    resident range ``scan``."""
    from mlx_sharding_tpu.ops import moe
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    rng = np.random.default_rng(3)
    e, k, h, mi, gs = 4, 2, 64, 32, 16
    packed = (_packed_stack(rng, e, mi, h, gs), _packed_stack(rng, e, mi, h, gs),
              _packed_stack(rng, e, h, mi, gs))
    dense = tuple(jnp.ones(shape, jnp.float32) for shape in ((e, h, mi), (e, h, mi), (e, mi, h)))

    def run(n, stacks, **kw):
        fn = jax.jit(lambda *a: moe.apply_experts(*a, group_size=gs, **kw))
        for _ in range(3):
            fn(jnp.ones((n, h), jnp.float32), *_routing(rng, n, e, k, "random"),
               *stacks).block_until_ready()

    before = moe.dispatch_counts()
    assert set(before) == {"kernel", "grouped", "dense_kernel", "scan",
                           "gather_packed", "gather"}
    run(8, packed)
    run(8, dense)
    run(17, packed)
    run(8, dense, expert_base=0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = tuple(_packed_stack(rng, e, 128, 128, 64) for _ in range(3))
    jax.make_jaxpr(lambda *a: moe.apply_experts(*a))(
        jnp.ones((8, 128), jnp.float32), *_routing(rng, 8, e, k, "random"), *wide)
    monkeypatch.undo()
    after = moe.dispatch_counts()
    assert after == {"kernel": before["kernel"] + 1, "scan": before["scan"] + 2,
                     "gather_packed": before["gather_packed"] + 1,
                     "gather": before["gather"] + 1, "grouped": before["grouped"],
                     "dense_kernel": before["dense_kernel"]}
    text = ServingMetrics().render()
    assert "# TYPE mst_moe_dispatch_total counter" in text
    assert "# HELP mst_moe_dispatch_total" in text
    for path, n in after.items():
        assert f'mst_moe_dispatch_total{{path="{path}"}} {n}' in text


#: what keeps a chunk (17 rows and more) off the grouped path, and what it
#: takes instead: name -> (backend answered, stacks, extra arguments, path)
CHUNK_DISPATCH = {
    "packed-on-a-tpu": ("tpu", "packed", {}, "grouped"),
    "packed-in-place-on-a-tpu": ("tpu", "layered", {"layer": 1}, "grouped"),
    "packed-bf16-rows-on-a-tpu": ("tpu", "packed", {"dtype": jnp.bfloat16}, "grouped"),
    "packed-ungated-on-a-tpu": ("tpu", "ungated", {}, "grouped"),
    "packed-off-the-chip": ("cpu", "packed", {}, "scan"),
    "dense-on-a-tpu": ("tpu", "dense", {}, "scan"),
    "resident-range": ("tpu", "packed", {"expert_base": 0}, "scan"),
    "resident-range-in-place": ("tpu", "layered", {"layer": 1, "expert_base": 0}, "scan"),
    "outside-the-kernels-contract": ("tpu", "odd", {}, "scan"),
}


@pytest.mark.parametrize("rows", [17, 256])
@pytest.mark.parametrize("case", list(CHUNK_DISPATCH))
def test_apply_experts_dispatch_of_a_chunk(case, rows, monkeypatch):
    """More rows than the decode kernel takes: packed stacks whose three
    projections the expert kernel serves at ``GROUP_TILE`` rows, on a TPU,
    go through the grouped path — gated or not, in place or one layer's —
    and everything else keeps the scan: off the chip, dense stacks, a
    resident range, a shape outside the kernel's contract."""
    from mlx_sharding_tpu.ops import moe

    backend, kind, kw, want = CHUNK_DISPATCH[case]
    rng = np.random.default_rng(sum(map(ord, case)) + rows)
    e, k, h, mi, gs = 4, 2, 256, 128, 64
    if kind == "dense":
        stacks = tuple(jnp.ones(s, jnp.float32) for s in ((e, h, mi), (e, h, mi), (e, mi, h)))
    elif kind == "odd":  # 96 rows: no 128-lane OUT tile
        h, mi, gs = 64, 96, 32
        stacks = (_packed_stack(rng, e, mi, h, gs), _packed_stack(rng, e, mi, h, gs),
                  _packed_stack(rng, e, h, mi, gs))
    else:
        stacks = (_packed_stack(rng, e, mi, h, gs), _packed_stack(rng, e, mi, h, gs),
                  _packed_stack(rng, e, h, mi, gs))
        if kind == "layered":
            stacks = jax.tree.map(lambda a: jnp.stack([a, a, a]), stacks)
        if kind == "ungated":
            stacks = (None, *stacks[1:])
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if kind == "odd":
        assert not moe.packed_kernel_ok(moe.GROUP_TILE, *stacks, gs, 4)
    before = moe.dispatch_counts()
    assert _paths_taken(rng, rows, e, k, h, stacks, group_size=gs, **kw) == _only(want)
    assert moe.dispatch_counts() == {**before, want: before[want] + 1}


def test_grouped_dispatch_under_ep_axis_keeps_the_scan(monkeypatch):
    """Expert-parallel stacks are sharded: each device scans its residents
    whatever the rows and the backend, and one psum combines."""
    from jax.sharding import PartitionSpec as P

    from mlx_sharding_tpu.ops import moe
    from mlx_sharding_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(21)
    e, k, h, mi, gs, n = 4, 2, 256, 128, 64, 32
    wg, wu = (_packed_stack(rng, e, mi, h, gs) for _ in range(2))
    wd = _packed_stack(rng, e, h, mi, gs)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh(pp=1, ep=2)
    rep, split = P(), jax.tree.map(lambda _: P("ep"), wg)
    before = moe.dispatch_counts()
    text = str(jax.make_jaxpr(jax.shard_map(
        lambda *a: moe.apply_experts(*a, ep_axis="ep", group_size=gs),
        mesh=mesh, in_specs=(rep, rep, rep, split, split, split), out_specs=rep,
        check_vma=False,
    ))(jnp.ones((n, h), jnp.float32), *_routing(rng, n, e, k, "random"), wg, wu, wd))
    assert "quant_matmul_experts" not in text and "psum" in text and "while" in text
    assert moe.dispatch_counts() == {**before, "scan": before["scan"] + 1}


def test_grouped_dispatch_is_counted_once_a_traced_call_and_shown_on_metrics(monkeypatch):
    """``mst_moe_dispatch_total{path="grouped"}``: one count per traced chunk
    program, none for its runs, and on ``/metrics`` beside the other paths.
    The program is traced with the backend answered as ``tpu`` and run in
    interpret mode, as the CPU runs a Pallas kernel."""
    import functools

    from mlx_sharding_tpu.ops import moe, quant_matmul
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    rng = np.random.default_rng(4)
    e, k, h, mi, gs, n = 4, 2, 64, 32, 16, 24
    stacks = (_packed_stack(rng, e, mi, h, gs), _packed_stack(rng, e, mi, h, gs),
              _packed_stack(rng, e, h, mi, gs))
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    weights, idx = _routing(rng, n, e, k, "random")
    # off the chip the contract is the interpreter's (no 128-lane tiles) and
    # the kernel runs interpreted: the dispatcher's choice is what is tested
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        quant_matmul, "experts_blocks",
        functools.partial(quant_matmul.experts_blocks, hardware=False))
    grouped = moe._apply_grouped_kernel
    monkeypatch.setattr(
        moe, "_apply_grouped_kernel",
        functools.wraps(grouped)(lambda *a, **kw: grouped(*a, interpret=True, **kw)))
    fn = jax.jit(lambda *a: moe.apply_experts(*a, group_size=gs))
    before = moe.dispatch_counts()
    outs = [np.asarray(fn(x, weights, idx, *stacks)) for _ in range(3)]
    after = moe.dispatch_counts()
    assert after == {**before, "grouped": before["grouped"] + 1}
    monkeypatch.undo()
    want = moe._apply_scan(x, weights, idx, *stacks, gs, 4)
    for out in outs:
        np.testing.assert_allclose(out, np.asarray(want), rtol=1e-4, atol=2e-5)
    text = ServingMetrics().render()
    assert f'mst_moe_dispatch_total{{path="grouped"}} {after["grouped"]}' in text
    assert after["grouped"] > 0


# ----------------------- the scan that visits the experts the rows picked


def _full_walk(x, weights, idx, w_gate, w_up, w_down, gs=64, bits=4, layer=None):
    """``_apply_scan`` as it was before it skipped: EVERY held expert in
    ascending order, an unpicked one's product scaled by a routing mass of
    exactly 0. The reference the distinct-expert loop must equal bit for
    bit, and the one a NaN in an unpicked expert poisons."""
    from mlx_sharding_tpu.ops.moe import _activate
    from mlx_sharding_tpu.ops.quant import linear

    if layer is not None:
        w_gate, w_up, w_down = jax.tree.map(lambda a: a[layer], (w_gate, w_up, w_down))
    num_experts = (w_up["q"] if is_quantized(w_up) else w_up).shape[0]

    def body(acc, xs):
        wg, wu, wd, e = xs
        coef = ((idx == e) * weights).sum(axis=-1)
        g = None if wg is None else linear(x, wg, gs, bits)
        y = linear(_activate(g, linear(x, wu, gs, bits)), wd, gs, bits)
        return acc + coef[:, None].astype(y.dtype) * y, None

    acc, _ = jax.lax.scan(
        body, jnp.zeros_like(x), (w_gate, w_up, w_down, jnp.arange(num_experts)))
    return acc


#: global picks of 4 rows x top-3 over 16 experts of which 4..11 are held:
#: held 1, 2, 5, 6 are picked, held 0, 3, 4, 7 are not; with ``base`` 4 three
#: picks fall below 0 and five at or above E_local
_SCAN_PICKS = np.array([[1, 5, 14], [5, 9, 15], [0, 9, 10], [2, 6, 13]])
_SCAN_HELD, _SCAN_BASE = 8, 4


def _scan_case(packed, gated, layered, ranged):
    """``(x, weights, local idx, stacks, poisoned stacks, layer)``: the held
    stacks of one case and the same with every expert the rows did NOT pick
    (and every other layer) turned to NaN."""
    rng = np.random.default_rng(17)
    n, h, mi, gs = _SCAN_PICKS.shape[0], 64, 32, 16
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=_SCAN_PICKS.shape), jnp.float32)
    if ranged:  # global ids less the range's base, as apply_experts hands them
        local = _SCAN_PICKS - _SCAN_BASE
    else:  # every pick inside the stacks: the same held experts hit
        local = np.where(
            (_SCAN_PICKS >= _SCAN_BASE) & (_SCAN_PICKS < _SCAN_BASE + _SCAN_HELD),
            _SCAN_PICKS - _SCAN_BASE, np.array([[1], [5], [6], [2]]))
    picked = np.isin(np.arange(_SCAN_HELD), local)
    assert picked.tolist() == [False, True, True, False, False, True, True, False]
    layers = 3 if layered else 1

    def stack(out_d, in_d):
        if packed:  # (L, E, out, in*bits/32) leaves
            per = [_packed_stack(rng, _SCAN_HELD, out_d, in_d, gs) for _ in range(layers)]
            return jax.tree.map(lambda *a: jnp.stack(a), *per)
        return jnp.asarray(
            rng.normal(size=(layers, _SCAN_HELD, in_d, out_d)) * 0.1, jnp.float32)

    stacks = (stack(mi, h) if gated else None, stack(mi, h), stack(h, mi))
    layer = 1 if layered else None
    keep = np.zeros((layers, _SCAN_HELD), bool)
    keep[1 if layered else 0] = picked

    def poison(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a  # packed words: their scales and biases carry the NaN
        return jnp.where(keep.reshape(keep.shape + (1,) * (a.ndim - 2)), a, jnp.nan)

    poisoned = jax.tree.map(poison, stacks)
    if not layered:
        stacks, poisoned = jax.tree.map(lambda a: a[0], (stacks, poisoned))
    return x, weights, jnp.asarray(local, jnp.int32), stacks, poisoned, layer, gs


@pytest.mark.parametrize("ranged", [False, True], ids=["all-held", "resident-range"])
@pytest.mark.parametrize("layered", [False, True], ids=["one-layer", "in-place"])
@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_scan_visits_only_the_picked_experts(packed, gated, layered, ranged):
    """The loop over the step's distinct held experts (a) equals the walk
    over EVERY held expert bit for bit — it leaves out only terms that are
    ``0 * y`` — and (b) does not READ an expert nobody picked: with those
    turned to NaN the full walk is poisoned and the loop's result stays what
    it was. Picks a resident range does not hold (below 0, at or above
    E_local) are not visited."""
    from mlx_sharding_tpu.ops import moe

    x, weights, idx, stacks, poisoned, layer, gs = _scan_case(
        packed, gated, layered, ranged)
    want = np.asarray(jax.jit(
        lambda *a: _full_walk(*a, gs, 4, layer=layer))(x, weights, idx, *stacks))
    scan = jax.jit(lambda *a: moe._apply_scan(*a, gs, 4, layer=layer))
    got = np.asarray(scan(x, weights, idx, *stacks))
    assert np.isfinite(want).all() and np.abs(want).max() > 0
    assert np.array_equal(got, want)
    assert np.isnan(np.asarray(jax.jit(
        lambda *a: _full_walk(*a, gs, 4, layer=layer))(x, weights, idx, *poisoned))).any()
    assert np.array_equal(np.asarray(scan(x, weights, idx, *poisoned)), want)
    if ranged:  # the dispatcher subtracts the base and comes here, at any row count
        before = moe.dispatch_counts()
        via = moe.apply_experts(
            x, weights, idx + _SCAN_BASE, *poisoned, group_size=gs,
            expert_base=_SCAN_BASE, layer=layer)
        assert np.array_equal(np.asarray(via), want)
        assert moe.dispatch_counts() == {**before, "scan": before["scan"] + 1}


@pytest.mark.parametrize("picks", ["below", "above", "both"])
def test_scan_with_no_held_expert_picked_gives_zeros(picks):
    """``live == 0``: every pick of the step belongs to another holder of
    the layer. Nothing is read (the stacks are all NaN) and the part is 0."""
    from mlx_sharding_tpu.ops import moe

    e, h, mi = 4, 16, 8
    idx = {"below": [[-3, -1], [-2, -1]], "above": [[4, 9], [7, 5]],
           "both": [[-1, 4], [6, -5]]}[picks]
    nan = lambda *shape: jnp.full(shape, jnp.nan, jnp.float32)  # noqa: E731
    got = moe._apply_scan(
        jnp.ones((2, h), jnp.float32), jnp.full((2, 2), 0.5, jnp.float32),
        jnp.asarray(idx, jnp.int32), nan(e, h, mi), nan(e, h, mi), nan(e, mi, h))
    assert np.array_equal(np.asarray(got), np.zeros((2, h), np.float32))


@pytest.mark.parametrize("ep", [2, 4])
def test_scan_under_ep_axis_equals_one_device(ep):
    """Expert-parallel: each device walks the picks among ITS residents (one
    holds none of them here, one holds one) and the psum follows the loop."""
    from jax.sharding import PartitionSpec as P

    from mlx_sharding_tpu.ops import moe
    from mlx_sharding_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(23)
    n, h, mi, e, k = 6, 16, 24, 8, 2
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(e, h, mi)) * 0.1, jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, mi, h)) * 0.1, jnp.float32)
    idx = jnp.asarray([[0, 1], [1, 5], [0, 5], [5, 1], [1, 0], [5, 0]], jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    want = _full_walk(x, weights, idx, wg, wu, wd)
    rep, split = P(), P("ep")
    sharded = jax.jit(jax.shard_map(
        lambda *a: moe.apply_experts(*a, ep_axis="ep"), mesh=make_mesh(pp=1, ep=ep),
        in_specs=(rep, rep, rep, split, split, split), out_specs=rep, check_vma=False,
    ))
    # the unpicked experts are never read, on any device
    picked = jnp.isin(jnp.arange(e), idx)[:, None, None]
    for stacks in ((wg, wu, wd), [jnp.where(picked, w, jnp.nan) for w in (wg, wu, wd)]):
        np.testing.assert_allclose(
            np.asarray(sharded(x, weights, idx, *stacks)), np.asarray(want),
            rtol=1e-5, atol=1e-6)


def _largest_read(jaxpr):
    """Elements of the largest array any ``gather`` / ``dynamic_slice`` of
    ``jaxpr`` (sub-jaxprs included) produces, and whether any ``while`` in
    it has a per-lane predicate (a condition that is not a scalar)."""
    largest, lane_bound = 0, False
    stack = [jaxpr]
    while stack:
        jp = stack.pop()
        for eqn in jp.eqns:
            if eqn.primitive.name in ("gather", "dynamic_slice"):
                largest = max([largest] + [int(np.prod(v.aval.shape)) for v in eqn.outvars])
            if eqn.primitive.name == "while":
                lane_bound |= any(v.aval.shape != () for v in eqn.params["cond_jaxpr"].jaxpr.outvars)
            for sub in jax.tree.leaves(
                    list(eqn.params.values()),
                    is_leaf=lambda x: hasattr(x, "eqns") or hasattr(x, "jaxpr")):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    stack.append(sub)
    return largest, lane_bound


@pytest.mark.parametrize("caller", ["resident-range", "ep-axis"])
@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_scan_under_vmap_is_one_walk_for_all_lanes(packed, caller):
    """The engine's vectorized decode step (``--ep``, ``--paged-attention
    gather``) calls the scan under ``jax.vmap`` over its M lanes of one row
    each. The lanes are folded into the rows: ONE list of the experts any
    lane picked, each read once — no ``gather`` of M whole experts an
    iteration and no per-lane loop bound, which is what ``vmap`` makes of a
    bound and an index that are loaded from the picks. The result is the
    walk over every held expert, and an expert NO lane picked is not read."""
    from jax.sharding import PartitionSpec as P

    from mlx_sharding_tpu.ops import moe
    from mlx_sharding_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(29)
    m, h, mi, e, k, gs = 5, 64, 32, 8, 2, 16
    x = jnp.asarray(rng.normal(size=(m, 1, h)), jnp.float32)
    idx = jnp.asarray([[[1, 6]], [[6, 2]], [[1, 2]], [[5, 1]], [[2, 6]]], jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, size=(m, 1, k)), jnp.float32)
    if packed:
        stacks = (_packed_stack(rng, e, mi, h, gs), _packed_stack(rng, e, mi, h, gs),
                  _packed_stack(rng, e, h, mi, gs))
    else:
        stacks = tuple(jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
                       for s in ((e, h, mi), (e, h, mi), (e, mi, h)))
    want = np.asarray(_full_walk(x[:, 0], weights[:, 0], idx[:, 0], *stacks, gs))
    picked = np.isin(np.arange(e), np.asarray(idx))
    poisoned = jax.tree.map(
        lambda a: jnp.where(picked.reshape((e,) + (1,) * (a.ndim - 1)), a, jnp.nan)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, stacks)

    if caller == "resident-range":  # global ids 3..10 are the held 0..7
        def lanes(x, weights, idx, *stacks):
            return jax.vmap(
                lambda *rows: moe.apply_experts(*rows, *stacks, group_size=gs, expert_base=3)
            )(x, weights, idx + 3)
    else:
        def per_device(x, weights, idx, *stacks):
            return jax.vmap(
                lambda *rows: moe.apply_experts(*rows, *stacks, group_size=gs, ep_axis="ep")
            )(x, weights, idx)
        rep, split = P(), P("ep")
        lanes = jax.shard_map(
            per_device, mesh=make_mesh(pp=1, ep=2), in_specs=(rep, rep, rep, split, split, split),
            out_specs=rep, check_vma=False)
    largest, lane_bound = _largest_read(jax.make_jaxpr(lanes)(x, weights, idx, *stacks).jaxpr)
    assert 0 < largest <= h * mi, "the lanes gather an expert each"
    assert not lane_bound, "the loop's bound is per lane"
    for given in (stacks, poisoned):
        got = np.asarray(jax.jit(lanes)(x, weights, idx, *given))
        assert got.shape == (m, 1, h)
        np.testing.assert_allclose(got[:, 0], want, rtol=1e-5, atol=1e-6)


def test_scan_under_vmap_over_the_stacks_goes_lane_by_lane():
    """Lanes with stacks of their own (no served path) are walked one after
    another, each over its own picks."""
    from mlx_sharding_tpu.ops import moe

    rng = np.random.default_rng(31)
    m, n, h, mi, e, k = 3, 2, 16, 8, 4, 2
    x = jnp.asarray(rng.normal(size=(m, n, h)), jnp.float32)
    weights, idx = (jnp.stack(a) for a in zip(*(_routing(rng, n, e, k, "random") for _ in range(m))))
    wg, wu = (jnp.asarray(rng.normal(size=(m, e, h, mi)) * 0.1, jnp.float32) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(m, e, mi, h)) * 0.1, jnp.float32)
    got = jax.vmap(moe._apply_scan)(x, weights, idx, wg, wu, wd)
    for i in range(m):
        assert np.array_equal(
            np.asarray(got[i]),
            np.asarray(moe._apply_scan(x[i], weights[i], idx[i], wg[i], wu[i], wd[i])))


# ------------------------ the layer scan that leaves the expert stacks whole


def _scanned_slices(monkeypatch):
    """The layer scan as it was before the stacks stayed whole: every leaf a
    scanned ``xs``, each layer handed its own ``(E, …)`` slice."""
    from mlx_sharding_tpu.models.deepseek_v2 import DeepseekV2Model

    monkeypatch.setattr(
        DeepseekV2Model, "scan_in_place", lambda self, group, stack: ()
    )


@pytest.mark.parametrize("padded", [False, True], ids=["unpadded", "padded"])
@pytest.mark.parametrize("rows", [2, 20], ids=["decode-2-rows", "chunk-20-rows"])
def test_run_layers_in_place_equals_scanned_slices(tmp_path, monkeypatch, padded, rows):
    """``run_layers`` over the tiny packed DeepSeek, expert stacks read in
    place by ``(layer, expert)``, against the scan that slices them per
    layer: hidden state, K and V equal to the last bit on the CPU (gather
    fallback at 2 rows, expert scan at 20), with and without a padded,
    masked-out layer slot of zero parameters."""
    from mlx_sharding_tpu.loading import load_model

    path = _quantized_tiny_deepseek(tmp_path)
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    layers, mask = params["layers"], None
    assert model.scan_in_place("moe", layers["moe"]) == ("w_gate", "w_up", "w_down")
    assert model.scan_in_place("dense", layers["dense"]) == ()
    if padded:  # a zero slot between the two MoE layers, as the fused engine pads
        layers = {**layers, "moe": jax.tree.map(
            lambda a: jnp.stack([a[0], jnp.zeros_like(a[0]), a[1]]), layers["moe"]
        )}
        mask = {"dense": jnp.asarray([True]), "moe": jnp.asarray([True, False, True])}
    n_layers = 3 + padded
    rng = np.random.default_rng(rows + padded)
    t = 1 if rows <= 16 else rows
    h = jnp.asarray(rng.normal(size=(rows // t, t, 64)), jnp.float32)
    cache = model.make_cache(rows // t, 32, jnp.float32)
    k = jnp.asarray(rng.normal(size=(n_layers, *cache.k.shape[1:])), jnp.float32)
    v = jnp.zeros((n_layers, *cache.v.shape[1:]), jnp.float32)

    def run():
        return jax.jit(
            lambda lp, h, k, v: model.run_layers(
                lp, h, k, v, jnp.asarray(5, jnp.int32), mask=mask)
        )(layers, h, k, v)

    got = run()
    _scanned_slices(monkeypatch)
    want = run()
    assert float(jnp.abs(want[0] - h).max()) > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize(
    "served", ["ragged-pp1", "fused-pp2-padded", "fused-pp1-ep2", "sp2-decode"]
)
def test_served_logprobs_in_place_equal_scanned_slices(tmp_path, monkeypatch, served):
    """The served tiny packed DeepSeek — the ragged paged decode body behind
    the batcher (pp=1), the fused pp=2 engine whose stages are padded with
    masked slots, the fused engine under ``--ep 2`` (each device reads
    ``(layer, e)`` of its half of the E axis), and decode over the
    sp-sharded cache (``sp_decode.py``'s body; the ring prefill in front of
    it has a scan of its own, ``sp_prefill.py``, which slices) — gives the
    same tokens and the same top-10 log-probabilities, bit for bit, as with
    the scanned slices."""
    from mlx_sharding_tpu.generate import Generator, TokenLogprobs
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
    from mlx_sharding_tpu.scheduler import ContinuousBatcher

    path = _quantized_tiny_deepseek(tmp_path)
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    prompt = [5, 9, 2, 61, 17]

    def serve():
        kw = dict(max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8)
        if served == "ragged-pp1":
            eng = PipelineEngine(
                model, params, make_mesh(pp=1), microbatches=2, pool_pages=20,
                page_size=8, paged_attention="ragged", **kw)
            assert eng.paged_attention == "ragged"
            b = ContinuousBatcher(eng, decode_block=3)
            try:
                out = list(b.generate_step(prompt, max_tokens=7, want_logprobs=True))
            finally:
                b.close()
        elif served == "fused-pp2-padded":
            eng = PipelineEngine(model, params, make_mesh(pp=2), **kw)
            assert not all(np.asarray(m).all() for m in jax.tree.leaves(eng.layer_masks))
            out = list(eng.generate_step(prompt, max_tokens=7, want_logprobs=True))
        elif served == "sp2-decode":
            gen = Generator(
                model, params, sp_mesh=make_mesh(sp=2), sp_decode=True,
                decode_block=3, **kw)
            out = list(gen.generate_step(prompt, max_tokens=7, want_logprobs=True))
        else:
            eng = PipelineEngine(model, params, make_mesh(pp=1, ep=2), **kw)
            wq = eng.layer_params["moe"]["w_gate"]["q"]
            assert wq.sharding.shard_shape(wq.shape)[2] == wq.shape[2] // 2
            out = list(eng.generate_step(prompt, max_tokens=7, want_logprobs=True))
        # the first token carries its whole row, a block's tokens a summary
        return [
            (int(t), [np.asarray(a).tolist() for a in (
                (lp.chosen, lp.top_indices, lp.top_values)
                if isinstance(lp, TokenLogprobs) else (lp,))])
            for t, lp in out
        ]

    from mlx_sharding_tpu.models import deepseek_v2

    layers_seen = []  # apply_experts(layer=…) at trace time: the form taken

    def spy(*a, **kw):
        layers_seen.append(kw.get("layer") is not None)
        return apply_experts(*a, **kw)

    apply_experts = deepseek_v2.apply_experts
    monkeypatch.setattr(deepseek_v2, "apply_experts", spy)
    got = serve()
    assert layers_seen and (any if served == "sp2-decode" else all)(layers_seen)
    del layers_seen[:]
    _scanned_slices(monkeypatch)
    want = serve()
    assert layers_seen and not any(layers_seen)
    assert len(want) == 7 and got == want
