"""Fused dequant-matmul: packed 4-bit weights through the whole stack.

VERDICT r1 item 10: quantized checkpoints should decode with the weights
STILL PACKED in HBM (4x capacity + bandwidth). Kernel parity runs in Pallas
interpret mode; the end-to-end path loads a quantized tiny-llama checkpoint
with keep_quantized=True and must match the dequantize-at-load path.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.ops.quant import dequantize, is_quantized, linear, quantize
from mlx_sharding_tpu.ops.quant_matmul import quant_matmul_pallas


@pytest.mark.parametrize(
    "m,in_dim,out_dim,gs,bits,block_out",
    [
        (128, 512, 128, 64, 4, 64),
        (1, 512, 256, 64, 4, 64),  # decode-shaped: one row
        (64, 1024, 128, 128, 4, 64),
        (8, 512, 128, 64, 8, 64),
        # a ragged last OUT tile: 64 x 3 and 64 x 5 rows are 1.5 and 2.5
        # tiles of 128 (DeepSeek-V2-Lite's dense width is 64 x 171), at a
        # decode batch's and a prefill chunk's rows
        (16, 512, 192, 64, 4, 128),
        (16, 512, 320, 64, 4, 128),
        (256, 512, 192, 64, 4, 128),
        (256, 512, 320, 64, 4, 128),
    ],
)
def test_pallas_kernel_matches_dense(m, in_dim, out_dim, gs, bits, block_out):
    rng = np.random.default_rng(3)
    w = rng.normal(size=(out_dim, in_dim)).astype(np.float32)
    q, s, b = quantize(w, group_size=gs, bits=bits)
    dense = np.asarray(
        dequantize(q, s, b, group_size=gs, bits=bits, dtype=jnp.float32)
    )
    x = rng.normal(size=(m, in_dim)).astype(np.float32)
    want = x @ dense.T

    got = quant_matmul_pallas(
        jnp.asarray(x), jnp.asarray(q), jnp.asarray(s, jnp.float32),
        jnp.asarray(b, jnp.float32), group_size=gs, bits=bits,
        block_m=64, block_out=block_out, block_in=256, interpret=True,
    )
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_pallas_kernel_refuses_ragged_in():
    """OUT may end in a partial tile, IN may not: what lies past the edge
    of a ragged IN block would be added into every output column."""
    rng = np.random.default_rng(3)
    q, s, b = quantize(rng.normal(size=(192, 512)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(16, 512)), jnp.float32)
    with pytest.raises(ValueError, match="IN=512"):
        quant_matmul_pallas(
            x, jnp.asarray(q), jnp.asarray(s, jnp.float32),
            jnp.asarray(b, jnp.float32), block_out=128, block_in=384,
            interpret=True,
        )


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, nested programs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for inner in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def _dsv2_lite_projections():
    """``pytest.param(name, OUT, IN)`` of every packed projection the served
    programs of ``dsv2-lite-q4`` run, from the configuration's own widths."""
    cfg = json.loads(
        (Path(__file__).parent.parent / "benchmarks/configs/dsv2-lite-q4.json")
        .read_text()
    )
    hidden, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dense = cfg["intermediate_size"]
    shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return [pytest.param(*p, id=p[0]) for p in (
        ("q", heads * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]), hidden),
        ("kv_a", cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], hidden),
        ("o", hidden, heads * cfg["v_head_dim"]),
        ("dense_gate", dense, hidden),
        ("dense_up", dense, hidden),
        ("dense_down", hidden, dense),
        ("shared_gate", shared, hidden),
        ("shared_up", shared, hidden),
        ("shared_down", hidden, shared),
        ("head", cfg["vocab_size"], hidden),
    )]


# block_out of each projection before the kernel took a ragged OUT tile, at
# a decode batch's 16 rows and a prefill chunk's 256: what
# ``pick_block_out`` gave at the commit before, for every shape that passed
# ``_pallas_ok`` there. None: refused there (10944 = 64 x 171 has no
# divisor that is a multiple of 128), fell to ``_quant_matmul_xla``.
_DSV2_BLOCK_OUT_BEFORE = {
    16: dict(q=1024, kv_a=576, o=1024, dense_gate=None, dense_up=None,
             dense_down=128, shared_gate=1408, shared_up=1408,
             shared_down=1024, head=1280),
    256: dict(q=1024, kv_a=576, o=1024, dense_gate=None, dense_up=None,
              dense_down=128, shared_gate=256, shared_up=256,
              shared_down=512, head=1024),
}


@pytest.mark.parametrize("m", [16, 256])
@pytest.mark.parametrize("name,out_dim,in_dim", _dsv2_lite_projections())
def test_dsv2_lite_projections_reach_a_kernel(name, out_dim, in_dim, m, monkeypatch):
    """On a TPU every packed projection of ``dsv2-lite-q4`` is served by a
    Pallas kernel at the cell's rows, none by the f32 dequantization in HBM
    that ``gate_proj`` and ``up_proj`` took for thirty PRs; and every shape
    that was served before is tiled as it was."""
    from mlx_sharding_tpu.ops import quant

    def refuse(*a, **k):
        raise AssertionError(f"{name} ({in_dim} -> {out_dim}) at M={m} fell to XLA")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(quant, "_quant_matmul_xla", refuse)
    before = quant.dispatch_counts()
    args = [
        jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
            ((m, in_dim), jnp.bfloat16), ((out_dim, in_dim // 8), jnp.uint32),
            ((out_dim, in_dim // 64), jnp.float32),
            ((out_dim, in_dim // 64), jnp.float32),
        )
    ]
    # traced, not compiled: the program as the chip's compiler would get it
    jaxpr = jax.make_jaxpr(
        lambda *a: quant._quant_matmul(*a, group_size=64, bits=4)
    )(*args)
    after = quant.dispatch_counts()
    assert after["xla"] == before["xla"]
    assert after["matmul"] == before["matmul"] + 1

    (call,) = _pallas_calls(jaxpr.jaxpr)
    assert call.params["name"] == "quant_matmul"
    mapping = call.params["grid_mapping"]
    out_block = [
        getattr(b, "block_size", b) for b in mapping.block_mappings[-1].block_shape
    ]
    block_m, block_out = out_block
    assert mapping.grid[:2] == (m // block_m, -(-out_dim // block_out))
    was = _DSV2_BLOCK_OUT_BEFORE[m][name]
    if was is None:  # the ragged arm: whole 128-lane tiles, the last partial
        assert block_out % 128 == 0 and out_dim % block_out
    else:
        assert block_out == was and out_dim % block_out == 0


def test_linear_dispatch_packed_vs_dense():
    """ops.quant.linear must produce the same numbers whether the weight is
    a dense (in, out) array or the packed MLX triple."""
    rng = np.random.default_rng(4)
    w = rng.normal(size=(96, 128)).astype(np.float32)  # (out, in)
    q, s, b = quantize(w, group_size=64, bits=4)
    dense = np.asarray(dequantize(q, s, b, dtype=jnp.float32))

    x = jnp.asarray(rng.normal(size=(2, 5, 128)), jnp.float32)
    want = np.asarray(x @ jnp.asarray(dense.T))
    packed = {
        "q": jnp.asarray(q),
        "scales": jnp.asarray(s, jnp.float32),
        "biases": jnp.asarray(b, jnp.float32),
    }
    assert is_quantized(packed) and not is_quantized(jnp.asarray(dense))
    got = np.asarray(linear(x, packed, 64, 4))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dispatch_is_counted_once_a_traced_call_and_shown_on_metrics():
    """``mst_quant_dispatch_total{path}`` counts where ``_quant_matmul``
    chooses: off the chip that is the XLA path, once per traced call and
    not once per run of the compiled program."""
    from mlx_sharding_tpu.ops import quant
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    rng = np.random.default_rng(5)
    q, s, b = quantize(rng.normal(size=(96, 128)).astype(np.float32))
    packed = {"q": jnp.asarray(q), "scales": jnp.asarray(s, jnp.float32),
              "biases": jnp.asarray(b, jnp.float32)}
    fn = jax.jit(lambda x: linear(x, packed))
    before = quant.dispatch_counts()
    for _ in range(3):
        fn(jnp.ones((16, 128), jnp.float32)).block_until_ready()
    after = quant.dispatch_counts()
    assert after == {**before, "xla": before["xla"] + 1}
    text = ServingMetrics().render()
    assert "# TYPE mst_quant_dispatch_total counter" in text
    assert "# HELP mst_quant_dispatch_total" in text
    for path, n in after.items():
        assert f'mst_quant_dispatch_total{{path="{path}"}} {n}' in text


def _quantized_tiny_llama(tmp_path: Path, group_size: int = 64):
    """Write a tiny llama checkpoint whose decoder projections AND vocab
    pair (embed_tokens / lm_head — published 4-bit checkpoints quantize
    both) are MLX-style 4-bit triples (config.quantization present)."""
    from safetensors.numpy import save_file

    cfg = dict(
        model_type="llama", vocab_size=128, hidden_size=64,
        intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=256,
        rms_norm_eps=1e-5, rope_theta=10000.0, tie_word_embeddings=False,
        quantization={"group_size": group_size, "bits": 4},
    )
    rng = np.random.default_rng(7)
    tensors = {}

    def dense(name, shape):
        tensors[name] = (rng.normal(size=shape) * 0.05).astype(np.float32)

    def quant(name, out_d, in_d):
        w = (rng.normal(size=(out_d, in_d)) * 0.05).astype(np.float32)
        q, s, b = quantize(w, group_size=group_size, bits=4)
        tensors[name] = q
        tensors[name.replace(".weight", ".scales")] = s
        tensors[name.replace(".weight", ".biases")] = b

    quant("model.embed_tokens.weight", 128, 64)
    dense("model.norm.weight", (64,))
    quant("lm_head.weight", 128, 64)
    for i in range(2):
        p = f"model.layers.{i}"
        dense(f"{p}.input_layernorm.weight", (64,))
        dense(f"{p}.post_attention_layernorm.weight", (64,))
        quant(f"{p}.self_attn.q_proj.weight", 64, 64)
        quant(f"{p}.self_attn.k_proj.weight", 32, 64)
        quant(f"{p}.self_attn.v_proj.weight", 32, 64)
        quant(f"{p}.self_attn.o_proj.weight", 64, 64)
        quant(f"{p}.mlp.gate_proj.weight", 128, 64)
        quant(f"{p}.mlp.up_proj.weight", 128, 64)
        quant(f"{p}.mlp.down_proj.weight", 64, 128)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    return tmp_path


def _leaf_bytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def test_keep_quantized_end_to_end(tmp_path):
    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.loading import load_model

    path = _quantized_tiny_llama(tmp_path)
    model_d, params_d = load_model(str(path), dtype=jnp.float32)
    model_p, params_p = load_model(
        str(path), dtype=jnp.float32, keep_quantized=True
    )
    # packed layers really are packed (and much smaller); the vocab pair
    # stays packed too — the head matmul is the biggest dense read of a
    # decode step
    assert is_quantized(
        jax.tree.map(
            lambda x: x, params_p["layers"]["q_proj"], is_leaf=is_quantized
        )
    )
    assert is_quantized(params_p["embed"]["weight"])
    assert is_quantized(params_p["lm_head"]["weight"])
    assert _leaf_bytes(params_p["layers"]) < _leaf_bytes(params_d["layers"]) / 2

    prompt = [3, 17, 42, 9, 77]
    ref = Generator(
        model_d, params_d, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    gen = Generator(
        model_p, params_p, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    want = [t for t, _ in ref.generate_step(prompt, max_tokens=10)]
    got = [t for t, _ in gen.generate_step(prompt, max_tokens=10)]
    assert got == want


def test_keep_quantized_tied_embedding(tmp_path):
    """Tied models project logits through the packed embed triple (MLX's
    (V, H) layout is already the head's packed orientation) and gather
    embed rows by dequantizing only the looked-up tokens."""
    import json as _json

    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.loading import load_model

    path = _quantized_tiny_llama(tmp_path)
    cfg = _json.loads((path / "config.json").read_text())
    cfg["tie_word_embeddings"] = True
    (path / "config.json").write_text(_json.dumps(cfg))

    model_d, params_d = load_model(str(path), dtype=jnp.float32)
    model_p, params_p = load_model(
        str(path), dtype=jnp.float32, keep_quantized=True
    )
    assert is_quantized(params_p["embed"]["weight"])
    assert "lm_head" not in params_p

    prompt = [3, 17, 42, 9, 77]
    ref = Generator(
        model_d, params_d, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    gen = Generator(
        model_p, params_p, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    want = [t for t, _ in ref.generate_step(prompt, max_tokens=10)]
    assert [t for t, _ in gen.generate_step(prompt, max_tokens=10)] == want


def _packed_ref(tmp_path):
    """Shared recipe: quantized checkpoint + packed load + reference tokens
    for the canonical prompt."""
    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.loading import load_model

    path = _quantized_tiny_llama(tmp_path)
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    ref = Generator(
        model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    want = [t for t, _ in ref.generate_step([5, 9, 2], max_tokens=8)]
    return path, model, params, want


def test_keep_quantized_fused_pipeline(tmp_path):
    """Packed params ride the fused SPMD engine (tree-aware stage split)."""
    from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    path, model, params, want = _packed_ref(tmp_path)
    eng = PipelineEngine(
        model, params, pipeline_mesh(2), max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8,
    )
    got = [t for t, _ in eng.generate_step([5, 9, 2], max_tokens=8)]
    assert got == want


def test_keep_quantized_gemma2(tmp_path):
    """Gemma-2 packed 4-bit: projections through _linear's quant dispatch,
    tied packed embedding (scaled row-gather dequant on lookup, softcapped
    packed head matmul) — token parity with the dequantize-at-load path."""
    import json as _json

    from safetensors.numpy import save_file

    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.loading import load_model

    gs = 32
    cfg = dict(
        model_type="gemma2", vocab_size=64, hidden_size=32,
        intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, sliding_window=8,
        query_pre_attn_scalar=8.0, rms_norm_eps=1e-6, rope_theta=10000.0,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        tie_word_embeddings=True, max_position_embeddings=128,
        quantization={"group_size": gs, "bits": 4},
    )
    rng = np.random.default_rng(11)
    tensors = {}

    def dense(name, shape):
        tensors[name] = (rng.normal(size=shape) * 0.05).astype(np.float32)

    def quant(name, out_d, in_d):
        w = (rng.normal(size=(out_d, in_d)) * 0.05).astype(np.float32)
        q, s, b = quantize(w, group_size=gs, bits=4)
        tensors[name] = q
        tensors[name.replace(".weight", ".scales")] = s
        tensors[name.replace(".weight", ".biases")] = b

    quant("model.embed_tokens.weight", 64, 32)
    dense("model.norm.weight", (32,))
    for i in range(2):
        p = f"model.layers.{i}"
        for n in ("input_layernorm", "post_attention_layernorm",
                  "pre_feedforward_layernorm", "post_feedforward_layernorm"):
            dense(f"{p}.{n}.weight", (32,))
        quant(f"{p}.self_attn.q_proj.weight", 32, 32)
        quant(f"{p}.self_attn.k_proj.weight", 16, 32)
        quant(f"{p}.self_attn.v_proj.weight", 16, 32)
        quant(f"{p}.self_attn.o_proj.weight", 32, 32)
        quant(f"{p}.mlp.gate_proj.weight", 64, 32)
        quant(f"{p}.mlp.up_proj.weight", 64, 32)
        quant(f"{p}.mlp.down_proj.weight", 32, 64)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(_json.dumps(cfg))

    model_d, params_d = load_model(str(tmp_path), dtype=jnp.float32)
    model_p, params_p = load_model(
        str(tmp_path), dtype=jnp.float32, keep_quantized=True
    )
    assert is_quantized(params_p["layers"]["q_proj"])
    assert is_quantized(params_p["embed"]["weight"])

    prompt = [3, 17, 42, 9]
    ref = Generator(
        model_d, params_d, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    gen = Generator(
        model_p, params_p, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8
    )
    want = [t for t, _ in ref.generate_step(prompt, max_tokens=10)]
    assert [t for t, _ in gen.generate_step(prompt, max_tokens=10)] == want


def test_keep_quantized_native_checkpoint_rejected(tmp_path):
    """Native (Orbax) checkpoints store dense weights; keep_quantized on
    one is a user error, not a silent no-op."""
    from mlx_sharding_tpu.loading import load_model

    d = tmp_path / "native"
    d.mkdir()
    (d / "native_checkpoint.json").write_text("{}")
    with pytest.raises(ValueError, match="keep_quantized"):
        load_model(str(d), dtype=jnp.float32, keep_quantized=True)


@pytest.mark.slow  # chained variant — fused-pipeline + tp keep the quick signal
def test_keep_quantized_chained_pipeline(tmp_path):
    """--engine chained with --keep-quantized: every stage loads packed."""
    from mlx_sharding_tpu.parallel.chained import load_chained_pipeline

    path, _, _, want = _packed_ref(tmp_path)
    chain = load_chained_pipeline(
        str(path), [(0, 1), (1, 2)], dtype=jnp.float32, keep_quantized=True,
        max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8,
    )
    for stage_params in chain.params:  # EVERY stage, not just stage 0
        assert is_quantized(stage_params["layers"]["q_proj"])
    got = [t for t, _ in chain.generate_step([5, 9, 2], max_tokens=8)]
    assert got == want


def test_keep_quantized_with_tensor_parallelism(tmp_path):
    """TP over packed 4-bit weights: column-parallel shards dim 0 of the
    (out, in/8) packed layout, row-parallel shards the packed in dim — the
    per-leaf divisibility checks guarantee nibble-word and quant-group
    alignment. Exact token parity at pp1xtp2 and pp2xtp2.

    group_size=32 so the row-parallel in-split (64/2=32) lands on a group
    boundary; gs=64 is the rejection test below."""
    from mlx_sharding_tpu.generate import Generator
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    path = _quantized_tiny_llama(tmp_path, group_size=32)
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    ref = Generator(model, params, max_seq=64, cache_dtype=jnp.float32, prefill_chunk=8)
    want = [t for t, _ in ref.generate_step([5, 9, 2], max_tokens=8)]

    for pp, tp in ((1, 2), (2, 2)):
        eng = PipelineEngine(
            model, params, make_mesh(pp=pp, tp=tp), max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
        )
        got = [t for t, _ in eng.generate_step([5, 9, 2], max_tokens=8)]
        assert got == want, f"pp={pp} tp={tp} diverged"
        # column-parallel q_proj: packed dim 0 (out) sharded
        qp = eng.layer_params["q_proj"]["q"]
        assert qp.sharding.shard_shape(qp.shape)[2] == qp.shape[2] // tp
        # row-parallel o_proj: packed dim 1 (in/8) sharded
        op = eng.layer_params["o_proj"]["q"]
        assert op.sharding.shard_shape(op.shape)[3] == op.shape[3] // tp


def test_keep_quantized_tp_group_misalignment_rejected(tmp_path):
    """gs=64 with in=64 and tp=2 would split a quant group in half — the
    scales divisibility check must reject it loudly."""
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.parallel.mesh import make_mesh
    from mlx_sharding_tpu.parallel.pipeline import PipelineEngine

    path = _quantized_tiny_llama(tmp_path)  # gs=64, o_proj in=64
    model, params = load_model(str(path), dtype=jnp.float32, keep_quantized=True)
    with pytest.raises(ValueError, match="not divisible"):
        PipelineEngine(
            model, params, make_mesh(pp=1, tp=2), max_seq=64,
            cache_dtype=jnp.float32, prefill_chunk=8,
        )


def test_keep_quantized_unsupported_arch_rejected(tmp_path, monkeypatch):
    """Architectures without packed wiring must reject keep_quantized
    loudly instead of silently loading dense (every in-tree family now
    supports packed, so the branch is exercised by flipping the flag)."""
    from mlx_sharding_tpu.loading import load_model
    from mlx_sharding_tpu.models.llama import LlamaModel

    path = _quantized_tiny_llama(tmp_path)
    monkeypatch.setattr(LlamaModel, "supports_packed", False)
    with pytest.raises(ValueError, match="keep_quantized is not supported"):
        load_model(str(path), dtype=jnp.float32, keep_quantized=True)


def test_speculative_rejects_mismatched_vocab():
    from mlx_sharding_tpu.config import LlamaConfig
    from mlx_sharding_tpu.models.llama import LlamaModel
    from mlx_sharding_tpu.speculative import SpeculativeGenerator

    tiny = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=1,
                num_attention_heads=4, num_key_value_heads=2)
    model = LlamaModel(LlamaConfig(vocab_size=128, **tiny))
    draft = LlamaModel(LlamaConfig(vocab_size=64, **tiny))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    dparams = draft.init_params(jax.random.PRNGKey(1), jnp.float32)
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeGenerator(model, params, draft, dparams, max_seq=64)


def test_keep_quantized_dense_checkpoint_rejected(tmp_path):
    """keep_quantized on a checkpoint with no quantization config must fail
    loudly — a silent dense load would quietly cost 4x the expected HBM."""
    import transformers

    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    )
    transformers.LlamaForCausalLM(cfg).save_pretrained(
        tmp_path, safe_serialization=True
    )
    from mlx_sharding_tpu.loading import load_model

    with pytest.raises(ValueError, match="quantized checkpoint"):
        load_model(str(tmp_path), dtype=jnp.float32, keep_quantized=True)
