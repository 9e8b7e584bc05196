"""Every jitted program of the served path has a name of its own (it is
``jit_<name>`` in a profile and in a compile log, and the benchmark finds
the decode block and the prefill chunk by it), and the served programs of a
DeepSeek-V2 carry the ``mst.*`` scope vocabulary of ``tracing.MODEL_SCOPES``
at their layer boundaries — and no ``mst.*`` name outside it."""

import re

import math

import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu import tracing
from mlx_sharding_tpu.config import DeepseekV2Config, LlamaConfig
from mlx_sharding_tpu.models.deepseek_v2 import DeepseekV2Model
from mlx_sharding_tpu.models.llama import LlamaModel
from mlx_sharding_tpu.parallel.mesh import pipeline_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout

TINY = dict(vocab_size=256, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2)


class JitLog:
    """Stands in for ``jax.jit`` and records what was handed to it."""

    def __init__(self, real):
        self.real = real
        self.seen = []  # (name, file, first line, id of the function)

    def __call__(self, fun, *args, **kwargs):
        code = getattr(fun, "__code__", None) or getattr(
            getattr(fun, "__func__", None), "__code__", None)
        if code is not None and "mlx_sharding_tpu" in code.co_filename:
            self.seen.append((fun.__name__, code.co_filename,
                              code.co_firstlineno, id(fun)))
        return self.real(fun, *args, **kwargs)


@hard_timeout(420)
def test_every_served_program_has_a_name_of_its_own(monkeypatch):
    """Build the served path with every option that adds programs — paged
    pool, a draft engine, log-probabilities, the solo generator — and run
    it, so that the lazily built programs exist too."""
    log = JitLog(jax.jit)
    monkeypatch.setattr(jax, "jit", log)
    model = LlamaModel(LlamaConfig(**TINY))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    mesh = pipeline_mesh(1)
    kw = dict(microbatches=2, max_seq=64, cache_dtype=jnp.float32,
              prefill_chunk=8)
    target = PipelineEngine(model, params, mesh, pool_pages=20, page_size=8, **kw)
    draft = PipelineEngine(model, params, mesh, **kw)
    spec = ContinuousBatcher(target, decode_block=3, draft_engine=draft,
                             spec_k=3, async_sched="off")
    ngram = ContinuousBatcher(target, decode_block=3, draft="ngram")
    try:
        for batcher in (spec, ngram):
            assert len(list(batcher.generate_step([3, 4, 5, 3, 4, 5, 3], max_tokens=9))) == 9
            # log-probabilities pause speculation: the plain block, its
            # _lp variant and the draft's replay
            assert len(list(batcher.generate_step(
                [3, 4, 5], max_tokens=5, want_logprobs=True))) == 5
    finally:
        spec.close()
        ngram.close()
    solo = PipelineEngine(model, params, mesh, microbatches=1, **{
        k: v for k, v in kw.items() if k != "microbatches"})
    assert len(list(solo.generate_step([3, 4, 5], max_tokens=12))) == 12
    assert len(list(solo.generate_step([3, 4, 5], max_tokens=12, want_logprobs=True))) == 12

    by_name: dict = {}
    for name, file, line, ident in log.seen:
        assert name != "<lambda>", f"anonymous program at {file}:{line}"
        by_name.setdefault(name, set()).add((file, line))
    shared = {n: sorted(w) for n, w in by_name.items() if len(w) > 1}
    assert not shared, f"one name, several programs: {shared}"
    # one function jitted under two names is two programs (block, block_lp):
    # fine; one function OBJECT jitted twice under one name is one program
    # built twice (two engines): fine too. What the benchmark finds by name:
    for name in ("block", "block_lp", "decode_step", "prefill_chunk",
                 "claim_slot", "finish_join", "resume_slot", "row_set",
                 "forward_sample", "forward_logits", "solo_block",
                 "solo_block_lp", "spec_propose_k3", "spec_verify_k3",
                 "spec_replay_k3", "export_pool_pages", "import_pool_pages",
                 "rewind_slot_offset"):
        assert name in by_name, f"{name} missing from {sorted(by_name)}"
    assert any(n.startswith("spec_verify_ngram_k") for n in by_name)
    assert "step" not in by_name and "prog" not in by_name


def _scopes_in(text: str) -> set:
    return set(re.findall(r"mst\.[A-Za-z0-9_.]*[A-Za-z0-9_]", text))


@pytest.fixture(scope="module")
def dsv2_batcher():
    cfg = DeepseekV2Config(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
        q_lora_rank=None, qk_rope_head_dim=8, qk_nope_head_dim=16,
        v_head_dim=12, n_routed_experts=4, n_shared_experts=1,
        num_experts_per_tok=2, first_k_dense_replace=1,
        mla_cache_mode="compressed",
    )
    model = DeepseekV2Model(cfg)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
        paged_attention="ragged",
    )
    batcher = ContinuousBatcher(eng, decode_block=3)
    yield batcher
    batcher.close()


@hard_timeout(420)
def test_served_deepseek_programs_carry_the_scope_vocabulary(dsv2_batcher):
    b, eng = dsv2_batcher, dsv2_batcher.engine
    assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
    lowered = b._decode_block_prog(False).lower(
        eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
        b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
        b.table,
    )
    block = lowered.as_text(debug_info=True)
    prefill = eng.prefill_slot().lower(
        eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
        jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
        jnp.asarray(8, jnp.int32), b.table,
    ).as_text(debug_info=True)
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    vocabulary = set(tracing.MODEL_SCOPES)
    for text in (block, prefill):
        assert _scopes_in(text) <= vocabulary, _scopes_in(text) - vocabulary
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.kv_write", "mst.attn.core",
              "mst.moe.router", "mst.moe.experts", "mst.moe.shared",
              "mst.mlp.dense", "mst.norm", "mst.kv_pool.regroup", "mst.head"}
    # decode: 2 rows take the gather path; the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.moe.experts.matmul", "mst.sample"}
    # prefill: 8 rows <= GATHER_PATH_MAX_TOKENS still gather; the first
    # token's sampler is a program of its own (first_sample)
    assert _scopes_in(prefill) == layers | {"mst.moe.experts.matmul"}
    # in the compiled program an operation's op_name holds the whole path,
    # and its scope is the deepest mst.* component: the layers' scopes sit
    # inside the scan's (what the trace reader, benchmarks/scope_reduce.py,
    # goes by)
    compiled = lowered.compile().as_text()
    assert re.search(
        r'op_name="jit\(block\)/[^"]*mst\.kv_pool\.regroup/[^"]*mst\.attn\.core/',
        compiled)
    assert _scopes_in(compiled) <= vocabulary


def test_packed_experts_and_the_scan_path_have_their_scopes(monkeypatch):
    """The expert paths the tiny dense model above does not take: the scan,
    the packed gather (off the chip) and the expert-indexed kernel (on it)."""
    from mlx_sharding_tpu.ops import moe

    x = jnp.ones((20, 32), jnp.float32)  # > GATHER_PATH_MAX_TOKENS rows
    w = jnp.ones((4, 32, 16), jnp.float32)
    wd = jnp.ones((4, 16, 32), jnp.float32)
    weights = jnp.full((20, 2), 0.5, jnp.float32)
    idx = jnp.zeros((20, 2), jnp.int32)
    scan = jax.jit(moe.apply_experts).lower(x, weights, idx, w, w, wd).as_text(
        debug_info=True)
    # the loop, its id list and its routing mass: nothing of the scan sits
    # under another scope, so scope_share.moe_experts reads all of it
    assert _scopes_in(scan) == {"mst.moe.experts", "mst.moe.experts.scan"}
    held = jax.jit(
        lambda *a: moe.apply_experts(*a, expert_base=2, layer=1)
    ).lower(x[:8], weights[:8], idx[:8] + 3, None, w[None].repeat(2, 0),
            wd[None].repeat(2, 0)).as_text(debug_info=True)
    assert _scopes_in(held) == {"mst.moe.experts", "mst.moe.experts.scan"}

    def packed(out_dim, in_dim):  # MLX orientation (E, out, in * bits / 32)
        return {"q": jnp.zeros((4, out_dim, in_dim // 8), jnp.uint32),
                "scales": jnp.ones((4, out_dim, in_dim // 64), jnp.float32),
                "biases": jnp.zeros((4, out_dim, in_dim // 64), jnp.float32)}

    x8 = jnp.ones((8, 64), jnp.float32)
    gather = jax.jit(moe.apply_experts).lower(
        x8, weights[:8], idx[:8], packed(128, 64), packed(128, 64), packed(64, 128),
    ).as_text(debug_info=True)
    assert {"mst.moe.experts.gather_dequant", "mst.moe.experts.matmul"} <= _scopes_in(gather)
    # on a TPU the same call is the 4-bit kernel, under the matmul scope
    # alone: gather_dequant is the fallback's, and reads 0 in a chip trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernel = jax.jit(moe.apply_experts).trace(
        jnp.ones((8, 128), jnp.float32), weights[:8], idx[:8],
        packed(128, 128), packed(128, 128), packed(128, 128),
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "quant_matmul_experts" in kernel
    assert _scopes_in(kernel) == {"mst.moe.experts", "mst.moe.experts.matmul"}
    assert _scopes_in(scan) | _scopes_in(gather) <= set(tracing.MODEL_SCOPES)


def test_no_scope_outside_the_vocabulary_in_the_source():
    """Every ``mst.*`` scope literal in the package is in the vocabulary,
    and every name of the vocabulary is used somewhere."""
    from pathlib import Path

    root = Path(tracing.__file__).parent
    used = set()
    for path in root.rglob("*.py"):
        if path.name == "tracing.py":
            continue
        used |= set(re.findall(r'named_scope\("(mst\.[^"]+)"\)', path.read_text()))
    assert used == set(tracing.MODEL_SCOPES)
    assert len(set(tracing.MODEL_SCOPES)) == len(tracing.MODEL_SCOPES)
    assert set(tracing.TICK_PHASES) >= {"harvest_wait", "idle_wait", "other"}
    assert tracing.phase_span_name("dispatch") == "mst.decode_block"
    assert tracing.phase_span_name("emit") == "mst.emit"


@pytest.fixture(scope="module")
def nemotron_batcher():
    from mlx_sharding_tpu.models import build_model

    model, _ = build_model(dict(
        model_type="nemotron_h", vocab_size=128, hidden_size=32,
        num_hidden_layers=4, hybrid_override_pattern="ME*M",
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
        conv_kernel=4, chunk_size=8, n_routed_experts=2,
        num_experts_per_tok=2, moe_intermediate_size=16, moe_latent_size=16,
        moe_shared_expert_intermediate_size=16, moe_expert_share=2,
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    batcher = ContinuousBatcher(eng, decode_block=3)
    yield batcher
    batcher.close()


@hard_timeout(420)
def test_served_nemotron_programs_carry_the_scope_vocabulary(nemotron_batcher):
    """The second family: its Mamba-2 layers, latent projections and state
    pool open the scopes PR 28 added, under the same program names."""
    b, eng = nemotron_batcher, nemotron_batcher.engine
    assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
    block = b._decode_block_prog(False).lower(
        eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
        b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
        b.table,
    ).as_text(debug_info=True)
    prefill = eng.prefill_slot().lower(
        eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
        jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
        jnp.asarray(8, jnp.int32), b.table,
    ).as_text(debug_info=True)
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    vocabulary = set(tracing.MODEL_SCOPES)
    for text in (block, prefill):
        assert _scopes_in(text) <= vocabulary, _scopes_in(text) - vocabulary
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.kv_write", "mst.attn.core",
              "mst.moe.router", "mst.moe.experts", "mst.moe.experts.scan",
              "mst.moe.shared", "mst.moe.latent", "mst.norm", "mst.head",
              "mst.ssm.in_proj", "mst.ssm.conv", "mst.ssm.out_proj",
              "mst.state_pool.regroup", "mst.kv_pool.regroup"}
    # decode: the one-step recurrence; the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.ssm.step", "mst.sample"}
    # prefill: the chunked (SSD) form
    assert _scopes_in(prefill) == layers | {"mst.ssm.scan"}


@hard_timeout(420)
def test_served_afmoe_programs_carry_the_scope_vocabulary():
    """The third family: window and full attention layers name their
    attention calls, the gate, the QK-norm and the ring pool."""
    from mlx_sharding_tpu.models import build_model

    s_, f_ = "sliding_attention", "full_attention"
    model, _ = build_model(dict(
        model_type="afmoe", vocab_size=128, hidden_size=32, intermediate_size=48,
        num_hidden_layers=3, num_dense_layers=1, layer_types=[s_, f_, s_],
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        sliding_window=8, num_experts=2, num_experts_per_tok=2,
        moe_intermediate_size=16, moe_expert_share=2,
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
        block = b._decode_block_prog(False).lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
            b.table,
        ).as_text(debug_info=True)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
    finally:
        b.close()
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.kv_write", "mst.attn.core",
              "mst.attn.window", "mst.attn.full", "mst.attn.gate", "mst.attn.qk_norm",
              "mst.moe.router", "mst.moe.experts", "mst.moe.experts.scan",
              "mst.moe.shared", "mst.mlp.dense", "mst.norm", "mst.head",
              "mst.kv_pool.regroup"}
    # decode reads the ring pool where it lies (no regroup); the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.sample"}
    # prefill takes the slot's rings out of the pool and puts them back
    assert _scopes_in(prefill) == layers | {"mst.kv_ring.regroup", "mst.state_pool.regroup"}
    assert _scopes_in(block) | _scopes_in(prefill) <= set(tracing.MODEL_SCOPES)


@hard_timeout(420)
def test_served_zaya_programs_carry_the_scope_vocabulary():
    """The fourth family: the latent attention's convolutions and value
    shift between the projections and the attention call, the MLP router,
    the per-slot state's update — under the same program names."""
    from mlx_sharding_tpu.models import build_model

    model, _ = build_model(dict(
        model_type="zaya", vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        moe_intermediate_size=16, num_experts=2, moe_expert_share=2,
        router_hidden_size=8,
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
        block = b._decode_block_prog(False).lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
            b.table,
        ).as_text(debug_info=True)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
    finally:
        b.close()
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.cca_mix", "mst.attn.kv_write",
              "mst.attn.core", "mst.moe.router", "mst.moe.experts",
              "mst.moe.experts.scan", "mst.state_pool.regroup", "mst.norm", "mst.head"}
    # decode carries the page pool through the layer scan: no regroup; the
    # sampler is in the block
    assert _scopes_in(block) == layers | {"mst.sample"}
    # prefill scans each layer's rows of the slot's contiguous view
    assert _scopes_in(prefill) == layers | {"mst.kv_pool.regroup"}
    assert _scopes_in(block) | _scopes_in(prefill) <= set(tracing.MODEL_SCOPES)


@hard_timeout(420)
def test_served_granite_programs_walk_their_periods_with_the_state_pool_in_place():
    """The fifth family: a Mamba-2 or attention mixer and an MLP in every
    block, under the same program names and scopes. Two periods of ``MMAM``:
    the decode block holds the Mamba body TWICE (once per run of a period,
    whatever the depth), each an in-place update of its rows of the state
    pool at a traced rank; the pool rides the carry of the period scan and of
    the run's scan, never a scan's ``xs`` or ``ys``, and nothing of the
    pool's size is selected, concatenated, padded or sliced out of it."""
    from mlx_sharding_tpu.models import build_model

    model, _ = build_model(dict(
        model_type="granitemoehybrid", vocab_size=128, hidden_size=32,
        num_hidden_layers=8, layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
        num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=48,
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
        attention_multiplier=0.0625, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8,
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
        args = (eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
                b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
                b.table)
        prog = b._decode_block_prog(False)
        block = prog.lower(*args).as_text(debug_info=True)
        jaxpr = jax.make_jaxpr(prog)(*args)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
        pool = b.cache.state["ssm"].shape[1:]  # (layers, slots + 1, H, P, N)
    finally:
        b.close()
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.kv_write", "mst.attn.core",
              "mst.mlp.dense", "mst.norm", "mst.head", "mst.ssm.in_proj",
              "mst.ssm.conv", "mst.ssm.out_proj", "mst.state_pool.regroup"}
    # decode: the one-step recurrence, the page pool carried (no regroup);
    # the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.ssm.step", "mst.sample"}
    # prefill: the chunked (SSD) form on the slot's contiguous rows
    assert _scopes_in(prefill) == layers | {"mst.ssm.scan", "mst.kv_pool.regroup"}
    assert _scopes_in(block) | _scopes_in(prefill) <= set(tracing.MODEL_SCOPES)

    walked = list(_walk(jaxpr.jaxpr))
    updates = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "dynamic_update_slice"
        and eqn.outvars[0].aval.shape == pool
    ]
    assert len(updates) == 2  # the run of two (a scan of its own) and the run of one
    assert sorted(len(scans) for scans in updates) == [2, 3]  # block > periods [> run]
    for scans in updates:
        for scan in scans:
            n_c, n_k = scan.params["num_consts"], scan.params["num_carry"]
            # (the block's own scan carries it with the stage axis in front)
            assert pool in [v.aval.shape[-5:] for v in scan.invars[n_c : n_c + n_k]]
    for eqn, _ in walked:
        if eqn.primitive.name == "scan":
            ys = [v.aval for v in eqn.outvars[eqn.params["num_carry"]:]]
            assert not any(a.shape[-4:] == pool[-4:] for a in _scanned(eqn) + ys)
    moved = [
        eqn.primitive.name for eqn, _ in walked for v in eqn.outvars
        if eqn.primitive.name in ("select_n", "concatenate", "pad", "slice", "gather", "copy")
        and getattr(v.aval, "shape", ())[-3:] == pool[-3:] and v.aval.size >= 2 * 4 * 16 * 8
        # x[0] of the stage axis is a slice that takes everything: no copy
        and not (eqn.primitive.name == "slice" and v.aval.size == eqn.invars[0].aval.size)
    ]
    # the frozen-slot select of each body, over the SLOTS' rows of one layer
    assert moved == ["select_n"] * 2, moved


def _pools_ride_every_carry_and_nothing_of_their_size_moves(
        walked, updates, writes, pool, pages, selects):
    """Of a decode block that walks a pattern of layers: every scan around an
    update of the state ``pool`` (layers, slots + 1, H, D, D) or a write to
    the page pool ``pages`` carries that pool (the block's own scan with the
    stage axis in front; a run of linear layers leaves the page pool alone,
    so its scan closes over it), no scan has either among its ``xs`` or
    ``ys``, and nothing of either pool's size is selected, concatenated,
    padded, sliced out of it or copied — but the ``selects`` frozen-slot
    selects over the SLOTS' rows of one layer."""
    def carried(scan):
        n_c, n_k = scan.params["num_consts"], scan.params["num_carry"]
        return [v.aval.shape for v in scan.invars[n_c : n_c + n_k]]

    for scans in updates:
        assert all(pool in [s[-5:] for s in carried(scan)] for scan in scans)
    for scans in writes:
        assert all(
            math.prod(pages) in [math.prod(s) for s in carried(scan) if s[-1:] == pages[-1:]]
            for scan in scans)
    for eqn, _ in walked:
        if eqn.primitive.name == "scan":
            ys = [v.aval for v in eqn.outvars[eqn.params["num_carry"]:]]
            assert not any(a.shape[-4:] == pool[-4:] for a in _scanned(eqn) + ys)
            assert not any(a.shape[-3:] == pages[-3:] for a in _scanned(eqn) + ys)
    moved = [
        eqn.primitive.name for eqn, _ in walked for v in eqn.outvars
        if eqn.primitive.name in ("select_n", "concatenate", "pad", "slice", "gather", "copy")
        and getattr(v.aval, "shape", ())[-3:] == pool[-3:] and v.aval.size >= 2 * math.prod(pool[-3:])
        # x[0] of the stage axis is a slice that takes everything: no copy
        and not (eqn.primitive.name == "slice" and v.aval.size == eqn.invars[0].aval.size)
    ]
    assert moved == ["select_n"] * selects, moved
    moved = [
        eqn.primitive.name for eqn, _ in walked for v in eqn.outvars
        if eqn.primitive.name in ("select_n", "concatenate", "pad", "slice", "copy")
        and getattr(v.aval, "size", 0) >= math.prod(pages[1:])
        and getattr(v.aval, "shape", ())[-1:] == pages[-1:]
        and not (eqn.primitive.name == "slice" and v.aval.size == eqn.invars[0].aval.size)
    ]
    assert moved == [], moved


@hard_timeout(420)
def test_served_kimi_linear_programs_walk_a_head_the_periods_and_a_tail():
    """The sixth family: a KDA or MLA mixer, then an MLP or experts, under
    the same program names and scopes. A head layer, two periods of ``K K M
    K`` and the tail ``K M``: the decode block holds the KDA body FOUR times
    (the head's, the period's run of two and run of one, the tail's) and the
    MLA body TWICE (the period's, the tail's), whatever the depth; the state
    pool and the page pool ride the carry of every scan, never its ``xs`` or
    ``ys``, and nothing of either pool's size is selected, concatenated,
    padded, sliced out of it or copied."""
    from mlx_sharding_tpu.models import build_model

    model, _ = build_model(dict(
        model_type="kimi_linear", vocab_size=128, hidden_size=32,
        num_hidden_layers=11, num_attention_heads=2, intermediate_size=48,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        moe_intermediate_size=16, num_experts=2, moe_expert_share=2,
        num_experts_per_token=2, routed_scaling_factor=2.446,
        linear_attn_config=dict(
            full_attn_layers=[4, 8, 11], kda_layers=[1, 2, 3, 5, 6, 7, 9, 10],
            head_dim=8, num_heads=2, short_conv_kernel_size=4),
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
        args = (eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
                b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
                b.table)
        prog = b._decode_block_prog(False)
        block = prog.lower(*args).as_text(debug_info=True)
        jaxpr = jax.make_jaxpr(prog)(*args)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
        pool = b.cache.state["kda"].shape[1:]  # (layers, slots + 1, H, D, D)
        pages = b.cache.k.shape[1:]  # (layers, pages + 1, 1, page, 1, rank + rope)
    finally:
        b.close()
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.kv_write", "mst.attn.core",
              "mst.mlp.dense", "mst.moe.router", "mst.moe.experts",
              "mst.moe.experts.scan", "mst.moe.shared", "mst.norm", "mst.head",
              "mst.kda.proj", "mst.kda.conv", "mst.kda.gate", "mst.kda.out",
              "mst.state_pool.regroup"}
    # decode: the one-step recurrence, the page pool carried (no regroup);
    # the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.kda.step", "mst.sample"}
    # prefill: the chunked (WY) form on the slot's contiguous rows
    assert _scopes_in(prefill) == layers | {"mst.kda.scan", "mst.kv_pool.regroup"}
    assert _scopes_in(block) | _scopes_in(prefill) <= set(tracing.MODEL_SCOPES)

    walked = list(_walk(jaxpr.jaxpr))
    updates = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "dynamic_update_slice"
        and eqn.outvars[0].aval.shape == pool
    ]
    # block [> periods [> run]]: the head's, the tail's, the run of one, the run of two
    assert sorted(len(scans) for scans in updates) == [1, 1, 2, 3]
    writes = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "scatter" and eqn.outvars[0].aval.size == math.prod(pages)
        and eqn.outvars[0].aval.shape[-1] == pages[-1]
    ]
    assert sorted(len(scans) for scans in writes) == [1, 2]  # the tail's, the period's
    # the frozen-slot select of each KDA body, over the SLOTS' rows of one layer
    _pools_ride_every_carry_and_nothing_of_their_size_moves(walked, updates, writes, pool, pages, selects=4)


@hard_timeout(420)
def test_served_qwen3_next_programs_walk_three_periods(monkeypatch):
    """The seventh family: a Gated DeltaNet or gated attention mixer, then
    softmax-routed experts beside a gated shared expert, under the same
    program names and scopes and ONE new scope (``mst.moe.shared_gate``).
    Three periods of ``G G G A``: the decode block holds the linear body ONCE
    (the period's run of three, an inner scan) and the attention body ONCE,
    whatever the depth; the state pool and the page pool ride the carry of
    every scan, never its ``xs`` or ``ys``, and nothing of either pool's size
    is selected, concatenated, padded, sliced out of it or copied. No
    program name is used for two programs."""
    from mlx_sharding_tpu.models import build_model

    log = JitLog(jax.jit)
    monkeypatch.setattr(jax, "jit", log)
    model, _ = build_model(dict(
        model_type="qwen3_next", vocab_size=128, hidden_size=32,
        num_hidden_layers=12, num_attention_heads=2, num_key_value_heads=1,
        head_dim=16, partial_rotary_factor=0.5, full_attention_interval=4,
        linear_conv_kernel_dim=4, linear_key_head_dim=8, linear_value_head_dim=8,
        linear_num_key_heads=1, linear_num_value_heads=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, num_experts=2, moe_expert_share=2,
        num_experts_per_tok=2,
    ))
    assert model.walk == ([], ["gdn", "gdn", "gdn", "attn"], 3, [])
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
        args = (eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
                b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
                b.table)
        prog = b._decode_block_prog(False)
        block = prog.lower(*args).as_text(debug_info=True)
        jaxpr = jax.make_jaxpr(prog)(*args)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
        pool = b.cache.state["gdn"].shape[1:]  # (layers, slots + 1, Hv, D, D)
        pages = b.cache.k.shape[1:]  # (layers, pages + 1, 1, page, 1, Hkv * D)
    finally:
        b.close()
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    by_name: dict = {}
    for name, file, line, _ in log.seen:
        assert name != "<lambda>", f"anonymous program at {file}:{line}"
        by_name.setdefault(name, set()).add((file, line))
    assert not {n: sorted(w) for n, w in by_name.items() if len(w) > 1}
    assert {"block", "prefill_chunk", "claim_slot", "finish_join"} <= set(by_name)
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.qk_norm", "mst.attn.gate",
              "mst.attn.kv_write", "mst.attn.core", "mst.moe.router", "mst.moe.experts",
              "mst.moe.experts.scan", "mst.moe.shared", "mst.moe.shared_gate", "mst.norm",
              "mst.head", "mst.kda.proj", "mst.kda.conv", "mst.kda.gate", "mst.kda.out",
              "mst.state_pool.regroup"}
    # decode: the one-step recurrence, the page pool carried (no regroup);
    # the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.kda.step", "mst.sample"}
    # prefill: the chunked (WY) form on the slot's contiguous rows
    assert _scopes_in(prefill) == layers | {"mst.kda.scan", "mst.kv_pool.regroup"}
    assert _scopes_in(block) | _scopes_in(prefill) <= set(tracing.MODEL_SCOPES)

    walked = list(_walk(jaxpr.jaxpr))
    updates = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "dynamic_update_slice"
        and eqn.outvars[0].aval.shape == pool
    ]
    # block > periods > the run of three: ONE linear body
    assert [len(scans) for scans in updates] == [3]
    assert [s.params["length"] for s in updates[0]] == [3, 3, 3]  # steps, periods, run
    writes = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "scatter" and eqn.outvars[0].aval.size == math.prod(pages)
        and eqn.outvars[0].aval.shape[-1] == pages[-1]
    ]
    assert [len(scans) for scans in writes] == [2, 2]  # K and V: block > periods

    # the frozen-slot select of the one linear body, over the SLOTS' rows of one layer
    _pools_ride_every_carry_and_nothing_of_their_size_moves(walked, updates, writes, pool, pages, selects=1)


# ------------------------------------------- what rides the layer scan


def _walk(jaxpr, scans=()):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, each with the
    ``scan`` equations that enclose it, outermost first."""
    for eqn in jaxpr.eqns:
        yield eqn, scans
        inner = scans + (eqn,) if eqn.primitive.name == "scan" else scans
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, inner)


def _scanned(eqn):
    """Avals of a ``scan`` equation's ``xs`` (not its consts, not its carry)."""
    skip = eqn.params["num_consts"] + eqn.params["num_carry"]
    return [v.aval for v in eqn.invars[skip:]]


@hard_timeout(420)
def test_packed_expert_stacks_do_not_ride_the_layer_scan(monkeypatch):
    """The tiny packed DeepSeek decode block, traced as on a TPU: the MoE
    layer scan's ``xs`` hold the small leaves, the layer's row in the pool
    and the layer counter — no leaf of the expert stacks — and the
    expert-indexed kernel's ``q`` operand is the whole stack as
    ``(L*E, out, words)``."""
    from mlx_sharding_tpu.models import build_model
    from mlx_sharding_tpu.ops.quant import quantize_jax

    n_moe, n_exp, width = 2, 4, 128
    model, _ = build_model(dict(
        model_type="deepseek_v2", vocab_size=128, hidden_size=width,
        intermediate_size=64, moe_intermediate_size=width,
        num_hidden_layers=1 + n_moe, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=16, q_lora_rank=None,
        qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=12,
        n_routed_experts=n_exp, n_shared_experts=1, num_experts_per_tok=2,
        first_k_dense_replace=1, mla_cache_mode="compressed",
        quantization={"group_size": 64, "bits": 4},
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    moe = params["layers"]["moe"]
    for name in ("w_gate", "w_up", "w_down"):  # (L, E, in, out) -> MLX triples
        moe[name] = dict(zip(
            ("q", "scales", "biases"),
            quantize_jax(jnp.swapaxes(moe[name], -1, -2), 64, 4),
        ))
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
        paged_attention="ragged",
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jaxpr = jax.make_jaxpr(b._decode_block_prog(False))(
            eng.layer_params, eng.layer_masks, eng.vocab_parts,
            eng.shared_params, b.last_tok, b.cache, b.active, b.recent,
            b.keys, b.sp, b.rep_sizes, b.table,
        )
    finally:
        b.close()
    kernels = [
        (eqn, scans) for eqn, scans in _walk(jaxpr.jaxpr)
        if eqn.primitive.name == "pallas_call"
        and eqn.params["name"] == "quant_matmul_experts"
    ]
    assert len(kernels) == 3  # gate, up, down: one layer body, traced once
    words = width // 8
    for eqn, scans in kernels:
        q = eqn.invars[3].aval  # ids, live, x_planes, q, scales, biases
        # 16 words are no multiple of 128: the kernel reads them transposed
        assert q.dtype == jnp.uint32 and q.shape == (n_moe * n_exp, words, width)
        layer_scan = scans[-1]  # the innermost: decode block > layer scan
        assert len(scans) == 2
        for aval in _scanned(layer_scan):
            assert aval.dtype != jnp.uint32 and aval.shape[1:2] != (n_exp,), aval
        assert any(  # the counter the kernel's id table is built from
            a.shape == (n_moe,) and a.dtype == jnp.int32 for a in _scanned(layer_scan)
        )
        whole = [v.aval for v in layer_scan.invars[: layer_scan.params["num_consts"]]]
        assert sum(a.shape == (n_moe, n_exp, width, words) for a in whole) == 3


DSV2_TINY = dict(
    model_type="deepseek_v2", vocab_size=256, hidden_size=32,
    intermediate_size=64, moe_intermediate_size=16, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
    q_lora_rank=None, qk_rope_head_dim=8, qk_nope_head_dim=16,
    v_head_dim=12, n_routed_experts=4, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1,
    mla_cache_mode="compressed",
)


@pytest.mark.parametrize(
    "config", [DSV2_TINY, dict(model_type="llama", **TINY)],
    ids=["latent-two-groups", "heads-apart"],
)
def test_ragged_block_carries_the_pool_through_its_layer_scans(config):
    """What a CPU run can see of the ragged decode step's cost: in the
    jaxpr of ``block`` no ``slice``, ``concatenate`` or ``pad`` produces a
    layer's pool or more, the pool ``(L * (P+1), page, H, D)`` enters and
    leaves every layer scan through its CARRY and no scan has a pool among
    its ``xs`` or ``ys``. A ``dynamic_slice`` of one layer's pages feeds the
    attention call only where the rows keep their heads apart (the call
    relayouts what it is handed), never more than a layer."""
    from mlx_sharding_tpu.models import build_model

    model, _ = build_model(config)
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=3, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
        paged_attention="ragged",
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        jaxpr = jax.make_jaxpr(b._decode_block_prog(False))(
            eng.layer_params, eng.layer_masks, eng.vocab_parts,
            eng.shared_params, b.last_tok, b.cache, b.active, b.recent,
            b.keys, b.sp, b.rep_sizes, b.table,
        )
        pool = b.cache.k  # (S, L, P+1, 1, page, H, D)
    finally:
        b.close()
    layers, pages = pool.shape[1], pool.shape[2]

    def pool_sized(aval):  # a layer's pool or more (tiny weights are larger)
        return (
            aval.shape[-3:] == pool.shape[-3:] and aval.size >= pool.size // layers
        )

    eqns = [eqn for eqn, _ in _walk(jaxpr.jaxpr)]
    sliced = [
        (e.primitive.name, v.aval.shape) for e in eqns for v in e.outvars
        if e.primitive.name in ("slice", "dynamic_slice", "concatenate", "pad")
        and pool_sized(v.aval)
    ]
    n_scans = len(model.sp_groups())  # dense and MoE (DeepSeek), or one stack
    apart = pool.shape[-2] > 1  # then K's and V's pages of the layer, a scan
    assert sliced == (
        [("dynamic_slice", (pages, *pool.shape[4:]))] * 2 * n_scans if apart else []
    )
    carrying = 0
    for e in eqns:
        if e.primitive.name != "scan":
            continue
        n_c, n_k = e.params["num_consts"], e.params["num_carry"]
        ys = [v.aval for v in e.outvars[n_k:]]
        assert not any(pool_sized(a) for a in _scanned(e) + ys)
        carry = [v.aval.shape for v in e.invars[n_c : n_c + n_k]]
        carrying += (layers * pages, *pool.shape[4:]) in carry
    assert carrying == n_scans


@pytest.mark.parametrize("family", ["llama", "mixtral"])
def test_other_models_layer_scans_are_as_they_were(family):
    """No read-in-place leaves named: every leaf of the stack, K and V are
    the scan's ``xs``, the hidden state its one carry, and nothing else."""
    from mlx_sharding_tpu.models import build_model

    extra = dict(num_local_experts=4, num_experts_per_tok=2) if family == "mixtral" else {}
    model, cfg = build_model(dict(model_type=family, **TINY, **extra))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    cache = model.make_cache(1, 16, jnp.float32)
    h = jnp.zeros((1, 4, cfg.hidden_size), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda lp, h, k, v: model.run_layers(lp, h, k, v, jnp.asarray(0, jnp.int32))
    )(params["layers"], h, cache.k, cache.v)
    outer = [eqn for eqn, scans in _walk(jaxpr.jaxpr)
             if eqn.primitive.name == "scan" and not scans]
    assert len(outer) == 1
    assert outer[0].params["num_carry"] == 1
    assert len(_scanned(outer[0])) == len(jax.tree.leaves(params["layers"])) + 2


@hard_timeout(420)
def test_served_sdar_moe_programs_keep_the_names_and_add_one_scope(monkeypatch):
    """The eighth family generates by diffusion over blocks: its decode
    program is compiled under the name ``block`` all the same (a step of it
    is one forward over every slot's block of 4), its join ends in a program
    of its own name (``finish_join_block``: no first token), and ONE scope is
    new, ``mst.diffusion.unmask``. The page pool rides the layer scan's carry:
    the only pool-sized things the block makes are each layer's scatter of
    the slots' rows. No program name is used for two programs."""
    from mlx_sharding_tpu.models import build_model

    log = JitLog(jax.jit)
    monkeypatch.setattr(jax, "jit", log)
    model, _ = build_model(dict(
        model_type="sdar_moe", vocab_size=128, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=16, num_experts=2, moe_expert_share=2,
        num_experts_per_tok=2, block_length=4, denoising_steps=2,
        remasking_strategy="low_confidence_dynamic", mask_token_id=0,
    ))
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6, 7, 8], max_tokens=5))) == 5
        args = (eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
                b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
                b.table)
        prog = b._decode_block_prog(False)
        block = prog.lower(*args).as_text(debug_info=True)
        jaxpr = jax.make_jaxpr(prog)(*args)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
        pages = b.cache.k.shape[1:]  # (layers, pages + 1, 1, page, 1, Hkv * D)
    finally:
        b.close()
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    by_name: dict = {}
    for name, file, line, _ in log.seen:
        assert name != "<lambda>", f"anonymous program at {file}:{line}"
        by_name.setdefault(name, set()).add((file, line))
    assert not {n: sorted(w) for n, w in by_name.items() if len(w) > 1}
    assert {"block", "prefill_chunk", "claim_slot", "finish_join_block"} <= set(by_name)
    assert "finish_join" not in by_name
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.qk_norm", "mst.attn.core",
              "mst.moe.router", "mst.moe.experts", "mst.moe.experts.scan", "mst.norm",
              "mst.head", "mst.kv_pool.regroup"}
    assert _scopes_in(block) == layers | {
        "mst.attn.kv_write", "mst.sample", "mst.diffusion.unmask"}
    assert _scopes_in(prefill) == layers | {"mst.attn.kv_write"}
    # the pool is written by scatter, in place on the carry, and never copied
    walked = list(_walk(jaxpr.jaxpr))
    size = math.prod(pages)
    made = [eqn.primitive.name for eqn, _ in walked
            if any(getattr(v.aval, "size", 0) == size for v in eqn.outvars)]
    assert set(made) <= {"scatter", "scan", "while", "pjit", "shard_map", "reshape",
                         "squeeze", "broadcast_in_dim", "jit", "closed_call",
                         "custom_jvp_call"}, sorted(set(made))
    # K and V, once a layer scan's body: a block of 3 forwards is a scanned pair
    # (a wide forward, both lanes in ONE scatter a pool, and a narrow one) and
    # one more wide forward behind it
    assert made.count("scatter") == 2 * 3


@hard_timeout(420)
def test_served_olmo_hybrid_programs_walk_two_periods(monkeypatch):
    """The thirteenth family: a Gated DeltaNet on a rectangular state or
    multi-head attention behind a full-width QK norm, then a dense MLP, each
    sub-layer's OUTPUT normed, under the same program names and scopes and NO
    new one. Two periods of ``G G G A``: the decode block holds the linear
    body ONCE (the period's run of three, an inner scan) and the attention
    body ONCE; the state pool — two heads side by side on its lanes — and the
    page pool ride the carry of every scan, never its ``xs`` or ``ys``. No
    program name is used for two programs."""
    from mlx_sharding_tpu.models import build_model

    log = JitLog(jax.jit)
    monkeypatch.setattr(jax, "jit", log)
    model, _ = build_model(dict(
        model_type="olmo_hybrid", vocab_size=128, hidden_size=32,
        num_hidden_layers=8, num_attention_heads=2, num_key_value_heads=2,
        intermediate_size=48, linear_conv_kernel_dim=4, linear_key_head_dim=8,
        linear_value_head_dim=64, linear_num_key_heads=2, linear_num_value_heads=2,
    ))
    assert model.walk == ([], ["gdn", "gdn", "gdn", "attn"], 2, [])
    assert model.state_pack == 2
    params = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    eng = PipelineEngine(
        model, params, pipeline_mesh(1), microbatches=2, max_seq=64,
        cache_dtype=jnp.float32, prefill_chunk=8, pool_pages=10, page_size=8,
    )
    b = ContinuousBatcher(eng, decode_block=3)
    try:
        assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
        args = (eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
                b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes,
                b.table)
        prog = b._decode_block_prog(False)
        block = prog.lower(*args).as_text(debug_info=True)
        jaxpr = jax.make_jaxpr(prog)(*args)
        prefill = eng.prefill_slot().lower(
            eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            jnp.zeros((1, 8), jnp.int32), jnp.asarray(0, jnp.int32), b.cache,
            jnp.asarray(8, jnp.int32), b.table,
        ).as_text(debug_info=True)
        pool = b.cache.state["gdn"].shape[1:]  # (layers, slots + 1, Hv / 2, Dk, 2 Dv)
        pages = b.cache.k.shape[1:]  # (layers, pages + 1, 1, page, 1, Hkv * D)
    finally:
        b.close()
    assert pool == (6, 3, 1, 8, 128) and pages[-1] == 32
    assert "module @jit_block " in block
    assert "module @jit_prefill_chunk " in prefill
    by_name: dict = {}
    for name, file, line, _ in log.seen:
        assert name != "<lambda>", f"anonymous program at {file}:{line}"
        by_name.setdefault(name, set()).add((file, line))
    assert not {n: sorted(w) for n, w in by_name.items() if len(w) > 1}
    assert {"block", "prefill_chunk", "claim_slot", "finish_join"} <= set(by_name)
    layers = {"mst.embed", "mst.attn.qkv", "mst.attn.qk_norm", "mst.attn.kv_write",
              "mst.attn.core", "mst.mlp.dense", "mst.norm", "mst.head",
              "mst.kda.proj", "mst.kda.conv", "mst.kda.gate", "mst.kda.out",
              "mst.state_pool.regroup"}
    # decode: the one-step recurrence, the page pool carried (no regroup);
    # the sampler is in the block
    assert _scopes_in(block) == layers | {"mst.kda.step", "mst.sample"}
    # prefill: the chunked (WY) form on the slot's contiguous rows
    assert _scopes_in(prefill) == layers | {"mst.kda.scan", "mst.kv_pool.regroup"}
    assert _scopes_in(block) | _scopes_in(prefill) <= set(tracing.MODEL_SCOPES)

    walked = list(_walk(jaxpr.jaxpr))
    updates = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "dynamic_update_slice"
        and eqn.outvars[0].aval.shape == pool
    ]
    # block > periods > the run of three: ONE linear body
    assert [len(scans) for scans in updates] == [3]
    assert [s.params["length"] for s in updates[0]] == [3, 2, 3]  # steps, periods, run
    writes = [
        scans for eqn, scans in walked
        if eqn.primitive.name == "scatter" and eqn.outvars[0].aval.size == math.prod(pages)
        and eqn.outvars[0].aval.shape[-1] == pages[-1]
    ]
    assert [len(scans) for scans in writes] == [2, 2]  # K and V: block > periods
    # nothing in the POOL's layout is selected or copied: off the chip the
    # one-step form views the slots' rows of one layer heads-apart, and its
    # frozen-slot select is over that view
    _pools_ride_every_carry_and_nothing_of_their_size_moves(walked, updates, writes, pool, pages, selects=0)
