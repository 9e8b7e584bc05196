"""Compile a configuration's prefill-chunk and decode-block programs for a
TPU that is described, not attached, at full size, and read
``memory_analysis()``: what the compiler refuses or what does not fit 16 GB
costs no chip time (on-chip-measurement guide, section 2). A compile that
passes is not a chip run.

The engine is the program's own ``PipelineEngine``, handed a
``ResidentWeights`` of shapes (the same split ``place_weights`` makes,
evaluated abstractly) on a mesh of the described devices; the decode block
is the scan ``scheduler.ContinuousBatcher._decode_block_prog`` builds over
``engine.decode_cb()``, written out here because a batcher cannot be built
without a device to hold its state.

``JAX_PLATFORMS=cpu python benchmarks/aot.py benchmarks/configs/<name>.json``
prints both analyses; ``benchmarks/tests/test_aot_compile.py`` asserts on
them.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def abstract_engine(config: dict, devices):
    """``(engine, weights)``: the program's engine over a ``ResidentWeights``
    whose every array is a ``ShapeDtypeStruct`` placed on a mesh of
    ``devices``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.config import family, program_config, published_config, server_flag
    from benchmarks.weights import LazyStack
    from mlx_sharding_tpu.models import build_model
    from mlx_sharding_tpu.ops.quant import dequantize, is_quantized
    from mlx_sharding_tpu.parallel.mesh import AXIS_PP, make_mesh
    from mlx_sharding_tpu.parallel.pipeline import (
        PipelineEngine,
        balanced_stage_bounds,
        split_stage_stacks,
    )
    from mlx_sharding_tpu.weights import ResidentWeights

    bench = config["bench"]
    stages = int(server_flag(config, "--num-stages", 1))
    mesh = make_mesh(pp=stages, devices=list(devices)[:stages])
    model, cfg = build_model(program_config(config))
    model.compute_dtype = jnp.bfloat16

    lazy = family(config).program_params(published_config(config), bench["weight_format"], 0)
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), lazy,
        is_leaf=lambda x: isinstance(x, LazyStack),
    )
    bounds = balanced_stage_bounds(cfg.num_hidden_layers, stages)
    side = {}

    def place(params):
        split, masks, slots = split_stage_stacks(model, params["layers"], bounds)
        side["slots"] = slots
        vs = -(-cfg.vocab_size // stages)
        gs, bits = model._quant_args()

        def dense(w, transpose):
            if is_quantized(w):
                w = dequantize(w["q"], w["scales"], w["biases"], gs, bits, jnp.bfloat16)
                return w.T if transpose else w
            return w

        table = dense(params["embed"]["weight"], False)
        table = jnp.pad(table, ((0, vs * stages - table.shape[0]), (0, 0)))
        head = dense(params["lm_head"]["weight"], True)
        head = jnp.pad(head, ((0, 0), (0, vs * stages - head.shape[1])))
        vparts = (table.reshape(stages, vs, -1),
                  head.reshape(-1, stages, vs).transpose(1, 0, 2))
        return split, masks, vparts, {"final_norm": params["final_norm"]}

    split, masks, vparts, shared = jax.eval_shape(place, shapes)
    staged = NamedSharding(mesh, P(AXIS_PP))
    rep = NamedSharding(mesh, P())

    def on(sharding):
        return lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    layer_params = jax.tree.map(on(staged), split)
    weights = ResidentWeights(
        mesh=mesh, stage_bounds=bounds,
        layer_specs=jax.tree.map(lambda _: P(AXIS_PP), split),
        layer_params=layer_params,
        layer_masks=jax.tree.map(on(staged), masks),
        layers_per_stage=side["slots"], fused_projections=[],
        vocab_size=cfg.vocab_size, head_tied=False,
        vocab_parts=jax.tree.map(on(staged), vparts),
        shared_params=jax.tree.map(on(rep), shared),
        weight_bytes=0,
    )
    engine = PipelineEngine(
        model, None, mesh, weights=weights,
        microbatches=int(server_flag(config, "--concurrent")),
        max_seq=int(server_flag(config, "--max-seq", 4096)), cache_dtype=jnp.bfloat16,
        prefill_chunk=int(server_flag(config, "--prefill-chunk", 256)),
        decode_block=int(server_flag(config, "--decode-block", 16)),
        pool_pages=int(server_flag(config, "--paged-pool")),
    )
    return engine, weights


def abstract_state(engine):
    """Shapes of the batcher's device state for ``engine`` (scheduler.py,
    ``ContinuousBatcher.__init__``: paged cache, table, sampler rows)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mlx_sharding_tpu.cache import KVCache
    from mlx_sharding_tpu.sample import make_sampler_params, stack_sampler_params

    mesh, m = engine.mesh, engine.microbatches
    rep = NamedSharding(mesh, P())
    staged = NamedSharding(mesh, engine._kv_spec)

    def sds(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    k_dim, v_dim = engine.model.cache_head_dim()
    pool = (engine.num_stages, engine.layers_per_stage, engine.pool_pages + 1, 1,
            engine.page_size, engine.model.cache_num_heads())
    cache = KVCache(
        k=sds((*pool, k_dim), jnp.bfloat16, staged),
        v=sds((*pool, v_dim), jnp.bfloat16, staged),
        offset=sds((m,), jnp.int32),
    )
    sp = jax.eval_shape(
        lambda: stack_sampler_params(
            [make_sampler_params(min_bias_slots=512) for _ in range(m)])
    )
    return {
        "cache": cache,
        "table": sds((m + 1, engine.slot_pages), jnp.int32),
        "tok": sds((m, 1), jnp.int32),
        "active": sds((m,), jnp.bool_),
        "recent": sds((m, 64), jnp.int32),  # repetition_window default
        "keys": sds((m, 2), jnp.uint32),
        "sp": jax.tree.map(lambda x: sds(x.shape, x.dtype), sp),
        "rep_sizes": sds((m,), jnp.int32),
    }


def compile_programs(config: dict, devices, steps_per_block: int = 8) -> dict:
    """``{"prefill": CompiledMemoryStats, "decode": ...}``."""
    import jax
    import jax.numpy as jnp

    engine, w = abstract_engine(config, devices)
    st = abstract_state(engine)
    rep = st["tok"].sharding

    prefill = engine.prefill_slot().lower(
        w.layer_params, w.layer_masks, w.vocab_parts, w.shared_params,
        jax.ShapeDtypeStruct((1, engine.prefill_chunk), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), st["cache"],
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep), st["table"],
    ).compile()

    step = engine.decode_cb()

    def block(layer_params, masks, vparts, shared, tok, cache, active, recent,
              keys, sp, rep_sizes, table):
        def body(carry, _):
            tok, cache, recent, keys = carry
            tok, _lp, cache, recent, keys = step(
                layer_params, masks, vparts, shared, tok, cache, active,
                recent, keys, sp, rep_sizes, table)
            return (tok, cache, recent, keys), (tok,)

        (tok, cache, recent, keys), outs = jax.lax.scan(
            body, (tok, cache, recent, keys), None, length=steps_per_block)
        return outs, tok, cache, recent, keys

    decode = jax.jit(block, donate_argnums=(5, 7, 8)).lower(
        w.layer_params, w.layer_masks, w.vocab_parts, w.shared_params,
        st["tok"], st["cache"], st["active"], st["recent"], st["keys"],
        st["sp"], st["rep_sizes"], st["table"],
    ).compile()
    return {"prefill": prefill.memory_analysis(), "decode": decode.memory_analysis(),
            "decode_text": decode.as_text(), "prefill_text": prefill.as_text()}


def resident_bytes(mem) -> int:
    """What a program needs on the chip while it runs: arguments, outputs
    that are not aliased to them, temporaries."""
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from jax.experimental import topologies

    import jax

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    config = json.loads(Path(argv[0]).read_text())
    # the dispatch predicates of ops/ ask jax.default_backend(), which stays
    # "cpu" here: answer "tpu" in their place, as tests/test_tpu_compile.py
    # does, or the XLA fallbacks are what gets compiled
    jax.default_backend = lambda: "tpu"
    out = compile_programs(config, topo.devices)
    for name in ("prefill", "decode"):
        m = out[name]
        print(name, {
            "arguments_gb": m.argument_size_in_bytes / 1e9,
            "outputs_gb": m.output_size_in_bytes / 1e9,
            "aliased_gb": m.alias_size_in_bytes / 1e9,
            "temporaries_gb": m.temp_size_in_bytes / 1e9,
            "resident_gb": resident_bytes(m) / 1e9,
            "kernels": {k: out[name + "_text"].count(k) for k in
                        ("quant_gemv_pipelined", "quant_matmul", "paged_attention",
                         "flash_attention", "tpu_custom_call")},
        })
    return 0


if __name__ == "__main__":
    sys.exit(main())
