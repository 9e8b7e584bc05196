"""How ``scopes_tpu.xplane.pb`` was recorded (one v5e chip, PR 24): two small
jitted programs whose operations sit in ``jax.named_scope``s of the served
path's vocabulary — one of them inside a ``lax.scan``, so that its operations
run inside a ``while`` — and one operation of the first program (the sort) left
outside every scope. What came out, beside what was written: the ``while``
itself carries no ``tf_op``; the scan's slicing of ``w`` costs no operation of
its own, so ``mst.kv_pool.regroup`` appears only as a component of the
layers' names; and XLA rewrote the matmul under ``mst.head`` (an operand
sliced out of the stack) into a fusion without any metadata — a scope the
compiler dropped, which the reduction can only count as ``unscoped``.

    python3 benchmarks/testdata/record_scopes.py <out dir>

``benchmarks/tests/test_scope_reduce.py`` reduces the recording
(``benchmarks/scope_reduce.py``) and checks it against what this script
wrote beside it (``scopes_tpu.expected.json``).
"""
import glob
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

N_BLOCK, N_CHUNK, LAYERS = 3, 2, 4


def main(out: Path) -> None:
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def block(x, w):
        def layer(h, w_l):
            with jax.named_scope("mst.attn.core"):
                h = jnp.tanh(h @ w_l)
            with jax.named_scope("mst.moe.experts"):
                with jax.named_scope("mst.moe.experts.matmul"):
                    h = (h @ w_l.T) * 0.01
            return h, None

        with jax.named_scope("mst.kv_pool.regroup"):
            x, _ = jax.lax.scan(layer, x, w)
        with jax.named_scope("mst.head"):
            x = x @ w[0]
        return jnp.sort(x, axis=-1)  # the one operation outside every scope

    @jax.jit
    def prefill_chunk(x, w):
        with jax.named_scope("mst.attn.qkv"):
            q = x @ w[0]
        with jax.named_scope("mst.norm"):
            return q / (1.0 + jnp.abs(q).mean(axis=-1, keepdims=True))

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.full((LAYERS, 1024, 1024), 0.001, jnp.bfloat16)
    block(x, w).block_until_ready()
    prefill_chunk(x, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = out / "_profile"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    for i in range(N_BLOCK):
        block(x, w).block_until_ready()
        if i < N_CHUNK:
            prefill_chunk(x, w).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(str(tmp / "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(src, out / "scopes_tpu.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    d = jax.devices()[0]
    (out / "scopes_tpu.expected.json").write_text(json.dumps({
        "platform": d.platform, "kind": d.device_kind,
        "executions": {"jit_block": N_BLOCK, "jit_prefill_chunk": N_CHUNK},
        "layers": LAYERS,
        "scopes": {
            "jit_block": ["mst.attn.core", "mst.moe.experts.matmul", "unscoped"],
            "jit_prefill_chunk": ["mst.attn.qkv", "mst.norm", "unscoped"],
        },
        "dropped_by_the_compiler": "mst.head",
        "matmul_flops": 2 * 1024 ** 3,
    }))
    print("recorded", (out / "scopes_tpu.xplane.pb").stat().st_size, "bytes")


if __name__ == "__main__":
    main(Path(sys.argv[1]))
