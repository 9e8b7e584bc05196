"""Expert parallelism: experts sharded over the ``ep`` mesh axis.

The reference keeps MoE experts fused inside the owning pipeline stage
(SURVEY §2.3 "EP: NO — fused and replicated within the owning stage"), and
that remains this framework's default (ops/moe.py). This module is the
scale-out path the reference never had: the expert stacks (E, …) shard over
``ep``, every device computes only its resident experts' contribution for
ALL tokens (masked accumulation, static shapes), and one ``psum`` over
``ep`` combines — routing stays replicated so there is no all-to-all, just
the single reduction riding ICI. Token counts per expert never need to be
known at compile time, so there is no capacity factor and no dropping.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mlx_sharding_tpu.parallel.mesh import AXIS_EP


def expert_parallel_apply(
    x: jax.Array,  # (N, H) tokens
    weights: jax.Array,  # (N, K) routing weights
    idx: jax.Array,  # (N, K) expert ids (global)
    w_gate: jax.Array,  # (E, H, I)
    w_up: jax.Array,  # (E, H, I)
    w_down: jax.Array,  # (E, I, H)
    mesh: Mesh,
    axis_name: str = AXIS_EP,
) -> jax.Array:
    """SwiGLU expert application with experts sharded over ``axis_name``.
    Exactly matches ops.moe.apply_experts run on one device."""
    size = mesh.shape[axis_name]
    num_experts = w_gate.shape[0]
    if num_experts % size:
        raise ValueError(f"{num_experts} experts not divisible over ep={size}")

    def local(x, weights, idx, w_gate, w_up, w_down):
        # local expert block e_local corresponds to global id base + e_local
        base = jax.lax.axis_index(axis_name) * (num_experts // size)

        def body(acc, xs):
            wg, wu, wd, e_local = xs
            coef = ((idx == base + e_local) * weights).sum(axis=-1)  # (N,)
            y = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
            return acc + coef[:, None].astype(y.dtype) * y, None

        acc0 = jnp.zeros_like(x)
        acc, _ = jax.lax.scan(
            body, acc0,
            (w_gate, w_up, w_down, jnp.arange(num_experts // size)),
        )
        return jax.lax.psum(acc, axis_name)

    expert_spec = P(axis_name)
    rep = P()
    f = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(rep, rep, rep, expert_spec, expert_spec, expert_spec),
        out_specs=rep,
        check_vma=False,
    )
    shard = NamedSharding(mesh, expert_spec)
    repl = NamedSharding(mesh, rep)
    return f(
        jax.device_put(x, repl),
        jax.device_put(weights, repl),
        jax.device_put(idx, repl),
        jax.device_put(w_gate, shard),
        jax.device_put(w_up, shard),
        jax.device_put(w_down, shard),
    )
