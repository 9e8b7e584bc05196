"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference has no long-context story at all: prefill materializes a dense
T×T mask and pushes the whole prompt through every stage in one call
(SURVEY §5 "Long-context"). Here long sequences shard over ``sp``: each
device keeps its Q block resident and the K/V blocks rotate around the ring
via ``lax.ppermute`` (one ICI hop per step) while a streaming flash-style
softmax (running max / normalizer / output, all fp32) accumulates the exact
attention result. Received blocks are processed in ``block_k`` sub-tiles, so
the live score tensor is O(T/S x block_k) — no (T/S)² (let alone T×T)
score matrix ever exists — and communication overlaps the block matmuls.

Causality is enforced with *global* positions: query block ``s`` holds
positions ``s*T_local + i``; at ring step ``j`` it sees K/V block
``(s - j) mod S``. Blocks strictly in the future contribute nothing and
their masked scores vanish in the streaming update.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mlx_sharding_tpu.parallel.mesh import AXIS_SP


def _block_update(scores, v_blk, o, m, l):
    """One streaming-softmax step. scores (B,Hkv,G,T,Tk) fp32 (may contain
    -inf), v_blk (B,Tk,Hkv,Dv). Returns updated (o, m, l)."""
    m_new = jnp.maximum(m, scores.max(axis=-1))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])  # -inf rows -> 0
    corr = jnp.exp(m - m_safe)
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bhgtk,bkhd->bhgtd", p, v_blk.astype(jnp.float32))
    o = o * corr[..., None] + pv
    return o, m_new, l


def ring_attention_local(
    q, k, v, scale: float, axis_name: str = AXIS_SP, block_k: int = 512,
    logit_softcap=None, sliding_window=None, values_from_k=None,
):
    """shard_map-level kernel: q/k/v are this device's (B, T_local, H, D)
    blocks of a sequence sharded over ``axis_name``. Causal, GQA-aware.
    Returns (B, T_local, Hq, Dv).

    Within each ring step the received K/V block is processed in ``block_k``
    sub-tiles through the same streaming-softmax update, so the live score
    tensor is (B, Hkv, G, T_local, block_k) — per-device activation memory
    stays O(T_local * block_k), never O(T_local^2).

    ``logit_softcap`` applies Gemma-2-style cap*tanh(s/cap) to the scores
    (before masking — tanh of a masked -inf would be NaN); ``sliding_window``
    (may be a traced per-layer scalar) restricts each query to the last W
    positions. Ring steps whose whole K/V block is irrelevant — strictly in
    the causal future, or entirely behind every query's window — skip their
    block matmuls via lax.cond (the rotation still runs): the causal skip
    alone halves the ring's compute, and a sliding window prunes most of the
    rest for long sequences.

    ``values_from_k`` (MLA's latent-as-values): attend values =
    keys[..., :n]; ``v`` is ignored and only the key blocks rotate around
    the ring — compressed MLA pays ~half the ICI bytes it would rotating a
    redundant value copy."""
    import math

    b, t, hq, dk = q.shape
    hkv = k.shape[2]
    groups = hq // hkv
    size = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)

    qg = q.reshape(b, t, hkv, groups, dk)
    q_pos = idx * t + jnp.arange(t)  # global positions of local queries

    bk = math.gcd(t, block_k)  # largest aligned sub-tile <= block_k
    nb = t // bk

    dv = values_from_k if values_from_k is not None else v.shape[-1]
    o = jnp.zeros((b, hkv, groups, t, dv), jnp.float32)
    m = jnp.full((b, hkv, groups, t), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, hkv, groups, t), jnp.float32)

    def step(carry, j):
        if values_from_k is None:
            o, m, l, k_blk, v_blk = carry
        else:
            o, m, l, k_blk = carry
            v_blk = k_blk[..., :values_from_k]
        blk = (idx - j) % size

        # (B, T, H, D) -> (nb, B, bk, H, D) sub-tiles for the inner scan
        k_sub = k_blk.reshape(b, nb, bk, hkv, -1).transpose(1, 0, 2, 3, 4)
        v_sub = v_blk.reshape(b, nb, bk, hkv, -1).transpose(1, 0, 2, 3, 4)

        def sub(carry2, xs):
            o, m, l = carry2
            ks, vs, si = xs
            k_pos = blk * t + si * bk + jnp.arange(bk)
            scores = jnp.einsum(
                "bthgd,bkhd->bhgtk", qg, ks, preferred_element_type=jnp.float32
            ) * scale
            if logit_softcap is not None:  # same gate as ops.attention
                scores = logit_softcap * jnp.tanh(scores / logit_softcap)
            allowed = k_pos[None, :] <= q_pos[:, None]  # (T, bk) global causal
            if sliding_window is not None:
                allowed &= k_pos[None, :] > q_pos[:, None] - sliding_window
            scores = jnp.where(allowed[None, None, None], scores, -jnp.inf)
            return _block_update(scores, vs, o, m, l), None

        def compute(oml):
            out, _ = jax.lax.scan(sub, oml, (k_sub, v_sub, jnp.arange(nb)))
            return out

        # whole-block relevance: its oldest position vs the newest query
        # (causal future) and its newest position vs the oldest query's
        # window edge — a fully-masked block would contribute exactly
        # nothing through the streaming update, so skipping is lossless
        in_future = blk * t > idx * t + (t - 1)
        relevant = ~in_future
        if sliding_window is not None:
            behind = (blk * t + t - 1) < (idx * t - sliding_window + 1)
            relevant &= ~behind
        o, m, l = jax.lax.cond(relevant, compute, lambda oml: oml, (o, m, l))
        k_next = jax.lax.ppermute(
            k_blk, axis_name, [(i, (i + 1) % size) for i in range(size)]
        )
        if values_from_k is not None:
            return (o, m, l, k_next), None
        v_next = jax.lax.ppermute(
            v_blk, axis_name, [(i, (i + 1) % size) for i in range(size)]
        )
        return (o, m, l, k_next, v_next), None

    init = (o, m, l, k) if values_from_k is not None else (o, m, l, k, v)
    outs, _ = jax.lax.scan(step, init, jnp.arange(size))
    o, m, l = outs[0], outs[1], outs[2]
    o = o / jnp.maximum(l[..., None], 1e-30)
    # (B, Hkv, G, T, Dv) -> (B, T, Hq, Dv)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, -1).astype(q.dtype)


def ring_attention(q, k, v, scale: float, mesh: Mesh, axis_name: str = AXIS_SP):
    """Driver-level entry: q/k/v (B, T, H, D) get sharded over ``axis_name``
    on their sequence dim and attended exactly. T must divide by the axis
    size."""
    spec = P(None, axis_name)
    f = jax.shard_map(
        lambda q, k, v: ring_attention_local(q, k, v, scale, axis_name),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    sharding = NamedSharding(mesh, spec)
    return f(
        jax.device_put(q, sharding),
        jax.device_put(k, sharding),
        jax.device_put(v, sharding),
    )
