"""Mixture-of-experts dispatch.

The reference keeps experts fused and stage-local — per-expert weights are
stacked into one ``switch_mlp`` tensor at load time
(ref: shard/server/model/deepseek_v2.py:101-112) and routing happens inside
the owning pipeline stage (SURVEY §2.3 "EP"). Same policy here, with two
TPU execution paths chosen by token count at trace time:

- **decode (few tokens)**: gather the top-k experts' weights per token and
  batch the tiny matmuls — HBM traffic is k/E of the expert weights, which
  is what decode is bound by;
- **prefill (many tokens)**: ``lax.scan`` over experts with masked
  accumulation — every matmul is a full-width MXU op with static shapes, no
  sorting, no capacity overflow. (A Pallas ragged-dispatch kernel is the
  planned upgrade for very large E.)

Routing is parameterized so Mixtral (softmax→topk→renorm) and DeepSeek-V2
(softmax scoring→greedy topk, optional renorm + scaling factor) share the
dispatch machinery.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GATHER_PATH_MAX_TOKENS = 16


@jax.named_scope("mst.moe.router")
def mixtral_routing(x, router_w, k: int):
    """HF Mixtral semantics: softmax over ALL expert logits, take top-k,
    renormalize the kept mass. Returns (weights (N,K) f32, idx (N,K))."""
    logits = (x @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / topv.sum(axis=-1, keepdims=True)
    return topv, topi


@jax.named_scope("mst.moe.router")
def deepseek_routing(
    x,
    router_w,
    k: int,
    *,
    norm_topk_prob: bool,
    routed_scaling_factor: float,
    topk_method: str = "greedy",
    n_group: int = 1,
    topk_group: int = 1,
):
    """DeepSeek-V2 gate: softmax scores in fp32, then 'greedy' top-k
    (V2-Lite) or 'group_limited_greedy' (V2/V2-Chat: keep only the
    topk_group expert groups with the highest per-group max score, then
    top-k within them), scaled by routed_scaling_factor."""
    logits = jnp.einsum(
        "nh,he->ne", x.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    scores = jax.nn.softmax(logits, axis=-1)
    if topk_method == "group_limited_greedy":
        n, e = scores.shape
        group_scores = scores.reshape(n, n_group, e // n_group).max(axis=-1)
        _, group_idx = jax.lax.top_k(group_scores, topk_group)  # (N, topk_group)
        group_mask = jnp.zeros_like(group_scores).at[
            jnp.arange(n)[:, None], group_idx
        ].set(1.0)
        score_mask = jnp.repeat(group_mask, e // n_group, axis=-1)
        scores = scores * score_mask
    elif topk_method != "greedy":
        raise ValueError(f"unknown topk_method {topk_method!r}")
    topv, topi = jax.lax.top_k(scores, k)
    if norm_topk_prob:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    return topv * routed_scaling_factor, topi


@jax.named_scope("mst.moe.experts")
def apply_experts(
    x, weights, idx, w_gate, w_up, w_down, ep_axis=None,
    group_size: int = 64, bits: int = 4,
):
    """SwiGLU expert application. x (N, H); w_* stacked (E, H, I)/(E, I, H)
    dense, or packed ``{q, scales, biases}`` triples with MLX-orientation
    leaves (E, out, in*bits/32) — 4-bit expert stacks stay resident in HBM
    and dequantize on the fly (ref quant predicate: shard/utils.py:54-65).
    weights/idx (N, K). Returns (N, H).

    ``ep_axis``: inside shard_map with the expert stacks sharded over that
    mesh axis, each device holds E/ep experts whose GLOBAL ids start at
    ``axis_index * E_local``; routing (weights/idx, global ids) is replicated,
    each device accumulates only its residents' contribution, and one psum
    combines — no all-to-all, no capacity factor, no token dropping."""
    from mlx_sharding_tpu.ops.quant import is_quantized

    n = x.shape[0]
    e_local = (w_gate["q"] if is_quantized(w_gate) else w_gate).shape[0]
    if ep_axis is not None:
        base = jax.lax.axis_index(ep_axis) * e_local
        acc = _apply_scan(
            x, weights, idx - base, w_gate, w_up, w_down, group_size, bits
        )
        return jax.lax.psum(acc, ep_axis)
    if n <= GATHER_PATH_MAX_TOKENS:
        # decode path: HBM traffic is k/E of the stacks — and 4x less again
        # when they are packed (gather the packed leaves, dequantize the
        # gathered slice in-register)
        if is_quantized(w_gate):
            return _apply_gather_packed(
                x, weights, idx, w_gate, w_up, w_down, group_size, bits
            )
        return _apply_gather(x, weights, idx, w_gate, w_up, w_down)
    return _apply_scan(x, weights, idx, w_gate, w_up, w_down, group_size, bits)


def _apply_gather(x, weights, idx, w_gate, w_up, w_down):
    wg = w_gate[idx]  # (N, K, H, I)
    wu = w_up[idx]
    wd = w_down[idx]  # (N, K, I, H)
    with jax.named_scope("mst.moe.experts.matmul"):
        g = jnp.einsum("nh,nkhi->nki", x, wg)
        u = jnp.einsum("nh,nkhi->nki", x, wu)
        y = jnp.einsum("nki,nkih->nkh", jax.nn.silu(g) * u, wd)
    return (y * weights[..., None].astype(y.dtype)).sum(axis=1).astype(x.dtype)


def _apply_gather_packed(x, weights, idx, w_gate, w_up, w_down, gs, bits):
    """Gather path over packed stacks: index the uint32/fp16 leaves by the
    top-k expert ids (reading k/E × 1/4 of the dense bytes), then dequantize
    just the gathered (N, K, out, in) slices. MLX orientation is (out, in),
    so the einsums contract the LAST dim."""
    from mlx_sharding_tpu.ops.quant import dequantize

    @jax.named_scope("mst.moe.experts.gather_dequant")
    def gathered(w):  # → (N, K, out, in) dense in x.dtype
        return dequantize(
            w["q"][idx], w["scales"][idx], w["biases"][idx], gs, bits, x.dtype
        )

    # gathered() opens its own, deeper scope inside this one
    with jax.named_scope("mst.moe.experts.matmul"):
        g = jnp.einsum("nh,nkih->nki", x, gathered(w_gate))
        u = jnp.einsum("nh,nkih->nki", x, gathered(w_up))
        y = jnp.einsum("nki,nkhi->nkh", jax.nn.silu(g) * u, gathered(w_down))
    return (y * weights[..., None].astype(y.dtype)).sum(axis=1).astype(x.dtype)


@jax.named_scope("mst.moe.experts.scan")
def _apply_scan(x, weights, idx, w_gate, w_up, w_down, gs=64, bits=4):
    from mlx_sharding_tpu.ops.quant import is_quantized, linear

    num_experts = (w_gate["q"] if is_quantized(w_gate) else w_gate).shape[0]

    def body(acc, xs):
        wg, wu, wd, e = xs
        coef = ((idx == e) * weights).sum(axis=-1)  # (N,) routing mass for e
        # linear() serves dense (in, out) slices and packed (out, in)
        # triples alike — the prefill path streams every expert's packed
        # bytes once, full-width MXU matmuls, no sorting
        y = linear(jax.nn.silu(linear(x, wg, gs, bits)) * linear(x, wu, gs, bits), wd, gs, bits)
        return acc + coef[:, None].astype(y.dtype) * y, None

    acc0 = jnp.zeros_like(x)
    acc, _ = jax.lax.scan(
        body, acc0, (w_gate, w_up, w_down, jnp.arange(num_experts))
    )
    return acc
