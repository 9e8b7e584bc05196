"""MLX grouped-affine quantization compatibility.

The published ``*-4bit-mlx`` checkpoints the reference loads store each linear
as a triple ``{weight, scales, biases}`` (ref: shard/utils.py:54-65 applies
``nn.quantize`` when config.json carries a ``quantization`` dict, with the
``"{path}.scales" in weights`` predicate). Layout (mlx.core.quantize):

- ``weight``: uint32, shape (out, in * bits / 32); each uint32 packs
  ``32/bits`` consecutive input-dim elements, least-significant bits first.
- ``scales``/``biases``: (out, in / group_size); element value is
  ``q * scale + bias`` per group.

SURVEY §7 hard-part (a): this must be decoded bit-exactly or outputs diverge.
Round 1 dequantizes on load to bf16 (weights then live in HBM dense); a
Pallas fused dequant-matmul is the follow-up optimization path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.ops.dispatch import DispatchCounter


def dequantize(
    w_q: jax.Array | np.ndarray,
    scales: jax.Array | np.ndarray,
    biases: jax.Array | np.ndarray,
    group_size: int = 64,
    bits: int = 4,
    dtype=jnp.bfloat16,
) -> jax.Array:
    """(…, out, in*bits/32) packed uint32 → (…, out, in) dense. Leading
    dims carry stacked layers / expert stacks / gathered top-k experts."""
    w_q = jnp.asarray(w_q)
    if w_q.dtype != jnp.uint32:
        raise ValueError(f"packed weight must be uint32, got {w_q.dtype}")
    lead = w_q.shape[:-1]
    per_word = 32 // bits
    shifts = jnp.arange(per_word, dtype=jnp.uint32) * bits
    # (…, out, in/per_word, per_word) → (…, out, in)
    vals = (w_q[..., None] >> shifts) & ((1 << bits) - 1)
    vals = vals.reshape(*lead, -1).astype(jnp.float32)
    in_dim = vals.shape[-1]
    scales = jnp.asarray(scales, jnp.float32).reshape(*lead, in_dim // group_size, 1)
    biases = jnp.asarray(biases, jnp.float32).reshape(*lead, in_dim // group_size, 1)
    grouped = vals.reshape(*lead, in_dim // group_size, group_size)
    return (grouped * scales + biases).reshape(*lead, in_dim).astype(dtype)


def is_quantized(w) -> bool:
    """True for a packed ``{q, scales, biases}`` param (kept-packed load
    mode); False for a dense array."""
    return isinstance(w, dict) and "q" in w


def fuse_packed(parts):
    """Concatenate packed triples that share an IN dimension along OUT
    (axis -2 of every leaf in the MLX layout) into one packed param.

    Build-time only: the fused param serves N projections (QKV, gate+up)
    with a single kernel invocation, so the activation planes are read
    once instead of N times and decode issues one launch where it issued
    N. Per output row the fused call computes the exact same sub-dot
    sequence as the separate calls, so results are bit-identical."""
    if not all(is_quantized(p) for p in parts):
        raise ValueError("fuse_packed expects packed {q, scales, biases} triples")
    return {
        leaf: jnp.concatenate([p[leaf] for p in parts], axis=-2)
        for leaf in ("q", "scales", "biases")
    }


def linear(x: jax.Array, w, group_size: int = 64, bits: int = 4) -> jax.Array:
    """``x @ w`` that transparently serves packed params.

    Dense path: ``w`` is the usual (in, out) array. Packed path: ``w`` is an
    MLX-layout triple (``q`` (out, in*bits/32) uint32, ``scales``/``biases``
    (out, in/group_size)) and the product routes through the fused Pallas
    dequant-matmul on TPU (XLA dequant+matmul elsewhere) — the dense weight
    never exists in HBM."""
    if not is_quantized(w):
        return x @ w
    lead = x.shape[:-1]
    in_dim = x.shape[-1]
    x2 = x.reshape(-1, in_dim)
    out = _quant_matmul(x2, w["q"], w["scales"], w["biases"], group_size, bits)
    return out.reshape(*lead, -1)


def _pallas_ok(m, in_dim, out_dim, group_size, bits) -> bool:
    # single source of truth for the dispatch contract: the kernel's own
    # block defaults and min() clamping
    from mlx_sharding_tpu.ops.quant_matmul import (
        DEFAULT_BLOCK_M,
        pick_block_in,
    )

    per_word = 32 // bits
    block_in = min(pick_block_in(in_dim), in_dim)
    # OUT need not divide by its block: the kernel takes a ragged last tile
    return (
        jax.default_backend() == "tpu"
        and m % min(DEFAULT_BLOCK_M, m) == 0
        and in_dim % block_in == 0
        and block_in % group_size == 0
        and block_in % per_word == 0
    )


# Which path _quant_matmul chose, once per traced call (ops/dispatch.py).
# /metrics shows it as ``mst_quant_dispatch_total{path}``: "xla" above 0 on
# a chip says some packed projection is dequantized in HBM every step.
_DISPATCHED = DispatchCounter("matmul", "xla")
dispatch_counts = _DISPATCHED.counts
_count_dispatch = _DISPATCHED.count


def _quant_matmul(x2, q, scales, biases, group_size, bits):
    m, in_dim = x2.shape
    out_dim = q.shape[0]
    if _pallas_ok(m, in_dim, out_dim, group_size, bits):
        from mlx_sharding_tpu.ops.quant_matmul import quant_matmul_pallas

        _count_dispatch("matmul")
        return quant_matmul_pallas(
            x2, q, scales, biases, group_size=group_size, bits=bits
        )
    _count_dispatch("xla")
    return _quant_matmul_xla(x2, q, scales, biases, group_size, bits)


def _quant_matmul_xla(x2, q, scales, biases, group_size, bits):
    """The plain form: dequantize the whole weight to f32 in HBM, then one
    matmul. It is what runs off the chip (the CPU tests and rehearsals), and
    the reference ``chip_smoke.py`` checks every kernel against. On a TPU
    ``_pallas_ok`` refuses only a row count above 128 that is no multiple of
    it, which no program of the served path has:
    ``mst_quant_dispatch_total{path="xla"}`` says if one ever does."""
    # mst: allow(MST105): dense tile is transient inside this one matmul
    w = dequantize(q, scales, biases, group_size, bits, jnp.float32)
    return (x2 @ w.astype(x2.dtype).T).astype(x2.dtype)


def quantize_jax(w: jax.Array, group_size: int = 64, bits: int = 4):
    """Device-side mlx-layout packer: (…, out, in) → (q (…, out, in*bits/32)
    uint32, scales, biases (…, out, in/group_size) f32). Same math as
    :func:`quantize`, jittable: ``chip_smoke.py`` packs its check matrices
    on the chip instead of round-tripping them through the host."""
    w = jnp.asarray(w, jnp.float32)
    *lead, out_dim, in_dim = w.shape
    if in_dim % group_size:
        raise ValueError(f"in_dim {in_dim} not divisible by group_size {group_size}")
    grouped = w.reshape(*lead, out_dim, in_dim // group_size, group_size)
    w_max = grouped.max(axis=-1, keepdims=True)
    w_min = grouped.min(axis=-1, keepdims=True)
    n_levels = (1 << bits) - 1
    scale = jnp.maximum((w_max - w_min) / n_levels, 1e-8)
    q = jnp.clip(jnp.round((grouped - w_min) / scale), 0, n_levels).astype(jnp.uint32)
    q = q.reshape(*lead, out_dim, in_dim)
    per_word = 32 // bits
    # (…, out, in/per_word, per_word): LSB-first nibbles within each word
    q = q.reshape(*lead, out_dim, in_dim // per_word, per_word)
    shifts = jnp.arange(per_word, dtype=jnp.uint32) * bits
    packed = (q << shifts).sum(axis=-1, dtype=jnp.uint32)
    return (
        packed,
        scale[..., 0].astype(jnp.float32),
        w_min[..., 0].astype(jnp.float32),
    )


def quantize(w: np.ndarray, group_size: int = 64, bits: int = 4):
    """Inverse of :func:`dequantize` — mlx-compatible packer. Used by the
    shard-writer tool and round-trip tests; numpy (host, offline)."""
    w = np.asarray(w, np.float32)
    out_dim, in_dim = w.shape
    if in_dim % group_size:
        raise ValueError(f"in_dim {in_dim} not divisible by group_size {group_size}")
    grouped = w.reshape(out_dim, in_dim // group_size, group_size)
    w_max = grouped.max(axis=-1, keepdims=True)
    w_min = grouped.min(axis=-1, keepdims=True)
    n_levels = (1 << bits) - 1
    scale = np.maximum((w_max - w_min) / n_levels, 1e-8)
    q = np.clip(np.round((grouped - w_min) / scale), 0, n_levels).astype(np.uint32)
    q = q.reshape(out_dim, in_dim)
    per_word = 32 // bits
    packed = np.zeros((out_dim, in_dim // per_word), np.uint32)
    for j in range(per_word):
        packed |= q[:, j::per_word] << np.uint32(j * bits)
    return packed, scale[..., 0].astype(np.float16), w_min[..., 0].astype(np.float16)
