"""Causal attention over a fixed-capacity KV cache.

Replaces two reference pieces at once:
- the dense additive causal mask the reference materializes per prefill
  (ref: shard/server/model/llama.py:48-53, gemma2.py:48-51) — here masking is
  computed inline from broadcasted iotas and fused by XLA, never stored;
- mlx's scaled_dot_product_attention inside the borrowed decoder blocks
  (SURVEY §2.2).

Inputs are the *full-capacity* cache buffers; validity is derived from the
cache offset, so the same compiled program serves prefill (T=prompt) and
decode (T=1) without recompiling on sequence position. Scores accumulate in
float32 on the MXU; GQA is handled by grouping query heads over KV heads
rather than repeating K/V (no HBM duplication).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _flash_eligible(q, k, v, logit_softcap, sliding_window, sinks) -> bool:
    """Whether a call takes the Pallas flash kernel: on TPU, standard causal
    GQA (no softcap, window or sinks), a prefill chunk with T a multiple of
    128 over a cache of S a multiple of 128, head dims 64-aligned (Mosaic
    pads sub-128 lane tails, which admits DeepSeek MLA's dk=192 full-mode
    and dk=rank+rope / dv=rank compressed-mode shapes). Every other call,
    T=1 decode among them, takes the XLA path."""
    if logit_softcap is not None or sliding_window is not None or sinks is not None:
        return False
    b, t, hq, dk = q.shape
    s, dv = k.shape[1], v.shape[-1]
    return (
        jax.default_backend() == "tpu"
        and t >= 128
        and t % 128 == 0
        and s % 128 == 0
        and dk % 64 == 0
        and dv % 64 == 0
    )


def attend(
    q: jax.Array,  # (B, T, Hq, Dk)
    k: jax.Array,  # (B, S, Hkv, Dk) — full cache buffer
    v: jax.Array,  # (B, S, Hkv, Dv)
    offset: jax.Array,  # scalar: first new position (query i sits at offset+i)
    scale: float,
    *,
    logit_softcap: Optional[float] = None,  # gemma2.py attn softcapping
    sliding_window=None,  # int or traced scalar — gemma-2 alternating layers
    sinks: Optional[jax.Array] = None,  # reserved for attention-sink variants
    block: Optional[int] = None,  # static: block-causal over blocks of it
) -> jax.Array:
    """Returns (B, T, Hq, Dv). Keys at positions > query position (or outside
    the sliding window, or beyond the valid prefix) contribute nothing.
    With ``block`` (a model that generates by diffusion over blocks:
    ``models/sdar_moe.py``) a query sees every key up to the END of its own
    block of ``block`` positions, ``k_pos <= q_pos - q_pos % block + block -
    1``; blocks start at multiples of ``block``.

    Prefill chunks that qualify route to the Pallas flash kernel
    (ops/flash_attention.py); everything else takes the fused-XLA path below."""
    if _flash_eligible(q, k, v, logit_softcap, sliding_window, sinks):
        from mlx_sharding_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, offset, scale, block=block)
    return _causal_attention_xla(
        q, k, v, offset, scale, logit_softcap, sliding_window, block
    )


#: :func:`attend` under the scope every model's attention call has had; a
#: model that names its layer kinds (``mst.attn.window``, ``mst.attn.full``)
#: calls :func:`attend` under its own
causal_attention = jax.named_scope("mst.attn.core")(attend)


def block_end(q_pos, block: Optional[int]):
    """The last key position a query at ``q_pos`` sees: itself, or with
    ``block`` the last position of its block."""
    return q_pos if block is None else q_pos - q_pos % block + (block - 1)


def _causal_attention_xla(
    q, k, v, offset, scale, logit_softcap=None, sliding_window=None, block=None
):
    """The fused-XLA path: every backend's fallback and the reference the
    flash kernel is checked against on the chip (chip_smoke.py)."""
    b, t, hq, dk = q.shape
    s, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv

    qg = q.reshape(b, t, hkv, groups, dk)
    # (B, Hkv, G, T, S) — operands stay in their (bf16) dtype so the MXU runs
    # at native throughput; accumulation is fp32 via preferred_element_type.
    scores = jnp.einsum(
        "bthgd,bshd->bhgts", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    if logit_softcap is not None:
        scores = logit_softcap * jnp.tanh(scores / logit_softcap)

    q_pos = offset + jnp.arange(t)[:, None]  # (T, 1)
    k_pos = jnp.arange(s)[None, :]  # (1, S)
    allowed = k_pos <= block_end(q_pos, block)
    if sliding_window is not None:
        allowed &= k_pos > q_pos - sliding_window
    scores = jnp.where(allowed[None, None, None], scores, -jnp.inf)

    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgts,bshd->bthgd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, t, hq, -1).astype(q.dtype)
