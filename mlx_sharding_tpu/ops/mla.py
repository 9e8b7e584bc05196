"""Multi-head latent attention's projection math, the one implementation
every family with such layers calls (``models/deepseek_v2.py``,
``models/kimi_linear.py``).

Queries (optionally LoRA-factored) are ``heads x (nope + rope)`` wide; keys
and values decompress from a shared low-rank latent (``kv_a_proj`` to ``rank``
and one shared ``rope``-wide head; ``kv_b_proj`` to per-head nope-K and V). A
family differs in the head count, in whether the query is factored, and in
the ROTARY embedding of ``q_pe`` and the shared ``k_pe``: ``(inv_freq,
scale)`` — plain or YaRN frequencies, interleaved pairs (DeepSeek-V2) — or
``None`` (Kimi-Linear's ``mla_use_nope``: position comes from the
linear-attention layers).

Compressed mode caches the latent, not per-head K/V: a position's row is
``[latent, k_pe]``, ``rank + rope`` values whatever the head count, one shared
head. ``kv_b_proj`` is absorbed on both sides — its nope-K half into the
query (:func:`mla_qkv`), its V half into the attention's output over the
latent (:func:`absorb_values`) — so the attention is MQA over the row with
``values_from_k = rank`` and the numbers are the decompressed form's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.ops.norms import rms_norm
from mlx_sharding_tpu.ops.rope import apply_rope_interleaved


def mla_qkv(linear, p, r, offset, *, nope: int, rope_d: int, v_d: int,
            rank: int, eps: float, rotary, compressed: bool, q_lora: bool):
    """``r (B, T, hidden)`` the normed input, ``p`` the layer's ``q_proj``
    (or ``q_a_proj``, ``q_a_norm``, ``q_b_proj`` with ``q_lora``),
    ``kv_a_proj``, ``kv_a_norm``, ``kv_b_proj``; ``linear(x, w)`` the model's
    projection. ``rotary``: ``(inv_freq, scale)`` or None. Compressed:
    ``(q_cat (B,T,H,rank+rope), k_new (B,T,1,rank+rope), None, w_bv
    (rank,H,v_d))``. Decompressed: ``(q_full, k, v, None)`` with per-head K/V."""
    b, t, _ = r.shape
    if q_lora:
        q = linear(rms_norm(linear(r, p["q_a_proj"]), p["q_a_norm"], eps), p["q_b_proj"])
    else:
        q = linear(r, p["q_proj"])
    q = q.reshape(b, t, -1, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]

    ckv = linear(r, p["kv_a_proj"])  # (B, T, rank + rope_d)
    compressed_kv, k_pe = ckv[..., :rank], ckv[..., rank:]
    latent = rms_norm(compressed_kv, p["kv_a_norm"], eps)
    k_pe = k_pe[:, :, None, :]  # single shared rope head
    if rotary is not None:
        inv_freq, rope_scale = rotary
        q_pe = apply_rope_interleaved(q_pe, inv_freq, offset, rope_scale)
        k_pe = apply_rope_interleaved(k_pe, inv_freq, offset, rope_scale)

    if compressed:
        w_b = p["kv_b_proj"].reshape(rank, -1, nope + v_d)
        w_bk, w_bv = w_b[..., :nope], w_b[..., nope:]
        q_lat = jnp.einsum(
            "bthn,rhn->bthr", q_nope, w_bk, preferred_element_type=jnp.float32
        ).astype(r.dtype)
        q_cat = jnp.concatenate([q_lat, q_pe], axis=-1)  # (B,T,H,rank+rope)
        k_new = jnp.concatenate([latent[:, :, None, :], k_pe], axis=-1)
        return q_cat, k_new, None, w_bv
    kv = linear(latent, p["kv_b_proj"]).reshape(b, t, -1, nope + v_d)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:-1], rope_d))],
        axis=-1,
    )
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    return q_full, k, v, None


@jax.named_scope("mst.attn.core")
def absorb_values(out_lat, w_bv, dtype):
    """Compressed mode's value side: ``kv_b_proj``'s V half applied to the
    attention output over the latent."""
    return jnp.einsum(
        "bthr,rhv->bthv", out_lat, w_bv, preferred_element_type=jnp.float32
    ).astype(dtype)
