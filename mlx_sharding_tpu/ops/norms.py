"""Normalization ops.

The reference gets RMSNorm from mlx ``nn.RMSNorm`` inside the borrowed
decoder blocks (SURVEY §2.2); here it is a plain fused-friendly jnp function.
Accumulation is in float32 regardless of activation dtype (XLA fuses the
casts into neighbouring ops).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("mst.norm")
def rms_norm(x, weight, eps: float = 1e-5, *, offset: float = 0.0):
    """RMSNorm. ``offset=1.0`` gives Gemma-style ``(1 + w) * x_hat``."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    x_hat = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    out = x_hat * (weight.astype(jnp.float32) + offset)
    return out.astype(dtype)
