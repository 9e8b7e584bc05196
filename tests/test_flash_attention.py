"""Pallas flash-attention kernel vs the XLA reference path (interpret mode
on CPU; the same kernel compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mlx_sharding_tpu.ops import causal_attention
from mlx_sharding_tpu.ops.flash_attention import flash_attention


@pytest.mark.parametrize(
    "b,t,s,hq,hkv,dk,offset",
    [
        (1, 128, 256, 4, 4, 64, 0),  # plain prefill from empty cache
        (1, 128, 256, 8, 2, 64, 64),  # GQA + continuation chunk at offset
        (2, 256, 256, 4, 2, 32, 0),  # batch, full-capacity prompt
    ],
)
def test_flash_matches_xla(b, t, s, hq, hkv, dk, offset):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, hq, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, dk)), jnp.float32)
    scale = dk**-0.5
    ref = causal_attention(q, k, v, jnp.asarray(offset), scale)
    got = flash_attention(
        q, k, v, jnp.asarray(offset), scale, block_q=64, block_k=64, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_rejects_ragged_blocks():
    q = jnp.zeros((1, 100, 2, 16))
    k = jnp.zeros((1, 128, 2, 16))
    v = jnp.zeros((1, 128, 2, 16))
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, k, v, jnp.asarray(0), 1.0, block_q=64, block_k=64, interpret=True)


@pytest.mark.parametrize(
    "b,t,s,hq,hkv,dk,dv,offset",
    [
        # DeepSeek MLA full mode: dk = qk_nope+qk_rope = 192, dv = 128
        (1, 128, 256, 8, 8, 192, 128, 0),
        # DeepSeek MLA compressed mode: MQA over one latent head,
        # dk = rank+rope = 576, "values" are the rank slice (512)
        (1, 128, 128, 16, 1, 576, 512, 0),
        (1, 128, 256, 8, 8, 192, 128, 96),  # continuation at offset
    ],
)
def test_flash_mla_head_dims(b, t, s, hq, hkv, dk, dv, offset):
    """VERDICT r1 item 7: the kernel must serve DeepSeek's 64-aligned (not
    128-aligned) head dims."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(b, t, hq, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, dv)), jnp.float32)
    scale = dk**-0.5
    ref = causal_attention(q, k, v, jnp.asarray(offset), scale)
    got = flash_attention(
        q, k, v, jnp.asarray(offset), scale, block_q=64, block_k=64, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "s,hq,hkv,dk,offset",
    [(256, 8, 2, 64, 17), (256, 16, 1, 576, 40), (128, 4, 4, 192, 127)],
)
def test_flash_decode_step(s, hq, hkv, dk, offset):
    """One query row, the kernel's smallest tile, against a long cache,
    offset mid-buffer — positions beyond the offset must contribute
    nothing. Called directly: the dispatcher sends T=1 to the XLA path."""
    rng = np.random.default_rng(2)
    dv = 512 if dk == 576 else dk
    q = jnp.asarray(rng.normal(size=(1, 1, hq, dk)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, s, hkv, dk)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, s, hkv, dv)), jnp.float32)
    scale = dk**-0.5
    ref = causal_attention(q, k, v, jnp.asarray(offset), scale)
    got = flash_attention(
        q, k, v, jnp.asarray(offset), scale, block_k=64, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_eligibility_gates(monkeypatch):
    from mlx_sharding_tpu.ops.attention import _flash_eligible

    q192 = jnp.zeros((1, 128, 8, 192))
    k192 = jnp.zeros((1, 256, 8, 192))
    v128 = jnp.zeros((1, 256, 8, 128))
    qd = jnp.zeros((1, 1, 8, 192))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _flash_eligible(q192, k192, v128, None, None, None)
    # softcap/window stay on XLA
    assert not _flash_eligible(q192, k192, v128, 30.0, None, None)
    assert not _flash_eligible(q192, k192, v128, None, 4096, None)
    # T=1 decode is never eligible: it takes the XLA path
    assert not _flash_eligible(qd, k192, v128, None, None, None)
