"""Pallas flash-attention kernel for prefill.

The XLA path (ops/attention.py) materializes (B, H, T, S) scores in HBM for
prefill chunks; this kernel keeps everything in VMEM: each program owns one
(block_q × head) query tile, streams K/V blocks through the online-softmax
recurrence (running max / normalizer / accumulator in fp32), and writes one
output tile — no score matrix ever exists. Matmuls are MXU-shaped
(block_q × head_dim × block_k), masking is computed from broadcasted iotas
against the cache offset (same validity rule as ops/attention.py).

Scope: standard causal GQA attention (Llama/Mistral/Qwen2/Mixtral/DeepSeek).
Gemma-2's softcap + sliding-window layers stay on the XLA path. K/V arrive
as the full-capacity cache buffers; blocks entirely in the future of the
query tile are skipped without compute.

The q-tile is picked against a VMEM budget (``pick_block_q``): every query
tile of a head re-streams the whole (S, Dk+Dv) K/V row, so fewer and larger
tiles amortize it. Only prefill chunks reach this kernel
(``ops.attention._flash_eligible``); T=1 decode takes the XLA path. Whether
prefill keeps the kernel is for a prefill-heavy benchmark cell to decide
(ROADMAP S6); ``chip_smoke.py`` checks its numbers on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlx_sharding_tpu.ops.attention import block_end

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30

# Per-program VMEM budget for the adaptive q-tile picker (same sizing
# rationale as ops/quant_matmul.py's _VMEM_BUDGET_BYTES: leave headroom in
# the ~16MB VMEM for double-buffering and the compiler's own scratch).
_VMEM_BUDGET_BYTES = 6 * 1024 * 1024


def pick_block_q(t: int, s: int, dk: int, dv: int, itemsize: int,
                 block_k: int = DEFAULT_BLOCK_K) -> int:
    """Largest 128-multiple divisor of T (or T itself, when it fits) whose
    per-program working set stays inside the VMEM budget. The whole-S K/V
    row is a FIXED per-program cost re-paid by every query tile of a head;
    growing the tile divides that cost across more queries — the lever that
    took quant_matmul from 8x off its roofline to a 2.2x win."""
    fixed = s * (dk + dv) * itemsize  # K/V rows resident per program
    # per query row: q/o tile bytes, fp32 softmax state (m, l, acc), and the
    # kernel's two (block_q, block_k) fp32 intermediates (scores s, probs p)
    per_q = (
        (dk + dv) * itemsize + (dv + 2 + dk) * 4 + 2 * block_k * 4
    )
    limit = max((_VMEM_BUDGET_BYTES - fixed) // per_q, 128)
    if t <= limit:
        return t
    best = None
    d = 128
    while d <= limit:
        if t % d == 0:
            best = d
        d += 128
    return best if best is not None else min(t, DEFAULT_BLOCK_Q)


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, *, scale, block_q, block_k, s_len,
            block=None):
    q = q_ref[0, 0].astype(jnp.float32)  # (bq, dk)
    offset = off_ref[0]
    iq = pl.program_id(2)
    dv = v_ref.shape[-1]

    q_pos = offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0
    )  # (bq, 1)
    # the last key each query sees: itself, or the end of its block of
    # ``block`` positions (ops/attention.py: block-causal)
    q_end = block_end(q_pos, block)
    num_k_blocks = s_len // block_k

    def body(ik, carry):
        m, l, acc = carry

        def attend(carry):
            m, l, acc = carry
            kblk = k_ref[0, 0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
            vblk = v_ref[0, 0, pl.ds(ik * block_k, block_k), :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, kblk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (bq, bk)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1
            )
            s = jnp.where(k_pos <= q_end, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1, keepdims=True)
            acc = acc * corr + jax.lax.dot_general(
                p, vblk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l, acc

        # skip K blocks entirely beyond this query tile's last position
        last_q_pos = block_end(offset + (iq + 1) * block_q - 1, block)
        return jax.lax.cond(
            ik * block_k <= last_q_pos, attend, lambda c: c, (m, l, acc)
        )

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, dv), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, num_k_blocks, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_q", "block_k", "interpret", "block")
)
def flash_attention(
    q: jax.Array,  # (B, T, Hq, Dk)
    k: jax.Array,  # (B, S, Hkv, Dk) — full cache buffer
    v: jax.Array,  # (B, S, Hkv, Dv)
    offset: jax.Array,  # scalar int32
    scale: float,
    *,
    block_q: int | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    block: int | None = None,
) -> jax.Array:
    """Drop-in for ops.attention.causal_attention on the standard causal/GQA
    case (``block``: its block-causal form). T must divide block_q*n and S must divide block_k*n (the callers'
    chunked-prefill invariants guarantee this for multiples of 128).
    ``block_q=None`` → the adaptive VMEM-budget picker."""
    b, t, hq, dk = q.shape
    s, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    groups = hq // hkv
    block_k = min(block_k, s)
    if block_q is None:
        block_q = pick_block_q(t, s, dk, dv, q.dtype.itemsize, block_k)
    block_q = min(block_q, t)
    if t % block_q or s % block_k:
        raise ValueError(f"T={t} and S={s} must be multiples of the block sizes")

    qt = q.transpose(0, 2, 1, 3)  # (B, Hq, T, Dk)
    kt = k.transpose(0, 2, 1, 3)  # (B, Hkv, S, Dk)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, hq, t // block_q)
    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, block_q=block_q, block_k=block_k, s_len=s,
            block=block,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # offset
            pl.BlockSpec(
                (1, 1, block_q, dk), lambda bi, hi, qi: (bi, hi, qi, 0)
            ),
            pl.BlockSpec(
                (1, 1, s, dk), lambda bi, hi, qi, g=groups: (bi, hi // g, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, s, dv), lambda bi, hi, qi, g=groups: (bi, hi // g, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, dv), lambda bi, hi, qi: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, t, dv), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(jnp.asarray(offset, jnp.int32).reshape(1), qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # (B, T, Hq, Dv)
