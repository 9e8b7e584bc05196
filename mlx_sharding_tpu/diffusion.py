"""Generation by diffusion over blocks (``models/sdar_moe.py``): what a decode
forward's logits become.

The sequence is blocks of ``L = block_length`` positions. A decode block
starts with its unknown positions MASKED (input id ``mask_token_id``; the
first block of a request starts with the ``P mod L`` prompt tokens that no
whole block held). One **denoise forward** over the block's ``L`` ids, at
positions ``off .. off + L - 1``, attends to the stored K/V of ``[0, off)``
and to itself, all ways; logits row ``i`` predicts position ``i`` ITSELF;
``x0_i`` is the sampled token with the mask id's logit at ``-inf`` and ``c_i``
its probability. ``n = L / denoising_steps`` masked positions take their
``x0`` a forward (:func:`unmask`): ``sequential`` the first ``n``,
``low_confidence_static`` the ``n`` of largest ``c``,
``low_confidence_dynamic`` every one with ``c > confidence_threshold`` or the
top ``n`` if fewer than ``n`` pass. The forward that leaves no position masked
FINISHES the block: its ids are the output. What is left is the **commit**:
the K/V of the final ids, which later blocks read. A forward writes its rows'
K/V either way: a denoise forward's are overwritten by the next forward of
the block, the commit's stay.

**The commit rides the next block's first denoise forward.** The published
loop runs the commit as a forward of its own, which reads every weight and the
slot's whole K/V to compute no token. Here a finished block waits beside the
next one (``done_ids``, ``pending``), and a **wide forward** carries two lanes
of ``L`` rows a slot at ``off .. off + 2L - 1``: lane 1 the finished block's
final ids (or, where the slot has none, its current block, denoising), lane 2
the next block's denoise rows where lane 1 is a commit. Lane 1 sees the keys
``[0, off + L)`` and lane 2 ``[0, off + 2L)``: every row computes what the
published loop's forward computes for it, and the next block reads the
committed rows — both lanes are written before either attends. ``off``
advances when a block's K/V is stored; a stream's last block is never stored
(nothing reads it). A **narrow forward** carries one lane, a block denoising.
A block program alternates the two (``scheduler._decode_block_prog``): under
a strategy that transfers by rank a block takes a wide forward (the commit
before it | its first denoise) and a narrow one (its second), two where the
loop takes three. A slot that holds a finished block at a narrow forward
stands still for it (a first block of one forward, a block that passed the
threshold whole): it falls in step with the others, so that a narrow forward
is narrow for every slot.

:func:`block_forward` is one forward's epilogue for every slot of a batch at
once, branch-free: slots are at different phases in one forward. Two
departures from the family's published loop, both noted in the benchmark's
configuration under ``assumed``: which positions are masked is a boolean
carried beside the ids (never inferred from an id, so a prompt that contains
the mask id is served right), and the mask id's logit is ``-inf`` before
sampling (the published loop can sample the mask id and then denoises that
position again: a block that never ends).

:func:`refuse` is the one start-up error of every feature that assumes one
token a step or re-enters a sequence at a position of its own choosing.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.generate import LOGPROB_TOPK
from mlx_sharding_tpu.sample import sample_token_batched

SINGLE_STREAM = "the single-stream generator (cli.generate, a server without --concurrent)"

#: flag -> why a model that generates by diffusion over blocks cannot take it
REFUSED = {
    "--draft": "a draft proposes and a verify accepts one token a position, left to right",
    "--prompt-cache": "a prefix hit starts a slot at a page border, inside the prompt's last whole block or not",
    "--prefix-store": "a store hit starts a slot at a page border, inside the prompt's last whole block or not",
    "--spill-bytes": "a spilled slot comes back with a last token, not with a block and its mask",
    "--overcommit": "a preempted slot is folded and resumed by tokens emitted, not by blocks committed",
    "--disagg": "the prefill-to-decode hand-off ends on a first token, and this family's prefill yields none",
    "--kv-share-map": "the decode block's rows are written through the pool's own layers",
    "--kv-compress-map": "the codec transports pages of whole committed tokens",
    "--num-stages": "the decode block's forward is the one-stage ragged body",
    "--tp": "the decode block's forward is the one-stage ragged body",
    "--ep": "the decode block's forward is the one-stage ragged body",
    "--paged-pool": "the decode block's forward attends over the page pool in place (give a pool, and leave --paged-attention at ragged)",
    SINGLE_STREAM: "it samples one token a step (serve the model through server.openai_api --concurrent N --paged-pool P)",
}


def block_of(model) -> Optional[int]:
    """``L`` for a model that generates by diffusion over blocks of ``L``
    positions, None for one that generates a token a step."""
    return getattr(model, "diffusion_block", None)


def refuse(model, flag: str) -> None:
    """Raise, naming the flag and the family, where ``model`` generates by
    diffusion over blocks; no-op otherwise (``cache.refuse_recurrent``'s
    shape, one table)."""
    if block_of(model) is None:
        return
    raise ValueError(
        f"{flag} cannot serve {type(model).__name__} ({model.config.model_type}): "
        f"it generates by diffusion over blocks of {block_of(model)}, and "
        f"{REFUSED[flag]}"
    )


def init_block(m: int, length: int) -> dict:
    """A batcher's per-slot block state: the block that denoises (its ids,
    which of them are masked, and the log-probability summary each position
    had at the forward that transferred it, read by a request that asked
    when the block is finished) and the finished block in front of it whose
    K/V no forward has stored yet (``pending``: there is one)."""
    return {
        "ids": jnp.zeros((m, length), jnp.int32),
        "masked": jnp.zeros((m, length), bool),
        "done_ids": jnp.zeros((m, length), jnp.int32),
        "pending": jnp.zeros((m,), bool),
        "lp_chosen": jnp.zeros((m, length), jnp.float32),
        "lp_top_v": jnp.zeros((m, length, LOGPROB_TOPK), jnp.float32),
        "lp_top_i": jnp.zeros((m, length, LOGPROB_TOPK), jnp.int32),
    }


def forward_input(blk, active, wide: bool):
    """``(tokens, live, second)`` of one forward. Wide: ``tokens (M, 2L)``,
    lane 1 the finished block where the slot holds one (``second``: lane 2,
    the block that denoises, is then computed too) and else the block that
    denoises; every active slot is ``live``. Narrow: ``tokens (M, L)`` the
    block that denoises, and a slot that holds a finished block is not
    ``live``: it waits for the wide forward that commits it."""
    pending = active & blk["pending"]
    if not wide:
        return blk["ids"], active & ~pending, jnp.zeros_like(pending)
    lane1 = jnp.where(pending[:, None], blk["done_ids"], blk["ids"])
    return jnp.concatenate([lane1, blk["ids"]], axis=1), active, pending


def first_block(prompt_tail, length: int, mask_id: int):
    """``(ids (L,), masked (L,))`` of a request's first decode block, numpy
    int32 both (a program's packed argument): the prompt tokens no whole
    block held, then masks."""
    n = len(prompt_tail)
    ids = np.full((length,), mask_id, np.int32)
    ids[:n] = prompt_tail
    return ids, (np.arange(length) >= n).astype(np.int32)


@jax.named_scope("mst.diffusion.unmask")
def unmask(masked, conf, *, strategy: str, n: int, tau: float):
    """Which masked positions take their sampled token this forward.
    ``masked (M, L)`` bool, ``conf (M, L)`` float32. Returns ``(transfer
    (M, L) bool, by_confidence (M,) bool)``: the second says the row's
    transfers passed the threshold (``low_confidence_dynamic`` with at least
    ``n`` above it), not a rank. Ties go to the lower position."""
    if strategy == "sequential":
        rank = jnp.cumsum(masked, axis=1) - 1
        return masked & (rank < n), jnp.zeros(masked.shape[:1], bool)
    c = jnp.where(masked, conf, -jnp.inf)
    # rank by confidence, descending: how many positions beat this one
    beats = (c[:, None, :] > c[:, :, None]) | (
        (c[:, None, :] == c[:, :, None])
        & (jnp.arange(c.shape[1])[None, None, :] < jnp.arange(c.shape[1])[None, :, None])
    )
    top = masked & (beats.sum(axis=2) < n)
    if strategy == "low_confidence_static":
        return top, jnp.zeros(masked.shape[:1], bool)
    high = masked & (conf > tau)
    enough = high.sum(axis=1) >= n
    return jnp.where(enough[:, None], high, top), enough


def block_forward(blk, logits, offset, live, second, recent, keys, sp,
                  rep_sizes, *, cfg, want_lp: bool):
    """One forward's epilogue for every slot. ``blk``: :func:`init_block`'s
    tree; ``logits (M, L, V)`` float32, of the lane that denoises; ``offset
    (M,)``; ``live`` and ``second``: :func:`forward_input`'s. A live slot
    denoised: sample every position, transfer by the strategy; where that
    leaves none masked the block is finished — its ids are its tokens, it
    waits for its commit and the next block starts all masked. Where
    ``second``, lane 1 stored the block finished before: ``offset += L``.
    Returns ``(out, blk, offset, recent, keys)``; ``out`` is what the host
    reads of the forward: ``done (M,)``, ``ids (M, L)`` (the block after the
    forward's transfers: a finished block's tokens), ``by_rank`` and
    ``by_confidence (M,)`` counts of positions transferred, and with
    ``want_lp`` the summaries of the block's positions."""
    m, length, vocab = logits.shape
    ids, masked = blk["ids"], blk["masked"]
    with jax.named_scope("mst.sample"):
        split = jax.vmap(lambda k: jax.random.split(k, length + 1))(keys)
        keys, subs = split[:, 0], split[:, 1:].reshape(m * length, -1)
        per_row = lambda x: jnp.repeat(x, length, axis=0)  # noqa: E731
        w = recent.shape[1]
        valid = jnp.arange(w)[None, :] >= (w - rep_sizes)[:, None]
        flat = logits.reshape(m * length, vocab).at[:, cfg.mask_token_id].set(-jnp.inf)
        sp_rows = jax.tree.map(per_row, sp)
        x0, logprobs = sample_token_batched(
            subs, flat, sp_rows, per_row(jnp.where(valid, recent, -1)),
            per_row(live),
        )
        # c: the sampled token's probability under the row's temperature
        # (top-p's renormalisation left out: it only raises every kept
        # token's probability by one factor a row)
        temp = jnp.where(sp_rows.temperature > 0, sp_rows.temperature, 1.0)
        chosen = jnp.take_along_axis(logprobs, x0[:, None], axis=-1)[:, 0]
        conf = jnp.exp(jnp.where(
            sp_rows.temperature > 0,
            chosen / temp - jax.nn.logsumexp(logprobs / temp[:, None], axis=-1),
            chosen,
        )).reshape(m, length)
        x0 = x0.reshape(m, length)
    transfer, by_conf = unmask(
        masked, conf, strategy=cfg.remasking_strategy,
        n=cfg.block_length // cfg.denoising_steps, tau=cfg.confidence_threshold,
    )
    with jax.named_scope("mst.diffusion.unmask"):
        transfer &= live[:, None]
        moved = transfer.sum(axis=1).astype(jnp.int32)
        ids = jnp.where(transfer, x0, ids)
        masked &= ~transfer
        done = live & ~masked.any(axis=1)
        out = {
            "done": done, "ids": ids,
            "by_rank": jnp.where(by_conf, 0, moved),
            "by_confidence": jnp.where(by_conf, moved, 0),
        }
        new = dict(blk)
        if want_lp:
            top_v, top_i = jax.lax.top_k(logprobs, LOGPROB_TOPK)
            for name, val in (
                ("lp_chosen", chosen.reshape(m, length)),
                ("lp_top_v", top_v.reshape(m, length, -1)),
                ("lp_top_i", top_i.reshape(m, length, -1)),
            ):
                sel = transfer.reshape(transfer.shape + (1,) * (val.ndim - 2))
                new[name] = jnp.where(sel, val, blk[name])
                out[name] = new[name]
        # a live slot's finished block, if it held one, was lane 1: stored
        new["done_ids"] = jnp.where(done[:, None], ids, blk["done_ids"])
        new["pending"] = jnp.where(live, done, blk["pending"])
        new["ids"] = jnp.where(done[:, None], cfg.mask_token_id, ids)
        new["masked"] = done[:, None] | masked
        offset = offset + jnp.where(second, length, 0).astype(offset.dtype)
        # a finished block's tokens enter the repetition window
        shifted = jnp.concatenate([recent[:, length:], ids[:, -w:]], axis=1)
        recent = jnp.where(done[:, None], shifted, recent)
    return out, new, offset, recent, keys
