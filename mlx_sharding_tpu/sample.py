"""Token sampling — fully on-device, jit-fused into the decode step.

Semantic parity with the reference's sampler closure
(ref: shard/utils.py:126-139 — logit bias, argmax at temperature 0, top-p
else categorical) and its repetition penalty over a sliding token window
(ref: shard/utils.py:166-177). The TPU-native difference: everything here is
traced into the same XLA program as the model forward, with temperature /
top-p / penalty as *dynamic* scalars, so changing sampler settings never
recompiles and the only per-token host transfer is the sampled token id.
What a step pays for follows those scalars through ``lax.cond`` on the
device, never through a host-chosen program: a greedy step takes an argmax,
a sampled one adds the Gumbel draw, and only ``top_p < 1`` sorts the
vocabulary (``sample_token`` per request, ``sample_token_batched`` per batch
of active rows).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class SamplerParams(NamedTuple):
    """Dynamic sampler state — one pytree so it jits as leaves."""

    temperature: jax.Array  # scalar f32; 0 → greedy
    top_p: jax.Array  # scalar f32; 1 → full distribution
    repetition_penalty: jax.Array  # scalar f32; 1 → off
    bias_indices: jax.Array  # (K,) int32, pad with 0
    bias_values: jax.Array  # (K,) f32, pad with 0 (no-op)


def sampler_params_host(
    temperature: float = 0.0,
    top_p: float = 1.0,
    repetition_penalty: Optional[float] = None,
    logit_bias: Optional[dict[int, float]] = None,
    *,
    slots: int,
) -> SamplerParams:
    """One request's sampler row as ``numpy`` arrays of fixed dtypes, its
    bias buffers ``slots`` wide: nothing is dispatched, so a caller that
    hands the row to a jitted program itself (the scheduler's slot claim,
    whose row is as wide as the batch's) pays for no eager operation.
    Every entry of ``logit_bias`` is applied — the reference applies all of
    them too (shard/utils.py:128-131)."""
    n = len(logit_bias) if logit_bias else 0
    if n > slots:
        raise ValueError(f"logit_bias with {n} entries exceeds {slots} slots")
    bias_idx = np.zeros((slots,), np.int32)
    bias_val = np.zeros((slots,), np.float32)
    if logit_bias:
        bias_idx[:n] = [int(k) for k in logit_bias]
        bias_val[:n] = [float(v) for v in logit_bias.values()]
    return SamplerParams(
        temperature=np.asarray(temperature, np.float32),
        top_p=np.asarray(top_p, np.float32),
        repetition_penalty=np.asarray(
            1.0 if repetition_penalty is None else repetition_penalty, np.float32
        ),
        bias_indices=bias_idx,
        bias_values=bias_val,
    )


def make_sampler_params(
    temperature: float = 0.0,
    top_p: float = 1.0,
    repetition_penalty: Optional[float] = None,
    logit_bias: Optional[dict[int, float]] = None,
    min_bias_slots: int = 16,
) -> SamplerParams:
    # Buffer sized to the request (rounded to a power of two so distinct bias
    # counts reuse a handful of compiled programs).
    n = len(logit_bias) if logit_bias else 0
    slots = max(min_bias_slots, 1 << (n - 1).bit_length() if n else 0)
    return jax.tree.map(jnp.asarray, sampler_params_host(
        temperature, top_p, repetition_penalty, logit_bias, slots=slots
    ))


def seed_key_row(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)``'s two words, made on the host: the
    default (threefry) key of an integer seed is its high and low 32 bits,
    and without ``jax_enable_x64`` the seed is cut to 32 bits first, so the
    high word is 0. Any integer ``PRNGKey`` takes (64 signed bits) gives the
    same row bit for bit; a larger one raises ``OverflowError`` as it does."""
    s = int(np.int64(seed))
    hi = (s >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([hi, s & 0xFFFFFFFF], np.uint32)


def apply_logit_bias(logits: jax.Array, indices: jax.Array, values: jax.Array):
    """Scatter-add biases. Padding entries have value 0 → no-op whatever the
    index (matches ref logit_bias semantics, shard/utils.py:128-131)."""
    return logits.at[..., indices].add(values)


def apply_repetition_penalty(
    logits: jax.Array, recent_tokens: jax.Array, penalty: jax.Array
) -> jax.Array:
    """Penalize tokens in ``recent_tokens`` (B, W), -1 = empty slot.

    Positive scores are divided by ``penalty``, negative multiplied — the
    standard CTRL-style rule the reference applies over its sliding window
    (shard/utils.py:166-177, via mlx_lm.apply_repetition_penalty)."""

    def one(logits_row, tokens_row):
        valid = tokens_row >= 0
        gather_idx = jnp.where(valid, tokens_row, 0)
        scores = logits_row[gather_idx]
        penalized = jnp.where(scores > 0, scores / penalty, scores * penalty)
        # Route empty slots out of bounds and drop them, so a padding slot can
        # never clobber a real token's penalized value (duplicate-index
        # scatter is last-write-wins).
        scatter_idx = jnp.where(valid, tokens_row, logits_row.shape[0])
        return logits_row.at[scatter_idx].set(penalized, mode="drop")

    return jax.vmap(one)(logits, recent_tokens)


def top_p_filter(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """Mask logits outside the top-p nucleus (ref: mlx_lm top_p_sampling used
    at shard/utils.py:136). Keeps the smallest prefix of the sorted
    distribution whose mass reaches ``top_p``; top_p >= 1 keeps everything.

    The full-vocab sort costs 1.2 ms a million logits on a v5e (a row a
    little over a power of two is sorted as the next one), so the filter
    sits behind a ``lax.cond``. Called on one row or one scalar ``top_p``
    (``sample_token``, the solo path) that is a real conditional and a
    request at the top_p=1 default never sorts. Under ``jax.vmap``
    (``nucleus_logits_batched``: the speculative programs, which need the
    filtered distribution of every row) the cond lowers to a select and
    every row sorts whatever its ``top_p``. The served decode step
    (``sample_token_batched``) calls the vmapped filter only inside a
    batch-level conditional of its own."""

    def nucleus(lo):
        sorted_logits = jnp.sort(lo, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p  # kept iff mass before it < top_p
        min_kept = jnp.min(
            jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
        )
        return jnp.where(lo >= min_kept, lo, -jnp.inf)

    return jax.lax.cond(top_p < 1.0, nucleus, lambda lo: lo, logits)


def transform_logits(
    logits: jax.Array,
    recent_tokens: Optional[jax.Array],
    params: SamplerParams,
) -> jax.Array:
    """bias → repetition penalty: the request-transformed logits every
    downstream consumer (greedy argmax, logprob reporting, nucleus
    sampling, speculative verification) derives from."""
    logits = apply_logit_bias(
        logits.astype(jnp.float32), params.bias_indices, params.bias_values
    )
    if recent_tokens is not None:
        logits = apply_repetition_penalty(
            logits, recent_tokens, params.repetition_penalty
        )
    return logits


def nucleus_logits(lo: jax.Array, params: SamplerParams) -> jax.Array:
    """Temperature then top-p on transformed logits — the log-domain
    (unnormalized) final sampling distribution of the sampled branch.
    Temperature first, THEN the nucleus cut: the kept set must be computed
    on the tempered distribution (matches mlx_lm top_p_sampling semantics
    used at ref shard/utils.py:136). Speculative rejection sampling defines
    both its p and q through this same function, which is what keeps its
    acceptance ratio aligned with what sample_token actually samples."""
    safe_temp = jnp.maximum(params.temperature, 1e-6)
    return top_p_filter(lo / safe_temp, params.top_p)


@jax.named_scope("mst.sample")
def sample_token(
    key: jax.Array,
    logits: jax.Array,  # (B, V) f32
    params: SamplerParams,
    recent_tokens: Optional[jax.Array] = None,  # (B, W) int32, -1 padded
) -> tuple[jax.Array, jax.Array]:
    """Returns (token (B,), logprobs (B, V)). Temperature / top-p are
    dynamic scalars, so one compiled program covers every request's sampler
    settings; the sampled branch (gumbel draw + nucleus sort) sits behind a
    ``lax.cond`` so greedy requests — the serving default — skip it."""
    logits = transform_logits(logits, recent_tokens, params)

    logprobs = jax.nn.log_softmax(logits, axis=-1)

    def sampled_fn(lo):
        filtered = nucleus_logits(lo, params)
        return jax.random.categorical(key, filtered, axis=-1).astype(jnp.int32)

    token = jax.lax.cond(
        params.temperature > 0,
        sampled_fn,
        lambda lo: jnp.argmax(lo, axis=-1).astype(jnp.int32),
        logits,
    )
    return token, logprobs


def stack_sampler_params(params_list: list[SamplerParams]) -> SamplerParams:
    """Per-request sampler params → one batched pytree with leading (B,)
    (bias buffers padded to a common width). Used by the continuous-batching
    scheduler, where every microbatch slot runs its own request with its own
    temperature/top-p/penalty/bias."""
    slots = max(p.bias_indices.shape[0] for p in params_list)

    def pad(p: SamplerParams) -> SamplerParams:
        n = p.bias_indices.shape[0]
        if n == slots:
            return p
        return p._replace(
            bias_indices=jnp.pad(p.bias_indices, (0, slots - n)),
            bias_values=jnp.pad(p.bias_values, (0, slots - n)),
        )

    return jax.tree.map(lambda *xs: jnp.stack(xs), *[pad(p) for p in params_list])


def transform_logits_batched(
    logits: jax.Array,  # (B, V)
    recent_tokens: jax.Array,  # (B, W) int32, -1 padded
    params: SamplerParams,  # every leaf with leading (B,)
) -> jax.Array:
    """Per-row bias → repetition penalty — the batched transform_logits
    (one continuous-batching slot per row)."""
    logits = logits.astype(jnp.float32)
    logits = jax.vmap(lambda l, i, v: l.at[i].add(v))(
        logits, params.bias_indices, params.bias_values
    )
    return jax.vmap(
        lambda l, r, p: apply_repetition_penalty(l[None], r[None], p)[0]
    )(logits, recent_tokens, params.repetition_penalty)


def nucleus_logits_batched(lo: jax.Array, params: SamplerParams) -> jax.Array:
    """Per-row temperature + top-p on transformed logits — the batched
    nucleus_logits; with transform_logits_batched it defines each slot's
    full sampling distribution (the p and q of batched speculative
    rejection sampling)."""
    safe_temp = jnp.maximum(params.temperature, 1e-6)[:, None]
    return jax.vmap(top_p_filter)(lo / safe_temp, params.top_p)


@jax.named_scope("mst.sample")
def sample_token_batched(
    keys: jax.Array,  # (B, 2) uint32 — one PRNG key per row
    logits: jax.Array,  # (B, V) f32
    params: SamplerParams,  # every leaf with leading (B,)
    recent_tokens: jax.Array,  # (B, W) int32, -1 padded
    active: jax.Array,  # (B,) bool — rows whose token somebody reads
) -> tuple[jax.Array, jax.Array]:
    """Per-row sampling with per-row params and per-row PRNG keys — each
    continuous-batching slot behaves exactly like a solo request with that
    seed, so draining a slot and re-running the request serially reproduces
    its tokens.

    What a step runs is decided per BATCH, on the device, by two real
    ``lax.cond``s outside any vmap: every step transforms the logits and
    takes the argmax; only if an ACTIVE row has ``temperature > 0`` does it
    also scale by temperature and draw (B x V Gumbel numbers); only if such
    a row also has ``top_p < 1`` does it sort the vocabulary (every row's:
    the filter inside is vmapped). An all-greedy batch — the server's
    default — pays neither. ``active`` masks both predicates because a
    freed slot keeps the row of the request that left it until the next
    claim. An inactive row's token is greedy whenever no active row draws;
    nobody reads it. The branches hand back (B,) tokens, so nothing of the
    vocabulary's size crosses a conditional's edge."""
    logits = transform_logits_batched(logits, recent_tokens, params)

    logprobs = jax.nn.log_softmax(logits, axis=-1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled_row = params.temperature > 0
    draws = active & sampled_row
    cuts = draws & (params.top_p < 1.0)

    def draw(lo):
        return jax.vmap(lambda k, l: jax.random.categorical(k, l))(keys, lo)

    def drawn():
        tempered = logits / jnp.maximum(params.temperature, 1e-6)[:, None]
        sampled = jax.lax.cond(
            jnp.any(cuts),
            lambda lo: draw(jax.vmap(top_p_filter)(lo, params.top_p)),
            draw,
            tempered,
        )
        return jnp.where(sampled_row, sampled.astype(jnp.int32), greedy)

    token = jax.lax.cond(jnp.any(draws), drawn, lambda: greedy)
    return token, logprobs


@jax.named_scope("mst.sample")
def update_recent_tokens(recent: jax.Array, token: jax.Array) -> jax.Array:
    """Shift the (B, W) window left and append the new token — the device-side
    version of the reference's ``repetition_context`` deque trim
    (shard/utils.py:171-177)."""
    return jnp.concatenate([recent[:, 1:], token[:, None]], axis=1)


def init_recent_tokens(batch: int, window: int, prompt=None) -> jax.Array:
    """Start the window from the prompt tail so the penalty applies to prompt
    content immediately (ref seeds repetition_context from the prompt,
    shard/utils.py:152-155). ``prompt``: optional (B, T) array-like."""
    recent = jnp.full((batch, window), -1, jnp.int32)
    if prompt is not None:
        tail = np.asarray(prompt, np.int32)[:, -window:]
        recent = recent.at[:, window - tail.shape[1] :].set(jnp.asarray(tail))
    return recent
