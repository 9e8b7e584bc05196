"""Autoregressive generation driver.

Replaces the reference's decode loops (``generate_step`` ref: generate.py:52-88
and ``create_generate_step_with_grpc`` ref: shard/utils.py:111-188) with a
TPU-shaped design:

- **Two compiled shapes, ever.** Prefill runs in fixed-size chunks (right-
  padded final chunk) and decode at T=1, so nothing recompiles on prompt
  length. Pad-position K/V entries are always overwritten before any valid
  query can attend them (writes are contiguous and each step writes before it
  reads), so padding needs no masking beyond the causal rule.
- **Sampling is fused into the decode program** (temperature / top-p /
  repetition-penalty as dynamic scalars) so the only host transfer per token
  is the sampled id — the reference instead pays Python serde per stage per
  token (SURVEY §3.5).
- **One-token lookahead**: step N+1 is dispatched before step N's token is
  read on host, the same overlap the reference gets from ``mx.async_eval``
  (ref: shard/utils.py:180-186) — with JAX's async dispatch it falls out
  naturally.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, refuse_recurrent, reset
from mlx_sharding_tpu.sample import (
    SamplerParams,
    init_recent_tokens,
    make_sampler_params,
    sample_token,
    update_recent_tokens,
)

DEFAULT_PREFILL_CHUNK = 256
REPETITION_WINDOW = 20  # reference default repetition_context_size (openai_api.py)
DEFAULT_DECODE_BLOCK = 16
LOGPROB_TOPK = 10  # the server's documented logprobs cap (ref openai_api.py:262)


@dataclass
class TokenLogprobs:
    """Per-token logprob summary, computed ON DEVICE inside the decode block
    (``jax.lax.top_k``) and pulled to host once per block — replacing the
    per-token full-vocab host argsort the reference's server does
    (ref: shard/openai_api.py:388-392). ``top_indices``/``top_values`` are
    descending, length LOGPROB_TOPK; slice to the requested k."""

    chosen: float
    top_indices: np.ndarray
    top_values: np.ndarray


@jax.named_scope("mst.sample")
def block_lp_outputs(tok_flat, logprobs):
    """Per-step scan outputs for a decode block when logprobs are wanted:
    ``(tokens, chosen, top_values, top_indices)``. Single source of the
    positional convention every engine's block program emits —
    :func:`block_token_logprobs` is its reader."""
    chosen = jnp.take_along_axis(
        logprobs, tok_flat.reshape(-1, 1).astype(jnp.int32), axis=-1
    )[:, 0]
    top_v, top_i = jax.lax.top_k(logprobs, LOGPROB_TOPK)
    return chosen, top_v, top_i


def block_token_logprobs(outs, j, row=0) -> TokenLogprobs:
    """Read one (step j, batch row) TokenLogprobs from a pulled block-output
    tuple ``(tokens, chosen, top_values, top_indices)``."""
    return TokenLogprobs(
        float(outs[1][j, row]), outs[3][j, row], outs[2][j, row]
    )


def blocked_token_stream(dispatch, carry, remaining, block_size, want_logprobs,
                         tok_index=(0,), sink=None):
    """The blocked-decode host loop shared by every engine: one-BLOCK
    lookahead — block i+1 is dispatched (chained on block i's device-side
    carry, no host sync) before block i's tokens are pulled, so the host
    pull's round trip overlaps the next block's compute. Per token that
    leaves max(step_time, RTT/block_size) instead of RTT.

    ``dispatch(carry) -> (block_outputs, carry)`` launches one block;
    ``tok_index`` selects the yielded row from the (K, …) token stack.
    ``sink`` (optional) receives each pulled block's full (K, …) token
    array — including tokens past ``remaining`` that are never yielded —
    so a prompt cache can account for every KV row the blocks wrote."""
    n_blocks = -(-remaining // block_size)
    pending, carry = dispatch(carry)
    pending = [pending]
    emitted = 0
    for bi in range(n_blocks):
        if bi + 1 < n_blocks:
            nxt, carry = dispatch(carry)
            pending.append(nxt)
        outs = jax.device_get(pending.pop(0))
        toks = outs[0]
        if sink is not None:
            sink(toks)
        for j in range(toks.shape[0]):
            if emitted >= remaining:
                break
            lp = block_token_logprobs(outs, j) if want_logprobs else None
            yield int(toks[(j, *tok_index)]), lp
            emitted += 1


@dataclass
class StreamChunk:
    text: str = ""
    token: Optional[int] = None
    logprobs: Optional[np.ndarray] = None
    finish_reason: Optional[str] = None
    # set on the final chunk, matching the reference's instrumentation
    # (generate.py:97-122): prompt/gen tok/s and TTFT
    prompt_tokens: int = 0
    generation_tokens: int = 0
    prompt_tps: float = 0.0
    generation_tps: float = 0.0
    ttft: float = 0.0


class Generator:
    """Owns the jitted step programs for one (model, params) pair.

    The same object serves many requests (the API server holds one, like the
    reference's ModelProvider, ref: shard/openai_api.py:70-127); per-request
    state (cache, recent-token window, PRNG key) is created per call.
    """

    def __init__(
        self,
        model,
        params,
        *,
        max_seq: int = 4096,
        batch: int = 1,
        cache_dtype=jnp.bfloat16,
        prefill_chunk: int = DEFAULT_PREFILL_CHUNK,
        sp_mesh=None,
        sp_decode: bool = False,
        decode_block: int = DEFAULT_DECODE_BLOCK,
        prompt_cache: bool = False,
    ):
        from mlx_sharding_tpu import diffusion  # (it imports this module)

        diffusion.refuse(model, diffusion.SINGLE_STREAM)
        self.model = model
        # Build-time projection fusion (keep-quantized loads, single-chip):
        # concatenate each declared group's packed triples along OUT so
        # decode runs QKV / gate+up as one fused projection launch each. The
        # caller's params are not mutated (shallow-copied layer stack);
        # sp paths keep the separate projections (long-prefill bound, and
        # their params are placed before fusion would apply).
        self.fused_projections: list[str] = []
        if sp_mesh is None:
            from mlx_sharding_tpu.models.base import apply_projection_fusion

            layers = params.get("layers")
            if isinstance(layers, dict):
                layers = {
                    k: dict(v) if isinstance(v, dict) else v
                    for k, v in layers.items()
                }
                fused = apply_projection_fusion(model, layers)
                if fused:
                    params = {**params, "layers": layers}
                    self.fused_projections = fused
        self.params = params
        # Prompt-prefix caching: keep the previous request's KV cache and
        # token sequence; a new request prefills only past the longest
        # common token prefix. The chat pattern — system prompt + growing
        # history — re-sends the whole previous context every turn, so TTFT
        # drops from O(context) to O(new tokens). Rows past the matched
        # prefix are stale but NEVER attended (validity derives from the
        # offset), the same invariant the speculative rollback leans on.
        # The reference resets every remote cache per request instead
        # (shard/utils.py:122-124).
        if prompt_cache:
            refuse_recurrent(
                model, "--prompt-cache",
                "a prefix hit rewinds the cache by lowering its offset",
            )
        self._prompt_cache = bool(prompt_cache)
        self._pc = None  # {"tokens": np (T,), "cache": KVCache}
        self.last_prefix_hit = 0  # observability + tests
        # optional sequence-parallel prefill: prompts longer than one chunk
        # are sharded over the mesh's sp axis (ring attention) instead of
        # looping chunks on one device — see parallel/sp_prefill.py.
        # sp_decode additionally keeps the KV cache sequence-sharded for the
        # whole generation (parallel/sp_decode.py): capacity scales with the
        # mesh instead of one chip's HBM, removing the round-2 all-gather.
        self.sp_mesh = sp_mesh
        self._sp_prefill = None
        self._sp_decode = None
        if sp_decode and sp_mesh is None:
            raise ValueError("sp_decode requires sp_mesh")
        if sp_mesh is not None:
            from mlx_sharding_tpu.parallel.sp_prefill import (
                SpPrefill,
                supports_sp_prefill,
            )

            if not supports_sp_prefill(model):
                raise ValueError(
                    f"{type(model).__name__} does not support sequence-"
                    "parallel prefill (needs supports_sp = True with the "
                    "sp_layer/sp_groups hooks, on a full first+last stage)"
                )
            self._sp_prefill = SpPrefill(
                model, params, sp_mesh, prefill_chunk, keep_sharded=sp_decode
            )
            if sp_decode:
                from mlx_sharding_tpu.parallel.sp_decode import SpDecode

                self._sp_decode = SpDecode(
                    model, self._sp_prefill.params, sp_mesh,
                    decode_block=decode_block,
                )
        # Round capacity up to a chunk multiple: every (possibly padded)
        # prefill chunk then writes entirely inside the buffer, so padded
        # writes can never clamp-and-corrupt valid entries. Sharded-decode
        # capacity must additionally split evenly across the sp devices.
        quantum = prefill_chunk
        if sp_decode:
            from mlx_sharding_tpu.parallel.mesh import AXIS_SP

            quantum = sp_mesh.shape[AXIS_SP] * prefill_chunk
        self.max_seq = -(-max_seq // quantum) * quantum
        self.batch = batch
        self.cache_dtype = cache_dtype
        self.prefill_chunk = prefill_chunk

        def prefill_fn(params, tokens, cache, n_valid):
            out, cache = model(params, tokens, cache, n_valid=n_valid)
            last = jax.lax.dynamic_index_in_dim(out, n_valid - 1, axis=1)
            return last[:, 0], cache  # (B, V) logits (or hidden mid-pipeline)

        def decode_fn(params, token, cache, recent, key, sp):
            logits, cache = model(params, token, cache)
            key, sub = jax.random.split(key)
            tok, logprobs = sample_token(sub, logits[:, -1], sp, recent)
            recent = update_recent_tokens(recent, tok)
            return tok, logprobs, cache, recent, key

        def sample_fn(logits, recent, key, sp):
            key, sub = jax.random.split(key)
            tok, logprobs = sample_token(sub, logits, sp, recent)
            recent = update_recent_tokens(recent, tok)
            return tok, logprobs, recent, key

        def decode_block_fn(params, token, cache, recent, key, sp, want_lp):
            """``decode_block`` decode steps fused into ONE program via
            lax.scan: the host pulls tokens once per block instead of once per
            token, so a slow device-to-host pull is paid per block and
            decode stays bound by the device step, not the round trip.
            Logprob summaries (chosen + top-k) are computed on device
            inside the same scan."""

            def step(carry, _):
                tok, cache, recent, key = carry
                logits, cache = model(params, tok[:, None], cache)
                key, sub = jax.random.split(key)
                tok, logprobs = sample_token(sub, logits[:, -1], sp, recent)
                recent = update_recent_tokens(recent, tok)
                if want_lp:
                    out = (tok, *block_lp_outputs(tok, logprobs))
                else:
                    out = (tok,)
                return (tok, cache, recent, key), out

            (token, cache, recent, key), outs = jax.lax.scan(
                step, (token, cache, recent, key), None, length=decode_block
            )
            return outs, token, cache, recent, key

        self._prefill = jax.jit(prefill_fn, donate_argnums=(2,))
        self._decode = jax.jit(decode_fn, donate_argnums=(2, 3))
        self._sample = jax.jit(sample_fn, donate_argnums=(1,))
        self._decode_block = jax.jit(
            decode_block_fn, donate_argnums=(2, 3), static_argnums=(6,)
        )
        self.decode_block = decode_block

    # ------------------------------------------------------------------
    def run_prefill(self, prompt: np.ndarray, cache):
        """Chunked prefill of ``prompt`` (B, T) into ``cache`` — fixed-size
        chunks, right-padded tail (see the module docstring). Returns
        (last_valid_logits, cache). Shared by generate_step and the
        speculative decoder (both models prefill the same way)."""
        c = self.prefill_chunk
        logits = None
        for start in range(0, prompt.shape[1], c):
            chunk = prompt[:, start : start + c]
            n_valid = chunk.shape[1]
            if n_valid < c:
                chunk = np.pad(chunk, ((0, 0), (0, c - n_valid)))
            logits, cache = self._prefill(
                self.params, jnp.asarray(chunk), cache,
                jnp.asarray(n_valid, jnp.int32),
            )
        return logits, cache

    def generate_step(
        self,
        prompt_tokens: list[int] | np.ndarray,
        *,
        temperature: float = 0.0,
        top_p: float = 1.0,
        repetition_penalty: Optional[float] = None,
        repetition_context_size: int = REPETITION_WINDOW,
        logit_bias: Optional[dict[int, float]] = None,
        seed: Optional[int] = None,
        max_tokens: int = 256,
        want_logprobs: bool = False,
    ) -> Iterator[tuple[int, Optional[TokenLogprobs]]]:
        """Yields ``(token, logprobs)`` per generated token — the contract of
        the reference's generate_step closures (shard/utils.py:152-186).
        ``logprobs`` is a :class:`TokenLogprobs` when ``want_logprobs`` else
        None; the summary is computed on device inside the decode block."""
        sp = make_sampler_params(temperature, top_p, repetition_penalty, logit_bias)
        key = jax.random.PRNGKey(int(time.time_ns()) & 0x7FFFFFFF if seed is None else seed)
        prompt = np.asarray(prompt_tokens, np.int32).reshape(self.batch, -1)
        n_prompt = prompt.shape[1]
        if n_prompt == 0:
            raise ValueError("empty prompt")
        if n_prompt + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({n_prompt}) + max_tokens ({max_tokens}) exceeds KV "
                f"capacity {self.max_seq}"
            )

        recent = init_recent_tokens(self.batch, repetition_context_size, prompt)
        if self._sp_decode is not None:
            yield from self._generate_sp(
                prompt, recent, key, sp, max_tokens, want_logprobs
            )
            return

        # prompt-prefix reuse: consume the previous request's cache (its
        # buffer is about to be donated either way) and compute the longest
        # common token prefix. Cap at n_prompt - 1 — at least one token must
        # prefill to produce logits.
        use_pc = self._prompt_cache and self.batch == 1
        pc_hit = 0
        cache = None
        if use_pc:
            pc, self._pc = self._pc, None
            if pc is not None:
                known = pc["tokens"]
                limit = min(known.size, n_prompt - 1)
                eq = known[:limit] == prompt[0, :limit]
                pc_hit = limit if eq.all() else int(eq.argmin())
                # the padded FINAL suffix chunk must not cross max_seq —
                # dynamic_update_slice would clamp its start and overwrite
                # valid rows. If a non-aligned hit would overflow, align it
                # down to a chunk boundary (aligned prefill always fits:
                # max_seq is a chunk multiple and n_prompt <= max_seq).
                c = self.prefill_chunk
                if pc_hit and pc_hit + -(-(n_prompt - pc_hit) // c) * c > self.max_seq:
                    pc_hit = (pc_hit // c) * c
                cache = (
                    pc["cache"]._replace(
                        offset=jnp.asarray(pc_hit, jnp.int32)
                    )
                    if pc_hit > 0
                    else reset(pc["cache"])  # reuse the buffer, offset 0
                )
        self.last_prefix_hit = pc_hit
        if cache is None:
            cache = self.model.make_cache(self.batch, self.max_seq, self.cache_dtype)

        # chunked prefill (ref does whole-prompt single shot, shard/utils.py:158;
        # chunking bounds activation memory and fixes compile shapes). Capacity
        # was verified above with host arithmetic — no per-chunk device sync.
        use_sp = (
            self._sp_prefill is not None
            and n_prompt > self.prefill_chunk
            and pc_hit == 0  # sp prefill shards the WHOLE prompt from 0
            # quantum padding may need more cache rows than the prompt itself;
            # fall back to the chunked path rather than fail a fitting request
            and self._sp_prefill.padded_len(n_prompt) <= cache.max_seq
        )
        if use_sp:
            last_logits, cache = self._sp_prefill(prompt, cache)
        else:
            last_logits, cache = self.run_prefill(prompt[:, pc_hit:], cache)

        tok, logprobs, recent, key = self._sample(last_logits, recent, key, sp)

        first_lp = None
        if want_logprobs:
            chosen, top_v, top_i = block_lp_outputs(tok, logprobs)
            first_lp = TokenLogprobs(
                float(chosen[0]), np.asarray(top_i[0]), np.asarray(top_v[0])
            )

        last = {"cache": cache}  # latest un-donated cache in the chain
        collected: list[np.ndarray] = []

        def dispatch(carry):
            outs, t, c, r, kk = self._decode_block(
                self.params, carry[0], carry[1], carry[2], carry[3],
                sp, want_logprobs,
            )
            last["cache"] = c
            return outs, (t, c, r, kk)

        try:
            yield int(tok[0]), first_lp
            remaining = max_tokens - 1
            if remaining <= 0:
                return
            yield from blocked_token_stream(
                dispatch, (tok, cache, recent, key), remaining,
                self.decode_block, want_logprobs,
                sink=(lambda toks: collected.append(np.asarray(toks)[:, 0]))
                if use_pc else None,
            )
        finally:
            if use_pc:
                # tokens whose KV rows we can ACCOUNT FOR: the prompt plus
                # every fed decode token from pulled blocks (the last
                # sampled token was never fed; rows written by dispatched-
                # but-unpulled lookahead blocks hold tokens we can't name —
                # the prefix match is simply capped at what we know)
                fed = [int(np.asarray(tok)[0])]
                for blk in collected:
                    fed.extend(int(t) for t in blk)
                self._pc = {
                    "tokens": np.concatenate(
                        [prompt[0], np.asarray(fed[:-1], np.int32)]
                    ),
                    "cache": last["cache"],
                }


    # ------------------------------------------------------------------
    def _generate_sp(self, prompt, recent, key, sp, max_tokens, want_logprobs):
        """Generation over an sp-sharded KV cache: sequence-parallel prefill
        (no gather), distributed decode attention (parallel/sp_decode.py).
        Same blocked/lookahead host loop as the dense path."""
        spd = self._sp_decode
        n_prompt = prompt.shape[1]
        # capacity holds by construction: max_seq is a quantum multiple and
        # generate_step already checked n_prompt + max_tokens <= max_seq
        assert self._sp_prefill.padded_len(n_prompt) <= self.max_seq
        cache = spd.make_cache(self.batch, self.max_seq, self.cache_dtype)
        logits, ks, vs = self._sp_prefill.prefill_sharded(prompt)
        cache = spd.write_prefill(cache, ks, vs, n_prompt)
        tok, logprobs, recent, key = self._sample(logits, recent, key, sp)

        first_lp = None
        if want_logprobs:
            chosen, top_v, top_i = block_lp_outputs(tok, logprobs)
            first_lp = TokenLogprobs(
                float(chosen[0]), np.asarray(top_i[0]), np.asarray(top_v[0])
            )
        yield int(tok[0]), first_lp
        remaining = max_tokens - 1
        if remaining <= 0:
            return

        prog = spd.block_prog(want_logprobs)

        def dispatch(carry):
            outs, tok, k, v, off, recent, key = prog(spd.params, *carry, sp)
            return outs, (tok, k, v, off, recent, key)

        yield from blocked_token_stream(
            dispatch, (tok, cache.k, cache.v, cache.offset, recent, key),
            remaining, spd.decode_block, want_logprobs,
        )


def stream_generate(
    generator: Generator,
    tokenizer,
    prompt_tokens: list[int],
    *,
    max_tokens: int = 256,
    stop_id_sequences: Optional[list[list[int]]] = None,
    eos_token_ids: Optional[list[int]] = None,
    **sampler_kwargs,
) -> Iterator[StreamChunk]:
    """Detokenized streaming with stop handling + tok/s instrumentation
    (semantics of ref generate.py:90-122 stream_generate)."""
    from mlx_sharding_tpu.tokenizer_utils import (
        StreamingDetokenizer,
        sequence_overlap,
        stopping_criteria,
    )

    stop_id_sequences = stop_id_sequences or []
    if eos_token_ids is None:
        eos = getattr(tokenizer, "eos_token_id", None)
        eos_token_ids = [eos] if eos is not None else []
    detok = StreamingDetokenizer(tokenizer)
    tokens: list[int] = []
    in_flight: list[int] = []  # withheld: could still grow into a stop sequence

    start = time.perf_counter()
    first_token_time = None
    finish_reason = "length"
    for token, logprobs in generator.generate_step(
        prompt_tokens, max_tokens=max_tokens, **sampler_kwargs
    ):
        if first_token_time is None:
            first_token_time = time.perf_counter()
        tokens.append(token)
        if token in eos_token_ids:
            finish_reason = "stop"
            in_flight.clear()
            break
        stop = stopping_criteria(tokens, stop_id_sequences, None)
        if stop.stop_met:
            # the matched stop sequence itself is trimmed, never emitted
            # (ref shard/openai_api.py:465-474 trim semantics)
            finish_reason = "stop"
            tokens = tokens[: len(tokens) - stop.trim_length]
            in_flight.clear()
            break
        if stop_id_sequences and any(
            sequence_overlap(tokens, s) for s in stop_id_sequences
        ):
            in_flight.append(token)
            continue
        for t in in_flight:
            detok.add_token(t)
        in_flight.clear()
        detok.add_token(token)
        if detok.last_segment:
            yield StreamChunk(text=detok.last_segment, token=token)
    # a run that ended on length while buffering emits the buffered tokens —
    # they were never part of a completed stop sequence
    for t in in_flight:
        detok.add_token(t)
    detok.finalize()
    end = time.perf_counter()

    n_prompt = len(prompt_tokens)
    ttft = (first_token_time or end) - start
    gen_time = max(end - (first_token_time or end), 1e-9)
    yield StreamChunk(
        text=detok.last_segment if detok.last_segment else "",
        finish_reason=finish_reason,
        prompt_tokens=n_prompt,
        generation_tokens=len(tokens),
        prompt_tps=n_prompt / max(ttft, 1e-9),
        generation_tps=max(len(tokens) - 1, 0) / gen_time,
        ttft=ttft,
    )
