"""Speculative decoding with a draft model — exact greedy acceleration.

ROADMAP item: the reference has no speculation of any kind. A small draft
model proposes ``spec_k`` tokens per round; the target model scores all of
them in ONE T=K forward (prefill-shaped — MXU-efficient, unlike K
sequential matvecs) and the longest prefix the target agrees with is
emitted, plus the target's own correction token at the first divergence.
Every emitted token is exactly what plain greedy decode would produce —
whatever the draft's quality, only throughput changes, never content
(tested token-exact in tests/test_speculative.py).

The TPU-shaped part is the rollback: this framework's caches derive
validity from the offset (rows past it are never attended and are
overwritten in place), so rejecting draft tokens costs ONE scalar — set
``offset = verified_prefix_end`` — no copying, no paging, no mask
rebuild. The draft model keeps its own cache and rewinds the same way.

Greedy requests (temperature == 0 — the serving default) use exact prefix
acceptance: every emitted token is what plain greedy decode would produce.
Sampled requests (temperature > 0) use REJECTION SAMPLING (Leviathan et
al.): the draft SAMPLES its proposals and records its distribution q_i;
the target's one T=K forward yields p_i; proposal d is accepted with
probability min(1, p_i(d)/q_i(d)), and the first rejection resamples from
the residual norm(max(p_i - q_i, 0)). The emitted stream is distributed
EXACTLY as plain sampling from the target (tested distributionally in
tests/test_speculative.py) — the draft only changes throughput, never the
distribution. Both p and q are the fully-transformed distributions
(logit_bias, repetition penalty over an exactly-evolved window,
temperature, top-p nucleus), so speculation composes with every sampler
knob; the token streams differ from non-speculative sampling for the same
seed (the PRNG is consumed differently), which is inherent to the method.
"""

from __future__ import annotations

import time
from typing import Callable, Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import refuse_recurrent
from mlx_sharding_tpu.generate import (
    REPETITION_WINDOW,
    Generator,
    TokenLogprobs,
)
from mlx_sharding_tpu.sample import (
    init_recent_tokens,
    make_sampler_params,
    nucleus_logits,
    sample_token,
    transform_logits,
    update_recent_tokens,
)

# the adaptive window ladder: 0 == drafting disabled for the slot, the
# nonzero rungs are the candidate speculation windows. Powers of two keep
# the number of distinct verify-program compilations at 3.
SPEC_WINDOW_LADDER = (0, 2, 4, 8)


def _dist_logits(logits, recent, sp):
    """The request's full sampling distribution in log domain (unnormalized),
    via the SAME pipeline sample_token samples from (sample.py
    transform_logits → nucleus_logits) — p and q below are both defined by
    it, which is what makes the acceptance ratio meaningful."""
    return nucleus_logits(transform_logits(logits, recent, sp), sp)


def rejection_round(key, drafts, q_logprobs, p_logprobs):
    """One round of speculative rejection sampling (pure math, jit-safe).

    drafts: (K, B) proposals; q_logprobs / p_logprobs: (K, B, V) draft and
    target log-distributions at each slot. Returns (gs, m, count):
    gs (K, B) — per-slot emitted token (draft token where accepted, the
    residual resample where rejected; only slots ≤ m are meaningful),
    m (B,) — last emitted slot, count (B,) = m + 1.

    Guarantee (the Leviathan et al. identity, unit-tested directly): the
    token emitted at a slot is distributed exactly as p at that slot."""
    K, B = drafts.shape
    k_u, k_res = jax.random.split(key)
    u = jax.random.uniform(k_u, (K, B))
    d_lp_q = jnp.take_along_axis(
        q_logprobs, drafts[..., None], axis=-1
    )[..., 0]  # (K, B)
    d_lp_p = jnp.take_along_axis(
        p_logprobs, drafts[..., None], axis=-1
    )[..., 0]
    # accept with prob min(1, p/q); exp of a clamped-to-0 log ratio avoids
    # overflow and u < 1 makes ratio >= 1 an unconditional accept
    accept = u < jnp.exp(jnp.minimum(d_lp_p - d_lp_q, 0.0))
    reject = ~accept

    # residual distribution per slot: norm(max(p - q, 0)); if its mass is
    # ~0 (p ≈ q everywhere) resampling from p is the correct limit
    p = jnp.exp(p_logprobs)
    q = jnp.exp(q_logprobs)
    res = jnp.maximum(p - q, 0.0)
    mass = res.sum(axis=-1, keepdims=True)
    res_logits = jnp.where(mass > 1e-9, jnp.log(res), p_logprobs)
    r = jax.vmap(jax.random.categorical)(
        jax.random.split(k_res, K), res_logits
    ).astype(jnp.int32)  # (K, B)

    gs = jnp.where(reject, r, drafts)
    any_rej = reject.any(axis=0)
    first = jnp.argmax(reject, axis=0)
    m = jnp.where(any_rej, first, K - 1)
    return gs, m, (m + 1).astype(jnp.int32)


def _round_epilogue(K, gs, m, count, off0, cache, recent):
    """Shared verify epilogue (greedy and rejection-sampled rounds): replay
    ONLY the emitted tokens into the recent window, keep exactly the
    verified prefix in the cache (gs[m] is the next feed token and is NOT
    cached), return the round tuple."""

    def replay(carry, i):
        recent = carry
        upd = update_recent_tokens(recent, gs[i])
        return jnp.where((i <= m)[:, None], upd, recent), None

    recent, _ = jax.lax.scan(replay, recent, jnp.arange(K))
    cache = cache._replace(offset=off0 + count[0])
    return gs, count, gs[m[0]], cache, recent


def one_hot_draft_logprobs(drafts, vocab_size):
    """The q-distribution of a DETERMINISTIC proposer (n-gram lookup) in
    log domain: probability 1 on the proposed token, ~0 elsewhere. With
    this q the rejection-sampling identity degenerates to: accept d with
    probability p(d), else resample from p with d removed (renormalized) —
    exact for any proposal chain. Built INSIDE jit from the (K, B) draft
    ids, so no (K, B, V) array ever crosses the host boundary."""
    hot = jax.nn.one_hot(drafts, vocab_size, dtype=bool)  # (K, B, V)
    return jnp.where(hot, 0.0, -1e9)


class NgramDraftProposer:
    """Prompt-lookup drafting: propose the K tokens that followed the most
    recent occurrence of the stream's trailing n-gram (n = max_ngram down
    to min_ngram) in the slot's prompt + produced history. Free speculation
    — no second checkpoint, no draft KV cache, no device work; repetitive
    streams (code, extraction, chat with quoting) accept long runs while
    novel text simply proposes nothing and the round degenerates to plain
    decode for that slot.

    Host-pure by contract: ``propose`` touches numpy only — it runs inside
    the scheduler's tick-hot path (mstcheck MST114 enforces that neither it
    nor the acceptance tracker ever performs a device sync). The trailing
    ``window`` tokens of the history act as the ring buffer: matching cost
    is O(window) vectorized per round, independent of stream length.

    Proposals shorter than ``k`` are padded with token 0 — a VALID id, not
    a sentinel: padded rows still flow through the verify forward, and the
    caller cuts them off via the per-slot window cap (``n_valid``). A -1
    pad would be clamped to row 0 by ``take_along_axis`` and one_hot(-1)
    is all-zero, which silently corrupts the sampled acceptance math."""

    def __init__(self, *, max_ngram: int = 3, min_ngram: int = 1,
                 window: int = 2048):
        if not (1 <= min_ngram <= max_ngram):
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"({min_ngram}, {max_ngram})"
            )
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram
        self.window = window

    def propose(self, tokens, k: int):
        """tokens: 1-D int sequence, most recent last (prompt + history).
        Returns ``(drafts, n_valid)``: drafts is (k,) int32 padded with
        token 0 past ``n_valid``; n_valid == 0 means no match anywhere."""
        toks = np.asarray(tokens, np.int32).ravel()
        if self.window and toks.size > self.window:
            toks = toks[-self.window:]
        out = np.zeros(k, np.int32)
        n_tok = int(toks.size)
        if k < 1 or n_tok < self.min_ngram + 1:
            return out, 0
        # longest context first; the trailing window itself is excluded
        # (a window over toks[:-1] can't start at the trailing position)
        hay = toks[:-1]
        for n in range(min(self.max_ngram, n_tok - 1), self.min_ngram - 1, -1):
            pat = toks[-n:]
            wins = np.lib.stride_tricks.sliding_window_view(hay, n)
            hits = np.nonzero((wins == pat).all(axis=1))[0]
            if hits.size == 0:
                continue
            start = int(hits[-1]) + n  # most recent occurrence wins
            cont = toks[start:start + k]
            out[:cont.size] = cont
            return out, int(cont.size)
        return out, 0


class AcceptanceTracker:
    """Per-slot adaptive speculation-window controller.

    Tracks an EWMA of tokens-emitted-per-round (``count`` ∈ [1, w]: 1 means
    the draft never agreed — the round cost a K-wide forward to emit what
    plain decode emits with a 1-wide one) and walks the slot's window along
    ``SPEC_WINDOW_LADDER``:

    - grow to the next rung when the EWMA fills ≥ ``grow_frac`` of the
      current window (the draft is saturating it);
    - shrink one rung when the EWMA pays for ≤ max(1.25, shrink_frac·w)
      tokens — below the bottom rung the slot DISABLES (window 0) and
      re-probes at the bottom rung after ``probe_after_s`` (injectable
      ``clock`` keeps the schedule deterministic under test).

    The same per-slot EWMAs order brownout shedding: at pressure level 2
    ``effective_windows`` sheds the lowest-acceptance half of live slots
    (speculation that barely pays is the first capacity lever to drop);
    level ≥ 3 sheds all. Shedding is per-round pressure, not slot state —
    the EWMA keeps evolving and the window returns the moment pressure
    clears. Host-pure: observe/effective_windows touch python ints only
    (MST114)."""

    def __init__(self, n_slots: int, *, w_max: int = 8, alpha: float = 0.25,
                 grow_frac: float = 0.85, shrink_frac: float = 0.35,
                 probe_after_s: float = 1.0,
                 clock: Callable[[], float] = time.monotonic):
        rungs = tuple(w for w in SPEC_WINDOW_LADDER if 0 < w <= max(w_max, 2))
        self.rungs = rungs
        self.alpha = alpha
        self.grow_frac = grow_frac
        self.shrink_frac = shrink_frac
        self.probe_after_s = probe_after_s
        self.clock = clock
        self.shed_events = 0
        self._win = [rungs[0]] * n_slots
        self._ewma: list[Optional[float]] = [None] * n_slots
        self._disabled_at: list[Optional[float]] = [None] * n_slots
        self._shed_prev: set[int] = set()

    def reset(self, slot: int):
        """New request in the slot: fresh window at the bottom rung (probe
        first, grow on evidence) and no carried-over acceptance history."""
        self._win[slot] = self.rungs[0]
        self._ewma[slot] = None
        self._disabled_at[slot] = None

    def observe(self, slot: int, window: int, count: int):
        """Fold one round's outcome (``count`` tokens emitted from a
        ``window``-wide round) into the slot's EWMA and resize."""
        if window < 1:
            return
        e = self._ewma[slot]
        e = float(count) if e is None else (
            self.alpha * count + (1.0 - self.alpha) * e
        )
        self._ewma[slot] = e
        w = self._win[slot]
        if w == 0:
            return
        if e >= self.grow_frac * w and w < self.rungs[-1]:
            self._win[slot] = self.rungs[
                min(self.rungs.index(w) + 1, len(self.rungs) - 1)
            ]
        elif e <= max(1.25, self.shrink_frac * w):
            i = self.rungs.index(w)
            if i == 0:
                self._win[slot] = 0
                self._disabled_at[slot] = self.clock()
                self._ewma[slot] = None  # the probe gets fresh evidence
            else:
                self._win[slot] = self.rungs[i - 1]

    def window(self, slot: int) -> int:
        """Current window for the slot, applying the re-probe schedule:
        a disabled slot returns to the bottom rung after probe_after_s."""
        if self._win[slot] == 0 and self._disabled_at[slot] is not None:
            if self.clock() - self._disabled_at[slot] >= self.probe_after_s:
                self._win[slot] = self.rungs[0]
                self._disabled_at[slot] = None
        return self._win[slot]

    def effective_windows(self, slots: Sequence[int], level: int = 0):
        """Per-round window plan for the live ``slots`` under brownout
        pressure ``level``: level >= 3 sheds every slot, level 2 sheds the
        lowest-EWMA half (no-evidence slots shed first — under pressure,
        unproven speculation goes before proven), below 2 sheds nothing.
        Returns {slot: window}; counts shed-set ENTRY transitions in
        ``shed_events``."""
        wins = {s: self.window(s) for s in slots}
        enabled = [s for s in slots if wins[s] > 0]
        if level >= 3:
            shed = set(enabled)
        elif level == 2 and enabled:
            order = sorted(
                enabled,
                key=lambda s: (
                    self._ewma[s] if self._ewma[s] is not None else 0.0, s
                ),
            )
            shed = set(order[: (len(enabled) + 1) // 2])
        else:
            shed = set()
        self.shed_events += len(shed - self._shed_prev)
        self._shed_prev = shed
        for s in shed:
            wins[s] = 0
        return wins

    def ewma(self, slot: int) -> Optional[float]:
        return self._ewma[slot]

    def stats(self) -> dict:
        """Gauge source for the mst_spec_* metrics and /health."""
        tracked = [e for e in self._ewma if e is not None]
        return {
            "windows": list(self._win),
            "disabled_slots": sum(
                1 for w, d in zip(self._win, self._disabled_at)
                if w == 0 and d is not None
            ),
            "shed_events": self.shed_events,
            "ewma_mean": (sum(tracked) / len(tracked)) if tracked else 0.0,
        }


def _refuse_recurrent_pair(*models) -> None:
    """A rejected draft is undone by lowering the cache's offset, which
    does not rewind a recurrent state: neither side may have one."""
    for m in models:
        refuse_recurrent(
            m, "--draft",
            "a rejected draft is undone by lowering the cache's offset",
        )


class SpeculativeGenerator:
    """``generate_step`` contract over a (target, draft) model pair.

    Holds two plain Generators (their prefill/sample programs are reused
    verbatim) plus two speculation programs: the draft's K-step greedy
    scan and the target's fused verify (T=K forward + transform-aware
    acceptance)."""

    def __init__(
        self,
        model,
        params,
        draft_model,
        draft_params,
        *,
        spec_k: int = 4,
        max_seq: int = 4096,
        cache_dtype=jnp.bfloat16,
        prefill_chunk: int = 256,
        decode_block: int = 16,
    ):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        _refuse_recurrent_pair(model, draft_model)
        tv = getattr(model.config, "vocab_size", None)
        dv = getattr(draft_model.config, "vocab_size", None)
        if tv != dv:
            # a mismatched pair would silently emit clamped-index garbage:
            # draft token ids index the target's embedding/logprob rows
            raise ValueError(
                f"draft vocab ({dv}) must match target vocab ({tv}) — "
                "speculation exchanges raw token ids between the models"
            )
        if not (model.config.is_first_stage and model.config.is_last_stage):
            raise ValueError(
                "speculative decoding needs the FULL model on one program "
                "(no start/end-layer stage slice)"
            )
        self.spec_k = spec_k
        # acceptance telemetry: tokens emitted per verify round averages
        # between 1 (draft never agrees) and K (always agrees)
        self.rounds = 0
        self.accepted_tokens = 0
        self.target = Generator(
            model, params, max_seq=max_seq, cache_dtype=cache_dtype,
            prefill_chunk=prefill_chunk, decode_block=decode_block,
        )
        self.draft = Generator(
            draft_model, draft_params, max_seq=max_seq,
            cache_dtype=cache_dtype, prefill_chunk=prefill_chunk,
        )
        self.max_seq = self.target.max_seq

        K = spec_k

        def draft_block_fn(dparams, token, dcache):
            """K greedy draft proposals (plain argmax — transforms live on
            the verify side where exactness is decided)."""

            def step(carry, _):
                tok, dcache = carry
                logits, dcache = draft_model(dparams, tok[:, None], dcache)
                tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (tok, dcache), tok

            (_, dcache), drafts = jax.lax.scan(
                step, (token, dcache), None, length=K
            )
            return drafts, dcache  # drafts (K, B)

        def finish_round(gs, m, count, off0, cache, recent):
            return _round_epilogue(K, gs, m, count, off0, cache, recent)

        def verify_fn(params, token, drafts, cache, recent, sp):
            """One target forward over [t0, d1..d_{K-1}] scores every draft
            position; acceptance walks the agreement prefix. Returns the
            emitted tokens (K, B; rows past ``count`` are garbage), the
            count, the next feed token, and state rewound to the verified
            prefix."""
            b = token.shape[0]
            x = jnp.concatenate([token[:, None], drafts[:-1].T], axis=1)  # (B, K)
            off0 = cache.offset
            logits, cache = model(params, x, cache)  # (B, K, V)
            zero_key = jax.random.PRNGKey(0)  # unused at temperature 0

            def score(carry, i):
                recent = carry
                g, _ = sample_token(zero_key, logits[:, i], sp, recent)
                recent = update_recent_tokens(recent, g)
                return recent, g

            _, gs = jax.lax.scan(score, recent, jnp.arange(K))  # (K, B)

            mism = gs != drafts  # position i: target's g_i vs proposal d_{i+1}
            any_mism = mism.any(axis=0)  # (B,)
            first = jnp.argmax(mism, axis=0)  # first True (0 if none)
            m = jnp.where(any_mism, first, K - 1)
            count = (m + 1).astype(jnp.int32)  # tokens emitted this round
            return finish_round(gs, m, count, off0, cache, recent)

        def draft_sampled_fn(dparams, token, dcache, recent, keys, sp):
            """K sampled draft proposals + the exact distribution each was
            drawn from (q_i log rows — the acceptance denominator). The
            draft sees the target's true recent window and evolves a local
            copy with its own proposals."""

            def step(carry, key_i):
                tok, dcache, recent = carry
                logits, dcache = draft_model(dparams, tok[:, None], dcache)
                f = _dist_logits(logits[:, -1], recent, sp)
                qlp = jax.nn.log_softmax(f, axis=-1)
                tok = jax.random.categorical(key_i, f, axis=-1).astype(
                    jnp.int32
                )
                recent = update_recent_tokens(recent, tok)
                return (tok, dcache, recent), (tok, qlp)

            (_, dcache, _), (drafts, qlps) = jax.lax.scan(
                step, (token, dcache, recent), keys
            )
            return drafts, qlps, dcache  # (K, B), (K, B, V)

        def verify_sampled_fn(params, token, drafts, qlps, cache, recent,
                              key, sp):
            """Target T=K forward + rejection sampling. Same bookkeeping as
            the greedy verify: gs[m] is the next feed token and is NOT in
            the cache; offset keeps exactly the verified prefix."""
            x = jnp.concatenate([token[:, None], drafts[:-1].T], axis=1)
            off0 = cache.offset
            logits, cache = model(params, x, cache)  # (B, K, V)

            def score(carry, i):
                recent = carry
                f = _dist_logits(logits[:, i], recent, sp)
                plp = jax.nn.log_softmax(f, axis=-1)
                # the consumed token at slot i+1 is drafts[i]; evolving with
                # it is exact on the accepted prefix (discarded past it)
                recent = update_recent_tokens(recent, drafts[i])
                return recent, plp

            _, plps = jax.lax.scan(score, recent, jnp.arange(K))  # (K, B, V)
            gs, m, count = rejection_round(key, drafts, qlps, plps)
            return finish_round(gs, m, count, off0, cache, recent)

        self._draft_block = jax.jit(draft_block_fn, donate_argnums=(2,))
        self._verify = jax.jit(verify_fn, donate_argnums=(3, 4))
        self._draft_sampled = jax.jit(draft_sampled_fn, donate_argnums=(2,))
        self._verify_sampled = jax.jit(
            verify_sampled_fn, donate_argnums=(4, 5)
        )
        self._rewind = jax.jit(
            lambda c, off: c._replace(offset=off), donate_argnums=(0,)
        )

    # ------------------------------------------------------------------
    def generate_step(
        self,
        prompt_tokens,
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
        if want_logprobs:
            # logprobs need per-token summaries the verify path doesn't
            # compute — take the exact normal path
            yield from self.target.generate_step(
                prompt_tokens, temperature=temperature, top_p=top_p,
                repetition_penalty=repetition_penalty,
                repetition_context_size=repetition_context_size,
                logit_bias=logit_bias, seed=seed, max_tokens=max_tokens,
                want_logprobs=want_logprobs,
            )
            return

        sampled = temperature > 0
        sp = make_sampler_params(
            temperature, top_p, repetition_penalty, logit_bias
        )
        prompt = np.asarray(prompt_tokens, np.int32).reshape(
            self.target.batch, -1
        )
        n_prompt = prompt.shape[1]
        if n_prompt + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({n_prompt}) + max_tokens ({max_tokens}) exceeds KV "
                f"capacity {self.max_seq}"
            )

        import time as _time

        t = self.target
        cache = t.model.make_cache(t.batch, t.max_seq, t.cache_dtype)
        recent = init_recent_tokens(t.batch, repetition_context_size, prompt)
        key = jax.random.PRNGKey(
            int(_time.time_ns()) & 0x7FFFFFFF if seed is None else seed
        )

        last_logits, cache = t.run_prefill(prompt, cache)
        # draft prefills the same prompt into its own cache
        d = self.draft
        dcache = d.model.make_cache(d.batch, d.max_seq, d.cache_dtype)
        _, dcache = d.run_prefill(prompt, dcache)

        tok, logprobs, recent, key = t._sample(last_logits, recent, key, sp)
        yield int(tok[0]), None
        emitted = 1
        # the first emitted token's row is in NEITHER cache: both models
        # consume it as the next round's feed token, exactly like normal
        # decode. ``offset`` mirrors cache.offset on host for the capacity
        # check (it grows by the accepted count each round).
        offset = n_prompt
        K = self.spec_k
        while emitted < max_tokens:
            if offset + K > self.max_seq or max_tokens - emitted < 2:
                # tail (or capacity edge): plain blocked decode from here
                remaining = max_tokens - emitted

                def dispatch(carry):
                    outs, tk, ch, rc, kk = t._decode_block(
                        t.params, carry[0], carry[1], carry[2], carry[3],
                        sp, False,
                    )
                    return outs, (tk, ch, rc, kk)

                from mlx_sharding_tpu.generate import blocked_token_stream

                yield from blocked_token_stream(
                    dispatch, (tok, cache, recent, key), remaining,
                    t.decode_block, False,
                )
                return

            if sampled:
                key, kd, kv = jax.random.split(key, 3)
                drafts, qlps, dcache = self._draft_sampled(
                    d.params, tok, dcache, recent, jax.random.split(kd, K), sp
                )
                gs, count, tok, cache, recent = self._verify_sampled(
                    t.params, tok, drafts, qlps, cache, recent, kv, sp
                )
            else:
                drafts, dcache = self._draft_block(d.params, tok, dcache)
                gs, count, tok, cache, recent = self._verify(
                    t.params, tok, drafts, cache, recent, sp
                )
            n, gs_host = int(count[0]), np.asarray(gs)
            self.rounds += 1
            self.accepted_tokens += n
            # draft consumed [t0, d1..d_{K-1}] = K rows; keep the verified
            # prefix (the accepted tokens ARE the draft's inputs there)
            dcache = self._rewind(
                dcache, dcache.offset - K + jnp.asarray(n, jnp.int32)
            )
            for j in range(n):
                if emitted >= max_tokens:
                    break
                yield int(gs_host[j, 0]), None
                emitted += 1
            offset += n


class NgramSpeculativeGenerator:
    """``generate_step`` contract with prompt-lookup drafts — no draft
    model, no draft KV cache. Proposals come from :class:`NgramDraftProposer`
    over the stream's own prompt + produced history; the target scores them
    in one T=K forward exactly like the draft-engine path. The window
    adapts per round via :class:`AcceptanceTracker`; a disabled window runs
    K=1 rounds (verify-only decode — one token per forward, still exact)
    until the re-probe timer fires.

    Greedy streams are token-exact vs plain decode (acceptance-prefix
    argument, draft-agnostic); sampled streams are distribution-exact via
    rejection sampling against the proposer's one-hot q. The per-round
    window cap is applied INSIDE the verify program (m = min(m, wcap-1)):
    truncating to a prefix of properly-accepted positions before anything
    past it is committed is exactly window-wcap speculation."""

    def __init__(
        self,
        model,
        params,
        *,
        spec_window_max: int = 8,
        max_seq: int = 4096,
        cache_dtype=jnp.bfloat16,
        prefill_chunk: int = 256,
        decode_block: int = 16,
        max_ngram: int = 3,
        clock: Callable[[], float] = time.monotonic,
    ):
        if spec_window_max < 2:
            raise ValueError(
                f"spec_window_max must be >= 2, got {spec_window_max}"
            )
        _refuse_recurrent_pair(model)
        if not (model.config.is_first_stage and model.config.is_last_stage):
            raise ValueError(
                "speculative decoding needs the FULL model on one program "
                "(no start/end-layer stage slice)"
            )
        self.target = Generator(
            model, params, max_seq=max_seq, cache_dtype=cache_dtype,
            prefill_chunk=prefill_chunk, decode_block=decode_block,
        )
        self.max_seq = self.target.max_seq
        self.proposer = NgramDraftProposer(max_ngram=max_ngram)
        self.tracker = AcceptanceTracker(1, w_max=spec_window_max, clock=clock)
        self.spec_window_max = spec_window_max
        self.rounds = 0
        self.accepted_tokens = 0
        self.draft_tokens = 0
        self._model = model
        self._verify_greedy: dict[int, Callable] = {}
        self._verify_sampled: dict[int, Callable] = {}

    def _greedy_prog(self, K: int):
        prog = self._verify_greedy.get(K)
        if prog is not None:
            return prog
        model = self._model

        def fn(params, token, drafts, wcap, cache, recent, sp):
            x = jnp.concatenate([token[:, None], drafts[:-1].T], axis=1)
            off0 = cache.offset
            logits, cache = model(params, x, cache)  # (B, K, V)
            zero_key = jax.random.PRNGKey(0)  # unused at temperature 0

            def score(carry, i):
                recent = carry
                g, _ = sample_token(zero_key, logits[:, i], sp, recent)
                recent = update_recent_tokens(recent, g)
                return recent, g

            _, gs = jax.lax.scan(score, recent, jnp.arange(K))  # (K, B)
            mism = gs != drafts
            any_mism = mism.any(axis=0)
            first = jnp.argmax(mism, axis=0)
            m = jnp.where(any_mism, first, K - 1)
            m = jnp.minimum(m, wcap - 1)  # per-round window cap
            count = (m + 1).astype(jnp.int32)
            return _round_epilogue(K, gs, m, count, off0, cache, recent)

        prog = jax.jit(fn, donate_argnums=(4, 5))
        self._verify_greedy[K] = prog
        return prog

    def _sampled_prog(self, K: int):
        prog = self._verify_sampled.get(K)
        if prog is not None:
            return prog
        model = self._model
        vocab = model.config.vocab_size

        def fn(params, token, drafts, wcap, cache, recent, key, sp):
            x = jnp.concatenate([token[:, None], drafts[:-1].T], axis=1)
            off0 = cache.offset
            logits, cache = model(params, x, cache)  # (B, K, V)

            def score(carry, i):
                recent = carry
                f = _dist_logits(logits[:, i], recent, sp)
                plp = jax.nn.log_softmax(f, axis=-1)
                recent = update_recent_tokens(recent, drafts[i])
                return recent, plp

            _, plps = jax.lax.scan(score, recent, jnp.arange(K))
            qlps = one_hot_draft_logprobs(drafts, vocab)
            gs, m, count = rejection_round(key, drafts, qlps, plps)
            m = jnp.minimum(m, wcap - 1)  # per-round window cap
            count = (m + 1).astype(jnp.int32)
            return _round_epilogue(K, gs, m, count, off0, cache, recent)

        prog = jax.jit(fn, donate_argnums=(4, 5))
        self._verify_sampled[K] = prog
        return prog

    # ------------------------------------------------------------------
    def generate_step(
        self,
        prompt_tokens,
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
        if want_logprobs:
            # logprobs need per-token summaries the verify path doesn't
            # compute — take the exact normal path
            yield from self.target.generate_step(
                prompt_tokens, temperature=temperature, top_p=top_p,
                repetition_penalty=repetition_penalty,
                repetition_context_size=repetition_context_size,
                logit_bias=logit_bias, seed=seed, max_tokens=max_tokens,
                want_logprobs=want_logprobs,
            )
            return

        sampled = temperature > 0
        sp = make_sampler_params(
            temperature, top_p, repetition_penalty, logit_bias
        )
        prompt = np.asarray(prompt_tokens, np.int32).reshape(
            self.target.batch, -1
        )
        n_prompt = prompt.shape[1]
        if n_prompt + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({n_prompt}) + max_tokens ({max_tokens}) exceeds KV "
                f"capacity {self.max_seq}"
            )

        t = self.target
        cache = t.model.make_cache(t.batch, t.max_seq, t.cache_dtype)
        recent = init_recent_tokens(t.batch, repetition_context_size, prompt)
        key = jax.random.PRNGKey(
            int(time.time_ns()) & 0x7FFFFFFF if seed is None else seed
        )
        self.tracker.reset(0)

        last_logits, cache = t.run_prefill(prompt, cache)
        tok, logprobs, recent, key = t._sample(last_logits, recent, key, sp)
        history = list(prompt[0]) + [int(tok[0])]
        yield int(tok[0]), None
        emitted = 1
        offset = n_prompt
        while emitted < max_tokens:
            w = self.tracker.window(0)
            K = w if w > 0 else 1  # disabled: verify-only decode round
            if offset + K > self.max_seq or max_tokens - emitted < 2:
                remaining = max_tokens - emitted

                def dispatch(carry):
                    outs, tk, ch, rc, kk = t._decode_block(
                        t.params, carry[0], carry[1], carry[2], carry[3],
                        sp, False,
                    )
                    return outs, (tk, ch, rc, kk)

                from mlx_sharding_tpu.generate import blocked_token_stream

                yield from blocked_token_stream(
                    dispatch, (tok, cache, recent, key), remaining,
                    t.decode_block, False,
                )
                return

            drafts_np, n_valid = self.proposer.propose(history, K)
            wc = min(K, max(1, n_valid))
            wcap = jnp.asarray([wc], jnp.int32)
            drafts = jnp.asarray(drafts_np[:, None])  # (K, 1)
            if sampled:
                key, kv = jax.random.split(key)
                gs, count, tok, cache, recent = self._sampled_prog(K)(
                    t.params, tok, drafts, wcap, cache, recent, kv, sp
                )
            else:
                gs, count, tok, cache, recent = self._greedy_prog(K)(
                    t.params, tok, drafts, wcap, cache, recent, sp
                )
            n, gs_host = int(count[0]), np.asarray(gs)
            self.rounds += 1
            if w > 0:
                # disabled rounds are plain decode in disguise — counting
                # their single token as "accepted" with zero draft tokens
                # would push accept_rate past 1.0
                self.accepted_tokens += n
                self.draft_tokens += wc
                self.tracker.observe(0, w, n)
            for j in range(n):
                if emitted >= max_tokens:
                    break
                yield int(gs_host[j, 0]), None
                history.append(int(gs_host[j, 0]))
                emitted += 1
            offset += n

    def spec_stats(self) -> dict:
        """CLI/telemetry summary of this stream's speculation outcome."""
        return {
            "mode": "ngram",
            "window_max": self.spec_window_max,
            "rounds": self.rounds,
            "draft_tokens": self.draft_tokens,
            "accepted_tokens": self.accepted_tokens,
            "accept_rate": self.accepted_tokens / max(1, self.draft_tokens),
            **self.tracker.stats(),
        }
