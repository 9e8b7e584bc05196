"""``sdar_moe`` (SDAR-MoE: Qwen3-MoE's decoder generating by diffusion over
blocks of 4) on the served path, against its plain reference
(``benchmarks/reference/sdar_moe.py``) at tiny widths on the CPU, with the
benchmark's seeded weights on both sides.

Sizes: pages and chunks of 8 (two blocks a chunk), two layers, 4 query heads
on 2 K/V heads of 32 merged on 64 lanes, 8 experts, 2 a token, blocks of 4 at 2
denoising steps (2 forwards a block on the served path: a block's commit rides
the next block's first denoise forward, where the published loop and the
reference take 3), mask id 0. Prompts of every ``P mod L``:
0 (the first decode block is all masks), 1, 2 and 3 (it starts with prompt
tokens), and one shorter than a block (no prefill chunk at all).

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so what
separates them is the order of sums: chunks and pages against one pass over
whole rows. Log-probabilities agree to ~4e-6; ``LP_TOL`` = 1e-4 leaves 25
times that and is a hundred times under the SMALLEST of the reference's faults
at these widths (the stale commit: 0.02; the others move it by 0.1 to 3).

The three strategies. ``low_confidence_dynamic`` needs confidences on both
sides of its threshold: on seeded weights at a vocabulary of 256 they lie in
0.018-0.053, so the test's configuration sets ``confidence_threshold`` 0.028,
their median, instead of scaling the head (the reference makes its weights
from the seed alone): both branches of the rule run, some blocks in 2 forwards.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import sdar_moe as ref
from mlx_sharding_tpu import diffusion, tracing
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.ops.paged_attention import _paged_attention_xla, paged_attention
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine, fold_block_queries
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout
from tests.test_afmoe import served  # [(token, {id: log-probability})] of one greedy request

LP_TOL = 1e-4
SEED = 11
PAGE, MAX_SEQ, L = 8, 64, 4
TINY = dict(
    model_type="sdar_moe", vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32, intermediate_size=96,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[], rms_norm_eps=1e-6, rope_theta=1000000,
    rope_scaling=None, tie_word_embeddings=False, use_sliding_window=False,
    hidden_act="silu", attention_bias=False, block_length=L, denoising_steps=2,
    remasking_strategy="sequential", confidence_threshold=0.028, mask_token_id=0,
)
rng = np.random.default_rng(3)
PROMPTS = {  # by P mod L
    0: rng.integers(1, 256, 16).tolist(),  # two chunks, ends on a block AND a page border
    1: rng.integers(1, 256, 13).tolist(),  # chunks of 8 and 4, one prompt token in the block
    2: rng.integers(1, 256, 10).tolist(),
    3: rng.integers(1, 256, 19).tolist(),  # the first decode block crosses no page: 16..19
    "short": rng.integers(1, 256, 3).tolist(),  # no whole block: no chunk is prefilled
}


def seeded_params(cfg: dict, seed: int = SEED):
    """The benchmark's seeded tree for ``cfg``, materialized and widened:
    bf16 VALUES in float32 leaves, so that every served path computes in
    float32 as the reference does."""
    lazy = lambda x: isinstance(x, W.LazyStack)  # noqa: E731
    return jax.tree.map(
        lambda x: (x[:] if lazy(x) else x).astype(jnp.float32),
        ref.program_params(cfg, "bf16", seed), is_leaf=lazy,
    )


def differences(cfg, prompt, got, fault=None):
    """Served minus reference log-probability at the served top ids: each
    generated position at the forward that transferred it, the reference
    rebuilding that forward's input from the served tokens."""
    toks = [t for t, _ in got]
    seq = list(prompt) + toks[:-1]
    rows = [len(prompt) - 1 + j for j in range(len(toks))]
    wanted = [sorted(top)[:8] for _, top in got]
    want = ref.forward(cfg, "bf16", SEED, seq, rows, wanted, fault=fault, pad_to=MAX_SEQ)[2]
    have = np.asarray([[top[i] for i in w] for (_, top), w in zip(got, wanted)])
    return have - want


def make_engine(model, params, *, slots=2, paged=True, **kw):
    return PipelineEngine(
        model, params, make_mesh(pp=1, tp=1, ep=1, devices=jax.devices()[:1]),
        microbatches=slots, max_seq=MAX_SEQ, cache_dtype=jnp.float32,
        prefill_chunk=PAGE, decode_block=4,
        pool_pages=8 * slots if paged else None, page_size=PAGE if paged else None,
        **kw,
    )


@pytest.fixture(scope="module")
def tiny():
    model, _ = build_model(TINY)
    return model, seeded_params(TINY)


def transferred_at(forwards, n_prompt, n):
    """``[(log-probabilities (V,), block, forward of the block)]`` of the
    ``n`` generated positions, each at the forward of ``ref.generate``'s
    published loop that transferred it."""
    out, seen = {}, {}
    for b, _, _, lp, move in forwards:
        step = seen[b] = seen.get(b, -1) + 1
        for i in np.flatnonzero(move):
            out[b * L + int(i) - n_prompt] = (lp[i], b, step)
    return [out[j] for j in range(n)]


@pytest.fixture(scope="module", params=ref.STRATEGIES)
def by_strategy(request, tiny, batcher):
    """``(cfg, batcher)`` for each ``remasking_strategy``: one slot and sync
    ticks (a harvest's counters are then exactly its stream's), but for
    ``sequential``, which is the module's batcher."""
    if request.param == TINY["remasking_strategy"]:
        yield TINY, batcher
        return
    cfg = {**TINY, "remasking_strategy": request.param}
    model, _ = build_model(cfg)
    b = ContinuousBatcher(make_engine(model, tiny[1], slots=1), decode_block=4,
                          async_sched="off")
    yield cfg, b
    b.close()


@pytest.fixture(scope="module")
def sync_batcher(tiny):
    """One slot, sync ticks, blocks of 4 forwards: what a harvest counts is
    what its one stream did."""
    b = ContinuousBatcher(make_engine(*tiny, slots=1), decode_block=4, async_sched="off")
    yield b
    b.close()


@pytest.fixture(scope="module")
def batcher(tiny):
    b = ContinuousBatcher(make_engine(*tiny), decode_block=4)
    assert b.engine.paged_attention == "ragged" and b._async and b._diffusion == L
    yield b
    b.close()


# ------------------------------------------------------------ the model


@hard_timeout(300)
def test_a_chunk_under_the_block_mask_matches_the_reference(tiny):
    """The model's own forward over a prompt's whole blocks: row ``i``'s
    logits under the block-causal mask (a query sees to its block's end)."""
    model, params = tiny
    ids = PROMPTS[0]
    cache = model.make_cache(1, MAX_SEQ, jnp.float32)
    assert cache.k.shape == cache.v.shape == (2, 1, MAX_SEQ, 1, 64)  # two heads of 32 merged
    logits, _ = model(params, jnp.asarray(ids)[None], cache)
    have = np.asarray(jax.nn.log_softmax(logits[0].at[:, 0].set(-jnp.inf), axis=-1))
    h, _ = ref.hidden_states(TINY, "bf16", SEED, ref.plain_rows(ids, TINY))
    wanted = np.argsort(-have, axis=-1)[:, :8]
    want = np.asarray(ref._head(ref.hashable(TINY), 8, W.seed_key(SEED), h[: len(ids)],
                                jnp.asarray(wanted), jnp.asarray(False))[2])
    np.testing.assert_allclose(np.take_along_axis(have, wanted, -1), want, atol=LP_TOL)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_moves_the_reference_far_past_the_tolerance(fault):
    """Plain causal inside a block, a cache that kept the first denoise
    forward's rows, the per-head norms left out, the renormalisation left
    out, fp8 weights: each would fail the comparisons of this file by two
    orders of magnitude — the stale commit, the smallest, among them."""
    prompt, n = PROMPTS[2], 14
    toks = ref.generate(TINY, "bf16", SEED, prompt, n, pad_to=MAX_SEQ)[0]
    seq = prompt + toks[:-1]
    rows, wanted = [len(prompt) - 1 + j for j in range(n)], [list(range(1, 9))] * n
    at = lambda f: ref.forward(  # noqa: E731
        TINY, "bf16", SEED, seq, rows, wanted, fault=f, pad_to=MAX_SEQ)[2]
    moved = np.abs(at(fault) - at(None)).max()
    assert moved > 100 * LP_TOL, (fault, moved)


def test_the_check_s_one_pass_is_the_published_loop():
    """``forward`` (one pass: the clean sequence with every state's rows
    appended) gives, for every generated position, the log-probabilities the
    published loop's own forward gave it when it transferred it."""
    prompt, n = PROMPTS[3], 11  # max_tokens falls inside a block
    toks, forwards = ref.generate(TINY, "bf16", SEED, prompt, n, pad_to=MAX_SEQ)
    assert len(toks) == n and len(forwards) == 7  # 1 + 2 + 2 + 2 denoise forwards
    seq, rows = prompt + toks[:-1], [len(prompt) - 1 + j for j in range(n)]
    wanted = [list(range(1, 9))] * n
    got = ref.forward(TINY, "bf16", SEED, seq, rows, wanted, pad_to=MAX_SEQ)[2]
    seen = 0
    for b, ids, masked, lp, move in forwards:
        for i in np.flatnonzero(move):
            j = b * L + int(i) - len(prompt)
            if j < n:
                np.testing.assert_allclose(got[j], lp[i, 1:9], atol=LP_TOL)
                seen += 1
    assert seen == n


def test_the_rule_that_transfers():
    """``diffusion.unmask`` against the reference's ``transfer`` on every
    mask of 4 and random confidences, all three strategies at n = 1 and 2."""
    rs = np.random.default_rng(5)
    masks = np.asarray([[bool(m >> i & 1) for i in range(L)] for m in range(1, 16)])
    conf = rs.uniform(0, 1, masks.shape).astype(np.float32)
    for strategy in ref.STRATEGIES:
        for n in (1, 2):
            have, by_conf = diffusion.unmask(
                jnp.asarray(masks), jnp.asarray(conf), strategy=strategy, n=n, tau=0.5)
            for row, (m, c) in enumerate(zip(masks, conf)):
                want = ref.transfer(m, c, strategy, n, 0.5)
                # (fewer masked than n: the rule moves what there is)
                np.testing.assert_array_equal(np.asarray(have[row]), want, err_msg=strategy)
                high = int((m & (c > 0.5)).sum())
                assert bool(by_conf[row]) == (
                    strategy == "low_confidence_dynamic" and high >= n)


# ------------------------------------------------------ the served path


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_prefill_denoise_and_commit_through_the_pages_match_the_reference(batcher, name):
    """The prompt's whole blocks prefilled in chunks under the block mask,
    the first decode block ``[prompt tail | masks]``, then a wide and a narrow
    forward a block through the page pool (each writing its rows, the
    commit's kept): the tokens are the published loop's, and each position's
    log-probabilities those of the forward that transferred it. 14 tokens:
    ``max_tokens`` falls inside a block for every ``P mod L`` but 2, and the
    tail is dropped."""
    prompt = PROMPTS[name]
    dropped0 = batcher.tick_phase_stats()["tokens_dropped"]["slot_finished"]
    got = served(batcher, prompt, 14)
    assert [t for t, _ in got] == ref.generate(
        TINY, "bf16", SEED, prompt, 14, pad_to=MAX_SEQ)[0]
    assert all(0 not in top for _, top in got)  # the mask id is never offered
    np.testing.assert_allclose(differences(TINY, prompt, got), 0, atol=LP_TOL)
    # what the last block held past max_tokens was computed and dropped
    tail = -(len(prompt) + 14) % L
    dropped = batcher.tick_phase_stats()["tokens_dropped"]["slot_finished"] - dropped0
    assert dropped >= tail and (tail == 0 or dropped > 0)


@hard_timeout(900)
@pytest.mark.parametrize("fault", ["block_mask_causal", "commit_stale_kv", "qk_norm_off",
                                   "moe_no_renorm"])
def test_the_served_path_with_a_fault_is_not_the_reference(batcher, fault):
    """The block mask, the commit's K/V, the per-head norms and the
    renormalisation: the served path is fifty tolerances from each wrong
    variant."""
    got = served(batcher, PROMPTS[1], 12)
    assert np.abs(differences(TINY, PROMPTS[1], got, fault)).max() > 50 * LP_TOL


@hard_timeout(900)
def test_a_prompt_that_contains_the_mask_id_is_served_right(batcher):
    """Masked is a boolean beside the ids: a prompt token equal to the mask
    id, in a prefilled block and in the first decode block's tail, is a
    token like any other."""
    prompt = list(PROMPTS[2])
    prompt[3], prompt[-1] = 0, 0
    got = served(batcher, prompt, 8)
    assert [t for t, _ in got] == ref.generate(TINY, "bf16", SEED, prompt, 8, pad_to=MAX_SEQ)[0]
    np.testing.assert_allclose(differences(TINY, prompt, got), 0, atol=LP_TOL)


@hard_timeout(900)
def test_slots_at_different_phases_share_a_forward(batcher):
    """Three requests on two slots with prompts of different ``P mod L``
    (first blocks of 2 and 1 forwards: slots a forward out of phase until the
    one ahead stands still for a narrow forward) and lengths: one slot's
    commit and next denoise share a wide forward with the other's first
    denoise, the third joins while another decodes and takes a left slot —
    with the block its last stream never had stored still pending there; each
    against the reference, LOGITS not tokens. The counters add up and
    ``/metrics`` shows them."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    jobs = {1: 9, 0: 14, 3: 11}
    s0 = batcher.tick_phase_stats()
    outs: dict = {}

    def run(name, n):
        try:
            outs[name] = served(batcher, PROMPTS[name], n)
        except Exception as e:  # noqa: BLE001 — surfaced below
            outs[name] = e

    threads = [threading.Thread(target=run, args=job, daemon=True) for job in jobs.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not any(t.is_alive() for t in threads), "generation thread hung"
    for name, n in jobs.items():
        assert not isinstance(outs[name], Exception), outs[name]
        assert len(outs[name]) == n
        np.testing.assert_allclose(
            differences(TINY, PROMPTS[name], outs[name]), 0, atol=LP_TOL, err_msg=str(name))
    s1 = batcher.tick_phase_stats()
    d = {k: s1["diffusion"][k] - s0["diffusion"][k] for k in s1["diffusion"]}
    # blocks handed on: ceil((P mod L + n) / L) a request
    assert d["blocks_committed"] >= sum(-(-(len(PROMPTS[k]) % L + n) // L) for k, n in jobs.items())
    assert d["by_confidence"] == 0 and d["by_rank"] >= sum(jobs.values())
    assert d["slot_forwards"] >= 2 * d["blocks_committed"]
    # every position a harvested forward computed was emitted or dropped
    assert s1["positions_computed"] - s0["positions_computed"] >= (
        s1["tokens_emitted"] - s0["tokens_emitted"]
        + sum(s1["tokens_dropped"][k] - s0["tokens_dropped"][k] for k in s1["tokens_dropped"]))
    assert s1["tokens_emitted"] - s0["tokens_emitted"] == sum(jobs.values())
    text = ServingMetrics(batcher_fn=lambda: batcher).render()
    for line in ("mst_diffusion_slot_forwards_total ", "mst_diffusion_blocks_committed_total ",
                 'mst_diffusion_tokens_transferred_total{by="rank"}',
                 'mst_diffusion_tokens_transferred_total{by="confidence"} 0',
                 'mst_decode_tokens_dropped_total{reason="denoise"}'):
        assert line in text, line


@hard_timeout(900)
@pytest.mark.parametrize("name", [0, 1, 3])
def test_every_strategy_is_the_published_loop_token_and_log_probability(by_strategy, name):
    """All three strategies at ``P mod L`` of 0, 1 and 3 (first blocks of 2,
    2 and 1 forwards: the last stands still for the narrow forward behind it)
    against the plain loop, ``want_lp`` on, 14 tokens (the stream ends inside
    a block): the same tokens, and every position's served top
    log-probabilities are the loop's at the forward that transferred it — the
    confidence orders too, which the check's one pass cannot replay."""
    cfg, b = by_strategy
    prompt, n = PROMPTS[name], 14
    got = served(b, prompt, n)
    want, forwards = ref.generate(cfg, "bf16", SEED, prompt, n, pad_to=MAX_SEQ)
    assert [t for t, _ in got] == want
    for (tok, top), (lp, _, _) in zip(got, transferred_at(forwards, len(prompt), n)):
        assert 0 not in top and tok == max(top, key=top.get)
        np.testing.assert_allclose(
            [top[i] for i in top], [lp[i] for i in top], atol=LP_TOL)


@hard_timeout(900)
@pytest.mark.parametrize("strategy", ["low_confidence_static", "low_confidence_dynamic"])
def test_the_confidence_orders_move_what_the_published_loop_moves(tiny, strategy):
    """The two strategies the check cannot replay, against the plain loop:
    the same tokens, and the same positions moved by rank and by passing the
    threshold (set at the confidences' median: module docstring). A block
    that passed the threshold whole took one forward in the loop; here its
    slot then stands still for the narrow forward behind it."""
    cfg = {**TINY, "remasking_strategy": strategy}
    model, _ = build_model(cfg)
    b = ContinuousBatcher(make_engine(model, tiny[1], slots=1), decode_block=4)
    try:
        prompt, n = PROMPTS[1], 16
        got = [t for t, _ in b.generate_step(prompt, max_tokens=n)]
        stats = b.tick_phase_stats()["diffusion"]
    finally:
        b.close()
    want, forwards = ref.generate(cfg, "bf16", SEED, prompt, n, pad_to=MAX_SEQ)
    assert got == want
    tau, n_t = cfg["confidence_threshold"], 2
    by_conf = by_rank = 0
    for _, _, masked, lp, move in forwards:
        high = sum(m and float(np.exp(lp[i].max())) > tau for i, m in enumerate(masked))
        if strategy == "low_confidence_dynamic" and high >= n_t:
            by_conf += int(move.sum())
        else:
            by_rank += int(move.sum())
    # the last block may be cut by max_tokens on the host; the device ran it whole
    assert stats["by_confidence"] >= by_conf and stats["by_rank"] >= by_rank
    if strategy == "low_confidence_dynamic":
        assert by_conf > 0 and by_rank > 0, "the threshold splits nothing"
        assert len(forwards) < 2 * -(-(len(prompt) % L + n) // L)  # some block took one forward
    else:
        assert stats["by_confidence"] == 0


def _stream(b, prompt, n):
    """``(tokens, what the stream added to the batcher's diffusion counters,
    the slot's offset behind it)`` of one stream on a sync batcher of one
    slot."""
    s0 = b.tick_phase_stats()["diffusion"]
    toks = [t for t, _ in b.generate_step(prompt, max_tokens=n)]
    s1 = b.tick_phase_stats()["diffusion"]
    return toks, {k: s1[k] - s0[k] for k in s1}, int(b.cache.offset[0])


# (P mod L, tokens) -> (programs of 4 forwards, blocks handed on, blocks whose
# K/V a forward stored before the stream's last harvest)
COUNTED = {
    # [d1 | d2 b0 | commit b0, d1 b1 | d2 b1]: 8 tokens out of ONE program, where the
    # three-forward loop hands on b0 at its 3rd forward and b1 at its 6th; b1 is never stored
    "two-blocks-one-program": (0, 8, 1, 2, 1),
    # 4 blocks, 8 forwards: two slot-forwards a block
    "two-forwards-a-block": (0, 16, 2, 4, 3),
    # a first block of one forward: [d b0 | stands still | commit b0, d1 b1 | d2 b1]
    "a-first-block-of-one-forward-waits": (3, 5, 1, 2, 1),
    # the stream ends inside its fourth block, at its second program's last forward
    "ends-inside-a-block": (1, 13, 2, 4, 3),
    # ... and inside its third, at the second forward of a program: the device runs the
    # slot on to the harvest, and the wide forward behind stores a block nobody reads
    "runs-on-to-its-harvest": (1, 9, 2, 3, 3),
}


@hard_timeout(900)
@pytest.mark.parametrize("case", list(COUNTED))
def test_the_counters_of_one_stream(sync_batcher, case):
    """Slot-forwards a block 2 under ``sequential``, a block's tokens handed
    on at the forward that transferred its last masked position (one forward
    earlier than the commit that used to emit them), and the stream's LAST
    block never stored where the stream ends with its program: the slot's
    offset stops in front of it, and every token arrived all the same."""
    name, n, programs, blocks, stored = COUNTED[case]
    prompt = PROMPTS[name]
    toks, d, offset = _stream(sync_batcher, prompt, n)
    assert toks == ref.generate(TINY, "bf16", SEED, prompt, n, pad_to=MAX_SEQ)[0]
    assert d["slot_forwards"] == 4 * programs and d["blocks_committed"] == blocks
    assert offset == len(prompt) // L * L + stored * L
    assert d["by_rank"] >= n and d["by_confidence"] == 0


@hard_timeout(900)
def test_later_blocks_read_the_committed_rows(sync_batcher, tiny):
    """What a wide forward's first lane stores: after a stream of five blocks
    the slot's pages hold, for every block whose K/V was stored, the rows the
    model's own forward over the CLEAN sequence writes (final ids under the
    block mask) — not a denoise forward's (the fault ``commit_stale_kv``
    stands for: rows computed from ids still masked), and nothing of the
    block behind, whose denoise rows rode the same forward."""
    model, params = tiny
    prompt, n = PROMPTS[2], 18  # P mod L = 2: blocks at 8, 12, 16, 20, 24
    toks, _, offset = _stream(sync_batcher, prompt, n)
    assert offset in (24, 28)  # the four blocks in front of the last, or all five
    table = np.asarray(sync_batcher.table[0])  # (a slot's row outlives its release)
    clean = (prompt + toks)[:offset]
    _, cache = model(params, jnp.asarray(clean)[None], model.make_cache(1, MAX_SEQ, jnp.float32))
    stale = (prompt + [0] * n)[:offset]  # ids still masked, as a first denoise forward has them
    _, wrong = model(params, jnp.asarray(stale)[None], model.make_cache(1, MAX_SEQ, jnp.float32))
    for have, want, other in ((sync_batcher.cache.k, cache.k, wrong.k),
                              (sync_batcher.cache.v, cache.v, wrong.v)):
        pool = np.asarray(have)[0]  # (layers, pages + 1, 1, page, 1, 64)
        rows = pool[:, table[: -(-offset // PAGE)], 0].reshape(pool.shape[0], -1, 64)[:, :offset]
        np.testing.assert_allclose(rows, np.asarray(want)[:, 0, :offset, 0], atol=1e-5)
        assert np.abs(rows - np.asarray(other)[:, 0, :offset, 0]).max() > 1e-2


@hard_timeout(900)
def test_a_stream_that_fills_max_seq_ends_right(sync_batcher):
    """``prompt + max_tokens == max_seq``: the wide forward that commits the
    last-but-one block writes the last block's rows into the slot's last
    page, and the one behind it (the device runs a finished stream's slot
    until its harvest) has no page for its second lane: those rows go to the
    scratch page, and the stream is the published loop's to its last token."""
    prompt, n = PROMPTS[0], MAX_SEQ - len(PROMPTS[0])
    toks, d, _ = _stream(sync_batcher, prompt, n)
    assert toks == ref.generate(TINY, "bf16", SEED, prompt, n, pad_to=MAX_SEQ)[0]
    assert d["blocks_committed"] == n // L and d["slot_forwards"] == 2 * n // L


@hard_timeout(600)
def test_sampled_rows_draw_under_their_own_keys_and_never_the_mask(batcher):
    """Temperature and top-p go through ``sample.py``'s per-row transforms: a
    seed reproduces its stream, another seed gives another, no position is
    ever given the mask id."""
    kw = dict(max_tokens=12, temperature=1.5, top_p=0.9)
    a = [t for t, _ in batcher.generate_step(PROMPTS[2], seed=5, **kw)]
    b = [t for t, _ in batcher.generate_step(PROMPTS[2], seed=5, **kw)]
    c = [t for t, _ in batcher.generate_step(PROMPTS[2], seed=6, **kw)]
    assert a == b and a != c and 0 not in a + c and len(a) == 12


@hard_timeout(600)
def test_a_request_s_trace_shows_its_denoise_spans(batcher):
    """One ``denoise`` span a harvested program, with its forwards and the
    blocks they finished for the request; TTFT is the first block's last
    transfer. The first program of 4 forwards hands on TWO blocks (its 2nd and
    4th forward; the three-forward loop's handed on one, at its 3rd)."""
    tracer = tracing.configure("on", buffer=8)
    try:
        tr = tracing.begin("sdar-1")
        n = len(list(batcher.generate_step(PROMPTS[1], max_tokens=10, _trace=tr)))
        tracing.finish(tr)
        frozen = tracer.get("sdar-1")
    finally:
        tracing.configure("off")
    assert n == 10
    spans = [s for s in frozen["spans"] if s[0] == "denoise"]
    assert spans and all(s[3]["forwards"] == 4 for s in spans)
    assert sum(s[3]["blocks"] for s in spans) == 3  # ceil((1 + 10) / 4) blocks handed on
    assert spans[0][3]["blocks"] == 2
    assert "decode_tick" not in {s[0] for s in frozen["spans"]}
    first = next(t for name, t, _ in frozen["marks"] if name == "first_token")
    assert spans[0][1] <= first  # stamped while the first program is emitted


# ------------------------------------------------ kernel, share, refusals


@pytest.mark.parametrize("lengths", [[8, 20, 0, 44], [4, 4, 64, 12]])
@pytest.mark.parametrize("per_lane,lanes", [(4, 1), (2, 2), (4, 2)],
                         ids=["group-32", "group-32-two-lengths", "group-64-two-lengths"])
def test_the_kernel_at_a_folded_group_of_32_is_the_xla_path(lengths, per_lane, lanes):
    """``ops/paged_attention.py``'s kernel in interpret mode with a block's 4
    queries folded into the query group (4 x 8 = 32 a K/V head, 4 K/V heads
    merged on the lanes) against ``_paged_attention_xla`` a query at a time:
    every query of a slot sees the same keys, so no mask is new. With two
    lanes (two blocks of 2, or of 4: a group of 64) the fold puts lane 1 in
    the group's leading rows, which see a block less (``lead_lengths``): each
    query against the XLA path under its own lane's length."""
    rs = np.random.default_rng(7)
    m, hq, hkv, d, page, spg = 4, 32, 4, 16, 8, 8
    t = per_lane * lanes
    q = jnp.asarray(rs.normal(size=(m, t, hq, d)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(m * spg + 1, page, 1, hkv * d)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(m * spg + 1, page, 1, hkv * d)), jnp.float32)
    tables = jnp.asarray(rs.permutation(m * spg).reshape(m, spg), jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    lead = jnp.maximum(lens - per_lane, 0)  # lane 1 sees a block less
    two = {} if lanes == 1 else dict(lead_lengths=lead, lead_rows=per_lane * hq // hkv)
    have = fold_block_queries(
        lambda q1: paged_attention(q1, k, v, tables, lens, d ** -0.5, kv_heads=hkv,
                                   interpret=True, **two), q, hkv)
    assert have.shape == (m, t, hq, d)
    for i in range(t):
        seen = lead if lanes == 2 and i < per_lane else lens
        want = _paged_attention_xla(q[:, i], k, v, tables, seen, d ** -0.5, None, None,
                                    None, kv_heads=hkv)
        np.testing.assert_allclose(have[:, i], want, atol=2e-5)


def test_sixteen_holders_parts_add_up_to_the_uncut_layer(tiny):
    """A layer that holds a sixteenth of the experts routes over all of them
    and computes its own experts' part: the sixteen parts sum to the whole
    layer's mixture (the reference's, uncut), the router and the attention
    whole in each."""
    whole = {**TINY, "num_experts": 16, "num_hidden_layers": 1}
    units = ref.model_units(whole)["layers"]
    skey, r0 = W.seed_key(SEED), jnp.asarray(0, jnp.int32)
    lin = ref._lin(units, skey, r0, jnp.asarray(False))
    u = jnp.asarray(np.random.default_rng(9).normal(size=(12, 64)), jnp.float32)
    want = ref._moe(whole, lin, u, jnp.asarray(False))
    from mlx_sharding_tpu.ops.moe import apply_experts, mixtral_routing

    router = W.dense_logical(skey, units["router"], r0)
    weights, idx = mixtral_routing(u, router, 2)
    stack = lambda name: jnp.stack([  # noqa: E731
        W.dense_logical(skey, units[name], r0, e) for e in range(16)])
    total = jnp.zeros_like(u)
    for holder in range(16):
        part = lambda name: stack(name)[holder:holder + 1]  # noqa: E731
        total += apply_experts(u, weights, idx, part("w_gate"), part("w_up"),
                               part("w_down"), expert_base=holder)
    np.testing.assert_allclose(total, want, atol=1e-5)


def _published():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    path = Path(__file__).resolve().parents[1] / "benchmarks/configs/sdar-30b-a3b-bf16-ep16.json"
    return published_config(json.loads(path.read_text()))


def test_the_cut_s_arithmetic():
    """ISSUE 55's numbers from ``model_units``: 56.88 M parameters a layer at
    8 held experts, 5.62 GB of weights over 48 layers and an eighth of the
    vocabulary, 98,304 B of K/V a token; and a forward's bytes: weights
    outside the experts once, the 8 held experts the 128 rows hit, the K/V
    under a key of its own and NOT in ``total``."""
    from benchmarks.bytes_model import unit_bytes

    cfg = _published()
    units = ref.model_units(cfg)
    per_layer = sum(u.out * max(u.inn, 1) * max(u.experts, 1) for u in units["layers"].values())
    attn = 2048 * 4096 * 2 + 2048 * 512 * 2
    assert per_layer == attn + 2048 * 128 + 8 * 3 * 2048 * 768 + 2 * 2048 + 2 * 128
    assert round(per_layer / 1e6, 2) == 56.89
    weights = 48 * sum(unit_bytes(u, "bf16") * max(u.experts, 1) for u in units["layers"].values())
    weights += 2 * 2 * 19072 * 2048 + 2 * 2048
    assert round(weights / 1e9, 2) == 5.62
    assert 48 * ref.kv_row_bytes(cfg) == 98304
    need = ref.decode_step_bytes(cfg, "bf16", 32, 32 * 1024)
    assert need["total"] == need["fixed_weights"] + need["routed_experts"]
    assert 5.3e9 < need["total"] < 5.6e9  # all 8 held experts are hit at 128 rows
    assert need["kv_pages"] == ref.paged_attn_step_bytes(cfg, 32, 1024) == 32 * 48 * 1028 * 2048


def test_the_seeded_tree_is_the_program_s(tiny):
    model, params = tiny
    mine = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), jnp.float32))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(lambda x: x.shape, mine)


def test_map_weights_reads_the_held_experts_by_their_published_names(tiny):
    """Qwen3-MoE's tensor names; a config with a share loads experts ``base
    .. base + held`` and the router whole."""
    cfg = {**TINY, "num_experts": 2, "moe_expert_share": 4, "moe_expert_share_index": 1,
           "num_hidden_layers": 1}
    model, _ = build_model(cfg)
    rs = np.random.default_rng(2)
    t = lambda *s: rs.normal(size=s).astype(np.float32)  # noqa: E731
    hf = {
        "model.embed_tokens.weight": t(256, 64), "model.norm.weight": t(64),
        "lm_head.weight": t(256, 64),
        "model.layers.0.input_layernorm.weight": t(64),
        "model.layers.0.post_attention_layernorm.weight": t(64),
        "model.layers.0.self_attn.q_proj.weight": t(128, 64),
        "model.layers.0.self_attn.k_proj.weight": t(64, 64),
        "model.layers.0.self_attn.v_proj.weight": t(64, 64),
        "model.layers.0.self_attn.o_proj.weight": t(64, 128),
        "model.layers.0.self_attn.q_norm.weight": t(32),
        "model.layers.0.self_attn.k_norm.weight": t(32),
        "model.layers.0.mlp.gate.weight": t(8, 64),
    }
    for e in range(8):
        for which, shape in (("gate_proj", (32, 64)), ("up_proj", (32, 64)), ("down_proj", (64, 32))):
            hf[f"model.layers.0.mlp.experts.{e}.{which}.weight"] = t(*shape)
    params = model.map_weights(hf, jnp.float32)
    lay = params["layers"]
    assert lay["router"].shape == (1, 64, 8) and lay["w_gate"].shape == (1, 2, 64, 32)
    np.testing.assert_array_equal(
        lay["w_down"][0, 1], hf["model.layers.0.mlp.experts.3.down_proj.weight"].T)
    np.testing.assert_array_equal(
        lay["q_proj"][0], hf["model.layers.0.self_attn.q_proj.weight"].T)
    np.testing.assert_array_equal(
        params["lm_head"]["weight"][:, :256], hf["lm_head.weight"].T)


REFUSED = {
    "--prompt-cache": lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_cache=True),
    "--prefix-store": lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_store=object()),
    "--spill-bytes": lambda m, p: ContinuousBatcher(make_engine(m, p), spill_bytes=1 << 20),
    "--overcommit": lambda m, p: ContinuousBatcher(make_engine(m, p), overcommit=True),
    "--draft": lambda m, p: ContinuousBatcher(make_engine(m, p), draft="ngram"),
    "--kv-share-map": lambda m, p: make_engine(m, p, kv_share_map=object()),
    "--kv-compress-map": lambda m, p: make_engine(m, p, kv_compress_map=object()),
    "--paged-pool": lambda m, p: make_engine(m, p, paged=False),
    "--disagg": lambda m, p: next(ContinuousBatcher(make_engine(m, p)).generate_step(
        [1, 2, 3], max_tokens=2, _prefill_only=True)),
    diffusion.SINGLE_STREAM: lambda m, p: Generator(m, p, max_seq=MAX_SEQ),
}


@pytest.mark.parametrize("flag", list(REFUSED))
def test_what_assumes_a_token_a_step_is_refused_by_name(tiny, flag):
    with pytest.raises(ValueError) as e:
        REFUSED[flag](*tiny)
    assert flag in str(e.value) and "sdar_moe" in str(e.value)
    assert "diffusion over blocks" in str(e.value)


@pytest.mark.parametrize("kw,flag", [({"pp": 2}, "--num-stages"), ({"tp": 2}, "--tp"),
                                     ({"ep": 2}, "--ep")])
def test_other_layouts_refuse_by_name(tiny, kw, flag):
    model, params = tiny
    mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"{flag} cannot serve SdarMoeModel"):
        PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)


def test_unwired_configurations_refuse_by_name():
    from mlx_sharding_tpu.config import config_from_dict

    for key, val in (("remasking_strategy", "random"), ("denoising_steps", 3),
                     ("block_length", 6), ("mask_token_id", 256), ("norm_topk_prob", False),
                     ("mlp_only_layers", [1]), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            config_from_dict({**TINY, key: val})
    assert set(diffusion.REFUSED) >= set(REFUSED)
