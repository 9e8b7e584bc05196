"""``qwen3_next`` (Qwen3-Next: a gated delta rule under one decay a head or
gated attention, then 512-way softmax-routed experts beside a gated shared
expert, in every block) on the served path, against its plain reference
(``benchmarks/reference/qwen3_next.py``) at tiny widths on the CPU, with the
benchmark's seeded weights on both sides.

Sizes: pages and chunks of 8; three periods of ``G G G A`` (a run of three
linear layers in an inner scan, then the attention layer); 2 key heads under
4 value heads of 16 behind 4 taps; 4 query heads on 2 K/V heads of 32, the
rotary on their first 8 channels; 8 experts, 2 a token. Prompts end inside a
chunk, one row into a chunk (the convolution's three taps behind it lie in
the chunk before) and on a chunk border.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so what
separates them is the order of sums: chunks and the chunked (WY) form against
one position at a time, pages against whole rows. Log-probabilities agree to
~5e-5; ``LP_TOL`` = 4e-4 leaves several times that and is a thousand times
under the SMALLEST of the reference's faults (the state rounded to bfloat16:
0.6; the others move it by 1.5 to 4.6).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import qwen3_next as ref
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.models.base import LayerRow
from mlx_sharding_tpu.models.qwen3_next import regroup_by_key_head
from mlx_sharding_tpu.ops import kda
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout
from tests.test_afmoe import served  # [(token, {id: log-probability})] of one greedy request

LP_TOL = 4e-4
SEED = 11
PAGE, MAX_SEQ = 8, 64
TINY = dict(
    model_type="qwen3_next", vocab_size=256, hidden_size=64, num_hidden_layers=12,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32, intermediate_size=96,
    partial_rotary_factor=0.25, full_attention_interval=4, linear_conv_kernel_dim=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
    rms_norm_eps=1e-6, rope_theta=10000000, rope_scaling=None,
    tie_word_embeddings=False, use_sliding_window=False, hidden_act="silu",
)
rng = np.random.default_rng(3)
PROMPTS = {
    "inside": rng.integers(1, 256, 13).tolist(),  # chunks of 8 and 5
    "one-past": rng.integers(1, 256, 17).tolist(),  # 8, 8 and ONE row: every tap behind a border
    "border": rng.integers(1, 256, 24).tolist(),  # ends on a border: decode starts on one
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


def reference_at(cfg, ids, rows, wanted, fault=None, seed=SEED):
    return ref.forward(cfg, "bf16", seed, ids, rows, wanted, fault=fault)[2]


def differences(cfg, prompt, got, fault=None):
    """Served minus reference log-probability at the served top ids, the
    reference teacher-forced on the served tokens."""
    toks = [t for t, _ in got]
    seq = list(prompt) + toks[:-1]
    rows = [len(prompt) - 1 + j for j in range(len(toks))]
    wanted = [sorted(top)[:8] for _, top in got]
    want = reference_at(cfg, seq, rows, wanted, fault)
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


@pytest.fixture(scope="module")
def batcher(tiny):
    b = ContinuousBatcher(make_engine(*tiny), decode_block=4)
    assert b.engine.paged_attention == "ragged" and b._async
    yield b
    b.close()


# ------------------------------------------------------------ the model


@hard_timeout(300)
def test_full_forward_matches_the_reference(tiny):
    model, params = tiny
    assert model.walk == ([], ["gdn", "gdn", "gdn", "attn"], 3, [])
    ids = PROMPTS["border"]
    cache = model.make_cache(1, MAX_SEQ, jnp.float32)
    # a K/V row's two heads of 32 merged on the lane axis
    assert cache.k.shape == cache.v.shape == (3, 1, MAX_SEQ, 1, 64)
    assert {k: (v.shape, v.dtype) for k, v in cache.state.items()} == {
        "gdn": ((9, 1, 4, 16, 16), jnp.float32), "conv": ((9, 1, 3 * 128), jnp.float32),
    }
    logits, _ = model(params, jnp.asarray(ids)[None], cache)
    have = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    rows = list(range(len(ids)))
    wanted = np.argsort(-have, axis=-1)[:, :8]
    want = reference_at(TINY, ids, rows, wanted)
    np.testing.assert_allclose(np.take_along_axis(have, wanted, -1), want, atol=LP_TOL)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_moves_the_reference_far_past_the_tolerance(fault):
    """A lost state, a dropped decay, bf16 state, rotary over every channel,
    either sigmoid gate left out, the renormalisation left out, the gate read
    from the wrong half of ``q_proj``, a zero-centred norm taken plain, the
    plain gated norm taken zero-centred, fp8 weights: each would fail the
    comparisons of this file by three orders of magnitude —
    ``gdn_state_bf16``, the smallest, among them."""
    ids = PROMPTS["border"]
    rows, wanted = list(range(8, len(ids))), [list(range(1, 9))] * (len(ids) - 8)
    clean = reference_at(TINY, ids, rows, wanted)
    moved = np.abs(reference_at(TINY, ids, rows, wanted, fault) - clean).max()
    assert moved > 1000 * LP_TOL, (fault, moved)


# --------------------------------------------------- the recurrence's forms


def _gdn_inputs(b, t, hk, hv, d, a_max, seed=0):
    """``hk`` key heads under ``hv`` value heads, one decay a VALUE head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    spread = lambda x: jnp.repeat(x, hv // hk, axis=2)  # noqa: E731
    q = spread(kda._l2norm(jax.random.normal(ks[0], (b, t, hk, d)))) * d**-0.5
    k = spread(kda._l2norm(jax.random.normal(ks[1], (b, t, hk, d))))
    v = jax.random.normal(ks[2], (b, t, hv, d))
    # head 0 decays at exp(A_log) = a_max, the strongest; the last hardly
    g = -jnp.linspace(a_max, 0.01, hv) * jax.nn.softplus(jax.random.normal(ks[3], (b, t, hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, hv)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, hv, d, d))


def test_the_scalar_decay_chunked_form_is_the_sequential_recurrence():
    """Three blocks of 64 and a ragged one of 8, ``exp(A_log)`` = 16 on head
    0 (``exp(-G)`` alone would overflow float32 inside the first block), 2 key
    heads under 4 value heads: finite, and the numbers of the sequential form
    run on ``g`` BROADCAST over the key channels (the per-channel definition)
    to 2e-5 — float32 sums in another order; outputs are O(1). The
    per-channel chunked form on the broadcast decay gives them too."""
    q, k, v, g, beta, s0 = _gdn_inputs(2, 200, 2, 4, 32, 16.0)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    o_seq, s_seq = kda.kda_sequential(q, k, v, wide, beta, s0)
    for decay in (g, wide):
        o, s = jax.jit(kda.kda_chunked)(q, k, v, decay, beta, s0)
        assert np.isfinite(o).all() and np.isfinite(s).all()
        np.testing.assert_allclose(o, o_seq, atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(s, s_seq, atol=2e-5, rtol=2e-5)
    o, s = kda.kda_sequential(q, k, v, g, beta, s0)  # the definition takes either rank
    np.testing.assert_array_equal(o, o_seq)
    np.testing.assert_array_equal(s, s_seq)


def test_a_scalar_decay_chunk_builds_no_pairwise_channel_array():
    """What the issue asks of the chunked form: with one decay a head no
    ``(C, C, Dk)`` array is built (the per-channel form builds one a head);
    the pairwise sums are matrix products."""
    q, k, v, g, beta, s0 = _gdn_inputs(1, 128, 2, 4, 32, 4.0)

    def biggest(decay):
        jaxpr = jax.make_jaxpr(kda.kda_chunked)(q, k, v, decay, beta, s0)
        seen = []

        def walk(j):
            for eqn in j.eqns:
                seen.extend(v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape"))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        return [s for s in seen if s[-3:] == (kda.CHUNK, kda.CHUNK, 32)]

    assert not biggest(g)
    assert biggest(jnp.broadcast_to(g[..., None], k.shape))


def _mixer_args(tiny, t, b=2, seed=4):
    model, params = tiny
    p = LayerRow(params["layers"]["gdn"], 1)
    u = jax.random.normal(jax.random.PRNGKey(seed), (b, t, 64), jnp.float32)
    pool = jax.random.normal(jax.random.PRNGKey(seed + 1), (3, b + 1, 4, 16, 16))
    tail = jax.random.normal(jax.random.PRNGKey(seed + 2), (b, 3, model.conv_dim))
    kw = dict(key_heads=2, value_heads=4, head_dim=16, taps=4, eps=1e-6)
    return model, p, u, pool, tail, kw


def test_rows_past_n_valid_and_inactive_slots_advance_nothing(tiny):
    """A chunk of 16 of which 13 rows are valid, the second sequence inactive:
    the first sequence's state, tail and valid outputs are those of the 13
    rows alone; the second's state and tail stay; no other layer's rows and
    not the scratch row move."""
    model, p, u, pool, tail, kw = _mixer_args(tiny, 16)
    linear = model._linear
    out, new, new_tail = kda.gdn_mixer(
        linear, p, u, pool, jnp.asarray(1), tail, jnp.asarray(13),
        jnp.asarray([True, False]), **kw)
    want, want_pool, want_tail = kda.gdn_mixer(
        linear, p, u[:1, :13], pool, jnp.asarray(1), tail[:1], None, None, **kw)
    np.testing.assert_allclose(out[0, :13], want[0], atol=1e-5)
    np.testing.assert_allclose(new[1, 0], want_pool[1, 0], atol=1e-5)
    np.testing.assert_allclose(new_tail[0], want_tail[0], atol=1e-6)
    np.testing.assert_array_equal(new[1, 1:], pool[1, 1:])
    np.testing.assert_array_equal(new_tail[1], tail[1])
    np.testing.assert_array_equal(new[jnp.asarray([0, 2])], pool[jnp.asarray([0, 2])])


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_the_one_step_form_at_16_key_heads_under_32_value_heads(interpret):
    """The published head counts and sizes: layer 1 of a pool of two, two
    sequences and a scratch row, ``g (B, 32)`` one decay a head. The active
    sequence gets the sequential form's step (float32, 1e-6), the inactive
    one keeps its state, and nothing else of the pool moves."""
    q, k, v, g, beta, _ = _gdn_inputs(2, 1, 16, 32, 128, 16.0, seed=1)
    pool = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 32, 128, 128))
    before = kda.dispatch_counts()
    o, new = kda.kda_step(
        pool, jnp.asarray(1), q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
        jnp.asarray([True, False]), interpret,
    )
    after = kda.dispatch_counts()
    path = "kernel" if interpret else "xla"
    assert after[path] == before[path] + 1
    o_seq, s_seq = kda.kda_sequential(q, k, v, g, beta, pool[1, :2])
    np.testing.assert_allclose(o[0], o_seq[0, 0], atol=1e-6)
    np.testing.assert_allclose(new[1, 0], s_seq[0], atol=1e-6)
    np.testing.assert_array_equal(new[1, 1:], pool[1, 1:])
    np.testing.assert_array_equal(new[0], pool[0])


# ------------------------------------------------------------ the share


def test_the_four_quarters_routed_parts_and_the_shared_expert_once_add_up(tiny):
    """Four holders of two experts each route over all eight and compute
    their own experts' part: the four parts and the gated shared expert,
    counted once, are the uncut reference's expert layer (float32, 1e-5)."""
    model, params = tiny
    rank = 2
    stacks = params["layers"]["gdn"]
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 64), jnp.float32)
    p = LayerRow(stacks, rank)
    gate = jax.nn.sigmoid(u @ p["shared_expert_gate"])[..., None]
    shared = gate * model._swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    total = shared
    for i in range(4):
        holder, _ = build_model(
            {**TINY, "num_experts": 2, "moe_expert_share": 4, "moe_expert_share_index": i})
        held = {**stacks, **{n: stacks[n][:, 2 * i : 2 * i + 2] for n in ref.EXPERTS}}
        part = holder._moe(LayerRow(held, rank), held, rank, u) - shared
        assert float(jnp.abs(part).max()) > 1e-3  # every holder has a part
        total = total + part
    np.testing.assert_allclose(total, model._moe(p, stacks, rank, u), atol=1e-5)
    flt = ref.fault_flags(None)
    cfg, lin, _ = ref._parts(ref.hashable(TINY), "gdn", W.seed_key(SEED), rank, flt)
    with jax.default_matmul_precision("highest"):
        want, _ = ref._moe(cfg, lin, u[0], flt)
    np.testing.assert_allclose(total[0], want, atol=1e-5)


# ---------------------------------------------- through pool and state


def test_the_state_pool_sits_beside_the_k_v_pages(batcher):
    eng = batcher.engine
    assert eng.has_state and eng.has_recurrent and not eng.ring_rows
    assert eng.layers_per_stage == 3 and eng.state_layers == 9
    cache, _ = eng.init_cache_paged()
    # 16 pages + scratch in the three attention layers; a row is both heads
    assert cache.k.shape == cache.v.shape == (1, 3, 17, 1, PAGE, 1, 64)
    # 2 slots + the scratch row in the nine linear layers
    assert cache.state["gdn"].shape == (1, 9, 3, 4, 16, 16)
    assert cache.state["conv"].shape == (1, 9, 3, 3 * 128)
    assert eng.state_bytes() == 9 * 3 * (4 * 16 * 16 + 3 * 128) * 4


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks whose borders fall inside and between the
    convolution's taps (the scalar-decay chunked form), then decode through
    the K/V pages (the ragged path at two merged heads) and the state pool:
    log-probabilities against the reference's one full-sequence pass."""
    got = served(batcher, PROMPTS[name], 14)
    np.testing.assert_allclose(differences(TINY, PROMPTS[name], got), 0, atol=LP_TOL)


@hard_timeout(900)
@pytest.mark.parametrize("fault", [
    "gdn_state_reset", "gdn_no_decay", "gdn_state_bf16", "rope_full", "qgate_halves",
    "norms_plain", "gdn_norm_centred", "attn_gate_off", "shared_gate_off",
])
def test_the_served_path_with_a_fault_is_not_the_reference(batcher, fault):
    """The partial rotary, the gate's place in ``q_proj``, the zero-centred
    norms, the plain gated norm, both sigmoid gates and the float32 state:
    the served path is a hundred tolerances from each wrong variant."""
    got = served(batcher, PROMPTS["one-past"], 12)
    assert np.abs(differences(TINY, PROMPTS["one-past"], got, fault)).max() > 100 * LP_TOL


@hard_timeout(900)
def test_slots_join_and_leave_mid_run_and_a_reused_slot_starts_from_zero(batcher):
    """Three requests on two slots, each against the reference's full pass
    over its own sequence, LOGITS not tokens: the third joins while another
    decodes (its chunks run between the other's decode blocks, which must
    leave its state rows alone) and takes a slot whose state its last
    occupant left behind."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    jobs = {"inside": 9, "border": 14, "one-past": 11}
    resets0 = batcher.state_stats()["resets"]
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
            differences(TINY, PROMPTS[name], outs[name]), 0, atol=LP_TOL, err_msg=name)
    assert batcher.state_stats()["resets"] - resets0 == 3
    text = ServingMetrics(batcher_fn=lambda: batcher).render()
    assert f"mst_state_bytes {batcher.engine.state_bytes()}" in text
    assert 'mst_kda_dispatch_total{path="xla"}' in text


@hard_timeout(900)
def test_the_gather_body_the_dense_cache_and_the_solo_generator_agree(tiny, batcher):
    model, params = tiny
    want = [t for t, _ in served(batcher, PROMPTS["one-past"], 10)]
    for kw in (dict(paged_attention="gather"), dict(paged=False)):
        other = ContinuousBatcher(make_engine(model, params, **kw), decode_block=4)
        try:
            assert [t for t, _ in other.generate_step(PROMPTS["one-past"], max_tokens=10)] == want
        finally:
            other.close()
    gen = Generator(model, params, max_seq=MAX_SEQ, cache_dtype=jnp.float32,
                    prefill_chunk=PAGE, decode_block=4)
    assert [t for t, _ in gen.generate_step(PROMPTS["one-past"], max_tokens=10)] == want


# ------------------------------------------------------------ refusals


REFUSED = {
    "--prompt-cache": lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_cache=True),
    "--prefix-store": lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_store=object()),
    "--spill-bytes": lambda m, p: ContinuousBatcher(make_engine(m, p), spill_bytes=1 << 20),
    "--draft": lambda m, p: ContinuousBatcher(make_engine(m, p), draft="ngram"),
    "--kv-share-map": lambda m, p: make_engine(m, p, kv_share_map=object()),
    "--kv-compress-map": lambda m, p: make_engine(m, p, kv_compress_map=object()),
    "--disagg": lambda m, p: next(ContinuousBatcher(make_engine(m, p)).generate_step(
        [1, 2, 3], max_tokens=2, _prefill_only=True)),
}


@pytest.mark.parametrize("flag", list(REFUSED))
def test_what_re_enters_a_sequence_from_pages_alone_is_refused_by_name(tiny, flag):
    with pytest.raises(ValueError, match="recurrent state") as err:
        REFUSED[flag](*tiny)
    assert flag in str(err.value) and "Qwen3NextModel" in str(err.value)


@pytest.mark.parametrize("kw,what", [
    (dict(pp=2), r"not wired for qwen3_next.*--num-stages 1"),
    (dict(tp=2), "tensor parallelism is not wired for Qwen3NextModel"),
    (dict(ep=2), "expert parallelism is not wired for Qwen3NextModel"),
], ids=["num-stages", "tp", "ep"])
def test_other_layouts_refuse_by_name(tiny, kw, what):
    model, params = tiny
    mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=what):
        PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)


def test_unwired_configurations_refuse_by_name():
    with pytest.raises(ValueError, match=r"qwen3_next.*--num-stages 1"):
        build_model({**TINY, "start_layer": 0, "end_layer": 4})
    for bad in (dict(decoder_sparse_step=2), dict(mlp_only_layers=[0]),
                dict(norm_topk_prob=False), dict(use_sliding_window=True),
                dict(tie_word_embeddings=True), dict(rope_scaling={"type": "linear", "factor": 2})):
        with pytest.raises(ValueError, match="qwen3_next is wired for"):
            build_model({**TINY, **bad})
    with pytest.raises(ValueError, match="must divide linear_num_value_heads"):
        build_model({**TINY, "linear_num_key_heads": 3})
    with pytest.raises(ValueError, match="linear_key_head_dim == linear_value_head_dim"):
        build_model({**TINY, "linear_value_head_dim": 32})


# ------------------------------------------------- weights and the tables


def _by_key_head(w, key_heads, widths):
    """The inverse of ``regroup_by_key_head``: the program's ``[part, part,
    …]`` columns, each head-major, back into the checkpoint's key-head groups."""
    cuts = np.cumsum((0, *(key_heads * x for x in widths)))
    parts = [np.asarray(w)[:, lo:hi].reshape(w.shape[0], key_heads, -1)
             for lo, hi in zip(cuts, cuts[1:])]
    return np.concatenate(parts, axis=-1).reshape(w.shape[0], -1)


def test_map_weights_round_trips_the_published_grouping(tiny):
    """A checkpoint under the family's tensor names (torch orientation: a
    linear is ``(out, in)``, the convolution ``(C, 1, k)``, the shared
    expert's gate ``(1, hidden)``; ``in_proj_qkvz`` and ``in_proj_ba`` grouped
    by KEY head, a head's ``[q 16 | k 16 | v 2 x 16 | z 2 x 16]`` and ``[b 2 |
    a 2]``; all eight experts, of which a holder reads its own; an ``mtp``
    layer nobody reads) loads into the tree the model runs."""
    model, params = tiny
    t = lambda w: np.asarray(w).T  # noqa: E731
    hf = {"model.embed_tokens.weight": params["embed"]["weight"],
          "model.norm.weight": params["final_norm"]["weight"],
          "lm_head.weight": t(params["lm_head"]["weight"]),
          "mtp.layers.0.input_layernorm.weight": np.zeros(64)}
    special = {
        "qkvz_proj": lambda w: t(_by_key_head(w, 2, (16, 16, 32, 32))),
        "ba_proj": lambda w: t(_by_key_head(w, 2, (2, 2))),
        "conv_w": lambda w: np.asarray(w)[:, None, :],
        "shared_expert_gate": lambda w: np.asarray(w)[None, :],
    }
    for g, idxs in model.layer_group_layers().items():
        stack = params["layers"][g]
        for rank, i in enumerate(idxs):
            pre = f"model.layers.{i}."
            for suffix, (our, transposed) in model.NAMES[g].items():
                w = stack[our][rank]
                hf[pre + suffix] = special[our](w) if our in special else t(w) if transposed else w
            for our, which in model.EXPERTS.items():
                for e in range(8):
                    hf[pre + f"mlp.experts.{e}.{which}.weight"] = t(stack[our][rank, e])
    hf = {k: np.asarray(v) for k, v in hf.items()}
    # the grouping is no identity: the checkpoint's columns lie elsewhere
    first = params["layers"]["gdn"]["qkvz_proj"][0]
    assert not np.array_equal(_by_key_head(first, 2, (16, 16, 32, 32)), first)
    np.testing.assert_array_equal(
        regroup_by_key_head(_by_key_head(first, 2, (16, 16, 32, 32)), 2, (16, 16, 32, 32)), first)
    jax.tree.map(np.testing.assert_array_equal, model.map_weights(hf, jnp.float32), params)
    holder, _ = build_model(
        {**TINY, "num_experts": 2, "moe_expert_share": 4, "moe_expert_share_index": 3})
    got = holder.map_weights(hf, jnp.float32)["layers"]["attn"]["w_up"]
    np.testing.assert_array_equal(got, params["layers"]["attn"]["w_up"][:, 6:8])


def _published():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    path = Path(__file__).parents[1] / "benchmarks/configs/qwen3-next-80b-bf16-ep4.json"
    return published_config(json.loads(path.read_text()))


def test_step_bytes_of_the_published_configuration():
    """ISSUE 51's arithmetic: 10.85 GB of weights held, of which a step at 32
    rows reads what lies outside the experts and the ~60 of 128 experts a
    layer its picks hit; 2.15 MB of state a slot and linear layer; 6144 B of
    K/V a token."""
    cfg = _published()
    assert ref.layer_groups(cfg) == ["gdn", "gdn", "gdn", "attn"] * 3
    need = ref.decode_step_bytes(cfg, "bf16", 32, 32 * 4500)
    units = ref.model_units(cfg)
    held = sum(
        len(idxs) * sum(2 * u.out * max(u.inn, 1) * max(u.experts, 1) for u in units[g].values())
        for g, idxs in ref.group_layers(cfg).items()
    ) + 2 * 2 * 38016 * 2048
    assert 10.8e9 < held < 10.9e9
    assert 0.98e9 < need["fixed_weights"] < 1.08e9  # 12 layers' 0.87 GB and the head's 0.16
    assert 4.2e9 < need["routed_experts"] < 4.8e9  # 12 x ~60 x 6.29 MB
    assert need["recurrent_state"] == ref.kda_state_step_bytes(cfg, 32)
    assert need["recurrent_state"] == 2 * 32 * 9 * (32 * 128 * 128 * 4 + 8192 * 3 * 2)
    assert need["kv_pages"] == 32 * 4500 * 3 * 2048 == ref.paged_attn_step_bytes(cfg, 32, 4500)
    assert need["total"] == sum(v for k, v in need.items() if k != "total")


def test_the_seeded_tree_is_the_program_s_and_its_router_is_balanced(tiny):
    model, params = tiny
    made = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params)
    # a zero-centred norm's w is the seeded vector minus 1, exactly
    unit = ref.model_units(TINY)["attn"]["q_norm"]
    np.testing.assert_array_equal(
        1.0 + params["layers"]["attn"]["q_norm"][1], W.logical_norm(W.seed_key(SEED), unit, 1))
    # every column of the router has one norm (bf16 rounding apart)
    norms = np.linalg.norm(np.asarray(params["layers"]["gdn"]["router"]), axis=1)
    assert np.abs(norms - 1).max() < 2e-3
    # and picks every expert about equally often: 8192 rows, 2 of 8 (at 64
    # inputs two columns' logits correlate by an eighth, which alone moves a
    # rate by a tenth; at the published 2048 sampling is what is left)
    rates = ref.pick_rates(TINY, SEED)
    assert {g: r.shape for g, r in rates.items()} == {"gdn": (9, 8), "attn": (3, 8)}
    for r in rates.values():
        np.testing.assert_allclose(np.asarray(r).sum(-1), 2.0, atol=1e-5)
        assert np.abs(np.asarray(r) / (2 / 8) - 1).max() < 0.2
