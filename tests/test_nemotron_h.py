"""``nemotron_h`` (Mamba-2 + attention + latent MoE) on the served path,
against its plain reference (``benchmarks/reference/nemotron_h.py``) at tiny
widths on the CPU, with the benchmark's seeded weights on both sides.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so
what separates them is the order of sums: the served path's chunked (SSD)
prefill against the reference's one-position-at-a-time recurrence, a cache
against none, 2 stages against 1. Log-probabilities then agree to ~1e-5;
``LP_TOL`` = 2e-3 leaves two orders of magnitude and is four below what any
lost or stale state shows (tenths). Where two served runs must be the SAME
computation (a reused slot against a fresh one) they are compared exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import nemotron_h as ref
from mlx_sharding_tpu.generate import Generator, TokenLogprobs
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.models.nemotron_h import ssd_chunked, ssm_sequential
from mlx_sharding_tpu.ops import moe
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine, split_stage_stacks
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout, run_concurrent

LP_TOL = 2e-3
SEED = 7
TINY = dict(
    model_type="nemotron_h", vocab_size=256, hidden_size=64,
    num_hidden_layers=6, hybrid_override_pattern="MEM*EM",
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, layer_norm_epsilon=1e-5,
    n_routed_experts=8, num_experts_per_tok=3, moe_intermediate_size=32,
    moe_latent_size=24, moe_shared_expert_intermediate_size=48,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.5,
)
PROMPTS = {
    "a": list(range(3, 16)),  # 13 tokens: chunks of 8 and 5
    "b": list(range(40, 47)),  # one padded chunk
    "c": list(range(90, 111)),  # 21: chunks of 8, 8, 5
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


def served(gen, prompt, n, **kw):
    """``[(token, {id: log-probability})]`` of one greedy request: the first
    token carries its whole row, a decode block's tokens a top-k summary."""
    out = []
    for tok, lp in gen.generate_step(prompt, max_tokens=n, want_logprobs=True, **kw):
        if isinstance(lp, TokenLogprobs):
            top = dict(zip(np.asarray(lp.top_indices).tolist(),
                           np.asarray(lp.top_values).tolist()))
        else:
            row = np.asarray(lp).reshape(-1)
            top = {int(i): float(row[i]) for i in np.argsort(-row)[:10]}
        out.append((int(tok), top))
    return out


def assert_matches_reference(cfg, prompt, got, fault=None):
    """Teacher-forced: the reference sees the prompt and the served path's
    own earlier tokens, and is asked for the served top ids at each row."""
    toks = [t for t, _ in got]
    seq = list(prompt) + toks[:-1]
    rows = [len(prompt) - 1 + j for j in range(len(toks))]
    wanted = [sorted(top)[:8] for _, top in got]
    want = reference_at(cfg, seq, rows, wanted, fault)
    have = np.asarray([[top[i] for i in w] for (_, top), w in zip(got, wanted)])
    np.testing.assert_allclose(have, want, atol=LP_TOL, rtol=0)


def make_engine(model, params, *, stages=1, ep=1, slots=2, paged=True):
    n = stages * ep
    return PipelineEngine(
        model, params,
        make_mesh(pp=stages, tp=1, ep=ep, devices=jax.devices()[:n]),
        microbatches=slots, max_seq=64, cache_dtype=jnp.float32,
        prefill_chunk=8, decode_block=4,
        pool_pages=8 * slots if paged else None, page_size=8 if paged else None,
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
    ids = np.random.default_rng(0).integers(1, 256, 37)
    logits, cache = model(params, jnp.asarray(ids)[None], model.make_cache(1, 64, jnp.float32))
    lp = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    wanted = np.argsort(-lp, axis=-1)[:, :8]
    want = reference_at(TINY, ids, list(range(37)), wanted)
    np.testing.assert_allclose(np.take_along_axis(lp, wanted, -1), want, atol=LP_TOL, rtol=0)
    # K/V rows for the one attention layer only, state for the three Mamba
    assert cache.k.shape[0] == 1 and cache.state["ssm"].shape[0] == 3
    assert cache.state["ssm"].dtype == jnp.float32


def test_chunked_ssd_equals_the_sequential_recurrence():
    rng = np.random.default_rng(1)
    b, t, h, p, n = 2, 21, 4, 8, 16  # 21 rows: two whole chunks of 8 and 5 more
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, bm, cm, s0 = f(b, t, h, p), f(b, t, h, n), f(b, t, h, n), f(b, h, p, n)
    dt = jnp.asarray(rng.uniform(0.001, 0.3, (b, t, h)), jnp.float32)
    dt = dt.at[1, 17:].set(0.0)  # rows that must not advance sequence 1
    a = -jnp.asarray(rng.uniform(1, 16, (h,)), jnp.float32)
    y, s = ssd_chunked(x, dt, a, bm, cm, s0, 8)
    y_seq, s_seq = ssm_sequential(x, dt, a, bm, cm, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_seq), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_seq), rtol=1e-4, atol=1e-5)
    _, s17 = ssm_sequential(x[1:, :17], dt[1:, :17], a, bm[1:, :17], cm[1:, :17], s0[1:])
    np.testing.assert_allclose(np.asarray(s[1:]), np.asarray(s17), rtol=1e-4, atol=1e-5)


def test_the_stage_split_pads_no_group_with_another_kind(tiny):
    model, params = tiny
    split, masks, kv_layers = split_stage_stacks(model, params["layers"], [(0, 3), (3, 6)])
    # "MEM" | "*EM": rows per stage and group; K/V layers = attention slots
    assert {g: m.tolist() for g, m in masks.items()} == {
        "mamba": [[True, True], [True, False]], "moe": [[True], [True]],
        "attn": [[False], [True]],
    }
    assert kv_layers == 1 and split["mamba"]["in_proj"].shape[:2] == (2, 2)
    np.testing.assert_array_equal(split["mamba"]["A_log"][1, 0], params["layers"]["mamba"]["A_log"][2])
    assert not np.asarray(split["mamba"]["A_log"][1, 1]).any()
    plan = model.stage_plan([(0, 3), (3, 6)])
    assert plan == [(("mamba", "attn"), (0, 0)), (("moe", "moe"), (0, 0)),
                    (("mamba", "mamba"), (1, 0))]


def test_map_weights_reads_the_checkpoint_names_and_the_share():
    """The loader's half: HF tensor names (torch's (out, in) matrices, the
    convolution as (C, 1, K)) into the three stacks ``init_params`` makes;
    a config with a share loads the experts it holds, by their global ids."""
    cfg = dict(TINY, n_routed_experts=2, moe_expert_share=4, moe_expert_share_index=1)
    model, _ = build_model(cfg)
    want = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(8)
    d, conv, nh = model.d_inner, model.conv_dim, 8
    f = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    ckpt = {"backbone.embeddings.weight": f(256, 64), "backbone.norm_f.weight": f(64),
            "lm_head.weight": f(256, 64)}
    for i, ch in enumerate(cfg["hybrid_override_pattern"]):
        pre = f"backbone.layers.{i}."
        ckpt[pre + "norm.weight"] = f(64)
        if ch == "M":
            ckpt.update({
                pre + "mixer.in_proj.weight": f(d + conv + nh, 64),
                pre + "mixer.conv1d.weight": f(conv, 1, 4), pre + "mixer.conv1d.bias": f(conv),
                pre + "mixer.dt_bias": f(nh), pre + "mixer.A_log": f(nh), pre + "mixer.D": f(nh),
                pre + "mixer.norm.weight": f(d), pre + "mixer.out_proj.weight": f(64, d)})
        elif ch == "*":
            ckpt.update({pre + "mixer.q_proj.weight": f(64, 64), pre + "mixer.k_proj.weight": f(32, 64),
                         pre + "mixer.v_proj.weight": f(32, 64), pre + "mixer.o_proj.weight": f(64, 64)})
        else:
            ckpt.update({
                pre + "mixer.gate.weight": f(8, 64), pre + "mixer.gate.e_score_correction_bias": f(8),
                pre + "mixer.fc1_latent_proj.weight": f(24, 64),
                pre + "mixer.fc2_latent_proj.weight": f(64, 24),
                pre + "mixer.shared_experts.up_proj.weight": f(48, 64),
                pre + "mixer.shared_experts.down_proj.weight": f(64, 48)})
            for e in range(8):  # the whole model's experts: 2 and 3 are this holder's
                ckpt[pre + f"mixer.experts.{e}.up_proj.weight"] = f(32, 24)
                ckpt[pre + f"mixer.experts.{e}.down_proj.weight"] = f(24, 32)
    got = model.map_weights(ckpt, jnp.float32)
    assert jax.tree.map(np.shape, got) == jax.tree.map(np.shape, want)
    mamba, moe_ = got["layers"]["mamba"], got["layers"]["moe"]
    np.testing.assert_array_equal(mamba["in_proj"][1], ckpt["backbone.layers.2.mixer.in_proj.weight"].T)
    np.testing.assert_array_equal(mamba["conv_w"][2], ckpt["backbone.layers.5.mixer.conv1d.weight"][:, 0])
    np.testing.assert_array_equal(moe_["w_up"][1, 0], ckpt["backbone.layers.4.mixer.experts.2.up_proj.weight"].T)
    np.testing.assert_array_equal(moe_["w_down"][0, 1], ckpt["backbone.layers.1.mixer.experts.3.down_proj.weight"].T)
    assert moe_["router"].shape == (2, 64, 8) and mamba["A_log"].dtype == np.float32


# ----------------------------------------------- through engine and batcher


@hard_timeout(600)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks of unequal length, then decode through the state
    pool and the pages: log-probabilities against the reference's one full
    forward pass."""
    got = served(batcher, PROMPTS[name], 11)
    assert_matches_reference(TINY, PROMPTS[name], got)


@hard_timeout(600)
def test_a_reused_slot_serves_what_a_fresh_one_does(batcher):
    first = served(batcher, PROMPTS["b"], 9)
    resets = batcher.state_resets
    served(batcher, PROMPTS["c"], 7)  # the same slot, another request's state
    again = served(batcher, PROMPTS["b"], 9)
    assert again == first
    assert batcher.state_resets == resets + 2
    assert batcher.state_stats()["slots_in_use"] == 0
    assert batcher.state_stats()["bytes"] == batcher.engine.state_bytes() > 0


def test_metrics_expose_the_state_pool(batcher):
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    served(batcher, PROMPTS["b"], 3)
    text = ServingMetrics(batcher_fn=lambda: batcher).render()
    assert "mst_state_slots_in_use 0" in text
    assert f"mst_state_bytes {batcher.engine.state_bytes()}" in text
    assert f"mst_state_resets_total {batcher.state_resets}" in text
    assert "# HELP mst_state_resets_total" in text


@hard_timeout(600)
def test_slots_join_and_leave_at_different_steps(batcher):
    """Three requests on two slots: each against itself alone. ``a`` ends
    inside a block with its lookahead in flight (positions past its end are
    computed and dropped: the frozen active mask advanced its state), and
    ``c`` takes the freed slot."""
    jobs = {"a": 6, "b": 14, "c": 9}
    alone = {k: served(batcher, PROMPTS[k], n) for k, n in jobs.items()}
    dropped = batcher._tokens_dropped["slot_finished"]
    outs = run_concurrent(
        batcher, [(PROMPTS[k], dict(max_tokens=n)) for k, n in jobs.items()])
    assert outs == [[t for t, _ in alone[k]] for k in jobs]
    assert batcher._tokens_dropped["slot_finished"] > dropped
    for k in jobs:
        assert_matches_reference(TINY, PROMPTS[k], alone[k])


@hard_timeout(600)
def test_two_stages_equal_one(tiny, batcher):
    """``--num-stages 2``: "MEM" on one device and "*EM" on the other (the
    stages differ in kind at their first layer)."""
    two = ContinuousBatcher(make_engine(*tiny, stages=2), decode_block=4)
    try:
        for k in ("a", "c"):
            got, want = served(two, PROMPTS[k], 8), served(batcher, PROMPTS[k], 8)
            assert [t for t, _ in got] == [t for t, _ in want]
            assert_matches_reference(TINY, PROMPTS[k], got)
        assert two.engine.state_layers == 2 and two.engine.layers_per_stage == 1
    finally:
        two.close()


@hard_timeout(600)
def test_the_dense_cache_and_the_solo_generator_agree(tiny, batcher):
    model, params = tiny
    want = [t for t, _ in served(batcher, PROMPTS["a"], 8)]
    dense = ContinuousBatcher(make_engine(model, params, paged=False), decode_block=4)
    try:
        assert [t for t, _ in dense.generate_step(PROMPTS["a"], max_tokens=8)] == want
    finally:
        dense.close()
    gen = Generator(model, params, max_seq=64, cache_dtype=jnp.float32,
                    prefill_chunk=8, decode_block=4)
    assert [t for t, _ in gen.generate_step(PROMPTS["a"], max_tokens=8)] == want


REFUSED = {
    "--prompt-cache": lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_cache=True),
    "--prefix-store": lambda m, p: ContinuousBatcher(make_engine(m, p), prefix_store=object()),
    "--spill-bytes": lambda m, p: ContinuousBatcher(make_engine(m, p), spill_bytes=1 << 20),
    "--draft": lambda m, p: ContinuousBatcher(make_engine(m, p), draft="ngram"),
    "--kv-share-map": lambda m, p: PipelineEngine(
        m, p, make_mesh(pp=1, tp=1, ep=1, devices=jax.devices()[:1]),
        pool_pages=8, page_size=8, prefill_chunk=8, max_seq=64, kv_share_map=object()),
    "--kv-compress-map": lambda m, p: PipelineEngine(
        m, p, make_mesh(pp=1, tp=1, ep=1, devices=jax.devices()[:1]),
        pool_pages=8, page_size=8, prefill_chunk=8, max_seq=64, kv_compress_map=object()),
    "--disagg": lambda m, p: next(ContinuousBatcher(make_engine(m, p)).generate_step(
        [1, 2, 3], max_tokens=2, _prefill_only=True)),
}


@pytest.mark.parametrize("flag", list(REFUSED))
def test_what_cannot_carry_recurrent_state_is_refused_by_name(tiny, flag):
    with pytest.raises(ValueError, match="recurrent state") as err:
        REFUSED[flag](*tiny)
    assert flag in str(err.value)


def test_solo_generators_refuse_rewinds_too(tiny):
    from mlx_sharding_tpu.speculative import NgramSpeculativeGenerator

    with pytest.raises(ValueError, match=r"--prompt-cache.*recurrent state"):
        Generator(*tiny, prompt_cache=True)
    with pytest.raises(ValueError, match=r"--draft.*recurrent state"):
        NgramSpeculativeGenerator(*tiny)


# ---------------------------------------------------------------- routing


def _route(x, w, bias, k=2, norm=True, scale=1.0):
    return moe.nemotron_routing(
        jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32),
        jnp.asarray(bias, jnp.float32), k, norm_topk_prob=norm,
        routed_scaling_factor=scale)


def test_routing_ties_go_to_the_lower_index():
    w = np.zeros((4, 6), np.float32)
    w[:, 1] = w[:, 4] = 1.0  # experts 1 and 4 score alike, above the rest
    w[:, 2] = w[:, 5] = 0.5
    wts, idx = _route(np.ones((3, 4)), w, np.zeros(6), k=3)
    assert np.asarray(idx).tolist() == [[1, 4, 2]] * 3
    np.testing.assert_allclose(np.asarray(wts)[:, 0], np.asarray(wts)[:, 1])


def test_the_selection_bias_changes_the_choice_and_not_the_weight():
    rng = np.random.default_rng(2)
    x, w = rng.normal(size=(5, 8)), rng.normal(size=(8, 6))
    scores = 1.0 / (1.0 + np.exp(-(x @ w)))
    _, idx0 = _route(x, w, np.zeros(6), norm=False)
    bias = np.zeros(6)
    loser = int(np.argmin(scores[0]))
    bias[loser] = 10.0  # chosen everywhere now
    wts, idx = _route(x, w, bias, norm=False)
    assert loser not in np.asarray(idx0)[0] and (np.asarray(idx)[:, 0] == loser).all()
    # its weight is its own sigmoid score: the bias is not in it
    np.testing.assert_allclose(np.asarray(wts)[:, 0], scores[:, loser], rtol=1e-5)


def test_routing_weights_sum_to_the_scaling_factor():
    rng = np.random.default_rng(3)
    x, w = rng.normal(size=(4, 8)), rng.normal(size=(8, 6))
    wts, _ = _route(x, w, rng.normal(size=6) * 0.05, k=3, scale=5.0)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 5.0, rtol=1e-5)
    raw, _ = _route(x, w, np.zeros(6), k=3, norm=False, scale=5.0)
    assert (np.asarray(raw) <= 5.0).all() and not np.allclose(np.asarray(raw).sum(-1), 5.0)


# -------------------------------------------------- un-gated experts, share


def _relu2_experts(x, weights, idx, w_up, w_down):
    y = np.zeros_like(x)
    for n in range(x.shape[0]):
        for k in range(idx.shape[1]):
            e = idx[n, k]
            y[n] += weights[n, k] * (np.maximum(x[n] @ w_up[e], 0) ** 2) @ w_down[e]
    return y


def test_ungated_experts_on_the_dense_paths():
    rng = np.random.default_rng(4)
    n, h, mi, e, k = 6, 16, 12, 8, 3
    x = rng.normal(size=(n, h)).astype(np.float32)
    wu = rng.normal(size=(e, h, mi)).astype(np.float32) / 4
    wd = rng.normal(size=(e, mi, h)).astype(np.float32) / 4
    idx = np.stack([rng.choice(e, k, replace=False) for _ in range(n)])
    wts = rng.uniform(0.1, 1, (n, k)).astype(np.float32)
    want = _relu2_experts(x, wts, idx, wu, wd)
    args = (jnp.asarray(x), jnp.asarray(wts), jnp.asarray(idx), None, jnp.asarray(wu), jnp.asarray(wd))
    for got in (moe._apply_gather(*args), moe._apply_scan(*args), moe.apply_experts(*args)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    # a resident range of a wider routing: experts 2..5 held, the rest left out
    held = moe.apply_experts(*args[:4], jnp.asarray(wu[2:6]), jnp.asarray(wd[2:6]), expert_base=2)
    inside = (idx >= 2) & (idx < 6)
    np.testing.assert_allclose(
        np.asarray(held), _relu2_experts(x, wts * inside, idx, wu, wd), rtol=1e-4, atol=1e-5)
    # the same out of whole (layers, experts, ...) stacks, read in place
    stacked = moe.apply_experts(
        *args[:4], jnp.asarray(np.stack([wu[:4], wu[2:6]])),
        jnp.asarray(np.stack([wd[:4], wd[2:6]])), expert_base=2, layer=1)
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(held), rtol=1e-6)


def test_ungated_experts_on_the_packed_paths():
    from tests.test_quant_moe import _packed_stack, _routing

    rng = np.random.default_rng(5)
    n, h, mi, e, k, gs = 8, 64, 32, 4, 2, 16
    x = jnp.asarray(rng.normal(size=(n, h)), jnp.float32)
    wu, wd = _packed_stack(rng, e, mi, h, gs, False), _packed_stack(rng, e, h, mi, gs, False)
    weights, idx = _routing(rng, n, e, k, "random")
    want = moe._apply_gather_packed(x, weights, idx, None, wu, wd, gs, 4)
    got = moe._apply_packed_kernel(x, weights, idx, None, wu, wd, gs, 4, interpret=True)
    scan = moe._apply_scan(x, weights, idx, None, wu, wd, gs, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(scan), np.asarray(want), rtol=1e-4, atol=2e-5)
    assert float(jnp.abs(want).max()) > 0


@hard_timeout(300)
def test_four_shares_add_up_to_the_uncut_layer(tiny):
    """Each of four holders routes over all 8 experts and computes its own
    2: the routed parts, with the shared expert and the latent projections
    counted once, are the uncut reference's layer."""
    _, params = tiny
    rank, t = 1, 12
    h = jnp.asarray(np.random.default_rng(6).normal(size=(1, t, 64)), jnp.float32)
    uncut, _ = ref._moe_layer(ref.hashable(TINY), "bf16", W.seed_key(SEED),
                              jnp.asarray(rank, jnp.int32), h[0])
    want = np.asarray(uncut - h[0])  # the mixer's output, residual taken off
    from mlx_sharding_tpu.ops import rms_norm

    stacks = params["layers"]["moe"]
    small = {n_: w[rank] for n_, w in stacks.items() if n_ not in ("w_up", "w_down")}
    u = rms_norm(h, small["norm"], 1e-5)
    parts, shared = [], None
    for i in range(4):
        model_i, _ = build_model(dict(
            TINY, n_routed_experts=2, moe_expert_share=4, moe_expert_share_index=i))
        held = {n_: stacks[n_][:, 2 * i : 2 * i + 2] for n_ in ("w_up", "w_down")}
        out = model_i._moe(small, held, rank, u, None)[0]
        none_held = {n_: jnp.zeros_like(w) for n_, w in held.items()}
        shared = model_i._moe(small, none_held, rank, u, None)[0]  # what all compute alike
        parts.append(np.asarray(out - shared))
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), want, atol=2e-4, rtol=0)
    assert all(np.abs(p).max() > 1e-3 for p in parts)


@hard_timeout(600)
def test_ep4_on_four_devices_equals_one(tiny, batcher):
    """``--ep 4``: the same expert code with ``axis_index`` and the ``psum``;
    its engine takes the gathered-page decode body."""
    four = ContinuousBatcher(make_engine(*tiny, ep=4), decode_block=4)
    try:
        assert four.engine.paged_attention == "gather"
        got = served(four, PROMPTS["a"], 8)
        assert [t for t, _ in got] == [t for t, _ in served(batcher, PROMPTS["a"], 8)]
        assert_matches_reference(TINY, PROMPTS["a"], got)
    finally:
        four.close()


def test_decode_step_bytes_of_the_published_configuration():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    cfg = published_config(json.loads(
        (Path(ref.__file__).parents[1] / "configs/nemotron3-super-bf16-ep4.json").read_text()))
    need = ref.decode_step_bytes(cfg, "bf16", 32, 32 * 512)
    assert need["total"] == pytest.approx(sum(v for k, v in need.items() if k != "total"))
    one_expert = 2 * 2 * 1024 * 2688
    # 32 rows x top-22 of 512 hit 75.5 % of the experts, held or not
    assert need["routed_experts"] == pytest.approx(5 * 128 * 0.7551 * one_expert, rel=1e-3)
    assert need["recurrent_state"] == ref.ssm_state_step_bytes(cfg, 32) == pytest.approx(
        2 * 32 * 5 * (4 * 128 * 64 * 128 + 2 * 10240 * 3))
    assert need["kv_pages"] == 32 * 512 * 1024
    assert 1.6e9 < need["fixed_weights"] < 2.0e9
