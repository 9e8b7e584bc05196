"""``kimi_linear`` (Kimi-Linear: a gated delta-rule mixer (KDA) or NoPE latent
attention, then a dense MLP or sigmoid-routed experts, in every block) on the
served path, against its plain reference
(``benchmarks/reference/kimi_linear.py``) at tiny widths on the CPU, with the
benchmark's seeded weights on both sides.

Sizes: pages and chunks of 8; the published pattern cut to a head layer, two
periods and the tail (``D  K K M K  K K M K  K M``: a run of two KDA layers
in an inner scan, the MLA layer, a run of one); 4 KDA heads of 16 behind 4
taps; 4 MLA heads of 16 + 8 on a latent of 32; 8 experts, 2 a token, the
PUBLISHED scaling factor. Prompts end inside a chunk, one row into a chunk
(the convolution's three taps behind it lie in the chunk before) and on a
chunk border.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so what
separates them is the order of sums: chunks and the chunked (WY) form against
one position at a time, the absorbed ``kv_b`` against per-head keys and
values. Log-probabilities agree to ~6e-5 (the logits are not flattened as
granite's are); ``LP_TOL`` = 4e-4 leaves several times that and is two
thousand times under the SMALLEST of the reference's faults (the state
rounded to bfloat16: 0.9; a dropped gate, rotary on the NoPE layers, the
renormalisation left out and fp8 weights move it by 1.5 to 5).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import kimi_linear as ref
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.models.base import LayerRow
from mlx_sharding_tpu.models.kimi_linear import pattern_walk
from mlx_sharding_tpu.ops import kda
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout, run_concurrent
from tests.test_afmoe import served  # [(token, {id: log-probability})] of one greedy request

LP_TOL = 4e-4
SEED = 11
PAGE, MAX_SEQ = 8, 64
TINY = dict(
    model_type="kimi_linear", vocab_size=256, hidden_size=64, num_hidden_layers=11,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96,
    kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, mla_use_nope=True, first_k_dense_replace=1, moe_layer_freq=1,
    moe_intermediate_size=32, num_experts=8, num_experts_per_token=2,
    num_shared_experts=1, num_expert_group=1, topk_group=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446,
    rms_norm_eps=1e-5, rope_theta=10000, tie_word_embeddings=False,
    linear_attn_config=dict(
        full_attn_layers=[4, 8, 11], kda_layers=[1, 2, 3, 5, 6, 7, 9, 10],
        head_dim=16, num_heads=4, short_conv_kernel_size=4,
    ),
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


def test_the_published_pattern_is_a_head_six_periods_and_a_tail():
    kinds = ["K" if i + 1 not in (4, 8, 12, 16, 20, 24, 27) else "M" for i in range(27)]
    kinds[0] = "D"
    assert pattern_walk(kinds) == (["D"], ["K", "K", "M", "K"], 6, ["K", "M"])
    assert pattern_walk(list("KMKMKM")) == ([], ["K", "M"], 3, [])
    assert pattern_walk(list("DKM")) == (["D", "K", "M"], [], 0, [])


@hard_timeout(300)
def test_full_forward_matches_the_reference(tiny):
    model, params = tiny
    assert model.walk == (["dense"], ["kda", "kda", "mla", "kda"], 2, ["kda", "mla"])
    ids = PROMPTS["border"]
    cache = model.make_cache(1, MAX_SEQ, jnp.float32)
    # a row [latent 32, k_pe 8] padded to a whole lane tile
    assert cache.k.shape == (3, 1, MAX_SEQ, 1, 128) and cache.v.shape[-1] == 1
    assert {k: (v.shape, v.dtype) for k, v in cache.state.items()} == {
        "kda": ((8, 1, 4, 16, 16), jnp.float32), "conv": ((8, 1, 576), jnp.float32),
    }
    logits, _ = model(params, jnp.asarray(ids)[None], cache)
    have = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    rows = list(range(len(ids)))
    wanted = np.argsort(-have, axis=-1)[:, :8]
    want = reference_at(TINY, ids, rows, wanted)
    np.testing.assert_allclose(np.take_along_axis(have, wanted, -1), want, atol=LP_TOL)


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_moves_the_reference_far_past_the_tolerance(fault):
    """bf16 state, a dropped decay gate, rotary on the NoPE layers, the
    renormalisation left out, a lost state, fp8 weights: each would fail the
    comparisons of this file by three orders of magnitude."""
    ids = PROMPTS["border"]
    rows, wanted = list(range(8, len(ids))), [list(range(1, 9))] * (len(ids) - 8)
    clean = reference_at(TINY, ids, rows, wanted)
    moved = np.abs(reference_at(TINY, ids, rows, wanted, fault) - clean).max()
    assert moved > 1000 * LP_TOL, (fault, moved)


# --------------------------------------------------- the recurrence's forms


def _kda_inputs(b, t, h, d, a_max, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda._l2norm(jax.random.normal(ks[0], (b, t, h, d))) * d**-0.5
    k = kda._l2norm(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    # head 0 decays at exp(A_log) = a_max, the strongest; the last hardly
    a = jnp.linspace(a_max, 0.01, h)[:, None]
    g = -a * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, h, d, d))


def test_the_chunked_form_is_the_sequential_recurrence_at_the_strongest_decay():
    """Three blocks of 64 and a ragged one of 8, ``exp(A_log)`` = 16 on head
    0 (``exp(-G)`` alone would overflow float32 inside the first block):
    finite, and the sequential form's numbers to 2e-5 — float32 sums in
    another order; outputs are O(1)."""
    args = _kda_inputs(2, 200, 3, 32, 16.0)
    o, s = jax.jit(kda.kda_chunked)(*args)
    o_seq, s_seq = kda.kda_sequential(*args)
    assert np.isfinite(o).all() and np.isfinite(s).all()
    np.testing.assert_allclose(o, o_seq, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, s_seq, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_the_one_step_form_updates_the_pool_where_it_lies(interpret):
    """Layer 1 of a pool of three, two sequences and a scratch row: the
    active sequence gets the sequential form's step (float32, 1e-6), the
    inactive one keeps its state, and nothing else of the pool moves."""
    q, k, v, g, beta, _ = _kda_inputs(2, 1, 4, 16, 16.0, seed=1)
    pool = jax.random.normal(jax.random.PRNGKey(2), (3, 3, 4, 16, 16))
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
    np.testing.assert_array_equal(new[jnp.asarray([0, 2])], pool[jnp.asarray([0, 2])])


# ------------------------------------------------------------ the share


def test_the_shares_routed_parts_and_the_shared_expert_once_add_up():
    """Four holders of two experts each route over all eight and compute
    their own experts' part: the four parts and the shared expert, counted
    once, are the uncut reference's expert layer (float32, 1e-5)."""
    model, params = build_model(TINY)[0], seeded_params(TINY)
    rank = 2
    stacks = params["layers"]["kda"]
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 6, 64), jnp.float32)
    p = LayerRow(stacks, rank)
    shared = model._swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    total = shared
    for i in range(4):
        holder, _ = build_model(
            {**TINY, "num_experts": 2, "moe_expert_share": 4, "moe_expert_share_index": i})
        held = {**stacks, **{n: stacks[n][:, 2 * i : 2 * i + 2] for n in ref.EXPERTS}}
        part = holder._moe(LayerRow(held, rank), held, rank, u) - shared
        assert float(jnp.abs(part).max()) > 1e-3  # every holder has a part
        total = total + part
    np.testing.assert_allclose(total, model._moe(p, stacks, rank, u), atol=1e-5)
    cfg, lin, _ = ref._parts(ref.hashable(TINY), "kda", W.seed_key(SEED), rank, False)
    bias = ref.balancing_biases(ref.hashable(TINY), SEED)["kda"][rank]
    with jax.default_matmul_precision("highest"):
        want, _ = ref._moe(cfg, lin, bias, u[0], False)
    np.testing.assert_allclose(total[0], want, atol=1e-5)


# ---------------------------------------------- through pool and state


def test_the_state_pool_sits_beside_the_latent_pages(batcher):
    eng = batcher.engine
    assert eng.has_state and eng.has_recurrent and not eng.ring_rows
    assert eng.layers_per_stage == 3 and eng.state_layers == 8
    cache, _ = eng.init_cache_paged()
    # 16 pages + scratch in the three MLA layers; a row is [latent, k_pe, 0s]
    assert cache.k.shape == (1, 3, 17, 1, PAGE, 1, 128)
    assert cache.v.shape == (1, 3, 17, 1, PAGE, 1, 1)
    # 2 slots + the scratch row in the eight KDA layers
    assert cache.state["kda"].shape == (1, 8, 3, 4, 16, 16)
    assert cache.state["conv"].shape == (1, 8, 3, 576)
    assert eng.state_bytes() == 8 * 3 * (4 * 16 * 16 + 576) * 4


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks whose borders fall inside and between the
    convolution's taps, then decode through the latent pages (the absorbed
    form) and the state pool: log-probabilities against the reference's one
    full-sequence pass with per-head keys and values."""
    got = served(batcher, PROMPTS[name], 14)
    np.testing.assert_allclose(differences(TINY, PROMPTS[name], got), 0, atol=LP_TOL)


@hard_timeout(900)
@pytest.mark.parametrize("fault", ["kda_state_reset", "kda_no_decay", "mla_rotary_on"])
def test_the_served_path_with_a_fault_is_not_the_reference(batcher, fault):
    got = served(batcher, PROMPTS["one-past"], 12)
    assert np.abs(differences(TINY, PROMPTS["one-past"], got, fault)).max() > 100 * LP_TOL


@hard_timeout(900)
def test_slots_join_and_leave_mid_run_and_a_reused_slot_starts_from_zero(batcher):
    """Three requests on two slots, each against itself alone: the third
    joins while another decodes (its chunks run between the other's decode
    blocks, which must leave its state rows alone) and takes a slot whose
    state its last occupant left behind."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    jobs = {"inside": 9, "border": 14, "one-past": 11}
    alone = {k: [t for t, _ in served(batcher, PROMPTS[k], n)] for k, n in jobs.items()}
    resets0 = batcher.state_stats()["resets"]
    outs = run_concurrent(
        batcher, [(PROMPTS[k], dict(max_tokens=n)) for k, n in jobs.items()])
    assert outs == [alone[k] for k in jobs]
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
    assert flag in str(err.value) and "KimiLinearModel" in str(err.value)


@pytest.mark.parametrize("kw,what", [
    (dict(pp=2), r"not wired for kimi_linear.*--num-stages 1"),
    (dict(tp=2), "tensor parallelism is not wired for KimiLinearModel"),
    (dict(ep=2), "expert parallelism is not wired for KimiLinearModel"),
], ids=["num-stages", "tp", "ep"])
def test_other_layouts_refuse_by_name(tiny, kw, what):
    model, params = tiny
    mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=what):
        PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)


def test_unwired_configurations_refuse_by_name():
    with pytest.raises(ValueError, match=r"kimi_linear.*--num-stages 1"):
        build_model({**TINY, "start_layer": 0, "end_layer": 4})
    for bad in (dict(mla_use_nope=False), dict(q_lora_rank=24), dict(num_expert_group=2),
                dict(moe_router_activation_func="softmax"), dict(tie_word_embeddings=True)):
        with pytest.raises(ValueError, match="kimi_linear is wired for"):
            build_model({**TINY, **bad})
    lin = TINY["linear_attn_config"]
    with pytest.raises(ValueError, match="name each of the layers"):
        build_model({**TINY, "linear_attn_config": {**lin, "kda_layers": [1, 2, 3]}})
    with pytest.raises(ValueError, match="leading dense layers that are KDA"):
        build_model({**TINY, "first_k_dense_replace": 4})


# ------------------------------------------------- weights and the tables


def test_map_weights_reads_the_checkpoint_s_names(tiny):
    """A checkpoint under the family's tensor names (torch orientation: a
    linear is ``(out, in)``, a convolution ``(C, 1, k)``; q, k, v and the two
    gates' inner projections apart; all eight experts, of which a holder
    reads its own) loads into the tree the model runs."""
    model, params = tiny
    t = lambda w: np.asarray(w).T  # noqa: E731
    hf = {"model.embed_tokens.weight": params["embed"]["weight"],
          "model.norm.weight": params["final_norm"]["weight"],
          "lm_head.weight": t(params["lm_head"]["weight"])}
    width = model.kda_width
    for g, idxs in model.layer_group_layers().items():
        stack = params["layers"][g]
        for rank, i in enumerate(idxs):
            pre = f"model.layers.{i}."
            for suffix, (our, transposed) in model.NAMES[g].items():
                hf[pre + suffix] = t(stack[our][rank]) if transposed else stack[our][rank]
            if g != "mla":
                hf[pre + "self_attn.A_log"] = np.asarray(stack["A_log"][rank]).reshape(1, 1, -1, 1)
                for j, n in enumerate("qkv"):
                    cols = slice(j * width, (j + 1) * width)
                    hf[pre + f"self_attn.{n}_proj.weight"] = t(stack["qkv_proj"][rank][:, cols])
                    hf[pre + f"self_attn.{n}_conv1d.weight"] = np.asarray(
                        stack["conv_w"][rank][cols])[:, None, :]
                for j, n in enumerate(("f_a_proj", "g_a_proj")):
                    hf[pre + f"self_attn.{n}.weight"] = t(
                        stack["gate_a"][rank][:, j * 16 : (j + 1) * 16])
            if g != "dense":
                for our, which in model.EXPERTS.items():
                    for e in range(8):
                        hf[pre + f"block_sparse_moe.experts.{e}.{which}.weight"] = t(stack[our][rank, e])
    hf = {k: np.asarray(v) for k, v in hf.items()}
    jax.tree.map(np.testing.assert_array_equal, model.map_weights(hf, jnp.float32), params)
    holder, _ = build_model(
        {**TINY, "num_experts": 2, "moe_expert_share": 4, "moe_expert_share_index": 3})
    got = holder.map_weights(hf, jnp.float32)["layers"]["mla"]["w_up"]
    np.testing.assert_array_equal(got, params["layers"]["mla"]["w_up"][:, 6:8])


def _published():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    path = Path(__file__).parents[1] / "benchmarks/configs/kimi-linear-48b-bf16-ep16.json"
    return published_config(json.loads(path.read_text()))


def test_step_bytes_of_the_published_configuration():
    """ISSUE 48's arithmetic: 8.59 GB of weights held, of which a step at 40
    rows reads what lies outside the experts and the 11.4 of 16 experts a
    layer its picks hit; 43.4 MB of state a slot; 8064 B of latent a token."""
    cfg = _published()
    assert ref.layer_groups(cfg).count("kda") == 19 and ref.layer_groups(cfg).count("mla") == 7
    need = ref.decode_step_bytes(cfg, "bf16", 40, 40 * 3000)
    units = ref.model_units(cfg)
    held = sum(
        len(idxs) * sum(2 * u.out * max(u.inn, 1) * max(u.experts, 1) for u in units[g].values())
        for g, idxs in ref.group_layers(cfg).items()
    ) + 2 * 2 * 20480 * 2304
    assert 8.5e9 < held < 8.7e9
    assert 2.4e9 < need["fixed_weights"] < 2.7e9
    assert 4.0e9 < need["routed_experts"] < 4.4e9  # 26 x 11.4 x 14.2 MB
    assert need["recurrent_state"] == ref.kda_state_step_bytes(cfg, 40)
    assert need["recurrent_state"] == 2 * 40 * 20 * (32 * 128 * 128 * 4 + 12288 * 3 * 2)
    assert need["kv_pages"] == 40 * 3000 * 7 * 1152 == ref.paged_attn_step_bytes(cfg, 40, 3000)
    assert need["total"] == sum(v for k, v in need.items() if k != "total")


def test_the_seeded_tree_is_the_program_s_and_its_bias_balances(tiny):
    model, params = tiny
    made = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params)
    # fresh inputs choose every expert within a third of its share
    x = jax.random.normal(jax.random.PRNGKey(9), (4096, 64), jnp.float32)
    stack = params["layers"]["kda"]
    scores = jax.nn.sigmoid(x @ stack["router"][0]) + stack["router_bias"][0]
    load = np.bincount(np.asarray(jax.lax.top_k(scores, 2)[1]).ravel(), minlength=8) / 8192
    assert np.abs(load * 8 - 1).max() < 0.33


# ------------------------------------------- the MLA both families call


@pytest.mark.parametrize("mode,yarn,first,digest", [
    ("compressed", True, [-1.1009501218795776, -1.7118580341339111],
     "f522c092f5eaffa46a1348b279fd12ab7042c7051ffa2d93e6acdd89b6dd628a"),
    ("compressed", False, [-0.19437633454799652, 0.05097236484289169],
     "b00a95156e26a843cfcce4d35c9586a9bc32875abbe1fb0ca49517c7113f24ad"),
    ("full", True, [-1.100949764251709, -1.7118587493896484],
     "11116c40c4b87ec719bd747d37433391068267b6b5b1e37671bb09489f251ac9"),
    ("full", False, [-0.19437600672245026, 0.05097194015979767],
     "3ee56a190f48aefd5b71812bf17741d87f3fc3fdce9bea36f14788be2542c447"),
])
def test_deepseek_v2_computes_bit_for_bit_what_it_did_before_the_mla_moved(
        mode, yarn, first, digest):
    """``ops/mla.py`` is ``models/deepseek_v2.py``'s projection math, moved
    and given its rotary as a parameter: on a seeded tiny config (YaRN with a
    plain query, or plain rotary with a factored one; either cache mode) a
    prefill of 18 rows and three decode steps give the logits the parent of
    PR 48 gave, bit for bit (digests taken on that commit, float32 on the
    CPU, op by op)."""
    model, _ = build_model(dict(
        model_type="deepseek_v2", vocab_size=64, hidden_size=32, num_hidden_layers=3,
        num_attention_heads=4, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        kv_lora_rank=16, q_lora_rank=None if yarn else 24, qk_rope_head_dim=8,
        qk_nope_head_dim=16, v_head_dim=16, first_k_dense_replace=1,
        mla_cache_mode=mode, max_position_embeddings=64,
        rope_scaling=dict(type="yarn", factor=4.0, original_max_position_embeddings=16,
                          beta_fast=32, beta_slow=1, mscale=0.707,
                          mscale_all_dim=0.707) if yarn else None,
    ))
    params = model.init_params(jax.random.PRNGKey(7), jnp.float32)
    ids = jnp.asarray(np.random.default_rng(3).integers(1, 64, (2, 21)), jnp.int32)
    cache = model.make_cache(2, 32, jnp.float32)
    logits, cache = model(params, ids[:, :18], cache)
    outs = [logits[:, -1]]
    for t in range(18, 21):
        logits, cache = model(params, ids[:, t:t + 1], cache)
        outs.append(logits[:, -1])
    out = np.asarray(jnp.stack(outs), np.float32)
    assert out[0, 0, :2].tolist() == first
    assert hashlib.sha256(out.tobytes()).hexdigest() == digest
