"""``zaya`` (attention in a compressed latent behind two causal convolutions
and a value shift; top-1 experts chosen by an MLP router whose state runs
down the layers) on the served path, against its plain reference
(``benchmarks/reference/zaya.py``) at tiny widths on the CPU, with the
benchmark's seeded weights on both sides.

Sizes: pages and chunks of 8, four layers, 4 query on 2 K/V heads of 16, 16
experts top-1. Prompts end inside a chunk, one row into a chunk (the first
convolution's tap behind it lies in the chunk before, the second's two
chunks' state) and on a chunk border, so the per-slot state is read at every
place a border can fall between the two convolutions' taps.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so
what separates them is the order of sums: chunks and a per-slot state
against one full-sequence pass. Log-probabilities then agree to ~1e-5;
``LP_TOL`` = 2e-3 leaves two orders of magnitude and is two below what a
lost convolution tail, a value half from the wrong token or a router that
forgot the layer above shows.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import zaya as ref
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.ops import moe as moe_ops
from mlx_sharding_tpu.ops import paged_attention as paged_ops
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout, run_concurrent
from tests.test_afmoe import served  # [(token, {id: log-probability})] of one greedy request

LP_TOL = 2e-3
SEED = 11
PAGE, MAX_SEQ = 8, 64
TINY = dict(
    model_type="zaya", vocab_size=256, hidden_size=64, num_hidden_layers=4,
    layer_types=["hybrid"] * 4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
    rope_parameters={"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                                "rope_type": "default"}, "rope_type": "default"},
    rms_norm_eps=1e-5, moe_intermediate_size=32, num_experts=16,
    num_experts_per_tok=1, router_hidden_size=16, tie_word_embeddings=True,
)
rng = np.random.default_rng(3)
PROMPTS = {
    "inside": rng.integers(1, 256, 13).tolist(),  # chunks of 8 and 5
    "one-past": rng.integers(1, 256, 17).tolist(),  # 8, 8 and ONE row: both taps behind a border
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
    n = 40
    ids = np.random.default_rng(0).integers(1, 256, n)
    logits, cache = model(params, jnp.asarray(ids)[None], model.make_cache(1, MAX_SEQ, jnp.float32))
    lp = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    wanted = np.argsort(-lp, axis=-1)[:, :8]
    want = reference_at(TINY, ids, list(range(n)), wanted)
    np.testing.assert_allclose(np.take_along_axis(lp, wanted, -1), want, atol=LP_TOL, rtol=0)
    # every layer keeps rows (heads merged) AND a state: two convolution tails, half a value
    assert cache.k.shape == (4, 1, MAX_SEQ, 1, 32)
    assert {k: v.shape for k, v in cache.state.items()} == {
        "cca_u": (4, 1, 96), "cca_c1": (4, 1, 96), "v_prev": (4, 1, 16)}


@pytest.mark.parametrize(
    "fault", ["conv_state_reset", "value_shift_off", "qk_mean_off", "depth_state_off",
              "shift_cache_one"])
def test_the_reference_s_faults_are_seen_at_this_tolerance(fault):
    """What the tolerance is for: each wrong variant of the reference stands
    far outside it. ``conv_state_reset`` loses the state at position 0 of a
    512-row chunk only, so the rows compared are decode steps."""
    ids = np.random.default_rng(1).integers(1, 256, 40)
    rows = list(range(20, 40))
    wanted = np.tile(np.arange(8), (len(rows), 1))
    clean = reference_at(TINY, ids, rows, wanted)
    wrong = reference_at(TINY, ids, rows, wanted, fault=fault)
    assert np.abs(wrong - clean).max() > 50 * LP_TOL


def test_the_state_pool_sits_beside_the_pages_of_every_layer(batcher):
    eng = batcher.engine
    assert eng.has_state and eng.has_recurrent and not eng.ring_rows
    assert eng.layers_per_stage == 4 and eng.state_layers == 4
    cache, _ = eng.init_cache_paged()
    # 16 pages + scratch; a row's two heads of 16 merged on the lane axis
    assert cache.k.shape == (1, 4, 17, 1, PAGE, 1, 32)
    # 2 slots + the scratch row
    assert cache.state["cca_u"].shape == (1, 4, 3, 96)
    assert cache.state["v_prev"].shape == (1, 4, 3, 16)
    assert eng.state_bytes() == 4 * 3 * (96 + 96 + 16) * 4


# ---------------------------------------------- through pool and state


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks whose borders fall inside and between the
    convolutions' taps, then decode through the page pool and the per-slot
    state: log-probabilities against the reference's one full-sequence
    pass."""
    got = served(batcher, PROMPTS[name], 14)
    np.testing.assert_allclose(differences(TINY, PROMPTS[name], got), 0, atol=LP_TOL)


@hard_timeout(900)
@pytest.mark.parametrize("fault", ["conv_state_reset", "value_shift_off"])
def test_the_served_path_with_a_fault_is_not_the_reference(batcher, fault):
    """The comparison above would see a served path that lost its state or
    took the value half from the wrong token."""
    got = served(batcher, PROMPTS["one-past"], 12)
    assert np.abs(differences(TINY, PROMPTS["one-past"], got, fault)).max() > 50 * LP_TOL


@hard_timeout(900)
def test_the_gather_body_and_the_kernel_agree_with_the_reference(tiny, monkeypatch):
    """The gathered-page decode body (each slot's contiguous view, the state
    under ``vmap``) and the ragged body through the KERNEL (interpret mode,
    merged heads): both the reference's numbers, and the kernel takes no XLA
    path."""
    gather = ContinuousBatcher(
        make_engine(*tiny, paged_attention="gather"), decode_block=4)
    try:
        assert gather.engine.paged_attention == "gather"
        got = served(gather, PROMPTS["one-past"], 10)
    finally:
        gather.close()
    np.testing.assert_allclose(differences(TINY, PROMPTS["one-past"], got), 0, atol=LP_TOL)
    monkeypatch.setattr(
        paged_ops, "paged_attention",
        functools.partial(paged_ops.paged_attention, interpret=True),
    )
    before = paged_ops.dispatch_counts()
    b = ContinuousBatcher(make_engine(*tiny), decode_block=4)
    try:
        got = served(b, PROMPTS["one-past"], 10)
    finally:
        b.close()
    after = paged_ops.dispatch_counts()
    assert after["xla"] == before["xla"] and after["kernel"] > before["kernel"]
    np.testing.assert_allclose(differences(TINY, PROMPTS["one-past"], got), 0, atol=LP_TOL)


@hard_timeout(900)
def test_a_slot_joins_beside_decoding_slots_and_a_reused_slot_starts_from_zero(batcher):
    """Three requests on two slots, each against itself alone: the third
    joins while another decodes (its chunks run between the other's decode
    blocks, which must leave its state alone) and takes a slot whose state
    its last occupant left behind."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    jobs = {"inside": 9, "border": 14, "one-past": 11}
    alone = {k: [t for t, _ in served(batcher, PROMPTS[k], n)] for k, n in jobs.items()}
    resets0 = batcher.state_stats()["resets"]
    outs = run_concurrent(
        batcher, [(PROMPTS[k], dict(max_tokens=n)) for k, n in jobs.items()])
    assert outs == [alone[k] for k in jobs]
    # one reset a join: a request's first chunk starts its slot from zero
    assert batcher.state_stats()["resets"] - resets0 == 3
    text = ServingMetrics(batcher_fn=lambda: batcher).render()
    assert f"mst_state_bytes {batcher.engine.state_bytes()}" in text
    assert "mst_state_slots_in_use 0" in text
    assert f"mst_state_resets_total {batcher.state_stats()['resets']}" in text


@hard_timeout(900)
def test_the_dense_cache_and_the_solo_generator_agree(tiny, batcher):
    model, params = tiny
    want = [t for t, _ in served(batcher, PROMPTS["one-past"], 10)]
    dense = ContinuousBatcher(make_engine(model, params, paged=False), decode_block=4)
    try:
        assert [t for t, _ in dense.generate_step(PROMPTS["one-past"], max_tokens=10)] == want
    finally:
        dense.close()
    gen = Generator(model, params, max_seq=MAX_SEQ, cache_dtype=jnp.float32,
                    prefill_chunk=PAGE, decode_block=4)
    assert [t for t, _ in gen.generate_step(PROMPTS["one-past"], max_tokens=10)] == want


@hard_timeout(600)
def test_the_decode_block_names_its_scopes_and_moves_no_pool(batcher):
    """Each new scope is in the lowered text of ``block``; and the ragged
    body's layer scan carries the page pool whole: no equation inside it
    slices a layer's pages out of the pool or puts them back."""
    from tests.test_program_names import _scopes_in, _walk

    b, eng = batcher, batcher.engine
    assert len(list(b.generate_step([3, 4, 5, 6], max_tokens=5))) == 5
    args = (eng.layer_params, eng.layer_masks, eng.vocab_parts, eng.shared_params,
            b.last_tok, b.cache, b.active, b.recent, b.keys, b.sp, b.rep_sizes, b.table)
    prog = b._decode_block_prog(False)
    text = prog.lower(*args).as_text(debug_info=True)
    assert "module @jit_block " in text
    assert {"mst.attn.cca_mix", "mst.attn.core", "mst.moe.router",
            "mst.state_pool.regroup"} <= _scopes_in(text)
    # the layer scan carries the pool: nothing of it is regrouped
    assert "mst.kv_pool.regroup" not in _scopes_in(text)
    pool = b.cache.k.shape[1:]  # (L, P+1, B, page, 1, Hkv * D)
    one_layer = int(np.prod(pool[1:]))
    moved = [
        (eqn.primitive.name, [v.aval.shape for v in eqn.outvars])
        for eqn, scans in _walk(jax.make_jaxpr(prog)(*args).jaxpr)
        if scans and eqn.primitive.name in (
            "dynamic_slice", "dynamic_update_slice", "gather", "concatenate", "select_n")
        and any(v.aval.shape[-3:] == pool[-3:] and int(np.prod(v.aval.shape)) >= one_layer
                for v in eqn.outvars)
    ]
    assert not moved, moved


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
    assert flag in str(err.value)


def test_solo_generators_and_other_layouts_refuse_too(tiny):
    from mlx_sharding_tpu.speculative import NgramSpeculativeGenerator

    model, params = tiny
    with pytest.raises(ValueError, match=r"--prompt-cache.*recurrent state"):
        Generator(model, params, prompt_cache=True)
    with pytest.raises(ValueError, match=r"--draft.*recurrent state"):
        NgramSpeculativeGenerator(model, params)
    devs = jax.devices()
    for kw, what in ((dict(pp=2), r"--num-stages 1"),
                     (dict(tp=2), "tensor parallelism is not wired"),
                     (dict(ep=2), "expert parallelism is not wired")):
        mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=devs[:2])
        with pytest.raises(ValueError, match=what):
            PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)
    for bad in (dict(cca_time0=3), dict(sliding_window=4096),
                dict(layer_types=["hybrid", "hybrid_sliding"] * 2)):
        with pytest.raises(ValueError, match="zaya is wired for"):
            build_model({**TINY, **bad})


# ------------------------------------------------- routing and the share


def test_the_gate_weighs_by_probability_and_chooses_with_the_bias():
    logits = jnp.asarray([[2.0, 1.9, 0.0, -1.0], [0.0, 0.1, 0.2, 3.0]])
    bias = jnp.asarray([0.0, 0.5, 0.0, 0.0])
    p = np.asarray(jax.nn.softmax(logits, axis=-1))
    w, idx = moe_ops.biased_softmax_routing(logits, bias, 1)
    # row 0: the bias turns the choice to expert 1, whose weight is its own
    # probability and not probability + bias; row 1: the bias changes nothing
    assert np.asarray(idx).tolist() == [[1], [3]]
    np.testing.assert_allclose(np.asarray(w)[:, 0], [p[0, 1], p[1, 3]], rtol=1e-6)
    w2, idx2 = moe_ops.biased_softmax_routing(logits, bias, 2)
    # top-2: the bias makes expert 1 row 1's second choice, at its own small probability
    assert np.asarray(idx2).tolist() == [[1, 0], [3, 1]]
    np.testing.assert_allclose(np.asarray(w2), [[p[0, 1], p[0, 0]], [p[1, 3], p[1, 1]]], rtol=1e-6)


@hard_timeout(300)
def test_two_shares_add_up_to_the_uncut_layer(tiny):
    """Each of two holders routes over all 16 experts and computes its own
    eight: the two MoE sub-layers' outputs add up to the uncut reference's,
    and the router's state — what both compute alike, as they do attention —
    is the same on both and counted once."""
    _, params = tiny
    rank, t = 2, 12
    r = np.random.default_rng(6)
    x = jnp.asarray(r.normal(size=(1, t, 64)), jnp.float32)
    s_prev = jnp.asarray(r.normal(size=(1, t, 16)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        cfg, mat, _, nrm = ref._parts(ref.hashable(TINY), W.seed_key(SEED), rank,
                                      jnp.asarray(False))
        h = ref.rmsnorm(x[0], nrm("moe_norm"), 1e-5)
        bias = ref.balancing_biases(ref.hashable(TINY), SEED)[rank]
        want, s_want, picks = ref._moe(cfg, mat, bias, nrm, h, s_prev[0])
    stacks = params["layers"]
    small = {n_: w[rank] for n_, w in stacks.items() if n_ not in ref.EXPERTS}
    parts = []
    for i in range(2):
        model_i, _ = build_model(dict(
            TINY, num_experts=8, moe_expert_share=2, moe_expert_share_index=i))
        held = {n_: stacks[n_][:, 8 * i : 8 * i + 8] for n_ in ref.EXPERTS}
        out, s = model_i._moe({**small, **held, "layer": rank}, x, s_prev)
        np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s_want), atol=2e-5, rtol=0)
        parts.append(np.asarray(out[0]))
    np.testing.assert_allclose(sum(parts), np.asarray(want), atol=2e-5, rtol=0)
    # top-1: a row's expert lives on ONE holder, the other adds nothing to it
    on_first = np.asarray(picks)[:, 0] < 8
    assert 0 < on_first.sum() < t
    assert np.abs(parts[1][on_first]).max() == 0 and np.abs(parts[0][~on_first]).max() == 0


def test_map_weights_reads_the_convolutions_and_the_share():
    """A checkpoint's tensors land in the program's stack: the torch
    ``Conv1d`` weights as taps and per-head matrices; a share loads its own
    experts only; the tied head needs no ``lm_head``."""
    cfg = dict(TINY, num_hidden_layers=1, layer_types=["hybrid"], num_experts=2,
               moe_expert_share=2, moe_expert_share_index=1)
    model, _ = build_model(cfg)
    r = np.random.default_rng(0)
    t = lambda *shape: r.normal(size=shape).astype(np.float32)  # noqa: E731
    weights = {"model.embed_tokens.weight": t(256, 64), "model.norm.weight": t(64)}
    pre = "model.layers.0."
    shapes = {
        "input_layernorm.weight": (64,), "post_attention_layernorm.weight": (64,),
        "attn_res_scale": (64,), "mlp_res_scale": (64,),
        "self_attn.q_proj.weight": (64, 64), "self_attn.k_proj.weight": (32, 64),
        "self_attn.v_proj.weight": (16, 64), "self_attn.v_shift_proj.weight": (16, 64),
        "self_attn.o_proj.weight": (64, 64),
        "self_attn.conv_qk.0.weight": (96, 1, 2), "self_attn.conv_qk.0.bias": (96,),
        "self_attn.conv_qk.1.weight": (96, 16, 2), "self_attn.conv_qk.1.bias": (96,),
        "self_attn.temp": (2,), "mlp.router.down_proj.weight": (16, 64),
        "mlp.router.depth_gate": (16,), "mlp.router.norm.weight": (16,),
        "mlp.router.mlp.0.weight": (16, 16), "mlp.router.mlp.1.weight": (16, 16),
        "mlp.router.mlp.2.weight": (4, 16), "mlp.router.balancing_bias": (4,),
    }
    for name, shape in shapes.items():
        weights[pre + name] = t(*shape)
    for e in range(4):
        for n_, shape in (("gate_proj", (32, 64)), ("up_proj", (32, 64)), ("down_proj", (64, 32))):
            weights[pre + f"mlp.experts.{e}.{n_}.weight"] = t(*shape)
    params = model.map_weights(weights, jnp.float32)
    layers = params["layers"]
    assert layers["w_gate"].shape == (1, 2, 64, 32) and layers["router_w3"].shape == (1, 16, 4)
    np.testing.assert_array_equal(layers["w_down"][0, 0], weights[pre + "mlp.experts.2.down_proj.weight"].T)
    c0, c1 = weights[pre + "self_attn.conv_qk.0.weight"], weights[pre + "self_attn.conv_qk.1.weight"]
    np.testing.assert_array_equal(layers["conv0_w"][0, 1], c0[:, 0, 1])  # tap 1: the current row
    # out channel 21 = head 1, channel 5: (tap 0, head 1)[in 3, out 5] is its weight on channel 16 + 3
    np.testing.assert_array_equal(layers["conv1_w"][0, 0 * 6 + 1, 3, 5], c1[21, 3, 0])
    assert "lm_head" not in params and layers["router_bias"].dtype == jnp.float32
    logits, _ = model(params, jnp.asarray([[1, 2, 3]]), model.make_cache(1, 16, jnp.float32))
    assert logits.shape == (1, 3, 256) and bool(jnp.isfinite(logits).all())
    fresh = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, fresh) == jax.tree.map(jnp.shape, params)


# ------------------------------------------------- the benchmark's tables


def _published():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    return published_config(json.loads(
        (Path(ref.__file__).parents[1] / "configs/zaya1-8b-bf16-pp2ep2.json").read_text()))


def test_decode_step_bytes_of_the_published_configuration():
    cfg = _published()
    need = ref.decode_step_bytes(cfg, "bf16", 24, 24 * 10000)
    assert need["total"] == pytest.approx(sum(v for k, v in need.items() if k != "total"))
    # 1024 B a position a layer: K and V, 2 heads of 128, bf16
    assert ref.kv_row_bytes(cfg) == 1024
    assert need["kv_pages"] == ref.paged_attn_step_bytes(cfg, 24, 10000) == 24 * 10000 * 20 * 1024
    one_expert = 3 * 2 * 2048 * 2048
    # 24 rows x top-1 of 16 hit 78.8 % of the experts, held or not: 6.3 of the 8 held
    assert need["routed_experts"] == pytest.approx(20 * 8 * 0.7875 * one_expert, rel=1e-3)
    # attention 5.57 M (projections 5.24, convolutions 0.33), router 0.66 M a layer; the table once
    assert need["fixed_weights"] == pytest.approx(
        2 * (20 * (5.243e6 + 0.330e6 + 0.660e6) + 131136 * 2048), rel=2e-3)


@pytest.mark.parametrize("seed", [SEED, 4200000401])
def test_the_seeded_balancing_bias_balances_its_router(seed):
    """Every seed does the same work: under its layer's bias the seeded
    router chooses each of the 16 experts about equally often on rows it was
    not fitted on, which with no bias it does not; the program's tree holds
    the reference's numbers."""
    items = ref.hashable(TINY)
    biases = ref.balancing_biases(items, seed)
    assert biases.shape == (4, 16) and biases.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(ref.program_params(TINY, "bf16", seed)["layers"]["router_bias"]), np.asarray(biases))
    skey = W.seed_key(seed)
    for rank in range(4):
        _, mat, _, nrm = ref._parts(items, skey, rank, jnp.asarray(False))
        s = jax.random.normal(jax.random.PRNGKey(rank), (4096, 16), jnp.float32)
        with jax.default_matmul_precision("highest"):
            p = ref._router(TINY, mat, nrm, s)
        load = lambda b: np.bincount(np.asarray(jnp.argmax(p + b, -1)), minlength=16) * 16 / 4096  # noqa: E731
        assert np.abs(load(biases[rank]) - 1).max() < 0.35 < 1.0 < np.abs(load(0.0) - 1).max()


def test_the_seeded_tree_is_the_program_s(tiny):
    """``program_params`` has the leaves ``init_params`` has, shape for
    shape: what the launcher hands the engine is what ``map_weights`` would."""
    model, params = tiny
    fresh = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, fresh) == jax.tree.map(jnp.shape, params)


def test_the_attention_roofline_reader_counts_every_layer_s_rows(monkeypatch):
    """``attn_core_hbm_share``: per stream its context's rows in every layer,
    contexts off the client's log, over ``mst.attn.core``'s part of a step
    (its share of ``jit_block``'s self time, times the median block / 8). A family without the function, or a
    program without the scope, leaves it out."""
    import json
    from pathlib import Path

    from benchmarks import scope_reduce
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "attn_core_hbm_share")
    configs = Path(ref.__file__).parents[1] / "configs"
    stream = {"prompt_tokens": 8192, "first": 9.0, "last": 11.0,
              "chunks": [(9.0, 8), (11.0, 8)]}
    ctx = {
        "config": json.loads((configs / "zaya1-8b-bf16-pp2ep2.json").read_text()),
        "samples": [{"t": 10.0, "slots_active": 2.0}, {"t": 20.0, "slots_active": 2.0}],
        "all_records": [stream, dict(stream), {**stream, "first": None}],
        "trace": {"module_seconds": {"jit_block": [0.16, 0.16], "jit_prefill_chunk": [0.03]}},
        "device": {"kind": "TPU v5 lite"},
    }
    scoped = {"devices": 1, "programs": {"jit_block": {
        "mst.attn.core": {"self_s": 0.06}, "mst.attn.cca_mix": {"self_s": 0.01},
        "mst.moe.experts.scan": {"self_s": 0.05}}}}
    monkeypatch.setattr(scope_reduce, "for_run", lambda ctx: scoped)
    need = 2 * 8200 * 20 * 1024  # two streams at 8192 + 8 tokens when sampled
    # the scope is 0.06 of the block's 0.12 s of self time: half of a 20 ms step
    assert read(ctx) == pytest.approx(100 * need / 819e9 / (0.16 / 8 * 0.5), rel=1e-6)
    monkeypatch.setattr(scope_reduce, "for_run", lambda ctx: {"devices": 1, "programs": {}})
    assert read(ctx) is None  # a program from before the scope
    monkeypatch.setattr(scope_reduce, "for_run", lambda ctx: scoped)
    other = json.loads((configs / "dsv2-lite-q4.json").read_text())
    assert read({**ctx, "config": other}) is None  # a family without the function
