"""``granitemoehybrid`` (Granite 4.0-H: a Mamba-2 or attention mixer AND a
SwiGLU MLP in every block, four multipliers, a tied head) on the served path,
against its plain reference (``benchmarks/reference/granitemoehybrid.py``) at
tiny widths on the CPU, with the benchmark's seeded weights on both sides.

Sizes: pages and chunks of 8, two periods of ``MMAM`` (a run of two Mamba
layers in an inner scan, the attention layer, a run of one), 4 query on 2 K/V
heads of 16, 8 Mamba heads of 16 on one group, state 16, the PUBLISHED
multipliers. Prompts end inside a chunk, one row into a chunk (the
convolution's three taps behind it lie in the chunk before) and on a chunk
border.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so what
separates them is the order of sums: chunks, the chunked (SSD) form and a
per-slot state against one position at a time. ``logits_scaling`` 8 flattens
every distribution, so log-probabilities agree to ~1e-6; ``LP_TOL`` = 6e-6
leaves several times that and is four times under what the reference with its
state rounded to bfloat16 shows (``ssm_state_bf16``, the smallest of the
faults), a thousand under a lost state, a missed multiplier or fp8 weights.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import granitemoehybrid as ref
from mlx_sharding_tpu.generate import Generator
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.ops import paged_attention as paged_ops
from mlx_sharding_tpu.ops.mamba2 import ssd_chunked, ssm_sequential
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout, run_concurrent
from tests.test_afmoe import served  # [(token, {id: log-probability})] of one greedy request

LP_TOL = 6e-6
SEED = 11
PAGE, MAX_SEQ = 8, 64
MULTIPLIERS = dict(attention_multiplier=0.015625, embedding_multiplier=12,
                   residual_multiplier=0.22, logits_scaling=8)
TINY = dict(
    model_type="granitemoehybrid", vocab_size=256, hidden_size=64,
    num_hidden_layers=8, layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
    num_attention_heads=4, num_key_value_heads=2, intermediate_size=96,
    shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
    mamba_n_groups=1, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8,
    mamba_expand=2, mamba_conv_bias=True, mamba_proj_bias=False,
    rms_norm_eps=1e-5, tie_word_embeddings=True, num_local_experts=0,
    num_experts_per_tok=0, position_embedding_type="nope", **MULTIPLIERS,
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


def full_forward(cfg, ids, params=None):
    """Log-probabilities of every position from one call of the model."""
    model, _ = build_model(cfg)
    params = seeded_params(cfg) if params is None else params
    logits, cache = model(
        params, jnp.asarray(ids)[None], model.make_cache(1, MAX_SEQ, jnp.float32))
    return np.asarray(jax.nn.log_softmax(logits[0], axis=-1)), cache


# ------------------------------------------------------------ the model


@hard_timeout(300)
def test_full_forward_matches_the_reference(tiny):
    n = 40
    ids = np.random.default_rng(0).integers(1, 256, n)
    lp, cache = full_forward(TINY, ids, tiny[1])
    wanted = np.argsort(-lp, axis=-1)[:, :8]
    want = reference_at(TINY, ids, list(range(n)), wanted)
    np.testing.assert_allclose(np.take_along_axis(lp, wanted, -1), want, atol=LP_TOL, rtol=0)
    # the two attention layers keep rows (heads merged), the six Mamba layers
    # a state: (H, P, N) float32 and the convolution's 3 inputs x 160
    # channels, flat
    assert tiny[0].periods == 2 and tiny[0].runs == [("mamba", 2), ("attn", 1), ("mamba", 1)]
    assert cache.k.shape == (2, 1, MAX_SEQ, 1, 32)
    assert {k: (v.shape, v.dtype) for k, v in cache.state.items()} == {
        "ssm": ((6, 1, 8, 16, 16), jnp.float32), "conv": ((6, 1, 3 * 160), jnp.float32)}


@pytest.mark.parametrize("name", list(MULTIPLIERS))
def test_each_multiplier_set_to_one_moves_the_logits(name):
    """A port that dropped one of the four would not pass the comparison
    above: the same weights with that multiplier at 1 stand far outside the
    tolerance."""
    ids = np.random.default_rng(0).integers(1, 256, 24)
    lp, _ = full_forward(TINY, ids)
    dropped, _ = full_forward({**TINY, name: 1.0}, ids)
    assert np.abs(dropped - lp).max() > 1000 * LP_TOL


@pytest.mark.parametrize(
    "fault,times", [("ssm_state_reset", 1000), ("attn_scale_default", 1000),
                    ("weights_fp8", 1000), ("ssm_state_bf16", 4)])
def test_the_reference_s_faults_are_seen_at_this_tolerance(fault, times):
    """What the tolerance is for: each wrong variant of the reference stands
    outside it, bfloat16 state (the nearest) four times over."""
    ids = np.random.default_rng(1).integers(1, 256, 40)
    rows = list(range(20, 40))
    wanted = np.tile(np.arange(8), (len(rows), 1))
    clean = reference_at(TINY, ids, rows, wanted)
    wrong = reference_at(TINY, ids, rows, wanted, fault=fault)
    assert np.abs(wrong - clean).max() > times * LP_TOL


def test_the_chunked_form_is_the_sequential_recurrence_at_chunk_256():
    """The published chunk (256) with a ragged last chunk (600 = 256 + 256 +
    88), one B/C group read by every head, a state to start from: the SSD
    form gives the sequential recurrence's outputs and final state. Products
    at ``highest`` precision, float32: the order of sums is all that differs
    (1e-4 of values of order 1)."""
    b, t, h, p, n = 2, 600, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 3.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32, 0.0, 2.5))
    one_group = lambda k: jnp.repeat(  # noqa: E731
        jax.random.normal(k, (b, t, 1, n), jnp.float32), h, axis=2)
    bm, cm = one_group(ks[3]), one_group(ks[4])
    s0 = jax.random.normal(ks[5], (b, h, p, n), jnp.float32)
    y, s = ssd_chunked(x, dt, a, bm, cm, s0, 256)
    y_seq, s_seq = ssm_sequential(x, dt, a, bm, cm, s0)
    np.testing.assert_allclose(y, y_seq, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s, s_seq, atol=2e-4, rtol=2e-4)


def test_the_state_pool_sits_beside_the_pages_of_the_attention_layers(batcher):
    eng = batcher.engine
    assert eng.has_state and eng.has_recurrent and not eng.ring_rows
    assert eng.layers_per_stage == 2 and eng.state_layers == 6
    cache, _ = eng.init_cache_paged()
    # 16 pages + scratch in the two attention layers; a row's two heads of
    # 16 merged on the lane axis
    assert cache.k.shape == (1, 2, 17, 1, PAGE, 1, 32)
    # 2 slots + the scratch row in the six Mamba layers
    assert cache.state["ssm"].shape == (1, 6, 3, 8, 16, 16)
    assert cache.state["conv"].shape == (1, 6, 3, 480)
    assert eng.state_bytes() == 6 * 3 * (8 * 16 * 16 + 480) * 4


# ---------------------------------------------- through pool and state


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks whose borders fall inside and between the
    convolution's taps, then decode through the page pool and the state
    pool: log-probabilities against the reference's one full-sequence
    pass."""
    got = served(batcher, PROMPTS[name], 14)
    np.testing.assert_allclose(differences(TINY, PROMPTS[name], got), 0, atol=LP_TOL)


@hard_timeout(900)
@pytest.mark.parametrize("fault", ["ssm_state_reset", "attn_scale_default"])
def test_the_served_path_with_a_fault_is_not_the_reference(batcher, fault):
    """The comparison above would see a served path that lost its state at
    the hand-over from prefill to decode, or attended at ``head_dim**-0.5``."""
    got = served(batcher, PROMPTS["one-past"], 12)
    assert np.abs(differences(TINY, PROMPTS["one-past"], got, fault)).max() > 100 * LP_TOL


@hard_timeout(900)
def test_the_gather_body_and_the_kernel_agree_with_the_reference(tiny, monkeypatch):
    """The gathered-page decode body and the ragged body through the KERNEL
    (interpret mode, merged heads, the multiplier as its scale): both the
    reference's numbers, and the kernel takes no XLA path."""
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
    assert flag in str(err.value) and "GraniteMoeHybridModel" in str(err.value)


def test_other_layouts_and_unwired_configurations_refuse_by_name(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match=r"--prompt-cache.*recurrent state"):
        Generator(model, params, prompt_cache=True)
    devs = jax.devices()
    for kw, what in (
        (dict(pp=2), r"not wired for granitemoehybrid.*--num-stages 1"),
        (dict(tp=2), "tensor parallelism is not wired for GraniteMoeHybridModel"),
        (dict(ep=2), "expert parallelism is not wired for GraniteMoeHybridModel"),
    ):
        mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=devs[:2])
        with pytest.raises(ValueError, match=what):
            PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)
    with pytest.raises(ValueError, match=r"granitemoehybrid.*--num-stages 1"):
        build_model({**TINY, "start_layer": 0, "end_layer": 4})
    with pytest.raises(ValueError, match="num_local_experts 0"):
        build_model({**TINY, "num_local_experts": 8, "num_experts_per_tok": 2})
    for bad in (dict(position_embedding_type="rope"), dict(attention_bias=True),
                dict(mamba_proj_bias=True)):
        with pytest.raises(ValueError, match="granitemoehybrid is wired for"):
            build_model({**TINY, **bad})
    with pytest.raises(ValueError, match="not wired"):
        build_model({**TINY, "layer_types": ["mamba", "moe"] * 4})


# ------------------------------------------------- weights and the tables


def test_map_weights_reads_the_checkpoint_s_names(tiny):
    """A checkpoint under the family's tensor names (torch orientation: a
    linear is ``(out, in)``, the convolution ``(C, 1, k)``) loads into the
    tree the model runs, row by rank in its group."""
    model, params = tiny
    hf = {"model.embed_tokens.weight": params["embed"]["weight"],
          "model.norm.weight": params["final_norm"]["weight"]}
    names = {g: {**model.SHARED, **model.NAMES[g]} for g in ("mamba", "attn")}
    for g, idxs in model.layer_group_layers().items():
        for rank, i in enumerate(idxs):
            for suffix, (our, transposed) in names[g].items():
                w = params["layers"][g][our][rank]
                hf[f"model.layers.{i}.{suffix}"] = w.T if transposed else w
            if g == "mamba":
                stack = params["layers"][g]
                hf[f"model.layers.{i}.mamba.in_proj.weight"] = jnp.concatenate(
                    [stack["in_proj"][rank], stack["dt_proj"][rank]], axis=-1).T
                hf[f"model.layers.{i}.mamba.conv1d.weight"] = (
                    params["layers"][g]["conv_w"][rank][:, None, :])
    got = model.map_weights({k: np.asarray(v) for k, v in hf.items()}, jnp.float32)
    jax.tree.map(np.testing.assert_array_equal, got, params)


def _published():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    path = Path(__file__).parents[1] / "benchmarks/configs/granite4-h-micro-bf16.json"
    return published_config(json.loads(path.read_text()))


def test_step_bytes_of_the_published_configuration():
    """ISSUE 44's arithmetic: 3.19 B parameters (6.38 GB in bf16, the table
    once), 75.5 MB of SSM state a slot, 8192 B of K/V a token."""
    cfg = _published()
    need = ref.decode_step_bytes(cfg, "bf16", 48, 48 * 2500)
    assert 6.37e9 < need["fixed_weights"] < 6.40e9
    assert need["recurrent_state"] == ref.ssm_state_step_bytes(cfg, 48)
    assert need["recurrent_state"] == 2 * 48 * 36 * (64 * 64 * 128 * 4 + 4352 * 3 * 2)
    assert 7.3e9 < need["recurrent_state"] < 7.4e9
    assert need["kv_pages"] == 48 * 2500 * 8192 == ref.paged_attn_step_bytes(cfg, 48, 2500)
    assert need["total"] == sum(v for k, v in need.items() if k != "total")


def test_the_seeded_tree_is_the_program_s(tiny):
    model, params = tiny
    made = model.init_params(jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree.map(jnp.shape, made) == jax.tree.map(jnp.shape, params)


# ------------------------------------------- the mixer both families call


def test_nemotron_h_computes_bit_for_bit_what_it_did_before_the_mixer_moved():
    """``ops/mamba2.py`` is ``models/nemotron_h.py``'s Mamba-2, moved: on a
    seeded tiny config a ragged chunked prefill (18 rows at chunk 8) and
    three decode steps give the logits and the final SSM state the parent
    of PR 44 gave, bit for bit (digests taken on that commit, float32 on the
    CPU, op by op)."""
    import hashlib

    model, _ = build_model(dict(
        model_type="nemotron_h", vocab_size=64, hidden_size=32,
        num_hidden_layers=4, hybrid_override_pattern="ME*M",
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=8,
        conv_kernel=4, chunk_size=8, n_routed_experts=2,
        num_experts_per_tok=2, moe_intermediate_size=16, moe_latent_size=16,
        moe_shared_expert_intermediate_size=16,
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
    digest = lambda a: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()  # noqa: E731
    assert out[0, 0, :2].tolist() == [1.0993270874023438, 0.9874696135520935]
    assert digest(out) == "5a27c8cace84519ad54e8ea336f859cc9629dfedfb3cf17f07f61932a0c846b4"
    assert digest(cache.state["ssm"]) == (
        "4da3763009b7d78d620cac53b1536737c7fe0aecdb063fea9ebcdde8918c2498")
