"""``afmoe`` (gated GQA with window and full layers side by side, sigmoid
top-k MoE with a shared expert) on the served path, against its plain
reference (``benchmarks/reference/afmoe.py``) at tiny widths on the CPU,
with the benchmark's seeded weights on both sides.

Sizes: window 16, pages and chunks of 8, so a slot's ring in a window layer
is ``(16 + 8) / 8 + 1 = 4`` pages = 32 rows; contexts run to 100 positions,
which crosses the window by ten pages and wraps the ring three times. The
one full layer of five keeps full-length pages.

Tolerances. Both sides hold the same bf16-valued weights and compute in
float32 (the tests' ``jax_default_matmul_precision`` is ``highest``), so
what separates them is the order of sums: a ring and pages against no cache,
chunks against one pass. Log-probabilities then agree to ~1e-5; ``LP_TOL`` =
2e-3 leaves two orders of magnitude and is two below what a lost window
row, a stale ring row or rotary on the wrong layer kind shows.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.reference import afmoe as ref
from mlx_sharding_tpu.cache import window_ring_rows
from mlx_sharding_tpu.generate import Generator, TokenLogprobs
from mlx_sharding_tpu.models import build_model
from mlx_sharding_tpu.ops import paged_attention as paged_ops
from mlx_sharding_tpu.parallel.mesh import make_mesh
from mlx_sharding_tpu.parallel.pipeline import PipelineEngine
from mlx_sharding_tpu.scheduler import ContinuousBatcher
from tests.helpers import hard_timeout, run_concurrent

LP_TOL = 2e-3
SEED = 11
WINDOW, PAGE, MAX_SEQ = 16, 8, 128
S, F = "sliding_attention", "full_attention"
TINY = dict(
    model_type="afmoe", vocab_size=256, hidden_size=64, intermediate_size=96,
    num_hidden_layers=5, num_dense_layers=1, layer_types=[S, S, F, S, S],
    num_attention_heads=12, num_key_value_heads=2, head_dim=16,
    sliding_window=WINDOW, rope_theta=10000.0, rms_norm_eps=1e-5,
    num_experts=16, num_experts_per_tok=4, num_shared_experts=1,
    moe_intermediate_size=32, route_norm=True, route_scale=2.448,
    score_func="sigmoid", n_group=1, topk_group=1, mup_enabled=True,
)
rng = np.random.default_rng(3)
PROMPTS = {
    "short": rng.integers(1, 256, 13).tolist(),  # inside the window: chunks of 8 and 5
    "past-window": rng.integers(1, 256, 50).tolist(),  # wraps the ring in prefill
    "long": rng.integers(1, 256, 75).tolist(),  # two wraps in prefill, a third in decode
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
    """``[(token, {id: log-probability})]`` of one greedy request."""
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
        pool_pages=16 * slots if paged else None, page_size=PAGE if paged else None,
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
    n = 70  # four windows and more
    ids = np.random.default_rng(0).integers(1, 256, n)
    logits, cache = model(params, jnp.asarray(ids)[None], model.make_cache(1, MAX_SEQ, jnp.float32))
    lp = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    wanted = np.argsort(-lp, axis=-1)[:, :8]
    want = reference_at(TINY, ids, list(range(n)), wanted)
    np.testing.assert_allclose(np.take_along_axis(lp, wanted, -1), want, atol=LP_TOL, rtol=0)
    # full-length rows for the one full layer only, rings for the four window layers
    assert cache.k.shape[0] == 1 and cache.state["win_k"].shape[0] == 4


@pytest.mark.parametrize("fault", ["window_off", "rope_on_full", "gate_off", "shift_cache_one"])
def test_the_reference_s_faults_are_seen_at_this_tolerance(tiny, fault):
    """What the tolerance is for: each wrong variant of the reference stands
    far outside it on a context that crosses the window."""
    model, params = tiny
    ids = np.random.default_rng(1).integers(1, 256, 60)
    rows = list(range(40, 60))
    wanted = np.tile(np.arange(8), (len(rows), 1))
    clean = reference_at(TINY, ids, rows, wanted)
    wrong = reference_at(TINY, ids, rows, wanted, fault=fault)
    assert np.abs(wrong - clean).max() > 50 * LP_TOL


def test_the_ring_is_the_window_a_chunk_and_a_page(tiny, batcher):
    eng = batcher.engine
    assert eng.ring_rows == window_ring_rows(WINDOW, PAGE, PAGE, MAX_SEQ) == 32
    assert window_ring_rows(4096, 512, 512, 16384) == 10 * 512  # the cell's
    assert window_ring_rows(4096, 512, 512, 2048) == 2048  # never past the context
    assert eng.layers_per_stage == 1 and eng.state_layers == 4
    cache, _ = eng.init_cache_paged()
    assert cache.k.shape[1] == 1  # one full layer's pages
    # 2 slots + scratch; a row's two heads of 16 merged on the lane axis
    assert cache.state["win_k"].shape == (1, 4, 3, 32, 1, 32)
    assert cache.k.shape[-2:] == (1, 32)


# ------------------------------------------------- through ring and pool


@hard_timeout(900)
@pytest.mark.parametrize("name", list(PROMPTS))
def test_chunked_prefill_then_decode_matches_the_reference(batcher, name):
    """Prefill in chunks, then decode through the ring and the pool:
    log-probabilities against the reference's one full forward pass, on
    contexts that end 25 positions past the prompt."""
    got = served(batcher, PROMPTS[name], 25)
    np.testing.assert_allclose(differences(TINY, PROMPTS[name], got), 0, atol=LP_TOL)


@hard_timeout(900)
def test_the_served_path_with_a_fault_is_not_the_reference(batcher):
    """The comparison above would see a served window that is not applied:
    against the reference WITHOUT its window the served path is far off."""
    got = served(batcher, PROMPTS["past-window"], 12)
    assert np.abs(differences(TINY, PROMPTS["past-window"], got, "window_off")).max() > 50 * LP_TOL


@hard_timeout(900)
def test_slots_join_and_leave_at_different_steps(batcher):
    """Three requests on two slots, each against itself alone: a slot's
    ring is reused by the next request with whatever it held."""
    jobs = {"short": 9, "long": 14, "past-window": 11}
    alone = {k: [t for t, _ in served(batcher, PROMPTS[k], n)] for k, n in jobs.items()}
    outs = run_concurrent(
        batcher, [(PROMPTS[k], dict(max_tokens=n)) for k, n in jobs.items()])
    assert outs == [alone[k] for k in jobs]


@hard_timeout(900)
def test_the_kernel_serves_window_and_full_layers(tiny, monkeypatch):
    """The ragged body's attention through the KERNEL (interpret mode): the
    window layers over the ring table with their window, the full layer
    over its pages; none takes the XLA path, and the log-probabilities are
    the reference's."""
    monkeypatch.setattr(
        paged_ops, "paged_attention",
        functools.partial(paged_ops.paged_attention, interpret=True),
    )
    before = paged_ops.dispatch_counts()
    b = ContinuousBatcher(make_engine(*tiny), decode_block=4)
    try:
        got = served(b, PROMPTS["past-window"], 20)
    finally:
        b.close()
    after = paged_ops.dispatch_counts()
    assert after["xla"] == before["xla"] and after["kernel"] > before["kernel"]
    np.testing.assert_allclose(differences(TINY, PROMPTS["past-window"], got), 0, atol=LP_TOL)


@hard_timeout(900)
def test_the_dense_cache_and_the_solo_generator_agree(tiny, batcher):
    model, params = tiny
    want = [t for t, _ in served(batcher, PROMPTS["past-window"], 10)]
    dense = ContinuousBatcher(make_engine(model, params, paged=False), decode_block=4)
    try:
        assert [t for t, _ in dense.generate_step(PROMPTS["past-window"], max_tokens=10)] == want
    finally:
        dense.close()
    gen = Generator(model, params, max_seq=MAX_SEQ, cache_dtype=jnp.float32,
                    prefill_chunk=PAGE, decode_block=4)
    assert [t for t, _ in gen.generate_step(PROMPTS["past-window"], max_tokens=10)] == want


# ------------------------------------------------ what the pool accounts


@hard_timeout(900)
def test_window_bytes_are_flat_and_full_pages_grow(tiny):
    """A request decoded to 4 x the window (pages claimed as it grows,
    ``overcommit``; by default they are claimed for prompt + max_tokens at
    admission): the window layers' bytes are the same at every length, the
    full layer's pages grow with it, the rows inside the window stop at the
    window, and ring pages are overwritten."""
    from mlx_sharding_tpu.utils.observability import ServingMetrics

    batcher = ContinuousBatcher(make_engine(*tiny), decode_block=4, overcommit=True)
    try:
        _window_accounts(batcher, ServingMetrics(batcher_fn=lambda: batcher))
    finally:
        batcher.close()


def _window_accounts(batcher, metrics):
    eng = batcher.engine
    n_prompt, n_out = 12, 4 * WINDOW
    seen = []
    wraps0 = batcher.window_stats()["ring_wraps"]
    for i, _ in enumerate(batcher.generate_step(PROMPTS["short"][:n_prompt], max_tokens=n_out)):
        if i % 8 == 0:
            seen.append((batcher.window_stats(), batcher.page_stats()[1]))
    bytes_seen = {w["bytes"] for w, _ in seen}
    assert bytes_seen == {eng.state_bytes()} and eng.state_bytes() == (
        4 * 3 * 32 * 2 * 16 * 4 * 2)  # layers x (slots + 1) x rows x heads x dim x f32 x (K, V)
    pages = [p for _, p in seen]
    assert pages == sorted(pages) and pages[-1] >= pages[0] + 4 * WINDOW // PAGE - 2
    live = [w["rows_live"] for w, _ in seen]
    assert max(live) == 4 * WINDOW  # four window layers, one slot: capped at the window
    assert live[0] < 4 * WINDOW
    # 76 positions over 8-row pages: pages 4.. of the request overwrite the ring's 4
    assert batcher.window_stats()["ring_wraps"] - wraps0 == (n_prompt + n_out - 1) // PAGE + 1 - 4
    assert batcher.window_stats()["rows_live"] == 0 and batcher.page_stats()[1] == 0
    text = metrics.render()
    assert f"mst_kv_window_bytes {eng.state_bytes()}" in text
    assert "mst_kv_window_rows_live 0" in text
    assert f"mst_kv_ring_wraps_total {batcher.window_stats()['ring_wraps']}" in text
    assert "mst_state_bytes" not in text  # that family is the recurrent state's


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
def test_what_moves_full_length_pages_only_is_refused_by_name(tiny, flag):
    with pytest.raises(ValueError, match="window layers") as err:
        REFUSED[flag](*tiny)
    assert flag in str(err.value)


def test_solo_generators_and_other_layouts_refuse_too(tiny):
    from mlx_sharding_tpu.speculative import NgramSpeculativeGenerator

    model, params = tiny
    with pytest.raises(ValueError, match=r"--prompt-cache.*window layers"):
        Generator(model, params, prompt_cache=True)
    with pytest.raises(ValueError, match=r"--draft.*window layers"):
        NgramSpeculativeGenerator(model, params)
    devs = jax.devices()
    for kw, what in ((dict(pp=2), "pipeline stages"), (dict(tp=2), "tensor parallelism"),
                     (dict(ep=2), "expert parallelism")):
        mesh = make_mesh(**{"pp": 1, "tp": 1, "ep": 1, **kw}, devices=devs[:2])
        with pytest.raises(ValueError, match=f"{what} (are|is) not wired"):
            PipelineEngine(model, params, mesh, max_seq=MAX_SEQ, prefill_chunk=PAGE)


# --------------------------------------------------------------- the share


@hard_timeout(300)
def test_sixteen_shares_add_up_to_the_uncut_layer(tiny):
    """Each of sixteen holders routes over all 16 experts and computes its
    own one: the routed parts, with the shared expert counted once, are the
    uncut reference's MoE."""
    _, params = tiny
    rank, t = 2, 12
    u = jnp.asarray(np.random.default_rng(6).normal(size=(1, t, 64)), jnp.float32)
    units = ref.model_units(TINY)["moe"]
    with jax.default_matmul_precision("highest"):
        lin = ref._lin(units, "bf16", W.seed_key(SEED), rank, jnp.asarray(False))
        bias = ref.small_vector(W.seed_key(SEED), rank, 16)
        want, picks = ref._moe(TINY, lin, bias, u[0])
    stacks = params["layers"]["moe"]
    experts = ("w_gate", "w_up", "w_down")
    small = {n_: w[rank] for n_, w in stacks.items() if n_ not in experts}
    parts, shared = [], None
    for i in range(16):
        model_i, _ = build_model(dict(
            TINY, num_experts=1, moe_expert_share=16, moe_expert_share_index=i))
        held = {n_: stacks[n_][:, i : i + 1] for n_ in experts}
        out = model_i._moe(small, held, rank, u)[0]
        none_held = {n_: jnp.zeros_like(w) for n_, w in held.items()}
        shared = model_i._moe(small, none_held, rank, u)[0]  # what all compute alike
        parts.append(np.asarray(out - shared))
    np.testing.assert_allclose(sum(parts) + np.asarray(shared), np.asarray(want), atol=2e-4, rtol=0)
    # 12 rows x 4 picks land on most of the 16 experts: most shares add something
    assert sum(np.abs(p).max() > 1e-3 for p in parts) >= len(np.unique(picks)) >= 8


def test_map_weights_reads_the_checkpoint_names_and_the_share():
    """A checkpoint's tensors by the family's names land in the program's
    stacks; a share loads its own experts only."""
    cfg = dict(TINY, num_hidden_layers=2, layer_types=[S, F], num_dense_layers=1,
               num_experts=2, moe_expert_share=2, moe_expert_share_index=1)
    model, _ = build_model(cfg)
    r = np.random.default_rng(0)
    t = lambda *shape: r.normal(size=shape).astype(np.float32)  # noqa: E731
    weights = {"model.embed_tokens.weight": t(256, 64), "model.norm.weight": t(64),
               "lm_head.weight": t(256, 64)}
    for i in range(2):
        pre = f"model.layers.{i}."
        for n_ in ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm",
                   "post_mlp_layernorm"):
            weights[pre + n_ + ".weight"] = t(64)
        for n_, out in (("q_proj", 192), ("k_proj", 32), ("v_proj", 32), ("gate_proj", 192)):
            weights[pre + f"self_attn.{n_}.weight"] = t(out, 64)
        weights[pre + "self_attn.o_proj.weight"] = t(64, 192)
        weights[pre + "self_attn.q_norm.weight"] = t(16)
        weights[pre + "self_attn.k_norm.weight"] = t(16)
    for n_, shape in (("gate_proj", (96, 64)), ("up_proj", (96, 64)), ("down_proj", (64, 96))):
        weights[f"model.layers.0.mlp.{n_}.weight"] = t(*shape)
    pre = "model.layers.1.mlp."
    weights[pre + "router.gate.weight"] = t(4, 64)
    weights[pre + "expert_bias"] = t(4)
    for n_, shape in (("gate_proj", (32, 64)), ("up_proj", (32, 64)), ("down_proj", (64, 32))):
        weights[pre + f"shared_experts.{n_}.weight"] = t(*shape)
        for e in range(4):
            weights[pre + f"experts.{e}.{n_}.weight"] = t(*shape)
    params = model.map_weights(weights, jnp.float32)
    moe = params["layers"]["moe"]
    assert moe["w_gate"].shape == (1, 2, 64, 32) and moe["router"].shape == (1, 64, 4)
    np.testing.assert_array_equal(moe["w_down"][0, 0], weights[pre + "experts.2.down_proj.weight"].T)
    np.testing.assert_array_equal(moe["router_bias"][0], weights[pre + "expert_bias"])
    np.testing.assert_array_equal(
        params["layers"]["dense"]["attn_gate"][0],
        weights["model.layers.0.self_attn.gate_proj.weight"].T)
    logits, _ = model(params, jnp.asarray([[1, 2, 3]]), model.make_cache(1, 16, jnp.float32))
    assert logits.shape == (1, 3, 256) and bool(jnp.isfinite(logits).all())


def test_decode_step_bytes_of_the_published_configuration():
    import json
    from pathlib import Path

    from benchmarks.config import published_config

    cfg = published_config(json.loads(
        (Path(ref.__file__).parents[1] / "configs/trinity-large-bf16-ep16.json").read_text()))
    need = ref.decode_step_bytes(cfg, "bf16", 32, 32 * 12000)
    assert need["total"] == pytest.approx(sum(v for k, v in need.items() if k != "total"))
    # 4096 B a position a layer; four windows of 4096 rows and one full layer of 12000
    assert ref.kv_row_bytes(cfg) == 4096
    assert need["kv_pages"] == ref.paged_attn_step_bytes(cfg, 32, 12000) == (
        32 * (12000 + 4 * 4096) * 4096)
    assert ref.paged_attn_step_bytes(cfg, 32, 1000) == 32 * 5 * 1000 * 4096
    one_expert = 3 * 2 * 3072 * 3072
    # 32 rows x top-4 of 256 hit 39.6 % of the experts, held or not
    assert need["routed_experts"] == pytest.approx(4 * 16 * 0.3958 * one_expert, rel=1e-3)
    # attention 62.91 M, shared 28.31 M, router 0.79 M a MoE layer; dense layer 176.16 M
    assert need["fixed_weights"] == pytest.approx(
        2 * (4 * (62.91e6 + 28.31e6 + 0.786e6) + 62.91e6 + 113.25e6 + 25024 * 3072), rel=2e-3)


def test_the_attention_roofline_reader_counts_window_and_full_rows(monkeypatch):
    """``paged_attn_hbm_share``: per stream min(context, window) rows in the
    four window layers and its context in the full one, contexts off the
    client's log, over the self time a step under the two attention scopes.
    Another family, or a program without the scopes, leaves it out."""
    import json
    from pathlib import Path

    from benchmarks import scope_reduce
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "paged_attn_hbm_share")
    configs = Path(ref.__file__).parents[1] / "configs"
    stream = {"prompt_tokens": 8192, "first": 9.0, "last": 11.0,
              "chunks": [(9.0, 8), (11.0, 8)]}
    ctx = {
        "config": json.loads((configs / "trinity-large-bf16-ep16.json").read_text()),
        "samples": [{"t": 10.0, "slots_active": 2.0}, {"t": 20.0, "slots_active": 2.0}],
        "all_records": [stream, dict(stream), {**stream, "first": None}],
        "trace": {"module_seconds": {"jit_block": [0.16, 0.16], "jit_prefill_chunk": [0.03]}},
        "device": {"kind": "TPU v5 lite"},
    }
    scoped = {"devices": 1, "programs": {"jit_block": {
        "mst.attn.window": {"self_s": 0.04}, "mst.attn.full": {"self_s": 0.02},
        "mst.moe.experts.scan": {"self_s": 0.05}}}}
    monkeypatch.setattr(scope_reduce, "for_run", lambda ctx: scoped)
    need = 2 * (8200 + 4 * 4096) * 4096  # two streams at 8192 + 8 tokens when sampled
    assert read(ctx) == pytest.approx(100 * need / 819e9 / (0.06 / 16), rel=1e-6)
    monkeypatch.setattr(scope_reduce, "for_run", lambda ctx: {"devices": 1, "programs": {}})
    assert read(ctx) is None  # a program from before the scopes
    monkeypatch.setattr(scope_reduce, "for_run", lambda ctx: scoped)
    other = json.loads((configs / "nemotron3-super-bf16-ep4.json").read_text())
    assert read({**ctx, "config": other}) is None  # a family without window layers
