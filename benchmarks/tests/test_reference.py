"""The plain reference and the seeded generator, at a toy width on the CPU,
against the program's own model called directly (no server): guards
``benchmarks/reference`` and ``benchmarks/weights`` at no chip time. The
served path through the launcher is in test_runner.py."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights as W
from benchmarks.launcher import program_config, published_config
from benchmarks.reference import deepseek_v2 as ref

ROOT = Path(__file__).resolve().parents[2]
TINY = json.loads((ROOT / "benchmarks/configs/tiny-q4.json").read_text())
SEED = 2**31 + 12345  # the driver's seeds are large


def program_logprobs(fmt, ids):
    """Log-probabilities of the program's model over ``ids`` (one prefill)."""
    from mlx_sharding_tpu.cache import KVCache
    from mlx_sharding_tpu.models import build_model

    config = dict(TINY, bench=dict(TINY["bench"], weight_format=fmt))
    model, cfg = build_model(program_config(config))
    model.compute_dtype = jnp.float32
    params = jax.tree.map(
        jnp.asarray, ref.program_params(published_config(config), fmt, SEED),
        is_leaf=lambda x: isinstance(x, W.LazyStack),
    )
    kd, vd = model.cache_head_dim()
    shape = (cfg.num_hidden_layers, 1, 64, model.cache_num_heads())
    cache = KVCache(k=jnp.zeros((*shape, kd), jnp.float32),
                    v=jnp.zeros((*shape, vd), jnp.float32),
                    offset=jnp.zeros((), jnp.int32))
    logits, _ = model(params, jnp.asarray(ids[None]), cache)
    return jax.nn.log_softmax(logits[0].astype(jnp.float32), -1)


@pytest.fixture(scope="module")
def served_q4():
    ids = np.random.default_rng(0).integers(1, TINY["vocab_size"], 40)
    rows = np.arange(28, 40)
    top_v, top_i = jax.lax.top_k(program_logprobs("q4", ids)[rows], 10)
    return ids, rows, np.asarray(top_v), np.asarray(top_i)


def test_reference_agrees_with_the_program_in_float32(served_q4):
    """4-bit weights dequantize exactly, and the CPU computes the program in
    float32 here, so the two forward passes differ by rounding order only.
    1e-4 is 25 times the worst seen (4e-6) and a thousand times under what
    either negative control reads."""
    ids, rows, top_v, top_i = served_q4
    ref_i, ref_v, at = ref.forward(published_config(TINY), "q4", SEED, ids, rows, top_i)
    assert np.abs(top_v - at).max() < 1e-4
    assert (ref_i[:, 0] == top_i[:, 0]).all()
    assert ref_v.shape == (len(rows), 20)


@pytest.mark.parametrize("fault", ["shift_cache", "shift_cache_one", "experts_3bit", "experts_2bit"])
def test_negative_controls_are_caught(served_q4, fault):
    ids, rows, top_v, top_i = served_q4
    _, _, at = ref.forward(published_config(TINY), "q4", SEED, ids, rows, top_i, fault=fault)
    per_row = np.abs(top_v - at).max(axis=1)
    assert np.median(per_row) > 0.05, per_row


def test_bf16_format_agrees_within_bf16_rounding():
    """bf16 weights and bf16 activations in the program against float32 in
    the reference: the worst of 12 rows read 0.09 at this width; 0.25 leaves
    room and is under the cache-shift control (0.13-0.45 per row, median
    0.23 > tolerance on the median 0.12)."""
    ids = np.random.default_rng(1).integers(1, TINY["vocab_size"], 40)
    rows = np.arange(28, 40)
    top_v, top_i = jax.lax.top_k(program_logprobs("bf16", ids)[rows], 10)
    _, _, at = ref.forward(published_config(TINY), "bf16", SEED, ids, rows, np.asarray(top_i))
    per_row = np.abs(np.asarray(top_v) - at).max(axis=1)
    assert per_row.max() < 0.25 and np.median(per_row) < 0.12, per_row


def test_every_unit_is_a_function_of_seed_and_name_only():
    cfg = published_config(TINY)
    a = ref.program_params(cfg, "q4", SEED)["layers"]["moe"]["w_up"]["q"]
    whole = np.asarray(a[:])
    assert whole.shape == a.shape and a.nbytes == whole.nbytes
    np.testing.assert_array_equal(np.asarray(a[1:2]), whole[1:2])
    other = ref.program_params(cfg, "q4", SEED + 1)["layers"]["moe"]["w_up"]["q"]
    assert (np.asarray(other[:1]) != whole[:1]).any()
    unit = ref.model_units(cfg)["moe"]["w_up"]
    skey = W.seed_key(SEED)
    first = ref.group_ranges(cfg)["moe"][0]
    one = W.packed_q(W.unit_key(skey, "w_up", first + 1, 2), unit.out, unit.inn)
    np.testing.assert_array_equal(np.asarray(one), whole[1, 2])


def test_yarn_tables_match_the_published_constants():
    cfg = json.loads((ROOT / "benchmarks/configs/dsv2-lite-q4.json").read_text())
    inv_freq, cos_scale, softmax_scale = ref.rope_tables(cfg)
    assert cos_scale == pytest.approx(1.0)
    mscale = 0.1 * 0.707 * np.log(40) + 1.0
    assert softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2)
    inv = np.asarray(inv_freq)
    assert inv[0] == pytest.approx(1.0)  # fastest dimension: untouched
    assert inv[-1] == pytest.approx(10000 ** (-62 / 64) / 40)  # slowest: / factor
