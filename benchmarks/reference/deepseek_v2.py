"""Plain reference for DeepSeek-V2(-Lite): the forward pass in ``jax.numpy``
and float32, matrix products at ``highest`` precision, no cache, no kernel,
no batching. Written from the published equations (DeepSeek-V2, arXiv
2405.04434, and the model's own ``modeling_deepseek.py``), not from
``mlx_sharding_tpu/models/deepseek_v2.py``:

- **MLA with decoupled rope.** ``q = x W_q`` split per head into a 128-wide
  no-position part and a 64-wide rope part; ``c = rmsnorm(x W_kva[:512])``
  is the latent, ``k_pe = rope(x W_kva[512:])`` the one rope key shared by
  all heads; ``[k_nope, v] = c W_kvb`` per head; scores over ``[q_nope,
  q_pe]·[k_nope, k_pe]``. Here keys and values are decompressed for every
  position (the served path keeps only the latent and absorbs ``W_kvb``
  into the query and output sides: same mathematics, other order).
- **Rope** rotates pairs ``(x[2i], x[2i+1])`` by ``pos * inv_freq[i]``.
  YaRN: ``inv_freq`` blends ``base**(-2i/d)`` and the same over ``factor``
  with a linear ramp between the dimensions that complete ``beta_fast`` and
  ``beta_slow`` turns within the original length; cos/sin are scaled by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 for the
  published config) and the softmax scale by ``mscale(factor,
  mscale_all_dim)**2``, with ``mscale(s, m) = 0.1 m ln s + 1``.
- **Experts.** The first ``first_k_dense_replace`` layers have a dense
  SwiGLU MLP. The others add the shared experts' SwiGLU (width ``n_shared *
  moe_intermediate``) to ``sum_k s_k * expert_k(x)`` over the
  ``num_experts_per_tok`` experts with the largest softmax scores
  (``topk_method`` greedy), ``s_k`` being the scores themselves times
  ``routed_scaling_factor`` (``norm_topk_prob`` false: not renormalised).

Departures: none in the mathematics. Weights are not held: each matrix is
regenerated from ``(seed, name, layer, expert)`` by ``benchmarks.weights``
when it is needed, one layer and one expert at a time, so the reference
needs no second copy of the model beside the served one. Every expert is
applied to every token and masked by its routing weight (no gather), which
costs FLOPs, not correctness.

Deliberately wrong variants (``fault``) show what the check resolves:
``shift_cache`` hands every layer keys and values shifted by one position
(an off-by-one in the cache's write index, which all layers share),
``shift_cache_one`` only the middle layer; ``experts_3bit`` and
``experts_2bit`` round the routed experts' 4-bit weights to 3 and 2 bits.
Which layers are shifted and how wide the rounding bins are are run-time
inputs, so the clean pass and every control run the same compiled programs.

This file is the model family's whole share of the benchmark
(``benchmarks.config.family``): beside the reference, the table of the
family's matrices (:func:`model_units`), the tree the program's loader
returns for them (:func:`program_params`) and the bytes a decode step must
move (:func:`decode_step_bytes`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import expected_distinct_experts, unit_bytes
from benchmarks.config import Unit

#: fault name -> (which layers see shifted keys and values, rounding bin width)
FAULTS = {
    None: (lambda n: (), 1.0),
    "shift_cache": (lambda n: range(n), 1.0),
    "shift_cache_one": (lambda n: (n // 2,), 1.0),
    "experts_3bit": (lambda n: (), 2.0),
    "experts_2bit": (lambda n: (), 4.0),
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def model_units(cfg: dict) -> dict:
    """{group: {name: Unit}} for a DeepSeek-V2 config dict, plus the group
    "top" (embedding, head, final norm). Names are the program's own leaf
    names, so the launcher's tree needs no second table."""
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("only q_lora_rank = null (DeepSeek-V2-Lite) is wired")
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    nope, rope, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    inter, mi = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e = cfg["n_routed_experts"]
    si = mi * (cfg.get("n_shared_experts") or 1)
    attn = {
        "input_norm": Unit("input_norm", "norm", h, 0),
        "post_norm": Unit("post_norm", "norm", h, 0),
        "kv_a_norm": Unit("kv_a_norm", "norm", rank, 0),
        "q_proj": Unit("q_proj", "linear", heads * (nope + rope), h),
        "kv_a_proj": Unit("kv_a_proj", "linear", rank + rope, h),
        # consumed as a raw tensor by the absorbed-latent einsums: the loader
        # keeps it dense (models/deepseek_v2.py packed_keep_dense_re)
        "kv_b_proj": Unit("kv_b_proj", "linear", heads * (nope + v), rank,
                          keep_dense=True),
        "o_proj": Unit("o_proj", "linear", h, heads * v),
    }
    return {
        "dense": {
            **attn,
            "gate_proj": Unit("gate_proj", "linear", inter, h),
            "up_proj": Unit("up_proj", "linear", inter, h),
            "down_proj": Unit("down_proj", "linear", h, inter),
        },
        "moe": {
            **attn,
            "router": Unit("router", "linear", e, h, keep_dense=True),
            "w_gate": Unit("w_gate", "linear", mi, h, experts=e),
            "w_up": Unit("w_up", "linear", mi, h, experts=e),
            "w_down": Unit("w_down", "linear", h, mi, experts=e),
            "shared_gate": Unit("shared_gate", "linear", si, h),
            "shared_up": Unit("shared_up", "linear", si, h),
            "shared_down": Unit("shared_down", "linear", h, si),
        },
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "lm_head": Unit("lm_head", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def group_ranges(cfg: dict) -> dict:
    """{group: (first global layer, one past the last)}."""
    fk = min(max(cfg["first_k_dense_replace"], 0), cfg["num_hidden_layers"])
    out = {}
    if fk > 0:
        out["dense"] = (0, fk)
    if fk < cfg["num_hidden_layers"]:
        out["moe"] = (fk, cfg["num_hidden_layers"])
    return out


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/deepseek_v2.map_weights`` stacks them, ``embed``,
    ``final_norm``, ``lm_head``."""
    if fmt not in ("q4", "bf16"):
        raise ValueError(f"unknown weight format {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    layers = {}
    for group, (g0, g1) in group_ranges(cfg).items():
        layers[group] = {
            name: W.layer_stack(skey, unit, fmt, g0, g1 - g0)
            for name, unit in units[group].items()
        }
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
        "lm_head": {"weight": W.top_leaf(skey, top["lm_head"], fmt)},
    }


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM.
    Counted once per step: every weight a one-token-per-slot forward pass
    has to read — attention projections, the dense layer's MLP, the shared
    experts, the router, the output head as the engine holds it (bf16: the
    engine dequantizes a packed head when it places it), the *distinct*
    routed experts the active slots' tokens select — and the latent cache
    rows of ``cache_tokens`` tokens (pages in use times the page length is
    an upper bound). Not counted: activations, the embedding rows, writes.
    A lower bound on traffic: the gather path reads one expert copy per
    (token, choice), not per distinct expert, and whatever temporaries the
    compiler adds."""
    units = model_units(cfg)
    ranges = group_ranges(cfg)
    n_dense = ranges["dense"][1] - ranges["dense"][0] if "dense" in ranges else 0
    n_moe = ranges["moe"][1] - ranges["moe"][0] if "moe" in ranges else 0
    dense_layer = sum(unit_bytes(u, fmt) for u in units["dense"].values())
    moe_fixed = sum(unit_bytes(u, fmt) for u in units["moe"].values() if not u.experts)
    one_expert = sum(unit_bytes(u, fmt) for u in units["moe"].values() if u.experts)
    distinct = expected_distinct_experts(
        cfg["n_routed_experts"], cfg["num_experts_per_tok"], active_slots)
    head = 2 * cfg["vocab_size"] * cfg["hidden_size"]  # bf16 as placed
    kv_row = 2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    out = {
        "fixed_weights": n_dense * dense_layer + n_moe * moe_fixed + head,
        "routed_experts": n_moe * distinct * one_expert,
        "latent_cache": cache_tokens * kv_row * cfg["num_hidden_layers"],
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_tables(cfg: dict):
    """``(inv_freq (d/2,), cos/sin scale, softmax scale)``."""
    d = cfg["qk_rope_head_dim"]
    base = float(cfg["rope_theta"])
    head = cfg["qk_nope_head_dim"] + d
    i = jnp.arange(0, d, 2, dtype=jnp.float32)
    extra = 1.0 / base ** (i / d)
    rs = cfg.get("rope_scaling")
    if not rs:
        return extra, 1.0, head ** -0.5
    if rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError("only YaRN rope scaling is written here")
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def correction_dim(turns: float) -> float:
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rs["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # 1: unscaled (fast dimensions), 0: divided by factor
    inv_freq = extra / factor * (1.0 - keep) + extra * keep
    m, m_all = float(rs.get("mscale", 1.0)), float(rs.get("mscale_all_dim", 0.0))
    cos_scale = _mscale(factor, m) / _mscale(factor, m_all)
    softmax_scale = head ** -0.5
    if m_all:
        softmax_scale *= _mscale(factor, m_all) ** 2
    return inv_freq, cos_scale, softmax_scale


def rope(x, pos, inv_freq, cos_scale):
    """``x (T, heads, d)``: rotate each pair ``(x[2i], x[2i+1])``."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * cos_scale)[:, None, :]
    sin = (jnp.sin(ang) * cos_scale)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, lin, names, expert=None, bin_width=None):
    """``down(silu(gate(x)) * up(x))``; ``lin(x, name, ...)`` multiplies by
    the named matrix."""
    gate, up, down = names
    return lin(jax.nn.silu(lin(x, gate, expert, bin_width)) * lin(x, up, expert, bin_width),
               down, expert, bin_width)


def linear(cfg_units, fmt, skey, layer):
    """``lin(x, name, expert=None, bin_width=None) -> x @ M[name]`` for one
    layer's units."""
    def lin(x, name, expert=None, bin_width=None):
        return W.apply_linear(x, skey, cfg_units[name], fmt, layer, expert, bin_width)
    return lin


def _attention(cfg, fmt, skey, units, layer, h, shift):
    t = h.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rd, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    inv_freq, cos_scale, scale = rope_tables(cfg)
    pos = jnp.arange(t)

    lin = linear(units, fmt, skey, layer)
    r = rmsnorm(h, W.logical_norm(skey, units["input_norm"], layer), eps)
    q = lin(r, "q_proj").reshape(t, heads, nope + rd)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], pos, inv_freq, cos_scale)], axis=-1
    )
    ckv = lin(r, "kv_a_proj")
    latent = rmsnorm(ckv[:, :rank], W.logical_norm(skey, units["kv_a_norm"], layer), eps)
    k_pe = rope(ckv[:, None, rank:], pos, inv_freq, cos_scale)
    kv = lin(latent, "kv_b_proj").reshape(t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, heads, rd))], axis=-1
    )
    v = kv[..., nope:]
    # the negative control: this layer sees the row of the position before
    k = jnp.where(shift, jnp.roll(k, 1, axis=0), k)
    v = jnp.where(shift, jnp.roll(v, 1, axis=0), v)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hts,shd->thd", p, v).reshape(t, heads * vd)
    return h + lin(out, "o_proj")


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _dense_layer(cfg_items, fmt, skey, layer, h, shift):
    cfg = _unhash(cfg_items)
    units = model_units(cfg)["dense"]
    with jax.default_matmul_precision("highest"):
        h = _attention(cfg, fmt, skey, units, layer, h, shift)
        r = rmsnorm(h, W.logical_norm(skey, units["post_norm"], layer), cfg["rms_norm_eps"])
        return h + swiglu(r, linear(units, fmt, skey, layer),
                          ("gate_proj", "up_proj", "down_proj"))


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _moe_layer(cfg_items, fmt, skey, layer, h, shift, bin_width):
    cfg = _unhash(cfg_items)
    units = model_units(cfg)["moe"]
    k = cfg["num_experts_per_tok"]
    if cfg.get("topk_method", "greedy") != "greedy" or cfg.get("scoring_func", "softmax") != "softmax":
        raise ValueError("only softmax scoring with greedy top-k is written here")
    with jax.default_matmul_precision("highest"):
        h = _attention(cfg, fmt, skey, units, layer, h, shift)
        r = rmsnorm(h, W.logical_norm(skey, units["post_norm"], layer), cfg["rms_norm_eps"])
        lin = linear(units, fmt, skey, layer)
        scores = jax.nn.softmax(lin(r, "router"), axis=-1)
        top_v, top_i = jax.lax.top_k(scores, k)
        if cfg.get("norm_topk_prob"):
            top_v = top_v / (top_v.sum(axis=-1, keepdims=True) + 1e-20)
        else:
            top_v = top_v * float(cfg.get("routed_scaling_factor", 1.0))

        def one_expert(acc, e):
            coef = jnp.sum(jnp.where(top_i == e, top_v, 0.0), axis=-1)
            y = swiglu(r, lin, ("w_gate", "w_up", "w_down"), e, bin_width)
            return acc + coef[:, None] * y, None

        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(r), jnp.arange(cfg["n_routed_experts"])
        )
        shared = swiglu(r, lin, ("shared_gate", "shared_up", "shared_down"))
        return h + routed + shared, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt", "top"))
def _head(cfg_items, fmt, top, skey, h, ids_wanted):
    """Log-probabilities at the given rows: the reference's own ``top`` ids
    and values, and the values at ``ids_wanted (rows, n)``."""
    cfg = _unhash(cfg_items)
    units = model_units(cfg)["top"]
    with jax.default_matmul_precision("highest"):
        r = rmsnorm(h, W.logical_norm(skey, units["final_norm"], 0), cfg["rms_norm_eps"])
        logits = W.apply_linear(r, skey, units["lm_head"], fmt, 0)
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _embed(cfg_items, fmt, skey, ids):
    cfg = _unhash(cfg_items)
    return W.logical_rows(skey, model_units(cfg)["top"]["embed"], fmt, ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument (rope_scaling is a nested dict)."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, dict, type(None)))
    ))


def _unhash(cfg_items: tuple) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}


def forward(cfg: dict, fmt: str, seed: int, ids, rows, ids_wanted, *,
            top: int = 20, fault=None, pad_to: int = 0):
    """Teacher-forced forward pass over the token ids ``ids`` (one
    sequence, positions 0..T-1, padded at the end to the longer of its own
    length and ``pad_to``, rounded up to a multiple of 128, so that the
    check's prompts share one compiled program; causal masking keeps padding out of every row that is
    read).

    ``rows``: positions whose next-token distribution is wanted.
    ``ids_wanted (len(rows), n)``: token ids whose log-probability is wanted
    there. Returns ``(top_ids, top_logprobs, logprobs_at_wanted)`` as numpy.
    """
    import numpy as np

    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    ids = np.asarray(ids, np.int32)
    t = len(ids)
    padded = -(-max(t, int(pad_to)) // 128) * 128
    h = _embed(cfg_items, fmt, skey, jnp.asarray(np.pad(ids, (0, padded - t))))
    ranges = group_ranges(cfg)
    n_layers = cfg["num_hidden_layers"]
    shifted = FAULTS[fault][0](n_layers)
    bin_width = jnp.asarray(FAULTS[fault][1], jnp.float32)
    for layer in range(n_layers):
        shift = jnp.asarray(layer in shifted)
        lyr = jnp.asarray(layer, jnp.int32)
        if "dense" in ranges and ranges["dense"][0] <= layer < ranges["dense"][1]:
            h = _dense_layer(cfg_items, fmt, skey, lyr, h, shift)
        else:
            h, _ = _moe_layer(cfg_items, fmt, skey, lyr, h, shift, bin_width)
    out = _head(cfg_items, fmt, top, skey, h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)))
    return tuple(np.asarray(x) for x in out)
