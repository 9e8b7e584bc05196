"""Plain reference for the ``nemotron_h`` family (Nemotron-H, Nemotron-3):
the forward pass in ``jax.numpy`` and float32, matrix products at
``highest`` precision, no cache, no kernel, no batching, no chunked form.
Written from the family's published equations (Mamba-2, arXiv 2405.21060;
the model's own ``modeling_nemotron_h.py``; its ``config.json``), not from
``mlx_sharding_tpu/models/nemotron_h.py``.

Every block is ``h = h + mixer(rmsnorm(h, eps = layer_norm_epsilon))`` with
one mixer, chosen by the block's character in ``hybrid_override_pattern``;
a final RMSNorm; an untied head. No bias except the convolution's.

- ``M`` **Mamba-2** (``d = mamba_num_heads * mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``, ``H`` heads of ``P``): ``[z (d), xBC
  (d + 2GN), dt (H)] = in_proj(u)``; ``xBC = silu(conv(xBC) + conv_bias)``,
  a causal depthwise convolution of ``conv_kernel`` taps (``out_t = sum_j
  w[:, j] * in_{t - (K-1) + j}``, zeros before position 0); split into ``x
  (H, P)``, ``B (G, N)``, ``C (G, N)``, head ``h`` reading group ``h // (H /
  G)``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` per head; the
  recurrence, ONE POSITION AT A TIME in a ``lax.scan``: ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; then ``y =
  groupwise_rmsnorm(y * silu(z), G groups) * norm_weight`` and ``out_proj``.
  ``time_step_min/max/floor`` are initialisation values and take no part.
- ``*`` **attention**: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``, no bias, scale
  ``head_dim**-0.5``, causal, NO rotary embedding (the family applies none;
  ``rope_theta`` is inert).
- ``E`` **latent MoE**: ``scores = sigmoid(x W_r)`` over all experts; the
  top ``num_experts_per_tok`` of ``scores + e_score_correction_bias`` are
  chosen (``n_group = topk_group = 1``: no group limit); their weights are
  their own ``scores`` divided by their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``l = fc1_latent_proj(x)``; ``r = sum_k w_k
  down_k(relu(up_k(l))^2)``; the layer gives ``fc2_latent_proj(r) +
  shared(x)`` with ``shared(x) = down(relu(up(x))^2)`` at the full width.

Departures.
- The multi-token-prediction block (``mtp_hybrid_override_pattern``) is not
  computed: it takes no part in next-token logits.
- The SHARE. A configuration may hold one chip's share of each layer's
  routed experts: ``n_routed_experts`` counts the experts held,
  ``moe_expert_share`` the holders, ``moe_expert_share_index`` which one
  this is. The router keeps its full width (``n_routed_experts *
  moe_expert_share``) and its experts per token; the held experts' part of
  ``r`` is computed and what the absent experts would add is LEFT OUT, here
  as in the program, and that partial result goes on to the next layer.
- Weights are not held: each matrix is regenerated from ``(seed, name, rank
  of the layer in its group, expert)`` by ``benchmarks.weights`` when it is
  needed. The small vectors Mamba-2 and the router need (``A_log``, ``D``,
  ``dt_bias``, the convolution's weight and bias, the selection bias) are
  generated here from the same keys (:func:`small_vector`).

Deliberately wrong variants (``fault``), run-time inputs of the same
compiled programs. ``shift_cache_one`` hands the attention layer keys and
values one position late. ``ssm_state_reset`` zeroes the middle Mamba
layer's state and convolution inputs where the compared rows begin (the
position after ``rows[0]``: the hand-over from the last prefill chunk to the
first decode step), ``ssm_state_reset_256`` at position 256, the first
chunk boundary (hundreds of positions before the compared rows of a
600-token prompt, and never reached by a 200-token one). ``ssm_state_bf16``
rounds every Mamba layer's state to bfloat16 after each position and
``drop_D`` leaves ``D x_t`` out (what a served path that did either looks
like). ``weights_fp8`` rounds every matrix to 3 mantissa bits (float8
e4m3's precision, bf16's range): the nearest precision below the one a bf16
configuration states.

This file is the family's whole share of the benchmark
(``benchmarks.config.family``): the reference, the table of its matrices
(:func:`model_units`), the tree the program's loader returns
(:func:`program_params`) and the bytes a decode step must move
(:func:`decode_step_bytes`, :func:`ssm_state_step_bytes`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import expected_distinct_experts, unit_bytes
from benchmarks.config import Unit, is_packed

GROUP_OF = {"M": "mamba", "*": "attn", "E": "moe"}

HANDOVER = "handover"  # a reset position: the row after the first compared one

#: fault name -> (attention shifted, reset position of the middle Mamba
#: layer or -1, state rounded to bf16, D x_t dropped, mantissa bits kept of
#: every matrix: 7 is bf16's own, so nothing changes)
FAULTS = {
    None: (False, -1, False, False, 7),
    "shift_cache_one": (True, -1, False, False, 7),
    "ssm_state_reset": (False, HANDOVER, False, False, 7),
    "ssm_state_reset_256": (False, 256, False, False, 7),
    "ssm_state_bf16": (False, -1, True, False, 7),
    "drop_D": (False, -1, False, True, 7),
    "weights_fp8": (False, -1, False, False, 3),
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    d = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return {
        "d_inner": d,
        "conv_dim": d + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
        "share": int(cfg.get("moe_expert_share", 1)),
        "base": int(cfg.get("moe_expert_share_index", 0)) * cfg["n_routed_experts"],
        "router": cfg["n_routed_experts"] * int(cfg.get("moe_expert_share", 1)),
    }


def group_layers(cfg: dict) -> dict:
    """{group: [global layer indices]} in pattern order."""
    out: dict = {}
    for i, ch in enumerate(cfg["hybrid_override_pattern"]):
        out.setdefault(GROUP_OF[ch], []).append(i)
    return out


def model_units(cfg: dict) -> dict:
    """{group: {the program's leaf name: Unit}} plus the group "top". A
    unit's own name carries its group, so that two groups' norms are not one
    vector; its layer key is the layer's rank in its group."""
    h = cfg["hidden_size"]
    dm = dims(cfg)
    d, lat = dm["d_inner"], cfg["moe_latent_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    mi, si = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    e = cfg["n_routed_experts"]

    def lin(group, name, out, inn, **kw):
        return Unit(f"{group}.{name}", "linear", out, inn, **kw)

    return {
        "mamba": {
            "norm": Unit("mamba.norm", "norm", h, 0),
            "in_proj": lin("mamba", "in_proj", d + dm["conv_dim"] + cfg["mamba_num_heads"], h),
            "ssm_norm": Unit("mamba.ssm_norm", "norm", d, 0),
            "out_proj": lin("mamba", "out_proj", h, d),
        },
        "attn": {
            "norm": Unit("attn.norm", "norm", h, 0),
            "q_proj": lin("attn", "q_proj", q, h),
            "k_proj": lin("attn", "k_proj", kv, h),
            "v_proj": lin("attn", "v_proj", kv, h),
            "o_proj": lin("attn", "o_proj", h, q),
        },
        "moe": {
            "norm": Unit("moe.norm", "norm", h, 0),
            "router": lin("moe", "router", dm["router"], h, keep_dense=True),
            "latent_in": lin("moe", "latent_in", lat, h),
            "latent_out": lin("moe", "latent_out", h, lat),
            "w_up": lin("moe", "w_up", mi, lat, experts=e),
            "w_down": lin("moe", "w_down", lat, mi, experts=e),
            "shared_up": lin("moe", "shared_up", si, h),
            "shared_down": lin("moe", "shared_down", h, si),
        },
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "lm_head": Unit("lm_head", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def small_shapes(cfg: dict) -> dict:
    """{group: {leaf name: shape}} of the vectors that are no ``Unit``."""
    dm = dims(cfg)
    nh = cfg["mamba_num_heads"]
    return {
        "mamba": {
            "conv_w": (dm["conv_dim"], cfg["conv_kernel"]),
            "conv_b": (dm["conv_dim"],), "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
        },
        "moe": {"router_bias": (dm["router"],)},
    }


def small_vector(skey, group: str, name: str, rank, shape):
    """One small leaf, a function of ``(seed, group.name, rank)`` like a
    unit. Drawn as the family initialises them, so that the state neither
    dies nor grows over thousands of positions: ``dt = softplus(dt_bias +
    in_proj's part)`` around a per-head ``dt0`` log-uniform in
    ``time_step_min..max`` = 0.001..0.1 (in_proj's unit-variance part
    spreads a position's ``dt`` about ``dt0`` by a factor e either way), ``A
    = -exp(A_log)`` with ``exp(A_log)`` uniform in 1..16 (a head forgets over
    1 to 1000 positions), ``D`` near 1, convolution taps
    uniform in ``+-K**-0.5``, a small convolution bias, and a selection bias
    large enough (0.05, a tenth of the scores' spread) to change choices."""
    key = W.unit_key(skey, f"{group}.{name}", rank)
    if name == "conv_w":
        k = shape[-1]
        return jax.random.uniform(key, shape, jnp.float32, -(k ** -0.5), k ** -0.5).astype(jnp.bfloat16)
    if name == "conv_b":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)
    if name == "dt_bias":
        dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        return jnp.log(jnp.expm1(dt0))  # softplus**-1
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "D":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name == "router_bias":
        return 0.05 * jax.random.normal(key, shape, jnp.float32)
    raise KeyError(name)


@functools.partial(jax.jit, static_argnames=("group", "name", "n", "shape"))
def _small_stack(skey, group, name, n, shape):
    return jax.vmap(lambda r: small_vector(skey, group, name, r, shape))(jnp.arange(n))


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/nemotron_h.map_weights`` stacks them (a layer's
    row is its rank in its group), the matrices generated when the engine's
    placement slices them (``LazyStack``), the small vectors resident;
    ``embed``, ``final_norm``, ``lm_head``."""
    if fmt not in ("q4", "bf16"):
        raise ValueError(f"unknown weight format {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    smalls = small_shapes(cfg)
    layers = {}
    for group, idxs in group_layers(cfg).items():
        n = len(idxs)
        layers[group] = {
            name: W.layer_stack(skey, unit, fmt, 0, n)
            for name, unit in units[group].items()
        }
        for name, shape in smalls.get(group, {}).items():
            layers[group][name] = _small_stack(skey, group, name, n, shape)
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
        "lm_head": {"weight": W.top_leaf(skey, top["lm_head"], fmt)},
    }


def ssm_state_step_bytes(cfg: dict, active_slots: float) -> float:
    """Bytes of recurrent state one decode step must read and write: per
    active slot and Mamba layer, the SSM state (float32) and the
    convolution's last ``K - 1`` inputs (bf16), each once in and once out."""
    dm = dims(cfg)
    ssm = 4 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    conv = 2 * dm["conv_dim"] * (cfg["conv_kernel"] - 1)
    return 2.0 * active_slots * len(group_layers(cfg).get("mamba", [])) * (ssm + conv)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every weight outside the routed experts (Mamba
    and attention projections, router at its full width, latent
    projections, shared expert, norms, the small vectors, the head as the
    engine holds it), the DISTINCT held experts the active rows' choices hit
    (each row picks ``num_experts_per_tok`` of the router's full width; only
    picks among the held count), the recurrent state of the active slots in
    and out, and the attention layers' K/V rows of ``cache_tokens`` tokens.
    Not counted: activations, the embedding rows, K/V writes. A lower
    bound: the program's expert scan reads every held expert, hit or not."""
    units = model_units(cfg)
    groups = group_layers(cfg)
    dm = dims(cfg)
    small = {
        g: sum(2 * math.prod(s) if n in ("conv_w", "conv_b") else 4 * math.prod(s)
               for n, s in leaves.items())
        for g, leaves in small_shapes(cfg).items()
    }
    fixed = 0
    for g, idxs in groups.items():
        per_layer = sum(unit_bytes(u, fmt) for u in units[g].values() if not u.experts)
        fixed += len(idxs) * (per_layer + small.get(g, 0))
    one_expert = sum(unit_bytes(u, fmt) for u in units["moe"].values() if u.experts)
    hit = expected_distinct_experts(
        dm["router"], cfg["num_experts_per_tok"], active_slots
    ) / dm["share"]
    kv_row = 2 * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]
    out = {
        "fixed_weights": fixed + 2 * cfg["vocab_size"] * cfg["hidden_size"],
        "routed_experts": len(groups.get("moe", [])) * hit * one_expert,
        "recurrent_state": ssm_state_step_bytes(cfg, active_slots),
        "kv_pages": cache_tokens * kv_row * len(groups.get("attn", [])),
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _lin(units, fmt, skey, rank, coarse):
    """``lin(x, name, expert=None) -> x @ M[name]`` for one layer's units.
    ``coarse`` (a run-time boolean): dense matrices rounded to 3 mantissa
    bits first. ``reduce_precision`` and not a pair of converts: the TPU
    compiler may drop a round trip through a narrower type."""
    def lin(x, name, expert=None):
        unit = units[name]
        if is_packed(unit, fmt):
            return W.apply_linear(x, skey, unit, fmt, rank, expert)
        m = W.dense_logical(skey, unit, rank, expert)
        return x @ jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m)
    return lin


def _small(cfg, skey, group, name, rank):
    return small_vector(skey, group, name, rank, small_shapes(cfg)[group][name]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _mamba_layer(cfg_items, fmt, skey, rank, h, reset_at, round_state, drop_d, coarse):
    """``reset_at``: the position before which this layer's state and
    convolution inputs are lost (-1: never). ``round_state``, ``drop_d``:
    the other two faults, as run-time booleans."""
    cfg = dict(cfg_items)
    units = model_units(cfg)["mamba"]
    dm = dims(cfg)
    t = h.shape[0]
    nh, p, g, n, k = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                      cfg["ssm_state_size"], cfg["conv_kernel"])
    d = dm["d_inner"]
    with jax.default_matmul_precision("highest"):
        lin = _lin(units, fmt, skey, rank, coarse)
        u = rmsnorm(h, W.logical_norm(skey, units["norm"], rank), cfg["layer_norm_epsilon"])
        zxd = lin(u, "in_proj")
        z, xbc, dt = zxd[:, :d], zxd[:, d:d + dm["conv_dim"]], zxd[:, d + dm["conv_dim"]:]
        pos = jnp.arange(t)
        # causal depthwise convolution; an input from before the reset is lost
        w = _small(cfg, skey, "mamba", "conv_w", rank)  # (C, K)
        conv = jnp.zeros_like(xbc)
        for j in range(k):
            back = k - 1 - j  # tap j reads the input `back` positions earlier
            src = jnp.roll(xbc, back, axis=0)
            lost = (pos < back) | ((pos >= reset_at) & (pos - back < reset_at))
            conv = conv + jnp.where(lost[:, None], 0.0, src) * w[:, j]
        xbc = jax.nn.silu(conv + _small(cfg, skey, "mamba", "conv_b", rank))
        x = xbc[:, :d].reshape(t, nh, p)
        b_mat = jnp.repeat(xbc[:, d:d + g * n].reshape(t, g, n), nh // g, axis=1)
        c_mat = jnp.repeat(xbc[:, d + g * n:].reshape(t, g, n), nh // g, axis=1)
        dt = jax.nn.softplus(dt + _small(cfg, skey, "mamba", "dt_bias", rank))
        a = -jnp.exp(_small(cfg, skey, "mamba", "A_log", rank))
        d_skip = jnp.where(drop_d, 0.0, _small(cfg, skey, "mamba", "D", rank))

        def step(s, xs):
            x_t, dt_t, b_t, c_t, pos_t = xs
            s = jnp.where(pos_t == reset_at, 0.0, s)
            s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            s = jnp.where(round_state, jax.lax.reduce_precision(s, 8, 7), s)
            return s, jnp.sum(s * c_t[:, None, :], axis=-1) + d_skip[:, None] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32), (x, dt, b_mat, c_mat, pos))
        y = y.reshape(t, d) * jax.nn.silu(z)
        yg = y.reshape(t, g, d // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["layer_norm_epsilon"])
        y = yg.reshape(t, d) * W.logical_norm(skey, units["ssm_norm"], rank)
        return h + lin(y, "out_proj")


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _attn_layer(cfg_items, fmt, skey, rank, h, shift, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)["attn"]
    t = h.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    with jax.default_matmul_precision("highest"):
        lin = _lin(units, fmt, skey, rank, coarse)
        u = rmsnorm(h, W.logical_norm(skey, units["norm"], rank), cfg["layer_norm_epsilon"])
        q = lin(u, "q_proj").reshape(t, hkv, hq // hkv, hd)
        k = lin(u, "k_proj").reshape(t, hkv, hd)
        v = lin(u, "v_proj").reshape(t, hkv, hd)
        # the negative control: this layer sees the row of the position before
        k = jnp.where(shift, jnp.roll(k, 1, axis=0), k)
        v = jnp.where(shift, jnp.roll(v, 1, axis=0), v)
        pos = jnp.arange(t)
        s = jnp.einsum("tkgd,skd->kgts", q, k) * hd ** -0.5
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
        out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v).reshape(t, hq * hd)
        return h + lin(out, "o_proj")


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _moe_layer(cfg_items, fmt, skey, rank, h, coarse=False):
    cfg = dict(cfg_items)
    units = model_units(cfg)["moe"]
    dm = dims(cfg)
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("only n_group = topk_group = 1 is written here")
    with jax.default_matmul_precision("highest"):
        lin = _lin(units, fmt, skey, rank, coarse)
        u = rmsnorm(h, W.logical_norm(skey, units["norm"], rank), cfg["layer_norm_epsilon"])
        scores = jax.nn.sigmoid(lin(u, "router"))
        _, top_i = jax.lax.top_k(scores + _small(cfg, skey, "moe", "router_bias", rank),
                                 cfg["num_experts_per_tok"])
        top_v = jnp.take_along_axis(scores, top_i, axis=-1)
        if cfg.get("norm_topk_prob", True):
            top_v = top_v / (top_v.sum(axis=-1, keepdims=True) + 1e-20)
        top_v = top_v * float(cfg.get("routed_scaling_factor", 1.0))
        lat = lin(u, "latent_in")

        def one_expert(acc, e):  # e: the expert's place among those held
            coef = jnp.sum(jnp.where(top_i == e + dm["base"], top_v, 0.0), axis=-1)
            y = lin(jnp.square(jax.nn.relu(lin(lat, "w_up", e))), "w_down", e)
            return acc + coef[:, None] * y, None

        routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(lat),
                                 jnp.arange(cfg["n_routed_experts"]))
        shared = lin(jnp.square(jax.nn.relu(lin(u, "shared_up"))), "shared_down")
        return h + lin(routed, "latent_out") + shared, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt", "top"))
def _head(cfg_items, fmt, top, skey, h, ids_wanted, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)["top"]
    with jax.default_matmul_precision("highest"):
        r = rmsnorm(h, W.logical_norm(skey, units["final_norm"], 0), cfg["layer_norm_epsilon"])
        logits = _lin(units, fmt, skey, 0, coarse)(r, "lm_head")
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _embed(cfg_items, fmt, skey, ids):
    return W.logical_rows(skey, model_units(dict(cfg_items))["top"]["embed"], fmt, ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument (every key here is a scalar)."""
    return tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, type(None)))
    ))


def hidden_states(cfg: dict, fmt: str, seed: int, ids, fault=None, handover: int = -1):
    """The final hidden states ``(T, hidden)`` of one sequence (before the
    final norm) and each expert layer's choices. ``handover``: the position
    a ``HANDOVER`` reset falls on."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    shift, reset_at, round_state, drop_d, mantissa = FAULTS[fault]
    reset_at = handover if reset_at == HANDOVER else reset_at
    coarse = jnp.asarray(mantissa < 7)
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    h = _embed(cfg_items, fmt, skey, jnp.asarray(ids, jnp.int32))
    n_mamba = len(group_layers(cfg).get("mamba", []))
    seen: dict = {}
    picks = []
    for ch in cfg["hybrid_override_pattern"]:
        group = GROUP_OF[ch]
        rank = seen.get(group, 0)
        seen[group] = rank + 1
        r = jnp.asarray(rank, jnp.int32)
        if group == "mamba":
            at = reset_at if rank == n_mamba // 2 else -1
            h = _mamba_layer(cfg_items, fmt, skey, r, h, jnp.asarray(at, jnp.int32),
                             jnp.asarray(round_state), jnp.asarray(drop_d), coarse)
        elif group == "attn":
            h = _attn_layer(cfg_items, fmt, skey, r, h, jnp.asarray(shift), coarse)
        else:
            h, top_i = _moe_layer(cfg_items, fmt, skey, r, h, coarse)
            picks.append(top_i)
    return h, picks


def forward(cfg: dict, fmt: str, seed: int, ids, rows, ids_wanted, *,
            top: int = 20, fault=None, pad_to: int = 0):
    """Teacher-forced forward pass over the token ids ``ids`` (one
    sequence, positions 0..T-1, padded at the end to the longer of its own
    length and ``pad_to``, rounded up to a multiple of 128, so that the
    check's prompts share one compiled program; every mixer is causal, so
    padding stays out of every row that is read).

    ``rows``: positions whose next-token distribution is wanted.
    ``ids_wanted (len(rows), n)``: token ids whose log-probability is wanted
    there. Returns ``(top_ids, top_logprobs, logprobs_at_wanted)`` as numpy.
    """
    import numpy as np

    ids = np.asarray(ids, np.int32)
    t = len(ids)
    padded = -(-max(t, int(pad_to)) // 128) * 128
    h, _ = hidden_states(cfg, fmt, seed, np.pad(ids, (0, padded - t)), fault,
                         handover=int(np.asarray(rows)[0]) + 1)
    coarse = jnp.asarray(FAULTS[fault][4] < 7)
    out = _head(hashable(cfg), fmt, top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), coarse)
    return tuple(np.asarray(x) for x in out)
