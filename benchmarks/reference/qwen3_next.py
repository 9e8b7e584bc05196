"""Plain reference for the ``qwen3_next`` family (Qwen3-Next): the forward pass
in ``jax.numpy`` and float32, matrix products at ``highest`` precision, no
cache, no kernel, no batching, no chunked form. Written from the published
equations (Gated DeltaNet, arXiv 2412.06464; the model's ``config.json`` and
its published module layout), not from ``mlx_sharding_tpu/models/qwen3_next.py``.

Pre-norm residual blocks, eps ``rms_norm_eps``, no bias anywhere: ``h = h +
mixer(norm(h, input_layernorm))``; ``h = h + moe(norm(h,
post_attention_layernorm))``; logits ``= norm(h, norm) @ lm_head`` (untied,
embedding unscaled). ``norm(x, w) = x * rsqrt(mean x^2 + eps) * (1 + w)``:
every layer norm, the final norm and the two per-head norms are ZERO-CENTRED.
Layer ``i`` (from 0) is full attention when ``(i + 1) %
full_attention_interval == 0``, else linear attention.

- **Linear attention (Gated DeltaNet)**, ``Hk`` key heads, ``Hv`` value heads
  of ``D``, ``K`` taps, ``u`` the normed input: ``[q, k, v, z] = W_qkvz u``
  (widths ``Hk D, Hk D, Hv D, Hv D``), ``[b, a] = W_ba u`` (``Hv`` each);
  ``[q, k, v] = silu(conv([q, k, v]))``, ONE causal depthwise convolution over
  the joined channels (``out_t = sum_j w[:, j] * in_{t - (K-1) + j}``, zeros
  before position 0, no bias); per key head ``q = l2norm(q) * D**-0.5``, ``k =
  l2norm(k)`` (``x * rsqrt(sum x^2 + 1e-6)``); value head ``j`` reads key head
  ``j // (Hv / Hk)``; ``beta = sigmoid(b)[h]``, ``g = -exp(A_log[h]) *
  softplus(a[h] + dt_bias[h])``, ``alpha = exp(g)``: a decay a VALUE HEAD; the
  recurrence, ONE POSITION AT A TIME in a ``lax.scan`` over a ``(D, D)`` state
  a head: ``S' = alpha_t S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T``, ``o_t = S_t^T q_t``; ``y = rmsnorm_over_D(o) * w_norm * silu(z)``
  (this norm's weight is PLAIN, not ``1 + w``); ``W_out y``.
- **Full attention**: ``W_q u`` viewed ``(heads, 2 head_dim)``, per head
  ``[query | gate]``; ``W_k u``, ``W_v u`` to ``num_key_value_heads`` heads;
  ``q = norm(query, q_norm)``, ``k = norm(k, k_norm)`` over ``head_dim``
  (zero-centred); rotary, HALF-SPLIT (channel ``i`` pairs with ``i + r / 2``),
  on the first ``r = partial_rotary_factor * head_dim`` channels of ``q`` and
  ``k`` at ``rope_theta``, no scaling; causal GQA ``softmax(q k^T *
  head_dim**-0.5) v``; ``W_o (attn * sigmoid(gate))``.
- **MoE, every layer**: ``p = softmax(u W_r)`` over all experts; the top
  ``num_experts_per_tok``; their weights ``p_i / sum_top p`` (``norm_topk_prob``);
  routed SwiGLU experts, a plain loop over the held ones; plus
  ``sigmoid(u . w_sg) * shared(u)``, one shared SwiGLU expert behind a gate of
  one scalar a row. No selection bias: the published router has none.

Departures.
- Attention is computed in blocks of ``Q_BLOCK`` queries (each against every
  key, masked), so that 1.5k positions of 16 heads fit beside a served model:
  the same numbers, no (heads, T, T) score matrix.
- The SHARE, the sliced vocabulary and the weights: as
  ``benchmarks/reference/afmoe.py`` says. ``W_qkvz`` and ``W_ba`` are seeded
  matrices whose columns lie ``[q, k, v, z]`` and ``[b, a]``, each head-major,
  as the program holds them (the checkpoint groups them by key head: matrices
  of independent entries either way). A zero-centred norm's ``w`` is the
  seeded norm vector (``1 + 0.1 normal``, bf16) MINUS 1, exactly: ``1 + w`` is
  the vector every family's norms have. The small vectors (``A_log``,
  ``dt_bias`` one a value head, the convolution's taps) are
  ``benchmarks/reference/nemotron_h.py``'s ``small_vector``'s. The ROUTER is
  made balanced (:func:`router_matrix`): each of its columns scaled to unit
  norm before it is rounded to bf16, so every expert's logit has the same
  spread on unit-variance inputs and no seed hands the held quarter more or
  fewer rows than its share; :func:`pick_rates` shows how far each expert's
  pick rate lies from ``k / experts``. The seed makes the weights, not the work.

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``gdn_state_reset`` zeroes the middle linear layer's state and
convolution inputs where the compared rows begin (the position after
``rows[0]``: the hand-over from the last prefill chunk to the first decode
step). ``gdn_no_decay``: ``alpha = 1``, the plain delta rule. ``gdn_state_bf16``
rounds every linear layer's state to bfloat16 after each position.
``rope_full`` turns all ``head_dim`` channels: what a port that reused another
family's attention unchanged would serve. ``attn_gate_off`` and
``shared_gate_off`` leave a sigmoid gate out (1 in its place).
``moe_no_renorm`` leaves the chosen probabilities' sum out. ``qgate_halves``
reads ``W_q u`` as ``[all queries | all gates]`` instead of a head's ``[query
| gate]``; ``norms_plain`` takes the zero-centred norms' ``w`` as the whole
weight; ``gdn_norm_centred`` applies the gated norm as ``1 + w``.
``weights_fp8`` rounds every matrix to 3 mantissa bits (float8 e4m3's
precision, bf16's range): the nearest precision below the one a bf16
configuration states.

This file is the family's whole share of the benchmark
(``benchmarks.config.family``): the reference, the table of its matrices
(:func:`model_units`), the tree the program's loader returns
(:func:`program_params`) and the bytes a decode step must move
(:func:`decode_step_bytes`, :func:`kda_state_step_bytes`,
:func:`paged_attn_step_bytes`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import expected_distinct_experts, unit_bytes
from benchmarks.config import Unit
from benchmarks.reference.nemotron_h import HANDOVER, _small_stack, small_vector

GROUPS = ("gdn", "attn")
EXPERTS = ("w_gate", "w_up", "w_down")
Q_BLOCK = 128
#: the leaves whose seeded norm vector is ``1 + w``: the program holds ``w``
ZERO_CENTRED = ("norm", "ffn_norm", "q_norm", "k_norm", "final_norm")
#: rows :func:`pick_rates` draws a layer
BALANCE_ROWS = 8192

#: fault name -> what departs from the clean pass (:data:`CLEAN`)
CLEAN = {
    "reset_at": -1, "no_decay": False, "state_bf16": False, "rope_full": False,
    "attn_gate_off": False, "shared_gate_off": False, "no_renorm": False,
    "qgate_halves": False, "norms_plain": False, "gdn_norm_centred": False,
    "mantissa": 7,  # bits kept of every matrix: 7 is bf16's own
}
FAULTS = {
    None: {},
    "gdn_state_reset": {"reset_at": HANDOVER},
    "gdn_no_decay": {"no_decay": True},
    "gdn_state_bf16": {"state_bf16": True},
    "rope_full": {"rope_full": True},
    "attn_gate_off": {"attn_gate_off": True},
    "shared_gate_off": {"shared_gate_off": True},
    "moe_no_renorm": {"no_renorm": True},
    "qgate_halves": {"qgate_halves": True},
    "norms_plain": {"norms_plain": True},
    "gdn_norm_centred": {"gdn_norm_centred": True},
    "weights_fp8": {"mantissa": 3},
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    share = int(cfg.get("moe_expert_share", 1))
    d = cfg["linear_key_head_dim"]
    if cfg["linear_value_head_dim"] != d:
        raise ValueError("only linear_key_head_dim == linear_value_head_dim is written here")
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    heads, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return {
        "gdn_dim": d, "key_heads": hk, "value_heads": hv,
        "key_width": hk * d, "value_width": hv * d, "conv": (2 * hk + hv) * d,
        "taps": cfg["linear_conv_kernel_dim"],
        "heads": heads, "kv_heads": hkv, "head_dim": hd,
        "rot": int(hd * cfg["partial_rotary_factor"]),
        "share": share,
        "base": int(cfg.get("moe_expert_share_index", 0)) * cfg["num_experts"],
        "router": cfg["num_experts"] * share,
    }


def layer_groups(cfg: dict) -> list:
    """Each layer's group, in order: ``attn`` where ``(i + 1) %
    full_attention_interval == 0``, else ``gdn``."""
    n = cfg["full_attention_interval"]
    return ["attn" if (i + 1) % n == 0 else "gdn" for i in range(cfg["num_hidden_layers"])]


def group_layers(cfg: dict) -> dict:
    """{group: [global layer indices]} in pattern order."""
    out: dict = {}
    for i, group in enumerate(layer_groups(cfg)):
        out.setdefault(group, []).append(i)
    return out


def model_units(cfg: dict) -> dict:
    """{group: {the program's leaf name: Unit}} plus the group "top". A
    unit's own name carries its group; its layer key is the layer's rank in
    its group."""
    h, dm = cfg["hidden_size"], dims(cfg)
    mi, si, e = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"], cfg["num_experts"]

    def group(g):
        lin = lambda name, out, inn, **kw: Unit(f"{g}.{name}", "linear", out, inn, **kw)  # noqa: E731
        nrm = lambda name, n: Unit(f"{g}.{name}", "norm", n, 0)  # noqa: E731
        out = {"norm": nrm("norm", h), "ffn_norm": nrm("ffn_norm", h)}
        if g == "gdn":
            out.update(
                qkvz_proj=lin("qkvz_proj", dm["conv"] + dm["value_width"], h),
                ba_proj=lin("ba_proj", 2 * dm["value_heads"], h),
                o_norm=nrm("o_norm", dm["gdn_dim"]),
                o_proj=lin("o_proj", h, dm["value_width"]),
            )
        else:
            out.update(
                q_proj=lin("q_proj", 2 * dm["heads"] * dm["head_dim"], h),
                k_proj=lin("k_proj", dm["kv_heads"] * dm["head_dim"], h),
                v_proj=lin("v_proj", dm["kv_heads"] * dm["head_dim"], h),
                q_norm=nrm("q_norm", dm["head_dim"]), k_norm=nrm("k_norm", dm["head_dim"]),
                o_proj=lin("o_proj", h, dm["heads"] * dm["head_dim"]),
            )
        out.update(
            router=lin("router", dm["router"], h, keep_dense=True),
            shared_gate=lin("shared_gate", si, h), shared_up=lin("shared_up", si, h),
            shared_down=lin("shared_down", h, si),
            shared_expert_gate=lin("shared_expert_gate", 1, h, keep_dense=True),
            w_gate=lin("w_gate", mi, h, experts=e), w_up=lin("w_up", mi, h, experts=e),
            w_down=lin("w_down", h, mi, experts=e),
        )
        return out

    return {
        "gdn": group("gdn"), "attn": group("attn"),
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "lm_head": Unit("lm_head", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def small_shapes(cfg: dict) -> dict:
    """{leaf name: shape} of a linear layer's vectors that are no ``Unit``,
    drawn by ``benchmarks/reference/nemotron_h.py``'s ``small_vector`` as it
    draws Mamba-2's: ``exp(A_log)`` uniform in 1..16 and ``dt_bias =
    softplus**-1(dt0)``, ``dt0`` log-uniform in 0.001..0.1, one a VALUE HEAD
    (``W_ba``'s unit-variance part spreads a position's ``softplus`` about
    ``dt0`` by a factor e either way), so a head forgets over 1 to 1000
    positions: the state neither dies nor grows over the longest request;
    convolution taps uniform in ``+-K**-0.5``, bf16."""
    dm = dims(cfg)
    return {"conv_w": (dm["conv"], dm["taps"]),
            "A_log": (dm["value_heads"],), "dt_bias": (dm["value_heads"],)}


def router_matrix(skey, unit: Unit, rank):
    """The balanced router ``(hidden, experts)`` of one layer, bf16: the
    seeded matrix with each column (one expert's) scaled to unit norm, so
    every expert's logit has unit variance on unit-variance inputs."""
    m = W.dense_logical(skey, unit, rank)
    return (m * jax.lax.rsqrt(jnp.sum(m * m, axis=0, keepdims=True))).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("unit", "n"))
def _router_stack(skey, unit, n):
    return jax.vmap(lambda r: router_matrix(skey, unit, r))(jnp.arange(n))


@functools.partial(jax.jit, static_argnames=("cfg_items", "group", "n"))
def _pick_rates(cfg_items, group, skey, n):
    cfg = dict(cfg_items)
    e, k = dims(cfg)["router"], cfg["num_experts_per_tok"]
    unit = model_units(cfg)[group]["router"]

    def one_layer(rank):
        x = jax.random.normal(W.unit_key(skey, f"{group}.pick_rates", rank),
                              (BALANCE_ROWS, cfg["hidden_size"]), jnp.float32)
        _, top_i = jax.lax.top_k(x @ router_matrix(skey, unit, rank).astype(jnp.float32), k)
        return jnp.mean(jnp.sum(jax.nn.one_hot(top_i, e), axis=-2), axis=0)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one_layer, jnp.arange(n))


def pick_rates(cfg: dict, seed: int) -> dict:
    """``{group: (layers, router outputs)}``: each expert's pick rate on
    ``BALANCE_ROWS`` seeded unit-variance inputs a layer under the balanced
    router; ``k / experts`` is every expert's share (at 8192 rows, 10 of 512,
    a rate is a mean of 160 picks: sampling alone spreads it by 8 %)."""
    groups = group_layers(cfg)
    return {g: _pick_rates(hashable(cfg), g, W.seed_key(seed), len(idxs))
            for g, idxs in groups.items()}


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/qwen3_next.map_weights`` stacks them (a layer's
    row is its rank in its group), the matrices generated when the engine's
    placement slices them (``LazyStack``); the small vectors, the
    zero-centred norms' ``w``, the balanced router and the shared expert's
    gate vector resident; ``embed``, ``final_norm``, ``lm_head``."""
    if fmt != "bf16":
        raise ValueError(f"qwen3_next is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    layers = {}
    for group, idxs in group_layers(cfg).items():
        n = len(idxs)
        tree = {name: W.layer_stack(skey, unit, fmt, 0, n)
                for name, unit in units[group].items()}
        for name in ZERO_CENTRED:
            if name in tree:
                tree[name] = tree[name][:] - 1  # exact in bf16
        tree["router"] = _router_stack(skey, units[group]["router"], n)
        tree["shared_expert_gate"] = tree["shared_expert_gate"][:][..., 0]
        if group == "gdn":
            for name, shape in small_shapes(cfg).items():
                tree[name] = _small_stack(skey, group, name, n, shape)
        layers[group] = tree
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt) - 1},
        "lm_head": {"weight": W.top_leaf(skey, top["lm_head"], fmt)},
    }


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of one position's K and V rows in one attention layer (bf16)."""
    dm = dims(cfg)
    return 2 * 2 * dm["kv_heads"] * dm["head_dim"]


def paged_attn_step_bytes(cfg: dict, active_slots: float, context: float) -> float:
    """K/V bytes a decode step's attention must read: per active slot its
    ``context`` rows in every full-attention layer."""
    return active_slots * context * len(group_layers(cfg).get("attn", [])) * kv_row_bytes(cfg)


def kda_state_step_bytes(cfg: dict, active_slots: float) -> float:
    """Bytes of recurrent state one decode step must read and write: per
    active slot and linear layer, the state ``(Hv, D, D)`` (float32) and the
    convolution's last ``K - 1`` inputs (bf16), each once in and once out."""
    dm = dims(cfg)
    state = 4 * dm["value_heads"] * dm["gdn_dim"] ** 2
    conv = 2 * dm["conv"] * (dm["taps"] - 1)
    return 2.0 * active_slots * len(group_layers(cfg).get("gdn", [])) * (state + conv)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every weight outside the routed experts (both
    mixers, norms, router at its full width, shared expert and its gate,
    small vectors, the head's slice), the DISTINCT held experts the active
    rows' choices hit (a balanced router: the uniform formula), the linear
    layers' recurrent state of the active slots in and out, and the
    attention layers' rows of the ``cache_tokens`` tokens in the pool. Not
    counted: activations, the embedding's rows, cache writes. A lower bound."""
    units = model_units(cfg)
    groups = group_layers(cfg)
    dm = dims(cfg)
    small = sum((2 if n == "conv_w" else 4) * math.prod(s)
                for n, s in small_shapes(cfg).items())
    fixed = 0
    for g, idxs in groups.items():
        per_layer = sum(unit_bytes(u, fmt) for u in units[g].values() if not u.experts)
        fixed += len(idxs) * (per_layer + (small if g == "gdn" else 0))
    one_expert = sum(unit_bytes(units["gdn"][n], fmt) for n in EXPERTS)
    hit = expected_distinct_experts(
        dm["router"], cfg["num_experts_per_tok"], active_slots
    ) / dm["share"]
    out = {
        # the head's slice and the final norm; the embedding's rows are not read
        "fixed_weights": fixed + unit_bytes(units["top"]["lm_head"], fmt)
        + unit_bytes(units["top"]["final_norm"], fmt),
        "routed_experts": cfg["num_hidden_layers"] * hit * one_expert,
        "recurrent_state": kda_state_step_bytes(cfg, active_slots),
        "kv_pages": cache_tokens * kv_row_bytes(cfg) * len(groups.get("attn", [])),
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def unit_rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope_half(x, pos, rot: int, theta: float):
    """Rotary embedding on the first ``rot`` channels of ``x (T, heads, D)``
    at positions ``pos (T,)``, half-split: channel ``i < rot / 2`` pairs with
    ``i + rot / 2`` and turns by ``pos * theta**(-2 i / rot)``."""
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos.astype(jnp.float32)[:, None, None] * freq  # (T, 1, rot / 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _parts(cfg_items, group, skey, rank, flt):
    """``(cfg, lin, nrm)`` for one layer: ``lin(x, name, expert=None) -> x @
    M[name]``, ``nrm(name)`` a norm leaf's seeded vector (``1 + w`` for a
    zero-centred one). ``flt["coarse"]`` (a run-time boolean): matrices rounded
    to 3 mantissa bits first — ``reduce_precision`` and not a pair of
    converts: the TPU compiler may drop a round trip through a narrower type."""
    cfg = dict(cfg_items)
    units = model_units(cfg)[group]

    def lin(x, name, expert=None):
        if name == "router":
            m = router_matrix(skey, units[name], rank).astype(jnp.float32)
        else:
            m = W.dense_logical(skey, units[name], rank, expert)
        return x @ jnp.where(flt["coarse"], jax.lax.reduce_precision(m, 8, 3), m)

    return cfg, lin, lambda name: W.logical_norm(skey, units[name], rank)


def znorm(x, one_plus_w, eps, flt):
    """The zero-centred norm ``x_hat * (1 + w)``; under ``norms_plain`` ``w``
    alone is taken for the weight."""
    w = one_plus_w - 1.0
    return unit_rms(x, eps) * jnp.where(flt["norms_plain"], w, 1.0 + w)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _gdn_half(cfg_items, skey, rank, h, reset_at, flt):
    """``h + gated_delta_net(norm(h))``. ``reset_at``: the position before
    which this layer's state and convolution inputs are lost (-1: never)."""
    cfg, lin, nrm = _parts(cfg_items, "gdn", skey, rank, flt)
    dm = dims(cfg)
    t = h.shape[0]
    d, hk, hv, taps = dm["gdn_dim"], dm["key_heads"], dm["value_heads"], dm["taps"]
    kw, conv_dim, eps = dm["key_width"], dm["conv"], cfg["rms_norm_eps"]
    small = lambda name: small_vector(  # noqa: E731
        skey, "gdn", name, rank, small_shapes(cfg)[name]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = znorm(h, nrm("norm"), eps, flt)
        qkvz = lin(u, "qkvz_proj")
        qkv, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
        pos = jnp.arange(t)
        # causal depthwise convolution; an input from before the reset is lost
        w = small("conv_w")  # ((2 Hk + Hv) D, K)
        conv = jnp.zeros_like(qkv)
        for j in range(taps):
            back = taps - 1 - j  # tap j reads the input `back` positions earlier
            lost = (pos < back) | ((pos >= reset_at) & (pos - back < reset_at))
            conv = conv + jnp.where(lost[:, None], 0.0, jnp.roll(qkv, back, axis=0)) * w[:, j]
        qkv = jax.nn.silu(conv)
        q = l2norm(qkv[:, :kw].reshape(t, hk, d)) * d ** -0.5
        k = l2norm(qkv[:, kw:2 * kw].reshape(t, hk, d))
        v = qkv[:, 2 * kw:].reshape(t, hv, d)
        # value head j reads key head j // (Hv / Hk)
        q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
        ba = lin(u, "ba_proj")
        beta = jax.nn.sigmoid(ba[:, :hv])
        g = -jnp.exp(small("A_log")) * jax.nn.softplus(ba[:, hv:] + small("dt_bias"))
        alpha = jnp.where(flt["no_decay"], 1.0, jnp.exp(g))  # (T, Hv)

        def step(s, xs):
            q_t, k_t, v_t, a_t, b_t, pos_t = xs
            s = jnp.where(pos_t == reset_at, 0.0, s)
            s = a_t[:, None, None] * s
            r = jnp.sum(s * k_t[:, :, None], axis=1)  # S'^T k (Hv, D)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - r)[:, None, :]
            s = jnp.where(flt["state_bf16"], jax.lax.reduce_precision(s, 8, 7), s)
            return s, jnp.sum(s * q_t[:, :, None], axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((hv, d, d), jnp.float32),
                            (q, k, v, alpha, beta, pos))
        w_norm = nrm("o_norm")
        w_norm = jnp.where(flt["gdn_norm_centred"], 1.0 + w_norm, w_norm)
        y = unit_rms(o, eps) * w_norm * jax.nn.silu(z).reshape(t, hv, d)
        return h + lin(y.reshape(t, hv * d), "o_proj")


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _attn_half(cfg_items, skey, rank, h, flt):
    """``h + gated_attention(norm(h))``."""
    cfg, lin, nrm = _parts(cfg_items, "attn", skey, rank, flt)
    dm = dims(cfg)
    t = h.shape[0]
    nh, hkv, d, eps = dm["heads"], dm["kv_heads"], dm["head_dim"], cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = znorm(h, nrm("norm"), eps, flt)
        qg = lin(u, "q_proj")
        per_head = qg.reshape(t, nh, 2 * d)  # a head's [query | gate]
        q = jnp.where(flt["qgate_halves"], qg[:, :nh * d].reshape(t, nh, d), per_head[..., :d])
        gate = jnp.where(flt["qgate_halves"], qg[:, nh * d:].reshape(t, nh, d), per_head[..., d:])
        k = lin(u, "k_proj").reshape(t, hkv, d)
        v = lin(u, "v_proj").reshape(t, hkv, d)
        q = znorm(q, nrm("q_norm"), eps, flt)
        k = znorm(k, nrm("k_norm"), eps, flt)
        pos = jnp.arange(t)
        theta = float(cfg["rope_theta"])
        turn = lambda x: jnp.where(  # noqa: E731
            flt["rope_full"], rope_half(x, pos, d, theta), rope_half(x, pos, dm["rot"], theta))
        q, k = turn(q), turn(k)
        # query head j reads K/V head j // (heads / kv_heads)
        k, v = (jnp.repeat(x, nh // hkv, axis=1) for x in (k, v))

        def block(args):
            qb, q_pos = args  # (Q, H, D), (Q,)
            s = jnp.einsum("qhd,shd->hqs", qb, k) * d ** -0.5
            s = jnp.where(pos[None, :] <= q_pos[:, None], s, -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)

        qb = min(Q_BLOCK, t)
        if t % qb:
            raise ValueError(f"{t} positions are no multiple of the query block {qb}")
        out = jax.lax.map(block, (q.reshape(t // qb, qb, nh, d), pos.reshape(t // qb, qb)))
        out = out.reshape(t, nh, d) * jnp.where(flt["attn_gate_off"], 1.0, jax.nn.sigmoid(gate))
        return h + lin(out.reshape(t, nh * d), "o_proj")


def _moe(cfg, lin, u, flt):
    """``(moe(u), the choices)`` of one layer: the held experts' part and the
    gated shared expert."""
    dm = dims(cfg)
    p = jax.nn.softmax(lin(u, "router"), axis=-1)
    top_v, top_i = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top_v = top_v / jnp.where(flt["no_renorm"], 1.0, top_v.sum(axis=-1, keepdims=True))

    def one_expert(acc, e):  # e: the expert's place among those held
        coef = jnp.sum(jnp.where(top_i == e + dm["base"], top_v, 0.0), axis=-1)
        y = lin(jax.nn.silu(lin(u, "w_gate", e)) * lin(u, "w_up", e), "w_down", e)
        return acc + coef[:, None] * y, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(cfg["num_experts"]))
    shared = lin(jax.nn.silu(lin(u, "shared_gate")) * lin(u, "shared_up"), "shared_down")
    open_ = jnp.where(flt["shared_gate_off"], 1.0, jax.nn.sigmoid(lin(u, "shared_expert_gate")))
    return routed + open_ * shared, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "group"))
def _moe_half(cfg_items, group, skey, rank, h, flt):
    """``(h + moe(norm(h)), the layer's choices)``."""
    cfg, lin, nrm = _parts(cfg_items, group, skey, rank, flt)
    with jax.default_matmul_precision("highest"):
        m, top_i = _moe(cfg, lin, znorm(h, nrm("ffn_norm"), cfg["rms_norm_eps"], flt), flt)
        return h + m, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "top"))
def _head(cfg_items, top, skey, h, ids_wanted, flt):
    cfg, lin, nrm = _parts(cfg_items, "top", skey, 0, flt)
    with jax.default_matmul_precision("highest"):
        logits = lin(znorm(h, nrm("final_norm"), cfg["rms_norm_eps"], flt), "lm_head")
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(cfg_items, skey, ids):
    cfg = dict(cfg_items)
    return W.logical_rows(skey, model_units(cfg)["top"]["embed"], "bf16", ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: its scalars."""
    return tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, type(None)))
    ))


def fault_flags(fault, handover: int = -1) -> dict:
    """:data:`CLEAN` with ``fault``'s departures, as run-time values of the
    compiled programs; a ``HANDOVER`` reset falls on ``handover``."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    f = {**CLEAN, **FAULTS[fault]}
    f["reset_at"] = handover if f["reset_at"] == HANDOVER else f["reset_at"]
    f["coarse"] = f.pop("mantissa") < 7
    return {k: jnp.asarray(v) for k, v in f.items()}


def hidden_states(cfg: dict, fmt: str, seed: int, ids, fault=None, handover: int = -1):
    """The final hidden states ``(T, hidden)`` of one sequence (before the
    final norm) and each layer's choices. ``handover``: the position a
    ``HANDOVER`` reset falls on (the MIDDLE linear layer's)."""
    if fmt != "bf16":
        raise ValueError(f"qwen3_next is served in bf16 here, not {fmt!r}")
    flt = fault_flags(fault, handover)
    reset_at = flt.pop("reset_at")
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    h = _embed(cfg_items, skey, jnp.asarray(ids, jnp.int32))
    groups = layer_groups(cfg)
    linear = [i for i, g in enumerate(groups) if g == "gdn"]
    middle = linear[len(linear) // 2]
    seen: dict = {}
    picks = []
    for i, group in enumerate(groups):
        rank = seen.get(group, 0)
        seen[group] = rank + 1
        r = jnp.asarray(rank, jnp.int32)
        if group == "attn":
            h = _attn_half(cfg_items, skey, r, h, flt)
        else:
            at = reset_at if i == middle else jnp.asarray(-1)
            h = _gdn_half(cfg_items, skey, r, h, jnp.asarray(at, jnp.int32), flt)
        h, top_i = _moe_half(cfg_items, group, skey, r, h, flt)
        picks.append(top_i)
    return h, picks


def forward(cfg: dict, fmt: str, seed: int, ids, rows, ids_wanted, *,
            top: int = 20, fault=None, pad_to: int = 0):
    """Teacher-forced forward pass over the token ids ``ids`` (one sequence,
    positions 0..T-1, padded at the end to the longer of its own length and
    ``pad_to``, rounded up to a multiple of 128, so that the check's prompts
    share one compiled program; every mixer is causal, so padding stays out
    of every row that is read).

    ``rows``: positions whose next-token distribution is wanted.
    ``ids_wanted (len(rows), n)``: token ids whose log-probability is wanted
    there. Returns ``(top_ids, top_logprobs, logprobs_at_wanted)`` as numpy.
    """
    import numpy as np

    ids = np.asarray(ids, np.int32)
    t = len(ids)
    padded = -(-max(t, int(pad_to)) // Q_BLOCK) * Q_BLOCK
    h, _ = hidden_states(cfg, fmt, seed, np.pad(ids, (0, padded - t)), fault,
                         handover=int(np.asarray(rows)[0]) + 1)
    flt = fault_flags(fault)
    flt.pop("reset_at")
    out = _head(hashable(cfg), top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), flt)
    return tuple(np.asarray(x) for x in out)
