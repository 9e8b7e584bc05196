"""Plain reference for the ``kimi_linear`` family (Moonshot Kimi-Linear): the
forward pass in ``jax.numpy`` and float32, matrix products at ``highest``
precision, no cache, no kernel, no batching, no chunked form. Written from
the published equations (Kimi Delta Attention, arXiv 2510.26692; DeepSeek-V2's
multi-head latent attention; the model's ``config.json``), not from
``mlx_sharding_tpu/models/kimi_linear.py``.

Pre-norm residual blocks, eps ``rms_norm_eps``, no bias anywhere: ``h = h +
mixer(rmsnorm(h, input_layernorm))``; ``h = h + ffn(rmsnorm(h,
post_attention_layernorm))``; logits ``= rmsnorm(h, norm) @ lm_head``
(untied). ``linear_attn_config`` numbers the layers from 1: those of
``full_attn_layers`` are MLA, those of ``kda_layers`` KDA.

- **KDA** (``H`` heads of ``D``, ``K`` taps), ``u`` the normed input: ``q, k,
  v = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))``, a causal
  depthwise convolution (``out_t = sum_j w[:, j] * in_{t - (K-1) + j}``, zeros
  before position 0, no bias); per head ``q = l2norm(q) * D**-0.5``, ``k =
  l2norm(k)`` (``x / sqrt(sum x^2 + 1e-6)``); ``g = -exp(A_log[h]) *
  softplus((W_fb (W_fa u))[h, d] + dt_bias[h, d])``, ``alpha = exp(g)``, a
  decay a KEY CHANNEL; ``beta = sigmoid(W_b u)[h]``; the recurrence, ONE
  POSITION AT A TIME in a ``lax.scan`` over a ``(D, D)`` state a head: ``S' =
  alpha_t (.) S_{t-1}`` (rows scaled), ``S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T``, ``o_t = S_t^T q_t``; ``y = rmsnorm_over_D(o) * o_norm *
  sigmoid(W_gb (W_ga u))``; ``W_o y``.
- **MLA** in its DECOMPRESSED form: ``q = W_q u -> (heads, nope + rope)``;
  ``[c, k_pe] = W_kva u``; ``c = rmsnorm(c, kv_a_layernorm)``; ``[k_nope, v] =
  W_kvb c -> (heads, nope + v)``; ``k = [k_nope, k_pe for every head]``;
  causal ``softmax(q k^T * (nope + rope)**-0.5) v``; ``W_o``. NO rotary
  embedding (``mla_use_nope``; ``rope_theta`` is inert). The program serves
  the compressed form (``kv_b`` absorbed into the query and the output), so
  this form checks the absorption.
- **FFN**: the first ``first_k_dense_replace`` layers ``W_down(silu(W_gate r)
  * W_up r)``. The others: ``s = sigmoid(W_r r)`` over all experts; the top
  ``num_experts_per_token`` of ``s + e_score_correction_bias`` are chosen
  (``num_expert_group = topk_group = 1``: no group limit); their weights are
  their own ``s`` over their sum (``moe_renormalize``) times
  ``routed_scaling_factor``; routed SwiGLU experts, a plain loop over the
  held ones, plus one shared SwiGLU expert on every token.

Departures.
- Attention is computed in blocks of ``Q_BLOCK`` queries (each against every
  key, masked), so that 1.5k positions of 32 heads fit beside a served model:
  the same numbers, no (heads, T, T) score matrix.
- The SHARE, the sliced vocabulary and the weights: as
  ``benchmarks/reference/afmoe.py`` says. A KDA layer's three projections are
  one seeded matrix ``qkv_proj`` (its columns ``[q, k, v]``) and the two
  low-rank gates' inner projections one ``gate_a`` (``[f_a, g_a]``), as the
  program holds them: matrices of independent entries either way. The small
  vectors (``A_log``, ``dt_bias``, the convolution's taps) are
  ``benchmarks/reference/nemotron_h.py``'s ``small_vector``'s
  (:func:`small_shapes`); the selection bias is FITTED (:func:`balancing_biases`):
  it does what a trained router's balancing bias is there for, every expert
  chosen equally often, so that the held sixteenth sees its share whatever
  the seed.

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``kda_state_reset`` zeroes the middle KDA layer's state and
convolution inputs where the compared rows begin (the position after
``rows[0]``: the hand-over from the last prefill chunk to the first decode
step). ``kda_no_decay``: ``alpha = 1``, the plain delta rule a port that
dropped the gate would serve. ``kda_state_bf16`` rounds every KDA layer's
state to bfloat16 after each position. ``mla_rotary_on`` rotates ``q_pe`` and
``k_pe`` with ``benchmarks/reference/deepseek_v2.py``'s own ``rope``
(interleaved pairs at ``rope_theta``): what a port that reused
``deepseek_v2``'s path unchanged would serve. ``moe_no_renorm`` leaves the
chosen scores' sum out. ``weights_fp8`` rounds every matrix to 3 mantissa bits
(float8 e4m3's precision, bf16's range): the nearest precision below the one
a bf16 configuration states.

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
from benchmarks.reference.deepseek_v2 import rope, rope_tables
from benchmarks.reference.nemotron_h import HANDOVER, _small_stack, rmsnorm, small_vector

GROUPS = ("dense", "kda", "mla")
EXPERTS = ("w_gate", "w_up", "w_down")
Q_BLOCK = 128
#: the balancing bias is fitted on this many seeded router inputs a layer, in
#: this many steps of a shrinking size (:func:`balancing_biases`)
BALANCE_ROWS, BALANCE_STEPS = 8192, 300

#: fault name -> (reset position of the middle KDA layer or -1, alpha = 1,
#: state rounded to bf16, rotary on the MLA layers, renormalisation left
#: out, mantissa bits kept of every matrix: 7 is bf16's own)
FAULTS = {
    None: (-1, False, False, False, False, 7),
    "kda_state_reset": (HANDOVER, False, False, False, False, 7),
    "kda_no_decay": (-1, True, False, False, False, 7),
    "kda_state_bf16": (-1, False, True, False, False, 7),
    "mla_rotary_on": (-1, False, False, True, False, 7),
    "moe_no_renorm": (-1, False, False, False, True, 7),
    "weights_fp8": (-1, False, False, False, False, 3),
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    lin = dict(cfg["linear_attn_config"])  # a dict, or hashable()'s pairs
    share = int(cfg.get("moe_expert_share", 1))
    heads = cfg["num_attention_heads"]
    nope, rope_d = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "kda_width": lin["num_heads"] * lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        # ASSUMED: the two low-rank gates' inner width is the KDA head_dim
        "gate_rank": lin["head_dim"],
        "mla": set(lin["full_attn_layers"]),
        "q": heads * (nope + rope_d), "kv_b": heads * (nope + cfg["v_head_dim"]),
        "o": heads * cfg["v_head_dim"],
        "row": cfg["kv_lora_rank"] + rope_d,
        "share": share,
        "base": int(cfg.get("moe_expert_share_index", 0)) * cfg["num_experts"],
        "router": cfg["num_experts"] * share,
    }


def layer_groups(cfg: dict) -> list:
    """Each layer's group, in order: ``mla``, or a KDA layer's ``dense``
    (among the first ``first_k_dense_replace``) or ``kda``."""
    mla, fk = dims(cfg)["mla"], cfg.get("first_k_dense_replace", 0)
    out = ["mla" if i + 1 in mla else "dense" if i < fk else "kda"
           for i in range(cfg["num_hidden_layers"])]
    if "mla" in out[:fk]:
        raise ValueError("a leading dense layer with MLA is not written here")
    return out


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
    mi, e = cfg["moe_intermediate_size"], cfg["num_experts"]

    def group(g, mixer, ffn):
        lin = lambda name, out, inn, **kw: Unit(f"{g}.{name}", "linear", out, inn, **kw)  # noqa: E731
        out = {"norm": Unit(f"{g}.norm", "norm", h, 0),
               "ffn_norm": Unit(f"{g}.ffn_norm", "norm", h, 0)}
        if mixer == "kda":
            out.update(
                qkv_proj=lin("qkv_proj", 3 * dm["kda_width"], h),
                gate_a=lin("gate_a", 2 * dm["gate_rank"], h),
                f_b=lin("f_b", dm["kda_width"], dm["gate_rank"]),
                g_b=lin("g_b", dm["kda_width"], dm["gate_rank"]),
                b_proj=lin("b_proj", dm["kda_heads"], h),
                o_norm=Unit(f"{g}.o_norm", "norm", dm["kda_dim"], 0),
                o_proj=lin("o_proj", h, dm["kda_width"]),
            )
        else:
            out.update(
                q_proj=lin("q_proj", dm["q"], h),
                kv_a_proj=lin("kv_a_proj", dm["row"], h),
                kv_a_norm=Unit(f"{g}.kv_a_norm", "norm", cfg["kv_lora_rank"], 0),
                kv_b_proj=lin("kv_b_proj", dm["kv_b"], cfg["kv_lora_rank"], keep_dense=True),
                o_proj=lin("o_proj", h, dm["o"]),
            )
        if ffn == "mlp":
            out.update(
                gate_proj=lin("gate_proj", cfg["intermediate_size"], h),
                up_proj=lin("up_proj", cfg["intermediate_size"], h),
                down_proj=lin("down_proj", h, cfg["intermediate_size"]),
            )
        else:
            out.update(
                router=lin("router", dm["router"], h, keep_dense=True),
                shared_gate=lin("shared_gate", mi, h), shared_up=lin("shared_up", mi, h),
                shared_down=lin("shared_down", h, mi),
                w_gate=lin("w_gate", mi, h, experts=e), w_up=lin("w_up", mi, h, experts=e),
                w_down=lin("w_down", h, mi, experts=e),
            )
        return out

    return {
        "dense": group("dense", "kda", "mlp"),
        "kda": group("kda", "kda", "moe"),
        "mla": group("mla", "mla", "moe"),
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "lm_head": Unit("lm_head", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def small_shapes(cfg: dict) -> dict:
    """{leaf name: shape} of a KDA layer's vectors that are no ``Unit``, drawn
    by ``benchmarks/reference/nemotron_h.py``'s ``small_vector`` as it draws
    Mamba-2's: ``exp(A_log)`` uniform in 1..16 a head; ``dt_bias =
    softplus**-1(dt0)``, ``dt0`` log-uniform in 0.001..0.1 a KEY CHANNEL (the
    low-rank gate's unit-variance part spreads a position's ``softplus``
    about ``dt0`` by a factor e either way), so a channel forgets over 1 to
    1000 positions; convolution taps uniform in ``+-K**-0.5``, bf16."""
    dm = dims(cfg)
    return {"conv_w": (3 * dm["kda_width"], dm["taps"]),
            "A_log": (dm["kda_heads"],), "dt_bias": (dm["kda_width"],)}


@functools.partial(jax.jit, static_argnames=("cfg_items", "group", "n"))
def _balance(cfg_items, group, skey, n):
    cfg = dict(cfg_items)
    e, k = dims(cfg)["router"], cfg["num_experts_per_token"]
    unit = model_units(cfg)[group]["router"]

    def one_layer(rank):
        x = jax.random.normal(W.unit_key(skey, f"{group}.router_bias", rank),
                              (BALANCE_ROWS, cfg["hidden_size"]), jnp.float32)
        p = jax.nn.sigmoid(x @ W.dense_logical(skey, unit, rank))
        size = 0.5 * jnp.std(p)

        def step(i, b):
            _, top_i = jax.lax.top_k(p + b, k)
            load = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, e), axis=-2), axis=0) / k
            return b - size / (1.0 + i / 30.0) * (load * e - 1.0)

        return jax.lax.fori_loop(0, BALANCE_STEPS, step, jnp.zeros(e, jnp.float32))

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(one_layer, jnp.arange(n))


@functools.lru_cache(maxsize=4)
def balancing_biases(cfg_items: tuple, seed: int) -> dict:
    """Every expert layer's selection bias, ``{group: (layers, experts)}``
    float32: fitted so that the layer's own router, on ``BALANCE_ROWS``
    seeded unit-variance inputs, chooses every expert equally often — ``b <-
    b - size (load x experts - 1)`` with the chosen shares ``load`` under ``s
    + b``, the step shrinking as ``1 / (1 + i / 30)`` from half the spread of
    ``s`` (``benchmarks/reference/zaya.py``'s fit). One cached tree serves
    the program's tree and the reference, so both hold the same numbers."""
    groups = group_layers(dict(cfg_items))
    return {g: _balance(cfg_items, g, W.seed_key(seed), len(groups[g]))
            for g in ("kda", "mla") if g in groups}


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/kimi_linear.map_weights`` stacks them (a layer's
    row is its rank in its group), the matrices generated when the engine's
    placement slices them (``LazyStack``), the small vectors and the fitted
    selection bias resident; ``embed``, ``final_norm``, ``lm_head``."""
    if fmt != "bf16":
        raise ValueError(f"kimi_linear is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    biases = balancing_biases(hashable(cfg), seed)
    layers = {}
    for group, idxs in group_layers(cfg).items():
        layers[group] = {
            name: W.layer_stack(skey, unit, fmt, 0, len(idxs))
            for name, unit in units[group].items()
        }
        if group != "mla":
            for name, shape in small_shapes(cfg).items():
                layers[group][name] = _small_stack(skey, group, name, len(idxs), shape)
        if group != "dense":
            layers[group]["router_bias"] = biases[group]
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
        "lm_head": {"weight": W.top_leaf(skey, top["lm_head"], fmt)},
    }


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of one position's row ``[latent, k_pe]`` in one MLA layer (bf16)."""
    return 2 * dims(cfg)["row"]


def paged_attn_step_bytes(cfg: dict, active_slots: float, context: float) -> float:
    """Latent bytes a decode step's attention must read: per active slot its
    ``context`` rows in every MLA layer."""
    return active_slots * context * len(group_layers(cfg).get("mla", [])) * kv_row_bytes(cfg)


def kda_state_step_bytes(cfg: dict, active_slots: float) -> float:
    """Bytes of recurrent state one decode step must read and write: per
    active slot and KDA layer, the state ``(H, D, D)`` (float32) and the
    convolution's last ``K - 1`` inputs (bf16), each once in and once out."""
    dm = dims(cfg)
    state = 4 * dm["kda_heads"] * dm["kda_dim"] ** 2
    conv = 2 * 3 * dm["kda_width"] * (dm["taps"] - 1)
    groups = group_layers(cfg)
    n_kda = len(groups.get("dense", [])) + len(groups.get("kda", []))
    return 2.0 * active_slots * n_kda * (state + conv)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every weight outside the routed experts (both
    mixers, norms, the dense MLP, router at its full width, shared expert,
    small vectors, the head's slice), the DISTINCT held experts the active
    rows' choices hit (a balanced router: the uniform formula), the KDA
    layers' recurrent state of the active slots in and out, and the MLA
    layers' rows of the ``cache_tokens`` tokens in the pool. Not counted:
    activations, the embedding's rows, cache writes. A lower bound."""
    units = model_units(cfg)
    groups = group_layers(cfg)
    dm = dims(cfg)
    small = sum((2 if n == "conv_w" else 4) * math.prod(s)
                for n, s in small_shapes(cfg).items())
    fixed = 0
    for g, idxs in groups.items():
        per_layer = sum(unit_bytes(u, fmt) for u in units[g].values() if not u.experts)
        per_layer += small if g != "mla" else 0
        per_layer += 4 * dm["router"] if g != "dense" else 0
        fixed += len(idxs) * per_layer
    one_expert = sum(unit_bytes(units["kda"][n], fmt) for n in EXPERTS)
    hit = expected_distinct_experts(
        dm["router"], cfg["num_experts_per_token"], active_slots
    ) / dm["share"]
    n_moe = len(groups.get("kda", [])) + len(groups.get("mla", []))
    out = {
        # the head's slice and the final norm; the embedding's rows are not read
        "fixed_weights": fixed + unit_bytes(units["top"]["lm_head"], fmt)
        + unit_bytes(units["top"]["final_norm"], fmt),
        "routed_experts": n_moe * hit * one_expert,
        "recurrent_state": kda_state_step_bytes(cfg, active_slots),
        "kv_pages": cache_tokens * kv_row_bytes(cfg) * len(groups.get("mla", [])),
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _parts(cfg_items, group, skey, rank, coarse):
    """``(cfg, lin, nrm)`` for one layer: ``lin(x, name, expert=None) -> x @
    M[name]`` and ``nrm(name)`` its norm weight. ``coarse`` (a run-time
    boolean): matrices rounded to 3 mantissa bits first — ``reduce_precision``
    and not a pair of converts: the TPU compiler may drop a round trip
    through a narrower type."""
    cfg = dict(cfg_items)
    units = model_units(cfg)[group]

    def lin(x, name, expert=None):
        m = W.dense_logical(skey, units[name], rank, expert)
        return x @ jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m)

    return cfg, lin, lambda name: W.logical_norm(skey, units[name], rank)


@functools.partial(jax.jit, static_argnames=("cfg_items", "group"))
def _kda_half(cfg_items, group, skey, rank, h, reset_at, no_decay, round_state, coarse):
    """``h + kda(rmsnorm(h))``. ``reset_at``: the position before which this
    layer's state and convolution inputs are lost (-1: never)."""
    cfg, lin, nrm = _parts(cfg_items, group, skey, rank, coarse)
    dm = dims(cfg)
    t = h.shape[0]
    nh, d, k, width = dm["kda_heads"], dm["kda_dim"], dm["taps"], dm["kda_width"]
    small = lambda name: small_vector(  # noqa: E731
        skey, group, name, rank, small_shapes(cfg)[name]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h, nrm("norm"), cfg["rms_norm_eps"])
        qkv = lin(u, "qkv_proj")
        pos = jnp.arange(t)
        # causal depthwise convolution; an input from before the reset is lost
        w = small("conv_w")  # (3 H D, K)
        conv = jnp.zeros_like(qkv)
        for j in range(k):
            back = k - 1 - j  # tap j reads the input `back` positions earlier
            lost = (pos < back) | ((pos >= reset_at) & (pos - back < reset_at))
            conv = conv + jnp.where(lost[:, None], 0.0, jnp.roll(qkv, back, axis=0)) * w[:, j]
        qkv = jax.nn.silu(conv)
        heads = lambda z: z.reshape(t, nh, d)  # noqa: E731
        q = l2norm(heads(qkv[:, :width])) * d ** -0.5
        kk = l2norm(heads(qkv[:, width:2 * width]))
        v = heads(qkv[:, 2 * width:])
        inner = lin(u, "gate_a")
        f_a, g_a = inner[:, :dm["gate_rank"]], inner[:, dm["gate_rank"]:]
        g = -jnp.exp(small("A_log"))[:, None] * jax.nn.softplus(
            heads(lin(f_a, "f_b") + small("dt_bias")))
        alpha = jnp.where(no_decay, 1.0, jnp.exp(g))
        beta = jax.nn.sigmoid(lin(u, "b_proj"))

        def step(s, xs):
            q_t, k_t, v_t, a_t, b_t, pos_t = xs
            s = jnp.where(pos_t == reset_at, 0.0, s)
            s = a_t[:, :, None] * s
            r = jnp.sum(s * k_t[:, :, None], axis=1)  # S'^T k (H, D)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - r)[:, None, :]
            s = jnp.where(round_state, jax.lax.reduce_precision(s, 8, 7), s)
            return s, jnp.sum(s * q_t[:, :, None], axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32),
                            (q, kk, v, alpha, beta, pos))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
        y = o * nrm("o_norm") * heads(jax.nn.sigmoid(lin(g_a, "g_b")))
        return h + lin(y.reshape(t, width), "o_proj")


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _mla_half(cfg_items, skey, rank, h, rotary_on, coarse):
    """``h + mla(rmsnorm(h))``, decompressed: per-head keys and values."""
    cfg, lin, nrm = _parts(cfg_items, "mla", skey, rank, coarse)
    t = h.shape[0]
    nope, rope_d, v_d = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    lat = cfg["kv_lora_rank"]
    inv_freq, cos_scale, _ = rope_tables(cfg)  # rope_scaling null: plain rotary
    rotate = lambda x: rope(x, jnp.arange(t), inv_freq, cos_scale)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h, nrm("norm"), cfg["rms_norm_eps"])
        q = lin(u, "q_proj").reshape(t, -1, nope + rope_d)
        ckv = lin(u, "kv_a_proj")
        c = rmsnorm(ckv[:, :lat], nrm("kv_a_norm"), cfg["rms_norm_eps"])
        k_pe = ckv[:, None, lat:]
        q_pe = q[..., nope:]
        q_pe = jnp.where(rotary_on, rotate(q_pe), q_pe)
        k_pe = jnp.where(rotary_on, rotate(k_pe), k_pe)
        kv = lin(c, "kv_b_proj").reshape(t, -1, nope + v_d)
        nh = kv.shape[1]
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (t, nh, rope_d))], axis=-1)
        v = kv[..., nope:]
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k_pos = jnp.arange(t)

        def block(args):
            qb, q_pos = args  # (Q, H, nope + rope), (Q,)
            s = jnp.einsum("qhd,shd->hqs", qb, k) * (nope + rope_d) ** -0.5
            s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v).reshape(-1, nh * v_d)

        qb = min(Q_BLOCK, t)
        if t % qb:
            raise ValueError(f"{t} positions are no multiple of the query block {qb}")
        out = jax.lax.map(block, (q.reshape(t // qb, qb, nh, -1), k_pos.reshape(t // qb, qb)))
        return h + lin(out.reshape(t, nh * v_d), "o_proj")


def _moe(cfg, lin, bias, u, no_renorm):
    """``(ffn(u), the choices)`` of one expert layer: the held experts' part
    and the shared expert."""
    dm = dims(cfg)
    if cfg.get("num_expert_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("only num_expert_group = topk_group = 1 is written here")
    scores = jax.nn.sigmoid(lin(u, "router"))
    _, top_i = jax.lax.top_k(scores + bias, cfg["num_experts_per_token"])
    top_v = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.get("moe_renormalize", True):
        top_v = top_v / jnp.where(no_renorm, 1.0, top_v.sum(axis=-1, keepdims=True) + 1e-20)
    top_v = top_v * float(cfg.get("routed_scaling_factor", 1.0))

    def one_expert(acc, e):  # e: the expert's place among those held
        coef = jnp.sum(jnp.where(top_i == e + dm["base"], top_v, 0.0), axis=-1)
        y = lin(jax.nn.silu(lin(u, "w_gate", e)) * lin(u, "w_up", e), "w_down", e)
        return acc + coef[:, None] * y, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(cfg["num_experts"]))
    shared = lin(jax.nn.silu(lin(u, "shared_gate")) * lin(u, "shared_up"), "shared_down")
    return routed + shared, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "group"))
def _ffn_half(cfg_items, group, skey, rank, h, bias, no_renorm, coarse):
    """``(h + ffn(rmsnorm(h)), the expert layer's choices)``."""
    cfg, lin, nrm = _parts(cfg_items, group, skey, rank, coarse)
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h, nrm("ffn_norm"), cfg["rms_norm_eps"])
        if group == "dense":
            m = lin(jax.nn.silu(lin(u, "gate_proj")) * lin(u, "up_proj"), "down_proj")
            return h + m, jnp.zeros((0,), jnp.int32)
        m, top_i = _moe(cfg, lin, bias, u, no_renorm)
        return h + m, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "top"))
def _head(cfg_items, top, skey, h, ids_wanted, coarse):
    cfg, lin, nrm = _parts(cfg_items, "top", skey, 0, coarse)
    with jax.default_matmul_precision("highest"):
        logits = lin(rmsnorm(h, nrm("final_norm"), cfg["rms_norm_eps"]), "lm_head")
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(cfg_items, skey, ids):
    cfg = dict(cfg_items)
    return W.logical_rows(skey, model_units(cfg)["top"]["embed"], "bf16", ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: its scalars, and
    ``linear_attn_config`` as sorted pairs with tuples for its lists."""
    out = {k: v for k, v in cfg.items()
           if isinstance(v, (int, float, str, bool, type(None)))}
    out["linear_attn_config"] = tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, tuple)) else v)
        for k, v in dict(cfg["linear_attn_config"]).items()
    ))
    return tuple(sorted(out.items()))


def hidden_states(cfg: dict, fmt: str, seed: int, ids, fault=None, handover: int = -1):
    """The final hidden states ``(T, hidden)`` of one sequence (before the
    final norm) and each expert layer's choices. ``handover``: the position a
    ``HANDOVER`` reset falls on."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fmt != "bf16":
        raise ValueError(f"kimi_linear is served in bf16 here, not {fmt!r}")
    reset_at, no_decay, round_state, rotary_on, no_renorm, mantissa = FAULTS[fault]
    reset_at = handover if reset_at == HANDOVER else reset_at
    coarse = jnp.asarray(mantissa < 7)
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    biases = balancing_biases(cfg_items, seed)
    h = _embed(cfg_items, skey, jnp.asarray(ids, jnp.int32))
    groups = layer_groups(cfg)
    middle = [i for i, g in enumerate(groups) if g != "mla"]
    middle = middle[len(middle) // 2]
    seen: dict = {}
    picks = []
    for i, group in enumerate(groups):
        rank = seen.get(group, 0)
        seen[group] = rank + 1
        r = jnp.asarray(rank, jnp.int32)
        if group == "mla":
            h = _mla_half(cfg_items, skey, r, h, jnp.asarray(rotary_on), coarse)
        else:
            at = reset_at if i == middle else -1
            h = _kda_half(cfg_items, group, skey, r, h, jnp.asarray(at, jnp.int32),
                          jnp.asarray(no_decay), jnp.asarray(round_state), coarse)
        bias = biases[group][rank] if group != "dense" else jnp.zeros((0,), jnp.float32)
        h, top_i = _ffn_half(cfg_items, group, skey, r, h, bias,
                             jnp.asarray(no_renorm), coarse)
        if group != "dense":
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
    coarse = jnp.asarray(FAULTS[fault][5] < 7)
    out = _head(hashable(cfg), top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), coarse)
    return tuple(np.asarray(x) for x in out)
