"""Plain reference for the ``olmo_hybrid`` family (Olmo Hybrid): the forward
pass in ``jax.numpy`` and float32, matrix products at ``highest`` precision, no
cache, no kernel, no batching, no chunked form. Written from the published
equations (Gated DeltaNet, arXiv 2412.06464; Olmo 2's block, arXiv 2501.00656;
the model's ``config.json``), not from ``mlx_sharding_tpu/models/olmo_hybrid.py``.

No bias anywhere, eps ``rms_norm_eps``, ``rms(x; w) = x * rsqrt(mean x^2 + eps)
* w`` with a PLAIN weight. The residual path is Olmo 2's REORDERED norm, in
both kinds of layer: a sub-layer reads the residual stream un-normed and its
output is normed before it is added: ``h = x + rms(mixer(x);
post_attention_layernorm)``; ``y = h + rms(mlp(h); post_feedforward_layernorm)``
with ``mlp(h) = W_down (silu(W_gate h) * W_up h)``; logits ``= rms(y; norm) @
lm_head`` (untied, embedding unscaled). ``layer_types[i]`` picks the mixer.

- **Linear attention (Gated DeltaNet with negative eigenvalues)**, ``H`` heads
  (as many key heads as value heads), keys ``Dk`` wide and values ``Dv``, ``K``
  taps: ``q, k = W_q x, W_k x`` (``H Dk`` each), ``v = W_v x`` (``H Dv``), each
  through its own causal depthwise convolution (``out_t = sum_j w[:, j] * in_{t
  - (K-1) + j}``, zeros before position 0, no bias), then ``silu``; per head
  ``q = l2norm(q) * Dk**-0.5``, ``k = l2norm(k)`` (``x * rsqrt(sum x^2 +
  1e-6)``); ``beta = 2 sigmoid(W_b x)[h]`` (``linear_allow_neg_eigval``: the
  step's ``I - beta k k^T`` has the eigenvalue ``1 - beta`` in ``(-1, 1)``);
  ``g = -exp(A_log[h]) * softplus((W_a x)[h] + dt_bias[h])``, ``alpha =
  exp(g)``; the recurrence, ONE POSITION AT A TIME in a ``lax.scan`` over a
  ``(Dk, Dv)`` state a head: ``S' = alpha_t S_{t-1}``, ``S_t = S' + beta_t k_t
  (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``; ``y = rms_over_Dv(o; o_norm) *
  silu(W_g x)``; ``W_o y``.
- **Full attention**: ``q = rms(W_q x; q_norm)``, ``k = rms(W_k x; k_norm)``
  over ALL ``heads * head_dim`` channels, BEFORE the split into heads; ``v = W_v
  x``; NO rotation (``rope_parameters.rope_theta`` null); causal ``softmax(q
  k^T * head_dim**-0.5) v`` (query head ``j`` reads K/V head ``j // (heads /
  kv_heads)``); ``W_o``.

Departures.
- Attention is computed in blocks of ``Q_BLOCK`` queries (each against every
  key, masked): the same numbers, no (heads, T, T) score matrix.
- The weights are seeded (``benchmarks/weights.py``), not the checkpoint's.
  ``W_q | W_k | W_v | W_g`` of a linear layer are the column blocks of ONE seeded
  matrix ``qkvz_proj`` and ``W_b | W_a`` of ``ba_proj``, as the program holds
  them joined (matrices of independent entries either way); the three
  convolutions are the row blocks of one seeded ``conv_w``. The small vectors
  (``A_log``, ``dt_bias`` one a head, the taps) are
  ``benchmarks/reference/nemotron_h.py``'s ``small_vector``'s, as
  ``qwen3-next-80b-bf16-ep4.json`` ``gdn_vectors`` says.

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``gdn_state_reset`` zeroes the middle linear layer's state and
convolution inputs where the compared rows begin (the position after
``rows[0]``: the hand-over from the last prefill chunk to the first decode
step). ``beta_unscaled``: ``beta = sigmoid(b)``, the rule without negative
eigenvalues (what a port that reused ``qwen3_next``'s mixer unchanged would
serve). ``qk_norm_per_head``: q and k normed over each head's ``head_dim``
channels (the weight's slice of that head), ``qwen3``'s norm. ``linear_prenorm``:
a linear layer's mixer in the pre-norm arrangement, ``h = x + mixer(rms(x;
post_attention_layernorm))`` — the arrangement the catalog has no key for.
``rope_on``: split-half rotary at ``ROPE_ON_THETA`` on the full layers' q and
k. ``gdn_state_bf16`` rounds every linear layer's state to bfloat16 after each
position. ``weights_fp8`` rounds every matrix to 3 mantissa bits (float8
e4m3's precision, bf16's range): the nearest precision below the one a bf16
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
from benchmarks.bytes_model import unit_bytes
from benchmarks.config import Unit
from benchmarks.reference.nemotron_h import HANDOVER, _small_stack, small_vector

GROUPS = ("gdn", "attn")
KINDS = {"linear_attention": "gdn", "full_attention": "attn"}
Q_BLOCK = 128
#: the rotary base of the ``rope_on`` variant (Olmo 3's)
ROPE_ON_THETA = 500000.0

#: fault name -> what departs from the clean pass (:data:`CLEAN`)
CLEAN = {
    "reset_at": -1, "beta_unscaled": False, "qk_per_head": False,
    "linear_prenorm": False, "rope_on": False, "state_bf16": False,
    "mantissa": 7,  # bits kept of every matrix: 7 is bf16's own
}
FAULTS = {
    None: {},
    "gdn_state_reset": {"reset_at": HANDOVER},
    "beta_unscaled": {"beta_unscaled": True},
    "qk_norm_per_head": {"qk_per_head": True},
    "linear_prenorm": {"linear_prenorm": True},
    "rope_on": {"rope_on": True},
    "gdn_state_bf16": {"state_bf16": True},
    "weights_fp8": {"mantissa": 3},
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    if hk != hv:
        raise ValueError("only linear_num_key_heads == linear_num_value_heads is written here")
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    heads, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return {
        "lin_heads": hv, "key_dim": dk, "value_dim": dv,
        "key_width": hk * dk, "value_width": hv * dv, "conv": 2 * hk * dk + hv * dv,
        "taps": cfg["linear_conv_kernel_dim"],
        "heads": heads, "kv_heads": hkv, "head_dim": hd,
        "beta_scale": 2.0 if cfg.get("linear_allow_neg_eigval", True) else 1.0,
    }


def layer_groups(cfg: dict) -> list:
    """Each layer's group, in order, from ``layer_types``."""
    return [KINDS[t] for t in cfg["layer_types"]]


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
    h, mi, dm = cfg["hidden_size"], cfg["intermediate_size"], dims(cfg)

    def group(g):
        lin = lambda name, out, inn: Unit(f"{g}.{name}", "linear", out, inn)  # noqa: E731
        nrm = lambda name, n: Unit(f"{g}.{name}", "norm", n, 0)  # noqa: E731
        out = {"mixer_norm": nrm("mixer_norm", h), "ffn_norm": nrm("ffn_norm", h)}
        if g == "gdn":
            out.update(
                qkvz_proj=lin("qkvz_proj", dm["conv"] + dm["value_width"], h),
                ba_proj=lin("ba_proj", 2 * dm["lin_heads"], h),
                o_norm=nrm("o_norm", dm["value_dim"]),
                o_proj=lin("o_proj", h, dm["value_width"]),
            )
        else:
            qw, kvw = dm["heads"] * dm["head_dim"], dm["kv_heads"] * dm["head_dim"]
            out.update(
                q_proj=lin("q_proj", qw, h), k_proj=lin("k_proj", kvw, h),
                v_proj=lin("v_proj", kvw, h),
                q_norm=nrm("q_norm", qw), k_norm=nrm("k_norm", kvw),
                o_proj=lin("o_proj", h, qw),
            )
        out.update(
            gate_proj=lin("gate_proj", mi, h), up_proj=lin("up_proj", mi, h),
            down_proj=lin("down_proj", h, mi),
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
    draws Mamba-2's (``qwen3-next-80b-bf16-ep4.json`` ``gdn_vectors``):
    ``exp(A_log)`` uniform in 1..16 and ``dt_bias = softplus**-1(dt0)``,
    ``dt0`` log-uniform in 0.001..0.1, one a head, so a head forgets over 1 to
    1000 positions; convolution taps uniform in ``+-K**-0.5``, bf16."""
    dm = dims(cfg)
    return {"conv_w": (dm["conv"], dm["taps"]),
            "A_log": (dm["lin_heads"],), "dt_bias": (dm["lin_heads"],)}


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/olmo_hybrid.map_weights`` stacks them (a layer's
    row is its rank in its group), the matrices generated when the engine's
    placement slices them (``LazyStack``), the small vectors resident;
    ``embed``, ``final_norm``, ``lm_head``."""
    if fmt != "bf16":
        raise ValueError(f"olmo_hybrid is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    layers = {}
    for group, idxs in group_layers(cfg).items():
        n = len(idxs)
        tree = {name: W.layer_stack(skey, unit, fmt, 0, n)
                for name, unit in units[group].items()}
        if group == "gdn":
            for name, shape in small_shapes(cfg).items():
                tree[name] = _small_stack(skey, group, name, n, shape)
        layers[group] = tree
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
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
    active slot and linear layer, the state ``(H, Dk, Dv)`` (float32, no
    padded byte) and the convolutions' last ``K - 1`` inputs (bf16), each
    once in and once out."""
    dm = dims(cfg)
    state = 4 * dm["lin_heads"] * dm["key_dim"] * dm["value_dim"]
    conv = 2 * dm["conv"] * (dm["taps"] - 1)
    return 2.0 * active_slots * len(group_layers(cfg).get("gdn", [])) * (state + conv)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every layer's matrices, norms and small vectors,
    the head and the final norm, the linear layers' recurrent state of the
    active slots in and out, and the attention layers' rows of the
    ``cache_tokens`` tokens in the pool. Not counted: activations, the
    embedding's rows, cache writes. A lower bound."""
    units = model_units(cfg)
    groups = group_layers(cfg)
    small = sum((2 if n == "conv_w" else 4) * math.prod(s)
                for n, s in small_shapes(cfg).items())
    fixed = 0
    for g, idxs in groups.items():
        per_layer = sum(unit_bytes(u, fmt) for u in units[g].values())
        fixed += len(idxs) * (per_layer + (small if g == "gdn" else 0))
    out = {
        # the head and the final norm; the embedding's rows are not read
        "fixed_weights": fixed + unit_bytes(units["top"]["lm_head"], fmt)
        + unit_bytes(units["top"]["final_norm"], fmt),
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


def rope_half(x, pos, theta: float):
    """Rotary embedding on all channels of ``x (T, heads, D)`` at positions
    ``pos (T,)``, half-split: channel ``i < D / 2`` pairs with ``i + D / 2``
    and turns by ``pos * theta**(-2 i / D)``."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None, None] * freq  # (T, 1, D / 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _parts(cfg_items, group, skey, rank, flt):
    """``(cfg, lin, nrm)`` for one layer: ``lin(x, name) -> x @ M[name]``,
    ``nrm(name)`` a norm leaf's seeded vector. ``flt["coarse"]`` (a run-time
    boolean): matrices rounded to 3 mantissa bits first — ``reduce_precision``
    and not a pair of converts: the TPU compiler may drop a round trip through
    a narrower type."""
    cfg = dict(cfg_items)
    units = model_units(cfg)[group]

    def lin(x, name):
        m = W.dense_logical(skey, units[name], rank)
        return x @ jnp.where(flt["coarse"], jax.lax.reduce_precision(m, 8, 3), m)

    return cfg, lin, lambda name: W.logical_norm(skey, units[name], rank)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _gdn_half(cfg_items, skey, rank, x, reset_at, flt):
    """``x + rms(gated_delta_net(x); post_attention_layernorm)``.
    ``reset_at``: the position before which this layer's state and
    convolution inputs are lost (-1: never)."""
    cfg, lin, nrm = _parts(cfg_items, "gdn", skey, rank, flt)
    dm = dims(cfg)
    t = x.shape[0]
    nh, dk, dv, taps = dm["lin_heads"], dm["key_dim"], dm["value_dim"], dm["taps"]
    kw, conv_dim, eps = dm["key_width"], dm["conv"], cfg["rms_norm_eps"]
    small = lambda name: small_vector(  # noqa: E731
        skey, "gdn", name, rank, small_shapes(cfg)[name]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        # the wrong arrangement: the mixer reads the NORMED stream
        u = jnp.where(flt["linear_prenorm"], unit_rms(x, eps) * nrm("mixer_norm"), x)
        qkvz = lin(u, "qkvz_proj")
        qkv, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
        pos = jnp.arange(t)
        # three causal depthwise convolutions side by side; an input from
        # before the reset is lost
        w = small("conv_w")  # (2 H Dk + H Dv, K)
        conv = jnp.zeros_like(qkv)
        for j in range(taps):
            back = taps - 1 - j  # tap j reads the input `back` positions earlier
            lost = (pos < back) | ((pos >= reset_at) & (pos - back < reset_at))
            conv = conv + jnp.where(lost[:, None], 0.0, jnp.roll(qkv, back, axis=0)) * w[:, j]
        qkv = jax.nn.silu(conv)
        q = l2norm(qkv[:, :kw].reshape(t, nh, dk)) * dk ** -0.5
        k = l2norm(qkv[:, kw:2 * kw].reshape(t, nh, dk))
        v = qkv[:, 2 * kw:].reshape(t, nh, dv)
        ba = lin(u, "ba_proj")
        beta = jnp.where(flt["beta_unscaled"], 1.0, dm["beta_scale"]) * jax.nn.sigmoid(ba[:, :nh])
        g = -jnp.exp(small("A_log")) * jax.nn.softplus(ba[:, nh:] + small("dt_bias"))
        alpha = jnp.exp(g)  # (T, H)

        def step(s, xs):
            q_t, k_t, v_t, a_t, b_t, pos_t = xs
            s = jnp.where(pos_t == reset_at, 0.0, s)
            s = a_t[:, None, None] * s
            r = jnp.sum(s * k_t[:, :, None], axis=1)  # S'^T k (H, Dv)
            s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - r)[:, None, :]
            s = jnp.where(flt["state_bf16"], jax.lax.reduce_precision(s, 8, 7), s)
            return s, jnp.sum(s * q_t[:, :, None], axis=1)

        _, o = jax.lax.scan(step, jnp.zeros((nh, dk, dv), jnp.float32),
                            (q, k, v, alpha, beta, pos))
        y = unit_rms(o, eps) * nrm("o_norm") * jax.nn.silu(z).reshape(t, nh, dv)
        out = lin(y.reshape(t, nh * dv), "o_proj")
        normed = unit_rms(out, eps) * nrm("mixer_norm")
        return x + jnp.where(flt["linear_prenorm"], out, normed)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _attn_half(cfg_items, skey, rank, x, flt):
    """``x + rms(attention(x); post_attention_layernorm)``."""
    cfg, lin, nrm = _parts(cfg_items, "attn", skey, rank, flt)
    dm = dims(cfg)
    t = x.shape[0]
    nh, hkv, d, eps = dm["heads"], dm["kv_heads"], dm["head_dim"], cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):

        def qk_norm(y, name, heads):
            whole = unit_rms(y, eps)  # over the whole projection
            per_head = unit_rms(y.reshape(t, heads, d), eps).reshape(t, heads * d)
            return (jnp.where(flt["qk_per_head"], per_head, whole) * nrm(name)).reshape(t, heads, d)

        q = qk_norm(lin(x, "q_proj"), "q_norm", nh)
        k = qk_norm(lin(x, "k_proj"), "k_norm", hkv)
        v = lin(x, "v_proj").reshape(t, hkv, d)
        pos = jnp.arange(t)
        turn = lambda y: jnp.where(  # noqa: E731
            flt["rope_on"], rope_half(y, pos, ROPE_ON_THETA), y)
        q, k = turn(q), turn(k)
        # query head j reads K/V head j // (heads / kv_heads)
        k, v = (jnp.repeat(y, nh // hkv, axis=1) for y in (k, v))

        def block(args):
            qb, q_pos = args  # (Q, H, D), (Q,)
            s = jnp.einsum("qhd,shd->hqs", qb, k) * d ** -0.5
            s = jnp.where(pos[None, :] <= q_pos[:, None], s, -jnp.inf)
            return jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)

        qb = min(Q_BLOCK, t)
        if t % qb:
            raise ValueError(f"{t} positions are no multiple of the query block {qb}")
        out = jax.lax.map(block, (q.reshape(t // qb, qb, nh, d), pos.reshape(t // qb, qb)))
        out = lin(out.reshape(t, nh * d), "o_proj")
        return x + unit_rms(out, eps) * nrm("mixer_norm")


@functools.partial(jax.jit, static_argnames=("cfg_items", "group"))
def _mlp_half(cfg_items, group, skey, rank, h, flt):
    """``h + rms(mlp(h); post_feedforward_layernorm)``."""
    cfg, lin, nrm = _parts(cfg_items, group, skey, rank, flt)
    with jax.default_matmul_precision("highest"):
        out = lin(jax.nn.silu(lin(h, "gate_proj")) * lin(h, "up_proj"), "down_proj")
        return h + unit_rms(out, cfg["rms_norm_eps"]) * nrm("ffn_norm")


@functools.partial(jax.jit, static_argnames=("cfg_items", "top"))
def _head(cfg_items, top, skey, h, ids_wanted, flt):
    cfg, lin, nrm = _parts(cfg_items, "top", skey, 0, flt)
    with jax.default_matmul_precision("highest"):
        logits = lin(unit_rms(h, cfg["rms_norm_eps"]) * nrm("final_norm"), "lm_head")
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(cfg_items, skey, ids):
    cfg = dict(cfg_items)
    return W.logical_rows(skey, model_units(cfg)["top"]["embed"], "bf16", ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: its scalars and ``layer_types``."""
    items = [(k, v) for k, v in cfg.items()
             if isinstance(v, (int, float, str, bool, type(None)))]
    return tuple(sorted(items + [("layer_types", tuple(cfg["layer_types"]))]))


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
    final norm). ``handover``: the position a ``HANDOVER`` reset falls on
    (the MIDDLE linear layer's)."""
    if fmt != "bf16":
        raise ValueError(f"olmo_hybrid is served in bf16 here, not {fmt!r}")
    flt = fault_flags(fault, handover)
    reset_at = flt.pop("reset_at")
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    h = _embed(cfg_items, skey, jnp.asarray(ids, jnp.int32))
    groups = layer_groups(cfg)
    linear = [i for i, g in enumerate(groups) if g == "gdn"]
    middle = linear[len(linear) // 2]
    seen: dict = {}
    for i, group in enumerate(groups):
        rank = seen.get(group, 0)
        seen[group] = rank + 1
        r = jnp.asarray(rank, jnp.int32)
        if group == "attn":
            h = _attn_half(cfg_items, skey, r, h, flt)
        else:
            at = reset_at if i == middle else jnp.asarray(-1)
            h = _gdn_half(cfg_items, skey, r, h, jnp.asarray(at, jnp.int32), flt)
        h = _mlp_half(cfg_items, group, skey, r, h, flt)
    return h


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
    h = hidden_states(cfg, fmt, seed, np.pad(ids, (0, padded - t)), fault,
                      handover=int(np.asarray(rows)[0]) + 1)
    flt = fault_flags(fault)
    flt.pop("reset_at")
    out = _head(hashable(cfg), top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), flt)
    return tuple(np.asarray(x) for x in out)
