"""Plain reference for the ``afmoe`` family (Arcee Trinity): the forward pass
in ``jax.numpy`` and float32, matrix products at ``highest`` precision, no
cache, no kernel, no batching. Written from the family's ``config.json``
keys and its published modeling code as known here, not from
``mlx_sharding_tpu/models/afmoe.py``.

``h0 = embed(ids) * sqrt(hidden_size)`` (``mup_enabled``). Every layer, with
four RMSNorms (eps ``rms_norm_eps``): ``h = h + post_attn_norm(attn(
input_norm(h)))``; ``h = h + post_mlp_norm(mlp(pre_mlp_norm(h)))``. A final
RMSNorm, an untied head, no bias.

- **attention**: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``head_dim``; ``q, k, v = x Wq, x Wk,
  x Wv``; the output gate ``g = x Wg`` (hidden -> heads x head_dim); ``q`` and
  ``k`` RMS-normed over the head dim, each with a learned ``head_dim``-vector;
  on ``sliding_attention`` layers ONLY, rotary (theta ``rope_theta``, the
  whole head dim, split-half pairs ``(i, i + head_dim / 2)``) on ``q, k``, and
  a causal mask that also hides keys more than ``sliding_window - 1``
  positions back; ``full_attention`` layers apply NO rotary and see every
  earlier key; scale ``head_dim**-0.5``; ``out = (softmax(q k^T) v *
  sigmoid(g)) Wo``.
- **dense MLP** (layers ``0 .. num_dense_layers - 1``): SwiGLU of width
  ``intermediate_size``.
- **MoE** (the rest): ``s = sigmoid(x Wr)`` over all experts; the top
  ``num_experts_per_tok`` of ``s + expert_bias`` are chosen (``n_group =
  topk_group = 1``: no group limit); their weights are their own ``s`` over
  their sum (``route_norm``) times ``route_scale``; routed SwiGLU experts of
  width ``moe_intermediate_size`` plus one shared SwiGLU expert of the same
  width on every token.

Departures.
- Attention is computed in blocks of ``Q_BLOCK`` queries (each against every
  key, masked) and the dense MLP in blocks of rows, and a layer's attention
  half and MLP half are two compiled programs (the matrices one half
  generates are not live in the other), so that 8k positions fit beside a
  served model: the same numbers, no (T, T) score matrix per head, no
  (T, 12288) activation.
- "Depth-scaled" sandwich norm is an initialisation, not a forward term;
  ``load_balance_coeff`` is a training term. Neither is computed.
- The SHARE. A configuration may hold one chip's share of each layer's routed
  experts: ``num_experts`` counts the experts held, ``moe_expert_share`` the
  holders, ``moe_expert_share_index`` which one this is. The router keeps its
  full width (``num_experts * moe_expert_share``) and its experts per token;
  the held experts' part is computed and what the absent experts would add
  is LEFT OUT, here as in the program, and that partial result goes on.
- A sliced vocabulary is a smaller vocabulary: embedding, head and softmax
  are over the ``vocab_size`` rows held.
- Weights are not held: each matrix is regenerated from ``(seed, name, rank
  of the layer in its group, expert)`` by ``benchmarks.weights`` when it is
  needed; the selection bias is generated here (:func:`small_vector`).

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``window_off``: the window layers see every earlier key.
``rope_on_full``: the full layers rotate too. ``gate_off``: the output gate
is left out. ``shift_cache_one`` hands the middle layer keys and values one
position late. ``weights_fp8`` rounds every matrix to 3 mantissa bits (float8
e4m3's precision, bf16's range): the nearest precision below the one a bf16
configuration states.

This file is the family's whole share of the benchmark
(``benchmarks.config.family``): the reference, the table of its matrices
(:func:`model_units`), the tree the program's loader returns
(:func:`program_params`) and the bytes a decode step must move
(:func:`decode_step_bytes`, :func:`paged_attn_step_bytes`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import expected_distinct_experts, unit_bytes
from benchmarks.config import Unit, is_packed

WINDOW, FULL = "sliding_attention", "full_attention"
Q_BLOCK = 128
ROW_BLOCK = 1024  # rows a block of the dense MLP

#: fault name -> (window off, rotary on full layers, gate off, middle layer's
#: cache shifted, mantissa bits kept of every matrix: 7 is bf16's own)
FAULTS = {
    None: (False, False, False, False, 7),
    "window_off": (True, False, False, False, 7),
    "rope_on_full": (False, True, False, False, 7),
    "gate_off": (False, False, True, False, 7),
    "shift_cache_one": (False, False, False, True, 7),
    "weights_fp8": (False, False, False, False, 3),
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    share = int(cfg.get("moe_expert_share", 1))
    return {
        "share": share,
        "base": int(cfg.get("moe_expert_share_index", 0)) * cfg["num_experts"],
        "router": cfg["num_experts"] * share,
        "q": cfg["num_attention_heads"] * cfg["head_dim"],
        "kv": cfg["num_key_value_heads"] * cfg["head_dim"],
    }


def layer_types(cfg: dict) -> list:
    if cfg.get("layer_types"):
        return list(cfg["layer_types"])
    n = cfg.get("global_attn_every_n_layers", 4)
    return [FULL if (i + 1) % n == 0 else WINDOW for i in range(cfg["num_hidden_layers"])]


def group_layers(cfg: dict) -> dict:
    """{group: [global layer indices]}: the leading dense layers, then MoE."""
    nd, n = cfg.get("num_dense_layers", 0), cfg["num_hidden_layers"]
    out = {"dense": list(range(nd)), "moe": list(range(nd, n))}
    return {g: idxs for g, idxs in out.items() if idxs}


def model_units(cfg: dict) -> dict:
    """{group: {the program's leaf name: Unit}} plus the group "top". A
    unit's own name carries its group; its layer key is the layer's rank in
    its group."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    dm = dims(cfg)
    mi, e = cfg["moe_intermediate_size"], cfg["num_experts"]

    def attn(group):
        lin = lambda name, out, inn, **kw: Unit(f"{group}.{name}", "linear", out, inn, **kw)  # noqa: E731
        norm = lambda name, n: Unit(f"{group}.{name}", "norm", n, 0)  # noqa: E731
        return lin, {
            "input_norm": norm("input_norm", h), "post_attn_norm": norm("post_attn_norm", h),
            "pre_mlp_norm": norm("pre_mlp_norm", h), "post_mlp_norm": norm("post_mlp_norm", h),
            "q_proj": lin("q_proj", dm["q"], h), "k_proj": lin("k_proj", dm["kv"], h),
            "v_proj": lin("v_proj", dm["kv"], h), "attn_gate": lin("attn_gate", dm["q"], h),
            "o_proj": lin("o_proj", h, dm["q"]),
            "q_norm": norm("q_norm", d), "k_norm": norm("k_norm", d),
        }

    lin_d, dense = attn("dense")
    dense.update(
        gate_proj=lin_d("gate_proj", cfg["intermediate_size"], h),
        up_proj=lin_d("up_proj", cfg["intermediate_size"], h),
        down_proj=lin_d("down_proj", h, cfg["intermediate_size"]),
    )
    lin_m, moe = attn("moe")
    moe.update(
        router=lin_m("router", dm["router"], h, keep_dense=True),
        shared_gate=lin_m("shared_gate", mi, h), shared_up=lin_m("shared_up", mi, h),
        shared_down=lin_m("shared_down", h, mi),
        w_gate=lin_m("w_gate", mi, h, experts=e), w_up=lin_m("w_up", mi, h, experts=e),
        w_down=lin_m("w_down", h, mi, experts=e),
    )
    return {
        "dense": dense, "moe": moe,
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "lm_head": Unit("lm_head", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def small_vector(skey, rank, width: int):
    """A MoE layer's selection bias (``expert_bias``), float32: 0.05 normal,
    a tenth of the scores' spread, large enough to change choices."""
    return 0.05 * jax.random.normal(
        W.unit_key(skey, "moe.router_bias", rank), (width,), jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("n", "width"))
def _bias_stack(skey, n, width):
    return jax.vmap(lambda r: small_vector(skey, r, width))(jnp.arange(n))


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/afmoe.map_weights`` stacks them (a layer's row is
    its rank in its group), the matrices generated when the engine's
    placement slices them (``LazyStack``), the selection bias resident;
    ``embed``, ``final_norm``, ``lm_head``."""
    if fmt != "bf16":
        raise ValueError(f"afmoe is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    layers = {}
    for group, idxs in group_layers(cfg).items():
        layers[group] = {
            name: W.layer_stack(skey, unit, fmt, 0, len(idxs))
            for name, unit in units[group].items()
        }
    if "moe" in layers:
        layers["moe"]["router_bias"] = _bias_stack(
            skey, len(group_layers(cfg)["moe"]), dims(cfg)["router"]
        )
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
        "lm_head": {"weight": W.top_leaf(skey, top["lm_head"], fmt)},
    }


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of one position's K and V in one layer (bf16)."""
    return 2 * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def paged_attn_step_bytes(cfg: dict, active_slots: float, context: float) -> float:
    """K/V bytes a decode step's attention must read: per active slot,
    ``min(context, sliding_window)`` rows in each window layer and
    ``context`` rows in each full layer."""
    kinds = layer_types(cfg)
    rows = kinds.count(FULL) * context + kinds.count(WINDOW) * min(
        context, cfg["sliding_window"]
    )
    return active_slots * rows * kv_row_bytes(cfg)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every weight outside the routed experts
    (attention with its gate, norms, the dense layers' MLP, router at its
    full width, shared expert, selection bias, the head as the engine holds
    it), the DISTINCT held experts the active rows' choices hit, and the
    K/V rows attention must read (:func:`paged_attn_step_bytes`;
    ``cache_tokens`` are the tokens in the full-length pool, so a slot's
    context is their mean). Not counted: activations, the embedding rows,
    K/V writes. A lower bound: the program's expert scan reads every held
    expert, hit or not."""
    units = model_units(cfg)
    groups = group_layers(cfg)
    dm = dims(cfg)
    fixed = 0
    for g, idxs in groups.items():
        per_layer = sum(unit_bytes(u, fmt) for u in units[g].values() if not u.experts)
        fixed += len(idxs) * (per_layer + (4 * dm["router"] if g == "moe" else 0))
    one_expert = sum(unit_bytes(u, fmt) for u in units["moe"].values() if u.experts)
    hit = expected_distinct_experts(
        dm["router"], cfg["num_experts_per_tok"], active_slots
    ) / dm["share"]
    context = cache_tokens / active_slots if active_slots else 0.0
    out = {
        "fixed_weights": fixed + 2 * cfg["vocab_size"] * cfg["hidden_size"],
        "routed_experts": len(groups.get("moe", [])) * hit * one_expert,
        "kv_pages": paged_attn_step_bytes(cfg, active_slots, context),
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, theta: float):
    """Rotary embedding of ``x (T, heads, D)`` at positions ``0 .. T-1``:
    pair ``(i, i + D/2)`` turns by ``pos * theta**(-2i / D)``."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _lin(units, fmt, skey, rank, coarse):
    """``lin(x, name, expert=None) -> x @ M[name]`` for one layer's units.
    ``coarse`` (a run-time boolean): matrices rounded to 3 mantissa bits
    first (``reduce_precision``: the TPU compiler may drop a round trip
    through a narrower type)."""
    def lin(x, name, expert=None):
        unit = units[name]
        if is_packed(unit, fmt):
            return W.apply_linear(x, skey, unit, fmt, rank, expert)
        m = W.dense_logical(skey, unit, rank, expert)
        return x @ jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m)
    return lin


def _attention(cfg, lin, nrm, u, is_win, window_off, rope_full, gate_off, shift):
    """``attn(u)`` for one layer, ``u (T, hidden)`` normed; the four faults
    are run-time booleans, ``is_win`` is static."""
    t = u.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rmsnorm(lin(u, "q_proj").reshape(t, hq, hd), nrm("q_norm"), eps)
    k = rmsnorm(lin(u, "k_proj").reshape(t, hkv, hd), nrm("k_norm"), eps)
    v = lin(u, "v_proj").reshape(t, hkv, hd)
    gate = lin(u, "attn_gate")
    theta = float(cfg["rope_theta"])
    if is_win:
        q, k = rotate(q, theta), rotate(k, theta)
    else:
        q = jnp.where(rope_full, rotate(q, theta), q)
        k = jnp.where(rope_full, rotate(k, theta), k)
    # the negative control: this layer sees the row of the position before
    k = jnp.where(shift, jnp.roll(k, 1, axis=0), k)
    v = jnp.where(shift, jnp.roll(v, 1, axis=0), v)
    k_pos = jnp.arange(t)
    window = cfg["sliding_window"]

    def block(args):
        qb, q_pos = args  # (Q, Hq, D), (Q,)
        s = jnp.einsum("qkgd,skd->kgqs", qb.reshape(-1, hkv, hq // hkv, hd), k) * hd ** -0.5
        seen = k_pos[None, :] <= q_pos[:, None]
        if is_win:
            seen &= window_off | (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(-1, hq * hd)

    qb = min(Q_BLOCK, t)
    if t % qb:
        raise ValueError(f"{t} positions are no multiple of the query block {qb}")
    out = jax.lax.map(block, (q.reshape(t // qb, qb, hq, hd), k_pos.reshape(t // qb, qb)))
    out = out.reshape(t, hq * hd)
    out = out * jnp.where(gate_off, 1.0, jax.nn.sigmoid(gate))
    return lin(out, "o_proj")


def _moe(cfg, lin, bias, u):
    """``(mlp(u), the choices)`` of one MoE layer."""
    dm = dims(cfg)
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("only n_group = topk_group = 1 is written here")
    scores = jax.nn.sigmoid(lin(u, "router"))
    _, top_i = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top_v = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.get("route_norm", True):
        top_v = top_v / (top_v.sum(axis=-1, keepdims=True) + 1e-20)
    top_v = top_v * float(cfg.get("route_scale", 1.0))

    def one_expert(acc, e):  # e: the expert's place among those held
        coef = jnp.sum(jnp.where(top_i == e + dm["base"], top_v, 0.0), axis=-1)
        y = lin(jax.nn.silu(lin(u, "w_gate", e)) * lin(u, "w_up", e), "w_down", e)
        return acc + coef[:, None] * y, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(cfg["num_experts"]))
    shared = lin(jax.nn.silu(lin(u, "shared_gate")) * lin(u, "shared_up"), "shared_down")
    return routed + shared, top_i


def _halves(cfg_items, fmt, group, skey, rank, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)[group]
    lin = _lin(units, fmt, skey, rank, coarse)
    return cfg, lin, lambda name: W.logical_norm(skey, units[name], rank)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt", "group", "is_win"))
def _attn_half(cfg_items, fmt, group, is_win, skey, rank, h, window_off, rope_full,
               gate_off, shift, coarse):
    """``h + post_attn_norm(attn(input_norm(h)))``."""
    cfg, lin, nrm = _halves(cfg_items, fmt, group, skey, rank, coarse)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        a = _attention(cfg, lin, nrm, rmsnorm(h, nrm("input_norm"), eps), is_win,
                       window_off, rope_full, gate_off, shift)
        return h + rmsnorm(a, nrm("post_attn_norm"), eps)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt", "group"))
def _mlp_half(cfg_items, fmt, group, skey, rank, h, coarse):
    """``(h + post_mlp_norm(mlp(pre_mlp_norm(h))), the MoE's choices)``."""
    cfg, lin, nrm = _halves(cfg_items, fmt, group, skey, rank, coarse)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h, nrm("pre_mlp_norm"), eps)
        if group == "dense":
            t = u.shape[0]
            rows = ROW_BLOCK if t % ROW_BLOCK == 0 else t
            m = jax.lax.map(
                lambda x: lin(jax.nn.silu(lin(x, "gate_proj")) * lin(x, "up_proj"), "down_proj"),
                u.reshape(t // rows, rows, -1),
            ).reshape(t, -1)
            top_i = jnp.zeros((0,), jnp.int32)
        else:
            m, top_i = _moe(cfg, lin, small_vector(skey, rank, dims(cfg)["router"]), u)
        return h + rmsnorm(m, nrm("post_mlp_norm"), eps), top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt", "top"))
def _head(cfg_items, fmt, top, skey, h, ids_wanted, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)["top"]
    with jax.default_matmul_precision("highest"):
        r = rmsnorm(h, W.logical_norm(skey, units["final_norm"], 0), cfg["rms_norm_eps"])
        logits = _lin(units, fmt, skey, 0, coarse)(r, "lm_head")
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items", "fmt"))
def _embed(cfg_items, fmt, skey, ids):
    cfg = dict(cfg_items)
    rows = W.logical_rows(skey, model_units(cfg)["top"]["embed"], fmt, ids)
    return rows * cfg["hidden_size"] ** 0.5 if cfg.get("mup_enabled", True) else rows


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: scalars, and ``layer_types`` as
    a tuple."""
    out = [(k, v) for k, v in cfg.items()
           if isinstance(v, (int, float, str, bool, type(None)))]
    out.append(("layer_types", tuple(layer_types(cfg))))
    return tuple(sorted(out))


def hidden_states(cfg: dict, fmt: str, seed: int, ids, fault=None):
    """The final hidden states ``(T, hidden)`` of one sequence (before the
    final norm) and each MoE layer's choices."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    window_off, rope_full, gate_off, shift, mantissa = FAULTS[fault]
    coarse = jnp.asarray(mantissa < 7)
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    h = _embed(cfg_items, fmt, skey, jnp.asarray(ids, jnp.int32))
    kinds = layer_types(cfg)
    nd = cfg.get("num_dense_layers", 0)
    picks = []
    for i, kind in enumerate(kinds):
        group, rank = ("dense", i) if i < nd else ("moe", i - nd)
        r = jnp.asarray(rank, jnp.int32)
        h = _attn_half(
            cfg_items, fmt, group, kind == WINDOW, skey, r, h,
            jnp.asarray(window_off), jnp.asarray(rope_full), jnp.asarray(gate_off),
            jnp.asarray(shift and i == len(kinds) // 2), coarse,
        )
        h, top_i = _mlp_half(cfg_items, fmt, group, skey, r, h, coarse)
        if group == "moe":
            picks.append(top_i)
    return h, picks


def forward(cfg: dict, fmt: str, seed: int, ids, rows, ids_wanted, *,
            top: int = 20, fault=None, pad_to: int = 0):
    """Teacher-forced forward pass over the token ids ``ids`` (one sequence,
    positions 0..T-1, padded at the end to the longer of its own length and
    ``pad_to``, rounded up to a multiple of 128, so that the check's prompts
    share one compiled program; every layer is causal, so padding stays out
    of every row that is read).

    ``rows``: positions whose next-token distribution is wanted.
    ``ids_wanted (len(rows), n)``: token ids whose log-probability is wanted
    there. Returns ``(top_ids, top_logprobs, logprobs_at_wanted)`` as numpy.
    """
    import numpy as np

    ids = np.asarray(ids, np.int32)
    t = len(ids)
    padded = -(-max(t, int(pad_to)) // Q_BLOCK) * Q_BLOCK
    h, _ = hidden_states(cfg, fmt, seed, np.pad(ids, (0, padded - t)), fault)
    coarse = jnp.asarray(FAULTS[fault][4] < 7)
    out = _head(hashable(cfg), fmt, top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), coarse)
    return tuple(np.asarray(x) for x in out)
