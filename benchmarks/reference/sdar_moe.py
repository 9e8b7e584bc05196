"""Plain reference for the ``sdar_moe`` family (SDAR-MoE: Qwen3-MoE's decoder,
generated from by diffusion over blocks): the forward pass in ``jax.numpy`` and
float32, matrix products at ``highest`` precision, no cache, no kernel, no
batching — full attention under an explicit boolean mask. Written from the
model's ``config.json``, Qwen3-MoE's published block and the family's published
``generate.py`` loop, not from ``mlx_sharding_tpu/models/sdar_moe.py`` or
``mlx_sharding_tpu/diffusion.py``.

**The layer.** Pre-norm residual blocks, eps ``rms_norm_eps``, no bias: ``h = h
+ attn(norm(h))``; ``h = h + moe(norm(h))``; logits ``= norm(h) @ lm_head``
(untied); ``norm(x, w) = x * rsqrt(mean x^2 + eps) * w``. Attention: ``W_q u``
to ``num_attention_heads`` heads of ``head_dim``, ``W_k u`` and ``W_v u`` to
``num_key_value_heads``; ``q = norm(q, q_norm)``, ``k = norm(k, k_norm)`` over
``head_dim``, THEN rotary (half-split, all channels, ``rope_theta``, no
scaling) at the token's absolute position; ``softmax(q k^T * head_dim**-0.5) v``
in float32, query head ``j`` on K/V head ``j // (Hq / Hkv)``; ``W_o``. **The
mask: key ``p'`` is visible to query ``p`` iff ``p' // L <= p // L``**, ``L =
block_length``. MoE in every layer: ``p = softmax(u W_r)`` over all experts,
the top ``num_experts_per_tok``, weights ``p_i / sum_top p``
(``norm_topk_prob``), no bias, no shared expert; expert ``down(silu(gate(u)) *
up(u))``, a plain loop over the held ones.

**Generation** (:func:`generate`: the published loop, no cache). The sequence
is blocks of ``L``. The prompt's ``P // L`` whole blocks are context; the ``P
mod L`` tokens left start the first decode block, whose other positions hold
``mask_token_id``. A denoise forward over ``[context | block]`` under the mask
gives the block's logits, row ``i`` predicting position ``i`` ITSELF; ``x0`` is
the argmax with the mask id's logit at ``-inf``, ``c`` its probability; ``n = L
/ denoising_steps`` masked positions take their ``x0``: ``sequential`` the first
``n``; ``low_confidence_static`` the ``n`` of largest ``c``;
``low_confidence_dynamic`` all with ``c > confidence_threshold`` or the top
``n`` if fewer than ``n`` pass. With no position masked the block is committed.

**The check's pass** (:func:`forward`). The runner hands ``ids = prompt +
tokens[:-1]`` and ``rows[j] = P - 1 + j``: generated position ``j`` is sequence
position ``rows[j] + 1``, and is compared AT THE FORWARD THAT TRANSFERRED IT.
Under ``sequential`` that forward's input is a function of the final tokens:
block ``b``, step ``s``: the block's prompt positions, its first ``s * n``
generated positions' final tokens, MASK elsewhere (so the last token is never
an input). ONE pass a prompt: the clean sequence (every whole block in front of
the last compared one) under the block mask, with every state's ``L`` rows
appended at their own rotary positions, a state's rows seeing the clean rows
before their block and each other. That is the published loop exactly: a
committed block's K/V are those of the clean pass. The other two strategies
cannot be replayed from the tokens alone (PERF.md section 7).

Departures, each under ``assumed`` in the configuration's file.
- Which positions are masked is a boolean carried beside the ids, and the mask
  id's logit is ``-inf`` before the argmax (the published loop compares ids
  with ``mask_token_id`` and can sample the mask id itself).
- Attention is computed in blocks of ``Q_BLOCK`` queries (each against every
  key, masked): the same numbers, no (heads, T, T) score matrix.
- The SHARE, the sliced vocabulary and the weights: as
  ``benchmarks/reference/afmoe.py`` says. ``mask_token_id`` of the cut is 0, an
  id the harness never draws (the published 151669 lies outside the slice).

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``block_mask_causal``: plain causal inside a block, clean rows and
states alike: what a port that reused an autoregressive attention would serve.
``commit_stale_kv``: later blocks see, for each generated block, the rows of
its FIRST denoise forward in place of its clean rows: a cache that kept what
the first forward wrote and never ran (or never stored) the commit.
``qk_norm_off`` leaves the per-head norms out. ``moe_no_renorm`` leaves the
chosen probabilities' sum out. ``weights_fp8`` rounds every matrix to 3
mantissa bits (float8 e4m3's precision, bf16's range): the nearest precision
below the one a bf16 configuration states.

This file is the family's whole share of the benchmark
(``benchmarks.config.family``): the reference, the table of its matrices
(:func:`model_units`), the tree the program's loader returns
(:func:`program_params`) and the bytes a decode step — ONE forward over every
slot's block — must move (:func:`decode_step_bytes`,
:func:`paged_attn_step_bytes`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import weights as W
from benchmarks.bytes_model import expected_distinct_experts, unit_bytes
from benchmarks.config import Unit

Q_BLOCK = 128
STRATEGIES = ("sequential", "low_confidence_static", "low_confidence_dynamic")

#: fault name -> what departs from the clean pass (:data:`CLEAN`)
CLEAN = {
    "causal": False, "stale": False, "qk_norm_off": False, "no_renorm": False,
    "mantissa": 7,  # bits kept of every matrix: 7 is bf16's own
}
FAULTS = {
    None: {},
    "block_mask_causal": {"causal": True},
    "commit_stale_kv": {"stale": True},
    "qk_norm_off": {"qk_norm_off": True},
    "moe_no_renorm": {"no_renorm": True},
    "weights_fp8": {"mantissa": 3},
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
        "block": int(cfg.get("block_length", 4)),
    }


def model_units(cfg: dict) -> dict:
    """``{"layers": {the program's leaf name: Unit}, "top": {...}}``: one
    homogeneous stack, a unit's layer key the layer's index."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    dm = dims(cfg)
    mi, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    lin = lambda name, out, inn, **kw: Unit(f"layers.{name}", "linear", out, inn, **kw)  # noqa: E731
    norm = lambda name, n: Unit(f"layers.{name}", "norm", n, 0)  # noqa: E731
    return {
        "layers": {
            "input_norm": norm("input_norm", h), "post_norm": norm("post_norm", h),
            "q_proj": lin("q_proj", dm["q"], h), "k_proj": lin("k_proj", dm["kv"], h),
            "v_proj": lin("v_proj", dm["kv"], h), "o_proj": lin("o_proj", h, dm["q"]),
            "q_norm": norm("q_norm", d), "k_norm": norm("k_norm", d),
            "router": lin("router", dm["router"], h, keep_dense=True),
            "w_gate": lin("w_gate", mi, h, experts=e), "w_up": lin("w_up", mi, h, experts=e),
            "w_down": lin("w_down", h, mi, experts=e),
        },
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "lm_head": Unit("lm_head", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` stacked as
    ``models/sdar_moe.map_weights`` stacks them, the matrices generated when
    the engine's placement slices them (``LazyStack``); ``embed``,
    ``final_norm``, ``lm_head``."""
    if fmt != "bf16":
        raise ValueError(f"sdar_moe is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    top = units["top"]
    return {
        "layers": {
            name: W.layer_stack(skey, unit, fmt, 0, cfg["num_hidden_layers"])
            for name, unit in units["layers"].items()
        },
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
        "lm_head": {"weight": W.top_leaf(skey, top["lm_head"], fmt)},
    }


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of one position's K and V in one layer (bf16)."""
    return 2 * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def paged_attn_step_bytes(cfg: dict, active_slots: float, context: float) -> float:
    """K/V bytes a forward's attention must read: per active slot the ``c``
    committed rows of its context and its block's ``L``, in every layer (the
    block's ``L`` queries read them once: they share one key set)."""
    rows = cfg["num_hidden_layers"] * (context + dims(cfg)["block"])
    return active_slots * rows * kv_row_bytes(cfg)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path — ONE forward over every
    active slot's block of ``L`` rows — must move through HBM, counted once:
    every weight outside the routed experts (attention, norms, the router at
    its full width, the head as the engine holds it) and the DISTINCT held
    experts the ``active_slots * L`` rows' choices hit. ``kv_pages`` — the
    K/V rows attention reads at a context of ``cache_tokens / active_slots``
    — is reported under its own key and is NOT part of ``total``: the reader
    of ``decode_hbm_share`` hands ``cache_tokens`` from the pages CLAIMED at
    admission (the whole of prompt + max_tokens, where a slot holds half of
    that on average), which would count gigabytes a forward never reads. A
    lower bound cannot over-count; the attention's own roofline share is
    ``attn_core_hbm_share``, whose contexts come from the client's log. Not
    counted either: activations, the embedding rows, K/V writes."""
    units = model_units(cfg)
    dm = dims(cfg)
    n = cfg["num_hidden_layers"]
    per_layer = sum(unit_bytes(u, fmt) for u in units["layers"].values() if not u.experts)
    one_expert = sum(unit_bytes(u, fmt) for u in units["layers"].values() if u.experts)
    hit = expected_distinct_experts(
        dm["router"], cfg["num_experts_per_tok"], active_slots * dm["block"]
    ) / dm["share"]
    context = cache_tokens / active_slots if active_slots else 0.0
    out = {
        "fixed_weights": n * per_layer + 2 * cfg["vocab_size"] * cfg["hidden_size"],
        "routed_experts": n * hit * one_expert,
    }
    out["total"] = sum(out.values())
    out["kv_pages"] = paged_attn_step_bytes(cfg, active_slots, context)
    return out


# --------------------------------------------------------------------------
# the rows of one pass: the clean sequence and the states behind it


def plan(ids, n_prompt: int, n_compared: int, cfg: dict, pad_to: int = 0) -> dict:
    """The rows of the check's one pass over ``ids`` (the prompt and the
    generated tokens but the last): numpy arrays a row — ``tok``, ``pos``
    (rotary position), ``blk`` (block index), ``sid`` (the state a row
    belongs to, -1 for a clean row), ``first`` (a row of a block's FIRST
    denoise forward), ``rank`` (a state row's place in its block) — and
    ``out`` ``(n_compared,)``: the row at which each generated position is
    transferred. Shapes depend on ``pad_to`` and ``n_compared`` alone."""
    L = dims(cfg)["block"]
    n_t = L // int(cfg["denoising_steps"])
    mask_id = int(cfg["mask_token_id"])
    ids = np.asarray(ids, np.int32)
    P, n = int(n_prompt), int(n_compared)
    last = P + n - 1  # the last compared position
    clean = (last // L) * L  # whole blocks in front of the last compared one
    if clean > len(ids):
        raise ValueError("ids end before the last compared position's block")
    c_pad = -(-max(clean, int(pad_to)) // L) * L
    tok = list(ids[:clean]) + [0] * (c_pad - clean)
    pos = list(range(c_pad))
    blk = [p // L for p in pos]
    sid, first, rank = [-1] * c_pad, [False] * c_pad, [0] * c_pad
    out = np.zeros((n,), np.int32)
    states = 0
    for b in range(P // L, last // L + 1):
        g0 = max(b * L, P) - b * L  # the block's first generated position
        for s in range(-(-(L - g0) // n_t)):
            lo = g0 + s * n_t  # this forward transfers [lo, lo + n_t)
            if b * L + lo > last:
                break
            for i in range(L):
                p = b * L + i
                if lo <= i < lo + n_t and p <= last:
                    out[p - P] = len(tok)
                tok.append(int(ids[p]) if i < lo else mask_id)
                pos.append(p)
                blk.append(b)
                sid.append(states)
                first.append(s == 0)
                rank.append(i)
            states += 1
    # one shape a (pad_to, n_compared): the most states n positions can take
    total = c_pad + (n // L + 2) * int(cfg["denoising_steps"]) * L
    if total > Q_BLOCK:  # whole query blocks (a shorter pass is one block)
        total = -(-total // Q_BLOCK) * Q_BLOCK
    for _ in range(total - len(tok)):  # dummy rows: a state of their own each
        tok.append(0); pos.append(0); blk.append(0)  # noqa: E702
        sid.append(states); first.append(False); rank.append(0)  # noqa: E702
        states += 1
    return {
        "tok": np.asarray(tok, np.int32), "pos": np.asarray(pos, np.int32),
        "blk": np.asarray(blk, np.int32), "sid": np.asarray(sid, np.int32),
        "first": np.asarray(first, bool), "rank": np.asarray(rank, np.int32),
        "out": out, "gen_from": P // L,
    }


def plain_rows(ids, cfg: dict, pad_to: int = 0) -> dict:
    """:func:`plan`'s arrays for a plain sequence: clean rows only."""
    L = dims(cfg)["block"]
    t = max(len(ids), int(pad_to))
    t = -(-t // L) * L
    pos = np.arange(t, dtype=np.int32)
    return {
        "tok": np.pad(np.asarray(ids, np.int32), (0, t - len(ids))), "pos": pos,
        "blk": pos // L, "sid": np.full((t,), -1, np.int32),
        "first": np.zeros((t,), bool), "rank": np.zeros((t,), np.int32),
        "gen_from": t,
    }


# --------------------------------------------------------------------------
# the plain reference


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, pos, theta: float):
    """Rotary embedding of ``x (T, heads, D)`` at positions ``pos (T,)``: pair
    ``(i, i + D/2)`` turns by ``pos * theta**(-2i / D)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]  # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _lin(units, skey, rank, coarse):
    """``lin(x, name, expert=None) -> x @ M[name]`` for one layer's units;
    ``coarse`` (a run-time boolean): matrices rounded to 3 mantissa bits."""
    def lin(x, name, expert=None):
        m = W.dense_logical(skey, units[name], rank, expert)
        return x @ jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m)
    return lin


def visible(rows_q, rows_k, L: int, gen_from, causal, stale):
    """``(Q, K)`` boolean: which key rows each query row sees. Rows are
    :func:`plan`'s arrays; ``gen_from`` the first block that holds a
    generated position; ``causal`` and ``stale`` the two faults."""
    q = {k: v[:, None] for k, v in rows_q.items()}
    k = {k_: v[None, :] for k_, v in rows_k.items()}
    q_clean, k_clean = q["sid"] < 0, k["sid"] < 0
    same = (q["sid"] == k["sid"]) & ~q_clean
    # a clean query sees the clean rows up to its block's end; a state's row
    # the clean rows before its block, and its own state
    before = jnp.where(q_clean, k["blk"] <= q["blk"], k["blk"] < q["blk"])
    seen = (k_clean & before) | same
    # plain causal inside a block, clean rows and states alike
    seen_causal = (k_clean & (k["pos"] <= q["pos"]) & (q_clean | before)) | (
        same & (k["rank"] <= q["rank"])
    )
    seen = jnp.where(causal, seen_causal, seen)
    # a generated block's rows as later blocks see them: its first denoise
    # forward's, not its clean ones (states only: nothing compared is clean)
    generated = k["blk"] >= gen_from
    stale_seen = (k_clean & before & ~generated) | (
        ~k_clean & k["first"] & generated & (k["blk"] < q["blk"])
    ) | same
    return jnp.where(stale & ~q_clean, stale_seen, seen)


def _attention(cfg, lin, nrm, u, rows, gen_from, flags):
    t = u.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = lin(u, "q_proj").reshape(t, hq, hd)
    k = lin(u, "k_proj").reshape(t, hkv, hd)
    v = lin(u, "v_proj").reshape(t, hkv, hd)
    q = jnp.where(flags["qk_norm_off"], q, rmsnorm(q, nrm("q_norm"), eps))
    k = jnp.where(flags["qk_norm_off"], k, rmsnorm(k, nrm("k_norm"), eps))
    q, k = rotate(q, rows["pos"], theta), rotate(k, rows["pos"], theta)
    L = dims(cfg)["block"]

    def block(args):
        qb, rows_q = args
        s = jnp.einsum("qkgd,skd->kgqs", qb.reshape(-1, hkv, hq // hkv, hd), k) * hd ** -0.5
        seen = visible(rows_q, rows, L, gen_from, flags["causal"], flags["stale"])
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(-1, hq * hd)

    qb = min(Q_BLOCK, t)
    if t % qb:
        raise ValueError(f"{t} rows are no multiple of the query block {qb}")
    cut = lambda x: x.reshape(t // qb, qb, *x.shape[1:])  # noqa: E731
    out = jax.lax.map(block, (cut(q), {name: cut(x) for name, x in rows.items()}))
    return lin(out.reshape(t, hq * hd), "o_proj")


def _moe(cfg, lin, u, no_renorm):
    dm = dims(cfg)
    p = jax.nn.softmax(lin(u, "router"), axis=-1)
    top_v, top_i = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    top_v = jnp.where(no_renorm, top_v, top_v / top_v.sum(axis=-1, keepdims=True))

    def one_expert(acc, e):  # e: the expert's place among those held
        coef = jnp.sum(jnp.where(top_i == e + dm["base"], top_v, 0.0), axis=-1)
        y = lin(jax.nn.silu(lin(u, "w_gate", e)) * lin(u, "w_up", e), "w_down", e)
        return acc + coef[:, None] * y, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), jnp.arange(cfg["num_experts"]))
    return routed


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _layer(cfg_items, skey, rank, h, rows, gen_from, flags):
    cfg = dict(cfg_items)
    units = model_units(cfg)["layers"]
    lin = _lin(units, skey, rank, flags["coarse"])
    nrm = lambda name: W.logical_norm(skey, units[name], rank)  # noqa: E731
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        h = h + _attention(cfg, lin, nrm, rmsnorm(h, nrm("input_norm"), eps), rows,
                           gen_from, flags)
        return h + _moe(cfg, lin, rmsnorm(h, nrm("post_norm"), eps), flags["no_renorm"])


@functools.partial(jax.jit, static_argnames=("cfg_items", "top"))
def _head(cfg_items, top, skey, h, ids_wanted, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)["top"]
    with jax.default_matmul_precision("highest"):
        r = rmsnorm(h, W.logical_norm(skey, units["final_norm"], 0), cfg["rms_norm_eps"])
        logits = _lin(units, skey, 0, coarse)(r, "lm_head")
    # a position is never given the mask id
    lp = jax.nn.log_softmax(logits.at[:, cfg["mask_token_id"]].set(-jnp.inf), axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(cfg_items, skey, ids):
    return W.logical_rows(skey, model_units(dict(cfg_items))["top"]["embed"], "bf16", ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: its scalars."""
    return tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool, type(None)))
    ))


def hidden_states(cfg: dict, fmt: str, seed: int, rows: dict, fault=None):
    """The final hidden states (before the final norm) of :func:`plan`'s (or
    :func:`plain_rows`') rows."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fmt != "bf16":
        raise ValueError(f"sdar_moe is served in bf16 here, not {fmt!r}")
    f = {**CLEAN, **FAULTS[fault]}
    flags = {
        "causal": jnp.asarray(f["causal"]), "stale": jnp.asarray(f["stale"]),
        "qk_norm_off": jnp.asarray(f["qk_norm_off"]),
        "no_renorm": jnp.asarray(f["no_renorm"]), "coarse": jnp.asarray(f["mantissa"] < 7),
    }
    cfg_items, skey = hashable(cfg), W.seed_key(seed)
    arrays = {k: jnp.asarray(rows[k]) for k in ("pos", "blk", "sid", "first", "rank")}
    h = _embed(cfg_items, skey, jnp.asarray(rows["tok"]))
    gen_from = jnp.asarray(rows["gen_from"], jnp.int32)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(cfg_items, skey, jnp.asarray(i, jnp.int32), h, arrays, gen_from, flags)
    return h, flags["coarse"]


def forward(cfg: dict, fmt: str, seed: int, ids, rows, ids_wanted, *,
            top: int = 20, fault=None, pad_to: int = 0):
    """The check's pass (module docstring). ``ids``: the prompt and the
    served tokens but the last; ``rows``: ``P - 1 + j`` for each generated
    position ``j`` (consecutive); ``ids_wanted (len(rows), n)``: token ids
    whose log-probability is wanted at each. ``pad_to``: the longest of the
    check's sequences, so that they share one compiled program. Returns
    ``(top_ids, top_logprobs, logprobs_at_wanted)`` as numpy, row ``j`` the
    distribution of generated position ``j`` at the forward that transferred
    it, log-softmax with the mask id at ``-inf``."""
    rows = [int(r) for r in rows]
    if rows != list(range(rows[0], rows[0] + len(rows))):
        raise ValueError("the compared rows are consecutive positions")
    if cfg.get("remasking_strategy", "sequential") != "sequential":
        raise ValueError(
            "only a sequential trajectory is a function of the final tokens: "
            f"the check cannot replay {cfg['remasking_strategy']!r}"
        )
    p = plan(ids, rows[0] + 1, len(rows), cfg, pad_to)
    h, coarse = hidden_states(cfg, fmt, seed, p, fault)
    out = _head(hashable(cfg), top, W.seed_key(seed), h[p["out"]],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), coarse)
    return tuple(np.asarray(x) for x in out)


def block_logprobs(cfg: dict, fmt: str, seed: int, context, block, fault=None,
                   pad_to: int = 0):
    """Log-probabilities ``(L, V)`` of one forward of the published loop:
    ``block``'s ``L`` ids behind the committed ``context`` (whole blocks), no
    cache — the whole sequence under the block mask; the mask id at ``-inf``."""
    ids = list(context) + list(block)
    rows = plain_rows(ids, cfg, pad_to)
    h, coarse = hidden_states(cfg, fmt, seed, rows, fault)
    at = np.arange(len(context), len(ids))
    lp = _head(hashable(cfg), cfg["vocab_size"], W.seed_key(seed), h[at],
               jnp.zeros((len(at), 1), jnp.int32), coarse)
    order, vals = np.asarray(lp[0]), np.asarray(lp[1])
    out = np.full(vals.shape, -np.inf, np.float32)
    np.put_along_axis(out, order, vals, axis=-1)
    return out


def transfer(masked, conf, strategy: str, n: int, tau: float):
    """Which of a block's masked positions take their token this forward
    (numpy, one block): the published loop's three strategies, restricted to
    masked positions; ties go to the lower position."""
    masked, conf = np.asarray(masked, bool), np.asarray(conf, np.float64)
    where = np.flatnonzero(masked)
    out = np.zeros_like(masked)
    if strategy == "sequential":
        out[where[:n]] = True
        return out
    ranked = where[np.argsort(-conf[where], kind="stable")]
    if strategy == "low_confidence_dynamic":
        high = where[conf[where] > tau]
        if len(high) >= n:
            out[high] = True
            return out
    out[ranked[:n]] = True
    return out


def generate(cfg: dict, fmt: str, seed: int, prompt, max_tokens: int, *,
             strategy=None, pad_to: int = 0):
    """The published loop, greedy, no cache: ``(tokens, forwards)``, the
    second a list of ``(block index, ids in, masked in, log-probabilities (L,
    V), transferred (L,) bool)`` for each denoise forward (a commit forward
    computes nothing the output depends on). Whole blocks; tokens past
    ``max_tokens`` are dropped."""
    strategy = strategy or cfg.get("remasking_strategy", "sequential")
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}")
    L = dims(cfg)["block"]
    n_t = L // int(cfg["denoising_steps"])
    tau, mask_id = float(cfg["confidence_threshold"]), int(cfg["mask_token_id"])
    prompt = [int(t) for t in prompt]
    done = prompt[: len(prompt) // L * L]
    tail = prompt[len(done):]
    tokens, forwards = [], []
    while len(tokens) < max_tokens:
        ids = tail + [mask_id] * (L - len(tail))
        masked = [False] * len(tail) + [True] * (L - len(tail))
        fresh = len(tail)
        tail = []
        while any(masked):
            lp = block_logprobs(cfg, fmt, seed, done, ids, pad_to=pad_to)
            x0 = lp.argmax(axis=-1)
            conf = np.exp(lp[np.arange(L), x0])
            move = transfer(masked, conf, strategy, n_t, tau)
            forwards.append((len(done) // L, list(ids), list(masked), lp, move))
            ids = [int(x0[i]) if move[i] else ids[i] for i in range(L)]
            masked = [m and not move[i] for i, m in enumerate(masked)]
        done += ids
        tokens += ids[fresh:]
    return tokens[:max_tokens], forwards
