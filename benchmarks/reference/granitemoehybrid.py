"""Plain reference for the ``granitemoehybrid`` family (IBM Granite 4.0-H):
the forward pass in ``jax.numpy`` and float32, matrix products at ``highest``
precision, no cache, no kernel, no batching, no chunked form. Written from
the family's published equations (Mamba-2, arXiv 2405.21060; the model's
``config.json``), not from ``mlx_sharding_tpu/models/granitemoehybrid.py``.

With ``e`` = ``embedding_multiplier``, ``r`` = ``residual_multiplier``, ``a``
= ``attention_multiplier``, ``l`` = ``logits_scaling``, eps ``rms_norm_eps``,
no bias but the convolution's:

- ``h_0 = e * embed[token]``; for each layer ``h = h + r * mixer(rmsnorm(h,
  input_layernorm))``, then ``h = h + r * mlp(rmsnorm(h,
  post_attention_layernorm))``; logits ``= (rmsnorm(h, norm) @ embed^T) / l``
  (``tie_word_embeddings``).
- ``mlp(u)`` (``shared_mlp``): ``[g, v] = split(input_linear(u),
  shared_intermediate_size)``; ``output_linear(silu(g) * v)``.
  ``num_local_experts`` is 0: there is no ``block_sparse_moe``, and a
  configuration that has one is refused.
- ``mixer`` at ``layer_types[i] == "mamba"`` (``d = mamba_n_heads *
  mamba_d_head``, ``G = mamba_n_groups``, ``N = mamba_d_state``, ``H`` heads
  of ``P``): ``[z (d), xBC (d + 2GN), dt (H)] = in_proj(u)``; ``xBC =
  silu(conv(xBC) + conv_bias)``, a causal depthwise convolution of
  ``mamba_d_conv`` taps (``out_t = sum_j w[:, j] * in_{t - (K-1) + j}``, zeros
  before position 0); split into ``x (H, P)``, ``B (G, N)``, ``C (G, N)``,
  head ``h`` reading group ``h // (H / G)``; ``dt = softplus(dt + dt_bias)``;
  ``A = -exp(A_log)`` per head; the recurrence, ONE POSITION AT A TIME in a
  ``lax.scan``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t
  C_t + D x_t``; then ``y = groupwise_rmsnorm(y * silu(z), G groups) *
  norm_weight`` and ``out_proj``. ``mamba_chunk_size`` is how a kernel cuts
  the sequence and takes no part.
- ``mixer`` at ``"attention"``: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads of ``hidden_size / num_attention_heads``,
  causal, ``softmax(a * q k^T)``, NO rotary embedding
  (``position_embedding_type`` ``nope``; ``rope_theta`` is inert).

Weights are not held: each matrix is regenerated from ``(seed, name, rank of
the layer in its group)`` by ``benchmarks.weights`` when it is needed. The
small vectors Mamba-2 needs (``A_log``, ``D``, ``dt_bias``, the convolution's
weight and bias) are ``benchmarks/reference/nemotron_h.py``'s
``small_vector``, drawn as that family's file states them.

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``ssm_state_reset`` zeroes the middle Mamba layer's state and
convolution inputs where the compared rows begin (the position after
``rows[0]``: the hand-over from the last prefill chunk to the first decode
step). ``ssm_state_bf16`` rounds every Mamba layer's state to bfloat16 after
each position. ``attn_scale_default`` scales the attention scores by
``head_dim**-0.5`` in place of ``attention_multiplier``: what a port that
missed the multiplier would serve. ``weights_fp8`` rounds every matrix to 3
mantissa bits (float8 e4m3's precision, bf16's range): the nearest precision
below the one a bf16 configuration states.

This file is the family's whole share of the benchmark
(``benchmarks.config.family``): the reference, the table of its matrices
(:func:`model_units`), the tree the program's loader returns
(:func:`program_params`) and the bytes a decode step must move
(:func:`decode_step_bytes`, :func:`ssm_state_step_bytes`,
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
from benchmarks.reference.nemotron_h import HANDOVER, rmsnorm, small_vector

GROUP_OF = {"mamba": "mamba", "attention": "attn"}
HEAD_BLOCK = 12544  # vocabulary rows a block of the head: 100352 = 8 x 12544

#: fault name -> (reset position of the middle Mamba layer or -1, state
#: rounded to bf16, attention at head_dim**-0.5, mantissa bits kept of every
#: matrix: 7 is bf16's own, so nothing changes)
FAULTS = {
    None: (-1, False, False, 7),
    "ssm_state_reset": (HANDOVER, False, False, 7),
    "ssm_state_bf16": (-1, True, False, 7),
    "attn_scale_default": (-1, False, True, 7),
    "weights_fp8": (-1, False, False, 3),
}


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    if cfg.get("num_local_experts", 0):
        raise ValueError("granitemoehybrid with routed experts is not written here")
    d = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return {
        "d_inner": d,
        "conv_dim": d + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"],
        "head_dim": head_dim,
        "q": cfg["num_attention_heads"] * head_dim,
        "kv": cfg["num_key_value_heads"] * head_dim,
    }


def group_layers(cfg: dict) -> dict:
    """{group: [global layer indices]} in pattern order."""
    out: dict = {}
    for i, kind in enumerate(cfg["layer_types"]):
        out.setdefault(GROUP_OF[kind], []).append(i)
    return out


def model_units(cfg: dict) -> dict:
    """{group: {the program's leaf name: Unit}} plus the group "top". A
    unit's own name carries its group, so that two groups' norms are not one
    vector; its layer key is the layer's rank in its group. Every row of
    either group holds its mixer, both norms and its MLP."""
    h, mi = cfg["hidden_size"], cfg["shared_intermediate_size"]
    dm = dims(cfg)

    def group(g, mixer):
        lin = lambda name, out, inn: Unit(f"{g}.{name}", "linear", out, inn)  # noqa: E731
        return {
            "norm": Unit(f"{g}.norm", "norm", h, 0),
            "mlp_norm": Unit(f"{g}.mlp_norm", "norm", h, 0),
            "mlp_in": lin("mlp_in", 2 * mi, h),
            "mlp_out": lin("mlp_out", h, mi),
            **{name: lin(name, out, inn) for name, (out, inn) in mixer.items()},
        }

    mamba = group("mamba", {
        # the checkpoint's one in_proj as the program holds it: the columns
        # of [z, xBC] and those of dt, two matrices of independent entries
        "in_proj": (dm["d_inner"] + dm["conv_dim"], h),
        "dt_proj": (cfg["mamba_n_heads"], h),
        "out_proj": (h, dm["d_inner"]),
    })
    mamba["ssm_norm"] = Unit("mamba.ssm_norm", "norm", dm["d_inner"], 0)
    return {
        "mamba": mamba,
        "attn": group("attn", {
            "q_proj": (dm["q"], h), "k_proj": (dm["kv"], h),
            "v_proj": (dm["kv"], h), "o_proj": (h, dm["q"]),
        }),
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def small_shapes(cfg: dict) -> dict:
    """{leaf name: shape} of a Mamba layer's vectors that are no ``Unit``."""
    nh = cfg["mamba_n_heads"]
    return {
        "conv_w": (dims(cfg)["conv_dim"], cfg["mamba_d_conv"]),
        "conv_b": (dims(cfg)["conv_dim"],), "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
    }


@functools.partial(jax.jit, static_argnames=("name", "n", "shape"))
def _small_stack(skey, name, n, shape):
    return jax.vmap(lambda r: small_vector(skey, "mamba", name, r, shape))(jnp.arange(n))


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` grouped
    and stacked as ``models/granitemoehybrid.map_weights`` stacks them (a
    layer's row is its rank in its group), the matrices generated when the
    engine's placement slices them (``LazyStack``), the small vectors
    resident; ``embed`` (the tied head's too) and ``final_norm``."""
    if fmt != "bf16":
        raise ValueError(f"granitemoehybrid is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    layers = {}
    for group, idxs in group_layers(cfg).items():
        layers[group] = {
            name: W.layer_stack(skey, unit, fmt, 0, len(idxs))
            for name, unit in units[group].items()
        }
    for name, shape in small_shapes(cfg).items():
        layers["mamba"][name] = _small_stack(skey, name, len(group_layers(cfg)["mamba"]), shape)
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
    }


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of one position's K and V in one attention layer (bf16)."""
    return 2 * 2 * dims(cfg)["kv"]


def paged_attn_step_bytes(cfg: dict, active_slots: float, context: float) -> float:
    """K/V bytes a decode step's attention must read: per active slot its
    ``context`` rows in every attention layer."""
    return active_slots * context * len(group_layers(cfg).get("attn", [])) * kv_row_bytes(cfg)


def ssm_state_step_bytes(cfg: dict, active_slots: float) -> float:
    """Bytes of recurrent state one decode step must read and write: per
    active slot and Mamba layer, the SSM state (float32) and the
    convolution's last ``K - 1`` inputs (bf16), each once in and once out."""
    ssm = 4 * cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
    conv = 2 * dims(cfg)["conv_dim"] * (cfg["mamba_d_conv"] - 1)
    return 2.0 * active_slots * len(group_layers(cfg).get("mamba", [])) * (ssm + conv)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every weight (both groups' mixers, norms and
    MLPs, the Mamba layers' small vectors, the tied embedding once as the
    head), the recurrent state of the active slots in and out, and the
    attention layers' K/V rows of ``cache_tokens`` tokens. Not counted:
    activations, the embedding's rows, K/V writes."""
    units = model_units(cfg)
    groups = group_layers(cfg)
    small = sum(
        (2 if n in ("conv_w", "conv_b") else 4) * math.prod(s)
        for n, s in small_shapes(cfg).items()
    )
    fixed = sum(
        len(idxs) * (sum(unit_bytes(u, fmt) for u in units[g].values())
                     + (small if g == "mamba" else 0))
        for g, idxs in groups.items()
    )
    out = {
        "fixed_weights": fixed + sum(unit_bytes(u, fmt) for u in units["top"].values()),
        "recurrent_state": ssm_state_step_bytes(cfg, active_slots),
        "kv_pages": cache_tokens * kv_row_bytes(cfg) * len(groups.get("attn", [])),
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def _parts(cfg_items, group, skey, rank, coarse):
    """``(cfg, lin, nrm)`` for one layer: ``lin(x, name) -> x @ M[name]``
    and ``nrm(name)`` its norm weight. ``coarse`` (a run-time boolean):
    matrices rounded to 3 mantissa bits first — ``reduce_precision`` and not
    a pair of converts: the TPU compiler may drop a round trip through a
    narrower type."""
    cfg = dict(cfg_items)
    units = model_units(cfg)[group]

    def lin(x, name):
        m = W.dense_logical(skey, units[name], rank)
        return x @ jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m)

    return cfg, lin, lambda name: W.logical_norm(skey, units[name], rank)


def _mlp_half(cfg, lin, nrm, h):
    u = rmsnorm(h, nrm("mlp_norm"), cfg["rms_norm_eps"])
    g, v = jnp.split(lin(u, "mlp_in"), 2, axis=-1)
    return h + cfg["residual_multiplier"] * lin(jax.nn.silu(g) * v, "mlp_out")


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _mamba_layer(cfg_items, skey, rank, h, reset_at, round_state, coarse):
    """``reset_at``: the position before which this layer's state and
    convolution inputs are lost (-1: never). ``round_state``: the state
    rounded to bf16 after every position."""
    cfg, lin, nrm = _parts(cfg_items, "mamba", skey, rank, coarse)
    dm = dims(cfg)
    t = h.shape[0]
    nh, p, g, n, k = (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"],
                      cfg["mamba_d_state"], cfg["mamba_d_conv"])
    d = dm["d_inner"]
    small = lambda name: small_vector(  # noqa: E731
        skey, "mamba", name, rank, small_shapes(cfg)[name]).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h, nrm("norm"), cfg["rms_norm_eps"])
        zx, dt = lin(u, "in_proj"), lin(u, "dt_proj")
        z, xbc = zx[:, :d], zx[:, d:]
        pos = jnp.arange(t)
        # causal depthwise convolution; an input from before the reset is lost
        w = small("conv_w")  # (C, K)
        conv = jnp.zeros_like(xbc)
        for j in range(k):
            back = k - 1 - j  # tap j reads the input `back` positions earlier
            lost = (pos < back) | ((pos >= reset_at) & (pos - back < reset_at))
            conv = conv + jnp.where(lost[:, None], 0.0, jnp.roll(xbc, back, axis=0)) * w[:, j]
        xbc = jax.nn.silu(conv + small("conv_b"))
        x = xbc[:, :d].reshape(t, nh, p)
        b_mat = jnp.repeat(xbc[:, d:d + g * n].reshape(t, g, n), nh // g, axis=1)
        c_mat = jnp.repeat(xbc[:, d + g * n:].reshape(t, g, n), nh // g, axis=1)
        dt = jax.nn.softplus(dt + small("dt_bias"))
        a = -jnp.exp(small("A_log"))
        d_skip = small("D")

        def step(s, xs):
            x_t, dt_t, b_t, c_t, pos_t = xs
            s = jnp.where(pos_t == reset_at, 0.0, s)
            s = jnp.exp(dt_t * a)[:, None, None] * s + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
            s = jnp.where(round_state, jax.lax.reduce_precision(s, 8, 7), s)
            return s, jnp.sum(s * c_t[:, None, :], axis=-1) + d_skip[:, None] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), jnp.float32), (x, dt, b_mat, c_mat, pos))
        y = y.reshape(t, d) * jax.nn.silu(z)
        yg = y.reshape(t, g, d // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
        y = yg.reshape(t, d) * nrm("ssm_norm")
        h = h + cfg["residual_multiplier"] * lin(y, "out_proj")
        return _mlp_half(cfg, lin, nrm, h)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _attn_layer(cfg_items, skey, rank, h, default_scale, coarse):
    cfg, lin, nrm = _parts(cfg_items, "attn", skey, rank, coarse)
    t = h.shape[0]
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], dims(cfg)["head_dim"]
    with jax.default_matmul_precision("highest"):
        u = rmsnorm(h, nrm("norm"), cfg["rms_norm_eps"])
        q = lin(u, "q_proj").reshape(t, hkv, hq // hkv, hd)
        k = lin(u, "k_proj").reshape(t, hkv, hd)
        v = lin(u, "v_proj").reshape(t, hkv, hd)
        pos = jnp.arange(t)
        scale = jnp.where(default_scale, hd ** -0.5, cfg["attention_multiplier"])
        s = jnp.einsum("tkgd,skd->kgts", q, k) * scale
        s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
        out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, axis=-1), v).reshape(t, hq * hd)
        h = h + cfg["residual_multiplier"] * lin(out, "o_proj")
        return _mlp_half(cfg, lin, nrm, h)


@functools.partial(jax.jit, static_argnames=("cfg_items", "top"))
def _head(cfg_items, top, skey, h, ids_wanted, coarse):
    """Log-probabilities over the whole vocabulary for the rows of ``h``,
    the tied table widened a block of rows at a time so that it fits beside
    a served model."""
    cfg = dict(cfg_items)
    units = model_units(cfg)["top"]
    v, hid = cfg["vocab_size"], cfg["hidden_size"]
    table = W.embed_matrix(W.unit_key(skey, "embed", 0), v, hid)  # bf16
    block = HEAD_BLOCK if v % HEAD_BLOCK == 0 else v
    with jax.default_matmul_precision("highest"):
        r = rmsnorm(h, W.logical_norm(skey, units["final_norm"], 0), cfg["rms_norm_eps"])

        def one_block(_, rows):
            m = rows.astype(jnp.float32)
            return None, r @ jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m).T

        _, logits = jax.lax.scan(one_block, None, table.reshape(v // block, block, hid))
    logits = jnp.moveaxis(logits, 0, 1).reshape(h.shape[0], v) / cfg["logits_scaling"]
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(cfg_items, skey, ids):
    cfg = dict(cfg_items)
    rows = W.logical_rows(skey, model_units(cfg)["top"]["embed"], "bf16", ids)
    return cfg["embedding_multiplier"] * rows


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: its scalars and the pattern."""
    out = {k: v for k, v in cfg.items()
           if isinstance(v, (int, float, str, bool, type(None)))}
    out["layer_types"] = tuple(cfg["layer_types"])
    return tuple(sorted(out.items()))


def hidden_states(cfg: dict, fmt: str, seed: int, ids, fault=None, handover: int = -1):
    """The final hidden states ``(T, hidden)`` of one sequence (before the
    final norm). ``handover``: the position a ``HANDOVER`` reset falls on."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fmt != "bf16":
        raise ValueError(f"granitemoehybrid is served in bf16 here, not {fmt!r}")
    reset_at, round_state, default_scale, mantissa = FAULTS[fault]
    reset_at = handover if reset_at == HANDOVER else reset_at
    coarse = jnp.asarray(mantissa < 7)
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    h = _embed(cfg_items, skey, jnp.asarray(ids, jnp.int32))
    n_mamba = len(group_layers(cfg).get("mamba", []))
    seen: dict = {}
    for kind in cfg["layer_types"]:
        group = GROUP_OF[kind]
        rank = seen.get(group, 0)
        seen[group] = rank + 1
        r = jnp.asarray(rank, jnp.int32)
        if group == "mamba":
            at = reset_at if rank == n_mamba // 2 else -1
            h = _mamba_layer(cfg_items, skey, r, h, jnp.asarray(at, jnp.int32),
                             jnp.asarray(round_state), coarse)
        else:
            h = _attn_layer(cfg_items, skey, r, h, jnp.asarray(default_scale), coarse)
    return h


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
    h = hidden_states(cfg, fmt, seed, np.pad(ids, (0, padded - t)), fault,
                      handover=int(np.asarray(rows)[0]) + 1)
    coarse = jnp.asarray(FAULTS[fault][3] < 7)
    out = _head(hashable(cfg), top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), coarse)
    return tuple(np.asarray(x) for x in out)
