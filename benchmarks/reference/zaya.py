"""Plain reference for the ``zaya`` family (Zyphra ZAYA1): the forward pass in
``jax.numpy`` and float32, matrix products at ``highest`` precision, no cache,
no kernel, no batching, full-sequence convolutions. Written from the family's
``config.json`` keys and the two public descriptions of the family
(Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1 report,
arXiv:2511.17127) as ISSUE 42 writes them down, not from
``mlx_sharding_tpu/models/zaya.py``.

Every layer ``l`` is two sub-layers on the hidden ``x (T, hidden)``, RMSNorm
eps ``rms_norm_eps``; each sub-layer's output is multiplied by a learned
``hidden``-vector before it joins the residual ("residual-scaled").

**Attention**, ``h = rmsnorm(x)``; ``Hq = num_attention_heads``, ``Hkv =
num_key_value_heads``, ``D = head_dim``:
1. ``q~ = h Wq`` (hidden -> Hq x D), ``k~ = h Wk`` (hidden -> Hkv x D).
2. ``u = [q~ ; k~]``; ``c1_t = a0 * u_{t-1} + a1 * u_t + b`` (depthwise, one
   pair of taps a channel, ``cca_time0`` = 2, zero before position 0);
   ``c2_t = B0 c1_{t-1} + B1 c1_t + b'``, block-diagonal over the Hq + Hkv
   heads (a head's D channels mix among themselves, ``cca_time1`` = 2).
3. The mean of q and k BEFORE the convolutions, per head: ``m_q = (q~ +
   rep(k~)) / 2`` with each K/V head repeated over its ``Hq / Hkv`` query
   heads, ``m_k`` the mean of ``m_q`` over each group's query heads. ``q =
   c2[: Hq D] + m_q``, ``k = c2[Hq D :] + m_k``.
4. ``q <- sqrt(D) q / |q|``, ``k <- tau sqrt(D) k / |k|`` per head, ``tau`` a
   learned scalar a K/V head.
5. ``v_t = [h_t Wv1 ; h_{t-1} Wv2]``, each hidden -> Hkv D / 2, split into
   the Hkv heads (``h_{-1} = 0``): half of the value channels come from the
   PREVIOUS token.
6. Rotary on the first ``partial_rotary_factor x D`` dimensions of each head
   (theta ``rope_parameters.hybrid.rope_theta``, split-half pairs), q and k.
7. Causal softmax attention at scale ``D**-0.5``, query head ``i`` on K/V
   head ``i // (Hq / Hkv)``; ``y = o Wo``; ``x <- x + scale_a * y``.

**MoE**, ``h = rmsnorm(x)``:
1. ``r_l = h Wd`` (hidden -> ``router_hidden_size``); the router's state runs
   down the layers: ``s_l = r_l + g_l * s_{l-1}``, ``s_{-1} = 0``.
2. ``z = W3 gelu(W2 gelu(W1 rmsnorm(s_l)))`` (erf GELU, no bias), ``p =
   softmax(z)`` over all experts.
3. The top ``num_experts_per_tok`` of ``p + b`` are chosen (``b`` a balancing
   bias, selection only); ``y = sum p_e Expert_e(h)``, ``Expert(h) = (silu(h
   Wg) * (h Wu)) Wdn``; no shared expert. ``x <- x + scale_m * y``.

A final RMSNorm; the head is the embedding transposed; no bias but the
convolutions'.

Departures.
- What ``config.json`` has no key for is ASSUMED and listed in the
  configuration file: the convolutions' biases, ``tau`` as a plain factor,
  the depth-averaging form ``s_l = r_l + g_l s_{l-1}``, the router MLP's
  depth 3 and GELU, the residual scaling as one vector a sub-layer. The
  "MoD" of the family's description has no key and is NOT implemented.
- Attention is computed in blocks of ``Q_BLOCK`` queries (each against every
  key, masked), and a layer's attention half and MoE half are two compiled
  programs, so that 6k positions fit beside a served model: the same numbers.
- The SHARE, the sliced vocabulary and the weights: as
  ``benchmarks/reference/afmoe.py`` says. The convolutions' biases are
  generated here (:func:`small_vector`), and so is the selection bias
  (:func:`balancing_biases`): it does what a trained router's balancing bias
  is there for, every expert chosen equally often. A seeded router MLP
  carries a token-independent part through its two GELUs (their outputs'
  mean), so without it a step's 24 rows visit 85-99 of a share's 160
  experts x layers, another count for every seed, and a seed's decode step
  follows it (``PERF.md`` section 6, PR 42): with it every seed does the
  uniform formula's 126.

Deliberately wrong variants (``fault``), run-time inputs of the same compiled
programs. ``conv_state_reset``: what a served path that lost its per-slot
state computes — at every chunk border (``CHUNK`` positions) and at every
position after the first compared row (a decode step) the row behind is read
as zero by both convolutions and by the value shift. ``value_shift_off``: the
second half of the values comes from the current token. ``qk_mean_off``: step
3 is left out. ``depth_state_off``: every layer's router starts from ``s =
0``. ``shift_cache_one`` hands the middle layer keys and values one position
late. ``weights_fp8`` rounds every matrix to 3 mantissa bits (float8 e4m3's
precision, bf16's range): the nearest precision below the one a bf16
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
from benchmarks.config import Unit

Q_BLOCK = 128
#: positions a prefill chunk of the served configuration holds: where
#: ``conv_state_reset`` loses the state inside a prompt
CHUNK = 512
GROUP = "layer"  # the one layer group's name in a unit's own name
EXPERTS = ("w_gate", "w_up", "w_down")

#: fault name -> (state reset, value shift off, q-k mean off, depth state
#: off, middle layer's cache shifted, mantissa bits kept of every matrix: 7
#: is bf16's own)
FAULTS = {
    None: (False, False, False, False, False, 7),
    "conv_state_reset": (True, False, False, False, False, 7),
    "value_shift_off": (False, True, False, False, False, 7),
    "qk_mean_off": (False, False, True, False, False, 7),
    "depth_state_off": (False, False, False, True, False, 7),
    "shift_cache_one": (False, False, False, False, True, 7),
    "weights_fp8": (False, False, False, False, False, 3),
}

#: the seeded vectors that are no Unit: name -> (width key of dims, spread).
#: 0.1 on a convolution's bias beside unit-variance channels
VECTORS = {"conv0_b": ("mix", 0.1), "conv1_b": ("mix", 0.1)}
#: the balancing bias is fitted on this many seeded router states a layer, in
#: this many steps of a shrinking size (:func:`balancing_biases`)
BALANCE_ROWS, BALANCE_STEPS = 8192, 300


# --------------------------------------------------------------------------
# the family's matrices, the served tree, the bytes of a decode step


def dims(cfg: dict) -> dict:
    share = int(cfg.get("moe_expert_share", 1))
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {
        "share": share,
        "base": int(cfg.get("moe_expert_share_index", 0)) * cfg["num_experts"],
        "router": cfg["num_experts"] * share,
        "q": q, "kv": kv, "mix": q + kv,
        "heads": cfg["num_attention_heads"] + cfg["num_key_value_heads"],
    }


def rope(cfg: dict) -> tuple:
    """``(theta, rotated dimensions of a head)``."""
    hybrid = (cfg.get("rope_parameters") or {}).get("hybrid", {})
    factor = hybrid.get("partial_rotary_factor", cfg.get("partial_rotary_factor", 0.5))
    return (float(hybrid.get("rope_theta", cfg.get("rope_theta", 5e6))),
            int(cfg["head_dim"] * factor))


def model_units(cfg: dict) -> dict:
    """{group: {the program's leaf name: Unit}}: the one layer group and
    "top". A layer's two convolutions are units too: ``conv0_w`` the ``(2,
    channels)`` taps, ``conv1_w`` one ``(D, D)`` matrix per tap and head
    (``experts`` = 2 x heads: tap ``j`` of head ``i`` is number ``j * heads +
    i``)."""
    h, d, rh = cfg["hidden_size"], cfg["head_dim"], cfg["router_hidden_size"]
    dm = dims(cfg)
    mi, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    lin = lambda name, out, inn, **kw: Unit(f"{GROUP}.{name}", "linear", out, inn, **kw)  # noqa: E731
    norm = lambda name, n: Unit(f"{GROUP}.{name}", "norm", n, 0)  # noqa: E731
    layer = {
        "attn_norm": norm("attn_norm", h), "moe_norm": norm("moe_norm", h),
        "attn_scale": norm("attn_scale", h), "moe_scale": norm("moe_scale", h),
        "k_temp": norm("k_temp", cfg["num_key_value_heads"]),
        "q_proj": lin("q_proj", dm["q"], h), "k_proj": lin("k_proj", dm["kv"], h),
        "v1_proj": lin("v1_proj", dm["kv"] // 2, h),
        "v2_proj": lin("v2_proj", dm["kv"] // 2, h),
        "o_proj": lin("o_proj", h, dm["q"]),
        "conv0_w": lin("conv0_w", dm["mix"], 2, keep_dense=True),
        "conv1_w": lin("conv1_w", d, d, experts=2 * dm["heads"], keep_dense=True),
        "router_down": lin("router_down", rh, h, keep_dense=True),
        "router_gate": norm("router_gate", rh), "router_norm": norm("router_norm", rh),
        "router_w1": lin("router_w1", rh, rh, keep_dense=True),
        "router_w2": lin("router_w2", rh, rh, keep_dense=True),
        "router_w3": lin("router_w3", dm["router"], rh, keep_dense=True),
        "w_gate": lin("w_gate", mi, h, experts=e), "w_up": lin("w_up", mi, h, experts=e),
        "w_down": lin("w_down", h, mi, experts=e),
    }
    return {
        GROUP: layer,
        "top": {
            "embed": Unit("embed", "linear", cfg["vocab_size"], h),
            "final_norm": Unit("final_norm", "norm", h, 0),
        },
    }


def small_vector(skey, name: str, rank, cfg: dict):
    """One of a layer's seeded ``VECTORS``, a convolution's bias: float32
    holding bf16 values, as the program holds it."""
    width, spread = VECTORS[name]
    x = spread * jax.random.normal(
        W.unit_key(skey, f"{GROUP}.{name}", rank), (dims(cfg)[width],), jnp.float32
    )
    return x.astype(jnp.bfloat16).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("name", "cfg_items", "n"))
def _vector_stack(skey, name, cfg_items, n):
    out = jax.vmap(lambda r: small_vector(skey, name, r, dict(cfg_items)))(jnp.arange(n))
    return out.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("cfg_items", "n"))
def _balance(cfg_items, skey, n):
    cfg = dict(cfg_items)
    e, k = dims(cfg)["router"], cfg["num_experts_per_tok"]

    def one_layer(rank):
        _, mat, _, nrm = _parts(cfg_items, skey, rank, jnp.asarray(False))
        s = jax.random.normal(W.unit_key(skey, f"{GROUP}.router_bias", rank),
                              (BALANCE_ROWS, cfg["router_hidden_size"]), jnp.float32)
        p = _router(cfg, mat, nrm, s)
        size = 0.5 * jnp.std(p)

        def step(i, b):
            _, top_i = jax.lax.top_k(p + b, k)
            load = jnp.mean(jnp.sum(jax.nn.one_hot(top_i, e), axis=-2), axis=0) / k
            return b - size / (1.0 + i / 30.0) * (load * e - 1.0)

        return jax.lax.fori_loop(0, BALANCE_STEPS, step, jnp.zeros(e, jnp.float32))

    with jax.default_matmul_precision("highest"):
        return jax.vmap(one_layer)(jnp.arange(n))


@functools.lru_cache(maxsize=4)
def balancing_biases(cfg_items: tuple, seed: int):
    """Every layer's selection bias ``(layers, experts)``, float32: fitted so
    that the layer's own router, on ``BALANCE_ROWS`` seeded isotropic states
    ``s``, chooses every expert equally often — ``b <- b - size (load x
    experts - 1)`` with the chosen shares ``load`` under ``p + b``, the step
    shrinking as ``1 / (1 + i / 30)`` from half the spread of ``p``: the
    subgradient descent of the balanced assignment's dual (held-out rows then
    load every expert within a tenth of its share). One cached array serves
    the program's tree and the reference, so both hold the same numbers."""
    return _balance(cfg_items, W.seed_key(seed), dict(cfg_items)["num_hidden_layers"])


def program_params(cfg: dict, fmt: str, seed: int) -> dict:
    """The tree ``load_model`` returns for this config: ``layers`` one stack
    as ``models/zaya.map_weights`` stacks it, the matrices generated when the
    engine's placement slices them (``LazyStack``), the small vectors
    resident; ``embed`` (the tied head's too) and ``final_norm``."""
    if fmt != "bf16":
        raise ValueError(f"zaya is served in bf16 here, not {fmt!r}")
    skey = W.seed_key(seed)
    units = model_units(cfg)
    n = cfg["num_hidden_layers"]
    layers = {name: W.layer_stack(skey, unit, fmt, 0, n) for name, unit in units[GROUP].items()}
    for name in VECTORS:
        layers[name] = _vector_stack(skey, name, hashable(cfg), n)
    layers["router_bias"] = balancing_biases(hashable(cfg), int(seed))
    top = units["top"]
    return {
        "layers": layers,
        "embed": {"weight": W.top_leaf(skey, top["embed"], fmt)},
        "final_norm": {"weight": W.top_leaf(skey, top["final_norm"], fmt)},
    }


def kv_row_bytes(cfg: dict) -> int:
    """Bytes of one position's K and V in one layer (bf16)."""
    return 2 * 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def paged_attn_step_bytes(cfg: dict, active_slots: float, context: float) -> float:
    """K/V bytes a decode step's attention must read: per active slot its
    ``context`` rows in every layer."""
    return active_slots * context * cfg["num_hidden_layers"] * kv_row_bytes(cfg)


def decode_step_bytes(cfg: dict, fmt: str, active_slots: float,
                      cache_tokens: float) -> dict:
    """Bytes one decode step of the served path must move through HBM,
    counted once per step: every weight outside the routed experts
    (projections, both convolutions, router, norms and scales, the tied
    embedding once as the head), the DISTINCT held experts the active rows'
    choices hit, and the K/V rows attention must read
    (:func:`paged_attn_step_bytes`; ``cache_tokens`` are the tokens in the
    pool, so a slot's context is their mean). Not counted: activations, the
    embedding's rows, K/V writes, the per-slot state (5.4 KB a slot a layer).
    The hit experts are the uniform formula's — top-1 of 16 with 8 held:
    ``8 (1 - (15/16)^rows)`` a layer. A seeded router left to itself hits
    FEWER (``PERF.md`` section 7 records 30 % fewer for two other families,
    and this one read 85-102 of the formula's 126 a step); under the fitted
    balancing bias (:func:`balancing_biases`) the formula holds here."""
    units = model_units(cfg)[GROUP]
    dm = dims(cfg)
    per_layer = sum(
        unit_bytes(u, fmt) * max(u.experts, 1)
        for name, u in units.items() if name not in EXPERTS
    ) + 2 * 2 * dm["mix"] + 4 * dm["router"]
    one_expert = sum(unit_bytes(units[name], fmt) for name in EXPERTS)
    hit = expected_distinct_experts(
        dm["router"], cfg["num_experts_per_tok"], active_slots
    ) / dm["share"]
    n = cfg["num_hidden_layers"]
    context = cache_tokens / active_slots if active_slots else 0.0
    out = {
        "fixed_weights": n * per_layer + 2 * cfg["vocab_size"] * cfg["hidden_size"],
        "routed_experts": n * hit * one_expert,
        "kv_pages": paged_attn_step_bytes(cfg, active_slots, context),
    }
    out["total"] = sum(out.values())
    return out


# --------------------------------------------------------------------------
# the plain reference


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate_part(x, theta: float, n: int):
    """Rotary embedding of the first ``n`` dimensions of ``x (T, heads, D)``
    at positions ``0 .. T-1``: pair ``(i, i + n/2)`` turns by ``pos *
    theta**(-2i / n)``; the rest passes."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # (T, n/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : n // 2], x[..., n // 2 : n]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., n:]], axis=-1)


def _matrix(units, skey, rank, coarse):
    """``mat(name, number=None)``: one float32 matrix ``M`` of a layer with
    ``y = x @ M``. ``coarse`` (a run-time boolean): rounded to 3 mantissa
    bits first (``reduce_precision``: the TPU compiler may drop a round trip
    through a narrower type)."""
    def mat(name, number=None):
        m = W.dense_logical(skey, units[name], rank, number)
        return jnp.where(coarse, jax.lax.reduce_precision(m, 8, 3), m)
    return mat


def _attention(cfg, mat, vec, nrm, x, lost, shift_off, mean_off, shift):
    """``attn(x)`` for one layer, ``x (T, hidden)``. ``lost (T,)``: the rows
    that read the row behind them as zero; the other faults are run-time
    booleans."""
    t = x.shape[0]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    g, nh = hq // hkv, hq + hkv
    h = rmsnorm(x, nrm("attn_norm"), cfg["rms_norm_eps"])

    def behind(z):  # row t-1 at row t, zero before position 0 and where lost
        z = jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)
        return jnp.where(lost.reshape(t, *([1] * (z.ndim - 1))), 0.0, z)

    u = jnp.concatenate([h @ mat("q_proj"), h @ mat("k_proj")], axis=-1)  # (T, C)
    taps = mat("conv0_w")  # (2, C)
    c1 = taps[0] * behind(u) + taps[1] * u + vec("conv0_b")
    mix = jax.vmap(lambda i: mat("conv1_w", i))(jnp.arange(2 * nh)).reshape(2, nh, d, d)
    heads = lambda z: z.reshape(t, nh, d)  # noqa: E731
    c2 = (
        jnp.einsum("thi,hio->tho", heads(behind(c1)), mix[0])
        + jnp.einsum("thi,hio->tho", heads(c1), mix[1])
        + vec("conv1_b").reshape(nh, d)
    )
    uh = heads(u)
    m_q = 0.5 * (uh[:, :hq] + jnp.repeat(uh[:, hq:], g, axis=1))
    m_k = m_q.reshape(t, hkv, g, d).mean(axis=2)
    q = c2[:, :hq] + jnp.where(mean_off, 0.0, m_q)
    k = c2[:, hq:] + jnp.where(mean_off, 0.0, m_k)
    length = lambda z: jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True))  # noqa: E731
    q = d ** 0.5 * q / length(q)
    k = nrm("k_temp")[:, None] * d ** 0.5 * k / length(k)
    v2 = h @ mat("v2_proj")
    v = jnp.concatenate(
        [h @ mat("v1_proj"), jnp.where(shift_off, v2, behind(v2))], axis=-1
    ).reshape(t, hkv, d)
    theta, n_rot = rope(cfg)
    q, k = rotate_part(q, theta, n_rot), rotate_part(k, theta, n_rot)
    # the negative control: this layer sees the row of the position before
    k = jnp.where(shift, jnp.roll(k, 1, axis=0), k)
    v = jnp.where(shift, jnp.roll(v, 1, axis=0), v)
    k_pos = jnp.arange(t)

    def block(args):
        qb, q_pos = args  # (Q, Hq, D), (Q,)
        s = jnp.einsum("qkgd,skd->kgqs", qb.reshape(-1, hkv, g, d), k) * d ** -0.5
        s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, -jnp.inf)
        return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, axis=-1), v).reshape(-1, hq * d)

    qb = min(Q_BLOCK, t)
    if t % qb:
        raise ValueError(f"{t} positions are no multiple of the query block {qb}")
    out = jax.lax.map(block, (q.reshape(t // qb, qb, hq, d), k_pos.reshape(t // qb, qb)))
    return out.reshape(t, hq * d) @ mat("o_proj")


def _router(cfg, mat, nrm, s):
    """The experts' probabilities ``(T, experts)`` from the router's state."""
    gelu = lambda a: jax.nn.gelu(a, approximate=False)  # noqa: E731
    z = rmsnorm(s, nrm("router_norm"), cfg["rms_norm_eps"])
    z = gelu(gelu(z @ mat("router_w1")) @ mat("router_w2")) @ mat("router_w3")
    return jax.nn.softmax(z, axis=-1)


def _moe(cfg, mat, bias, nrm, h, s_prev):
    """``(moe(h), s, the choices)`` of one layer, ``h (T, hidden)`` normed;
    ``bias`` the layer's row of :func:`balancing_biases`."""
    dm = dims(cfg)
    s = h @ mat("router_down") + nrm("router_gate") * s_prev
    p = _router(cfg, mat, nrm, s)
    _, top_i = jax.lax.top_k(p + bias, cfg["num_experts_per_tok"])
    top_v = jnp.take_along_axis(p, top_i, axis=-1)

    def one_expert(acc, e):  # e: the expert's place among those held
        coef = jnp.sum(jnp.where(top_i == e + dm["base"], top_v, 0.0), axis=-1)
        y = (jax.nn.silu(h @ mat("w_gate", e)) * (h @ mat("w_up", e))) @ mat("w_down", e)
        return acc + coef[:, None] * y, None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(cfg["num_experts"]))
    return out, s, top_i


def _parts(cfg_items, skey, rank, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)[GROUP]
    return (cfg, _matrix(units, skey, rank, coarse),
            lambda name: small_vector(skey, name, rank, cfg),
            lambda name: W.logical_norm(skey, units[name], rank))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _attn_half(cfg_items, skey, rank, x, lost, shift_off, mean_off, shift, coarse):
    """``x + scale_a * attn(rmsnorm(x))``."""
    cfg, mat, vec, nrm = _parts(cfg_items, skey, rank, coarse)
    with jax.default_matmul_precision("highest"):
        a = _attention(cfg, mat, vec, nrm, x, lost, shift_off, mean_off, shift)
        return x + nrm("attn_scale") * a


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _moe_half(cfg_items, skey, rank, x, s_prev, bias, coarse):
    """``(x + scale_m * moe(rmsnorm(x)), s, the choices)``."""
    cfg, mat, _, nrm = _parts(cfg_items, skey, rank, coarse)
    with jax.default_matmul_precision("highest"):
        h = rmsnorm(x, nrm("moe_norm"), cfg["rms_norm_eps"])
        m, s, top_i = _moe(cfg, mat, bias, nrm, h, s_prev)
        return x + nrm("moe_scale") * m, s, top_i


@functools.partial(jax.jit, static_argnames=("cfg_items", "top"))
def _head(cfg_items, top, skey, h, ids_wanted, coarse):
    cfg = dict(cfg_items)
    units = model_units(cfg)["top"]
    with jax.default_matmul_precision("highest"):
        r = rmsnorm(h, W.logical_norm(skey, units["final_norm"], 0), cfg["rms_norm_eps"])
        table = W.embed_matrix(
            W.unit_key(skey, "embed", 0), cfg["vocab_size"], cfg["hidden_size"]
        ).astype(jnp.float32)
        logits = r @ jnp.where(coarse, jax.lax.reduce_precision(table, 8, 3), table).T
    lp = jax.nn.log_softmax(logits, axis=-1)
    top_v, top_i = jax.lax.top_k(lp, top)
    return top_i, top_v, jnp.take_along_axis(lp, ids_wanted, axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(cfg_items, skey, ids):
    return W.logical_rows(skey, model_units(dict(cfg_items))["top"]["embed"], "bf16", ids)


def hashable(cfg: dict) -> tuple:
    """The config as a static jit argument: its scalars, and the rotary's
    two out of ``rope_parameters``."""
    out = {k: v for k, v in cfg.items()
           if isinstance(v, (int, float, str, bool, type(None)))}
    out["rope_theta"], n_rot = rope(cfg)
    out["partial_rotary_factor"] = n_rot / cfg["head_dim"]
    return tuple(sorted(out.items()))


def hidden_states(cfg: dict, fmt: str, seed: int, ids, fault=None, first_row=None):
    """The final hidden states ``(T, hidden)`` of one sequence (before the
    final norm) and each layer's choices. ``first_row``: the first compared
    row — every later position is a decode step of the served path, which is
    where ``conv_state_reset`` loses the state beside the chunk borders."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    if fmt != "bf16":
        raise ValueError(f"zaya is served in bf16 here, not {fmt!r}")
    reset, shift_off, mean_off, depth_off, shift, mantissa = FAULTS[fault]
    coarse = jnp.asarray(mantissa < 7)
    cfg_items = hashable(cfg)
    skey = W.seed_key(seed)
    ids = jnp.asarray(ids, jnp.int32)
    pos = jnp.arange(ids.shape[0])
    lost = jnp.zeros(ids.shape, bool)
    if reset:
        lost = pos % CHUNK == 0
        if first_row is not None:
            lost |= pos > first_row
    h = _embed(cfg_items, skey, ids)
    s = jnp.zeros((ids.shape[0], cfg["router_hidden_size"]), jnp.float32)
    n = cfg["num_hidden_layers"]
    biases = balancing_biases(cfg_items, int(seed))
    picks = []
    for i in range(n):
        r = jnp.asarray(i, jnp.int32)
        h = _attn_half(cfg_items, skey, r, h, lost, jnp.asarray(shift_off),
                       jnp.asarray(mean_off), jnp.asarray(shift and i == n // 2), coarse)
        h, s_new, top_i = _moe_half(cfg_items, skey, r, h, s, biases[i], coarse)
        s = jnp.zeros_like(s_new) if depth_off else s_new
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
    h, _ = hidden_states(cfg, fmt, seed, np.pad(ids, (0, padded - t)), fault,
                         first_row=int(min(rows)))
    coarse = jnp.asarray(FAULTS[fault][5] < 7)
    out = _head(hashable(cfg), top, W.seed_key(seed), h[np.asarray(rows)],
                jnp.asarray(np.asarray(ids_wanted, np.int32)), coarse)
    return tuple(np.asarray(x) for x in out)
