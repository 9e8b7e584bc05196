"""Moonshot Kimi-Linear decoder (``model_type: kimi_linear``).

Pre-norm residual blocks, no bias anywhere: ``h = h + mixer(rmsnorm(h,
input_layernorm))``; ``h = h + ffn(rmsnorm(h, post_attention_layernorm))``;
logits ``= rmsnorm(h, norm) @ lm_head`` (untied).

- ``kda`` **Kimi Delta Attention** (``ops/kda.py``): 32 heads of 128 behind a
  4-tap convolution at the published sizes, a decay a key channel. Per
  sequence the layer keeps ``S (H, D, D)`` in float32 and the last ``taps -
  1`` inputs of its convolution in the activation dtype, FLAT ``((taps - 1) *
  3 H D,)`` as ``models/granitemoehybrid.py`` keeps its tails and says why.
  The three projections are one matrix ``qkv_proj`` and the two low-rank
  gates' inner projections one ``gate_a``.
- ``mla`` **multi-head latent attention** (``ops/mla.py``, shared with
  ``models/deepseek_v2.py``) with NO rotary embedding (``mla_use_nope``),
  always in the compressed form: a position's row is ``[latent, k_pe]``
  (576 values at the published sizes), one shared head, ``kv_b_proj``
  absorbed on both sides. The row is stored PADDED with zeros to whole lane
  tiles (640): a TPU lays an array out by its shape, and a page pool whose
  rows are 576 wide (4.5 tiles) it keeps with the page's 512 positions on
  the lanes instead, so that every served program began and ended with a
  copy of the whole pool into the layout the ragged kernel reads (2.0 GB
  each way a decode block: AOT listing and chip, PR 48). The query is
  padded alike, so the scores are the unpadded row's.
- The feed-forward: a SwiGLU MLP in the first ``first_k_dense_replace``
  layers; in the others sigmoid scores in float32 over all experts, the top
  ``num_experts_per_token`` of ``scores + e_score_correction_bias`` chosen
  and weighted by their own scores over their sum times
  ``routed_scaling_factor`` (``ops.moe.nemotron_routing``: the same gate),
  routed SwiGLU experts plus one shared SwiGLU expert on every token. The
  layer may hold a share of the routed experts (``config.KimiLinearConfig``):
  it routes over all of them and computes its own experts' part.

Layers: three stacked groups — ``dense`` (KDA and the MLP: the leading
layers), ``kda`` (KDA and experts), ``mla`` (MLA and experts). The pattern is
periodic only between a head and a tail (the published one: the dense layer,
six times ``K K M K``, then ``K M``), so the walk is the head's layers, a
``lax.scan`` over the periods whose body runs the period's runs of like
layers (a run of several an inner scan), and the tail's layers
(:func:`pattern_walk`): a compiled program holds the KDA body four times and
the MLA body twice, not 20 and 7. The state pool and the K/V (the engine's
page pool in a ragged decode step, a slot's contiguous rows otherwise) ride
the scans' CARRY whole and a layer is its rank in it; the expert stacks are
read where they lie by ``(layer, expert)``. One pipeline stage, no tensor or
expert parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import KimiLinearConfig
from mlx_sharding_tpu.models.base import (
    BaseModel,
    LayerRow,
    dense_init,
    put_row,
    stack_layers,
    take_row,
)
from mlx_sharding_tpu.ops import causal_attention, rms_norm
from mlx_sharding_tpu.ops.kda import kda_mixer
from mlx_sharding_tpu.ops.mamba2 import put_rows, take_rows
from mlx_sharding_tpu.ops.mla import absorb_values, mla_qkv
from mlx_sharding_tpu.ops.moe import apply_experts, nemotron_routing

GROUPS = ("dense", "kda", "mla")
ONE_STAGE = (
    "pipeline stages are not wired for kimi_linear: the state pool and the "
    "period scan belong to one stage (run it with --num-stages 1)"
)


def pattern_walk(kinds: list) -> tuple:
    """``(head, period, periods, tail)`` of a layer pattern that repeats
    between a head and a tail: the split with the fewest layers outside the
    repeats and in one period (the first such; a pattern that repeats nowhere
    is all head). ``head + period * periods + tail == kinds``."""
    n = len(kinds)
    best = (n, list(kinds), [], 0, [])
    for a in range(n):
        for p in range(1, (n - a) // 2 + 1):
            c = 1
            while a + (c + 1) * p <= n and kinds[a + c * p : a + (c + 1) * p] == kinds[a : a + p]:
                c += 1
            cost = n - (c - 1) * p
            if c > 1 and cost < best[0]:
                best = (cost, kinds[:a], kinds[a : a + p], c, kinds[a + c * p :])
    return best[1:]


def runs_of(kinds: list) -> list:
    """``[(kind, layers in the run)]`` of like neighbours."""
    runs: list = []
    for kind in kinds:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [tuple(r) for r in runs]


def run_pattern(walk: tuple, groups: tuple, layer, carry):
    """The layers of ``walk`` (:func:`pattern_walk`'s ``(head, period,
    periods, tail)`` over the kinds ``groups``) in order: the head's, one
    ``lax.scan`` over the periods, the tail's. ``layer(group, rank, carry) ->
    carry`` runs the layer at row ``rank`` (may be traced) of ``group``'s
    stacks; inside a stretch each run of like neighbours of more than one is
    an inner scan."""

    def run(kinds, first, carry):
        """``kinds`` in order, each layer at its row: ``first[g]`` (may be
        traced) plus the like layers before it here."""
        seen = dict.fromkeys(groups, 0)
        for group, n in runs_of(kinds):
            at = first[group] + seen[group]
            seen[group] += n
            if n == 1:
                carry = layer(group, at, carry)
            else:
                carry, _ = jax.lax.scan(
                    lambda c, j, g=group, f=at: (layer(g, f + j, c), None),
                    carry, jnp.arange(n),
                )
        return carry

    head, period, periods, tail = walk
    count = lambda kinds: {g: kinds.count(g) for g in groups}  # noqa: E731
    in_head, in_period = count(head), count(period)
    carry = run(head, dict.fromkeys(groups, 0), carry)
    if periods:
        carry, _ = jax.lax.scan(
            lambda c, i: (run(period, {
                g: in_head[g] + i * in_period[g] for g in groups
            }, c), None),
            carry, jnp.arange(periods),
        )
    return run(tail, {g: in_head[g] + periods * in_period[g] for g in groups}, carry)


class KimiLinearModel(BaseModel):
    #: engines carry a per-slot recurrent state beside the K/V pages
    #: (cache.KVCache.state); whatever rewinds a slot by lowering its offset
    #: cannot serve this model (cache.refuse_recurrent)
    has_recurrent_state = True

    def __init__(self, config: KimiLinearConfig):
        super().__init__(config)
        if (config.start_layer, config.end_layer) != (0, config.num_hidden_layers):
            raise ValueError(ONE_STAGE)
        lin = config.linear_attn_config
        self.kda_heads, self.kda_dim = lin["num_heads"], lin["head_dim"]
        self.kda_taps = lin["short_conv_kernel_size"]
        self.kda_width = self.kda_heads * self.kda_dim
        #: a cached row ``[latent, k_pe]``, padded to whole lane tiles
        self.row_dim = -(-(config.kv_lora_rank + config.qk_rope_head_dim) // 128) * 128
        self.scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
        #: each layer's group, in order
        self.layer_groups = [
            "mla" if kind == "mla" else "dense" if i < config.first_k_dense_replace else "kda"
            for i, kind in enumerate(config.layer_kinds)
        ]
        self.walk = pattern_walk(self.layer_groups)

    # -- layer structure ---------------------------------------------------
    def layer_group_layers(self) -> dict:
        """{group: [global layer indices]} — the groups interleave."""
        out: dict = {}
        for i, group in enumerate(self.layer_groups):
            out.setdefault(group, []).append(i)
        return out

    def layer_group_ranges(self) -> dict:
        raise NotImplementedError(
            "kimi_linear layer groups interleave: use layer_group_layers()"
        )

    def kv_groups(self) -> tuple:
        return ("mla",)

    def state_groups(self) -> tuple:
        return ("dense", "kda")

    def packed_keep_dense_re(self) -> str | None:
        return r"block_sparse_moe\.gate\.weight$"

    def stage_plan(self, stage_bounds) -> tuple:
        """The one stage's ``(start, end)``: the walk is the model's own
        period scan — asked for by an engine that carries ``cache.state``."""
        if len(stage_bounds) != 1:
            raise ValueError(ONE_STAGE)
        return tuple(stage_bounds[0])

    # -- cache and state ---------------------------------------------------
    def cache_num_heads(self) -> int:
        return 1  # the shared latent head

    def cache_head_dim(self):
        return (self.row_dim, 1)  # values are a slice of the key row

    def state_shapes(self, batch: int) -> dict:
        """Per KDA layer and sequence: {name: (shape after (layer,), dtype)}."""
        return {
            "kda": ((batch, self.kda_heads, self.kda_dim, self.kda_dim), jnp.float32),
            "conv": ((batch, (self.kda_taps - 1) * 3 * self.kda_width), None),
        }

    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        n = {g: self.layer_groups.count(g) for g in GROUPS}
        kv = init_cache(n["mla"], batch, max_seq, 1, self.cache_head_dim(), dtype)
        return kv._replace(state={
            name: jnp.zeros((n["dense"] + n["kda"], *shape), dt or dtype)
            for name, (shape, dt) in self.state_shapes(batch).items()
        })

    # -- the sub-layers ----------------------------------------------------
    def _kda(self, p, u, state, rank, n_valid, active):
        """The KDA mixer of the layer at ``rank`` of the state pool ``{name:
        (layers, rows, …)}``: its rows of the ``B`` sequences of ``u`` are
        read, advanced and written back where the pool lies (rows past ``B``,
        an engine's scratch row, are neither read nor written). Returns
        ``(out, state)``."""
        nb = u.shape[0]
        tail = take_rows(state["conv"], rank, nb)
        out, pool, tail = kda_mixer(
            self._linear, p, u, state["kda"], rank,
            tail.reshape(nb, self.kda_taps - 1, 3 * self.kda_width), n_valid,
            active, heads=self.kda_heads, head_dim=self.kda_dim,
            taps=self.kda_taps, eps=self.config.rms_norm_eps,
        )
        state = {"kda": pool, "conv": put_rows(state["conv"], rank, tail.reshape(nb, -1))}
        return out, state

    def _mla(self, p, u, k_buf, v_buf, offset, paged):
        """``k_buf`` / ``v_buf``: the layer's contiguous rows ``(B, S, 1,
        row_dim)`` and the dummy ``(B, S, 1, 1)``, or with ``paged`` (the
        engine's ``(attn_fn, done)`` over the pool where it lies) unused.
        Returns ``(out, k_buf, v_buf)``."""
        cfg = self.config
        b, t, _ = u.shape
        rank = cfg.kv_lora_rank
        with jax.named_scope("mst.attn.qkv"):
            q, k_new, _, w_bv = mla_qkv(
                self._linear, p, u, offset, nope=cfg.qk_nope_head_dim,
                rope_d=cfg.qk_rope_head_dim, v_d=cfg.v_head_dim, rank=rank,
                eps=cfg.rms_norm_eps, rotary=None, compressed=True, q_lora=False,
            )
            pad = lambda x: jnp.pad(  # noqa: E731
                x, ((0, 0),) * 3 + ((0, self.row_dim - x.shape[-1]),))
            q, k_new = pad(q), pad(k_new)
            dummy_v = jnp.zeros((b, t, 1, 1), k_new.dtype)
        if paged is not None:
            attn_fn, done = paged
            out_lat = attn_fn(q, k_new, dummy_v, values_from_k=rank)
            k_buf, v_buf = done["k"], done["v"]
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, k_new, dummy_v, offset)
            out_lat = causal_attention(q, k_buf, k_buf[..., :rank], offset, self.scale)
        attn = absorb_values(out_lat, w_bv, u.dtype)
        with jax.named_scope("mst.attn.qkv"):
            out = self._linear(attn.reshape(b, t, -1), p["o_proj"])
        return out, k_buf, v_buf

    def _swiglu(self, x, gate, up, down):
        return self._linear(
            jax.nn.silu(self._linear(x, gate)) * self._linear(x, up), down
        )

    def _moe(self, p, stacks, rank, u):
        """``p``: the layer's small leaves; ``stacks``: the group's whole
        ``(L, E, …)`` expert stacks, read at ``rank`` inside the expert scan."""
        cfg = self.config
        b, t, hidden = u.shape
        flat = u.reshape(b * t, hidden)
        weights, idx = nemotron_routing(
            flat, p["router"], p["router_bias"], cfg.num_experts_per_token,
            norm_topk_prob=cfg.moe_renormalize,
            routed_scaling_factor=cfg.routed_scaling_factor,
        )
        routed = apply_experts(
            flat, weights, idx, stacks["w_gate"], stacks["w_up"], stacks["w_down"],
            group_size=self._gs, bits=self._bits,
            expert_base=(
                cfg.moe_expert_share_index * cfg.num_experts
                if cfg.moe_expert_share > 1 else None
            ),
            layer=rank,
        )
        with jax.named_scope("mst.moe.shared"):
            shared = self._swiglu(
                flat, p["shared_gate"], p["shared_up"], p["shared_down"]
            )
            return (routed + shared).reshape(b, t, hidden)

    # -- the layer walk ----------------------------------------------------
    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None, *, state=None, n_valid=None, active=None, plan=None,
        stage_axis=None, paged_attn=None,
    ):
        """All layers: the head's, one scan over the periods, the tail's.
        ``state`` holds every KDA layer's rows of the ``B`` sequences of
        ``h`` (and, from an engine's ragged decode, a scratch row past them).
        ``k`` / ``v``: every MLA layer's contiguous rows ``(L, B, S, 1, …)``
        — or, with ``paged_attn``, the engine's page pool; either is carried
        whole. ``mask``, ``plan`` and ``stage_axis`` are unused: one stage
        has no padding layer. Returns ``(h, k, v, state)``."""
        if tp_axis is not None or ep_axis is not None:
            raise ValueError(
                "tensor and expert parallelism are not wired for kimi_linear"
            )
        eps = self.config.rms_norm_eps
        n_dense = self.layer_groups.count("dense")

        def layer(group, rank, carry):
            """The layer at row ``rank`` of ``group``'s stacks (may be traced)."""
            h, k, v, state = carry
            p = LayerRow(layer_params[group], rank)
            u = rms_norm(h, p["norm"], eps)
            if group != "mla":
                # the state pool holds the dense group's rows, then kda's
                at = rank if group == "dense" else rank + n_dense
                out, state = self._kda(p, u, state, at, n_valid, active)
            elif paged_attn is not None:
                out, k, v = self._mla(p, u, k, v, offset, paged_attn(k, v, layer=rank))
            else:
                with jax.named_scope("mst.kv_pool.regroup"):
                    k_l, v_l = take_row(k, rank), take_row(v, rank)
                out, k_l, v_l = self._mla(p, u, k_l, v_l, offset, None)
                with jax.named_scope("mst.kv_pool.regroup"):
                    k, v = put_row(k, rank, k_l), put_row(v, rank, v_l)
            h = h + out.astype(h.dtype)
            u = rms_norm(h, p["ffn_norm"], eps)
            if group == "dense":
                with jax.named_scope("mst.mlp.dense"):
                    out = self._swiglu(u, p["gate_proj"], p["up_proj"], p["down_proj"])
            else:
                out = self._moe(p, layer_params[group], rank, u)
            return h + out.astype(h.dtype), k, v, state

        return run_pattern(self.walk, GROUPS, layer, (h, k, v, state))

    # -- embed / head ------------------------------------------------------
    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        h = self.embed(params, x)
        offset = cache.offset
        # position 0 has no history: whatever the buffers hold is not state
        state = jax.tree.map(lambda s: jnp.where(offset == 0, 0, s), cache.state)
        h, k, v, state = self.run_layers(
            params["layers"], h, cache.k, cache.v, offset, state=state,
            n_valid=None if x.shape[1] == 1 else n_valid,
        )
        cache = KVCache(k=k, v=v, offset=offset, state=state)
        cache = advance(cache, x.shape[1] if n_valid is None else n_valid)
        return self.apply_head(params, h), cache

    # -- weights -----------------------------------------------------------
    #: checkpoint suffix -> (our leaf, transposed to (in, out)?). The catalog
    #: gives the family's config.json, not its tensor names: these follow the
    #: family's published module names and are ASSUMED.
    NORMS = {
        "input_layernorm.weight": ("norm", False),
        "post_attention_layernorm.weight": ("ffn_norm", False),
    }
    KDA = {
        "self_attn.f_b_proj.weight": ("f_b", True),
        "self_attn.g_b_proj.weight": ("g_b", True),
        "self_attn.b_proj.weight": ("b_proj", True),
        "self_attn.A_log": ("A_log", False),
        "self_attn.dt_bias": ("dt_bias", False),
        "self_attn.o_norm.weight": ("o_norm", False),
        "self_attn.o_proj.weight": ("o_proj", True),
    }
    MLA = {
        "self_attn.q_proj.weight": ("q_proj", True),
        "self_attn.kv_a_proj_with_mqa.weight": ("kv_a_proj", True),
        "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
        "self_attn.kv_b_proj.weight": ("kv_b_proj", True),
        "self_attn.o_proj.weight": ("o_proj", True),
    }
    MLP = {
        "mlp.gate_proj.weight": ("gate_proj", True),
        "mlp.up_proj.weight": ("up_proj", True),
        "mlp.down_proj.weight": ("down_proj", True),
    }
    MOE = {
        "block_sparse_moe.gate.weight": ("router", True),
        "block_sparse_moe.gate.e_score_correction_bias": ("router_bias", False),
        "block_sparse_moe.shared_experts.gate_proj.weight": ("shared_gate", True),
        "block_sparse_moe.shared_experts.up_proj.weight": ("shared_up", True),
        "block_sparse_moe.shared_experts.down_proj.weight": ("shared_down", True),
    }
    EXPERTS = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
    NAMES = {
        "dense": {**NORMS, **KDA, **MLP},
        "kda": {**NORMS, **KDA, **MOE},
        "mla": {**NORMS, **MLA, **MOE},
    }
    #: the recurrence's vectors and the selection bias stay float32
    KEEP_F32 = ("A_log", "dt_bias", "router_bias")

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """HF tensors (``model.layers.<i>.*``) → ``{dense, kda, mla}`` stacks.
        A KDA layer's ``q_proj``, ``k_proj``, ``v_proj`` become one
        ``qkv_proj``, its three torch ``Conv1d`` weights ``(C, 1, k)`` one
        ``conv_w (3 C, k)``, ``f_a_proj`` and ``g_a_proj`` one ``gate_a``;
        ``A_log`` and ``dt_bias`` are flattened. A config with an expert
        share loads only the experts it holds."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        pre = "model.layers.{}.".format
        base = cfg.moe_expert_share_index * cfg.num_experts

        def fetch(i, suffix, our="", transposed=True):
            kind = jnp.float32 if our in self.KEEP_F32 else dtype
            return fetch_weight(weights, pre(i) + suffix, kind, transposed)

        layers: dict = {}
        for group, idxs in self.layer_group_layers().items():
            out = {
                our: stack_tree([fetch(i, suffix, our, tr) for i in idxs])
                for suffix, (our, tr) in self.NAMES[group].items()
            }
            if group != "mla":
                for our in ("A_log", "dt_bias"):  # (1, 1, H, 1) and (H D,)
                    out[our] = jnp.asarray(out[our]).reshape(len(idxs), -1)
                for our, names in (("qkv_proj", ("q_proj", "k_proj", "v_proj")),
                                   ("gate_a", ("f_a_proj", "g_a_proj"))):
                    out[our] = jnp.stack([
                        jnp.concatenate(
                            [fetch(i, f"self_attn.{n}.weight") for n in names], axis=-1
                        )
                        for i in idxs
                    ])
                out["conv_w"] = jnp.stack([
                    jnp.concatenate([
                        jnp.asarray(
                            weights[pre(i) + f"self_attn.{n}_conv1d.weight"], dtype
                        ).reshape(self.kda_width, self.kda_taps)
                        for n in "qkv"
                    ])
                    for i in idxs
                ])
            if group != "dense":
                for our, which in self.EXPERTS.items():
                    out[our] = stack_tree([
                        stack_tree([
                            fetch_weight(
                                weights,
                                pre(i) + f"block_sparse_moe.experts.{base + e}.{which}.weight",
                                dtype,
                            )
                            for e in range(cfg.num_experts)
                        ])
                        for i in idxs
                    ])
            layers[group] = out
        embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
        norm = first_key(weights, "model.norm.weight", "norm.weight")
        return {
            "layers": layers,
            "embed": {"weight": vocab_param(embed, dtype)},
            "final_norm": {"weight": jnp.asarray(norm, dtype)},
            "lm_head": {
                "weight": vocab_param(weights["lm_head.weight"], dtype, transpose=True)
            },
        }

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd, kw, nh, d = cfg.hidden_size, self.kda_width, self.kda_heads, self.kda_dim
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope_d, v_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        e, mi = cfg.num_experts, cfg.moe_intermediate_size
        keys = iter(jax.random.split(key, 32 * cfg.num_hidden_layers + 4))
        norm = lambda n: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

        def kda():
            # a channel's decay a step exp(-A dt): A in 1..16, dt = softplus(
            # dt_bias + small) log-uniform in 0.001..0.1 (models/nemotron_h.py)
            dt0 = jnp.exp(jax.random.uniform(
                next(keys), (kw,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            return {
                "qkv_proj": dense_init(next(keys), hd, 3 * kw, dtype),
                "conv_w": dense_init(next(keys), self.kda_taps, 3 * kw, dtype).T,
                "gate_a": dense_init(next(keys), hd, 2 * d, dtype),
                "f_b": dense_init(next(keys), d, kw, dtype),
                "g_b": dense_init(next(keys), d, kw, dtype),
                "b_proj": dense_init(next(keys), hd, nh, dtype),
                "A_log": jnp.log(jax.random.uniform(next(keys), (nh,), jnp.float32, 1.0, 16.0)),
                "dt_bias": jnp.log(jnp.expm1(dt0)),
                "o_norm": norm(d),
                "o_proj": dense_init(next(keys), kw, hd, dtype),
            }

        def mla():
            return {
                "q_proj": dense_init(next(keys), hd, heads * (nope + rope_d), dtype),
                "kv_a_proj": dense_init(next(keys), hd, rank + rope_d, dtype),
                "kv_a_norm": norm(rank),
                "kv_b_proj": dense_init(next(keys), rank, heads * (nope + v_d), dtype),
                "o_proj": dense_init(next(keys), heads * v_d, hd, dtype),
            }

        def mlp():
            return {
                "gate_proj": dense_init(next(keys), hd, cfg.intermediate_size, dtype),
                "up_proj": dense_init(next(keys), hd, cfg.intermediate_size, dtype),
                "down_proj": dense_init(next(keys), cfg.intermediate_size, hd, dtype),
            }

        def moe():
            kg, ku, kd = jax.random.split(next(keys), 3)
            stack = lambda k_, i, o: jax.vmap(  # noqa: E731
                lambda kk: dense_init(kk, i, o, dtype))(jax.random.split(k_, e))
            return {
                "router": dense_init(next(keys), hd, cfg.router_width, dtype),
                "router_bias": 0.05 * jax.random.normal(
                    next(keys), (cfg.router_width,), jnp.float32),
                "w_gate": stack(kg, hd, mi), "w_up": stack(ku, hd, mi),
                "w_down": stack(kd, mi, hd),
                "shared_gate": dense_init(next(keys), hd, mi, dtype),
                "shared_up": dense_init(next(keys), hd, mi, dtype),
                "shared_down": dense_init(next(keys), mi, hd, dtype),
            }

        make = {
            "dense": lambda: {**kda(), **mlp()},
            "kda": lambda: {**kda(), **moe()},
            "mla": lambda: {**mla(), **moe()},
        }
        per: dict = {}
        for group in self.layer_groups:
            per.setdefault(group, []).append(
                {"norm": norm(hd), "ffn_norm": norm(hd), **make[group]()}
            )
        return {
            "layers": {g: stack_layers(rows) for g, rows in per.items()},
            "embed": {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            },
            "final_norm": {"weight": norm(hd)},
            "lm_head": {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)},
        }
