"""Qwen3-Next decoder (``model_type: qwen3_next``).

Pre-norm residual blocks, no bias anywhere: ``h = h + mixer(norm(h,
input_layernorm))``; ``h = h + moe(norm(h, post_attention_layernorm))``;
logits ``= norm(h, norm) @ lm_head`` (untied). Every layer norm, the final
norm and the per-head Q/K norms are ZERO-CENTRED: ``x_hat * (1 + w)``
(``ops.norms.rms_norm``'s ``offset``); a weight is held as the checkpoint
has it.

- ``gdn`` **Gated DeltaNet** (``ops/kda.py``: the recurrence ``kimi_linear``
  runs, under one decay a HEAD): 16 key heads under 32 value heads of 128
  behind one 4-tap convolution over ``[q, k, v]`` joined at the published
  sizes. Per sequence the layer keeps ``S (Hv, D, D)`` in float32 and the last
  ``taps - 1`` inputs of its convolution in the activation dtype, FLAT
  ``((taps - 1) * (2 Hk + Hv) D,)`` as ``models/granitemoehybrid.py`` keeps
  its tails and says why. The checkpoint's ``in_proj_qkvz`` and
  ``in_proj_ba`` are grouped by key head; the program holds them REGROUPED
  (``qkvz_proj``: columns ``[q, k, v, z]``, each head-major; ``ba_proj``:
  ``[b, a]``), so that the convolution's channels and the output gate are
  slices at whole lane tiles (:func:`regroup_by_key_head`).
- ``attn`` **gated attention**: GQA on ``head_dim``-wide heads (16 query on 2
  K/V heads of 256). ``q_proj`` is twice as wide as the query: per head
  ``[query | gate]``; per-head RMSNorm of ``q`` and ``k``; rotary
  (split-half) on the first ``partial_rotary_factor`` of each head's
  channels; causal softmax at ``head_dim**-0.5``; ``o_proj(attn *
  sigmoid(gate))``. K/V rows keep their heads MERGED on the lane axis,
  ``(…, 1, Hkv * D)``, as ``models/afmoe.py`` says why.
- The feed-forward of every layer: softmax in float32 over all experts, the
  top ``num_experts_per_tok`` renormalised (``ops.moe.mixtral_routing``: the
  same router), routed SwiGLU experts, plus one shared SwiGLU expert times
  ``sigmoid(u . shared_expert_gate)``, one scalar a row. The layer may hold a
  share of the routed experts (``config.Qwen3NextConfig``): it routes over
  all of them and computes its own experts' part; the shared expert whole.

Layers: two stacked groups, ``gdn`` and ``attn``, walked in pattern order
(three ``gdn`` then one ``attn``, periodic) as ``models/kimi_linear.py``
walks its three (``pattern_walk``, ``run_pattern``): a ``lax.scan`` over the
periods whose body is an inner scan over the run of three and the attention
layer. The state pool and the K/V (the engine's page pool in a ragged decode
step, a slot's contiguous rows otherwise) ride the scans' CARRY whole and a
layer is its rank in it; the expert stacks are read where they lie by
``(layer, expert)``. One pipeline stage, no tensor or expert parallelism.
The checkpoint's multi-token-prediction layer (``mtp.*``) is not loaded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import Qwen3NextConfig
from mlx_sharding_tpu.models.base import (
    BaseModel,
    LayerRow,
    dense_init,
    put_row,
    stack_layers,
    take_row,
)
from mlx_sharding_tpu.models.kimi_linear import pattern_walk, run_pattern
from mlx_sharding_tpu.ops import causal_attention, rms_norm
from mlx_sharding_tpu.ops.kda import gdn_mixer
from mlx_sharding_tpu.ops.mamba2 import put_rows, take_rows
from mlx_sharding_tpu.ops.moe import apply_experts, mixtral_routing
from mlx_sharding_tpu.ops.rope import apply_rope, rope_frequencies

GROUPS = ("gdn", "attn")
ONE_STAGE = (
    "pipeline stages are not wired for qwen3_next: the state pool and the "
    "period scan belong to one stage (run it with --num-stages 1)"
)


def regroup_by_key_head(w, key_heads: int, widths: tuple):
    """A checkpoint matrix ``(in, out)`` whose columns are grouped by KEY
    head — for each head ``widths[0]`` columns of the first part, then
    ``widths[1]`` of the second, … — as the program holds it: the parts side
    by side, each head-major. (``in_proj_qkvz``: ``[q D | k D | v r D | z r
    D]`` a head; ``in_proj_ba``: ``[b r | a r]``; ``r`` value heads a key
    head.)"""
    per_head = w.reshape(w.shape[0], key_heads, sum(widths))
    cuts = np.cumsum((0, *widths))
    xp = np if isinstance(w, np.ndarray) else jnp  # host arrays stay on the host
    return xp.concatenate([
        per_head[..., lo:hi].reshape(w.shape[0], -1) for lo, hi in zip(cuts, cuts[1:])
    ], axis=-1)


class Qwen3NextModel(BaseModel):
    #: engines carry a per-slot recurrent state beside the K/V pages
    #: (cache.KVCache.state); whatever rewinds a slot by lowering its offset
    #: cannot serve this model (cache.refuse_recurrent)
    has_recurrent_state = True

    def __init__(self, config: Qwen3NextConfig):
        super().__init__(config)
        if (config.start_layer, config.end_layer) != (0, config.num_hidden_layers):
            raise ValueError(ONE_STAGE)
        self.gdn_dim = config.linear_key_head_dim
        self.key_heads = config.linear_num_key_heads
        self.value_heads = config.linear_num_value_heads
        self.gdn_taps = config.linear_conv_kernel_dim
        #: the convolution's channels: [q, k, v] joined
        self.conv_dim = (2 * self.key_heads + self.value_heads) * self.gdn_dim
        self.kv_dim = config.num_key_value_heads * config.head_dim
        self.scale = config.head_dim ** -0.5
        self.rot_dim = int(config.head_dim * config.partial_rotary_factor)
        self.inv_freq = jnp.asarray(
            rope_frequencies(self.rot_dim, config.rope_theta, None)
        )
        self.layer_groups = config.layer_kinds
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
            "qwen3_next layer groups interleave: use layer_group_layers()"
        )

    def kv_groups(self) -> tuple:
        return ("attn",)

    def state_groups(self) -> tuple:
        return ("gdn",)

    def packed_keep_dense_re(self) -> str | None:
        return r"mlp\.(gate|shared_expert_gate)\.weight$"

    def stage_plan(self, stage_bounds) -> tuple:
        """The one stage's ``(start, end)``: the walk is the model's own
        period scan — asked for by an engine that carries ``cache.state``."""
        if len(stage_bounds) != 1:
            raise ValueError(ONE_STAGE)
        return tuple(stage_bounds[0])

    # -- cache and state ---------------------------------------------------
    def cache_num_heads(self) -> int:
        return 1  # a row's heads are merged on the lane axis

    def cache_head_dim(self):
        return self.kv_dim

    def state_shapes(self, batch: int) -> dict:
        """Per ``gdn`` layer and sequence: {name: (shape after (layer,), dtype)}."""
        return {
            "gdn": ((batch, self.value_heads, self.gdn_dim, self.gdn_dim), jnp.float32),
            "conv": ((batch, (self.gdn_taps - 1) * self.conv_dim), None),
        }

    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        n = {g: self.layer_groups.count(g) for g in GROUPS}
        kv = init_cache(n["attn"], batch, max_seq, 1, self.kv_dim, dtype)
        return kv._replace(state={
            name: jnp.zeros((n["gdn"], *shape), dt or dtype)
            for name, (shape, dt) in self.state_shapes(batch).items()
        })

    # -- the sub-layers ----------------------------------------------------
    def _gdn(self, p, u, state, rank, n_valid, active):
        """The Gated DeltaNet mixer of the layer at ``rank`` of the state
        pool ``{name: (layers, rows, …)}``: its rows of the ``B`` sequences of
        ``u`` are read, advanced and written back where the pool lies (rows
        past ``B``, an engine's scratch row, are neither read nor written).
        Returns ``(out, state)``."""
        nb = u.shape[0]
        tail = take_rows(state["conv"], rank, nb)
        out, pool, tail = gdn_mixer(
            self._linear, p, u, state["gdn"], rank,
            tail.reshape(nb, self.gdn_taps - 1, self.conv_dim), n_valid, active,
            key_heads=self.key_heads, value_heads=self.value_heads,
            head_dim=self.gdn_dim, taps=self.gdn_taps, eps=self.config.rms_norm_eps,
        )
        state = {"gdn": pool, "conv": put_rows(state["conv"], rank, tail.reshape(nb, -1))}
        return out, state

    def _head_norm(self, x, w):
        """Zero-centred RMSNorm over the head dim (the Q/K norm)."""
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.config.rms_norm_eps
        )
        return (x32 * (1.0 + w.astype(jnp.float32))).astype(x.dtype)

    def _partial_rope(self, x, offset):
        r = self.rot_dim
        return jnp.concatenate(
            [apply_rope(x[..., :r], self.inv_freq, offset), x[..., r:]], axis=-1
        )

    def _attn(self, p, u, k_buf, v_buf, offset, paged):
        """``k_buf`` / ``v_buf``: the layer's contiguous rows ``(B, S, 1, Hkv
        * D)``, or with ``paged`` (the engine's ``(attn_fn, done)`` over the
        pool where it lies) unused. Returns ``(out, k_buf, v_buf)``."""
        cfg = self.config
        b, t, _ = u.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        with jax.named_scope("mst.attn.qkv"):
            qg = self._linear(u, p["q_proj"]).reshape(b, t, hq, 2 * d)
            k = self._linear(u, p["k_proj"]).reshape(b, t, hkv, d)
            v = self._linear(u, p["v_proj"]).reshape(b, t, 1, hkv * d)
        with jax.named_scope("mst.attn.gate"):
            q, gate = qg[..., :d], qg[..., d:]  # a head's [query | gate]
        with jax.named_scope("mst.attn.qk_norm"):
            q = self._head_norm(q, p["q_norm"])
            k = self._head_norm(k, p["k_norm"])
        with jax.named_scope("mst.attn.qkv"):
            q = self._partial_rope(q, offset)
            k = self._partial_rope(k, offset).reshape(b, t, 1, hkv * d)
        if paged is not None:
            attn_fn, done = paged
            attn = attn_fn(q, k, v, kv_heads=hkv)
            k_buf, v_buf = done["k"], done["v"]
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
            split = lambda z: z.reshape(*z.shape[:2], hkv, d)  # noqa: E731
            attn = causal_attention(q, split(k_buf), split(v_buf), offset, self.scale)
        with jax.named_scope("mst.attn.gate"):
            attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)
        with jax.named_scope("mst.attn.qkv"):
            out = self._linear(attn.reshape(b, t, hq * d), p["o_proj"])
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
        weights, idx = mixtral_routing(flat, p["router"], cfg.num_experts_per_tok)
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
        with jax.named_scope("mst.moe.shared_gate"):
            # one scalar a row: sigmoid(u . shared_expert_gate), float32
            open_ = jax.nn.sigmoid(jnp.sum(
                flat.astype(jnp.float32) * p["shared_expert_gate"].astype(jnp.float32),
                axis=-1, keepdims=True,
            ))
            return (routed + open_.astype(shared.dtype) * shared).reshape(b, t, hidden)

    # -- the layer walk ----------------------------------------------------
    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None, *, state=None, n_valid=None, active=None, plan=None,
        stage_axis=None, paged_attn=None,
    ):
        """All layers, one scan over the pattern's periods. ``state`` holds
        every ``gdn`` layer's rows of the ``B`` sequences of ``h`` (and, from
        an engine's ragged decode, a scratch row past them). ``k`` / ``v``:
        every attention layer's contiguous rows ``(L, B, S, 1, Hkv * D)`` —
        or, with ``paged_attn``, the engine's page pool; either is carried
        whole. ``mask``, ``plan`` and ``stage_axis`` are unused: one stage
        has no padding layer. Returns ``(h, k, v, state)``."""
        if tp_axis is not None or ep_axis is not None:
            raise ValueError(
                "tensor and expert parallelism are not wired for qwen3_next"
            )
        eps = self.config.rms_norm_eps

        def layer(group, rank, carry):
            """The layer at row ``rank`` of ``group``'s stacks (may be traced)."""
            h, k, v, state = carry
            p = LayerRow(layer_params[group], rank)
            u = rms_norm(h, p["norm"], eps, offset=1.0)
            if group == "gdn":
                out, state = self._gdn(p, u, state, rank, n_valid, active)
            elif paged_attn is not None:
                out, k, v = self._attn(p, u, k, v, offset, paged_attn(k, v, layer=rank))
            else:
                with jax.named_scope("mst.kv_pool.regroup"):
                    k_l, v_l = take_row(k, rank), take_row(v, rank)
                out, k_l, v_l = self._attn(p, u, k_l, v_l, offset, None)
                with jax.named_scope("mst.kv_pool.regroup"):
                    k, v = put_row(k, rank, k_l), put_row(v, rank, v_l)
            h = h + out.astype(h.dtype)
            u = rms_norm(h, p["ffn_norm"], eps, offset=1.0)
            out = self._moe(p, layer_params[group], rank, u)
            return h + out.astype(h.dtype), k, v, state

        return run_pattern(self.walk, GROUPS, layer, (h, k, v, state))

    # -- embed / head ------------------------------------------------------
    def head_input(self, params, h):
        return rms_norm(
            h, params["final_norm"]["weight"], self.config.rms_norm_eps, offset=1.0
        )

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
    SHARED = {
        "input_layernorm.weight": ("norm", False),
        "post_attention_layernorm.weight": ("ffn_norm", False),
        "mlp.gate.weight": ("router", True),
        "mlp.shared_expert.gate_proj.weight": ("shared_gate", True),
        "mlp.shared_expert.up_proj.weight": ("shared_up", True),
        "mlp.shared_expert.down_proj.weight": ("shared_down", True),
        "mlp.shared_expert_gate.weight": ("shared_expert_gate", False),  # (1, hidden)
    }
    NAMES = {
        "gdn": {
            **SHARED,
            "linear_attn.in_proj_qkvz.weight": ("qkvz_proj", True),
            "linear_attn.in_proj_ba.weight": ("ba_proj", True),
            "linear_attn.conv1d.weight": ("conv_w", False),  # (C, 1, taps)
            "linear_attn.A_log": ("A_log", False),
            "linear_attn.dt_bias": ("dt_bias", False),
            "linear_attn.norm.weight": ("o_norm", False),
            "linear_attn.out_proj.weight": ("o_proj", True),
        },
        "attn": {
            **SHARED,
            "self_attn.q_proj.weight": ("q_proj", True),
            "self_attn.k_proj.weight": ("k_proj", True),
            "self_attn.v_proj.weight": ("v_proj", True),
            "self_attn.o_proj.weight": ("o_proj", True),
            "self_attn.q_norm.weight": ("q_norm", False),
            "self_attn.k_norm.weight": ("k_norm", False),
        },
    }
    EXPERTS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    #: the recurrence's vectors stay float32
    KEEP_F32 = ("A_log", "dt_bias")

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """HF tensors (``model.layers.<i>.*``) → ``{gdn, attn}`` stacks. A
        ``gdn`` layer's ``in_proj_qkvz`` and ``in_proj_ba`` are regrouped from
        the checkpoint's key-head groups (:func:`regroup_by_key_head`), its
        torch ``Conv1d`` weight ``(C, 1, k)`` becomes ``(C, k)``, the shared
        expert's gate ``(1, hidden)`` a vector. A config with an expert share
        loads only the experts it holds; ``mtp.*`` is never read."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        pre = "model.layers.{}.".format
        base = cfg.moe_expert_share_index * cfg.num_experts
        d, ratio = self.gdn_dim, self.value_heads // self.key_heads

        def one(i, suffix, our, transposed):
            w = fetch_weight(
                weights, pre(i) + suffix,
                jnp.float32 if our in self.KEEP_F32 else dtype, transposed,
            )
            if our == "qkvz_proj":
                return regroup_by_key_head(w, self.key_heads, (d, d, ratio * d, ratio * d))
            if our == "ba_proj":
                return regroup_by_key_head(w, self.key_heads, (ratio, ratio))
            if our == "conv_w":
                return w.reshape(self.conv_dim, self.gdn_taps)
            if our == "shared_expert_gate":
                return w.reshape(-1)
            return w

        layers: dict = {}
        for group, idxs in self.layer_group_layers().items():
            out = {
                our: stack_tree([one(i, suffix, our, tr) for i in idxs])
                for suffix, (our, tr) in self.NAMES[group].items()
            }
            for our, which in self.EXPERTS.items():
                out[our] = stack_tree([
                    stack_tree([
                        fetch_weight(
                            weights, pre(i) + f"mlp.experts.{base + e}.{which}.weight", dtype
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
        hd, d = cfg.hidden_size, cfg.head_dim
        hq, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
        e, mi = cfg.num_experts, cfg.moe_intermediate_size
        si = cfg.shared_expert_intermediate_size
        vw = self.value_heads * self.gdn_dim
        keys = iter(jax.random.split(key, 32 * cfg.num_hidden_layers + 4))
        # a zero-centred norm's weight lies about 0, the gated norm's about 1
        small = lambda n: (  # noqa: E731
            0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

        def gdn():
            # a head's decay a step exp(-A dt): A in 1..16, dt = softplus(
            # dt_bias + small) log-uniform in 0.001..0.1 (models/nemotron_h.py)
            dt0 = jnp.exp(jax.random.uniform(
                next(keys), (self.value_heads,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            return {
                "qkvz_proj": dense_init(next(keys), hd, self.conv_dim + vw, dtype),
                "ba_proj": dense_init(next(keys), hd, 2 * self.value_heads, dtype),
                "conv_w": dense_init(next(keys), self.gdn_taps, self.conv_dim, dtype).T,
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), (self.value_heads,), jnp.float32, 1.0, 16.0)),
                "dt_bias": jnp.log(jnp.expm1(dt0)),
                "o_norm": 1.0 + small(self.gdn_dim),
                "o_proj": dense_init(next(keys), vw, hd, dtype),
            }

        def attn():
            return {
                "q_proj": dense_init(next(keys), hd, 2 * hq * d, dtype),
                "k_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "v_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "o_proj": dense_init(next(keys), hq * d, hd, dtype),
                "q_norm": small(d), "k_norm": small(d),
            }

        def moe():
            kg, ku, kd = jax.random.split(next(keys), 3)
            stack = lambda k_, i, o: jax.vmap(  # noqa: E731
                lambda kk: dense_init(kk, i, o, dtype))(jax.random.split(k_, e))
            return {
                "router": dense_init(next(keys), hd, cfg.router_width, dtype),
                "w_gate": stack(kg, hd, mi), "w_up": stack(ku, hd, mi),
                "w_down": stack(kd, mi, hd),
                "shared_gate": dense_init(next(keys), hd, si, dtype),
                "shared_up": dense_init(next(keys), hd, si, dtype),
                "shared_down": dense_init(next(keys), si, hd, dtype),
                "shared_expert_gate": dense_init(next(keys), hd, 1, dtype)[:, 0],
            }

        make = {"gdn": gdn, "attn": attn}
        per: dict = {}
        for group in self.layer_groups:
            per.setdefault(group, []).append(
                {"norm": small(hd), "ffn_norm": small(hd), **make[group](), **moe()}
            )
        return {
            "layers": {g: stack_layers(rows) for g, rows in per.items()},
            "embed": {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            },
            "final_norm": {"weight": small(hd)},
            "lm_head": {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)},
        }
