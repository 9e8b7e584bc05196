"""Olmo Hybrid decoder (``model_type: olmo_hybrid``).

Olmo 2's REORDERED norm around every sub-layer, no bias anywhere: the
sub-layer reads the residual stream un-normed and its output is normed before
it is added, ``h = x + norm(mixer(x), post_attention_layernorm)``; ``y = h +
norm(mlp(h), post_feedforward_layernorm)``; logits ``= norm(y, norm) @
lm_head`` (untied). ``norm(x, w) = x * rsqrt(mean x^2 + eps) * w``, a plain
weight. ``config.layer_types`` picks each layer's mixer; every layer has the
dense SwiGLU MLP ``down(silu(gate h) * up h)``.

- ``gdn`` **Gated DeltaNet with negative eigenvalues** (``ops/kda.py``, the
  recurrence ``qwen3_next`` runs): as many key heads as value heads, keys
  ``linear_key_head_dim`` wide and values ``linear_value_head_dim`` — a
  RECTANGULAR state ``(Dk, Dv)`` a head — and ``beta = 2 sigmoid(b)`` under
  ``linear_allow_neg_eigval``. The checkpoint has ``q/k/v/g_proj``, three
  depthwise convolutions and ``b_proj`` / ``a_proj`` apart; the program holds
  them JOINED as the mixer reads them (``qkvz_proj``: columns ``[q, k, v,
  g]``; ``conv_w`` over ``[q, k, v]``; ``ba_proj``: ``[b, a]``): a depthwise
  convolution over joined channels is the three side by side. Per sequence the
  layer keeps its state in float32 with :func:`ops.kda.lane_pack` heads side
  by side on the lanes, ``(Hv / P, Dk, P Dv)`` — at 192-wide values two heads
  are three whole lane tiles where one would lie padded to 256 — and the last
  ``taps - 1`` inputs of its convolution in the activation dtype, flat, as
  ``models/qwen3_next.py`` keeps them.
- ``attn`` **multi-head attention behind a full-width QK norm**: ``q =
  norm(x Wq, q_norm)``, ``k = norm(x Wk, k_norm)`` over ALL ``heads *
  head_dim`` channels before the split into heads; NO positional encoding
  (``rope_parameters.rope_theta`` is null, and the config refuses a number);
  causal softmax at ``head_dim**-0.5``. K/V rows keep their heads
  MERGED on the lane axis, ``(…, 1, Hkv * D)``, as ``models/afmoe.py`` says
  why.

Layers: two stacked groups walked in pattern order as ``models/qwen3_next.py``
walks its two (``pattern_walk``, ``run_pattern``): the state pool and the K/V
ride the scans' carry whole and a layer is its rank in it. One pipeline stage,
no tensor parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import OlmoHybridConfig
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
from mlx_sharding_tpu.ops.kda import gdn_mixer, lane_pack
from mlx_sharding_tpu.ops.mamba2 import put_rows, take_rows

GROUPS = ("gdn", "attn")
ONE_STAGE = (
    "pipeline stages are not wired for olmo_hybrid: the state pool and the "
    "period scan belong to one stage (run it with --num-stages 1)"
)


class OlmoHybridModel(BaseModel):
    #: engines carry a per-slot recurrent state beside the K/V pages
    #: (cache.KVCache.state); whatever rewinds a slot by lowering its offset
    #: cannot serve this model (cache.refuse_recurrent)
    has_recurrent_state = True

    def __init__(self, config: OlmoHybridConfig):
        super().__init__(config)
        if (config.start_layer, config.end_layer) != (0, config.num_hidden_layers):
            raise ValueError(ONE_STAGE)
        self.key_dim = config.linear_key_head_dim
        self.value_dim = config.linear_value_head_dim
        self.key_heads = config.linear_num_key_heads
        self.value_heads = config.linear_num_value_heads
        self.gdn_taps = config.linear_conv_kernel_dim
        #: the convolution's channels: [q, k, v] joined
        self.conv_dim = (
            2 * self.key_heads * self.key_dim + self.value_heads * self.value_dim
        )
        #: heads of the state side by side on the pool's lane axis
        self.state_pack = lane_pack(self.value_heads, self.value_dim)
        self.beta_scale = 2.0 if config.linear_allow_neg_eigval else 1.0
        self.kv_dim = config.num_key_value_heads * config.head_dim
        self.scale = config.head_dim ** -0.5
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
            "olmo_hybrid layer groups interleave: use layer_group_layers()"
        )

    def kv_groups(self) -> tuple:
        return ("attn",)

    def state_groups(self) -> tuple:
        return ("gdn",)

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
        p = self.state_pack
        return {
            "gdn": (
                (batch, self.value_heads // p, self.key_dim, p * self.value_dim),
                jnp.float32,
            ),
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
    def _gdn(self, p, x, state, rank, n_valid, active):
        """The Gated DeltaNet mixer of the layer at ``rank`` of the state
        pool ``{name: (layers, rows, …)}``: its rows of the ``B`` sequences of
        ``x`` are read, advanced and written back where the pool lies (rows
        past ``B``, an engine's scratch row, are neither read nor written).
        Returns ``(out, state)``."""
        nb = x.shape[0]
        tail = take_rows(state["conv"], rank, nb)
        out, pool, tail = gdn_mixer(
            self._linear, p, x, state["gdn"], rank,
            tail.reshape(nb, self.gdn_taps - 1, self.conv_dim), n_valid, active,
            key_heads=self.key_heads, value_heads=self.value_heads,
            head_dim=self.key_dim, value_dim=self.value_dim,
            beta_scale=self.beta_scale, taps=self.gdn_taps,
            eps=self.config.rms_norm_eps,
        )
        state = {"gdn": pool, "conv": put_rows(state["conv"], rank, tail.reshape(nb, -1))}
        return out, state

    def _attn(self, p, x, k_buf, v_buf, offset, paged):
        """``k_buf`` / ``v_buf``: the layer's contiguous rows ``(B, S, 1, Hkv
        * D)``, or with ``paged`` (the engine's ``(attn_fn, done)`` over the
        pool where it lies) unused. Returns ``(out, k_buf, v_buf)``."""
        cfg = self.config
        b, t, _ = x.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        eps = cfg.rms_norm_eps
        with jax.named_scope("mst.attn.qkv"):
            q = self._linear(x, p["q_proj"])
            k = self._linear(x, p["k_proj"])
            v = self._linear(x, p["v_proj"]).reshape(b, t, 1, hkv * d)
        with jax.named_scope("mst.attn.qk_norm"):
            # over the whole projection, before the split into heads
            q = rms_norm(q, p["q_norm"], eps).reshape(b, t, hq, d)
            k = rms_norm(k, p["k_norm"], eps).reshape(b, t, 1, hkv * d)
        if paged is not None:
            attn_fn, done = paged
            attn = attn_fn(q, k, v, kv_heads=hkv)
            k_buf, v_buf = done["k"], done["v"]
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
            split = lambda z: z.reshape(*z.shape[:2], hkv, d)  # noqa: E731
            attn = causal_attention(q, split(k_buf), split(v_buf), offset, self.scale)
        with jax.named_scope("mst.attn.qkv"):
            out = self._linear(attn.reshape(b, t, hq * d), p["o_proj"])
        return out, k_buf, v_buf

    def _mlp(self, p, x):
        with jax.named_scope("mst.mlp.dense"):
            return self._linear(
                jax.nn.silu(self._linear(x, p["gate_proj"])) * self._linear(x, p["up_proj"]),
                p["down_proj"],
            )

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
                "tensor and expert parallelism are not wired for olmo_hybrid"
            )
        eps = self.config.rms_norm_eps

        def normed(out, w, h):
            with jax.named_scope("mst.norm"):
                return h + rms_norm(out.astype(h.dtype), w, eps)

        def layer(group, rank, carry):
            """The layer at row ``rank`` of ``group``'s stacks (may be traced)."""
            h, k, v, state = carry
            p = LayerRow(layer_params[group], rank)
            if group == "gdn":
                out, state = self._gdn(p, h, state, rank, n_valid, active)
            elif paged_attn is not None:
                out, k, v = self._attn(p, h, k, v, offset, paged_attn(k, v, layer=rank))
            else:
                with jax.named_scope("mst.kv_pool.regroup"):
                    k_l, v_l = take_row(k, rank), take_row(v, rank)
                out, k_l, v_l = self._attn(p, h, k_l, v_l, offset, None)
                with jax.named_scope("mst.kv_pool.regroup"):
                    k, v = put_row(k, rank, k_l), put_row(v, rank, v_l)
            h = normed(out, p["mixer_norm"], h)
            return normed(self._mlp(p, h), p["ffn_norm"], h), k, v, state

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
    SHARED = {
        "post_attention_layernorm.weight": ("mixer_norm", False),
        "post_feedforward_layernorm.weight": ("ffn_norm", False),
        "mlp.gate_proj.weight": ("gate_proj", True),
        "mlp.up_proj.weight": ("up_proj", True),
        "mlp.down_proj.weight": ("down_proj", True),
    }
    NAMES = {
        "gdn": {
            **SHARED,
            "linear_attn.A_log": ("A_log", False),
            "linear_attn.dt_bias": ("dt_bias", False),
            "linear_attn.o_norm.weight": ("o_norm", False),
            "linear_attn.o_proj.weight": ("o_proj", True),
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
    #: a ``gdn`` layer's joined leaves: ours -> the checkpoint's parts in
    #: column order (``linear_attn.<part>.weight``)
    JOINED = {
        "qkvz_proj": ("q_proj", "k_proj", "v_proj", "g_proj"),
        "ba_proj": ("b_proj", "a_proj"),
        "conv_w": ("q_conv1d", "k_conv1d", "v_conv1d"),
    }
    #: the recurrence's vectors stay float32
    KEEP_F32 = ("A_log", "dt_bias")

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """HF tensors (``model.layers.<i>.*``) → ``{gdn, attn}`` stacks. A
        ``gdn`` layer's ``q/k/v/g_proj`` are joined along their outputs into
        ``qkvz_proj``, ``b_proj`` / ``a_proj`` into ``ba_proj``, and its three
        torch ``Conv1d`` weights ``(C, 1, taps)`` into ``conv_w (2 Hk Dk + Hv
        Dv, taps)``."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        pre = "model.layers.{}.".format

        def one(i, suffix, our, transposed):
            return fetch_weight(
                weights, pre(i) + suffix,
                jnp.float32 if our in self.KEEP_F32 else dtype, transposed,
            )

        def joined(i, our):
            conv = our == "conv_w"
            parts = [
                one(i, f"linear_attn.{part}.weight", our, not conv)
                for part in self.JOINED[our]
            ]
            if conv:
                parts = [w.reshape(-1, self.gdn_taps) for w in parts]
            xp = np if isinstance(parts[0], np.ndarray) else jnp
            return xp.concatenate(parts, axis=0 if conv else -1)

        layers: dict = {}
        for group, idxs in self.layer_group_layers().items():
            out = {
                our: stack_tree([one(i, suffix, our, tr) for i in idxs])
                for suffix, (our, tr) in self.NAMES[group].items()
            }
            if group == "gdn":
                for our in self.JOINED:
                    out[our] = stack_tree([joined(i, our) for i in idxs])
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
        hd, mi = cfg.hidden_size, cfg.intermediate_size
        qw, kvw = cfg.num_attention_heads * cfg.head_dim, self.kv_dim
        vw = self.value_heads * self.value_dim
        keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 4))
        near_one = lambda n: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
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
                "o_norm": near_one(self.value_dim),
                "o_proj": dense_init(next(keys), vw, hd, dtype),
            }

        def attn():
            return {
                "q_proj": dense_init(next(keys), hd, qw, dtype),
                "k_proj": dense_init(next(keys), hd, kvw, dtype),
                "v_proj": dense_init(next(keys), hd, kvw, dtype),
                "o_proj": dense_init(next(keys), qw, hd, dtype),
                "q_norm": near_one(qw), "k_norm": near_one(kvw),
            }

        def mlp():
            return {
                "gate_proj": dense_init(next(keys), hd, mi, dtype),
                "up_proj": dense_init(next(keys), hd, mi, dtype),
                "down_proj": dense_init(next(keys), mi, hd, dtype),
            }

        make = {"gdn": gdn, "attn": attn}
        per: dict = {}
        for group in self.layer_groups:
            per.setdefault(group, []).append({
                "mixer_norm": near_one(hd), "ffn_norm": near_one(hd),
                **make[group](), **mlp(),
            })
        return {
            "layers": {g: stack_layers(rows) for g, rows in per.items()},
            "embed": {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            },
            "final_norm": {"weight": near_one(hd)},
            "lm_head": {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)},
        }
