"""Gemma-2 decoder.

Capability parity: shard/server/model/gemma2.py — tied embeddings so the
embedding table is needed on the first AND last stage (ref gemma2.py:23-24,
sanitize :98-99), embedding scaled by sqrt(hidden) (ref :42-43), final logit
softcapping (ref :80-84). Architecture specifics beyond the reference's
borrowed blocks (SURVEY §2.2): zero-centered (1+w) RMSNorm, four norms per
layer (pre/post attention, pre/post feedforward), attention-logit
softcapping, alternating sliding/global attention (window on even layers),
GeGLU MLP, query_pre_attn_scalar attention scale.

The alternating window runs inside the single layer scan: the layer index is
scanned alongside the stacked params and selects window-vs-global as a traced
scalar — no per-layer Python modules, no unrolling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, write_layer_kv
from mlx_sharding_tpu.config import Gemma2Config
from mlx_sharding_tpu.models.base import BaseModel, dense_init, stack_layers
from mlx_sharding_tpu.ops import apply_rope, causal_attention, rms_norm, rope_frequencies

_GLOBAL_WINDOW = 1 << 30  # "no window" encoded as a huge traced window


class Gemma2Model(BaseModel):
    supports_packed = True
    supports_sp = True  # sp_layer below carries the window/softcap opts

    def __init__(self, config: Gemma2Config):
        super().__init__(config)
        self.inv_freq = jnp.asarray(
            rope_frequencies(config.head_dim, config.rope_theta, config.rope_scaling)
        )
        self.scale = config.query_pre_attn_scalar**-0.5

    # ------------------------------------------------------------------
    def _window(self, layer_idx):
        # sliding window on even layers, global on odd (HF Gemma-2 layout)
        return jnp.where(
            layer_idx % 2 == 0, self.config.sliding_window, _GLOBAL_WINDOW
        )

    @jax.named_scope("mst.attn.qkv")
    def layer_attn_inputs(self, p, h, offset):
        """Pre-attention half: zero-centered norm + QKV + RoPE. Head counts
        derive from the projection shards, so the same code runs the full
        model and any tp slice (heads split over tp)."""
        cfg = self.config
        b, t, _ = h.shape
        d = cfg.head_dim
        r = rms_norm(h, p["input_norm"], cfg.rms_norm_eps, offset=1.0)
        q = self._linear(r, p["q_proj"]).reshape(b, t, -1, d)
        k = self._linear(r, p["k_proj"]).reshape(b, t, -1, d)
        v = self._linear(r, p["v_proj"]).reshape(b, t, -1, d)
        q = apply_rope(q, self.inv_freq, offset)
        k = apply_rope(k, self.inv_freq, offset)
        return q, k, v

    def layer_finish(self, p, h, attn, tp_axis=None):
        """Post-attention half: O projection into the POST-attention norm
        (sandwich norms), then GeGLU into the post-ffw norm."""
        cfg = self.config
        b, t, _ = h.shape
        eps = cfg.rms_norm_eps
        with jax.named_scope("mst.attn.core"):
            attn_out = self._linear(attn.reshape(b, t, -1), p["o_proj"])
            if tp_axis is not None:
                # the post-attention norm is NONLINEAR: partial row-parallel
                # products must be summed BEFORE it, unlike Llama's plain
                # residual
                attn_out = jax.lax.psum(attn_out, tp_axis)
            h = h + rms_norm(attn_out, p["post_attn_norm"], eps, offset=1.0)

        r = rms_norm(h, p["pre_ffw_norm"], eps, offset=1.0)
        with jax.named_scope("mst.mlp.dense"):
            ff = self._linear(
                jax.nn.gelu(self._linear(r, p["gate_proj"]), approximate=True)
                * self._linear(r, p["up_proj"]),
                p["down_proj"],
            )
            if tp_axis is not None:
                ff = jax.lax.psum(ff, tp_axis)
            return h + rms_norm(ff, p["post_ffw_norm"], eps, offset=1.0)

    def sp_layer(self, p, h, offset, attn_fn, group=None):
        """Sequence-parallel layer: the injected attention gets Gemma-2's
        logit softcap and the layer's sliding/global window — the ring
        backend skips K/V blocks entirely behind a window (VERDICT r4 #4:
        window-aware ring block skipping)."""
        cfg = self.config
        q, k, v = self.layer_attn_inputs(p, h, offset)
        attn = attn_fn(
            q, k, v,
            logit_softcap=cfg.attn_logit_softcapping,
            sliding_window=self._window(p["layer_idx"]),
        )
        return self.layer_finish(p, h, attn), k, v

    def _layer(self, h, p, k_buf, v_buf, offset, layer_idx, tp_axis=None):
        cfg = self.config
        q, k, v = self.layer_attn_inputs(p, h, offset)
        k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
        attn = causal_attention(
            q, k_buf, v_buf, offset, self.scale,
            logit_softcap=cfg.attn_logit_softcapping,
            sliding_window=self._window(layer_idx),
        )
        return self.layer_finish(p, h, attn, tp_axis), k_buf, v_buf

    def run_layers(self, layer_params, h, k, v, offset, mask=None, tp_axis=None):
        # The GLOBAL layer index travels inside the param stack
        # ("layer_idx", added by map_weights/init_params): window alternation
        # follows it, so arbitrary stage slices — including the fused SPMD
        # engine's per-device shards, which can't see start_layer — stay
        # consistent with the full model.
        from mlx_sharding_tpu.models.base import scan_layers

        def body(h, p, k_buf, v_buf):
            return self._layer(h, p, k_buf, v_buf, offset, p["layer_idx"], tp_axis)

        return scan_layers(body, h, layer_params, k, v, mask)

    def tp_layer_axes(self) -> dict:
        return {
            "input_norm": None, "post_attn_norm": None, "pre_ffw_norm": None,
            "post_ffw_norm": None, "layer_idx": None,
            "q_proj": 1, "k_proj": 1, "v_proj": 1, "o_proj": 0,
            "gate_proj": 1, "up_proj": 1, "down_proj": 0,
        }

    def embed_transform(self, h):
        # embedding scaled by sqrt(hidden) (ref gemma2.py:42-43)
        return h * jnp.asarray(self.config.hidden_size**0.5, h.dtype)

    def head_input(self, params, h):
        return rms_norm(
            h, params["final_norm"]["weight"], self.config.rms_norm_eps, offset=1.0
        )

    def head_transform(self, logits):
        cap = self.config.final_logit_softcapping
        if cap:  # ref gemma2.py:80-84
            logits = cap * jnp.tanh(logits / cap)
        return logits

    def head_is_tied(self) -> bool:
        return True  # always projects through the embedding (ref :23-24)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        cfg = self.config
        h = self.embed(params, x) if cfg.is_first_stage else x
        offset = cache.offset
        h, k, v = self.run_layers(params["layers"], h, cache.k, cache.v, offset)
        cache = KVCache(k=k, v=v, offset=offset)
        cache = advance(cache, x.shape[1] if n_valid is None else n_valid)
        if cfg.is_last_stage:
            return self.apply_head(params, h), cache
        return h, cache

    # ------------------------------------------------------------------
    HF_LAYER_MAP = {
        "input_layernorm.weight": ("input_norm", False),
        "post_attention_layernorm.weight": ("post_attn_norm", False),
        "pre_feedforward_layernorm.weight": ("pre_ffw_norm", False),
        "post_feedforward_layernorm.weight": ("post_ffw_norm", False),
        "self_attn.q_proj.weight": ("q_proj", True),
        "self_attn.k_proj.weight": ("k_proj", True),
        "self_attn.v_proj.weight": ("v_proj", True),
        "self_attn.o_proj.weight": ("o_proj", True),
        "mlp.gate_proj.weight": ("gate_proj", True),
        "mlp.up_proj.weight": ("up_proj", True),
        "mlp.down_proj.weight": ("down_proj", True),
    }

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        from mlx_sharding_tpu.loading import collect_layer_stack, first_key, vocab_param

        cfg = self.config
        layers = collect_layer_stack(weights, cfg, self.HF_LAYER_MAP, dtype)
        layers["layer_idx"] = jnp.arange(cfg.start_layer, cfg.end_layer, dtype=jnp.int32)
        params = {"layers": layers}
        if cfg.needs_embed:
            embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
            params["embed"] = {"weight": vocab_param(embed, dtype)}
        if cfg.needs_head:
            norm = first_key(weights, "model.norm.weight", "norm.weight")
            params["final_norm"] = {"weight": jnp.asarray(norm, dtype)}
        return params

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd, hq, hkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        inter, nl = cfg.intermediate_size, cfg.num_local_layers
        keys = iter(jax.random.split(key, 8 * nl + 4))

        def layer():
            return {
                "input_norm": jnp.zeros((hd,), dtype),
                "post_attn_norm": jnp.zeros((hd,), dtype),
                "pre_ffw_norm": jnp.zeros((hd,), dtype),
                "post_ffw_norm": jnp.zeros((hd,), dtype),
                "q_proj": dense_init(next(keys), hd, hq * d, dtype),
                "k_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "v_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "o_proj": dense_init(next(keys), hq * d, hd, dtype),
                "gate_proj": dense_init(next(keys), hd, inter, dtype),
                "up_proj": dense_init(next(keys), hd, inter, dtype),
                "down_proj": dense_init(next(keys), inter, hd, dtype),
            }

        layers = stack_layers([layer() for _ in range(nl)])
        layers["layer_idx"] = jnp.arange(cfg.start_layer, cfg.end_layer, dtype=jnp.int32)
        params = {"layers": layers}
        if cfg.needs_embed:
            params["embed"] = {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            }
        if cfg.needs_head:
            params["final_norm"] = {"weight": jnp.zeros((hd,), dtype)}
        return params
