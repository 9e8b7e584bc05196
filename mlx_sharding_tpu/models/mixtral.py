"""Mixtral (sparse MoE Llama) decoder.

BASELINE.json config #4 names Mixtral-8x7B with "expert routing inside
stage" — experts stay stage-local exactly as the reference treats MoE
(SURVEY §2.3 "EP": fused and replicated within the owning stage; the
reference itself only ships DeepSeek-V2's MoE, deepseek_v2.py:101-112).
Attention/norm structure is Llama's; the MLP is a top-2 router over 8 SwiGLU
experts (HF semantics: softmax over all logits → top-k → renormalize).
Expert weights are stacked (L, E, H, I) so the layer scan + expert
scan/gather dispatch (ops/moe.py) run with static shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, write_layer_kv
from mlx_sharding_tpu.config import MixtralConfig
from mlx_sharding_tpu.models.base import BaseModel, dense_init, stack_layers
from mlx_sharding_tpu.ops import apply_rope, causal_attention, rms_norm, rope_frequencies
from mlx_sharding_tpu.ops.moe import apply_experts, mixtral_routing


class MixtralModel(BaseModel):
    # attention projections and the (E, …) expert stacks may stay 4-bit
    # packed; the router loads dense (fp32 routing matmul on a tiny weight)
    supports_packed = True
    supports_sp = True  # sp_layer below (window-aware, replicated MoE MLP)

    def packed_keep_dense_re(self) -> str | None:
        return r"block_sparse_moe\.gate\.weight$"

    def __init__(self, config: MixtralConfig):
        super().__init__(config)
        self.inv_freq = jnp.asarray(
            rope_frequencies(config.head_dim, config.rope_theta, config.rope_scaling)
        )
        self.scale = config.head_dim**-0.5

    # ------------------------------------------------------------------
    @jax.named_scope("mst.attn.qkv")
    def layer_attn_inputs(self, p, h, offset):
        """Pre-attention half: norm + QKV + RoPE. Head counts derive from
        the projection shards, so the same code runs the full model and any
        tp slice (heads split over tp)."""
        cfg = self.config
        b, t, _ = h.shape
        d = cfg.head_dim
        r = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
        q = self._linear(r, p["q_proj"]).reshape(b, t, -1, d)
        k = self._linear(r, p["k_proj"]).reshape(b, t, -1, d)
        v = self._linear(r, p["v_proj"]).reshape(b, t, -1, d)
        q = apply_rope(q, self.inv_freq, offset)
        k = apply_rope(k, self.inv_freq, offset)
        return q, k, v

    def layer_finish(self, p, h, attn, tp_axis=None, ep_axis=None):
        """Post-attention half: O projection + routed top-k expert MLP."""
        cfg = self.config
        b, t, hidden = h.shape
        with jax.named_scope("mst.attn.core"):
            attn_out = self._linear(attn.reshape(b, t, -1), p["o_proj"])
            if tp_axis is not None:
                attn_out = jax.lax.psum(attn_out, tp_axis)
            h = h + attn_out

        r = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
        flat = r.reshape(b * t, hidden)
        weights, idx = mixtral_routing(flat, p["router"], cfg.num_experts_per_tok)
        moe = apply_experts(
            flat, weights, idx, p["w_gate"], p["w_up"], p["w_down"],
            ep_axis=ep_axis, group_size=self._gs, bits=self._bits,
        )
        if tp_axis is not None and ep_axis is None:
            # experts shard their intermediate dim over tp — the down-proj
            # outputs are partial products. Under tp x ep the expert stacks
            # shard over ep instead (ep overrides tp in the engine's merge)
            # and apply_experts' internal ep psum already made them full.
            moe = jax.lax.psum(moe, tp_axis)
        return h + moe.reshape(b, t, hidden)

    def sp_layer(self, p, h, offset, attn_fn, group=None):
        """Sequence-parallel layer: the injected attention gets Mixtral's
        (optional) sliding window; the MoE MLP runs replicated per sp
        device on its local T/S rows."""
        q, k, v = self.layer_attn_inputs(p, h, offset)
        attn = attn_fn(q, k, v, sliding_window=self.config.sliding_window)
        return self.layer_finish(p, h, attn), k, v

    def _layer(self, h, p, k_buf, v_buf, offset, tp_axis=None, ep_axis=None):
        q, k, v = self.layer_attn_inputs(p, h, offset)
        k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
        attn = causal_attention(
            q, k_buf, v_buf, offset, self.scale,
            sliding_window=self.config.sliding_window,
        )
        return self.layer_finish(p, h, attn, tp_axis, ep_axis), k_buf, v_buf

    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None,
    ):
        from mlx_sharding_tpu.models.base import scan_layers

        def body(h, p, k_buf, v_buf):
            return self._layer(
                h, p, k_buf, v_buf, offset, tp_axis=tp_axis, ep_axis=ep_axis
            )

        return scan_layers(body, h, layer_params, k, v, mask)

    def ep_layer_axes(self) -> dict:
        """Expert stacks shard their leading (E) dim over ep; everything
        else replicates across ep devices."""
        return {"w_gate": 0, "w_up": 0, "w_down": 0}

    def tp_layer_axes(self) -> dict:
        """Megatron column/row split for attention (whole heads per tp
        device); expert stacks shard their intermediate dim over tp, the
        router replicates (routing computed identically on every device).
        Dims counted after the stacked-L axis."""
        return {
            "input_norm": None, "post_norm": None,
            "q_proj": 1, "k_proj": 1, "v_proj": 1, "o_proj": 0,
            "router": None,
            "w_gate": 2, "w_up": 2, "w_down": 1,
        }

    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

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
        "post_attention_layernorm.weight": ("post_norm", False),
        "self_attn.q_proj.weight": ("q_proj", True),
        "self_attn.k_proj.weight": ("k_proj", True),
        "self_attn.v_proj.weight": ("v_proj", True),
        "self_attn.o_proj.weight": ("o_proj", True),
        "block_sparse_moe.gate.weight": ("router", True),
    }

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """Per-expert w1/w2/w3 tensors are stacked into fused (L, E, …)
        switch tensors — the same fusion the reference performs in sanitize
        (deepseek_v2.py:101-112), applied at load time."""
        from mlx_sharding_tpu.loading import (
            collect_layer_stack,
            fetch_weight,
            first_key,
            stack_tree,
            vocab_param,
        )

        cfg = self.config
        layers = collect_layer_stack(weights, cfg, self.HF_LAYER_MAP, dtype)

        def expert_stack(which: str):
            # (L, E, in, out) dense / {q,scales,biases} (L, E, out, …) packed
            return stack_tree(
                [
                    stack_tree(
                        [
                            fetch_weight(
                                weights,
                                f"model.layers.{i}.block_sparse_moe."
                                f"experts.{e}.{which}.weight",
                                dtype,
                            )
                            for e in range(cfg.num_local_experts)
                        ]
                    )
                    for i in range(cfg.start_layer, cfg.end_layer)
                ]
            )

        layers["w_gate"] = expert_stack("w1")
        layers["w_up"] = expert_stack("w3")
        layers["w_down"] = expert_stack("w2")
        params = {"layers": layers}
        if cfg.needs_embed:
            embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
            params["embed"] = {"weight": vocab_param(embed, dtype)}
        if cfg.needs_head:
            norm = first_key(weights, "model.norm.weight", "norm.weight")
            params["final_norm"] = {"weight": jnp.asarray(norm, dtype)}
            if not cfg.tie_word_embeddings:
                params["lm_head"] = {"weight": vocab_param(weights["lm_head.weight"], dtype, transpose=True)}
        return params

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd, hq, hkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        inter, nl, ne = cfg.intermediate_size, cfg.num_local_layers, cfg.num_local_experts
        keys = iter(jax.random.split(key, (8 + 3 * ne) * nl + 4))

        def layer():
            return {
                "input_norm": jnp.ones((hd,), dtype),
                "post_norm": jnp.ones((hd,), dtype),
                "q_proj": dense_init(next(keys), hd, hq * d, dtype),
                "k_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "v_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "o_proj": dense_init(next(keys), hq * d, hd, dtype),
                "router": dense_init(next(keys), hd, ne, dtype),
                "w_gate": jnp.stack([dense_init(next(keys), hd, inter, dtype) for _ in range(ne)]),
                "w_up": jnp.stack([dense_init(next(keys), hd, inter, dtype) for _ in range(ne)]),
                "w_down": jnp.stack([dense_init(next(keys), inter, hd, dtype) for _ in range(ne)]),
            }

        params = {"layers": stack_layers([layer() for _ in range(nl)])}
        if cfg.needs_embed:
            params["embed"] = {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            }
        if cfg.needs_head:
            params["final_norm"] = {"weight": jnp.ones((hd,), dtype)}
            if not cfg.tie_word_embeddings:
                params["lm_head"] = {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)}
        return params
