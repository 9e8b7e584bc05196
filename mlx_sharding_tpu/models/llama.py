"""Llama-family decoder (also serves Mistral/Qwen2 via MODEL_REMAPPING,
as in the reference: shard/utils.py:14-17).

Capability parity target: shard/server/model/llama.py — pipeline-aware
stage model with embed on first stage, norm + head (or tied embedding) on
last (llama.py:26-36,74-89), causal masking with cache offset (llama.py:48-53),
out-of-range weight dropping (sanitize, llama.py:92-107 — done in our loader).

TPU-native design: the stage's layers run as one ``lax.scan`` over stacked
parameters; the KV cache rides through the scan as xs/ys so XLA keeps all
per-layer state in HBM with in-place dynamic-update-slice writes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, write_layer_kv
from mlx_sharding_tpu.config import LlamaConfig
from mlx_sharding_tpu.models.base import BaseModel, dense_init
from mlx_sharding_tpu.ops import apply_rope, causal_attention, rms_norm, rope_frequencies


class LlamaModel(BaseModel):
    # decoder-layer projections may stay 4-bit packed in HBM
    # (loading.load_model(keep_quantized=True) → ops.quant.linear dispatch)
    supports_packed = True
    # sequence-parallel paths use the default sp_layer over the
    # layer_attn_inputs/layer_finish hook pair below
    supports_sp = True

    def __init__(self, config: LlamaConfig):
        super().__init__(config)
        self.inv_freq = jnp.asarray(
            rope_frequencies(config.head_dim, config.rope_theta, config.rope_scaling)
        )
        self.scale = config.head_dim ** -0.5

    # ------------------------------------------------------------------
    @jax.named_scope("mst.attn.qkv")
    def layer_attn_inputs(self, p, h, offset):
        """Pre-attention half of a decoder layer: norm + QKV + RoPE at
        absolute positions ``offset..offset+T``. Split out so the sequence-
        parallel prefill path (parallel/sp_prefill.py) can swap the attention
        op (ring over ``sp``) while reusing the exact projection math.

        Head counts are derived from the projection OUTPUT shapes, not the
        config — under tensor parallelism each device's param shard carries
        heads/tp heads and this same code runs unchanged on the slice."""
        cfg = self.config
        b, t, _ = h.shape
        d = cfg.head_dim

        r = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
        if "qkv_proj" in p:
            # build-time fused packed projection (engine applied
            # fused_projection_groups): one kernel launch, one pass over the
            # activation planes. Split sizes come from the CONFIG (not the
            # shard) because fusion is only applied at tp == 1.
            nq, nkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
            qkv = self._linear(r, p["qkv_proj"])
            q, k, v = jnp.split(qkv, [nq, nq + nkv], axis=-1)
        else:
            q = self._linear(r, p["q_proj"])
            k = self._linear(r, p["k_proj"])
            v = self._linear(r, p["v_proj"])
        if cfg.attention_bias:  # Qwen2-style QKV biases
            q = q + p["q_bias"]
            k = k + p["k_bias"]
            v = v + p["v_bias"]
        q = q.reshape(b, t, q.shape[-1] // d, d)
        k = k.reshape(b, t, k.shape[-1] // d, d)
        v = v.reshape(b, t, v.shape[-1] // d, d)
        q = apply_rope(q, self.inv_freq, offset)
        k = apply_rope(k, self.inv_freq, offset)
        return q, k, v

    def layer_finish(self, p, h, attn, tp_axis=None):
        """Post-attention half: output projection + SwiGLU MLP. Under TP the
        O and down projections contract over sharded dims, so their partial
        products psum over ``tp_axis`` — exactly two collectives per layer
        (Megatron-style column/row split), riding ICI."""
        cfg = self.config
        b, t, _ = h.shape
        with jax.named_scope("mst.attn.core"):
            attn_out = self._linear(attn.reshape(b, t, -1), p["o_proj"])
            if tp_axis is not None:
                attn_out = jax.lax.psum(attn_out, tp_axis)
            h = h + attn_out
        r = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
        with jax.named_scope("mst.mlp.dense"):
            if "gate_up_proj" in p:  # build-time fused packed gate+up (tp == 1)
                gu = self._linear(r, p["gate_up_proj"])
                gate, up = jnp.split(gu, [cfg.intermediate_size], axis=-1)
                ff = self._linear(jax.nn.silu(gate) * up, p["down_proj"])
            else:
                ff = self._linear(
                    jax.nn.silu(self._linear(r, p["gate_proj"]))
                    * self._linear(r, p["up_proj"]),
                    p["down_proj"],
                )
            if tp_axis is not None:
                ff = jax.lax.psum(ff, tp_axis)
            return h + ff

    def _layer(self, h, p, k_buf, v_buf, offset, tp_axis=None):
        q, k, v = self.layer_attn_inputs(p, h, offset)
        k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
        attn = causal_attention(q, k_buf, v_buf, offset, self.scale)
        return self.layer_finish(p, h, attn, tp_axis), k_buf, v_buf

    def run_layers(self, layer_params, h, k, v, offset, mask=None, tp_axis=None):
        """The stage body: scan the (local) stacked layers, threading the
        full-capacity K/V buffers (L, B, S, H, D) through as scan xs/ys.
        This is the piece the SPMD pipeline executes per tick; ``__call__``
        wraps it with embed/head for the single-program path. ``mask`` is an
        optional (L,) bool marking active layers — padding slots in the fused
        engine's uniform per-stage stacks scan as no-ops. ``tp_axis`` names
        the mesh axis attention heads / MLP columns are sharded over."""
        from mlx_sharding_tpu.models.base import scan_layers

        def body(h, p, k_buf, v_buf):
            return self._layer(h, p, k_buf, v_buf, offset, tp_axis)

        return scan_layers(body, h, layer_params, k, v, mask)

    def tp_layer_axes(self) -> dict:
        """Per-layer-param dim (counted after the stacked-L axis) sharded
        over tp: column-parallel QKV/gate/up (output dim), row-parallel
        O/down (contracting dim); norms replicated."""
        axes = {
            "input_norm": None, "post_norm": None,
            "q_proj": 1, "k_proj": 1, "v_proj": 1, "o_proj": 0,
            "gate_proj": 1, "up_proj": 1, "down_proj": 0,
        }
        if self.config.attention_bias:
            axes.update({"q_bias": 0, "k_bias": 0, "v_bias": 0})
        return axes

    def fused_projection_groups(self) -> dict:
        """QKV and gate+up share their input activations — the engines may
        concatenate each group's packed triples along OUT at build time so
        decode issues one kernel launch per group instead of three/two."""
        return {
            "qkv_proj": ("q_proj", "k_proj", "v_proj"),
            "gate_up_proj": ("gate_proj", "up_proj"),
        }

    def head_input(self, params, h):
        """Final norm before the (tied-embedding aware) LM head — ref
        llama.py:74-77, 84-89."""
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        """``n_valid`` (traced scalar) advances the cache by fewer positions
        than T when the input is a right-padded prefill chunk; pad-position
        K/V writes are overwritten by later contiguous writes before any
        valid query can attend them (see generate.py docstring)."""
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
        "mlp.gate_proj.weight": ("gate_proj", True),
        "mlp.up_proj.weight": ("up_proj", True),
        "mlp.down_proj.weight": ("down_proj", True),
    }

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """HF-named (already stage-filtered, dequantized) tensors → the
        scan-ready stacked pytree. Plays the role of the reference models'
        sanitize + load_weights (shard/server/model/llama.py:92-107,
        shard/utils.py:66-67)."""
        from mlx_sharding_tpu.loading import (
            collect_layer_stack,
            first_key,
            vocab_param,
        )

        cfg = self.config
        layer_map = dict(self.HF_LAYER_MAP)
        if cfg.attention_bias:  # Qwen2 checkpoints carry QKV biases
            layer_map.update(
                {
                    "self_attn.q_proj.bias": ("q_bias", False),
                    "self_attn.k_proj.bias": ("k_bias", False),
                    "self_attn.v_proj.bias": ("v_bias", False),
                }
            )
        params = {"layers": collect_layer_stack(weights, cfg, layer_map, dtype)}
        if cfg.needs_embed:
            embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
            params["embed"] = {"weight": vocab_param(embed, dtype)}
        if cfg.needs_head:
            norm = first_key(weights, "model.norm.weight", "norm.weight")
            params["final_norm"] = {"weight": jnp.asarray(norm, dtype)}
            if not cfg.tie_word_embeddings:
                head = first_key(weights, "lm_head.weight")
                params["lm_head"] = {"weight": vocab_param(head, dtype, transpose=True)}
        return params

    def init_params(self, key, dtype=jnp.bfloat16):
        """Random params for this stage — tests and benchmarks only."""
        cfg = self.config
        hd, hq, hkv, d = cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        inter, nl = cfg.intermediate_size, cfg.num_local_layers
        keys = iter(jax.random.split(key, 8 * nl + 4))

        def layer():
            out = {
                "input_norm": jnp.ones((hd,), dtype),
                "post_norm": jnp.ones((hd,), dtype),
                "q_proj": dense_init(next(keys), hd, hq * d, dtype),
                "k_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "v_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "o_proj": dense_init(next(keys), hq * d, hd, dtype),
                "gate_proj": dense_init(next(keys), hd, inter, dtype),
                "up_proj": dense_init(next(keys), hd, inter, dtype),
                "down_proj": dense_init(next(keys), inter, hd, dtype),
            }
            if cfg.attention_bias:
                out["q_bias"] = jnp.zeros((hq * d,), dtype)
                out["k_bias"] = jnp.zeros((hkv * d,), dtype)
                out["v_bias"] = jnp.zeros((hkv * d,), dtype)
            return out

        from mlx_sharding_tpu.models.base import stack_layers

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
