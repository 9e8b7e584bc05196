"""SDAR-MoE decoder (``model_type: sdar_moe``): Qwen3-MoE's block, generated
from by diffusion over blocks.

Pre-norm residual blocks, no bias anywhere: ``h = h + attn(norm(h))``; ``h =
h + moe(norm(h))``; logits ``= norm(h) @ lm_head`` (untied); plain RMSNorm
``x * rsqrt(mean x^2 + eps) * w``.

- **attention**: GQA on ``head_dim``-wide heads (32 query on 4 K/V heads of
  128); per-head RMSNorm of ``q`` and ``k`` with weights of their own, THEN
  rotary (split-half, every channel) at the token's absolute position, as
  ``models/qwen3.py`` orders them; softmax at ``head_dim**-0.5``. The mask is
  BLOCK-causal: a query at ``p`` sees every key up to the end of its block of
  ``block_length`` positions (``ops.attention.attend(block=...)``). One rule
  serves a prefill chunk (whole blocks) and a decode block (its ``L`` rows
  are written before it attends, so they see each other all ways). K/V rows
  keep their heads MERGED on the lane axis, ``(…, 1, Hkv * D)``, as
  ``models/afmoe.py`` says why.
- **the feed-forward of every layer**: softmax in float32 over all experts,
  the top ``num_experts_per_tok`` renormalised (``ops.moe.mixtral_routing``),
  routed SwiGLU experts, no shared expert. The layer may hold a share of the
  routed experts (``config.SdarMoeConfig``): it routes over all of them and
  computes its own experts' part. The expert stacks stay whole ``(L, E, …)``
  beside the layer scan (``scan_in_place``) and are read by ``(layer,
  expert)`` where they lie.

What makes the family different is not in this file: a forward yields a
block's logits, row ``i`` predicting position ``i`` ITSELF, and
``mlx_sharding_tpu/diffusion.py`` decides which of them become tokens.
``diffusion_block`` is what tells the engines (``parallel/pipeline.py``,
``scheduler.py``) that a decode step is such a forward — over one block a
slot, or over two: a finished block's commit with the next block's denoise
rows behind it, each lane masked from the other as the published loop's two
forwards are (``sp_layer`` sees ``2L`` rows a slot then, and the engine's
attention bounds each lane's keys). One pipeline stage, no tensor or expert
parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, write_layer_kv
from mlx_sharding_tpu.config import SdarMoeConfig
from mlx_sharding_tpu.models.base import (
    LAYER_INDEX,
    BaseModel,
    dense_init,
    scan_layers,
    stack_layers,
)
from mlx_sharding_tpu.ops import apply_rope, causal_attention, rms_norm, rope_frequencies
from mlx_sharding_tpu.ops.moe import apply_experts, mixtral_routing

EXPERT_STACKS = ("w_gate", "w_up", "w_down")


class SdarMoeModel(BaseModel):
    supports_sp = True  # sp_layer below: the ragged decode body's hook

    def __init__(self, config: SdarMoeConfig):
        super().__init__(config)
        self.inv_freq = jnp.asarray(
            rope_frequencies(config.head_dim, config.rope_theta, None)
        )
        self.scale = config.head_dim ** -0.5
        self.kv_dim = config.num_key_value_heads * config.head_dim

    @property
    def diffusion_block(self) -> int:
        """Positions a decode forward computes a sequence (engines read it
        through ``diffusion.block_of``; models that generate a token a step
        have no such attribute)."""
        return self.config.block_length

    # -- layer structure ---------------------------------------------------
    def scan_in_place(self, group, stack: dict) -> tuple:
        return EXPERT_STACKS

    def packed_keep_dense_re(self) -> str | None:
        return r"mlp\.gate\.weight$"

    def cache_num_heads(self) -> int:
        return 1  # a row's heads are merged on the lane axis

    def cache_head_dim(self):
        return self.kv_dim

    # -- one layer ---------------------------------------------------------
    def layer_attn_inputs(self, p, h, offset):
        """Norm, projections, per-head q/k norm, rotary: ``q (B, T, Hq, D)``,
        ``k`` and ``v`` merged ``(B, T, 1, Hkv * D)``."""
        cfg = self.config
        b, t, _ = h.shape
        hkv, d = cfg.num_key_value_heads, cfg.head_dim
        with jax.named_scope("mst.attn.qkv"):
            r = rms_norm(h, p["input_norm"], cfg.rms_norm_eps)
            q = self._linear(r, p["q_proj"]).reshape(b, t, -1, d)
            k = self._linear(r, p["k_proj"]).reshape(b, t, hkv, d)
            v = self._linear(r, p["v_proj"]).reshape(b, t, 1, hkv * d)
        with jax.named_scope("mst.attn.qk_norm"):
            q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
        with jax.named_scope("mst.attn.qkv"):
            q = apply_rope(q, self.inv_freq, offset)
            k = apply_rope(k, self.inv_freq, offset).reshape(b, t, 1, hkv * d)
        return q, k, v

    def layer_finish(self, p, h, attn, lanes: int = 1):
        """Output projection, residual, then the routed experts. ``lanes``:
        the rows are that many blocks a slot side by side (a wide decode
        forward, ``diffusion.py``) and go to the experts a lane a call, each
        the rows of a narrow forward — the count ``ops/dense_experts.py``'s
        kernel holds, where all of them at once would walk the loop."""
        cfg = self.config
        b, t, hidden = h.shape
        with jax.named_scope("mst.attn.qkv"):
            h = h + self._linear(attn.reshape(b, t, -1), p["o_proj"]).astype(h.dtype)

        def moe(r):  # (B, rows, hidden)
            flat = r.reshape(-1, hidden)
            weights, idx = mixtral_routing(flat, p["router"], cfg.num_experts_per_tok)
            return apply_experts(
                flat, weights, idx, p["w_gate"], p["w_up"], p["w_down"],
                group_size=self._gs, bits=self._bits,
                expert_base=(
                    cfg.moe_expert_share_index * cfg.num_experts
                    if cfg.moe_expert_share > 1 else None
                ),
                layer=p.get(LAYER_INDEX),
            ).reshape(r.shape)

        r = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
        out = moe(r) if lanes == 1 else jnp.concatenate(
            [moe(x) for x in jnp.split(r, lanes, axis=1)], axis=1
        )
        return h + out.astype(h.dtype)

    def sp_layer(self, p, h, offset, attn_fn, group=None):
        q, k, v = self.layer_attn_inputs(p, h, offset)
        attn = attn_fn(q, k, v, kv_heads=self.config.num_key_value_heads)
        lanes = h.shape[1] // self.config.block_length
        return self.layer_finish(p, h, attn, lanes), k, v

    def _layer(self, h, p, k_buf, v_buf, offset):
        """Over a sequence's contiguous rows ``(B, S, 1, Hkv * D)``: a prefill
        chunk, or a decode block of a single sequence."""
        cfg = self.config
        q, k, v = self.layer_attn_inputs(p, h, offset)
        k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
        split = lambda z: z.reshape(  # noqa: E731
            *z.shape[:2], cfg.num_key_value_heads, cfg.head_dim
        )
        attn = causal_attention(
            q, split(k_buf), split(v_buf), offset, self.scale,
            block=cfg.block_length,
        )
        return self.layer_finish(p, h, attn), k_buf, v_buf

    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None,
    ):
        if tp_axis is not None or ep_axis is not None:
            raise ValueError(
                "tensor and expert parallelism are not wired for sdar_moe"
            )

        def body(h, p, k_buf, v_buf):
            return self._layer(h, p, k_buf, v_buf, offset)

        return scan_layers(
            body, h, layer_params, k, v, mask,
            in_place=self.scan_in_place(None, layer_params),
        )

    # -- embed / head ------------------------------------------------------
    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        """Logits of ``x``'s rows at positions ``cache.offset ..`` under the
        block mask, their K/V written; the offset advances by ``n_valid``
        (0: a denoise forward, whose rows the next forward overwrites)."""
        h = self.embed(params, x)
        offset = cache.offset
        h, k, v = self.run_layers(params["layers"], h, cache.k, cache.v, offset)
        cache = KVCache(k=k, v=v, offset=offset)
        cache = advance(cache, x.shape[1] if n_valid is None else n_valid)
        return self.apply_head(params, h), cache

    # -- weights -----------------------------------------------------------
    #: checkpoint suffix -> (our leaf, transposed to (in, out)?). The catalog
    #: gives the family's config.json, not its tensor names: these follow
    #: Qwen3-MoE's module names, written from the description and ASSUMED.
    NAMES = {
        "input_layernorm.weight": ("input_norm", False),
        "post_attention_layernorm.weight": ("post_norm", False),
        "self_attn.q_proj.weight": ("q_proj", True),
        "self_attn.k_proj.weight": ("k_proj", True),
        "self_attn.v_proj.weight": ("v_proj", True),
        "self_attn.o_proj.weight": ("o_proj", True),
        "self_attn.q_norm.weight": ("q_norm", False),
        "self_attn.k_norm.weight": ("k_norm", False),
        "mlp.gate.weight": ("router", True),
    }
    EXPERTS = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """HF tensors (``model.layers.<i>.*``) → the stacked tree. A config
        with an expert share loads only the experts it holds (the router
        whole: it scores every expert)."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        pre = "model.layers.{}.".format
        base = cfg.moe_expert_share_index * cfg.num_experts
        idxs = range(cfg.start_layer, cfg.end_layer)
        layers = {
            our: stack_tree([
                fetch_weight(weights, pre(i) + suffix, dtype, tr) for i in idxs
            ])
            for suffix, (our, tr) in self.NAMES.items()
        }
        for our, which in self.EXPERTS.items():
            layers[our] = stack_tree([
                stack_tree([
                    fetch_weight(
                        weights, pre(i) + f"mlp.experts.{base + e}.{which}.weight", dtype
                    )
                    for e in range(cfg.num_experts)
                ])
                for i in idxs
            ])
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
        keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 4))
        near_one = lambda n: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)
        stack = lambda k_, i, o: jax.vmap(  # noqa: E731
            lambda kk: dense_init(kk, i, o, dtype))(jax.random.split(k_, e))

        def layer():
            return {
                "input_norm": near_one(hd), "post_norm": near_one(hd),
                "q_proj": dense_init(next(keys), hd, hq * d, dtype),
                "k_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "v_proj": dense_init(next(keys), hd, hkv * d, dtype),
                "o_proj": dense_init(next(keys), hq * d, hd, dtype),
                "q_norm": near_one(d), "k_norm": near_one(d),
                "router": dense_init(next(keys), hd, cfg.router_width, dtype),
                "w_gate": stack(next(keys), hd, mi),
                "w_up": stack(next(keys), hd, mi),
                "w_down": stack(next(keys), mi, hd),
            }

        return {
            "layers": stack_layers([layer() for _ in range(cfg.num_hidden_layers)]),
            "embed": {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            },
            "final_norm": {"weight": near_one(hd)},
            "lm_head": {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)},
        }
