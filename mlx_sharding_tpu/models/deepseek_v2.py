"""DeepSeek-V2 decoder — MLA attention + fine-grained MoE with shared experts.

Capability parity: shard/server/model/deepseek_v2.py — the reference reuses
mlx_lm's DeepseekV2DecoderLayer (ref :8,30), stacks per-expert weights into
fused switch tensors in sanitize (ref :101-112), and exposes the MLA tuple
head-dim cache shape (ref :120-125). Here the architecture is first-party:

- **MLA** (the projection math is ``ops/mla.py``'s, which
  ``models/kimi_linear.py`` calls without rotary): queries (optionally
  LoRA-factored), K/V decompressed from a shared low-rank latent (``kv_a_proj_with_mqa`` → rank + single-head rope
  part; ``kv_b_proj`` → per-head nope-K and V), interleaved complex-pair
  RoPE with YaRN frequencies/attention-scaling, K dim ≠ V dim in the cache
  (our KVCache carries per-tensor head dims).
- **MoE**: first ``first_k_dense_replace`` layers are dense SwiGLU; the rest
  route over ``n_routed_experts`` small experts (greedy or
  group-limited-greedy top-k on fp32 softmax scores, routed_scaling_factor)
  plus always-on shared experts. Experts stay stage-local (SURVEY §2.3 EP)
  as stacked (L, E, …) tensors driven by the scan/gather dispatch.

The stage's layers run as TWO scans (dense prefix, then MoE) since their
param trees differ; the KV cache is one stacked buffer sliced between them.
The MoE scan's packed expert stacks do not ride it: they stay ``(L, E, …)``
and each layer reads its chosen experts out of them by ``(layer, expert)``
(``scan_in_place``; models/base.py says why).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, write_layer_kv
from mlx_sharding_tpu.config import DeepseekV2Config
from mlx_sharding_tpu.models.base import (
    LAYER_INDEX,
    BaseModel,
    dense_init,
    scan_layers,
    stack_layers,
)
from mlx_sharding_tpu.ops import causal_attention, rms_norm
from mlx_sharding_tpu.ops.mla import absorb_values, mla_qkv
from mlx_sharding_tpu.ops.moe import apply_experts, deepseek_routing
from mlx_sharding_tpu.ops.quant import is_quantized
from mlx_sharding_tpu.ops.rope import (
    rope_frequencies,
    yarn_frequencies,
    yarn_get_mscale,
)


class DeepseekV2Model(BaseModel):
    # MLA projections and the (E, …) expert stacks may stay 4-bit packed in
    # HBM; the router (fp32 routing einsum) and — in compressed cache mode —
    # kv_b (absorbed into einsums as a tensor) load dense via
    # packed_keep_dense_re.
    supports_packed = True
    supports_sp = True  # sp_layer below (MLA-aware, grouped dense/moe scan)

    def __init__(self, config: DeepseekV2Config):
        super().__init__(config)
        scaling = config.rope_scaling
        rope_type = (scaling or {}).get("type", (scaling or {}).get("rope_type"))
        if rope_type == "yarn":
            inv_freq, self.rope_scale = yarn_frequencies(
                config.qk_rope_head_dim,
                config.rope_theta,
                scaling,
                config.max_position_embeddings,
            )
        else:
            inv_freq = rope_frequencies(config.qk_rope_head_dim, config.rope_theta, None)
            self.rope_scale = 1.0
        self.inv_freq = jnp.asarray(inv_freq)
        self.scale = config.head_dim**-0.5  # head_dim == qk_nope + qk_rope
        # DeepSeek's YaRN variant also rescales the softmax scale itself when
        # mscale_all_dim is set (mlx_lm DeepseekV2Attention; DeepSeek remote
        # code). The cos/sin attention_factor above is 1.0 for real V2
        # checkpoints (mscale == mscale_all_dim == 0.707), so without this the
        # logits come out ~1.59x too small at factor=40.
        if rope_type == "yarn" and scaling.get("mscale_all_dim"):
            self.scale *= yarn_get_mscale(
                float(scaling["factor"]), float(scaling["mscale_all_dim"])
            ) ** 2

    def cache_head_dim(self):
        cfg = self.config
        if cfg.mla_cache_mode == "compressed":
            # one shared "head": latent + rope dims; the v buffer is a dummy
            # (values are a slice of the latent key)
            return (cfg.kv_lora_rank + cfg.qk_rope_head_dim, 1)
        # (K dim, V dim) tuple — ref deepseek_v2.py:120-125
        return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim)

    def cache_num_heads(self) -> int:
        cfg = self.config
        return 1 if cfg.mla_cache_mode == "compressed" else cfg.num_attention_heads

    def cache_tp_replicated(self) -> bool:
        # the compressed-latent cache stores ONE shared latent "head" whose
        # writes are computed from tp-replicated projections — identical on
        # every tp device, so the buffer replicates while q heads shard
        return self.config.mla_cache_mode == "compressed"

    def layer_group_ranges(self) -> dict:
        cfg = self.config
        fk = min(max(cfg.first_k_dense_replace, 0), cfg.num_hidden_layers)
        out = {}
        if fk > 0:
            out["dense"] = (0, fk)
        if fk < cfg.num_hidden_layers:
            out["moe"] = (fk, cfg.num_hidden_layers)
        return out

    def scan_in_place(self, group, stack):
        """The moe group's routed expert stacks, when they are packed: a
        layer's three are 345 MB at V2-Lite's widths, of which a decode step
        reads the chosen experts' share and a prefill chunk every expert
        once — never a reason to copy them out of the stack first. One form
        for every row count: the 256-row prefill chunk (``_apply_scan``
        indexing the same flat view) compiles to smaller temporaries than
        with scanned slices and runs faster (PERF.md section 6, PR 29)."""
        names = ("w_gate", "w_up", "w_down")
        if group == "moe" and all(is_quantized(stack[n]) for n in names):
            return names
        return ()

    def ep_layer_axes(self) -> dict:
        """Nested (per-group) map: only the moe group's routed expert
        stacks shard over ep; shared experts/router/attention replicate."""
        return {"moe": {"w_gate": 0, "w_up": 0, "w_down": 0}}

    def packed_keep_dense_re(self) -> str | None:
        # router feeds the fp32 routing einsum; kv_b is consumed as a raw
        # (rank, heads, nope+v) tensor by the absorbed compressed-cache
        # einsums — per-token dequant there would cost more HBM traffic
        # than dense residency saves
        if self.config.mla_cache_mode == "compressed":
            return r"mlp\.gate\.weight$|self_attn\.kv_b_proj\.weight$"
        return r"mlp\.gate\.weight$"

    def tp_layer_axes(self) -> dict:
        """MLA tensor parallelism (per-group nested map; dims counted after
        the stacked-L axis). Per-head projections shard: q/q_b and kv_b
        column-parallel (whole heads per device — the output dim is
        (heads, head_dim) flattened, so a contiguous heads/tp split is
        head-aligned), o_proj row-parallel. The low-rank latent path
        (q_a/kv_a + norms) and the router replicate. Expert stacks shard
        their intermediate dim over tp (overridden to the E dim by
        ep_layer_axes when a tp x ep mesh is in play — the engine merges
        ep after tp); shared experts split column/row like a dense MLP."""
        attn = {
            "input_norm": None, "post_norm": None,
            "kv_a_proj": None, "kv_a_norm": None,
            "kv_b_proj": 1, "o_proj": 0,
        }
        if self.config.q_lora_rank is None:
            attn["q_proj"] = 1
        else:
            attn.update({"q_a_proj": None, "q_a_norm": None, "q_b_proj": 1})
        out = {}
        if "dense" in self.layer_group_ranges():
            out["dense"] = {
                **attn, "gate_proj": 1, "up_proj": 1, "down_proj": 0,
            }
        if "moe" in self.layer_group_ranges():
            out["moe"] = {
                **attn, "router": None,
                "shared_gate": 1, "shared_up": 1, "shared_down": 0,
                "w_gate": 2, "w_up": 2, "w_down": 1,
            }
        return out

    # ------------------------------------------------------------------
    @jax.named_scope("mst.attn.qkv")
    def _attn_qkv(self, p, h, offset):
        """The input norm and ``ops.mla.mla_qkv``, shared by the causal and
        sequence-parallel attention paths: ``(q_cat, k_new, None, w_bv)`` in
        compressed mode, ``(q_full, k, v, None)`` decompressed."""
        cfg = self.config
        return mla_qkv(
            self._linear, p, rms_norm(h, p["input_norm"], cfg.rms_norm_eps), offset,
            nope=cfg.qk_nope_head_dim, rope_d=cfg.qk_rope_head_dim,
            v_d=cfg.v_head_dim, rank=cfg.kv_lora_rank, eps=cfg.rms_norm_eps,
            rotary=(self.inv_freq, self.rope_scale),
            compressed=cfg.mla_cache_mode == "compressed",
            q_lora=cfg.q_lora_rank is not None,
        )

    def _attention(self, h, p, k_buf, v_buf, offset, tp_axis=None):
        """MLA under tensor parallelism: the low-rank latent path
        (kv_a_proj / kv_a_norm and the single rope head) is REPLICATED —
        it is head-count independent — while the per-head projections
        (q/q_b, kv_b, o) shard over tp. Head counts derive from the
        projection shard shapes, so this code runs the full model and any
        tp slice unchanged; one psum after o_proj completes the row-parallel
        output projection."""
        cfg = self.config
        b, t, _ = h.shape
        rank = cfg.kv_lora_rank
        q, k_new, v_new, w_bv = self._attn_qkv(p, h, offset)
        if cfg.mla_cache_mode == "compressed":
            # Cache the latent, not per-head K/V: per token only
            # rank + rope_d numbers, independent of head count. kv_b is
            # absorbed into the query (scores) and output (values) sides, so
            # the math is identical to the decompressed path.
            dummy_v = jnp.zeros((b, t, 1, 1), v_buf.dtype)
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, k_new, dummy_v, offset)
            # MQA over the single latent head; "values" are the latent slice
            # of the key buffer, so no second buffer is stored.
            out_lat = causal_attention(
                q, k_buf, k_buf[..., :rank], offset, self.scale
            )  # (B,T,H,rank)
            attn = absorb_values(out_lat, w_bv, h.dtype)
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, k_new, v_new, offset)
            attn = causal_attention(q, k_buf, v_buf, offset, self.scale)
        return self._attn_out(h, attn, p, tp_axis), k_buf, v_buf

    @jax.named_scope("mst.attn.core")
    def _attn_out(self, h, attn, p, tp_axis=None):
        """Output projection and the residual add."""
        b, t, _ = h.shape
        attn_out = self._linear(attn.reshape(b, t, -1), p["o_proj"])
        if tp_axis is not None:
            attn_out = jax.lax.psum(attn_out, tp_axis)
        return h + attn_out

    def sp_groups(self):
        return list(self.layer_group_ranges().keys())

    def sp_layer(self, p, h, offset, attn_fn, group=None):
        """Sequence-parallel MLA layer. Compressed mode rides the injected
        attention as MQA over the single latent head with ``values_from_k``
        (the latent slice of the key rows serves as values — the same kv_b
        absorption as _attention), so ring prefill and sharded-KV decode
        both work on the compressed cache layout; the returned rows match
        it (latent+rope keys, dummy values)."""
        cfg = self.config
        b, t, _ = h.shape
        rank = cfg.kv_lora_rank
        q, k_new, v_new, w_bv = self._attn_qkv(p, h, offset)
        if cfg.mla_cache_mode == "compressed":
            v_new = jnp.zeros((b, t, 1, 1), h.dtype)
            out_lat = attn_fn(q, k_new, v_new, values_from_k=rank)
            attn = absorb_values(out_lat, w_bv, h.dtype)
        else:
            attn = attn_fn(q, k_new, v_new)
        h = self._attn_out(h, attn, p)
        r = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
        if group == "moe":
            h = self._moe_residual(h, r, p)
        else:
            h = self._dense_residual(h, r, p)
        return h, k_new, v_new

    def _swiglu(self, r, gate, up, down):
        return self._linear(
            jax.nn.silu(self._linear(r, gate)) * self._linear(r, up), down
        )

    @jax.named_scope("mst.mlp.dense")
    def _dense_residual(self, h, r, p, tp_axis=None):
        ff = self._swiglu(r, p["gate_proj"], p["up_proj"], p["down_proj"])
        if tp_axis is not None:
            ff = jax.lax.psum(ff, tp_axis)
        return h + ff

    @jax.named_scope("mst.moe.shared")
    def _moe_residual(self, h, r, p, tp_axis=None, ep_axis=None):
        # router and routed experts open their own deeper scopes inside
        # _moe_mlp; what is left (routed + shared + residual) counts with
        # the always-on half of the block
        b, t, hidden = h.shape
        combined = self._moe_mlp(r.reshape(b * t, hidden), p, tp_axis, ep_axis)
        return h + combined.reshape(b, t, hidden)

    def _dense_layer(self, h, p, k_buf, v_buf, offset, tp_axis=None):
        cfg = self.config
        h, k_buf, v_buf = self._attention(h, p, k_buf, v_buf, offset, tp_axis)
        r = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
        return self._dense_residual(h, r, p, tp_axis), k_buf, v_buf

    def _moe_mlp(self, flat, p, tp_axis=None, ep_axis=None):
        """Routed + shared experts over (N, hidden) rows. Routing is
        replicated over ep (router weights replicated, global expert ids);
        only the expert stacks shard."""
        cfg = self.config
        weights, idx = deepseek_routing(
            flat, p["router"], cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            topk_method=cfg.topk_method,
            n_group=cfg.n_group,
            topk_group=cfg.topk_group,
        )
        routed = apply_experts(
            flat, weights, idx, p["w_gate"], p["w_up"], p["w_down"],
            ep_axis=ep_axis, group_size=self._gs, bits=self._bits,
            # whole (L, E, …) stacks beside the layer's index (scan_in_place)
            layer=p.get(LAYER_INDEX),
        )
        # shared experts are always-on and replicated across ep — their
        # contribution must NOT enter the ep psum
        shared = self._swiglu(
            flat, p["shared_gate"], p["shared_up"], p["shared_down"]
        )
        if tp_axis is not None:
            if ep_axis is None:
                # experts shard their intermediate dim over tp: routed AND
                # shared are both partial products — one combined psum
                return jax.lax.psum(routed + shared, tp_axis)
            # tp x ep: expert stacks shard over ep (full after the ep
            # psum inside apply_experts, replicated across tp); only the
            # tp-sharded shared experts need the tp psum
            return routed + jax.lax.psum(shared, tp_axis)
        return routed + shared

    def _moe_layer(self, h, p, k_buf, v_buf, offset, tp_axis=None, ep_axis=None):
        cfg = self.config
        h, k_buf, v_buf = self._attention(h, p, k_buf, v_buf, offset, tp_axis)
        r = rms_norm(h, p["post_norm"], cfg.rms_norm_eps)
        return self._moe_residual(h, r, p, tp_axis, ep_axis), k_buf, v_buf

    # ------------------------------------------------------------------
    def _layer_split(self) -> tuple[int, int]:
        """(#dense, #moe) layers in this stage's local range."""
        cfg = self.config
        n_dense = max(
            0, min(cfg.end_layer, cfg.first_k_dense_replace) - cfg.start_layer
        )
        return n_dense, cfg.num_local_layers - n_dense

    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None,
    ):
        """Two scans (dense prefix, MoE suffix) over structurally distinct
        param stacks. The group sizes come from the param stacks themselves
        (not the config bounds), so the fused engine's padded uniform stacks
        and the single-program/chained stage params both work; ``mask`` is a
        matching {group: (L,) bool} dict for padded slots."""
        n_dense = (
            # tree.leaves: group values may be packed {q, scales, biases}
            jax.tree.leaves(layer_params["dense"])[0].shape[0]
            if "dense" in layer_params
            else 0
        )
        ks, vs = [], []
        if "dense" in layer_params:
            with jax.named_scope("mst.kv_pool.regroup"):
                k_d, v_d = k[:n_dense], v[:n_dense]
            h, kd, vd = scan_layers(
                lambda h, p, kb, vb: self._dense_layer(
                    h, p, kb, vb, offset, tp_axis=tp_axis
                ),
                h, layer_params["dense"], k_d, v_d,
                None if mask is None else mask["dense"],
            )
            ks.append(kd)
            vs.append(vd)
        if "moe" in layer_params:
            with jax.named_scope("mst.kv_pool.regroup"):
                k_m, v_m = k[n_dense:], v[n_dense:]
            h, km, vm = scan_layers(
                lambda h, p, kb, vb: self._moe_layer(
                    h, p, kb, vb, offset, tp_axis=tp_axis, ep_axis=ep_axis
                ),
                h, layer_params["moe"], k_m, v_m,
                None if mask is None else mask["moe"],
                in_place=self.scan_in_place("moe", layer_params["moe"]),
            )
            ks.append(km)
            vs.append(vm)
        with jax.named_scope("mst.kv_pool.regroup"):
            return h, jnp.concatenate(ks, axis=0), jnp.concatenate(vs, axis=0)

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
    def _attn_map(self) -> dict:
        cfg = self.config
        m = {
            "input_layernorm.weight": ("input_norm", False),
            "post_attention_layernorm.weight": ("post_norm", False),
            "self_attn.kv_a_proj_with_mqa.weight": ("kv_a_proj", True),
            "self_attn.kv_a_layernorm.weight": ("kv_a_norm", False),
            "self_attn.kv_b_proj.weight": ("kv_b_proj", True),
            "self_attn.o_proj.weight": ("o_proj", True),
        }
        if cfg.q_lora_rank is None:
            m["self_attn.q_proj.weight"] = ("q_proj", True)
        else:
            m["self_attn.q_a_proj.weight"] = ("q_a_proj", True)
            m["self_attn.q_a_layernorm.weight"] = ("q_a_norm", False)
            m["self_attn.q_b_proj.weight"] = ("q_b_proj", True)
        return m

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """Stage-filtered HF tensors → {dense: (Ld,…), moe: (Lm,…)} stacks.
        Per-expert tensors fuse into switch stacks — the load-time version of
        the reference's sanitize stacking (deepseek_v2.py:101-112)."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        attn_map = self._attn_map()
        dense_map = {
            **attn_map,
            "mlp.gate_proj.weight": ("gate_proj", True),
            "mlp.up_proj.weight": ("up_proj", True),
            "mlp.down_proj.weight": ("down_proj", True),
        }
        moe_map = {
            **attn_map,
            "mlp.gate.weight": ("router", True),
            "mlp.shared_experts.gate_proj.weight": ("shared_gate", True),
            "mlp.shared_experts.up_proj.weight": ("shared_up", True),
            "mlp.shared_experts.down_proj.weight": ("shared_down", True),
        }

        def collect(indices, name_map):
            stacked = {our: [] for our, _ in name_map.values()}
            for i in indices:
                for suffix, (our, transpose) in name_map.items():
                    stacked[our].append(
                        fetch_weight(
                            weights, f"model.layers.{i}.{suffix}", dtype, transpose
                        )
                    )
            return {k2: stack_tree(v2) for k2, v2 in stacked.items()}

        dense_idx = [
            i for i in range(cfg.start_layer, cfg.end_layer)
            if i < cfg.first_k_dense_replace
        ]
        moe_idx = [
            i for i in range(cfg.start_layer, cfg.end_layer)
            if i >= cfg.first_k_dense_replace
        ]
        layers: dict = {}
        if dense_idx:
            layers["dense"] = collect(dense_idx, dense_map)
        if moe_idx:
            moe = collect(moe_idx, moe_map)
            for our, which in (
                ("w_gate", "gate_proj"),
                ("w_up", "up_proj"),
                ("w_down", "down_proj"),
            ):
                moe[our] = stack_tree(
                    [
                        stack_tree(
                            [
                                fetch_weight(
                                    weights,
                                    f"model.layers.{i}.mlp.experts.{e}.{which}.weight",
                                    dtype,
                                )
                                for e in range(cfg.n_routed_experts)
                            ]
                        )
                        for i in moe_idx
                    ]
                )
            layers["moe"] = moe

        params = {"layers": layers}
        if cfg.needs_embed:
            embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
            params["embed"] = {"weight": vocab_param(embed, dtype)}
        if cfg.needs_head:
            norm = first_key(weights, "model.norm.weight", "norm.weight")
            params["final_norm"] = {"weight": jnp.asarray(norm, dtype)}
            params["lm_head"] = {"weight": vocab_param(weights["lm_head.weight"], dtype, transpose=True)}
        return params

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd = cfg.hidden_size
        heads = cfg.num_attention_heads
        nope, rope_d, v_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        rank = cfg.kv_lora_rank
        keys = iter(jax.random.split(key, 64 * max(cfg.num_local_layers, 1) + 8))

        def attn_params():
            p = {
                "input_norm": jnp.ones((hd,), dtype),
                "post_norm": jnp.ones((hd,), dtype),
                "kv_a_proj": dense_init(next(keys), hd, rank + rope_d, dtype),
                "kv_a_norm": jnp.ones((rank,), dtype),
                "kv_b_proj": dense_init(next(keys), rank, heads * (nope + v_d), dtype),
                "o_proj": dense_init(next(keys), heads * v_d, hd, dtype),
            }
            if cfg.q_lora_rank is None:
                p["q_proj"] = dense_init(next(keys), hd, heads * (nope + rope_d), dtype)
            else:
                p["q_a_proj"] = dense_init(next(keys), hd, cfg.q_lora_rank, dtype)
                p["q_a_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
                p["q_b_proj"] = dense_init(
                    next(keys), cfg.q_lora_rank, heads * (nope + rope_d), dtype
                )
            return p

        n_dense, n_moe = self._layer_split()
        layers: dict = {}
        if n_dense:
            layers["dense"] = stack_layers(
                [
                    {
                        **attn_params(),
                        "gate_proj": dense_init(next(keys), hd, cfg.intermediate_size, dtype),
                        "up_proj": dense_init(next(keys), hd, cfg.intermediate_size, dtype),
                        "down_proj": dense_init(next(keys), cfg.intermediate_size, hd, dtype),
                    }
                    for _ in range(n_dense)
                ]
            )
        if n_moe:
            e, mi = cfg.n_routed_experts, cfg.moe_intermediate_size
            si = mi * (cfg.n_shared_experts or 1)
            layers["moe"] = stack_layers(
                [
                    {
                        **attn_params(),
                        "router": dense_init(next(keys), hd, e, dtype),
                        "w_gate": jnp.stack(
                            [dense_init(next(keys), hd, mi, dtype) for _ in range(e)]
                        ),
                        "w_up": jnp.stack(
                            [dense_init(next(keys), hd, mi, dtype) for _ in range(e)]
                        ),
                        "w_down": jnp.stack(
                            [dense_init(next(keys), mi, hd, dtype) for _ in range(e)]
                        ),
                        "shared_gate": dense_init(next(keys), hd, si, dtype),
                        "shared_up": dense_init(next(keys), hd, si, dtype),
                        "shared_down": dense_init(next(keys), si, hd, dtype),
                    }
                    for _ in range(n_moe)
                ]
            )
        params = {"layers": layers}
        if cfg.needs_embed:
            params["embed"] = {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            }
        if cfg.needs_head:
            params["final_norm"] = {"weight": jnp.ones((hd,), dtype)}
            params["lm_head"] = {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)}
        return params
