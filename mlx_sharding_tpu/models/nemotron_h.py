"""Nemotron-H / Nemotron-3 hybrid decoder (``model_type: nemotron_h``).

Every block is ``h = h + mixer(rmsnorm(h))`` with ONE mixer, chosen per
layer by ``hybrid_override_pattern``; a final RMSNorm; untied head; no bias
anywhere except the convolution's.

- ``M`` **Mamba-2** (``ops/mamba2.py``, shared with
  ``models/granitemoehybrid.py``): 128 heads of 64 on 8 groups, state 128, 4
  taps, chunk 128 at the published sizes. Per sequence the layer keeps the
  SSM state ``(H, P, N)`` in float32 and the last ``conv_kernel - 1`` inputs
  of its convolution ``(C, conv_kernel - 1)`` in the activation dtype.
- ``*`` **Attention.** GQA, scale ``head_dim**-0.5``, causal, NO rotary
  embedding (the family applies none; positions come from the Mamba layers).
- ``E`` **Latent MoE.** Sigmoid router over all experts with a selection
  bias (``ops.moe.nemotron_routing``); the routed experts are un-gated
  ``relu^2`` MLPs in a ``moe_latent_size``-wide space between
  ``fc1_latent_proj`` and ``fc2_latent_proj``; one shared expert at full
  width. The layer may hold a share of the routed experts
  (``config.NemotronHConfig``): it routes over all of them and computes its
  own experts' part.

The multi-token-prediction block (``mtp_hybrid_override_pattern``) is not
loaded: it takes no part in next-token logits.

Layers: three stacked groups (``mamba``, ``attn``, ``moe``), walked in the
pattern's order by an unrolled loop. K/V buffers exist for the attention
layers only and recurrent state for the Mamba layers only; the loop indexes
each by the layer's rank within its group. On a multi-stage mesh each stage
walks its own slice of the pattern: where the stages agree on a position's
kind the layer is called directly, where they differ a ``lax.switch`` on the
stage index picks it (the weights are stage-sharded, so no stage computes
another's layer).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import NemotronHConfig
from mlx_sharding_tpu.models.base import (
    BaseModel,
    LayerRow as _LayerRow,
    dense_init,
    put_row as _put,
    stack_layers,
    take_row as _take,
)
from mlx_sharding_tpu.ops import causal_attention, rms_norm
from mlx_sharding_tpu.ops.mamba2 import (  # noqa: F401 — the recurrences stay importable from here
    mamba2_mixer,
    put_rows,
    ssd_chunked,
    ssm_sequential,
    take_rows,
)
from mlx_sharding_tpu.ops.moe import apply_experts, nemotron_routing

GROUP_OF = {"M": "mamba", "*": "attn", "E": "moe"}


class NemotronHModel(BaseModel):
    #: engines carry a per-slot recurrent state beside the K/V pages
    #: (cache.KVCache.state); whatever rewinds a slot by lowering its offset
    #: cannot serve this model (cache.refuse_recurrent)
    has_recurrent_state = True

    def __init__(self, config: NemotronHConfig):
        super().__init__(config)
        self.scale = config.head_dim**-0.5
        self.d_inner = config.mamba_num_heads * config.mamba_head_dim
        self.conv_dim = self.d_inner + 2 * config.n_groups * config.ssm_state_size

    # -- layer structure ---------------------------------------------------
    def layer_group_layers(self) -> dict:
        """{group: [global layer indices]} — the groups interleave, so there
        are no contiguous ranges (``layer_group_ranges`` is not defined)."""
        out: dict = {}
        for i, ch in enumerate(self.config.hybrid_override_pattern):
            out.setdefault(GROUP_OF[ch], []).append(i)
        return out

    def layer_group_ranges(self) -> dict:
        raise NotImplementedError(
            "nemotron_h layer groups interleave: use layer_group_layers()"
        )

    def kv_groups(self) -> tuple:
        return ("attn",)

    def state_groups(self) -> tuple:
        return ("mamba",)

    def ep_layer_axes(self) -> dict:
        return {"moe": {"w_up": 0, "w_down": 0}}

    def packed_keep_dense_re(self) -> str | None:
        return r"mixer\.gate\.weight$"

    def stage_plan(self, stage_bounds) -> list:
        """For each position of a stage's layer walk, what every stage runs
        there: ``[(kinds, ranks)]`` with one entry per stage — the group of
        the layer (None past a shorter stage's end) and its row in that
        stage's stack of the group."""
        pattern = self.config.hybrid_override_pattern
        walks = []
        for s, e in stage_bounds:
            seen: dict = {}
            walk = []
            for ch in pattern[s:e]:
                g = GROUP_OF[ch]
                walk.append((g, seen.get(g, 0)))
                seen[g] = seen.get(g, 0) + 1
            walks.append(walk)
        depth = max(len(w) for w in walks)
        plan = []
        for i in range(depth):
            at = [w[i] if i < len(w) else (None, 0) for w in walks]
            plan.append((tuple(g for g, _ in at), tuple(r for _, r in at)))
        return plan

    # -- cache and state ---------------------------------------------------
    def state_shapes(self, batch: int) -> dict:
        """Per Mamba layer and sequence: {name: (shape after (layer,), dtype)}."""
        cfg = self.config
        return {
            "ssm": (
                (batch, cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size),
                jnp.float32,
            ),
            "conv": ((batch, self.conv_dim, cfg.conv_kernel - 1), None),
        }

    def init_state(self, n_layers: int, batch: int, dtype=jnp.bfloat16) -> dict:
        return {
            name: jnp.zeros((n_layers, *shape), dt or dtype)
            for name, (shape, dt) in self.state_shapes(batch).items()
        }

    def _local_count(self, group: str) -> int:
        cfg = self.config
        return sum(
            GROUP_OF[ch] == group
            for ch in cfg.hybrid_override_pattern[cfg.start_layer : cfg.end_layer]
        )

    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        cfg = self.config
        kv = init_cache(
            self._local_count("attn"), batch, max_seq, cfg.num_key_value_heads,
            cfg.head_dim, dtype,
        )
        return kv._replace(state=self.init_state(self._local_count("mamba"), batch, dtype))

    # -- mixers ------------------------------------------------------------
    def _mamba(self, p, u, state, rank, n_valid, active):
        """``u (B,T,hidden)`` normed input; ``state`` the pool ``{"ssm",
        "conv"}: (layers, rows, …)``, this layer's at ``rank``, the ``B``
        sequences' in its first ``B`` rows (the pool may carry rows past
        them, an engine's scratch row: neither read nor written); rows past
        ``n_valid`` and sequences outside ``active`` do not advance it.
        Returns ``(out (B,T,hidden), state)``. The mixer is ``ops.mamba2``'s;
        this family keeps the convolution's tail as ``(B, C, k-1)``."""
        cfg = self.config
        tail = take_rows(state["conv"], rank, u.shape[0])
        with jax.named_scope("mst.ssm.conv"):
            tail = jnp.swapaxes(tail, 1, 2)  # (B,k-1,C)
        out, ssm, tail = mamba2_mixer(
            self._linear, p, u, state["ssm"], rank, tail, n_valid, active,
            heads=cfg.mamba_num_heads, head_dim=cfg.mamba_head_dim,
            groups=cfg.n_groups, state=cfg.ssm_state_size,
            taps=cfg.conv_kernel, chunk=cfg.chunk_size,
            eps=cfg.layer_norm_epsilon,
        )
        with jax.named_scope("mst.ssm.conv"):
            tail = jnp.swapaxes(tail, 1, 2)
        return out, {"ssm": ssm, "conv": put_rows(state["conv"], rank, tail)}

    def _attn(self, p, u, k_buf, v_buf, offset, paged_attn):
        cfg = self.config
        b, t, _ = u.shape
        with jax.named_scope("mst.attn.qkv"):
            q = self._linear(u, p["q_proj"]).reshape(b, t, -1, cfg.head_dim)
            k = self._linear(u, p["k_proj"]).reshape(b, t, -1, cfg.head_dim)
            v = self._linear(u, p["v_proj"]).reshape(b, t, -1, cfg.head_dim)
        if paged_attn is not None:  # the engine's ragged pool attention
            attn_fn, done = paged_attn(k_buf, v_buf)
            attn = attn_fn(q, k, v)
            k_buf, v_buf = done["k"], done["v"]
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, k, v, offset)
            attn = causal_attention(q, k_buf, v_buf, offset, self.scale)
        with jax.named_scope("mst.attn.core"):
            out = self._linear(attn.reshape(b, t, -1), p["o_proj"])
        return out, k_buf, v_buf

    @staticmethod
    def _relu2_mlp(lin, x, up, down):
        return lin(jnp.square(jax.nn.relu(lin(x, up))), down)

    def _moe(self, p, stacks, rank, u, ep_axis):
        """``p``: the layer's small leaves; ``stacks``: the group's whole
        ``(L, E, …)`` expert stacks, read at ``rank`` inside the expert scan."""
        cfg = self.config
        b, t, hidden = u.shape
        flat = u.reshape(b * t, hidden)
        weights, idx = nemotron_routing(
            flat, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
        )
        with jax.named_scope("mst.moe.latent"):
            lat = self._linear(flat, p["latent_in"])
        routed = apply_experts(
            lat, weights, idx, None, stacks["w_up"], stacks["w_down"],
            ep_axis=ep_axis, group_size=self._gs, bits=self._bits,
            expert_base=cfg.moe_expert_share_index * cfg.n_routed_experts,
            layer=rank,
        )
        with jax.named_scope("mst.moe.latent"):
            routed = self._linear(routed, p["latent_out"])
        with jax.named_scope("mst.moe.shared"):
            shared = self._relu2_mlp(self._linear, flat, p["shared_up"], p["shared_down"])
            return (routed + shared).reshape(b, t, hidden)

    # -- the layer walk ----------------------------------------------------
    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None, *, state=None, n_valid=None, active=None, plan=None,
        stage_axis=None, paged_attn=None,
    ):
        """One stage's layers in pattern order. ``k``/``v`` hold the stage's
        attention layers, ``state`` its Mamba layers (leading axis: the
        layer's row in its group). ``plan`` (:meth:`stage_plan`) with
        ``stage_axis`` walks a multi-stage mesh; without it the walk is the
        config's own ``[start_layer, end_layer)``. ``mask`` is unused: the
        plan never visits a padding row. Returns ``(h, k, v, state)``."""
        if tp_axis is not None:
            raise ValueError("tensor parallelism is not wired for nemotron_h")
        cfg = self.config
        if plan is None:
            plan = self.stage_plan([(cfg.start_layer, cfg.end_layer)])
        n_stages = len(plan[0][0]) if plan else 1
        s_idx = jax.lax.axis_index(stage_axis) if n_stages > 1 else None
        eps = cfg.layer_norm_epsilon

        def layer(group, rank, carry):
            h, k, v, state = carry
            p = _LayerRow(layer_params[group], rank)
            u = rms_norm(h, p["norm"], eps)
            if group == "mamba":
                out, state = self._mamba(p, u, state, rank, n_valid, active)
            elif group == "attn":
                take = lambda pool: jax.tree.map(lambda x: _take(x, rank), pool)  # noqa: E731
                out, k_l, v_l = self._attn(p, u, take(k), take(v), offset, paged_attn)
                with jax.named_scope("mst.kv_pool.regroup"):
                    k = jax.tree.map(lambda x, new: _put(x, rank, new), k, k_l)
                    v = jax.tree.map(lambda x, new: _put(x, rank, new), v, v_l)
            else:
                out = self._moe(p, layer_params[group], rank, u, ep_axis)
            return h + out.astype(h.dtype), k, v, state

        carry = (h, k, v, state)
        for kinds, ranks in plan:
            if len(set(kinds)) == 1:
                rank = (
                    ranks[0] if len(set(ranks)) == 1
                    else jnp.asarray(ranks, jnp.int32)[s_idx]
                )
                carry = layer(kinds[0], rank, carry)
                continue
            # the stages differ here: each takes its own kind's branch
            present = sorted({g for g in kinds if g is not None}) + (
                [None] if None in kinds else []
            )
            rank = jnp.asarray(ranks, jnp.int32)[s_idx]
            which = jnp.asarray([present.index(g) for g in kinds], jnp.int32)[s_idx]
            branches = [
                (lambda c: c) if g is None
                else (lambda c, g=g: layer(g, rank, c))
                for g in present
            ]
            carry = jax.lax.switch(which, branches, carry)
        return carry

    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.layer_norm_epsilon)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        cfg = self.config
        h = self.embed(params, x) if cfg.is_first_stage else x
        offset = cache.offset
        state = cache.state
        # position 0 has no history: whatever the buffers hold is not state
        state = jax.tree.map(lambda s: jnp.where(offset == 0, 0, s), state)
        h, k, v, state = self.run_layers(
            params["layers"], h, cache.k, cache.v, offset, state=state,
            n_valid=None if x.shape[1] == 1 else n_valid,
        )
        cache = KVCache(k=k, v=v, offset=offset, state=state)
        cache = advance(cache, x.shape[1] if n_valid is None else n_valid)
        if cfg.is_last_stage:
            return self.apply_head(params, h), cache
        return h, cache

    # -- weights -----------------------------------------------------------
    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """Stage-filtered HF tensors (``backbone.layers.<i>.mixer.*``) →
        ``{mamba, attn, moe}`` stacks. A config with an expert share loads
        only the experts it holds."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        names = {
            "mamba": {
                "norm.weight": ("norm", False),
                "mixer.in_proj.weight": ("in_proj", True),
                "mixer.conv1d.bias": ("conv_b", False),
                "mixer.dt_bias": ("dt_bias", False),
                "mixer.A_log": ("A_log", False),
                "mixer.D": ("D", False),
                "mixer.norm.weight": ("ssm_norm", False),
                "mixer.out_proj.weight": ("out_proj", True),
            },
            "attn": {
                "norm.weight": ("norm", False),
                "mixer.q_proj.weight": ("q_proj", True),
                "mixer.k_proj.weight": ("k_proj", True),
                "mixer.v_proj.weight": ("v_proj", True),
                "mixer.o_proj.weight": ("o_proj", True),
            },
            "moe": {
                "norm.weight": ("norm", False),
                "mixer.gate.weight": ("router", True),
                "mixer.gate.e_score_correction_bias": ("router_bias", False),
                "mixer.fc1_latent_proj.weight": ("latent_in", True),
                "mixer.fc2_latent_proj.weight": ("latent_out", True),
                "mixer.shared_experts.up_proj.weight": ("shared_up", True),
                "mixer.shared_experts.down_proj.weight": ("shared_down", True),
            },
        }
        # the recurrence's per-head vectors and the selection bias stay float32
        keep_f32 = ("dt_bias", "A_log", "D", "router_bias")
        base = cfg.moe_expert_share_index * cfg.n_routed_experts
        layers: dict = {}
        for group, idxs in self.layer_group_layers().items():
            idxs = [i for i in idxs if cfg.start_layer <= i < cfg.end_layer]
            if not idxs:
                continue
            out = {
                our: stack_tree([
                    fetch_weight(
                        weights, f"backbone.layers.{i}.{suffix}",
                        jnp.float32 if our in keep_f32 else dtype, tr,
                    )
                    for i in idxs
                ])
                for suffix, (our, tr) in names[group].items()
            }
            if group == "mamba":  # torch conv1d weight (C, 1, k) → (C, k)
                out["conv_w"] = jnp.stack([
                    jnp.asarray(
                        weights[f"backbone.layers.{i}.mixer.conv1d.weight"], dtype
                    ).reshape(self.conv_dim, cfg.conv_kernel)
                    for i in idxs
                ])
            if group == "moe":
                for our, which in (("w_up", "up_proj"), ("w_down", "down_proj")):
                    out[our] = stack_tree([
                        stack_tree([
                            fetch_weight(
                                weights,
                                f"backbone.layers.{i}.mixer.experts.{base + e}.{which}.weight",
                                dtype,
                            )
                            for e in range(cfg.n_routed_experts)
                        ])
                        for i in idxs
                    ])
            layers[group] = out
        params = {"layers": layers}
        if cfg.needs_embed:
            embed = first_key(weights, "backbone.embeddings.weight", "embeddings.weight")
            params["embed"] = {"weight": vocab_param(embed, dtype)}
        if cfg.needs_head:
            norm = first_key(weights, "backbone.norm_f.weight", "norm_f.weight")
            params["final_norm"] = {"weight": jnp.asarray(norm, dtype)}
            params["lm_head"] = {
                "weight": vocab_param(weights["lm_head.weight"], dtype, transpose=True)
            }
        return params

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd, di, cd = cfg.hidden_size, self.d_inner, self.conv_dim
        nh, lat = cfg.mamba_num_heads, cfg.moe_latent_size
        mi, si = cfg.moe_intermediate_size, cfg.moe_shared_expert_intermediate_size
        e = cfg.n_routed_experts
        qd = cfg.num_attention_heads * cfg.head_dim
        kvd = cfg.num_key_value_heads * cfg.head_dim
        keys = iter(jax.random.split(key, 16 * max(cfg.num_local_layers, 1) + 8))
        norm = lambda n: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

        def mamba():
            # dt = softplus(dt_bias + small) spread log-uniformly over the
            # initialisation's time_step_min..max (0.001..0.1); A in 1..16
            dt0 = jnp.exp(jax.random.uniform(
                next(keys), (nh,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            return {
                "norm": norm(hd),
                "in_proj": dense_init(next(keys), hd, 2 * di + 2 * cfg.n_groups * cfg.ssm_state_size + nh, dtype),
                "conv_w": dense_init(next(keys), cfg.conv_kernel, cd, dtype).T,
                "conv_b": (0.1 * jax.random.normal(next(keys), (cd,), jnp.float32)).astype(dtype),
                "dt_bias": jnp.log(jnp.expm1(dt0)),
                "A_log": jnp.log(jax.random.uniform(next(keys), (nh,), jnp.float32, 1.0, 16.0)),
                "D": 1.0 + 0.1 * jax.random.normal(next(keys), (nh,), jnp.float32),
                "ssm_norm": norm(di),
                "out_proj": dense_init(next(keys), di, hd, dtype),
            }

        def attn():
            return {
                "norm": norm(hd),
                "q_proj": dense_init(next(keys), hd, qd, dtype),
                "k_proj": dense_init(next(keys), hd, kvd, dtype),
                "v_proj": dense_init(next(keys), hd, kvd, dtype),
                "o_proj": dense_init(next(keys), qd, hd, dtype),
            }

        def moe():
            ku, kd = jax.random.split(next(keys))
            return {
                "norm": norm(hd),
                "router": dense_init(next(keys), hd, cfg.router_width, dtype),
                "router_bias": 0.05 * jax.random.normal(
                    next(keys), (cfg.router_width,), jnp.float32),
                "latent_in": dense_init(next(keys), hd, lat, dtype),
                "latent_out": dense_init(next(keys), lat, hd, dtype),
                "w_up": jax.vmap(lambda k_: dense_init(k_, lat, mi, dtype))(
                    jax.random.split(ku, e)),
                "w_down": jax.vmap(lambda k_: dense_init(k_, mi, lat, dtype))(
                    jax.random.split(kd, e)),
                "shared_up": dense_init(next(keys), hd, si, dtype),
                "shared_down": dense_init(next(keys), si, hd, dtype),
            }

        make = {"mamba": mamba, "attn": attn, "moe": moe}
        per: dict = {}
        for ch in cfg.hybrid_override_pattern[cfg.start_layer : cfg.end_layer]:
            per.setdefault(GROUP_OF[ch], []).append(make[GROUP_OF[ch]]())
        params = {"layers": {g: stack_layers(rows) for g, rows in per.items()}}
        if cfg.needs_embed:
            params["embed"] = {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            }
        if cfg.needs_head:
            params["final_norm"] = {"weight": norm(hd)}
            params["lm_head"] = {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)}
        return params
