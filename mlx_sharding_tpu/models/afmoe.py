"""Arcee Trinity decoder (``model_type: afmoe``).

``h0 = embed(ids) * sqrt(hidden_size)`` (``mup_enabled``). Every layer is
sandwich-normed by four RMSNorms: ``h = h + post_attn_norm(attn(input_norm(
h)))``; ``h = h + post_mlp_norm(mlp(pre_mlp_norm(h)))``. Final RMSNorm,
untied head, no bias anywhere.

- **Attention** (GQA, scale ``head_dim**-0.5``): ``q, k, v = x Wq, x Wk, x
  Wv``; the output gate ``g = x Wg`` (hidden -> heads x head_dim); ``q`` and
  ``k`` RMS-normed over the head dim, each with a learned vector. A
  ``sliding_attention`` layer rotates ``q, k`` (rotary over the whole head
  dim, split-half) and hides keys more than ``sliding_window - 1`` positions
  back; a ``full_attention`` layer applies NO rotary and sees every earlier
  key. ``out = (softmax(q k^T) v * sigmoid(g)) Wo``.
- **Dense MLP** (the first ``num_dense_layers`` layers): SwiGLU.
- **MoE** (the rest): sigmoid scores in float32 over all experts; the top
  ``num_experts_per_tok`` of ``scores + expert_bias`` are chosen, weighted by
  their own scores over their sum (``route_norm``) times ``route_scale``
  (``ops.moe.nemotron_routing``: the same gate); routed SwiGLU experts plus
  one shared SwiGLU expert on every token. The layer may hold a share of the
  routed experts (``config.AfmoeConfig``): it routes over all of them and
  computes its own experts' part.

Layers: two stacked groups (``dense``, ``moe``) walked in order by an
unrolled loop (:meth:`AfmoeModel.stage_plan`). Two kinds of K/V live side by
side (``cache.py``): a full-attention layer's rows are the cache's ``k`` /
``v`` (pages, in a paged engine), a window layer's are a per-sequence RING in
``cache.state`` (``win_k``, ``win_v``: position ``p`` at row ``p %
ring_rows``), so the loop indexes each by the layer's rank among its kind.
Both keep a row's K/V heads MERGED on the lane axis, ``(…, 1, Hkv * D)``:
the layout of the ragged kernel's page block (``ops.paged_attention``'s
``kv_heads``), which a ``(…, Hkv, D)`` pool reaches only through a relayout
of the whole pool every step. One pipeline stage only; no tensor or expert
parallelism.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import AfmoeConfig
from mlx_sharding_tpu.models.base import (
    BaseModel,
    LayerRow,
    dense_init,
    put_row,
    stack_layers,
    take_row,
)
from mlx_sharding_tpu.ops import apply_rope, rms_norm, rope_frequencies
from mlx_sharding_tpu.ops.attention import attend
from mlx_sharding_tpu.ops.moe import apply_experts, nemotron_routing

WINDOW, FULL = "sliding_attention", "full_attention"
#: queries a block of the ring attention's score matrix (a prefill chunk of
#: 512 rows over a 5120-row ring is 0.5 GB of float32 scores unblocked)
RING_Q_BLOCK = 128


def ring_positions(n: int, last):
    """The position each of a ring's ``n`` rows holds once position ``last``
    is written: the latest ``p <= last`` with ``p % n == row`` (negative:
    never written)."""
    r = jnp.arange(n)
    return r + n * ((last - r) // n)


def ring_attention(q, k_ring, v_ring, offset, window: int, scale: float):
    """Windowed causal attention of ``q (B, T, Hq, D)`` at positions
    ``offset ..`` over a ring ``(B, N, Hkv, D)`` that already holds them.
    Rows of a padded chunk past its valid ones sit at positions no valid
    query sees yet. Plain XLA, float32 scores, in blocks of queries."""
    b, t, hq, d = q.shape
    n, hkv = k_ring.shape[1], k_ring.shape[2]
    k_pos = ring_positions(n, offset + t - 1)

    def block(args):
        qb, q_pos = args  # (B, Tb, Hq, D), (Tb,)
        qg = qb.reshape(b, -1, hkv, hq // hkv, d)
        s = jnp.einsum(
            "bthgd,bshd->bhgts", qg, k_ring, preferred_element_type=jnp.float32
        ) * scale
        seen = (
            (k_pos[None, :] <= q_pos[:, None])
            & (k_pos[None, :] > q_pos[:, None] - window)
            & (k_pos[None, :] >= 0)
        )
        s = jnp.where(seen[None, None, None], s, -jnp.inf)
        out = jnp.einsum(
            "bhgts,bshd->bthgd", jax.nn.softmax(s, axis=-1).astype(v_ring.dtype),
            v_ring, preferred_element_type=jnp.float32,
        )
        return out.reshape(b, -1, hq, d).astype(q.dtype)

    q_pos = offset + jnp.arange(t)
    if t <= RING_Q_BLOCK or t % RING_Q_BLOCK:
        return block((q, q_pos))
    nb = t // RING_Q_BLOCK
    out = jax.lax.map(
        block,
        (jnp.moveaxis(q.reshape(b, nb, RING_Q_BLOCK, hq, d), 1, 0),
         q_pos.reshape(nb, RING_Q_BLOCK)),
    )
    return jnp.moveaxis(out, 0, 1).reshape(b, t, hq, d)


class AfmoeModel(BaseModel):
    #: engines keep the window layers' K/V as per-slot rings in
    #: cache.KVCache.state; whatever moves or re-enters a sequence as
    #: full-length pages cannot serve this model (cache.refuse_recurrent)
    has_window_layers = True

    def __init__(self, config: AfmoeConfig):
        super().__init__(config)
        self.scale = config.head_dim**-0.5
        self.inv_freq = jnp.asarray(
            rope_frequencies(config.head_dim, config.rope_theta, None)
        )

    # -- layer structure ---------------------------------------------------
    def layer_group_ranges(self) -> dict:
        cfg = self.config
        nd, n = cfg.num_dense_layers, cfg.num_hidden_layers
        ranges = {"dense": (0, nd), "moe": (nd, n)}
        return {g: r for g, r in ranges.items() if r[1] > r[0]}

    def packed_keep_dense_re(self) -> str | None:
        return r"mlp\.router\.gate\.weight$"

    def _one_stage(self, stage_bounds) -> tuple:
        if len(stage_bounds) != 1:
            raise ValueError(
                "pipeline stages are not wired for afmoe: its window layers' "
                "rings belong to one stage (run it with --num-stages 1)"
            )
        return tuple(stage_bounds[0])

    def stage_plan(self, stage_bounds) -> list:
        """The layer walk of the one stage: ``[(group, rank in the group's
        stack, window layer?, rank among the layers of its attention kind)]``
        — the last indexes ``cache.state``'s rings or the K/V buffers."""
        s, e = self._one_stage(stage_bounds)
        cfg = self.config
        seen = {WINDOW: 0, FULL: 0}
        plan = []
        for i in range(s, e):
            kind = cfg.layer_types[i]
            dense = i < cfg.num_dense_layers
            first = s if dense else max(s, cfg.num_dense_layers)
            plan.append(("dense" if dense else "moe", i - first, kind == WINDOW, seen[kind]))
            seen[kind] += 1
        return plan

    def _count(self, kind: str, s: int, e: int) -> int:
        return sum(t == kind for t in self.config.layer_types[s:e])

    def kv_layer_slots(self, stage_bounds) -> int:
        """Layers with full-length K/V rows (the engine's pool layers)."""
        return self._count(FULL, *self._one_stage(stage_bounds))

    def state_layer_slots(self, stage_bounds) -> int:
        """Layers whose K/V is a ring in ``cache.state``."""
        return self._count(WINDOW, *self._one_stage(stage_bounds))

    # -- cache and state ---------------------------------------------------
    def cache_num_heads(self) -> int:
        return 1  # a row's heads are merged on the lane axis

    def cache_head_dim(self):
        return self.config.num_key_value_heads * self.config.head_dim

    def state_shapes(self, batch: int, ring_rows: int) -> dict:
        """Per window layer and sequence: {name: (shape after (layer,), dtype)}."""
        shape = (batch, ring_rows, 1, self.cache_head_dim())
        return {"win_k": (shape, None), "win_v": (shape, None)}

    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        """Single-stream cache: full layers' rows, and rings as long as the
        context (nothing wraps; an engine sizes real rings)."""
        cfg = self.config
        s, e = cfg.start_layer, cfg.end_layer
        kv = init_cache(
            self._count(FULL, s, e), batch, max_seq, 1, self.cache_head_dim(), dtype
        )
        n_win = self._count(WINDOW, s, e)
        return kv._replace(state={
            name: jnp.zeros((n_win, *shape), dt or dtype)
            for name, (shape, dt) in self.state_shapes(batch, max_seq).items()
        })

    # -- the layer's halves ------------------------------------------------
    def _head_norm(self, x, w):
        """RMSNorm over the head dim with a learned vector (QK-norm)."""
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.config.rms_norm_eps
        )
        return (x32 * w.astype(jnp.float32)).astype(x.dtype)

    def _attn(self, p, u, is_win, k_buf, v_buf, offset, paged_attn, ring=None):
        """``k_buf``/``v_buf``: a full layer's rows ``(B, S, 1, Hkv * D)`` or
        a window layer's ring ``(B, N, 1, Hkv * D)`` — or, with ``paged_attn``
        (an engine's ragged decode), the layer's page pool, or every window
        layer's ring pool with ``ring`` this layer's place in it: the hook
        writes and attends over it where it lies."""
        cfg = self.config
        b, t, _ = u.shape
        d = cfg.head_dim
        with jax.named_scope("mst.attn.qkv"):
            q = self._linear(u, p["q_proj"]).reshape(b, t, -1, d)
            k = self._linear(u, p["k_proj"]).reshape(b, t, -1, d)
            v = self._linear(u, p["v_proj"]).reshape(b, t, -1, d)
        with jax.named_scope("mst.attn.gate"):
            gate = self._linear(u, p["attn_gate"])
        with jax.named_scope("mst.attn.qk_norm"):
            q = self._head_norm(q, p["q_norm"])
            k = self._head_norm(k, p["k_norm"])
        if is_win:  # rotary on the window layers only
            with jax.named_scope("mst.attn.qkv"):
                q = apply_rope(q, self.inv_freq, offset)
                k = apply_rope(k, self.inv_freq, offset)
        window = cfg.sliding_window if is_win else None
        hkv = cfg.num_key_value_heads
        merge = lambda x: x.reshape(b, t, 1, hkv * d)  # noqa: E731
        heads = lambda x: x.reshape(*x.shape[:2], hkv, d)  # noqa: E731
        if paged_attn is not None:
            attn_fn, done = paged_attn(
                k_buf, v_buf, ring=ring,
                scope="mst.attn.window" if is_win else "mst.attn.full",
            )
            attn = attn_fn(q, merge(k), merge(v), sliding_window=window, kv_heads=hkv)
            k_buf, v_buf = done["k"], done["v"]
        elif is_win:
            with jax.named_scope("mst.attn.kv_write"):
                rows = (offset + jnp.arange(t)) % k_buf.shape[1]
                k_buf = k_buf.at[:, rows].set(merge(k).astype(k_buf.dtype))
                v_buf = v_buf.at[:, rows].set(merge(v).astype(v_buf.dtype))
            with jax.named_scope("mst.attn.window"):
                attn = ring_attention(
                    q, heads(k_buf), heads(v_buf), offset, window, self.scale
                )
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, merge(k), merge(v), offset)
            with jax.named_scope("mst.attn.full"):
                attn = attend(q, heads(k_buf), heads(v_buf), offset, self.scale)
        with jax.named_scope("mst.attn.gate"):
            attn = attn.reshape(b, t, -1) * jax.nn.sigmoid(
                gate.astype(jnp.float32)
            ).astype(attn.dtype)
        with jax.named_scope("mst.attn.core"):
            return self._linear(attn, p["o_proj"]), k_buf, v_buf

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
            flat, p["router"], p["router_bias"], cfg.num_experts_per_tok,
            norm_topk_prob=cfg.route_norm, routed_scaling_factor=cfg.route_scale,
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
        """The stage's layers in order. ``k``/``v`` hold its full-attention
        layers' rows, ``state`` its window layers' rings (leading axis: the
        layer's rank among its kind). ``mask``, ``n_valid`` and ``active``
        are unused: the plan visits no padding row, and a ring row written
        by a padded or inactive query holds a position no valid query sees.
        Returns ``(h, k, v, state)``."""
        if tp_axis is not None or ep_axis is not None:
            raise ValueError("tensor and expert parallelism are not wired for afmoe")
        cfg = self.config
        if plan is None:
            plan = self.stage_plan([(cfg.start_layer, cfg.end_layer)])
        eps = cfg.rms_norm_eps
        for group, rank, is_win, crank in plan:
            p = LayerRow(layer_params[group], rank)
            u = rms_norm(h, p["input_norm"], eps)
            ring = crank if is_win and paged_attn is not None else None
            if ring is not None:  # the hook reads the ring pool where it lies
                k_l, v_l = state["win_k"], state["win_v"]
            elif is_win:
                with jax.named_scope("mst.kv_ring.regroup"):
                    k_l, v_l = take_row(state["win_k"], crank), take_row(state["win_v"], crank)
            else:
                with jax.named_scope("mst.kv_pool.regroup"):
                    k_l = jax.tree.map(lambda x: take_row(x, crank), k)
                    v_l = jax.tree.map(lambda x: take_row(x, crank), v)
            out, k_l, v_l = self._attn(p, u, is_win, k_l, v_l, offset, paged_attn, ring)
            if ring is not None:
                state = {"win_k": k_l, "win_v": v_l}
            elif is_win:
                with jax.named_scope("mst.kv_ring.regroup"):
                    state = {
                        "win_k": put_row(state["win_k"], crank, k_l),
                        "win_v": put_row(state["win_v"], crank, v_l),
                    }
            else:
                with jax.named_scope("mst.kv_pool.regroup"):
                    k = jax.tree.map(lambda x, new: put_row(x, crank, new), k, k_l)
                    v = jax.tree.map(lambda x, new: put_row(x, crank, new), v, v_l)
            h = h + rms_norm(out, p["post_attn_norm"], eps).astype(h.dtype)
            u = rms_norm(h, p["pre_mlp_norm"], eps)
            if group == "dense":
                with jax.named_scope("mst.mlp.dense"):
                    out = self._swiglu(u, p["gate_proj"], p["up_proj"], p["down_proj"])
            else:
                out = self._moe(p, layer_params[group], rank, u)
            h = h + rms_norm(out, p["post_mlp_norm"], eps).astype(h.dtype)
        return h, k, v, state

    def embed_transform(self, h):
        if not self.config.mup_enabled:
            return h
        return h * jnp.asarray(self.config.hidden_size**0.5, h.dtype)

    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        cfg = self.config
        h = self.embed(params, x) if cfg.is_first_stage else x
        offset = cache.offset
        h, k, v, state = self.run_layers(
            params["layers"], h, cache.k, cache.v, offset, state=cache.state
        )
        cache = KVCache(k=k, v=v, offset=offset, state=state)
        cache = advance(cache, x.shape[1] if n_valid is None else n_valid)
        if cfg.is_last_stage:
            return self.apply_head(params, h), cache
        return h, cache

    # -- weights -----------------------------------------------------------
    ATTN_NAMES = {
        "input_layernorm.weight": ("input_norm", False),
        "post_attention_layernorm.weight": ("post_attn_norm", False),
        "pre_mlp_layernorm.weight": ("pre_mlp_norm", False),
        "post_mlp_layernorm.weight": ("post_mlp_norm", False),
        "self_attn.q_proj.weight": ("q_proj", True),
        "self_attn.k_proj.weight": ("k_proj", True),
        "self_attn.v_proj.weight": ("v_proj", True),
        "self_attn.gate_proj.weight": ("attn_gate", True),
        "self_attn.o_proj.weight": ("o_proj", True),
        "self_attn.q_norm.weight": ("q_norm", False),
        "self_attn.k_norm.weight": ("k_norm", False),
    }
    MLP_NAMES = {
        "dense": {
            "mlp.gate_proj.weight": ("gate_proj", True),
            "mlp.up_proj.weight": ("up_proj", True),
            "mlp.down_proj.weight": ("down_proj", True),
        },
        "moe": {
            "mlp.router.gate.weight": ("router", True),
            "mlp.expert_bias": ("router_bias", False),
            "mlp.shared_experts.gate_proj.weight": ("shared_gate", True),
            "mlp.shared_experts.up_proj.weight": ("shared_up", True),
            "mlp.shared_experts.down_proj.weight": ("shared_down", True),
        },
    }

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """Stage-filtered HF tensors (``model.layers.<i>.*``) → ``{dense,
        moe}`` stacks. A config with an expert share loads only the experts
        it holds; the selection bias stays float32."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        base = cfg.moe_expert_share_index * cfg.num_experts
        layers: dict = {}
        for group, (g0, g1) in self.layer_group_ranges().items():
            idxs = range(max(g0, cfg.start_layer), min(g1, cfg.end_layer))
            if not idxs:
                continue
            out = {
                our: stack_tree([
                    fetch_weight(
                        weights, f"model.layers.{i}.{suffix}",
                        jnp.float32 if our == "router_bias" else dtype, tr,
                    )
                    for i in idxs
                ])
                for suffix, (our, tr) in {**self.ATTN_NAMES, **self.MLP_NAMES[group]}.items()
            }
            if group == "moe":
                for our, which in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                   ("w_down", "down_proj")):
                    out[our] = stack_tree([
                        stack_tree([
                            fetch_weight(
                                weights,
                                f"model.layers.{i}.mlp.experts.{base + e}.{which}.weight",
                                dtype, True,
                            )
                            for e in range(cfg.num_experts)
                        ])
                        for i in idxs
                    ])
            layers[group] = out
        params = {"layers": layers}
        if cfg.needs_embed:
            embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
            params["embed"] = {"weight": vocab_param(embed, dtype)}
        if cfg.needs_head:
            norm = first_key(weights, "model.norm.weight", "norm.weight")
            params["final_norm"] = {"weight": jnp.asarray(norm, dtype)}
            params["lm_head"] = {
                "weight": vocab_param(weights["lm_head.weight"], dtype, transpose=True)
            }
        return params

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd, d = cfg.hidden_size, cfg.head_dim
        qd, kvd = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        mi, e = cfg.moe_intermediate_size, cfg.num_experts
        keys = iter(jax.random.split(key, 24 * max(cfg.num_local_layers, 1) + 8))
        norm = lambda n: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

        def layer(dense: bool):
            out = {
                "input_norm": norm(hd), "post_attn_norm": norm(hd),
                "pre_mlp_norm": norm(hd), "post_mlp_norm": norm(hd),
                "q_proj": dense_init(next(keys), hd, qd, dtype),
                "k_proj": dense_init(next(keys), hd, kvd, dtype),
                "v_proj": dense_init(next(keys), hd, kvd, dtype),
                "attn_gate": dense_init(next(keys), hd, qd, dtype),
                "o_proj": dense_init(next(keys), qd, hd, dtype),
                "q_norm": norm(d), "k_norm": norm(d),
            }
            if dense:
                out.update(
                    gate_proj=dense_init(next(keys), hd, cfg.intermediate_size, dtype),
                    up_proj=dense_init(next(keys), hd, cfg.intermediate_size, dtype),
                    down_proj=dense_init(next(keys), cfg.intermediate_size, hd, dtype),
                )
                return out
            kg, ku, kd = jax.random.split(next(keys), 3)
            stack = lambda k_, a, b: jax.vmap(  # noqa: E731
                lambda kk: dense_init(kk, a, b, dtype))(jax.random.split(k_, e))
            out.update(
                router=dense_init(next(keys), hd, cfg.router_width, dtype),
                router_bias=0.05 * jax.random.normal(
                    next(keys), (cfg.router_width,), jnp.float32),
                shared_gate=dense_init(next(keys), hd, mi, dtype),
                shared_up=dense_init(next(keys), hd, mi, dtype),
                shared_down=dense_init(next(keys), mi, hd, dtype),
                w_gate=stack(kg, hd, mi), w_up=stack(ku, hd, mi),
                w_down=stack(kd, mi, hd),
            )
            return out

        per: dict = {}
        for i in range(cfg.start_layer, cfg.end_layer):
            dense = i < cfg.num_dense_layers
            per.setdefault("dense" if dense else "moe", []).append(layer(dense))
        params = {"layers": {g: stack_layers(rows) for g, rows in per.items()}}
        if cfg.needs_embed:
            params["embed"] = {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            }
        if cfg.needs_head:
            params["final_norm"] = {"weight": norm(hd)}
            params["lm_head"] = {"weight": dense_init(next(keys), hd, cfg.vocab_size, dtype)}
        return params
