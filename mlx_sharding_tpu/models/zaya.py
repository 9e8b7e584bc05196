"""Zyphra ZAYA1 decoder (``model_type: zaya``).

Every layer is two sub-layers, each ``x = x + scale * f(rmsnorm(x))`` with a
learned ``hidden_size`` vector ``scale`` on its output. Final RMSNorm, head
tied to the embedding, no bias but the convolutions'.

- **Attention in a compressed latent** (Compressed Convolutional Attention,
  arXiv:2510.04476). ``q~ = h Wq`` (hidden -> ``Hq x D``, half the hidden
  size), ``k~ = h Wk`` (hidden -> ``Hkv x D``). The packed ``u = [q~ ; k~]``
  passes two causal convolutions over time, zero before position 0: ``c1_t =
  a0 * u_{t-1} + a1 * u_t + b`` (depthwise) and ``c2_t = B0 c1_{t-1} + B1
  c1_t + b'`` (each head's ``D`` channels mix among themselves). The
  PRE-convolution mean of q and k is added back per head: ``m_q = (q~ +
  rep(k~)) / 2``, ``m_k`` its mean over a K/V head's query heads. ``q`` and
  ``k`` are L2-normalised to length ``sqrt(D)`` per head, ``k`` times a
  learned temperature a K/V head. Values: ``v_t = [h_t Wv1 ; h_{t-1} Wv2]``,
  half of the channels from the PREVIOUS token. Rotary on the first
  ``partial_rotary_factor`` of each head, GQA softmax attention at scale
  ``D**-0.5``, ``o_proj`` back to the hidden size.
- **MoE** (arXiv:2511.17127). ``r = h Wd`` (hidden -> ``router_hidden_size``);
  the router's state runs DOWN the layers, ``s_l = r_l + g_l * s_{l-1}``
  (``s_{-1} = 0``); ``z = W3 gelu(W2 gelu(W1 rmsnorm(s_l)))``;
  ``ops.moe.biased_softmax_routing`` picks the top ``num_experts_per_tok`` of
  ``softmax(z) + bias`` and weighs by the probability; SwiGLU experts, no
  shared expert. The layer may hold a share of the routed experts
  (``config.ZayaConfig``): it routes over all of them and computes its own
  experts' part.

What a sequence keeps beside its K/V rows (``cache.KVCache.state``), per
layer: the last pre-convolution row ``u_{t-1}``, the last first-convolution
row ``c1_{t-1}`` and the previous token's ``h_{t-1} Wv2``. A prefill chunk
starts from them and leaves them; position 0 starts from zero.

Layers: one stacked group, one ``lax.scan`` over the layers' indices (each
small leaf sliced where it is used); the router's state is part of the
scan's carry beside the hidden state. K/V rows keep their heads MERGED
on the lane axis, ``(…, 1, Hkv * D)``, as ``models/afmoe.py`` says why. With
the engine's pool attention (ragged decode) the page pool rides the carry
whole and a layer is an offset into the page table; otherwise each layer's
contiguous rows ride the scan as ``xs`` / ``ys``. The expert stacks stay
whole beside the scan and are read by ``(layer, expert)``. One pipeline
stage only: neither the router's state nor the slot state crosses a stage,
tensor or expert boundary yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import ZayaConfig
from mlx_sharding_tpu.models.base import (
    LAYER_INDEX,
    BaseModel,
    LayerRow,
    dense_init,
    stack_layers,
)
from mlx_sharding_tpu.ops import apply_rope, causal_attention, rms_norm, rope_frequencies
from mlx_sharding_tpu.ops.moe import apply_experts, biased_softmax_routing

EXPERT_STACKS = ("w_gate", "w_up", "w_down")
_F32 = jnp.float32


class _LayerLeaves(LayerRow):
    """A layer's leaves inside the layer scan, which counts the layers and
    closes over the whole stacks: a small leaf is sliced where it is used
    (``models.base.LayerRow`` says why), the expert stacks stay whole beside
    ``p[LAYER_INDEX]``, to be read by ``(layer, expert)`` where they lie."""

    def __getitem__(self, name):
        if name == LAYER_INDEX:
            return self.rank
        if name in EXPERT_STACKS:
            return self.stacks[name]
        return super().__getitem__(name)


class ZayaModel(BaseModel):
    #: engines carry the convolutions' tails and the shifted value of every
    #: layer per slot beside the K/V pages (cache.KVCache.state); whatever
    #: re-enters a sequence from pages alone cannot serve this model
    #: (cache.refuse_recurrent)
    has_recurrent_state = True

    def __init__(self, config: ZayaConfig):
        super().__init__(config)
        d = config.head_dim
        self.scale = d**-0.5
        self.rot_dim = int(d * config.partial_rotary_factor)
        self.inv_freq = jnp.asarray(
            rope_frequencies(self.rot_dim, config.rope_theta, None)
        )
        self.q_dim = config.num_attention_heads * d
        self.kv_dim = config.num_key_value_heads * d
        self.mix_dim = self.q_dim + self.kv_dim  # channels the convolutions see

    # -- layer structure ---------------------------------------------------
    def scan_in_place(self, group, stack: dict) -> tuple:
        return EXPERT_STACKS

    def packed_keep_dense_re(self) -> str | None:
        return r"(router|conv_qk)\."

    def _one_stage(self, stage_bounds) -> tuple:
        if len(stage_bounds) != 1:
            raise ValueError(
                "pipeline stages are not wired for zaya: the router's state "
                "and the per-slot convolution state belong to one stage (run "
                "it with --num-stages 1)"
            )
        return tuple(stage_bounds[0])

    def stage_plan(self, stage_bounds) -> tuple:
        """The one stage's ``(start, end)``: one uniform group, no walk to
        plan — asked for by an engine that carries ``cache.state``."""
        return self._one_stage(stage_bounds)

    def state_layer_slots(self, stage_bounds) -> int:
        s, e = self._one_stage(stage_bounds)
        return e - s

    # -- cache and state ---------------------------------------------------
    def cache_num_heads(self) -> int:
        return 1  # a row's heads are merged on the lane axis

    def cache_head_dim(self):
        return self.kv_dim

    def state_shapes(self, batch: int) -> dict:
        """Per layer and sequence: {name: (shape after (layer,), dtype)}."""
        return {
            "cca_u": ((batch, self.mix_dim), None),
            "cca_c1": ((batch, self.mix_dim), None),
            "v_prev": ((batch, self.kv_dim // 2), None),
        }

    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        n = self.config.num_local_layers
        kv = init_cache(n, batch, max_seq, 1, self.kv_dim, dtype)
        return kv._replace(state={
            name: jnp.zeros((n, *shape), dt or dtype)
            for name, (shape, dt) in self.state_shapes(batch).items()
        })

    # -- the layer's halves ------------------------------------------------
    def _partial_rope(self, x, offset):
        r = self.rot_dim
        return jnp.concatenate(
            [apply_rope(x[..., :r], self.inv_freq, offset), x[..., r:]], axis=-1
        )

    def _attn(self, p, x, st, k_buf, v_buf, offset, n_valid, active, paged):
        """``x (B, T, hidden)``; ``st`` the layer's state rows of these ``B``
        sequences; ``k_buf`` / ``v_buf`` the layer's rows ``(B, S, 1, Hkv *
        D)``, or with ``paged`` (the engine's ``(attn_fn, done)`` over the
        pool where it lies) unused. Rows past ``n_valid`` and sequences
        outside ``active`` leave the state as it was. Returns ``(out, k_buf,
        v_buf, st)``."""
        cfg = self.config
        b, t, _ = x.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        g, nh = hq // hkv, hq + hkv
        h = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("mst.attn.qkv"):
            u = jnp.concatenate(
                [self._linear(h, p["q_proj"]), self._linear(h, p["k_proj"])], axis=-1
            )
            v_now = self._linear(h, p["v1_proj"])
            v_next = self._linear(h, p["v2_proj"])  # the NEXT token's half
        # [the row before this call, this call's rows]: row i is what row i
        # of the call sees one step back, row ``end`` what the next call does
        behind = lambda tail, rows: jnp.concatenate(  # noqa: E731
            [tail[:, None].astype(rows.dtype), rows], axis=1
        )
        with jax.named_scope("mst.attn.cca_mix"):
            u_seq = behind(st["cca_u"], u)
            w0 = p["conv0_w"].astype(_F32)  # (2, C): one pair of taps a channel
            c1 = (
                w0[0] * u_seq[:, :-1].astype(_F32) + w0[1] * u.astype(_F32)
                + p["conv0_b"].astype(_F32)
            ).astype(x.dtype)
            c1_seq = behind(st["cca_c1"], c1)
            w1 = p["conv1_w"].astype(_F32).reshape(2, nh, d, d)  # (tap, head, in, out)
            heads = lambda z: z.reshape(b, t, nh, d).astype(_F32)  # noqa: E731
            c2 = (
                jnp.einsum("bthi,hio->btho", heads(c1_seq[:, :-1]), w1[0])
                + jnp.einsum("bthi,hio->btho", heads(c1), w1[1])
                + p["conv1_b"].astype(_F32).reshape(nh, d)
            )
            uh = heads(u)
            m_q = 0.5 * (uh[:, :, :hq] + jnp.repeat(uh[:, :, hq:], g, axis=2))
            m_k = m_q.reshape(b, t, hkv, g, d).mean(axis=3)
            unit = lambda z: z * jax.lax.rsqrt(  # noqa: E731
                jnp.sum(z * z, axis=-1, keepdims=True) + 1e-12
            ) * d**0.5
            q = unit(c2[:, :, :hq] + m_q).astype(x.dtype)
            k = (
                unit(c2[:, :, hq:] + m_k) * p["k_temp"].astype(_F32)[:, None]
            ).astype(x.dtype)
            v_seq = behind(st["v_prev"], v_next)
            v = jnp.concatenate([v_now, v_seq[:, :-1]], axis=-1)
            q = self._partial_rope(q, offset)
            k = self._partial_rope(k, offset)
        with jax.named_scope("mst.state_pool.regroup"):
            end = t if n_valid is None else n_valid
            new = {"cca_u": u_seq, "cca_c1": c1_seq, "v_prev": v_seq}
            new = {
                name: jax.lax.dynamic_index_in_dim(seq, end, 1, keepdims=False)
                .astype(st[name].dtype)
                for name, seq in new.items()
            }
            if active is not None:
                new = {
                    name: jnp.where(active[:, None], row, st[name])
                    for name, row in new.items()
                }
        merge = lambda z: z.reshape(b, t, 1, hkv * d)  # noqa: E731
        if paged is not None:
            attn_fn, done = paged
            attn = attn_fn(q, merge(k), merge(v), kv_heads=hkv)
            k_buf, v_buf = done["k"], done["v"]
        else:
            k_buf, v_buf = write_layer_kv(k_buf, v_buf, merge(k), merge(v), offset)
            split = lambda z: z.reshape(*z.shape[:2], hkv, d)  # noqa: E731
            attn = causal_attention(q, split(k_buf), split(v_buf), offset, self.scale)
        with jax.named_scope("mst.attn.qkv"):
            out = self._linear(attn.reshape(b, t, hq * d), p["o_proj"])
        return out, k_buf, v_buf, new

    @jax.named_scope("mst.moe.router")
    def _router_logits(self, p, flat, s_prev):
        """``(logits (N, experts), s (N, router_hidden_size))``, float32."""
        w = lambda name: p[name].astype(_F32)  # noqa: E731
        s = flat.astype(_F32) @ w("router_down") + w("router_gate") * s_prev
        z = s * jax.lax.rsqrt(
            jnp.mean(s * s, axis=-1, keepdims=True) + self.config.rms_norm_eps
        ) * w("router_norm")
        gelu = lambda a: jax.nn.gelu(a, approximate=False)  # noqa: E731
        return gelu(gelu(z @ w("router_w1")) @ w("router_w2")) @ w("router_w3"), s

    def _moe(self, p, x, s_prev):
        """``p``: the layer's small leaves, the whole ``(L, E, …)`` expert
        stacks and ``p[LAYER_INDEX]`` to read them by. Returns ``(out,
        s)``."""
        cfg = self.config
        b, t, hidden = x.shape
        flat = rms_norm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(b * t, hidden)
        logits, s = self._router_logits(
            p, flat, s_prev.reshape(b * t, cfg.router_hidden_size)
        )
        weights, idx = biased_softmax_routing(
            logits, p["router_bias"], cfg.num_experts_per_tok
        )
        out = apply_experts(
            flat, weights, idx, p["w_gate"], p["w_up"], p["w_down"],
            group_size=self._gs, bits=self._bits,
            expert_base=(
                cfg.moe_expert_share_index * cfg.num_experts
                if cfg.moe_expert_share > 1 else None
            ),
            layer=p[LAYER_INDEX],
        )
        return out.reshape(b, t, hidden), s.reshape(b, t, -1)

    def _layer(self, p, x, s, st, k_buf, v_buf, offset, n_valid, active, paged):
        out, k_buf, v_buf, st = self._attn(
            p, x, st, k_buf, v_buf, offset, n_valid, active, paged
        )
        x = x + (out * p["attn_scale"]).astype(x.dtype)
        out, s = self._moe(p, x, s)
        return x + (out * p["moe_scale"]).astype(x.dtype), s, k_buf, v_buf, st

    # -- the layer scan ----------------------------------------------------
    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None, *, state=None, n_valid=None, active=None, plan=None,
        stage_axis=None, paged_attn=None,
    ):
        """The stage's layers, one scan. ``state`` holds every layer's rows
        of the ``B`` sequences of ``h`` (and, from an engine's ragged decode,
        a scratch row past them that is neither read nor written). ``k`` /
        ``v``: every layer's contiguous rows ``(L, B, S, 1, Hkv * D)``,
        scanned per layer — or, with ``paged_attn``, the engine's page pool,
        carried whole. ``mask``, ``plan`` and ``stage_axis`` are unused: one
        stage has no padding layer. Returns ``(h, k, v, state)``."""
        if tp_axis is not None or ep_axis is not None:
            raise ValueError("tensor and expert parallelism are not wired for zaya")
        nb = h.shape[0]
        index = jnp.arange(jax.tree.leaves(layer_params)[0].shape[0])
        s0 = jnp.zeros((*h.shape[:2], self.config.router_hidden_size), _F32)

        def layer(h, s, i, st_l, k_l, v_l, paged):
            st = jax.tree.map(lambda x: x[:nb], st_l)
            h, s, k_l, v_l, st = self._layer(
                _LayerLeaves(layer_params, i), h, s, st, k_l, v_l, offset,
                n_valid, active, paged,
            )
            with jax.named_scope("mst.state_pool.regroup"):
                st_l = jax.tree.map(
                    lambda x, new: jax.lax.dynamic_update_slice_in_dim(x, new, 0, 0),
                    st_l, st,
                )
            return h, s, k_l, v_l, st_l

        def rows_scanned(carry, xs):
            i, st_l, k_l, v_l = xs
            h, s, k_l, v_l, st_l = layer(*carry, i, st_l, k_l, v_l, None)
            return (h, s), (st_l, k_l, v_l)

        def pool_carried(carry, xs):
            h, s, k, v = carry
            i, st_l = xs
            h, s, k, v, st_l = layer(h, s, i, st_l, k, v, paged_attn(k, v, layer=i))
            return (h, s, k, v), st_l

        if paged_attn is None:
            # this scan's own work is slicing each layer's rows out of the
            # stack and stacking them back (models/base.py says which bodies)
            with jax.named_scope("mst.kv_pool.regroup"):
                (h, _), (state, k, v) = jax.lax.scan(
                    rows_scanned, (h, s0), (index, state, k, v)
                )
        else:
            (h, _, k, v), state = jax.lax.scan(
                pool_carried, (h, s0, k, v), (index, state)
            )
        return h, k, v, state

    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

    def __call__(self, params, x, cache: KVCache, n_valid=None):
        cfg = self.config
        h = self.embed(params, x) if cfg.is_first_stage else x
        offset = cache.offset
        # position 0 has no history: whatever the buffers hold is not state
        state = jax.tree.map(lambda s: jnp.where(offset == 0, 0, s), cache.state)
        h, k, v, state = self.run_layers(
            params["layers"], h, cache.k, cache.v, offset, state=state,
            n_valid=n_valid,
        )
        cache = KVCache(k=k, v=v, offset=offset, state=state)
        cache = advance(cache, x.shape[1] if n_valid is None else n_valid)
        if cfg.is_last_stage:
            return self.apply_head(params, h), cache
        return h, cache

    # -- weights -----------------------------------------------------------
    #: checkpoint suffix -> (our leaf, transposed to (in, out)?). The catalog
    #: gives the family's config.json, not its tensor names: these follow the
    #: layer's description above and are ASSUMED.
    NAMES = {
        "input_layernorm.weight": ("attn_norm", False),
        "self_attn.q_proj.weight": ("q_proj", True),
        "self_attn.k_proj.weight": ("k_proj", True),
        "self_attn.v_proj.weight": ("v1_proj", True),
        "self_attn.v_shift_proj.weight": ("v2_proj", True),
        "self_attn.o_proj.weight": ("o_proj", True),
        "self_attn.conv_qk.0.bias": ("conv0_b", False),
        "self_attn.conv_qk.1.bias": ("conv1_b", False),
        "self_attn.temp": ("k_temp", False),
        "attn_res_scale": ("attn_scale", False),
        "post_attention_layernorm.weight": ("moe_norm", False),
        "mlp.router.down_proj.weight": ("router_down", True),
        "mlp.router.depth_gate": ("router_gate", False),
        "mlp.router.norm.weight": ("router_norm", False),
        "mlp.router.mlp.0.weight": ("router_w1", True),
        "mlp.router.mlp.1.weight": ("router_w2", True),
        "mlp.router.mlp.2.weight": ("router_w3", True),
        "mlp.router.balancing_bias": ("router_bias", False),
        "mlp_res_scale": ("moe_scale", False),
    }

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """Stage-filtered tensors (``model.layers.<i>.*``) → one stack. The
        convolutions arrive as torch ``Conv1d`` weights: the depthwise one
        ``(C, 1, 2)`` → ``(2, C)``, the grouped one ``(C, D, 2)`` → ``(2 *
        heads, D in, D out)``. A config with an expert share loads only the
        experts it holds; the balancing bias stays float32."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        d, nh = cfg.head_dim, cfg.num_attention_heads + cfg.num_key_value_heads
        base = cfg.moe_expert_share_index * cfg.num_experts
        idxs = range(cfg.start_layer, cfg.end_layer)
        pre = "model.layers.{}.".format
        layers = {
            our: stack_tree([
                fetch_weight(
                    weights, pre(i) + suffix,
                    jnp.float32 if our == "router_bias" else dtype, tr,
                )
                for i in idxs
            ])
            for suffix, (our, tr) in self.NAMES.items()
        }
        conv = lambda i, j: jnp.asarray(  # noqa: E731
            weights[pre(i) + f"self_attn.conv_qk.{j}.weight"], dtype
        )
        layers["conv0_w"] = jnp.stack([conv(i, 0)[:, 0, :].T for i in idxs])
        layers["conv1_w"] = jnp.stack([
            conv(i, 1).reshape(nh, d, d, 2).transpose(3, 0, 2, 1).reshape(2 * nh, d, d)
            for i in idxs
        ])
        for our, which in zip(EXPERT_STACKS, ("gate_proj", "up_proj", "down_proj")):
            layers[our] = stack_tree([
                stack_tree([
                    fetch_weight(
                        weights, pre(i) + f"mlp.experts.{base + e}.{which}.weight",
                        dtype, True,
                    )
                    for e in range(cfg.num_experts)
                ])
                for i in idxs
            ])
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
        hd, d, rh = cfg.hidden_size, cfg.head_dim, cfg.router_hidden_size
        nh = cfg.num_attention_heads + cfg.num_key_value_heads
        mi, e = cfg.moe_intermediate_size, cfg.num_experts
        keys = iter(jax.random.split(key, 32 * max(cfg.num_local_layers, 1) + 4))
        near = lambda n, at=1.0, dt=dtype: (  # noqa: E731
            at + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dt)
        stack = lambda a, b, n: jax.vmap(  # noqa: E731
            lambda kk: dense_init(kk, a, b, dtype))(jax.random.split(next(keys), n))

        def layer():
            return {
                "attn_norm": near(hd), "moe_norm": near(hd),
                "attn_scale": near(hd), "moe_scale": near(hd),
                "q_proj": dense_init(next(keys), hd, self.q_dim, dtype),
                "k_proj": dense_init(next(keys), hd, self.kv_dim, dtype),
                "v1_proj": dense_init(next(keys), hd, self.kv_dim // 2, dtype),
                "v2_proj": dense_init(next(keys), hd, self.kv_dim // 2, dtype),
                "o_proj": dense_init(next(keys), self.q_dim, hd, dtype),
                "conv0_w": dense_init(next(keys), 2, self.mix_dim, dtype),
                "conv0_b": near(self.mix_dim, 0.0),
                "conv1_w": stack(d, d, 2 * nh),
                "conv1_b": near(self.mix_dim, 0.0),
                "k_temp": near(cfg.num_key_value_heads),
                "router_down": dense_init(next(keys), hd, rh, dtype),
                "router_gate": near(rh), "router_norm": near(rh),
                "router_w1": dense_init(next(keys), rh, rh, dtype),
                "router_w2": dense_init(next(keys), rh, rh, dtype),
                "router_w3": dense_init(next(keys), rh, cfg.router_width, dtype),
                "router_bias": near(cfg.router_width, 0.0, jnp.float32) * 0.03,
                "w_gate": stack(hd, mi, e), "w_up": stack(hd, mi, e),
                "w_down": stack(mi, hd, e),
            }

        params = {"layers": stack_layers([layer() for _ in range(cfg.num_local_layers)])}
        if cfg.needs_embed:
            params["embed"] = {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            }
        if cfg.needs_head:
            params["final_norm"] = {"weight": near(hd)}
        return params
