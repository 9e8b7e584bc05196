"""IBM Granite 4.0-H decoder (``model_type: granitemoehybrid``).

``h_0 = embedding_multiplier * embed[token]``; every block is two
sub-layers, ``h = h + r * mixer(rmsnorm(h))`` then ``h = h + r *
mlp(rmsnorm(h))`` with ``r = residual_multiplier``; logits are
``rmsnorm(h) @ embed^T / logits_scaling`` (the head is the embedding). No
bias but the convolution's.

- ``mamba`` **Mamba-2** (``ops/mamba2.py``, shared with
  ``models/nemotron_h.py``): 64 heads of 64 on one B/C group, state 128, 4
  taps, chunk 256 at the published sizes. Per sequence the layer keeps the
  SSM state ``(H, P, N)`` in float32 and the last ``mamba_d_conv - 1`` inputs
  of its convolution in the activation dtype, FLAT ``((taps - 1) * C,)``: a
  TPU tiles an array's two minor dimensions, and a minor dimension of 3
  would be stored as 128. For the same reason ``in_proj`` is held as two
  matrices, ``in_proj`` to ``[z, xBC]`` (8448 columns at the published sizes,
  66 lane tiles) and ``dt_proj`` to ``dt`` (64): the checkpoint's one matrix
  is 8512 wide, no multiple of 128, and a chip keeps such a stack with the
  OTHER dimension minor and copies all of it (1.25 GB) into the layout the
  product reads at the start of every served program.
- ``attention``: GQA, ``softmax(attention_multiplier * q k^T)`` (the
  multiplier, not ``head_dim**-0.5``), causal, NO rotary embedding
  (``position_embedding_type`` ``nope``). K/V rows keep their heads MERGED on
  the lane axis, ``(…, 1, Hkv * D)``, as ``models/afmoe.py`` says why.
- ``mlp`` (the checkpoint's ``shared_mlp``): ``[g, v] = split(input_linear(u))``,
  ``output_linear(silu(g) * v)``. ``num_local_experts`` is 0 in the models
  this file serves; routed experts beside the MLP are refused by the config.

Layers: two stacked groups, ``mamba`` and ``attn``, each row holding its
mixer, both norms and its MLP. ``layer_types`` is periodic (granite-4.0-h-micro:
four periods of ``MMMMMAMMMM``), so the walk is a ``lax.scan`` over the
periods whose body runs the period's runs of like layers, each run an inner
scan: a compiled program holds the Mamba body once per run of a period, not
once per layer. The recurrent state pool and the K/V (the engine's page pool
in a ragged decode step, a slot's contiguous rows otherwise) ride the scans'
CARRY whole, and a layer is its rank in it: a decode step updates its rows of
the SSM state in one pass where the pool lies (``ops.mamba2.ssm_pool_step``),
a chunk reads them by ``dynamic_slice`` and writes them by
``dynamic_update_slice``, as every step does the convolution's tails. One
pipeline stage, no tensor or expert parallelism: the state pool
and the scaled residual stream belong to one device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, advance, init_cache, write_layer_kv
from mlx_sharding_tpu.config import GraniteMoeHybridConfig
from mlx_sharding_tpu.models.base import (
    BaseModel,
    LayerRow,
    dense_init,
    put_row,
    stack_layers,
    take_row,
)
from mlx_sharding_tpu.ops import causal_attention, rms_norm
from mlx_sharding_tpu.ops.mamba2 import mamba2_mixer, put_rows, take_rows

GROUP_OF = {"mamba": "mamba", "attention": "attn"}
ONE_STAGE = (
    "pipeline stages are not wired for granitemoehybrid: the state pool and "
    "the period scan belong to one stage (run it with --num-stages 1)"
)


def period_runs(layer_types: list) -> tuple:
    """``(periods, runs)``: the smallest period of ``layer_types`` (a pattern
    that repeats nowhere is one period) and the period as runs of like
    layers, ``[(group, layers in the run)]``."""
    n = len(layer_types)
    p = next(
        p for p in range(1, n + 1)
        if n % p == 0 and all(layer_types[i] == layer_types[i % p] for i in range(n))
    )
    runs: list = []
    for kind in layer_types[:p]:
        if runs and runs[-1][0] == GROUP_OF[kind]:
            runs[-1][1] += 1
        else:
            runs.append([GROUP_OF[kind], 1])
    return n // p, [tuple(r) for r in runs]


class GraniteMoeHybridModel(BaseModel):
    #: engines carry a per-slot recurrent state beside the K/V pages
    #: (cache.KVCache.state); whatever rewinds a slot by lowering its offset
    #: cannot serve this model (cache.refuse_recurrent)
    has_recurrent_state = True

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(config)
        if (config.start_layer, config.end_layer) != (0, config.num_hidden_layers):
            raise ValueError(ONE_STAGE)
        self.scale = float(config.attention_multiplier)
        self.d_inner = config.mamba_n_heads * config.mamba_d_head
        self.conv_dim = self.d_inner + 2 * config.mamba_n_groups * config.mamba_d_state
        self.kv_dim = config.num_key_value_heads * config.head_dim
        self.periods, self.runs = period_runs(config.layer_types)

    # -- layer structure ---------------------------------------------------
    def layer_group_layers(self) -> dict:
        """{group: [global layer indices]} — the groups interleave."""
        out: dict = {}
        for i, kind in enumerate(self.config.layer_types):
            out.setdefault(GROUP_OF[kind], []).append(i)
        return out

    def layer_group_ranges(self) -> dict:
        raise NotImplementedError(
            "granitemoehybrid layer groups interleave: use layer_group_layers()"
        )

    def kv_groups(self) -> tuple:
        return ("attn",)

    def state_groups(self) -> tuple:
        return ("mamba",)

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
        """Per Mamba layer and sequence: {name: (shape after (layer,), dtype)}."""
        cfg = self.config
        return {
            "ssm": (
                (batch, cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
                jnp.float32,
            ),
            "conv": ((batch, (cfg.mamba_d_conv - 1) * self.conv_dim), None),
        }

    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        layers = self.layer_group_layers()
        kv = init_cache(len(layers.get("attn", ())), batch, max_seq, 1, self.kv_dim, dtype)
        n = len(layers.get("mamba", ()))
        return kv._replace(state={
            name: jnp.zeros((n, *shape), dt or dtype)
            for name, (shape, dt) in self.state_shapes(batch).items()
        })

    # -- the sub-layers ----------------------------------------------------
    def _mamba(self, p, u, state, rank, n_valid, active):
        """The Mamba-2 mixer of the layer at ``rank`` of the state pool
        ``{name: (layers, rows, …)}``: its rows of the ``B`` sequences of
        ``u`` are read, advanced and written back where the pool lies (rows
        past ``B``, an engine's scratch row, are neither read nor written).
        Returns ``(out, state)``."""
        cfg = self.config
        nb = u.shape[0]
        tail = take_rows(state["conv"], rank, nb)
        out, ssm, tail = mamba2_mixer(
            self._linear, p, u, state["ssm"], rank,
            tail.reshape(nb, cfg.mamba_d_conv - 1, self.conv_dim), n_valid, active,
            heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
            groups=cfg.mamba_n_groups, state=cfg.mamba_d_state,
            taps=cfg.mamba_d_conv, chunk=cfg.mamba_chunk_size,
            eps=cfg.rms_norm_eps,
        )
        state = {"ssm": ssm, "conv": put_rows(state["conv"], rank, tail.reshape(nb, -1))}
        return out, state

    def _attn(self, p, u, k_buf, v_buf, offset, paged):
        """``k_buf`` / ``v_buf``: the layer's contiguous rows ``(B, S, 1, Hkv
        * D)``, or with ``paged`` (the engine's ``(attn_fn, done)`` over the
        pool where it lies) unused. Returns ``(out, k_buf, v_buf)``."""
        cfg = self.config
        b, t, _ = u.shape
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        with jax.named_scope("mst.attn.qkv"):
            q = self._linear(u, p["q_proj"]).reshape(b, t, hq, d)
            k = self._linear(u, p["k_proj"]).reshape(b, t, 1, hkv * d)
            v = self._linear(u, p["v_proj"]).reshape(b, t, 1, hkv * d)
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

    def _mlp(self, p, u):
        with jax.named_scope("mst.mlp.dense"):
            g, v = jnp.split(self._linear(u, p["mlp_in"]), 2, axis=-1)
            return self._linear(jax.nn.silu(g) * v, p["mlp_out"])

    # -- the layer walk ----------------------------------------------------
    def run_layers(
        self, layer_params, h, k, v, offset, mask=None, tp_axis=None,
        ep_axis=None, *, state=None, n_valid=None, active=None, plan=None,
        stage_axis=None, paged_attn=None,
    ):
        """All layers, one scan over the pattern's periods. ``state`` holds
        every Mamba layer's rows of the ``B`` sequences of ``h`` (and, from
        an engine's ragged decode, a scratch row past them). ``k`` / ``v``:
        every attention layer's contiguous rows ``(L, B, S, 1, Hkv * D)`` —
        or, with ``paged_attn``, the engine's page pool; either is carried
        whole. ``mask``, ``plan`` and ``stage_axis`` are unused: one stage
        has no padding layer. Returns ``(h, k, v, state)``."""
        if tp_axis is not None or ep_axis is not None:
            raise ValueError(
                "tensor and expert parallelism are not wired for granitemoehybrid"
            )
        cfg = self.config
        eps, r = cfg.rms_norm_eps, cfg.residual_multiplier
        per_period = {
            g: sum(n for kind, n in self.runs if kind == g) for g in ("mamba", "attn")
        }

        def layer(group, rank, carry):
            h, k, v, state = carry
            p = LayerRow(layer_params[group], rank)
            u = rms_norm(h, p["norm"], eps)
            if group == "mamba":
                out, state = self._mamba(p, u, state, rank, n_valid, active)
            elif paged_attn is not None:
                out, k, v = self._attn(p, u, k, v, offset, paged_attn(k, v, layer=rank))
            else:
                with jax.named_scope("mst.kv_pool.regroup"):
                    k_l, v_l = take_row(k, rank), take_row(v, rank)
                out, k_l, v_l = self._attn(p, u, k_l, v_l, offset, None)
                with jax.named_scope("mst.kv_pool.regroup"):
                    k, v = put_row(k, rank, k_l), put_row(v, rank, v_l)
            h = h + (out * r).astype(h.dtype)
            out = self._mlp(p, rms_norm(h, p["mlp_norm"], eps))
            return h + (out * r).astype(h.dtype), k, v, state

        def period(carry, i):
            seen = {"mamba": 0, "attn": 0}
            for group, n in self.runs:
                first = i * per_period[group] + seen[group]
                seen[group] += n
                if n == 1:
                    carry = layer(group, first, carry)
                else:
                    carry, _ = jax.lax.scan(
                        lambda c, j, g=group, f=first: (layer(g, f + j, c), None),
                        carry, jnp.arange(n),
                    )
            return carry, None

        carry, _ = jax.lax.scan(period, (h, k, v, state), jnp.arange(self.periods))
        return carry

    # -- embed / head ------------------------------------------------------
    def embed_transform(self, h):
        return h * jnp.asarray(self.config.embedding_multiplier, h.dtype)

    def head_input(self, params, h):
        return rms_norm(h, params["final_norm"]["weight"], self.config.rms_norm_eps)

    def head_transform(self, logits):
        return logits / self.config.logits_scaling

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
    #: checkpoint suffix -> (our leaf, transposed to (in, out)?), per group.
    #: The catalog gives the family's config.json, not its tensor names:
    #: these follow the family's published module names and are ASSUMED.
    SHARED = {
        "input_layernorm.weight": ("norm", False),
        "post_attention_layernorm.weight": ("mlp_norm", False),
        "shared_mlp.input_linear.weight": ("mlp_in", True),
        "shared_mlp.output_linear.weight": ("mlp_out", True),
    }
    NAMES = {
        "mamba": {
            "mamba.conv1d.bias": ("conv_b", False),
            "mamba.dt_bias": ("dt_bias", False),
            "mamba.A_log": ("A_log", False),
            "mamba.D": ("D", False),
            "mamba.norm.weight": ("ssm_norm", False),
            "mamba.out_proj.weight": ("out_proj", True),
        },
        "attn": {
            "self_attn.q_proj.weight": ("q_proj", True),
            "self_attn.k_proj.weight": ("k_proj", True),
            "self_attn.v_proj.weight": ("v_proj", True),
            "self_attn.o_proj.weight": ("o_proj", True),
        },
    }

    def map_weights(self, weights: dict, dtype=jnp.bfloat16) -> dict:
        """HF tensors (``model.layers.<i>.*``) → ``{mamba, attn}`` stacks;
        the recurrence's per-head vectors stay float32, the torch ``Conv1d``
        weight ``(C, 1, k)`` becomes ``(C, k)``."""
        from mlx_sharding_tpu.loading import fetch_weight, first_key, stack_tree, vocab_param

        cfg = self.config
        keep_f32 = ("dt_bias", "A_log", "D")
        pre = "model.layers.{}.".format
        layers: dict = {}
        for group, idxs in self.layer_group_layers().items():
            out = {
                our: stack_tree([
                    fetch_weight(
                        weights, pre(i) + suffix,
                        jnp.float32 if our in keep_f32 else dtype, tr,
                    )
                    for i in idxs
                ])
                for suffix, (our, tr) in {**self.SHARED, **self.NAMES[group]}.items()
            }
            if group == "mamba":
                both = stack_tree([
                    fetch_weight(weights, pre(i) + "mamba.in_proj.weight", dtype, True)
                    for i in idxs
                ])
                cut = self.d_inner + self.conv_dim
                out["in_proj"], out["dt_proj"] = both[..., :cut], both[..., cut:]
                out["conv_w"] = jnp.stack([
                    jnp.asarray(
                        weights[pre(i) + "mamba.conv1d.weight"], dtype
                    ).reshape(self.conv_dim, cfg.mamba_d_conv)
                    for i in idxs
                ])
            layers[group] = out
        embed = first_key(weights, "model.embed_tokens.weight", "embed_tokens.weight")
        norm = first_key(weights, "model.norm.weight", "norm.weight")
        return {
            "layers": layers,
            "embed": {"weight": vocab_param(embed, dtype)},
            "final_norm": {"weight": jnp.asarray(norm, dtype)},
        }

    def init_params(self, key, dtype=jnp.bfloat16):
        cfg = self.config
        hd, di, cd = cfg.hidden_size, self.d_inner, self.conv_dim
        nh, mi = cfg.mamba_n_heads, cfg.shared_intermediate_size
        qd = cfg.num_attention_heads * cfg.head_dim
        keys = iter(jax.random.split(key, 16 * cfg.num_hidden_layers + 4))
        norm = lambda n: (  # noqa: E731
            1.0 + 0.1 * jax.random.normal(next(keys), (n,), jnp.float32)
        ).astype(dtype)

        def shared():
            return {
                "norm": norm(hd), "mlp_norm": norm(hd),
                "mlp_in": dense_init(next(keys), hd, 2 * mi, dtype),
                "mlp_out": dense_init(next(keys), mi, hd, dtype),
            }

        def mamba():
            # dt = softplus(dt_bias + small) spread log-uniformly over the
            # initialisation's 0.001..0.1; A in 1..16 (models/nemotron_h.py)
            dt0 = jnp.exp(jax.random.uniform(
                next(keys), (nh,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            return {
                **shared(),
                "in_proj": dense_init(next(keys), hd, di + cd, dtype),
                "dt_proj": dense_init(next(keys), hd, nh, dtype),
                "conv_w": dense_init(next(keys), cfg.mamba_d_conv, cd, dtype).T,
                "conv_b": (0.1 * jax.random.normal(next(keys), (cd,), jnp.float32)).astype(dtype),
                "dt_bias": jnp.log(jnp.expm1(dt0)),
                "A_log": jnp.log(jax.random.uniform(next(keys), (nh,), jnp.float32, 1.0, 16.0)),
                "D": 1.0 + 0.1 * jax.random.normal(next(keys), (nh,), jnp.float32),
                "ssm_norm": norm(di),
                "out_proj": dense_init(next(keys), di, hd, dtype),
            }

        def attn():
            return {
                **shared(),
                "q_proj": dense_init(next(keys), hd, qd, dtype),
                "k_proj": dense_init(next(keys), hd, self.kv_dim, dtype),
                "v_proj": dense_init(next(keys), hd, self.kv_dim, dtype),
                "o_proj": dense_init(next(keys), qd, hd, dtype),
            }

        make = {"mamba": mamba, "attn": attn}
        per: dict = {}
        for kind in cfg.layer_types:
            per.setdefault(GROUP_OF[kind], []).append(make[GROUP_OF[kind]]())
        return {
            "layers": {g: stack_layers(rows) for g, rows in per.items()},
            "embed": {
                "weight": dense_init(next(keys), cfg.vocab_size, hd, dtype, scale=0.02)
            },
            "final_norm": {"weight": norm(hd)},
        }
