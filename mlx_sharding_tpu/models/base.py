"""Shared model infrastructure.

The reference builds per-arch ``nn.Module`` trees with ``IdentityBlock``
placeholders for non-local layers so weight indices line up
(ref: shard/server/model/base.py:6-8, llama.py:28-33). On TPU that trick is
unnecessary and harmful: materializing per-layer Python modules defeats
``lax.scan``. Instead a stage's parameters are a pytree of arrays **stacked
over its local layers** (leading axis = layer), the forward pass is one scan,
and layer-index bookkeeping lives only in the checkpoint loader (which maps
global HF layer indices ``start_layer..end_layer`` onto stack positions
``0..L``) — the same sanitize-by-range semantics as
shard/server/model/llama.py:92-107, applied at load time.

Models here are *functional*: a model object holds only the (static) config;
parameters and KV cache are explicit pytree arguments. That is what makes
them jit/pjit/shard_map-transparent.

Which leaves ride the layer scan (``scan_layers``): every leaf of a group's
stack is a scanned ``xs`` and reaches the layer body as that layer's slice,
except the leaves a model names through ``BaseModel.scan_in_place`` —
DeepSeek-V2's packed expert stacks ``w_gate``, ``w_up``, ``w_down`` — which
stay whole ``(L, E, …)`` beside the layer's index and are read in place by
``(layer, expert)``. No other model names any, so their scans are as they
were.

Where the cache rides: ``scan_layers`` takes it as ``xs`` and returns it as
``ys`` (a layer's contiguous buffer is copied out of the stack and stacked
back: prefill chunks, the gathered-page decode step, the dense engines);
``scan_layers_carried`` keeps a page pool whole in the carry for a body
that writes a few rows and attends where the pool lies (the ragged decode
step, ``parallel/pipeline.py:_build_smapped_ragged``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mlx_sharding_tpu.cache import KVCache, init_cache
from mlx_sharding_tpu.ops.quant import (
    dequantize,
    is_quantized,
    linear as quant_linear,
)


def dense_init(key, in_dim: int, out_dim: int, dtype, scale: float | None = None):
    """Random (in, out) weight for x @ W. Used by tests only —
    real weights come from checkpoints."""
    if scale is None:
        scale = 1.0 / np.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


#: the key under which a layer body finds its index when some of its leaves
#: are whole stacks (scan_layers' ``in_place``)
LAYER_INDEX = "layer"


def _split_in_place(layer_params, in_place):
    """``(scanned, whole, index)``: the leaves that ride a layer scan, the
    ones named ``in_place`` that stay whole beside it, and the layers'
    indices to read those by (None where nothing is read in place)."""
    whole = {name: layer_params[name] for name in in_place}
    if not whole:
        return layer_params, whole, None
    scanned = {n: w for n, w in layer_params.items() if n not in whole}
    return scanned, whole, jnp.arange(jax.tree.leaves(whole)[0].shape[0])


def scan_layers(layer_fn, h, layer_params, k, v, mask=None, in_place=()):
    """``lax.scan`` over a stacked layer group with optional per-layer
    active masking.

    ``layer_fn(h, p, k_buf, v_buf) -> (h, k_buf, v_buf)`` is the single-layer
    body; ``mask`` is an (L,) bool array (or None == all active). Masked-out
    slots leave both the hidden state and their cache rows untouched, which is
    what lets the fused SPMD engine pad uneven/heterogeneous stages to a
    uniform per-stage slot count: padding slots carry zero params and scan
    through as no-ops regardless of architecture semantics.

    ``in_place`` names the leaves of ``layer_params`` that do NOT ride the
    scan. A scanned leaf reaches the body as that layer's slice, which the
    compiler materializes: a copy of the slice out of the stack, every
    iteration. That is right for the small leaves (norms, attention and
    shared projections, the router) and ruinous for a layer's packed expert
    stacks, of which the body reads a few experts. Such leaves are closed
    over whole, the scan counts the layers, and the body's ``p`` holds them
    as ``(L, …)`` stacks beside ``p[LAYER_INDEX]``, the index to read them
    by where they lie (``ops.moe.apply_experts(layer=…)``). A padding slot's
    index is still a valid row, of zero parameters.

    The cache rides this scan as ``xs`` and comes back as ``ys``: every
    layer's buffer is copied out of the stack and stacked back (the module
    docstring says which bodies want that and which take
    :func:`scan_layers_carried`)."""
    layer_params, whole, index = _split_in_place(layer_params, in_place)

    def body(h, xs):
        # ``m`` and ``i`` are None (empty pytrees, no scan operands) when
        # there is no mask and nothing is read in place
        p, k_buf, v_buf, m, i = xs
        if whole:
            p = {**p, **whole, LAYER_INDEX: i}
        h2, k2, v2 = layer_fn(h, p, k_buf, v_buf)
        if m is None:
            return h2, (k2, v2)
        # tree-map: K/V buffers may be int8 {d, s} leaf pairs (paged pools)
        sel = lambda a, b: jnp.where(m, a, b)  # noqa: E731
        return jnp.where(m, h2, h), (
            jax.tree.map(sel, k2, k_buf),
            jax.tree.map(sel, v2, v_buf),
        )

    xs = (layer_params, k, v, mask, index)
    # the scan's own work is slicing the cache per layer and stacking it
    # back; the layer body opens deeper scopes for everything it does
    with jax.named_scope("mst.kv_pool.regroup"):
        h, (k, v) = jax.lax.scan(body, h, xs)
    return h, k, v


def scan_layers_carried(layer_fn, h, layer_params, k, v, rows, mask=None,
                        in_place=()):
    """:func:`scan_layers` with the cache in the scan's CARRY: ``k`` and
    ``v`` (each a pytree holding EVERY layer's cache: a page pool) enter the
    scan whole, go through each layer whole and leave whole, never sliced
    per layer and never stacked back. ``rows`` is an (L,) int array, each
    layer's place in the pool, scanned beside the parameters.

    ``layer_fn(h, p, k, v, row, keep) -> (h, k, v)`` writes the layer's new
    rows into the pools and reads what it attends to where it lies, both by
    ``row``. ``keep`` is the layer's entry of ``mask`` (None without one): a
    masked-out padding layer's hidden state is dropped here, but keeping its
    writes out of the pool's live rows is ``layer_fn``'s own to do, on the
    few rows it writes (a ``where`` over the pool would read and write all
    of it, every layer). ``in_place`` as in :func:`scan_layers`."""
    layer_params, whole, index = _split_in_place(layer_params, in_place)

    def body(carry, xs):
        h, k, v = carry
        p, row, m, i = xs
        if whole:
            p = {**p, **whole, LAYER_INDEX: i}
        h2, k, v = layer_fn(h, p, k, v, row, m)
        return (h2 if m is None else jnp.where(m, h2, h), k, v), None

    # what is left of the scan's own work is the slice of each small
    # parameter leaf out of its stack
    with jax.named_scope("mst.kv_pool.regroup"):
        (h, k, v), _ = jax.lax.scan(
            body, (h, k, v), (layer_params, rows, mask, index)
        )
    return h, k, v


def take_row(x, i):
    return jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)


def put_row(x, i, val):
    return jax.lax.dynamic_update_index_in_dim(x, val.astype(x.dtype), i, 0)


class LayerRow:
    """One layer's leaves out of its group's stacks, each sliced where it is
    USED: the compiler copies a layer's matrix out of the stack (a fusion of
    its own), and that copy then carries the scope of the projection that
    reads it, not nobody's. For models that walk interleaved layer groups
    by an unrolled loop (``models/nemotron_h.py``, ``models/afmoe.py``)."""

    def __init__(self, stacks: dict, rank):
        self.stacks, self.rank = stacks, rank

    def __getitem__(self, name):
        return jax.tree.map(lambda x: take_row(x, self.rank), self.stacks[name])


def stack_layers(per_layer: list[dict]) -> dict:
    """[{name: (…)}, …] → {name: (L, …)} for lax.scan consumption."""
    out = {}
    for name in per_layer[0]:
        out[name] = jnp.stack([p[name] for p in per_layer])
    return out


def apply_projection_fusion(model, layer_stack: dict) -> list[str]:
    """Fuse each group the model declares via ``fused_projection_groups``
    IN PLACE in ``layer_stack`` (a flat ``{name: w}`` stack, or nested
    ``{group: {name: w}}`` keyed like ``layer_group_ranges``): the group's
    packed triples concatenate along OUT (ops.quant.fuse_packed) and the
    sources are removed, so decode serves the whole group with one fused
    projection launch over one pass of the activation planes. Groups with
    any dense (non-packed) member are left untouched. Returns the fused
    names added. Callers gate on tp == 1."""
    from mlx_sharding_tpu.ops.quant import fuse_packed

    groups = model.fused_projection_groups()
    if not groups:
        return []
    ranges = model.layer_group_ranges()
    stacks = (
        [layer_stack] if list(ranges) == [None]
        else [layer_stack[k] for k in ranges if k in layer_stack]
    )
    fused = []
    for stack in stacks:
        for fname, parts in groups.items():
            if not all(p in stack and is_quantized(stack[p]) for p in parts):
                continue
            stack[fname] = fuse_packed([stack[p] for p in parts])
            for p in parts:
                del stack[p]
            if fname not in fused:
                fused.append(fname)
    return fused


class BaseModel:
    """Common surface every architecture implements.

    ``__call__(params, x, cache)`` where ``x`` is int32 tokens (B, T) on the
    first stage or hidden states (B, T, H) downstream, returning logits on
    the last stage or hidden states otherwise — mirroring the reference's
    stage models (shard/server/model/llama.py:39-62).
    """

    #: decoder-layer projections may stay 4-bit packed in HBM
    #: (loading.load_model(keep_quantized=True) → ops.quant.linear dispatch)
    supports_packed = False

    def __init__(self, config):
        self.config = config
        q = getattr(config, "quantization", None) or {}
        self._gs = int(q.get("group_size", 64))
        self._bits = int(q.get("bits", 4))

    def _linear(self, x, w):
        """``x @ w`` that transparently serves packed 4-bit params
        (ops.quant.linear dispatch); dense arrays go straight to the MXU."""
        from mlx_sharding_tpu.ops.quant import linear

        return linear(x, w, self._gs, self._bits)

    def fused_projection_groups(self) -> dict:
        """{fused_param_name: (source_param_names, …)} — groups of packed
        per-layer projections sharing the same input activations that the
        engines may concatenate along OUT at build time (ops.quant.fuse_packed)
        so one kernel invocation serves the whole group. The forward code must
        dispatch on the fused name's presence in the layer pytree. Empty dict
        → the architecture has no fusable groups wired."""
        return {}

    def packed_keep_dense_re(self) -> str | None:
        """Regex over HF weight names that must stay DENSE under
        ``keep_quantized`` (their triples are dequantized on load). Used for
        weights consumed as tensors rather than matmul operands — e.g. MoE
        routers feeding the fp32 routing einsum, or MLA's kv_b when the
        compressed-latent cache absorbs it into einsums."""
        return None

    # -- cache ------------------------------------------------------------
    def make_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
        """Stage-local cache (the reference's make_cache / per-layer KVCache
        construction, shard/utils.py:142-150)."""
        cfg = self.config
        return init_cache(
            cfg.num_local_layers, batch, max_seq, self.cache_num_heads(),
            self.cache_head_dim(), dtype,
        )

    def cache_head_dim(self):
        """Int or (k_dim, v_dim) tuple (MLA, ref deepseek_v2.py:120-125)."""
        return self.config.head_dim

    def cache_num_heads(self) -> int:
        """Head count of the KV buffers. Models whose cache layout departs
        from plain GQA (e.g. MLA's single compressed-latent head) override
        this — engines must use it instead of config.num_key_value_heads."""
        return self.config.num_key_value_heads

    def cache_tp_replicated(self) -> bool:
        """True when the KV cache is head-count INDEPENDENT and must
        replicate over tp rather than head-shard (MLA's shared compressed
        latent). A genuine MQA model (num_key_value_heads == 1) is NOT
        that — its single head cannot be split, so tp > 1 must still be
        rejected by the divisibility check."""
        return False

    def tp_layer_axes(self) -> dict:
        """{layer_param_name: per-layer dim index (after the stacked-L axis)
        sharded over tp, or None for replicated}. Empty dict → the
        architecture has no tensor-parallel wiring yet and engines must
        reject tp > 1."""
        return {}

    def ep_layer_axes(self) -> dict:
        """Same shape as :meth:`tp_layer_axes` for the expert-parallel axis:
        which per-layer dims hold the expert stacks. Empty dict → the
        architecture has no EP wiring and engines must reject ep > 1."""
        return {}

    # -- layer structure ---------------------------------------------------
    def layer_group_ranges(self) -> dict:
        """Global-layer ranges of structurally distinct layer groups.

        ``{group_key: (g0, g1)}`` where ``group_key=None`` means the model's
        ``params["layers"]`` is itself the stacked pytree (homogeneous
        models); string keys name sub-dicts (DeepSeek's dense/moe split).
        The fused pipeline engine uses this to build per-stage uniform
        stacks with masked padding for uneven/heterogeneous splits."""
        return {None: (0, self.config.num_hidden_layers)}

    def scan_in_place(self, group, stack: dict) -> tuple:
        """Names of ``stack``'s leaves (``group`` as in ``sp_groups``) that a
        layer scan over it leaves where they lie instead of slicing per
        layer (``scan_layers``' ``in_place``): the model's layer body must
        then read them by ``p[LAYER_INDEX]``. Default: none."""
        return ()

    #: per-sequence state beside the K/V rows (cache.KVCache.state)
    has_recurrent_state = False

    # -- sequence parallelism ---------------------------------------------
    #: architectures wired for the sequence-parallel paths (sp_prefill's
    #: ring attention, sp_decode's partial-softmax merge) set this True
    supports_sp = False

    def sp_groups(self) -> list:
        """Layer-group keys the sp paths scan over, in forward order.
        ``[None]`` = ``params["layers"]`` is one homogeneous stack;
        DeepSeek returns its present ["dense", "moe"] sub-stacks."""
        return [None]

    def sp_layer(self, p, h, offset, attn_fn, group=None):
        """One decoder layer with the attention op INJECTED — the shared
        body of both sp paths. ``attn_fn(q, k_new, v_new, **opts) -> attn``
        is ring attention (prefill: k/v are this shard's T_local rows) or
        the sharded-KV partial-softmax attention (decode: the backend
        owner-writes k/v into its shard first). Supported opts:
        ``logit_softcap``, ``sliding_window`` (per-layer traced scalars ok),
        and ``values_from_k`` (attend values = keys[..., :n] — MLA's
        latent-as-values trick; v_new is then a dummy). Returns
        ``(h, k_new, v_new)`` — the new rows double as the prefill scan's
        cache ys. Default: the Llama-family hook pair."""
        q, k, v = self.layer_attn_inputs(p, h, offset)
        return self.layer_finish(p, h, attn_fn(q, k, v)), k, v

    # -- forward ----------------------------------------------------------
    def __call__(self, params, x, cache: KVCache):
        raise NotImplementedError

    def init_params(self, key, dtype=jnp.bfloat16):
        raise NotImplementedError

    # compute dtype for paths that must materialize dense values from
    # packed 4-bit params (embed row dequant); load_model overrides it with
    # the checkpoint load dtype so packed and dense loads agree bit-for-bit
    compute_dtype = jnp.bfloat16

    def _quant_args(self) -> tuple[int, int]:
        q = getattr(self.config, "quantization", None) or {}
        return int(q.get("group_size", 64)), int(q.get("bits", 4))

    def embed_tokens(self, params, tokens):
        w = params["embed"]["weight"]
        if is_quantized(w):
            # gather the packed rows for these tokens and dequantize just
            # those — O(T·H) work; the (V, H) dense table never exists
            gs, bits = self._quant_args()
            rows = jax.tree.map(lambda a: jnp.take(a, tokens, axis=0), w)
            return dequantize(
                rows["q"], rows["scales"], rows["biases"], gs, bits,
                self.compute_dtype,
            )
        return jnp.take(w, tokens, axis=0)

    # -- embed/head decomposition -----------------------------------------
    # The fused engine vocab-shards the embedding table and LM head over the
    # pp axis (each device holds vocab/S rows); these hooks isolate the
    # arch-specific pieces around the sharded table lookup / vocab matmul so
    # the engine can own the collectives. apply_head/embed compose them for
    # the single-program and chained paths.

    def embed_transform(self, h):
        """Post-lookup transform (Gemma-2 scales by sqrt(hidden))."""
        return h

    def head_input(self, params, h):
        """Transform before the vocab projection (the final norm)."""
        raise NotImplementedError

    def head_transform(self, logits):
        """Elementwise transform after the vocab projection (Gemma-2
        softcap). Must be shard-local: applied per vocab shard."""
        return logits

    def head_is_tied(self) -> bool:
        """True when logits project through the embedding table transposed."""
        return bool(getattr(self.config, "tie_word_embeddings", False))

    @jax.named_scope("mst.embed")
    def embed(self, params, tokens):
        return self.embed_transform(self.embed_tokens(params, tokens))

    @jax.named_scope("mst.head")
    def apply_head(self, params, h):
        h = self.head_input(params, h)
        w = (
            params["embed"]["weight"]
            if self.head_is_tied()
            else params["lm_head"]["weight"]
        )
        if is_quantized(w):
            # MLX packs (out, in) = (V, H) — exactly quant.linear's packed
            # orientation for the H→V projection, tied or not; the vocab
            # matmul runs off the packed bytes (4x less weight bandwidth
            # on the biggest dense read of a decode step)
            gs, bits = self._quant_args()
            return self.head_transform(quant_linear(h, w, gs, bits))
        w = w.T if self.head_is_tied() else w
        return self.head_transform(h @ w)
