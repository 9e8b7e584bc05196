"""SPMD collective pipeline — the framework's core.

This module replaces the reference's entire distributed execution model. The
reference chains pipeline stages with one blocking gRPC round-trip per stage
per token — serialize, TCP, Python-deserialize (ref: shard/utils.py:162-164,
shard/server/server.py:27-57; cost analysis SURVEY §3.5). Here the whole
multi-stage token step is ONE compiled XLA program on a ``pp`` mesh axis:
every stage's layers run where their weights live, and the activation hand-off
is a ``lax.ppermute`` hop over ICI — HBM-to-HBM, zero host involvement.

Schedule (GPipe-style collective pipeline): with S stages and M microbatches,
the program runs ``S+M-1`` ticks inside a ``lax.scan``. At tick ``t`` device
``s`` processes microbatch ``m = t - s`` (real iff ``0 <= m < M``); stage 0
injects embedded tokens, the last stage banks logits, and a single ``psum``
at the end replicates the (M, B, V) logits to every device so sampling can
run redundantly-deterministically on all of them — the sampled token is the
only thing that ever leaves the device. M=1 gives the reference's
single-request decode; M>1 fills the pipeline bubble for batch serving
(BASELINE.json config #5: microbatched decode).

Correctness of garbage ticks: devices compute every tick, but
- cache writes on non-real ticks are routed to a scratch microbatch slice
  (index M in an (M+1)-slot cache axis), so they can never corrupt state;
- logits writes on non-real ticks land on microbatch 0 strictly *before*
  its real write (t < S-1 implies writes precede the real tick S-1);
- the shared cache offset advances once per step outside the tick loop, so
  garbage ticks cannot desynchronize positions.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mlx_sharding_tpu.cache import (
    KVCache,
    dequantize_kv,
    has_recurrent_state,
    has_slot_state,
    has_window_layers,
    quantize_kv_rows,
    refuse_recurrent,
    window_ring_rows,
)
from mlx_sharding_tpu import diffusion
from mlx_sharding_tpu.ops.quant import dequantize, is_quantized
from mlx_sharding_tpu.parallel.mesh import (
    AXIS_EP,
    AXIS_PP,
    AXIS_TP,
    same_mesh_devices,
)
from mlx_sharding_tpu.weights import ResidentWeights
from mlx_sharding_tpu.sample import (
    SamplerParams,
    init_recent_tokens,
    make_sampler_params,
    nucleus_logits_batched,
    sample_token,
    sample_token_batched,
    transform_logits_batched,
    update_recent_tokens,
)


def put_global(tree, shardings):
    """``jax.device_put`` that is safe across processes. Single-process it IS
    device_put. Multi-process, ``device_put`` of host data onto a
    process-spanning sharding first broadcasts the whole tree through the
    control plane to assert every rank passed identical values — for model
    params and cache zeros that is pure overhead (every rank loaded the same
    checkpoint / computes the same zeros), it is the slowest possible way to
    place a model, and gloo-backed CPU ranks crash outright on large
    payloads. Build each global array from the local copy instead: no
    cross-host value traffic at all. ``shardings`` is a matching pytree of
    shardings or a single sharding applied to every leaf."""
    if jax.process_count() == 1:
        return jax.device_put(tree, shardings)

    def put(x, s):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, s, lambda idx, _x=x: _x[idx]
        )

    if isinstance(shardings, jax.sharding.Sharding):
        return jax.tree.map(lambda x: put(x, shardings), tree)
    return jax.tree.map(put, tree, shardings)


def balanced_stage_bounds(num_layers: int, num_stages: int) -> list[tuple[int, int]]:
    """Contiguous near-equal ``[start, end)`` bounds (larger stages first),
    the default when the caller gives no explicit split."""
    base, extra = divmod(num_layers, num_stages)
    bounds, start = [], 0
    for s in range(num_stages):
        size = base + (1 if s < extra else 0)
        bounds.append((start, start + size))
        start += size
    return bounds


def split_stage_stacks(model, layer_params: dict, stage_bounds) -> tuple[dict, dict, int]:
    """Split a full model's stacked layer params into per-stage uniform
    stacks for the fused SPMD engine, supporting uneven bounds and
    heterogeneous layer groups (DeepSeek's dense prefix + MoE suffix).

    Every stage gets the SAME structure — for each layer group, ``slots =
    max(layers of that group on any stage)`` rows, zero-padded — so the
    arrays stack to (S, slots, …) and shard over ``pp``. A bool mask marks
    the real rows; ``scan_layers`` turns padding slots into no-ops. This is
    how one SPMD program serves the reference's arbitrary ``[start, end)``
    splits (e.g. the BASELINE DeepSeek 0-14/14-27 config,
    /root/reference/shard/utils.py:36-39) without per-stage programs.

    Groups may interleave (``model.layer_group_layers``: Nemotron-H's
    Mamba, attention and expert layers): a stage then gets, per group, the
    rows of that group's layers inside its bounds — still contiguous in the
    group's own stack — and no group is padded with a layer of another kind.

    Returns ``(stacked_params, masks, total_slots)`` where ``masks`` mirrors
    the group structure of ``stacked_params`` ((S, slots) bool arrays) and
    ``total_slots`` is the per-stage KV-cache layer count (the slots of the
    groups that keep K/V: all of them unless ``model.kv_groups`` says).
    """
    stage_bounds = list(stage_bounds)
    S = len(stage_bounds)
    if stage_bounds[0][0] != 0 or stage_bounds[-1][1] != model.config.num_hidden_layers:
        raise ValueError(f"stage bounds {stage_bounds} must cover all layers")
    for (a0, a1), (b0, b1) in zip(stage_bounds, stage_bounds[1:]):
        if a1 != b0:
            raise ValueError(f"stage bounds {stage_bounds} must be contiguous")
    if any(e <= s for s, e in stage_bounds):
        raise ValueError(f"stage bounds {stage_bounds} contain an empty stage")

    # {group: [global layer indices]}: a model whose groups interleave lists
    # them itself, every other gives contiguous ranges
    if hasattr(model, "layer_group_layers"):
        groups = model.layer_group_layers()
    else:
        groups = {
            g: list(range(g0, g1))
            for g, (g0, g1) in model.layer_group_ranges().items()
        }

    def split_group(stack: dict, layers: list):
        # the group's rows that fall inside each stage's [s, e)
        rows_per_stage = [
            (sum(i < s for i in layers), sum(i < e for i in layers))
            for s, e in stage_bounds
        ]
        slots = max(hi - lo for lo, hi in rows_per_stage)

        def split_leaf(w):
            rows = []
            for lo, hi in rows_per_stage:
                part = w[lo:hi]
                if hi - lo < slots:
                    pad = [(0, slots - (hi - lo))] + [(0, 0)] * (w.ndim - 1)
                    part = jnp.pad(part, pad)
                rows.append(part)
            return jnp.stack(rows)

        # tree-map: plain arrays and packed {q, scales, biases} triples alike
        stacked = {
            name: jax.tree.map(split_leaf, w) for name, w in stack.items()
        }
        mask = np.zeros((S, slots), bool)
        for si, (lo, hi) in enumerate(rows_per_stage):
            mask[si, : hi - lo] = True
        return stacked, jnp.asarray(mask), slots

    if list(groups) == [None]:
        stacked, mask, slots = split_group(layer_params, groups[None])
        return stacked, mask, slots
    kv_groups = getattr(model, "kv_groups", lambda: tuple(groups))()
    stacked_all, masks_all, total = {}, {}, 0
    for key, layers in groups.items():
        stacked, mask, slots = split_group(layer_params[key], layers)
        stacked_all[key] = stacked
        masks_all[key] = mask
        if key in kv_groups:
            total += slots
    if hasattr(model, "kv_layer_slots"):
        # not every layer of a K/V group keeps full-length rows (window
        # layers keep a ring in the state pool)
        total = model.kv_layer_slots(stage_bounds)
    return stacked_all, masks_all, total


def stack_stage_params(stage_param_list: list[dict]) -> dict:
    """Per-stage loaded checkpoints ({name: (L, …)} each) → {name: (S, L, …)}.
    Lets per-stage checkpoints emitted by shard_tool feed the mesh directly."""
    names = stage_param_list[0].keys()
    return {n: jnp.stack([p[n] for p in stage_param_list]) for n in names}


def place_weights(model, params, mesh, *, stage_bounds=None) -> ResidentWeights:
    """Materialize a model's device-resident weight tree on ``mesh``: split
    the stacked layer params per pipeline stage, apply build-time projection
    fusion, derive per-name PartitionSpecs over pp/tp/ep, place everything
    with ``put_global``, and vocab-shard the embedding/head over pp. This
    is the entire per-replica spawn cost that ISN'T slot/cache setup —
    which is why it is a free function: the
    ``weights.WeightStore`` runs it once per key and every data-parallel
    replica constructs its ``PipelineEngine`` against the returned
    ``ResidentWeights`` (``weights=`` kwarg), aliasing the same arrays
    instead of re-uploading W bytes per replica."""
    cfg = model.config
    S = mesh.shape[AXIS_PP]
    tp = mesh.shape.get(AXIS_TP, 1)
    ep = mesh.shape.get(AXIS_EP, 1)
    stage_sharding = NamedSharding(mesh, P(AXIS_PP))
    replicated = NamedSharding(mesh, P())

    if stage_bounds is None:
        stage_bounds = balanced_stage_bounds(cfg.num_hidden_layers, S)
    elif len(stage_bounds) != S:
        raise ValueError(
            f"{len(stage_bounds)} stage bounds for a {S}-stage pp mesh"
        )
    stage_bounds = [tuple(b) for b in stage_bounds]
    split, masks, slots = split_stage_stacks(model, params["layers"], stage_bounds)

    # Build-time projection fusion (keep-quantized loads): concatenate
    # each declared group's packed triples along OUT so decode runs QKV
    # (and gate+up) as ONE fused projection launch sharing a single pass
    # over the activation planes. tp == 1 only — the fused OUT axis
    # interleaves the group's rows, which the column-parallel slicing
    # wouldn't split correctly. Forward code dispatches on the fused
    # name's presence in the layer pytree (models/llama.py).
    fused_projections: list[str] = []
    if tp == 1:
        from mlx_sharding_tpu.models.base import apply_projection_fusion

        fused_projections = apply_projection_fusion(model, split)

    # Per-name shard axes: tp (heads/MLP columns) and ep (expert stacks).
    # Models declare flat maps (homogeneous stacks) or nested
    # {group: {name: dim}} maps (DeepSeek's moe group). Values are
    # (per-layer dim, mesh axis name).
    def _merge(out, axes_map, axis_name):
        for n, ax in axes_map.items():
            if isinstance(ax, dict):
                out.setdefault(n, {})
                _merge(out[n], ax, axis_name)
            elif ax is not None:
                out[n] = (ax, axis_name)

    axes_by_name: dict = {}
    if tp > 1:
        _merge(axes_by_name, model.tp_layer_axes(), AXIS_TP)
    if ep > 1:
        _merge(axes_by_name, model.ep_layer_axes(), AXIS_EP)

    def _check_div(name, w, ax, axis_name):
        if w.shape[2 + ax] % mesh.shape[axis_name]:
            raise ValueError(
                f"{name} dim {w.shape[2 + ax]} not divisible over "
                f"{axis_name}={mesh.shape[axis_name]}"
            )
        dims = [AXIS_PP, None] + [None] * (w.ndim - 2)
        dims[2 + ax] = axis_name
        return P(*dims)

    def param_spec(entry, name, w):
        # (S, L, …) array → the model-declared per-layer dim shards over
        # its mesh axis, offset by the two leading stack axes
        if entry is None:
            return P(AXIS_PP)
        ax, axis_name = entry
        return _check_div(name, w, ax, axis_name)

    def quant_spec(entry, name, w):
        """Packed triples under TP/EP. The model declares axes in the
        DENSE orientation — trailing (…, in, out) matmul dims, any
        leading stack dims (the expert E axis) before them — but packed
        leaves keep those two trailing dims in MLX's (out, X) layout:
        q (out, in/8), scales/biases (out, in/group). Leading stack dims
        are layout-identical (EP's E axis shards as declared); within
        the matmul pair the dim flips: column-parallel (dense out)
        shards packed dim -2, row-parallel (dense in) shards packed
        dim -1. Per-leaf divisibility checks double as nibble-word and
        quant-group alignment guards (scales' in/group dim dividing the
        mesh axis ⇔ the in split lands on group boundaries)."""
        if entry is None:
            spec = P(AXIS_PP)
            return jax.tree.map(lambda _: spec, w)
        ax, axis_name = entry
        ndims = {a.ndim for a in w.values()}
        if len(ndims) != 1:
            raise ValueError(f"ragged packed leaves for {name}")
        nd = ndims.pop() - 2  # per-layer dims (drop the S, L stack axes)
        if ax < nd - 2:
            axq = ax  # leading stack dim (expert E): same position packed
        elif ax == nd - 1:
            axq = nd - 2  # dense out (column-parallel) → packed out dim
        else:
            axq = nd - 1  # dense in (row-parallel) → packed in/X dim
        return {
            leaf: _check_div(f"{name}.{leaf}", arr, axq, axis_name)
            for leaf, arr in w.items()
        }

    def build_specs(stack, axes):
        out = {}
        for name, w in stack.items():
            entry = axes.get(name)
            if isinstance(w, dict) and not is_quantized(w):
                out[name] = build_specs(w, entry or {})
            elif is_quantized(w):
                out[name] = quant_spec(entry, name, w)
            else:
                out[name] = param_spec(entry, name, w)
        return out

    if not axes_by_name:
        layer_specs = jax.tree.map(lambda _: P(AXIS_PP), split)
    else:
        layer_specs = build_specs(split, axes_by_name)
    layer_params = put_global(
        split,
        jax.tree.map(
            lambda s: NamedSharding(mesh, s), layer_specs,
            is_leaf=lambda x: isinstance(x, P),
        ),
    )
    layer_masks = put_global(masks, stage_sharding)

    # Vocab-shard the embedding table and LM head over pp: each device
    # holds vocab/S rows instead of a full replica (Llama-3 vocab in bf16
    # is ~1 GB/device replicated). Embedding rows are re-assembled with a
    # tiny (B,T,H) psum per tick; logits are computed per vocab shard
    # post-scan and all-gathered — (S-1)/S x V bytes/device vs the full-V
    # psum before, with head FLOPs divided by S.
    head_tied = model.head_is_tied()
    Vs = -(-cfg.vocab_size // S)
    table = params["embed"]["weight"]
    if is_quantized(table):
        # the vocab-sharded embed/head machinery is dense; a packed
        # table (keep-quantized load) dequantizes once at build — each
        # device still holds only its V/S rows afterwards
        gs, bits = model._quant_args()
        table = dequantize(
            table["q"], table["scales"], table["biases"], gs, bits,
            model.compute_dtype,
        )
    table = jnp.pad(table, ((0, Vs * S - table.shape[0]), (0, 0)))
    vparts = [table.reshape(S, Vs, -1)]
    if not head_tied:
        head = params["lm_head"]["weight"]  # (H, V)
        if is_quantized(head):
            gs, bits = model._quant_args()
            head = dequantize(
                head["q"], head["scales"], head["biases"], gs, bits,
                model.compute_dtype,
            ).T  # packed is MLX (V, H); the engine wants (H, V)
        head = jnp.pad(head, ((0, 0), (0, Vs * S - head.shape[1])))
        # (S, H, Vs) so each device's slice is its vocab shard
        vparts.append(head.reshape(-1, S, Vs).transpose(1, 0, 2))
    vocab_parts = put_global(tuple(vparts), stage_sharding)
    shared_params = put_global(
        {
            k: v for k, v in params.items()
            if k not in ("layers", "embed", "lm_head")
        },
        replicated,
    )

    # total weight bytes one decode tick streams from HBM (every param
    # leaf is read once per forward) — numerator of the
    # mst_decode_hbm_bytes_per_token{kind="weights"} gauge. Packed
    # triples count their actual packed bytes: this is where 4-bit shows
    # up as 4x less traffic than dense bf16.
    weight_bytes = sum(
        leaf.nbytes
        for leaf in jax.tree.leaves((layer_params, vocab_parts, shared_params))
    )
    return ResidentWeights(
        mesh=mesh,
        stage_bounds=stage_bounds,
        layer_specs=layer_specs,
        layer_params=layer_params,
        layer_masks=layer_masks,
        layers_per_stage=slots,
        fused_projections=fused_projections,
        vocab_size=cfg.vocab_size,
        head_tied=head_tied,
        vocab_parts=vocab_parts,
        shared_params=shared_params,
        weight_bytes=weight_bytes,
    )


def fold_block_queries(attend, q, kv_heads: int):
    """A block's ``T`` queries a slot through an attention that takes ONE
    query a slot: ``q (M, T, Hq, D)`` becomes ``(M, Hkv * T * G, D)``, each
    K/V head's ``T * G`` queries side by side — to the kernel a query group
    ``T`` times as large, every member seeing the same keys — and the result
    ``(M, Hkv * T * G, Dv)`` goes back to ``(M, T, Hq, Dv)``."""
    m, t, hq, d = q.shape
    g = hq // kv_heads
    folded = q.reshape(m, t, kv_heads, g, d).transpose(0, 2, 1, 3, 4)
    out = attend(folded.reshape(m, kv_heads * t * g, d))
    out = out.reshape(m, kv_heads, t, g, -1).transpose(0, 2, 1, 3, 4)
    return out.reshape(m, t, hq, -1)


class PipelineEngine:
    """Runs a full (unsharded-config) model across a ``pp`` mesh axis.

    ``params`` is the full model's pytree (stacked layers over ALL layers);
    layer stacks are split per stage and placed with a ``P('pp')`` sharding.
    The embedding table and LM head are vocab-sharded over pp (each device
    holds vocab/S rows; see the collectives in ``_vs_embed``/``_vs_head``);
    only the final norm stays replicated. The KV cache is one global array
    sharded on its leading stage axis — stage-local in HBM, exactly the
    reference's "KV stays on the shard" invariant (shard/server/server.py:9-10)
    without the process.
    """

    def __init__(
        self,
        model,
        params: dict,
        mesh: Mesh,
        *,
        stage_bounds=None,
        microbatches: int = 1,
        batch: int = 1,
        max_seq: int = 4096,
        cache_dtype=jnp.bfloat16,
        prefill_chunk: int = 256,
        decode_block: int = 16,
        pool_pages: Optional[int] = None,
        page_size: Optional[int] = None,
        paged_attention: str = "auto",
        kv_dtype: Optional[str] = None,
        kv_share_map=None,
        kv_compress_map=None,
        weights: Optional[ResidentWeights] = None,
    ):
        cfg = model.config
        if not (cfg.is_first_stage and cfg.is_last_stage):
            raise ValueError("PipelineEngine wants the full model config")
        self.model = model
        self.mesh = mesh
        self.num_stages = mesh.shape[AXIS_PP]
        self.tp = mesh.shape.get(AXIS_TP, 1)
        self.microbatches = microbatches
        self.batch = batch
        # chunk-multiple capacity: padded prefill writes stay in bounds
        self.max_seq = -(-max_seq // prefill_chunk) * prefill_chunk
        self.cache_dtype = cache_dtype
        self.prefill_chunk = prefill_chunk
        self.decode_block = decode_block

        # Paged KV (continuous-batching only): slots address up to
        # max_seq/page_size pages out of a SHARED pool of ``pool_pages``
        # physical pages per stage, instead of each owning a dense max_seq
        # allocation. The scheduler reserves pages at admission — mixed-
        # length workloads pack the pool far tighter than M x max_seq.
        self.paged = pool_pages is not None
        self.page_size = page_size or prefill_chunk
        self.pool_pages = pool_pages or 0
        if self.paged:
            if self.page_size % prefill_chunk:
                raise ValueError(
                    f"page_size {self.page_size} must be a multiple of the "
                    f"prefill chunk {prefill_chunk} (chunk writes must stay "
                    "inside one page)"
                )
            if self.max_seq % self.page_size:
                raise ValueError(
                    f"page_size {self.page_size} must divide max_seq "
                    f"{self.max_seq}"
                )
        self.slot_pages = self.max_seq // self.page_size  # table width

        # int8 paged KV: pool leaves become {d: int8 data, s: f32 per-row-
        # per-head scale (trailing dim 1)} dicts — halves KV bytes per
        # ragged-attention tick and ~doubles the slots a fixed pool holds.
        if kv_dtype is None and self.paged:
            # checkpoint may pin it (config.kv_cache_dtype); dense engines
            # ignore the pin rather than erroring on int8-tagged checkpoints
            kv_dtype = getattr(model.config, "kv_cache_dtype", None)
        if kv_dtype not in (None, "bf16", "bfloat16", "int8"):
            raise ValueError(f"kv_dtype={kv_dtype!r}: want int8 or bf16")
        self.kv_quant = kv_dtype == "int8"
        if self.kv_quant and not self.paged:
            raise ValueError(
                "kv_dtype='int8' requires a paged engine (pool_pages)"
            )

        # KVSharer layer-wise KV sharing (kv_share.KVShareMap): the pool
        # allocates one physical (k, v) buffer per share-GROUP and every
        # layer reads/writes through the group indirection. The identity
        # map keeps the unshared fast paths selected (and hashes to None
        # so legacy exported blocks compose). Validation against the
        # engine's LOCAL layer count happens below, once the resident
        # weights resolve the stage split.
        self.has_state = has_slot_state(model)
        self.has_recurrent = has_recurrent_state(model)
        # a model that generates by diffusion over blocks: a decode step is
        # a forward over every slot's whole block (diffusion.py)
        self.diffusion_block = diffusion.block_of(model)
        for flag, on in (
            ("--num-stages", self.num_stages > 1),
            ("--tp", self.tp > 1),
            ("--ep", mesh.shape.get(AXIS_EP, 1) > 1),
            ("--kv-share-map", kv_share_map is not None),
            ("--kv-compress-map", kv_compress_map is not None),
        ):
            if on:
                diffusion.refuse(model, flag)
        # window layers keep their K/V as one ring per slot in the state
        # pool (cache.py): rows that do not grow with the context
        self.ring_rows = (
            window_ring_rows(
                cfg.sliding_window, prefill_chunk, self.page_size, self.max_seq
            )
            if has_window_layers(model) else 0
        )
        if kv_share_map is not None:
            refuse_recurrent(
                model, "--kv-share-map",
                "a share map groups the layers of one K/V pool",
            )
            if not self.paged:
                raise ValueError(
                    "kv_share_map requires a paged engine (pool_pages)"
                )
            if self.num_stages != 1:
                raise ValueError(
                    "kv_share_map requires a pp=1 engine: share groups "
                    "span the full layer stack, which a stage split cuts"
                )
        self.kv_share = kv_share_map
        self.kv_share_hash = (
            kv_share_map.share_hash if kv_share_map is not None else None
        )
        self._share_active = (
            kv_share_map is not None and not kv_share_map.is_identity
        )
        self.kv_share_bytes_saved = 0  # filled by init_cache_paged

        tp_axes = model.tp_layer_axes()
        if self.tp > 1:
            if not tp_axes:
                raise ValueError(
                    f"tensor parallelism is not wired for {type(model).__name__}"
                )
            if (
                not model.cache_tp_replicated()
                and model.cache_num_heads() % self.tp
            ):
                raise ValueError(
                    f"tp={self.tp} must divide the {model.cache_num_heads()} "
                    "KV heads"
                )
        self.ep = mesh.shape.get(AXIS_EP, 1)
        if self.ep > 1 and not model.ep_layer_axes():
            raise ValueError(
                f"expert parallelism is not wired for {type(model).__name__}"
            )

        # Paged T=1 decode attention path: "ragged" attends over the page
        # pool in place (ops/paged_attention.py — no per-tick gather/
        # scatter); "gather" keeps the _paged_read contiguous view;
        # "auto" picks ragged whenever the wiring supports it. The ragged
        # body rides the sp_layer hook (injected attention), which has no
        # tp/ep plumbing, and the S==1 vectorized shape.
        if paged_attention not in ("auto", "ragged", "gather"):
            raise ValueError(
                f"paged_attention={paged_attention!r}: want auto|ragged|gather"
            )
        ragged_ok = (
            self.paged
            and self.num_stages == 1
            and self.tp == 1
            and self.ep == 1
            and self.batch == 1
            # the sp_layer hook, or a layer walk of the model's own that
            # takes the pool attention (run_layers(paged_attn=...))
            and (getattr(model, "supports_sp", False) or self.has_state)
        )
        if paged_attention == "ragged" and not ragged_ok:
            raise ValueError(
                "paged_attention='ragged' needs a paged (pool_pages) pp=1 "
                "engine with tp=ep=1, batch=1, and a model with supports_sp"
            )
        self.paged_attention = (
            "ragged" if paged_attention in ("auto", "ragged") and ragged_ok
            else "gather"
        )
        if self.diffusion_block:
            if self.paged_attention != "ragged":
                diffusion.refuse(model, "--paged-pool")
            if self.page_size % self.diffusion_block:
                raise ValueError(
                    f"a block of {self.diffusion_block} must divide the page "
                    f"of {self.page_size}: a block's rows lie in one page"
                )
        # run_layers parallelism kwargs, shared by every step body
        self._rl_kwargs = {}
        if self.tp > 1:
            self._rl_kwargs["tp_axis"] = AXIS_TP
        if self.ep > 1:
            self._rl_kwargs["ep_axis"] = AXIS_EP

        # under TP the KV heads axis is sharded too: each (pp, tp) device
        # holds its stage's cache for its own heads only. A head-count-
        # independent cache (model.cache_tp_replicated: DeepSeek's compressed
        # shared latent) replicates over tp instead, every tp device
        # computing identical writes from the replicated latent projections.
        self._kv_spec = (
            P(AXIS_PP, None, None, None, None, AXIS_TP)
            if self.tp > 1 and not model.cache_tp_replicated() else P(AXIS_PP)
        )

        # Weight residency. Private path: build this engine's own
        # device-resident tree (the full W-byte upload — split, fuse,
        # place). Aliased path (``weights=``): a ``weights.WeightStore``
        # lease already holds the resident tree for
        # this exact placement, and N data-parallel replicas execute
        # against the SAME arrays — constructing the engine costs
        # slot/cache setup only. The caller owns the lease and wires its
        # release through ``on_close()``.
        if weights is None:
            weights = place_weights(
                model, params, mesh, stage_bounds=stage_bounds
            )
            self.weights_shared = False
        else:
            if not same_mesh_devices(weights.mesh, mesh):
                raise ValueError(
                    "resident weights were placed on a different device "
                    "grid than this engine's mesh — aliased construction "
                    "needs identical placement (same devices, same axis "
                    "layout)"
                )
            if stage_bounds is not None and [
                tuple(b) for b in stage_bounds
            ] != list(weights.stage_bounds):
                raise ValueError(
                    f"stage_bounds {list(stage_bounds)} disagree with the "
                    f"resident tree's split {list(weights.stage_bounds)}"
                )
            # adopt the resident tree's Mesh OBJECT, not just an equal
            # grid: shard_map programs closed over the same mesh share
            # trace caches across aliased replicas
            self.mesh = mesh = weights.mesh
            self.weights_shared = True
        self.resident = weights
        self.stage_bounds = list(weights.stage_bounds)
        self.layer_specs = weights.layer_specs
        self.layer_params = weights.layer_params
        self.layer_masks = weights.layer_masks
        self.layers_per_stage = weights.layers_per_stage
        self.fused_projections = list(weights.fused_projections)
        if self.has_state:
            # the model walks its interleaved layer groups itself: tell it
            # what each stage runs at each position, and how many rows the
            # stage's state pool has (the slots of the groups that keep state)
            self._rl_kwargs["plan"] = model.stage_plan(self.stage_bounds)
            self._rl_kwargs["stage_axis"] = AXIS_PP
            self.state_layers = (
                model.state_layer_slots(self.stage_bounds)
                if hasattr(model, "state_layer_slots")
                else sum(
                    self.layer_masks[g].shape[1] for g in model.state_groups()
                )
            )
        self.vocab_size = weights.vocab_size
        self._head_tied = weights.head_tied
        self.vocab_parts = weights.vocab_parts
        self.shared_params = weights.shared_params
        self.weight_stream_bytes = weights.weight_bytes
        if self.kv_share is not None:
            # the map must cover exactly this engine's local layer stack
            # (padding from uneven heterogeneous splits counts — reject
            # rather than guess which stacked slots are real)
            self.kv_share.validate_for(self.layers_per_stage)
        # Compressed-latent KV transport (kv_compress.py): MLA-native
        # pools get the exact latent codec automatically; a calibrated
        # map opts a GQA pool into bounded-error lowrank. The codec rides
        # every KVPageBlock export so spill flushes, prefix demotions,
        # federation blobs, and handoff wires all move the compact form.
        from mlx_sharding_tpu.kv_compress import build_codec

        if kv_compress_map is not None:
            refuse_recurrent(
                model, "--kv-compress-map",
                "the codec transports pages of K/V only",
            )
        pool_layers = (
            self.kv_share.num_groups if self._share_active
            else self.layers_per_stage
        )
        self.kv_codec = build_codec(
            model,
            paged=self.paged,
            kv_quant=self.kv_quant,
            num_stages=self.num_stages,
            pool_layers=pool_layers,
            share_hash=self.kv_share_hash,
            compress_map=kv_compress_map,
        )
        self.kv_compress_hash = (
            self.kv_codec.compress_hash if self.kv_codec is not None else None
        )
        # resources the engine holds beyond its own arrays (today: the
        # shared-weight lease release) — close() runs each exactly once
        self._close_hooks: list = []

        self._decode = self._build_step(t_len=1, with_sampling=True)
        self._prefill = self._build_step(t_len=prefill_chunk, with_sampling=False)
        self._sample = jax.jit(self._sample_fn, donate_argnums=(1,))
        # continuous-batching programs, built on first use by the scheduler
        self._decode_cb = None
        self._diffusion_cbs: dict = {}  # (want_lp, wide) → forward + epilogue
        self._diffusion_bodies: dict = {}  # lanes → the ragged body at T = L
        self._prefill_slot = None
        self._decode_blocks: dict = {}  # (k_steps, want_lp) → jitted block
        self._spec_progs: dict = {}  # ("propose"|"verify", K) → jitted prog

    def on_close(self, cb):
        """Register a teardown callback (run once, from close()). The
        shared-weights spawn path hangs the store lease's release here, so
        drain/retire/hot-swap teardown — which all funnel through
        ``close()`` — decrement the refcount and the LAST engine frees the
        tree."""
        self._close_hooks.append(cb)

    def close(self):
        """Release resources held beyond the engine's own arrays.
        Idempotent: hooks run exactly once, so the drain→retire→fleet-close
        sequence (each of which closes the replica) releases a shared
        weight lease once, not thrice."""
        hooks, self._close_hooks = self._close_hooks, []
        for cb in hooks:
            cb()

    def decode_cb(self):
        if self._decode_cb is None:
            self._decode_cb = self._build_decode_cb()
        return self._decode_cb

    def decode_block_prog(self, k_steps: int, want_lp: bool):
        """K single-token decode steps scanned into ONE program — the host
        pulls tokens once per block instead of once per token (see
        generate.Generator: a per-token host pull can dominate the device
        step). Logprob summaries (chosen + top-10 via lax.top_k) are
        computed inside the scan when requested."""
        cache_key = (k_steps, want_lp)
        if cache_key not in self._decode_blocks:
            step, M, B = self._decode, self.microbatches, self.batch
            one = jnp.asarray(1, jnp.int32)

            def solo_block(layer_params, masks, vparts, shared, tok, cache, recent, key, sp):
                def body(carry, _):
                    tok, cache, recent, key = carry
                    tok, logprobs, cache, recent, key = step(
                        layer_params, masks, vparts, shared, tok[..., None],
                        cache, recent, key, sp, one,
                    )
                    if want_lp:
                        from mlx_sharding_tpu.generate import block_lp_outputs

                        out = (tok, *block_lp_outputs(tok.reshape(M * B), logprobs))
                    else:
                        out = (tok,)
                    return (tok, cache, recent, key), out

                (tok, cache, recent, key), outs = jax.lax.scan(
                    body, (tok, cache, recent, key), None, length=k_steps
                )
                return outs, tok, cache, recent, key

            # one name per program (jit_<name> in a profile): the block with
            # log-probabilities is another program than the one without
            if want_lp:
                solo_block.__name__ = solo_block.__qualname__ = "solo_block_lp"
            self._decode_blocks[cache_key] = jax.jit(solo_block, donate_argnums=(5, 6))
        return self._decode_blocks[cache_key]

    def prefill_slot(self):
        if self._prefill_slot is None:
            self._prefill_slot = self._build_prefill_slot()
        return self._prefill_slot

    # ------------------------------------------------------------------
    def init_cache(self) -> KVCache:
        cfg = self.model.config
        hd = self.model.cache_head_dim()
        k_dim, v_dim = (hd, hd) if not isinstance(hd, (tuple, list)) else hd
        S, L, M, B = (
            self.num_stages,
            self.layers_per_stage,
            self.microbatches,
            self.batch,
        )
        shape = (S, L, M + 1, B, self.max_seq, self.model.cache_num_heads())
        sharding = NamedSharding(self.mesh, self._kv_spec)
        # offset is PER MICROBATCH SLOT: continuous batching runs a different
        # request (at a different sequence position) in every slot
        return KVCache(
            k=put_global(jnp.zeros((*shape, k_dim), self.cache_dtype), sharding),
            v=put_global(jnp.zeros((*shape, v_dim), self.cache_dtype), sharding),
            offset=put_global(
                jnp.zeros((M,), jnp.int32), NamedSharding(self.mesh, P())
            ),
            state=self._init_state(),
        )

    def _init_state(self):
        """The per-slot recurrent state pool of a model that has one:
        ``{name: (S, state layers, (M+1) * B, …)}`` — slot m's sequences are
        rows ``m * B .. (m+1) * B``, the last B rows the scratch slot that
        garbage ticks read and write; sharded over pp like the K/V pool.
        (Slot and batch share ONE axis: with a B == 1 axis of its own between
        them the chip lays the array out with that axis outermost, and the
        decode block's loop then copies the whole pool in and out of its
        carry every step.) None otherwise."""
        if not self.has_state:
            return None
        S, rows = self.num_stages, (self.microbatches + 1) * self.batch
        pool = {
            name: jnp.zeros(
                (S, self.state_layers, rows, *shape[1:]), dt or self.cache_dtype
            )
            for name, (shape, dt) in self._state_shapes().items()
        }
        return put_global(pool, NamedSharding(self.mesh, P(AXIS_PP)))

    def _state_shapes(self) -> dict:
        sized = {"ring_rows": self.ring_rows} if self.ring_rows else {}
        return self.model.state_shapes(self.batch, **sized)

    def state_bytes(self) -> int:
        """Bytes of the state pool — recurrent state, or window layers'
        rings (0 without one)."""
        if not self.has_state:
            return 0
        return sum(
            self.num_stages * self.state_layers * (self.microbatches + 1)
            * int(np.prod(shape)) * jnp.dtype(dt or self.cache_dtype).itemsize
            for shape, dt in self._state_shapes().values()
        )

    def init_cache_paged(self) -> tuple[KVCache, jax.Array]:
        """Shared page pool + per-slot page table for continuous batching.

        Pool: (S, L, pool_pages+1, B, page, H, D) per stage — the last page
        is scratch: every unallocated table entry points there, so writes
        from inactive ticks and past-a-request's-reservation overshoot land
        harmlessly (the dense layout's scratch-slice trick, per page).
        Table: (M+1, slot_pages) int32 — row M is the all-scratch row
        garbage ticks route to. Table entries are POOL page ids; position p
        of slot m lives at pool page table[m][p // page_size], row
        p % page_size."""
        if not self.paged:
            raise ValueError("engine built without pool_pages")
        cfg = self.model.config
        hd = self.model.cache_head_dim()
        k_dim, v_dim = (hd, hd) if not isinstance(hd, (tuple, list)) else hd
        S, L, M, B = (
            self.num_stages, self.layers_per_stage, self.microbatches,
            self.batch,
        )
        # KVSharer: the pool's layer axis shrinks to the share-GROUP count —
        # one physical buffer per group, every layer a logical view
        L_pool = self.kv_share.num_groups if self._share_active else L
        shape = (
            S, L_pool, self.pool_pages + 1, B, self.page_size,
            self.model.cache_num_heads(),
        )
        sharding = NamedSharding(self.mesh, self._kv_spec)

        def pool(dim):
            if not self.kv_quant:
                return jnp.zeros((*shape, dim), self.cache_dtype)
            # int8 pool: data + per-row-per-head scale (trailing dim 1
            # broadcasts over head_dim) — D+4 bytes per row-head vs 2D bf16
            return {
                "d": jnp.zeros((*shape, dim), jnp.int8),
                "s": jnp.zeros((*shape, 1), jnp.float32),
            }

        cache = KVCache(
            k=put_global(pool(k_dim), sharding),
            v=put_global(pool(v_dim), sharding),
            offset=put_global(
                jnp.zeros((M,), jnp.int32), NamedSharding(self.mesh, P())
            ),
            state=self._init_state(),
        )
        table = put_global(
            jnp.full((M + 1, self.slot_pages), self.pool_pages, jnp.int32),
            NamedSharding(self.mesh, P()),
        )
        if self._share_active:
            # the allocation that DIDN'T happen: an unshared pool would be
            # L/G times these leaves (dtype/scale structure identical)
            pool_bytes = sum(
                leaf.nbytes for leaf in jax.tree.leaves((cache.k, cache.v))
            )
            self.kv_share_bytes_saved = int(
                pool_bytes * (L - L_pool) / L_pool
            )
        return cache, table

    def kv_share_stats(self) -> dict:
        """Observability surface for the ``mst_kv_share_*`` family."""
        m = self.kv_share
        return {
            "enabled": bool(self._share_active),
            "groups": m.num_groups if m is not None else self.layers_per_stage,
            "layers": self.layers_per_stage,
            "bytes_saved": int(self.kv_share_bytes_saved),
            "share_hash": self.kv_share_hash,
        }

    def kv_compress_stats(self) -> Optional[dict]:
        """Observability surface for the ``mst_kv_compress_*`` family —
        None when no codec is active (flag off, non-MLA model)."""
        return self.kv_codec.stats() if self.kv_codec is not None else None

    # ----------------------------------------------------- vocab sharding
    @jax.named_scope("mst.embed")
    def _vs_embed(self, s, vparts, ids):
        """Embedding lookup against this device's vocab shard + psum to
        assemble full rows (only the owner contributes non-zeros)."""
        table = vparts[0]  # (Vs, H)
        Vs = table.shape[0]
        lo = s * Vs
        rows = jnp.take(table, jnp.clip(ids - lo, 0, Vs - 1), axis=0)
        owned = (ids >= lo) & (ids < lo + Vs)
        rows = jnp.where(owned[..., None], rows, jnp.zeros((), rows.dtype))
        return self.model.embed_transform(jax.lax.psum(rows, AXIS_PP))

    @jax.named_scope("mst.head")
    def _vs_head(self, shared, vparts, h):
        """Final norm + per-shard vocab projection + all-gather. ``h`` must
        already be replicated (post-psum of the banked hidden states)."""
        model = self.model
        hn = model.head_input(shared, h)
        if self._head_tied:
            w = vparts[0]  # (Vs, H) — the embedding shard, transposed in-op
            logits = jnp.einsum("...h,vh->...v", hn, w)
        else:
            logits = hn @ vparts[1]  # (H, Vs)
        logits = model.head_transform(logits)
        full = jax.lax.all_gather(logits, AXIS_PP, axis=logits.ndim - 1, tiled=True)
        return full[..., : self.vocab_size].astype(jnp.float32)

    # ------------------------------------------------------------------
    def _paged_read(self, k, v, table_row):
        """Gather one slot's pages into the contiguous (L, B, S_virt, H, D)
        view run_layers expects. k/v: local pool (L, P+1, B, page, H, D) —
        or the int8 ``{d, s}`` pair, which dequantizes AFTER the gather so
        the pool→registers traffic is the int8 bytes, not the dense view.
        Under a KV share map the pool's leading axis is the GROUP count;
        the group rows expand to the per-layer view post-dequantize, so
        pool→registers traffic stays the G-sized bytes."""

        def gather(pool):
            g = jnp.take(pool, table_row, axis=1)  # (L, SPG, B, page, H, D)
            g = jnp.moveaxis(g, 1, 2)  # (L, B, SPG, page, H, D)
            return g.reshape(*g.shape[:2], -1, *g.shape[4:])

        out = tuple(
            dequantize_kv(jax.tree.map(gather, pool), self.cache_dtype)
            for pool in (k, v)
        )
        if self._share_active:
            gids = jnp.asarray(self.kv_share.group_of, jnp.int32)
            out = tuple(jnp.take(x, gids, axis=0) for x in out)
        return out

    def _paged_writeback(self, pool, buf, table_row, offset, n_pages=1):
        """Scatter the dirty page(s) of a slot's contiguous buffer back into
        the pool, starting at the page containing ``offset``. Chunk writes
        never straddle pages (page_size % prefill_chunk == 0 and offsets are
        chunk-aligned), so prefill and T=1 decode pass n_pages=1; a T=K
        speculative verify writes K rows at an arbitrary offset and passes
        the worst-case straddle count. Writing back a page the step didn't
        touch is idempotent (it holds exactly what the gather read — for the
        int8 pool, requantizing a dequantized row reproduces the same codes
        because the stored max element sits exactly at ±127, pinning the
        recomputed scale)."""
        quant = isinstance(pool, dict)
        if self._share_active:
            # only the owner layer's rows persist: reduce the expanded
            # (L, …) view back to the pool's (G, …) axis before scatter —
            # non-owner layers attended over the owner's history plus their
            # own current-tick rows, which are discarded here by design
            buf = jnp.take(
                buf, jnp.asarray(self.kv_share.owner_layers, jnp.int32),
                axis=0,
            )
        l, b = buf.shape[:2]
        page = self.page_size
        buf6 = buf.reshape(l, b, self.slot_pages, page, *buf.shape[3:])
        for i in range(n_pages):
            # out-of-range pidx clamps (dynamic_index semantics) to the last
            # buffer page and its table entry — an idempotent re-write
            pidx = jnp.minimum(offset // page + i, self.slot_pages - 1)
            dirty = jax.lax.dynamic_index_in_dim(buf6, pidx, 2, keepdims=False)
            if quant:  # quantize-on-writeback: the dense page never lands
                dirty = quantize_kv_rows(dirty)
                pool = jax.tree.map(
                    lambda p, d: jax.lax.dynamic_update_index_in_dim(
                        p, d.astype(p.dtype), table_row[pidx], 1
                    ),
                    pool, dirty,
                )
            else:
                pool = jax.lax.dynamic_update_index_in_dim(
                    pool, dirty.astype(pool.dtype), table_row[pidx], 1
                )
        return pool

    @jax.named_scope("mst.kv_pool.regroup")
    def _kv_read(self, paged, k, v, table, m_write):
        """One slot's contiguous KV view: page-table gather (paged) or
        slot-axis index (dense). Returns (k_m, v_m, table_row)."""
        if paged:
            row = table[m_write]
            k_m, v_m = self._paged_read(k, v, row)
            return k_m, v_m, row
        k_m = jax.lax.dynamic_index_in_dim(k, m_write, 1, keepdims=False)
        v_m = jax.lax.dynamic_index_in_dim(v, m_write, 1, keepdims=False)
        return k_m, v_m, None

    @jax.named_scope("mst.attn.kv_write")
    def _kv_write(self, paged, k, v, k_m, v_m, row, m_write, offset, n_pages=1):
        """Inverse of _kv_read: scatter the dirty page(s) back (paged) or
        update the slot slice (dense)."""
        if paged:
            return (
                self._paged_writeback(k, k_m, row, offset, n_pages),
                self._paged_writeback(v, v_m, row, offset, n_pages),
            )
        return (
            jax.lax.dynamic_update_index_in_dim(k, k_m, m_write, 1),
            jax.lax.dynamic_update_index_in_dim(v, v_m, m_write, 1),
        )

    @jax.named_scope("mst.state_pool.regroup")
    def _state_read(self, state, m_write, offset):
        """One slot's recurrent state out of the local pool ``(L, (M+1) * B,
        …)``. Position 0 has no history: whatever the row holds then (the
        slot's last occupant, a block that ran past its end) reads as zero —
        which is what resets a reused slot, chained on the device after
        anything in flight, with no dispatch of its own. (A ring of window
        K/V needs no reset: its rows are addressed by position.)"""
        rows = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            x, m_write * self.batch, self.batch, axis=1
        )
        if not self.has_recurrent:
            return jax.tree.map(rows, state)
        return jax.tree.map(
            lambda x: jnp.where(offset == 0, jnp.zeros((), x.dtype), rows(x)),
            state,
        )

    @jax.named_scope("mst.state_pool.regroup")
    def _state_write(self, state, new, m_write):
        return jax.tree.map(
            lambda x, n: jax.lax.dynamic_update_slice_in_dim(
                x, n.astype(x.dtype), m_write * self.batch, axis=1
            ),
            state, new,
        )

    def _run_slot(self, layer_params, masks, h_in, k_m, v_m, offset, state,
                  m_write, n_valid):
        """One slot's pass through this stage's layers: ``(h, k_m, v_m,
        state)``. With a state pool the slot's rows are read (zero at
        position 0), advanced over the ``n_valid`` real rows of the chunk and
        written back; without one ``state`` stays None."""
        if state is None:
            return (*self.model.run_layers(
                layer_params, h_in, k_m, v_m, offset, mask=masks,
                **self._rl_kwargs,
            ), None)
        h_out, k_m, v_m, st_m = self.model.run_layers(
            layer_params, h_in, k_m, v_m, offset, mask=masks,
            state=self._state_read(state, m_write, offset), n_valid=n_valid,
            **self._rl_kwargs,
        )
        return h_out, k_m, v_m, self._state_write(state, st_m, m_write)

    def _build_step(self, t_len: int, with_sampling: bool):
        smapped = self._build_smapped(t_len)
        return self._finish_step(smapped, t_len, with_sampling)

    def _build_smapped(self, t_len: int, paged: bool = False,
                       keep_all: bool = False):
        """``keep_all`` banks logits for EVERY position instead of only the
        last valid one — the T=K speculative verify needs all K scores. Only
        the S == 1 vectorized body supports it (speculative continuous
        batching is gated to pp=1)."""
        model, S, M, B = self.model, self.num_stages, self.microbatches, self.batch
        rl_kwargs = self._rl_kwargs
        if keep_all and S != 1:
            raise ValueError("keep_all logits need the S == 1 vectorized body")
        # int8 pools are {d, s} dicts: index/stack per leaf, and take the
        # compute dtype from the engine instead of the storage leaf
        cdt = self.cache_dtype
        unstack = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
        restack = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731

        def body(layer_params, masks, vparts, shared, tokens, k, v, offsets,
                 active, n_valid, table, state):
            # Per-device views: layer_params (1, L, …) → (L, …); k/v
            # (1, L, M+1, B, seq, H, D) → (L, M+1, …). ``offsets`` is (M,) —
            # each slot's sequence position — and ``active`` (M,) bool marks
            # slots holding a live request (inactive slots' compute is routed
            # to the scratch cache slice and their logits are garbage the
            # scheduler ignores).
            layer_params = jax.tree.map(lambda x: x[0], layer_params)
            masks = jax.tree.map(lambda x: x[0], masks)
            vparts = jax.tree.map(lambda x: x[0], vparts)
            k, v, state = unstack(k), unstack(v), unstack(state)
            s = jax.lax.axis_index(AXIS_PP)
            h0 = jnp.zeros((B, t_len, model.config.hidden_size), cdt)
            # bank HIDDEN states, not logits: the vocab projection runs once
            # post-scan against this device's vocab shard
            out0 = jnp.zeros((M, B, model.config.hidden_size), cdt)
            offsets_pad = jnp.concatenate([offsets, jnp.zeros((1,), jnp.int32)])

            def tick(carry, t):
                h_buf, k, v, state, out = carry
                m = jnp.clip(t - s, 0, M - 1)
                is_real = (t >= s) & (t - s < M) & active[m]

                tok_m = jax.lax.dynamic_index_in_dim(
                    tokens, jnp.clip(t, 0, M - 1), 0, keepdims=False
                )  # (B, T)
                h_first = self._vs_embed(s, vparts, tok_m).astype(h_buf.dtype)
                h_in = jnp.where(s == 0, h_first, h_buf)

                # scratch slice M swallows non-real writes (paged mode:
                # table row M routes every page to the scratch pool page)
                m_write = jnp.where(is_real, m, M)
                offset = offsets_pad[m_write]
                k_m, v_m, row = self._kv_read(paged, k, v, table, m_write)
                h_out, k_m, v_m, state = self._run_slot(
                    layer_params, masks, h_in, k_m, v_m, offset, state,
                    m_write, n_valid,
                )
                k, v = self._kv_write(paged, k, v, k_m, v_m, row, m_write, offset)

                # bank the last-valid-position hidden state on the final stage
                last = jax.lax.dynamic_index_in_dim(h_out, n_valid - 1, 1, keepdims=False)
                is_real_out = is_real & (s == S - 1)
                m_out = jnp.clip(t - (S - 1), 0, M - 1)
                out = jax.lax.dynamic_update_index_in_dim(
                    out, jnp.where(is_real_out, last.astype(out.dtype), out[m_out]),
                    m_out, 0,
                )

                h_next = jax.lax.ppermute(
                    h_out, AXIS_PP, [(i, (i + 1) % S) for i in range(S)]
                )
                return (h_next, k, v, state, out), None

            (h_buf, k, v, state, out), _ = jax.lax.scan(
                tick, (h0, k, v, state, out0), jnp.arange(S + M - 1)
            )
            out = jax.lax.psum(out, AXIS_PP)  # only stage S-1 contributed
            logits = self._vs_head(shared, vparts, out)  # (M, B, V) f32
            return logits, restack(k), restack(v), restack(state)

        def body_s1(layer_params, masks, vparts, shared, tokens, k, v,
                    offsets, active, n_valid, table, state):
            """S == 1 fast path: every microbatch is resident on the one
            stage, so the tick rotation above — which would run M sequential
            forwards, streaming the weights M times — collapses to ONE
            vmapped forward. XLA batches each layer's matmuls over the M
            lanes, so the M-slot continuous-batching step streams the
            weights once: aggregate decode throughput scales with slots
            instead of dividing by them. Per-lane KV views are gathered
            up front (the same reads the tick path does) and the dirty
            slices written back in a short sequential loop — lanes only
            ever collide on the scratch slice, where order is garbage
            anyway."""
            layer_params = jax.tree.map(lambda x: x[0], layer_params)
            masks = jax.tree.map(lambda x: x[0], masks)
            vparts = jax.tree.map(lambda x: x[0], vparts)
            k, v, state = unstack(k), unstack(v), unstack(state)
            s = jax.lax.axis_index(AXIS_PP)
            offsets_pad = jnp.concatenate([offsets, jnp.zeros((1,), jnp.int32)])
            m_write = jnp.where(active, jnp.arange(M), M)  # inactive → scratch
            offset_m = offsets_pad[m_write]

            if tokens.ndim == 2:
                # the continuous-batching step passes (M, B) single tokens
                # (the tick body relied on where() broadcasting them up)
                tokens = tokens[..., None]
            h_all = self._vs_embed(s, vparts, tokens).astype(cdt)  # (M, B, T, H)

            def read(mw):
                k_m, v_m, row = self._kv_read(paged, k, v, table, mw)
                return (k_m, v_m, row) if paged else (k_m, v_m, mw)

            k_ms, v_ms, rows = jax.vmap(read)(m_write)

            if state is None:
                def micro(h_m, k_m, v_m, off):
                    return model.run_layers(
                        layer_params, h_m, k_m, v_m, off, mask=masks, **rl_kwargs
                    )

                h_outs, k_ms, v_ms = jax.vmap(micro)(h_all, k_ms, v_ms, offset_m)
            else:
                def micro_st(h_m, k_m, v_m, off, mw):
                    return model.run_layers(
                        layer_params, h_m, k_m, v_m, off, mask=masks,
                        state=self._state_read(state, mw, off),
                        n_valid=n_valid, **rl_kwargs,
                    )

                h_outs, k_ms, v_ms, st_ms = jax.vmap(micro_st)(
                    h_all, k_ms, v_ms, offset_m, m_write
                )

            # T=K writes at a decode (non-chunk-aligned) offset can straddle
            # pages; prefill/decode offsets never do (page % chunk == 0)
            wb = (
                (t_len + self.page_size - 2) // self.page_size + 1
                if paged and keep_all else 1
            )

            def wr(i, kvs):
                k, v, state = kvs
                if state is not None:
                    state = self._state_write(
                        state, jax.tree.map(lambda x: x[i], st_ms), m_write[i]
                    )
                return (*self._kv_write(
                    paged, k, v, k_ms[i], v_ms[i],
                    rows[i] if paged else None, m_write[i], offset_m[i], wb,
                ), state)

            k, v, state = jax.lax.fori_loop(0, M, wr, (k, v, state))
            if keep_all:
                out = jnp.where(
                    active[:, None, None, None], h_outs, 0
                ).astype(cdt)  # (M, B, T, H) — every position's hidden
            else:
                out = jax.lax.dynamic_index_in_dim(
                    h_outs, n_valid - 1, 2, keepdims=False
                )  # (M, B, H)
                out = jnp.where(active[:, None, None], out, 0).astype(cdt)
            out = jax.lax.psum(out, AXIS_PP)  # identity at S=1; keeps the
            # body shape identical to the rotated one
            logits = self._vs_head(shared, vparts, out)
            return logits, restack(k), restack(v), restack(state)

        if S == 1:
            body = body_s1

        spec_stage, spec_rep = P(AXIS_PP), P()
        inner = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                self.layer_specs,
                jax.tree.map(lambda _: spec_stage, self.layer_masks),
                jax.tree.map(lambda _: spec_stage, self.vocab_parts),
                jax.tree.map(lambda _: spec_rep, self.shared_params),
                spec_rep,  # tokens
                self._kv_spec,  # k
                self._kv_spec,  # v
                spec_rep,  # offsets (M,)
                spec_rep,  # active (M,)
                spec_rep,  # n_valid
                spec_rep,  # page table (paged mode; dummy otherwise)
                spec_stage,  # recurrent state pool (None: no leaves)
            ),
            out_specs=(spec_rep, self._kv_spec, self._kv_spec, spec_stage),
            check_vma=False,
        )
        if paged:
            return inner
        dummy_table = jnp.zeros((1, 1), jnp.int32)

        def smapped(layer_params, masks, vparts, shared, tokens, k, v, offsets,
                    active, n_valid, state=None):
            return inner(
                layer_params, masks, vparts, shared, tokens, k, v, offsets,
                active, n_valid, dummy_table, state,
            )

        if t_len == 1 and not keep_all:
            self._smapped_decode = smapped  # shared by the continuous-batching step
        return smapped

    def _scan_layers_shared(self, layer_fn, h, layer_params, k_pool, v_pool,
                            gids, own, mask=None):
        """Share-map variant of ``models.base.scan_layers`` for the ragged
        body: the pool stays GROUP-sized in the scan *carry* (an L-sized
        xs/ys pool would materialize the very transient the share map
        exists to avoid). Each layer dynamic-indexes its group's buffer
        out of the carry; after the layer runs, only the group OWNER's
        writes persist — a non-owner layer attends over the owner's
        history plus its own current-tick rows and then discards them,
        and a masked-out padding layer persists nothing."""

        def body(carry, xs):
            h, k_pool, v_pool = carry
            if mask is None:
                p, gid, keep = xs
                m_l = None
            else:
                p, gid, keep, m_l = xs
                keep = keep & m_l
            idx = lambda pool: jax.tree.map(  # noqa: E731
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, gid, 0, keepdims=False
                ),
                pool,
            )
            k_buf, v_buf = idx(k_pool), idx(v_pool)
            h2, k2, v2 = layer_fn(h, p, k_buf, v_buf)
            if m_l is not None:
                h2 = jnp.where(m_l, h2, h)
            put = lambda pool, new, old: jax.tree.map(  # noqa: E731
                lambda x, n, o: jax.lax.dynamic_update_index_in_dim(
                    x, jnp.where(keep, n, o), gid, 0
                ),
                pool, new, old,
            )
            return (h2, put(k_pool, k2, k_buf), put(v_pool, v2, v_buf)), None

        xs = (
            (layer_params, gids, own) if mask is None
            else (layer_params, gids, own, mask)
        )
        with jax.named_scope("mst.kv_pool.regroup"):
            (h, k_pool, v_pool), _ = jax.lax.scan(
                body, (h, k_pool, v_pool), xs
            )
        return h, k_pool, v_pool

    def _build_smapped_ragged(self, lanes: int = 1):
        """T=1 paged decode body attending over the page pool IN PLACE
        (ops/paged_attention.py). Where the gather body materializes every
        live slot's full (max_seq) KV view and scatters the dirty page back
        each tick, this body scatters only the M new K/V rows into their
        pool pages and hands the pool itself to the ragged attention op —
        per-tick KV traffic drops from the whole cache (twice) to the pages
        slots actually occupy, and no FLOPs run past each slot's offset.

        Rides the sp_layer injected-attention hook with M as the batch dim
        (offsets become an (M,)-vector — apply_rope's per-row form), so one
        forward streams the weights once across all slots, like body_s1.
        Gated to S==1/tp=1/ep=1/B==1/supports_sp by the constructor.

        Nor is the pool itself moved. A model without per-slot state
        carries it whole through its layer scans
        (``models.base.scan_layers_carried``), a layer an offset into the
        page table; a model with state walks its own layers and is handed
        ``pool_attn``, which takes one layer's pool or — from a model whose
        layers all keep pages — the whole pool in its walk's carry and the
        layer's row; under a share map the group-sized pool is carried
        (``_scan_layers_shared``)."""
        model, M, B = self.model, self.microbatches, self.batch
        page = self.page_size
        cdt, kv_quant = self.cache_dtype, self.kv_quant
        from mlx_sharding_tpu.models.base import scan_layers_carried
        from mlx_sharding_tpu.ops.paged_attention import paged_attention

        # rows a slot computes a step: 1, or the whole block of a model that
        # generates by diffusion over blocks (diffusion.py). Its T rows are
        # written at offset .. offset + T - 1 — offsets are multiples of T
        # and T divides the page, so one page, never two — every one of its
        # T queries sees the same keys [0, offset + T), and the queries are
        # folded into the kernel's query-group axis: the kernel is called as
        # it is, with a group T times as large and no mask of its own.
        # ``lanes`` 2 (that family's wide forward): a slot computes two
        # blocks at offset .. offset + 2T - 1, the block whose K/V is being
        # committed and, where ``second``, the next one denoising. Each lane
        # is written to its own page (offset + T may open one; a lane 2 that
        # is not ``second`` goes to the scratch page), lane 1's queries see
        # [0, offset + T) and lane 2's [0, offset + 2T) — the kernel's
        # ``lead_lengths`` — and the head reads the lane that denoises
        T = self.diffusion_block or 1

        def body(layer_params, masks, vparts, shared, tokens, k, v,
                 offsets, active, n_valid, table, state, second=None):
            layer_params = jax.tree.map(lambda x: x[0], layer_params)
            masks = jax.tree.map(lambda x: x[0], masks)
            vparts = jax.tree.map(lambda x: x[0], vparts)
            share = self._share_active
            if state is None and not share:
                # every layer's pool as pages, (1, L, P+1, B, page, H, D) →
                # (L * (P+1), page, H, D): B == 1, a merge of leading
                # dimensions, no copy. int8 pools are {d, s} leaf pairs
                stacked = k, v
                k, v = jax.tree.map(
                    lambda x: x.reshape(-1, *x.shape[4:]), stacked
                )
            else:  # (L, P+1, B, page, H, D)
                k = jax.tree.map(lambda x: x[0], k)
                v = jax.tree.map(lambda x: x[0], v)
            # the barrier keeps the compiler from moving the first layer's
            # read of the pool in front of this reshape: read and in-place
            # write then name two views of one buffer, and the whole pool is
            # copied in and out of the decode block's carry every step (the
            # page pool's view above compiles the same with and without one)
            state = jax.lax.optimization_barrier(
                jax.tree.map(lambda x: x[0], state)
            )
            s = jax.lax.axis_index(AXIS_PP)

            offsets_pad = jnp.concatenate([offsets, jnp.zeros((1,), jnp.int32)])
            m_write = jnp.where(active, jnp.arange(M), M)  # inactive → scratch
            offset_m = offsets_pad[m_write]  # (M,)
            rows = table[m_write]  # (M, SPG) — inactive rows all-scratch
            page_ids = jnp.take_along_axis(
                rows, (offset_m // page)[:, None], axis=1
            )[:, 0]  # (M,) pool page holding each slot's write position
            row_pos = offset_m % page
            # valid prefix incl. the row written this tick; 0 zeroes the
            # garbage lanes' attention outright
            lengths = jnp.where(active, offset_m + T, 0).astype(jnp.int32)
            if T > 1:  # (M, T): slot m's T rows of its write page
                rows_at = row_pos[:, None] + jnp.arange(T)[None, :]
            lead = None  # lane 1's bound under a longer ``lengths``
            if lanes == 2:
                # (M, 2): each lane's page, and (M, 2, T) its rows there. A
                # lane 2 past the table's end (a stream that filled max_seq
                # has ended: the host drops what its slot computes) is
                # written to the scratch page too
                at_page = (offset_m + T) // page
                wide = active & second
                page_ids = jnp.stack([page_ids, jnp.where(
                    wide & (at_page < rows.shape[1]),
                    jnp.take_along_axis(
                        rows, jnp.minimum(at_page, rows.shape[1] - 1)[:, None],
                        axis=1,
                    )[:, 0],
                    self.pool_pages,
                )], axis=1)
                rows_at = jnp.stack(
                    [rows_at, (offset_m + T)[:, None] % page + jnp.arange(T)],
                    axis=1,
                )
                lead, lengths = lengths, jnp.where(wide, lengths + T, lengths)
            if self.ring_rows:
                # a window layer's ring pool viewed as pages: slot m's ring
                # is pages m * R .. (m + 1) * R, logical page j its page
                # j % R (row M is the scratch ring of the garbage lanes)
                ring_pages = self.ring_rows // page
                ring_rows_t = m_write[:, None] * ring_pages + (
                    jnp.arange(self.slot_pages, dtype=jnp.int32) % ring_pages
                )[None, :]
                ring_ids = m_write * ring_pages + (offset_m // page) % ring_pages

            # B == 1: treat the slot axis as the batch axis, (M, 1) tokens
            # embed straight to (M, T=1, hidden)
            h = self._vs_embed(s, vparts, tokens).astype(cdt)

            def pool_attn(k_buf, v_buf, ring=None, scope="mst.attn.core",
                          layer=None, keep=None):
                """``(attn_fn, done)`` over one layer's pool: ``attn_fn``
                scatters the M new rows and attends over the pool in place;
                the updated pool escapes through ``done`` (sp_decode.py's
                closure idiom). ``ring``: the buffers are the WHOLE ring
                pool of the window layers ``(layers, M+1, ring rows, H, D)``
                (never int8) and this is layer ``ring`` of it: taking the
                layer's rings out and putting them back would copy them
                (0.35 GB each way a layer at 32 slots of 5120 rows), so the
                pool is viewed as pages where it lies and the layer is an
                offset into the ring table. ``layer``: the buffers are the
                WHOLE page pool, every layer's, already viewed as pages
                ``(L * (P+1), page, H, D)`` — or as a model that walks its
                own layers was handed it, ``(L, P+1, B, page, H, D)``, and
                viewed so here — and this is its (traced) row
                ``layer``: the same offset, into the page table. ``keep``
                false (a padding layer) sends the M writes to the layer's
                scratch page. ``scope`` names the attention call."""
                done = {}
                quant = kv_quant and ring is None
                as_pages = as_given = attended = lambda x: x  # noqa: E731
                if layer is not None:
                    n_pages = self.pool_pages + 1
                    first = layer * n_pages
                    ids = page_ids if keep is None else jnp.where(
                        keep, page_ids, self.pool_pages
                    )
                    ids, tbl = ids + first, rows + first
                    if jax.tree.leaves(k_buf)[0].ndim == 6:
                        as_pages = lambda x: x.reshape(-1, *x.shape[3:])  # noqa: E731
                        as_given = lambda x: x.reshape(  # noqa: E731
                            -1, n_pages, B, *x.shape[1:]
                        )
                    if jax.tree.leaves(k_buf)[0].shape[-2] > 1:
                        # rows that keep their heads apart reach the
                        # kernel's (page, Hkv * D) block through a relayout
                        # of the pool it is handed (a TPU tiles the two
                        # minor dimensions): hand it this layer's pages, so
                        # that the slice and the relayout are one copy of
                        # one layer and not one of all L, every layer
                        tbl = rows
                        attended = lambda x: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                            x, first, n_pages
                        )
                elif ring is not None:  # → (layers * (M+1) * R, page, H, D)
                    first = ring * (M + 1) * ring_pages
                    ids, tbl = ring_ids + first, ring_rows_t + first
                    as_pages = lambda x: x.reshape(-1, page, *x.shape[3:])  # noqa: E731
                    as_given = lambda x: x.reshape(k_buf.shape)  # noqa: E731
                else:  # drop the B == 1 axis per leaf → (P+1, page, H, D)
                    ids, tbl = page_ids, rows
                    as_pages = lambda x: x[:, 0]  # noqa: E731
                    as_given = lambda x: x[:, None]  # noqa: E731

                def attn_fn(q, k_new, v_new, logit_softcap=None,
                            sliding_window=None, values_from_k=None,
                            **layout):
                    kl = jax.tree.map(as_pages, k_buf)
                    vl = jax.tree.map(as_pages, v_buf)

                    def put(pool, new):
                        if quant:  # quantize the M rows, scatter both
                            new = quantize_kv_rows(new)
                        at = (ids, row_pos) if T == 1 else (ids[..., None], rows_at)
                        return jax.tree.map(
                            lambda p, n: p.at[at].set(n.astype(p.dtype)),
                            pool, new,
                        )

                    def rows_of(new):  # as ``put`` indexes them
                        if T == 1:
                            return new[:, 0]
                        if lanes == 2:  # (M, 2T, …) → (M, 2, T, …)
                            return new.reshape(*rows_at.shape, *new.shape[2:])
                        return new

                    with jax.named_scope("mst.attn.kv_write"):
                        kl = put(kl, rows_of(k_new))
                        vl = put(vl, rows_of(v_new))
                        done["k"] = jax.tree.map(as_given, kl)
                        done["v"] = jax.tree.map(as_given, vl)
                    with jax.named_scope(scope):
                        kl = jax.tree.map(attended, kl)
                        vl = jax.tree.map(attended, vl)
                        if T > 1:
                            two = {} if lead is None else dict(
                                # a group is folded by query, then by head:
                                # lane 1 is its leading T x G rows
                                lead_lengths=lead,
                                lead_rows=T * q.shape[2] // layout["kv_heads"],
                            )
                            return fold_block_queries(
                                lambda q1: paged_attention(
                                    q1, kl, vl, tbl, lengths, model.scale,
                                    **layout, **two,
                                ),
                                q, layout["kv_heads"],
                            )
                        out = paged_attention(
                            q[:, 0],
                            kl["d"] if quant else kl,
                            vl["d"] if quant else vl,
                            tbl, lengths, model.scale,
                            logit_softcap=logit_softcap,
                            sliding_window=sliding_window,
                            values_from_k=values_from_k,
                            k_scale=kl["s"] if quant else None,
                            v_scale=vl["s"] if quant else None,
                            **layout,  # kv_heads: rows with merged heads
                        )
                        return out[:, None]  # (M, T=1, Hq, Dv)

                return attn_fn, done

            def make_layer(g):
                def layer(h, p, k_buf, v_buf, row=None, keep=None):
                    # one layer's (a share group's) pool in and out; with
                    # ``row`` the whole pool as pages, this layer at ``row``
                    attn_fn, done = pool_attn(k_buf, v_buf, layer=row, keep=keep)
                    h2, _, _ = model.sp_layer(p, h, offset_m, attn_fn, group=g)
                    return h2, done["k"], done["v"]

                return layer

            if state is not None:
                # the model walks its own interleaved groups: the slot axis
                # is its batch axis, the pool attention is handed in, and
                # its state rows 0..M-1 (B == 1: a row is a slot) advance
                # where ``active`` (row M, the scratch row, is not a decode
                # step's to touch)
                h, k, v, st = model.run_layers(
                    layer_params, h, k, v, offset_m, mask=masks, state=state,
                    active=active, paged_attn=pool_attn, **self._rl_kwargs,
                )
                out = jnp.where(active[:, None, None], h, 0).astype(cdt)
                logits = self._vs_head(
                    shared, vparts, jax.lax.psum(out, AXIS_PP)
                )
                return (
                    logits,
                    jax.tree.map(lambda x: x[None], k),
                    jax.tree.map(lambda x: x[None], v),
                    jax.tree.map(
                        lambda x: x[None], jax.lax.optimization_barrier(st)
                    ),
                )

            # per-group scans over the stacked layer sub-trees, the pool in
            # each scan's CARRY. Unshared, it is the view as pages made
            # above, and a layer is its row's offset into the page table: it
            # scatters its M rows and attends where the pool lies. A pool
            # cut into the groups' layer ranges, scanned as xs/ys and
            # concatenated is MOVED: at 599 MB (14 layers of
            # DeepSeek-V2-Lite's latent rows, 144 pages) two range slices,
            # a slice, a select and a stack-back a layer, and the
            # concatenate's pad + f32 maximum over the whole were a third
            # of a 20.6 ms step that computed nothing. Under a share map
            # the G-sized pool is carried and a layer dynamic-indexes its
            # share-group's buffer out of it.
            if share:
                gids_all = jnp.asarray(self.kv_share.group_of, jnp.int32)
                own_all = jnp.asarray(self.kv_share.owner_mask)
            lo = 0
            for g in model.sp_groups():
                if g is not None and g not in layer_params:
                    continue
                stack = layer_params if g is None else layer_params[g]
                mask_g = masks if g is None else masks[g]
                n_g = jax.tree.leaves(stack)[0].shape[0]
                if share:
                    h, k, v = self._scan_layers_shared(
                        make_layer(g), h, stack, k, v,
                        gids_all[lo : lo + n_g], own_all[lo : lo + n_g],
                        mask_g,
                    )
                else:
                    h, k, v = scan_layers_carried(
                        make_layer(g), h, stack, k, v,
                        jnp.arange(lo, lo + n_g), mask_g,
                        in_place=model.scan_in_place(g, stack),
                    )
                lo += n_g
            if share:
                k, v = jax.tree.map(lambda x: x[None], (k, v))
            else:
                k, v = jax.tree.map(
                    lambda x, was: x.reshape(was.shape), (k, v), stacked
                )

            if lanes == 2:  # the head reads the lane that denoises
                h = jnp.where(second[:, None, None], h[:, T:], h[:, :T])
            out = jnp.where(active[:, None, None], h, 0).astype(cdt)
            out = jax.lax.psum(out, AXIS_PP)  # identity at S=1; keeps the
            # body shape identical to the gather one
            logits = self._vs_head(shared, vparts, out)  # (M, B, V) f32
            return logits, k, v, None

        spec_stage, spec_rep = P(AXIS_PP), P()
        return jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                self.layer_specs,
                jax.tree.map(lambda _: spec_stage, self.layer_masks),
                jax.tree.map(lambda _: spec_stage, self.vocab_parts),
                jax.tree.map(lambda _: spec_rep, self.shared_params),
                spec_rep,  # tokens
                self._kv_spec,  # k
                self._kv_spec,  # v
                spec_rep,  # offsets (M,)
                spec_rep,  # active (M,)
                spec_rep,  # n_valid
                spec_rep,  # page table
                spec_stage,  # recurrent state pool (None: no leaves)
                *([spec_rep] if lanes == 2 else []),  # second (M,)
            ),
            out_specs=(spec_rep, self._kv_spec, self._kv_spec, spec_stage),
            check_vma=False,
        )

    def _finish_step(self, smapped, t_len: int, with_sampling: bool):
        M, B = self.microbatches, self.batch
        all_active = jnp.ones((M,), bool)

        if with_sampling:

            def forward_sample(layer_params, masks, vparts, shared, tokens, cache, recent, key, sp, n_valid):
                logits, k, v, state = smapped(
                    layer_params, masks, vparts, shared, tokens, cache.k, cache.v,
                    cache.offset, all_active, n_valid, cache.state,
                )
                key, sub = jax.random.split(key)
                flat = logits.reshape(M * B, -1)
                tok, logprobs = sample_token(sub, flat, sp, recent)
                recent = update_recent_tokens(recent, tok)
                new_cache = KVCache(
                    k=k, v=v, offset=cache.offset + n_valid, state=state
                )
                return tok.reshape(M, B), logprobs, new_cache, recent, key

            return jax.jit(forward_sample, donate_argnums=(5, 6))

        def forward_logits(layer_params, masks, vparts, shared, tokens, cache, n_valid):
            logits, k, v, state = smapped(
                layer_params, masks, vparts, shared, tokens, cache.k, cache.v,
                cache.offset, all_active, n_valid, cache.state,
            )
            new_cache = KVCache(
                k=k, v=v, offset=cache.offset + n_valid, state=state
            )
            return logits, new_cache

        return jax.jit(forward_logits, donate_argnums=(5,))

    # ---------------------------------------------------- continuous batching
    def _build_decode_cb(self):
        """Decode step for continuous batching: per-slot offsets advance only
        on active slots, per-slot sampler params and PRNG keys (each slot
        reproduces the solo request with that seed), inactive slots' tokens
        computed-but-ignored (and left out of the sampler's decision whether
        the step draws or sorts at all). Reuses the same shard_map body as
        the uniform decode; only the host-visible wrapper differs. In paged
        mode the step takes the page table as an extra trailing argument."""
        M, B = self.microbatches, self.batch
        if B != 1:
            raise ValueError("continuous batching expects batch=1 per slot")
        if self.paged:
            # ragged (default where supported): attend over the page pool in
            # place; gather: the contiguous _paged_read view. Prefill and the
            # T=K speculative verify always keep the gather path — chunked
            # writes want the contiguous buffer.
            if self.paged_attention == "ragged":
                inner = self._build_smapped_ragged()
            else:
                inner = self._build_smapped(t_len=1, paged=True)
        else:
            if self._smapped_decode is None:
                self._build_step(t_len=1, with_sampling=True)
            dense = self._smapped_decode
            # drop the table arg, keep the state
            inner = lambda *args: dense(*args[:-2], args[-1])  # noqa: E731

        def decode_step(
            layer_params, masks, vparts, shared, tokens, cache, active, recent,
            keys, sp, rep_sizes, table,
        ):
            one = jnp.asarray(1, jnp.int32)
            logits, k, v, state = inner(
                layer_params, masks, vparts, shared, tokens, cache.k, cache.v,
                cache.offset, active, one, table, cache.state,
            )
            with jax.named_scope("mst.sample"):
                split = jax.vmap(jax.random.split)(keys)  # (M, 2, 2)
                keys, subs = split[:, 0], split[:, 1]
                # per-slot effective repetition window: only the last
                # rep_sizes[m] entries of the fixed-width buffer participate,
                # so each slot's penalty semantics match a solo run with that
                # context size
                W = recent.shape[1]
                valid = jnp.arange(W)[None, :] >= (W - rep_sizes)[:, None]
                tok, logprobs = sample_token_batched(
                    subs, logits.reshape(M, -1), sp,
                    jnp.where(valid, recent, -1), active,
                )
                recent = update_recent_tokens(recent, tok)
            new_cache = KVCache(
                k=k, v=v, offset=cache.offset + active.astype(jnp.int32),
                state=state,
            )
            return tok.reshape(M, B), logprobs, new_cache, recent, keys

        return jax.jit(decode_step, donate_argnums=(5, 7, 8))

    def diffusion_cb(self, want_lp: bool, wide: bool):
        """``decode_cb`` of a model that generates by diffusion over blocks:
        one forward over every slot's block through the ragged body, then
        ``diffusion.block_forward``. ``wide``: the forward of two lanes a
        slot — a finished block's commit with the next block's denoise
        behind it — where the narrow one computes one block a slot
        (``diffusion.py``; a block program alternates the two). Takes the
        batcher's block state where ``decode_cb``'s step takes the last
        tokens, and returns ``(out, blk, cache, recent, keys)``: a slot's
        offset advances by a block where the forward stored one."""
        if (want_lp, wide) not in self._diffusion_cbs:
            cfg = self.model.config
            lanes = 2 if wide else 1
            if lanes not in self._diffusion_bodies:  # one for both variants
                self._diffusion_bodies[lanes] = self._build_smapped_ragged(lanes)
            inner = self._diffusion_bodies[lanes]
            length = jnp.asarray(self.diffusion_block, jnp.int32)

            def diffusion_step(layer_params, masks, vparts, shared, blk, cache,
                               active, recent, keys, sp, rep_sizes, table):
                tokens, live, second = diffusion.forward_input(blk, active, wide)
                logits, k, v, state = inner(
                    layer_params, masks, vparts, shared, tokens, cache.k,
                    cache.v, cache.offset, live, length, table, cache.state,
                    *([second] if wide else []),
                )
                out, blk, offset, recent, keys = diffusion.block_forward(
                    blk, logits, cache.offset, live, second, recent, keys, sp,
                    rep_sizes, cfg=cfg, want_lp=want_lp,
                )
                return out, blk, KVCache(
                    k=k, v=v, offset=offset, state=state
                ), recent, keys

            self._diffusion_cbs[want_lp, wide] = diffusion_step
        return self._diffusion_cbs[want_lp, wide]

    # ------------------------------------ speculative continuous batching
    def spec_propose_cb(self, K: int):
        """K draft proposals for every continuous-batching slot in ONE
        program — the draft side of speculative x continuous batching,
        running on the DRAFT engine. Greedy slots (temperature == 0) draft
        with plain argmax (transforms live on the verify side, where
        exactness is decided — speculative.py draft_block_fn); sampled slots
        draft from their fully-transformed per-slot distribution and record
        its log-probs q, the rejection-sampling denominator
        (speculative.py draft_sampled_fn), each evolving a LOCAL copy of its
        repetition window with its own proposals. Returns a jitted
        ``prog(layer_params, masks, vparts, shared, tok, cache, active,
        recent, dkeys, sp, rep_sizes) -> (drafts (K, M), q_logprobs
        (K, M, V), cache)``."""
        key = ("propose", K)
        if key not in self._spec_progs:
            M, B = self.microbatches, self.batch
            if self.num_stages != 1:
                raise ValueError(
                    "speculative continuous batching needs a pp=1 engine"
                )
            if B != 1:
                raise ValueError("continuous batching expects batch=1 per slot")
            if self.paged:
                raise ValueError("the draft engine must be dense (no pool_pages)")
            if self._smapped_decode is None:
                self._build_step(t_len=1, with_sampling=True)
            dense = self._smapped_decode
            one = jnp.asarray(1, jnp.int32)

            def prog(layer_params, masks, vparts, shared, tok, cache, active,
                     recent, dkeys, sp, rep_sizes):
                W = recent.shape[1]
                valid = jnp.arange(W)[None, :] >= (W - rep_sizes)[:, None]

                def step(carry, _):
                    tok, k, v, offsets, recent, dkeys = carry
                    logits, k, v, _ = dense(
                        layer_params, masks, vparts, shared, tok, k, v,
                        offsets, active, one,
                    )
                    flat = logits.reshape(M, -1)
                    split = jax.vmap(jax.random.split)(dkeys)
                    dkeys, subs = split[:, 0], split[:, 1]
                    f = nucleus_logits_batched(
                        transform_logits_batched(
                            flat, jnp.where(valid, recent, -1), sp
                        ),
                        sp,
                    )
                    qlp = jax.nn.log_softmax(f, axis=-1)
                    drawn = jax.vmap(
                        lambda kk, lo: jax.random.categorical(kk, lo)
                    )(subs, f)
                    tok = jnp.where(
                        sp.temperature > 0, drawn, jnp.argmax(flat, axis=-1)
                    ).astype(jnp.int32)
                    recent = update_recent_tokens(recent, tok)
                    offsets = offsets + active.astype(jnp.int32)
                    return (tok.reshape(M, B), k, v, offsets, recent, dkeys), (
                        tok, qlp,
                    )

                (tok, k, v, offsets, _, _), (drafts, qlps) = jax.lax.scan(
                    step,
                    (tok, cache.k, cache.v, cache.offset, recent, dkeys),
                    None, length=K,
                )
                return drafts, qlps, KVCache(k=k, v=v, offset=offsets)

            prog.__name__ = prog.__qualname__ = f"spec_propose_k{K}"
            self._spec_progs[key] = jax.jit(prog, donate_argnums=(5,))
        return self._spec_progs[key]

    def spec_verify_cb(self, K: int):
        """One T=K target forward over ``[t0, d1..d_{K-1}]`` per slot scores
        every draft position for all M slots at once (keep_all logits body);
        acceptance per slot is the exact greedy agreement prefix
        (temperature 0 — every emitted token is what plain decode would
        produce) or Leviathan rejection sampling with the slot's own PRNG
        key (sampled — emitted tokens distributed exactly as the slot's
        transformed target distribution). The rollback is one per-slot
        scalar: offset += count keeps exactly the verified prefix
        (speculative.py verify_fn/verify_sampled_fn vectorized over slots).
        ``wcap`` (M,) is the per-slot adaptive window cap: ``m`` is clamped
        to ``wcap - 1`` INSIDE the program, before any acceptance is
        committed — truncating to a prefix of properly-accepted positions
        is exactly window-wcap speculation (greedy rows are the target's
        own tokens; sampled prefixes are rejection-sampling-exact at every
        length), and cache offset / next-token / replay all derive from the
        capped m. Legacy fixed-K callers pass wcap == K (a no-op clamp).
        Returns a jitted ``prog(layer_params, masks, vparts, shared, tok,
        drafts, qlps, cache, active, recent, vkeys, sp, rep_sizes, wcap,
        table) -> (gs (K, M), count (M,), next_tok (M, 1), cache,
        recent)``."""
        cache_key = ("verify", K)
        if cache_key not in self._spec_progs:
            prog = self._spec_verify_fn(K)
            prog.__name__ = prog.__qualname__ = f"spec_verify_k{K}"
            self._spec_progs[cache_key] = jax.jit(prog, donate_argnums=(7, 9))
        return self._spec_progs[cache_key]

    def spec_verify_ngram_cb(self, K: int):
        """The :meth:`spec_verify_cb` program for DETERMINISTIC (n-gram
        prompt-lookup) proposals: q is the one-hot distribution on the
        proposed token, built in-jit from the (K, M) draft ids — the host
        never ships a (K, M, V) array and there is no draft engine or
        draft KV at all. Returns a jitted ``prog(layer_params, masks,
        vparts, shared, tok, drafts, cache, active, recent, vkeys, sp,
        rep_sizes, wcap, table) -> (gs, count, next_tok, cache, recent)``."""
        cache_key = ("verify_ngram", K)
        if cache_key not in self._spec_progs:
            from mlx_sharding_tpu.speculative import one_hot_draft_logprobs

            raw = self._spec_verify_fn(K)
            vocab = self.vocab_size

            def prog(layer_params, masks, vparts, shared, tok, drafts,
                     cache, active, recent, vkeys, sp, rep_sizes, wcap,
                     table):
                qlps = one_hot_draft_logprobs(drafts, vocab)
                return raw(layer_params, masks, vparts, shared, tok, drafts,
                           qlps, cache, active, recent, vkeys, sp, rep_sizes,
                           wcap, table)

            prog.__name__ = prog.__qualname__ = f"spec_verify_ngram_k{K}"
            self._spec_progs[cache_key] = jax.jit(
                prog, donate_argnums=(6, 8)
            )
        return self._spec_progs[cache_key]

    def _spec_verify_fn(self, K: int):
        """The raw (unjitted) verify program shared by the draft-engine and
        n-gram entry points (see :meth:`spec_verify_cb` for semantics)."""
        from mlx_sharding_tpu.speculative import rejection_round

        M, B = self.microbatches, self.batch
        if B != 1:
            raise ValueError("continuous batching expects batch=1 per slot")
        refuse_recurrent(
            self.model, "--draft",
            "a rejected draft is undone by lowering the slot's offset",
        )
        inner = self._build_smapped(t_len=K, paged=self.paged, keep_all=True)
        if self.paged:
            paged_inner = inner
            inner = lambda *args: paged_inner(*args, None)  # noqa: E731
        else:
            dense = inner
            inner = lambda *args: dense(*args[:-1])  # drop the table arg
        n_valid = jnp.asarray(K, jnp.int32)

        def prog(layer_params, masks, vparts, shared, tok, drafts, qlps,
                 cache, active, recent, vkeys, sp, rep_sizes, wcap, table):
            x = jnp.concatenate([tok, drafts[:-1].T], axis=1)  # (M, K)
            off0 = cache.offset
            logits_all, k, v, _ = inner(
                layer_params, masks, vparts, shared, x[:, None, :],
                cache.k, cache.v, off0, active, n_valid, table,
            )  # (M, 1, K, V)
            logits_all = logits_all.reshape(M, K, -1)
            W = recent.shape[1]
            valid = jnp.arange(W)[None, :] >= (W - rep_sizes)[:, None]
            sampled = sp.temperature > 0  # (M,)

            def score(rec, i):
                tl = transform_logits_batched(
                    logits_all[:, i], jnp.where(valid, rec, -1), sp
                )
                g = jnp.argmax(tl, axis=-1).astype(jnp.int32)
                plp = jax.nn.log_softmax(
                    nucleus_logits_batched(tl, sp), axis=-1
                )
                # the token consumed at position i+1: the draft's
                # proposal (sampled — exact on the accepted prefix,
                # discarded past it) or the greedy verdict
                rec = update_recent_tokens(
                    rec, jnp.where(sampled, drafts[i], g)
                )
                return rec, (g, plp)

            _, (gs_g, plps) = jax.lax.scan(score, recent, jnp.arange(K))
            # greedy: longest agreement prefix, then the correction token
            mism = gs_g != drafts
            any_m = mism.any(axis=0)
            m_g = jnp.where(any_m, jnp.argmax(mism, axis=0), K - 1)

            # rejection sampling, one vmapped lane per slot
            def rr(key_s, d, q, p):
                gs, m, _ = rejection_round(
                    key_s, d[:, None], q[:, None], p[:, None]
                )
                return gs[:, 0], m[0]

            gs_s, m_s = jax.vmap(rr, in_axes=(0, 1, 1, 1), out_axes=(1, 0))(
                vkeys, drafts, qlps, plps
            )
            gs = jnp.where(sampled[None, :], gs_s, gs_g)
            m = jnp.where(sampled, m_s, m_g)
            # per-slot adaptive window: clamp BEFORE anything commits
            m = jnp.minimum(m, wcap - 1)
            count = jnp.where(active, m + 1, 0).astype(jnp.int32)

            # replay ONLY the emitted tokens into the pre-round window
            # (the score scan's evolution was provisional)
            def replay(rec, i):
                upd = update_recent_tokens(rec, gs[i])
                keep = (i <= m) & active
                return jnp.where(keep[:, None], upd, rec), None

            recent, _ = jax.lax.scan(replay, recent, jnp.arange(K))
            nxt = jnp.take_along_axis(gs, m[None, :], axis=0)[0]  # (M,)
            next_tok = jnp.where(active, nxt, tok[:, 0])[:, None]
            return gs, count, next_tok, KVCache(
                k=k, v=v, offset=off0 + count
            ), recent

        return prog

    def spec_replay_cb(self, K: int):
        """Replay ``K`` recorded tokens through the dense decode body to
        advance the KV cache WITHOUT sampling — the scheduler uses this on a
        draft engine after a tick that fell back to plain (non-speculative)
        decode: the target advanced K positions, so the draft must ingest the
        same K tokens or its later proposals attend to stale KV and
        acceptance silently collapses. Logits are discarded; PRNG keys and
        repetition windows are untouched (the fallback block already
        consumed the slot's key chain on the target side). Returns a jitted
        ``prog(layer_params, masks, vparts, shared, toks (K, M, B), cache,
        active) -> cache``."""
        key = ("replay", K)
        if key not in self._spec_progs:
            if self.num_stages != 1:
                raise ValueError(
                    "speculative continuous batching needs a pp=1 engine"
                )
            if self.batch != 1:
                raise ValueError("continuous batching expects batch=1 per slot")
            if self.paged:
                raise ValueError("the draft engine must be dense (no pool_pages)")
            if self._smapped_decode is None:
                self._build_step(t_len=1, with_sampling=True)
            dense = self._smapped_decode
            one = jnp.asarray(1, jnp.int32)

            def prog(layer_params, masks, vparts, shared, toks, cache, active):
                def step(carry, tok):
                    k, v, offsets = carry
                    _, k, v, _ = dense(
                        layer_params, masks, vparts, shared, tok, k, v,
                        offsets, active, one,
                    )
                    return (k, v, offsets + active.astype(jnp.int32)), None

                (k, v, offsets), _ = jax.lax.scan(
                    step, (cache.k, cache.v, cache.offset), toks
                )
                return KVCache(k=k, v=v, offset=offsets)

            prog.__name__ = prog.__qualname__ = f"spec_replay_k{K}"
            self._spec_progs[key] = jax.jit(prog, donate_argnums=(5,))
        return self._spec_progs[key]

    def _build_prefill_slot(self):
        """Prefill one chunk of ONE slot's request while other slots' state
        stays untouched — the admit path of continuous batching. S ticks
        (single microbatch): stage s processes at tick s, cache writes land in
        slice ``slot`` at that slot's offset, last stage banks the
        last-valid-position logits."""
        model, S, M, B = self.model, self.num_stages, self.microbatches, self.batch
        t_len = self.prefill_chunk

        paged = self.paged

        def body(layer_params, masks, vparts, shared, tokens, slot, k, v,
                 offsets, n_valid, table, state):
            layer_params = jax.tree.map(lambda x: x[0], layer_params)
            masks = jax.tree.map(lambda x: x[0], masks)
            vparts = jax.tree.map(lambda x: x[0], vparts)
            k = jax.tree.map(lambda x: x[0], k)
            v = jax.tree.map(lambda x: x[0], v)
            state = jax.tree.map(lambda x: x[0], state)
            s = jax.lax.axis_index(AXIS_PP)
            h0 = jnp.zeros((B, t_len, model.config.hidden_size), self.cache_dtype)
            out0 = jnp.zeros((B, model.config.hidden_size), self.cache_dtype)
            offsets_pad = jnp.concatenate([offsets, jnp.zeros((1,), jnp.int32)])

            def tick(carry, t):
                h_buf, k, v, state, out = carry
                is_real = t == s
                h_first = self._vs_embed(s, vparts, tokens).astype(h_buf.dtype)
                h_in = jnp.where(s == 0, h_first, h_buf)
                m_write = jnp.where(is_real, slot, M)
                offset = offsets_pad[m_write]
                k_m, v_m, row = self._kv_read(paged, k, v, table, m_write)
                # chunk k+1 starts from chunk k's state, a first chunk
                # (offset 0) from zero; padded rows do not advance it
                h_out, k_m, v_m, state = self._run_slot(
                    layer_params, masks, h_in, k_m, v_m, offset, state,
                    m_write, n_valid,
                )
                k, v = self._kv_write(paged, k, v, k_m, v_m, row, m_write, offset)

                last = jax.lax.dynamic_index_in_dim(h_out, n_valid - 1, 1, keepdims=False)
                out = jnp.where(
                    is_real & (s == S - 1), last.astype(out.dtype), out
                )

                h_next = jax.lax.ppermute(
                    h_out, AXIS_PP, [(i, (i + 1) % S) for i in range(S)]
                )
                return (h_next, k, v, state, out), None

            (_, k, v, state, out), _ = jax.lax.scan(
                tick, (h0, k, v, state, out0), jnp.arange(S)
            )
            out = jax.lax.psum(out, AXIS_PP)
            logits = self._vs_head(shared, vparts, out)  # (B, V) f32
            return (
                logits,
                jax.tree.map(lambda x: x[None], k),
                jax.tree.map(lambda x: x[None], v),
                jax.tree.map(lambda x: x[None], state),
            )

        spec_stage, spec_rep = P(AXIS_PP), P()
        smapped = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(
                self.layer_specs,
                jax.tree.map(lambda _: spec_stage, self.layer_masks),
                jax.tree.map(lambda _: spec_stage, self.vocab_parts),
                jax.tree.map(lambda _: spec_rep, self.shared_params),
                spec_rep,  # tokens (B, T)
                spec_rep,  # slot
                self._kv_spec,  # k
                self._kv_spec,  # v
                spec_rep,  # offsets
                spec_rep,  # n_valid
                spec_rep,  # page table (paged mode; dummy otherwise)
                spec_stage,  # recurrent state pool (None: no leaves)
            ),
            out_specs=(spec_rep, self._kv_spec, self._kv_spec, spec_stage),
            check_vma=False,
        )
        dummy_table = jnp.zeros((1, 1), jnp.int32)

        def prefill_chunk(layer_params, masks, vparts, shared, tokens, slot,
                          cache, n_valid, table=None):
            logits, k, v, state = smapped(
                layer_params, masks, vparts, shared, tokens, slot, cache.k, cache.v,
                cache.offset, n_valid, dummy_table if table is None else table,
                cache.state,
            )
            offsets = cache.offset.at[slot].add(n_valid)
            return logits, KVCache(k=k, v=v, offset=offsets, state=state)

        return jax.jit(prefill_chunk, donate_argnums=(6,))

    @staticmethod
    def _sample_fn(logits, recent, key, sp):
        m, b = logits.shape[0], logits.shape[1]
        key, sub = jax.random.split(key)
        tok, logprobs = sample_token(sub, logits.reshape(m * b, -1), sp, recent)
        recent = update_recent_tokens(recent, tok)
        return tok.reshape(m, b), logprobs, recent, key

    # ------------------------------------------------------------------
    def generate_step(
        self,
        prompt_tokens,
        *,
        temperature: float = 0.0,
        top_p: float = 1.0,
        repetition_penalty: Optional[float] = None,
        repetition_context_size: int = 20,
        logit_bias: Optional[dict[int, float]] = None,
        seed: Optional[int] = None,
        max_tokens: int = 256,
        want_logprobs: bool = False,
    ):
        """Same contract as generate.Generator.generate_step — tokens stream
        out one at a time; every microbatch runs the same prompt (serving
        uses M=1; M>1 is the throughput path driven via raw step calls).
        ``want_logprobs`` yields TokenLogprobs summaries (device-side
        lax.top_k, pulled per block) instead of None."""
        import time as _time

        sp = make_sampler_params(temperature, top_p, repetition_penalty, logit_bias)
        key = jax.random.PRNGKey(
            int(_time.time_ns()) & 0x7FFFFFFF if seed is None else seed
        )
        M, B = self.microbatches, self.batch
        prompt = np.asarray(prompt_tokens, np.int32).reshape(1, 1, -1)
        prompt = np.broadcast_to(prompt, (M, B, prompt.shape[-1]))
        n_prompt = prompt.shape[-1]
        if n_prompt == 0:
            # the prefill loop below would be skipped and the first sample
            # would crash on logits=None — reject at entry instead
            raise ValueError("empty prompt")
        if n_prompt + max_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({n_prompt}) + max_tokens ({max_tokens}) exceeds KV "
                f"capacity {self.max_seq}"
            )

        cache = self.init_cache()
        recent = init_recent_tokens(
            M * B, repetition_context_size, prompt.reshape(M * B, -1)
        )

        c = self.prefill_chunk
        logits = None
        for start in range(0, n_prompt, c):
            chunk = prompt[..., start : start + c]
            n_valid = chunk.shape[-1]
            if n_valid < c:
                chunk = np.pad(chunk, ((0, 0), (0, 0), (0, c - n_valid)))
            logits, cache = self._prefill(
                self.layer_params, self.layer_masks, self.vocab_parts,
                self.shared_params, jnp.asarray(chunk), cache,
                jnp.asarray(n_valid, jnp.int32),
            )
        tok, logprobs, recent, key = self._sample(logits, recent, key, sp)

        from mlx_sharding_tpu.generate import (
            TokenLogprobs,
            block_lp_outputs,
        )

        first_lp = None
        if want_logprobs:
            chosen, tv, ti = block_lp_outputs(tok.reshape(M * B), logprobs)
            first_lp = TokenLogprobs(
                float(chosen[0]), np.asarray(ti[0]), np.asarray(tv[0])
            )
        yield int(tok[0, 0]), first_lp
        remaining = max_tokens - 1
        if remaining <= 0:
            return

        from mlx_sharding_tpu.generate import blocked_token_stream

        block = self.decode_block_prog(self.decode_block, want_logprobs)

        def dispatch(carry):
            outs, t, c, r, k = block(
                self.layer_params, self.layer_masks, self.vocab_parts,
                self.shared_params, carry[0], carry[1], carry[2], carry[3], sp,
            )
            return outs, (t, c, r, k)

        yield from blocked_token_stream(
            dispatch, (tok, cache, recent, key), remaining,
            self.decode_block, want_logprobs, tok_index=(0, 0),
        )
