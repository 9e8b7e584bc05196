"""Mixture-of-experts dispatch.

The reference keeps experts fused and stage-local — per-expert weights are
stacked into one ``switch_mlp`` tensor at load time
(ref: shard/server/model/deepseek_v2.py:101-112) and routing happens inside
the owning pipeline stage (SURVEY §2.3 "EP"). Same policy here, with the
execution path chosen at trace time from what ``apply_experts`` can
observe — token count, packed or dense stacks, backend, shapes:

- **decode (N <= GATHER_PATH_MAX_TOKENS rows), packed stacks, on a TPU**:
  the expert-indexed 4-bit kernel (``quant_matmul.quant_matmul_experts``).
  The step's DISTINCT expert ids go to the kernel as a scalar-prefetched
  table; each grid step reads one expert's packed tile straight out of the
  (E, out, in*bits/32) stack, unpacks it in VMEM and multiplies all N rows
  by it. HBM traffic is each chosen expert's packed bytes once, which is
  what decode is bound by; no dense expert tensor is written to HBM.
- **decode, packed stacks, elsewhere** (off the chip, a shape outside the
  kernel's contract): gather the packed leaves per pick and dequantize the
  gathered slices (N x K whole experts, dense, in HBM).
- **decode, dense stacks**: gather the top-k experts' weights per token and
  batch the tiny matmuls.
- **prefill (more rows than that), packed stacks, on a TPU**: the same
  kernel fed each expert's OWN rows (``_apply_grouped_kernel``). The chunk's
  (row, pick) pairs are sorted by expert, each expert's run padded to whole
  tiles of ``GROUP_TILE`` rows, and every tile is one entry of the kernel's
  id table with a row block of its own: a row meets the K experts it
  picked and no other. No capacity, no dropped pair. The decode step hands
  the kernel all rows against each distinct expert; the chunk hands it
  each expert's rows.
- **decode, dense stacks under a resident range or expert-parallel, on a
  TPU**: the dense expert-indexed kernel (``dense_experts.dense_experts``;
  bf16 stacks, at most ``dense_experts.MAX_ROWS`` rows). The step's DISTINCT
  held experts are the kernel's scalar-prefetched table, as for packed
  stacks: each grid step holds one tile of one expert's gate, up and down
  matrices, read out of the ``(E, H, I)`` / ``(E, I, H)`` stacks where they
  lie while the next one's arrive, multiplies all N rows through the three
  and adds the rows' routing mass times the result into one float32
  accumulator. One call a layer; HBM traffic is each picked expert's bytes
  once.
- **prefill over dense stacks or packed ones the kernel does not serve, a
  resident range and expert-parallel elsewhere** (a chunk's rows, float32
  or packed stacks, off the chip): the same walk as a loop
  over the DISTINCT held experts the rows picked, ascending, with masked
  accumulation — every matmul is a full-width MXU op with static shapes,
  read out of the stacks where they lie; no sorting, no capacity overflow.
  A held expert no row picked is not read: in a chunk every expert is hit,
  in a 32-row decode step under a resident range a part of them. Every
  visit multiplies ALL rows, also those whose routing mass for the expert
  is 0 (in a 256-row chunk of top-6 over 64, nine products in ten).

Routing is parameterized so Mixtral (softmax→topk→renorm), DeepSeek-V2
(softmax scoring→greedy topk, optional renorm + scaling factor) and
Nemotron-H (sigmoid scoring, a selection bias that chooses but does not
weigh) and ZAYA (softmax over logits an MLP router hands in, the same kind
of bias) share the dispatch machinery. Experts are SwiGLU (``w_gate`` given)
or un-gated ``relu(x W_up)^2 W_down`` (``w_gate=None``), on every path.

A layer that holds only a RANGE of the routed experts — one chip's share
of a layer divided over several — is told ``expert_base`` (the global id
of its first expert): routing stays over all experts, the layer computes
its own experts' part, and what the absent experts would add is left out.
That is the ``ep_axis`` branch without ``axis_index`` and without the
``psum``; under ``ep_axis`` the same code runs with both.

Which leaves ride a layer scan and which are read in place: a caller that
walks stacked layers may keep the expert stacks whole, ``(L, E, …)``, and
pass ``layer`` — the kernel and the scan then read expert ``(layer, e)``
as row ``layer * E + e`` of the stacks flattened to ``(L*E, …)`` (a view:
only the two minor dimensions are tiled), where they lie in HBM. Handing
them one layer's ``(E, …)`` slice instead makes the layer scan copy that
slice out of the stack every iteration (345 MB a layer for
DeepSeek-V2-Lite's packed experts). The off-chip fallbacks take the
layer's slice first and then do what they did.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

from mlx_sharding_tpu.ops.dispatch import DispatchCounter

logger = logging.getLogger(__name__)

GATHER_PATH_MAX_TOKENS = 16
# Rows a tile of the grouped path: one grid step of the expert kernel
# multiplies this many rows of ONE expert. Chosen on the chip at 256 rows x
# top-6 over 64 experts of 2048 -> 1408 -> 2048, 13 layers (with the tiles
# gathered as rows, 3 ms more than now at each): 17.9 ms at 32, 26.2 at 16
# (twice the grid steps, each with the same unpack), 30.1 at 64 (most
# groups hold 24 rows: the rest of a tile is idle work); the scan takes
# 119.9. The served chunk of that model: 30.2, 35.8 and 38.6 ms (PERF.md
# section 6, PR 50).
GROUP_TILE = 32

# Which path apply_experts chose, once per traced call (ops/dispatch.py).
# /metrics shows it as ``mst_moe_dispatch_total{path}``: "gather" or
# "gather_packed" above 0 on a chip is a decode step that copies N x K whole
# experts out of the stacks (dense ones; packed ones outside the kernel's
# contract) before it multiplies; "grouped" is a chunk over packed stacks;
# "dense_kernel" and "scan" are the walk over the distinct held experts, as
# one kernel (a decode step's rows over dense bf16 stacks) or as a loop.
_DISPATCHED = DispatchCounter(
    "kernel", "grouped", "dense_kernel", "scan", "gather_packed", "gather"
)
dispatch_counts = _DISPATCHED.counts
_count_dispatch = _DISPATCHED.count


@jax.named_scope("mst.moe.router")
def mixtral_routing(x, router_w, k: int):
    """HF Mixtral semantics: softmax over ALL expert logits, take top-k,
    renormalize the kept mass. Returns (weights (N,K) f32, idx (N,K))."""
    logits = (x @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / topv.sum(axis=-1, keepdims=True)
    return topv, topi


@jax.named_scope("mst.moe.router")
def deepseek_routing(
    x,
    router_w,
    k: int,
    *,
    norm_topk_prob: bool,
    routed_scaling_factor: float,
    topk_method: str = "greedy",
    n_group: int = 1,
    topk_group: int = 1,
):
    """DeepSeek-V2 gate: softmax scores in fp32, then 'greedy' top-k
    (V2-Lite) or 'group_limited_greedy' (V2/V2-Chat: keep only the
    topk_group expert groups with the highest per-group max score, then
    top-k within them), scaled by routed_scaling_factor."""
    logits = jnp.einsum(
        "nh,he->ne", x.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    scores = jax.nn.softmax(logits, axis=-1)
    if topk_method == "group_limited_greedy":
        n, e = scores.shape
        group_scores = scores.reshape(n, n_group, e // n_group).max(axis=-1)
        _, group_idx = jax.lax.top_k(group_scores, topk_group)  # (N, topk_group)
        group_mask = jnp.zeros_like(group_scores).at[
            jnp.arange(n)[:, None], group_idx
        ].set(1.0)
        score_mask = jnp.repeat(group_mask, e // n_group, axis=-1)
        scores = scores * score_mask
    elif topk_method != "greedy":
        raise ValueError(f"unknown topk_method {topk_method!r}")
    topv, topi = jax.lax.top_k(scores, k)
    if norm_topk_prob:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    return topv * routed_scaling_factor, topi


@jax.named_scope("mst.moe.router")
def nemotron_routing(
    x, router_w, select_bias, k: int, *, norm_topk_prob: bool,
    routed_scaling_factor: float,
):
    """Nemotron-H gate (``n_group == topk_group == 1``: no group limit):
    sigmoid scores in fp32; the top-k of ``scores + select_bias`` CHOOSES,
    the chosen experts' own scores WEIGH (the bias never enters a weight),
    renormalized to sum 1 when ``norm_topk_prob``, times
    ``routed_scaling_factor``."""
    logits = jnp.einsum(
        "nh,he->ne", x.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    scores = jax.nn.sigmoid(logits)
    _, topi = jax.lax.top_k(scores + select_bias.astype(jnp.float32), k)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if norm_topk_prob:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    return topv * routed_scaling_factor, topi


@jax.named_scope("mst.moe.router")
def biased_softmax_routing(logits, select_bias, k: int):
    """A gate whose logits the model computes itself (ZAYA's MLP router):
    softmax over all experts in fp32; the top-k of ``probs + select_bias``
    CHOOSES, the chosen experts' own probabilities WEIGH (the balancing bias
    never enters a weight, and nothing is renormalized: top-1 weighs its
    expert by its probability)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, topi = jax.lax.top_k(probs + select_bias.astype(jnp.float32), k)
    return jnp.take_along_axis(probs, topi, axis=-1), topi


def _activate(gate, up):
    """SwiGLU where the experts are gated, ``relu(up)^2`` where not."""
    if gate is None:
        return jnp.square(jax.nn.relu(up))
    return jax.nn.silu(gate) * up


@jax.named_scope("mst.moe.experts")
def apply_experts(
    x, weights, idx, w_gate, w_up, w_down, ep_axis=None,
    group_size: int = 64, bits: int = 4, expert_base=None, layer=None,
):
    """Expert application: SwiGLU, or ``relu^2`` un-gated with
    ``w_gate=None``. x (N, H); w_* stacked (E, H, I)/(E, I, H)
    dense, or packed ``{q, scales, biases}`` triples with MLX-orientation
    leaves (E, out, in*bits/32) — 4-bit expert stacks stay resident in HBM
    and dequantize on the fly (ref quant predicate: shard/utils.py:54-65).
    weights/idx (N, K). Returns (N, H).

    ``expert_base``: the stacks hold experts ``base .. base + E_local`` of a
    wider routing (``idx`` are global ids): only their part is computed.
    ``layer``: the stacks keep their leading layer axis ``(L, E, …)`` and
    the kernel and the scan read expert ``(layer, e)`` out of them where
    they lie — slicing one layer's experts out first would copy them
    (gigabytes a step) before anything could read them. The gather
    fallbacks (off the chip, dense stacks) do slice the layer first.

    ``ep_axis``: inside shard_map with the expert stacks sharded over that
    mesh axis, each device holds E/ep experts whose GLOBAL ids start at
    ``axis_index * E_local``; routing (weights/idx, global ids) is replicated,
    each device accumulates only its residents' contribution, and one psum
    combines — no all-to-all, no capacity factor, no token dropping."""
    from mlx_sharding_tpu.ops.quant import is_quantized

    n = x.shape[0]
    e_local = (w_up["q"] if is_quantized(w_up) else w_up).shape[
        0 if layer is None else 1
    ]
    if ep_axis is not None or expert_base is not None:
        base = 0 if expert_base is None else expert_base
        if ep_axis is not None:
            base = base + jax.lax.axis_index(ep_axis) * e_local
        acc = _apply_scan(
            x, weights, idx - base, w_gate, w_up, w_down, group_size, bits,
            layer=layer,
        )
        return acc if ep_axis is None else jax.lax.psum(acc, ep_axis)
    packed = is_quantized(w_up)
    if n > GATHER_PATH_MAX_TOKENS:
        # a chunk: over packed stacks the kernel serves, each expert
        # multiplies its own rows, GROUP_TILE of them a grid step; dense
        # stacks, and packed ones off the chip or outside the kernel's
        # contract, walk the distinct experts with all rows against each
        if packed and packed_kernel_ok(
            GROUP_TILE, w_gate, w_up, w_down, group_size, bits
        ):
            _log_path_once(
                _apply_grouped_kernel.__name__, n, idx.shape[1],
                tuple(w_up["q"].shape), layer is not None,
            )
            _count_dispatch("grouped")
            return _apply_grouped_kernel(
                x, weights, idx, w_gate, w_up, w_down, group_size, bits,
                layer=layer,
            )
        return _apply_scan(
            x, weights, idx, w_gate, w_up, w_down, group_size, bits, layer=layer
        )
    # decode path: HBM traffic is k/E of the stacks — and 4x less again
    # when they are packed: the kernel reads each chosen expert's packed
    # bytes once; off the chip, or at shapes it does not serve, gather
    # the packed leaves and dequantize the gathered slice
    kernel = packed and packed_kernel_ok(n, w_gate, w_up, w_down, group_size, bits)
    if packed:
        _log_path_once(
            (_apply_packed_kernel if kernel else _apply_gather_packed).__name__,
            n, idx.shape[1], tuple(w_up["q"].shape), kernel and layer is not None,
        )
    _count_dispatch(
        "kernel" if kernel else "gather_packed" if packed else "gather"
    )
    if kernel:
        return _apply_packed_kernel(
            x, weights, idx, w_gate, w_up, w_down, group_size, bits, layer=layer
        )
    if layer is not None:  # the gathers index one layer's (E, …) stacks
        w_gate, w_up, w_down = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
            (w_gate, w_up, w_down),
        )
    if packed:
        return _apply_gather_packed(
            x, weights, idx, w_gate, w_up, w_down, group_size, bits
        )
    return _apply_gather(x, weights, idx, w_gate, w_up, w_down)


def _apply_gather(x, weights, idx, w_gate, w_up, w_down):
    wg = None if w_gate is None else w_gate[idx]  # (N, K, H, I)
    wu = w_up[idx]
    wd = w_down[idx]  # (N, K, I, H)
    with jax.named_scope("mst.moe.experts.matmul"):
        g = None if wg is None else jnp.einsum("nh,nkhi->nki", x, wg)
        u = jnp.einsum("nh,nkhi->nki", x, wu)
        y = jnp.einsum("nki,nkih->nkh", _activate(g, u), wd)
    return (y * weights[..., None].astype(y.dtype)).sum(axis=1).astype(x.dtype)


def packed_kernel_ok(n, w_gate, w_up, w_down, gs, bits) -> bool:
    """Whether ``n`` rows over these packed stacks run the expert-indexed
    4-bit kernel: a TPU backend and all three projections inside the
    kernel's own contract (quant_matmul.experts_blocks)."""
    from mlx_sharding_tpu.ops.quant_matmul import experts_blocks

    if jax.default_backend() != "tpu":
        return False
    return all(
        experts_blocks(
            n, w["q"].shape[-2], w["q"].shape[-1] * 32 // bits, gs, bits
        ) is not None
        for w in (w_gate, w_up, w_down) if w is not None
    )


def dense_kernel_block(x, w_gate, w_up, w_down, interpret=False) -> int | None:
    """The width tile with which the rows ``x`` run the dense expert-indexed
    kernel over these stacks, or None where they walk the loop: packed
    stacks, rows of another dtype than the stacks, no TPU backend
    (``interpret`` stands in for one: the tests), or a shape outside the
    kernel's own contract (dense_experts.dense_experts_block: float32
    stacks, more rows than it holds in VMEM — a prefill chunk — or a width
    off the lanes)."""
    from mlx_sharding_tpu.ops.dense_experts import dense_experts_block
    from mlx_sharding_tpu.ops.quant import is_quantized

    if is_quantized(w_up) or x.dtype != w_up.dtype:
        return None
    if not interpret and jax.default_backend() != "tpu":
        return None
    hidden, inter = w_up.shape[-2:]
    return dense_experts_block(
        x.shape[0], hidden, inter, w_up.dtype, gated=w_gate is not None,
        hardware=not interpret,
    )


@functools.lru_cache(maxsize=None)
def _log_path_once(
    path: str, n: int, k: int, stack_shape: tuple, in_place: bool
) -> None:
    """One line per distinct (path, shape): the choice is made at trace time
    from static shapes, so this is once per compiled program, at its build —
    ``in_place`` included: whether the kernel reads ``(layer, expert)`` out
    of the whole ``(L, E, …)`` stacks or is handed one layer's slice."""
    logger.info(
        "moe experts: %s for %d rows x top-%d over packed stacks %s, %s",
        path, n, k, stack_shape,
        "read in place by (layer, expert)" if in_place
        else "one layer's stacks as handed in",
    )


def _flat_layers(*stacks):
    """``(L, E, …)`` stacks as ``(L*E, …)``, a view: ONE index on ONE axis
    is what a Pallas index map takes and what the compiler fuses into a
    matmul's operand read. Two nested indices let it hoist the layer's and
    copy that layer's whole expert stack every iteration; one slice over
    two axes it materializes per expert."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stacks)


def distinct_experts(idx, num_experts: int):
    """``(ids (T,), live (1,))``, T = min(E, N*K) static: the distinct expert
    ids among the picks in ascending order, padded with the last real one;
    ``live`` counts the real ones."""
    t = min(num_experts, idx.size)
    present = (idx.reshape(-1, 1) == jnp.arange(num_experts)).any(axis=0)
    live = present.sum(dtype=jnp.int32)
    ids = jnp.nonzero(present, size=t, fill_value=0)[0].astype(jnp.int32)
    ids = jnp.where(jnp.arange(t) < live, ids, ids[live - 1])
    return ids, live[None]


def _planes(a, per_word: int):
    """``(..., N, IN) -> (..., per_word, N, IN / per_word)``: the rows as the
    expert kernel reads them, ``planes[j][n, w] = a[n, per_word * w + j]``."""
    a = a.reshape(*a.shape[:-1], a.shape[-1] // per_word, per_word)
    return jnp.moveaxis(a, -1, -3)


def _apply_packed_kernel(
    x, weights, idx, w_gate, w_up, w_down, gs, bits, interpret=False,
    layer=None,
):
    """Each distinct expert of the step once: the projections (three, or
    two for un-gated experts) run as
    ``quant_matmul_experts`` over the packed stacks as they lie in HBM, all
    N rows against one expert's tile a grid step, and the down projection
    combines with ``coef[t, n] = sum_k weights[n, k] * (idx[n, k] == ids[t])``
    — the arithmetic of ``_apply_scan``'s body over the same list of
    experts. No dense expert tensor is written to HBM. With ``layer`` the
    stacks are ``(L, E, …)`` and the kernel's id table names row ``layer *
    E + id`` of their ``(L*E, …)`` view: the same bytes by another index."""
    from mlx_sharding_tpu.ops.quant_matmul import quant_matmul_experts

    per_word = 32 // bits
    num_experts = w_up["q"].shape[0 if layer is None else 1]
    ids, live = distinct_experts(idx, num_experts)
    coef = ((idx == ids[:, None, None]) * weights).sum(axis=-1)  # (T, N)
    if layer is not None:
        w_gate, w_up, w_down = _flat_layers(w_gate, w_up, w_down)
        ids = layer * num_experts + ids

    def experts(x_planes, w, coef=None):
        return quant_matmul_experts(
            x_planes, ids, live, w["q"], w["scales"], w["biases"], coef,
            group_size=gs, bits=bits, interpret=interpret,
        )

    with jax.named_scope("mst.moe.experts.matmul"):
        xp = _planes(x, per_word)[None]
        g = None if w_gate is None else experts(xp, w_gate)
        h = _activate(g, experts(xp, w_up))  # (T, N, I) f32
        y = experts(_planes(h.astype(x.dtype), per_word), w_down, coef)
    return y.astype(x.dtype)


def group_rows(idx, num_experts: int, tile: int):
    """The ``N * K`` (row, pick) pairs of ``idx (N, K)`` sorted by expert
    (stable), each expert's run padded up to whole tiles of ``tile`` rows.
    ``T`` tile slots, a static bound: an expert some row picked wastes less
    than one tile, so ``T = (N*K + min(E, N*K) * (tile - 1)) // tile`` holds
    every routing. Returns

    - ``ids (T,)``: the slot's expert, ascending; slots past ``live`` repeat
      the last real one, as ``distinct_experts`` does;
    - ``live (1,)``: the slots that hold a pair;
    - ``rows (T, tile)``: the row of ``x`` each tile row holds; a padding
      row points at row 0 and is taken back by no pair;
    - ``dest (N, K)``: where pair ``(n, k)`` lies among the ``T * tile``
      tile rows. No capacity, no dropped pair: every pair has one tile row
      of its own, in a slot of its expert.

    ``idx`` names held experts only (``0 <= idx < num_experts``)."""
    n, k = idx.shape
    pairs = n * k
    slots = (pairs + min(num_experts, pairs) * (tile - 1)) // tile
    flat = idx.reshape(pairs)
    order = jnp.argsort(flat, stable=True)  # place among the sorted -> pair
    counts = (flat[:, None] == jnp.arange(num_experts)).sum(
        axis=0, dtype=jnp.int32
    )
    tiles = -(-counts // tile)
    tile_end = jnp.cumsum(tiles)
    first_tile = tile_end - tiles  # an expert's first slot
    first_pair = jnp.cumsum(counts) - counts  # and its first sorted pair
    live = tile_end[-1]
    slot = jnp.arange(slots, dtype=jnp.int32)
    ids = (slot[:, None] >= tile_end).sum(axis=1, dtype=jnp.int32)
    ids = jnp.where(slot < live, ids, ids[live - 1])
    # a tile row's rank within its expert's run; past the run it is padding
    # (every row of a slot past ``live`` is)
    rank = (slot - first_tile[ids])[:, None] * tile + jnp.arange(tile)
    real = rank < counts[ids][:, None]
    place = jnp.where(real, first_pair[ids][:, None] + rank, 0)
    rows = jnp.where(real, order[place] // k, 0).astype(jnp.int32)
    by_expert = flat[order]
    lies = first_tile[by_expert] * tile + jnp.arange(pairs) - first_pair[by_expert]
    dest = lies[jnp.argsort(order)].reshape(n, k).astype(jnp.int32)
    return ids, live[None], rows, dest


def _apply_grouped_kernel(
    x, weights, idx, w_gate, w_up, w_down, gs, bits, interpret=False,
    layer=None, tile=GROUP_TILE,
):
    """A chunk's rows through ``quant_matmul_experts``, each expert against
    the rows that picked it: ``group_rows`` lays the (row, pick) pairs out
    in tiles of one expert each, every tile a table entry with its own row
    block (``lead == T``), through gate, up and down; then each pair takes
    its row of the result back and a row's K terms are weighed and summed
    in float32, cast once. The product of a (row, expert) pair is the
    decode path's — the same unpack, the same float32 accumulation over IN
    blocks; ``_apply_scan`` adds a row's terms in the rows' dtype one expert
    at a time. With ``layer`` the stacks are ``(L, E, …)``, read in place
    as in ``_apply_packed_kernel``."""
    from mlx_sharding_tpu.ops.quant_matmul import quant_matmul_experts

    per_word = 32 // bits
    num_experts = w_up["q"].shape[0 if layer is None else 1]
    ids, live, rows, dest = group_rows(idx, num_experts, tile)
    if layer is not None:
        w_gate, w_up, w_down = _flat_layers(w_gate, w_up, w_down)
        ids = layer * num_experts + ids

    def experts(planes, w):  # (T, per_word, tile, IN / per_word) -> (T, tile, OUT)
        return quant_matmul_experts(
            planes, ids, live, w["q"], w["scales"], w["biases"],
            group_size=gs, bits=bits, interpret=interpret,
        )

    with jax.named_scope("mst.moe.experts.matmul"):
        # planes of the N rows first (a megabyte), then the gather: the
        # tiles are born in the kernel's layout but for one major-dimension
        # copy; gathered as rows they take two transposing copies (on the
        # chip 13 layers read 14.9 ms for 17.9)
        xt = jnp.moveaxis(_planes(x, per_word)[:, rows], 0, 1)
        g = None if w_gate is None else experts(xt, w_gate)
        h = _activate(g, experts(xt, w_up))
        y = experts(_planes(h.astype(x.dtype), per_word), w_down)
    y = y.reshape(-1, y.shape[-1])[dest]  # (N, K, H) f32, a pair's own row
    return (y * weights[..., None]).sum(axis=1).astype(x.dtype)


def _apply_gather_packed(x, weights, idx, w_gate, w_up, w_down, gs, bits):
    """Gather path over packed stacks: index the uint32/fp16 leaves by the
    top-k expert ids (reading k/E × 1/4 of the dense bytes), then dequantize
    just the gathered (N, K, out, in) slices. MLX orientation is (out, in),
    so the einsums contract the LAST dim."""
    from mlx_sharding_tpu.ops.quant import dequantize

    @jax.named_scope("mst.moe.experts.gather_dequant")
    def gathered(w):  # → (N, K, out, in) dense in x.dtype
        return dequantize(
            w["q"][idx], w["scales"][idx], w["biases"][idx], gs, bits, x.dtype
        )

    # gathered() opens its own, deeper scope inside this one
    with jax.named_scope("mst.moe.experts.matmul"):
        g = (
            None if w_gate is None
            else jnp.einsum("nh,nkih->nki", x, gathered(w_gate))
        )
        u = jnp.einsum("nh,nkih->nki", x, gathered(w_up))
        y = jnp.einsum("nki,nkhi->nkh", _activate(g, u), gathered(w_down))
    return (y * weights[..., None].astype(y.dtype)).sum(axis=1).astype(x.dtype)


@jax.named_scope("mst.moe.experts.scan")
def _apply_scan(x, weights, idx, w_gate, w_up, w_down, gs=64, bits=4,
                layer=None):
    """Each DISTINCT held expert the rows picked, in ascending order: all N
    rows against its matrices, read out of the stacks where they lie, times
    the rows' routing mass for it. An expert nobody picked is not read — its
    term would be ``0 * y``. ``idx`` may name experts the stacks do not hold
    (below 0, at or above E: a resident range, ``ep_axis``); they match no
    held expert and are not visited.

    A decode step's rows over dense bf16 stacks on a TPU take the walk as
    ONE expert-indexed kernel (``dense_kernel_block`` says which); a chunk's
    rows, packed or float32 stacks and every other backend take it as a loop.

    Under ``jax.vmap`` over the rows (the engine's M decode lanes: ``--ep``,
    ``--paged-attention gather``) the lanes are ONE walk over the experts
    any of them picked (``_distinct_walk``'s batching rule)."""
    from mlx_sharding_tpu.ops.quant import is_quantized

    num_experts = (w_up["q"] if is_quantized(w_up) else w_up).shape[
        0 if layer is None else 1
    ]
    _count_dispatch(
        "scan" if dense_kernel_block(x, w_gate, w_up, w_down) is None
        else "dense_kernel"
    )
    first = 0  # the stacks' row of this layer's expert 0
    if layer is not None:
        w_gate, w_up, w_down = _flat_layers(w_gate, w_up, w_down)
        first = layer * num_experts
    return _distinct_walk(num_experts, gs, bits)(
        x, weights, idx, (w_gate, w_up, w_down), jnp.asarray(first, jnp.int32)
    )


@functools.lru_cache(maxsize=None)
def _distinct_walk(num_experts: int, gs: int, bits: int, interpret: bool = False):
    """``walk(x (N, H), weights (N, K), idx (N, K), stacks, first)``: the
    walk of ``_apply_scan`` over rows ``first .. first + num_experts`` of the
    stacks, with a batching rule of its own. Which expert is read is loaded
    from the picks, so ``jax.vmap`` as it stands would give every lane its
    own list and its own row: a batched ``while`` that gathers one whole
    expert PER LANE an iteration. The rule folds the
    lanes into the rows instead — one list of the experts any lane picked,
    each read once for all lanes, as a scan by the loop's counter was.

    Rows and stacks the dense kernel serves (``dense_kernel_block``, asked
    of the rows in hand: lanes fold to more) walk as
    ``dense_experts.dense_experts`` — the list and the rows' mass for each
    entry built once, one call for the layer; all else as a loop with a
    traced bound, an expert an iteration. ``interpret`` runs the kernel off
    the chip (the tests)."""
    from mlx_sharding_tpu.ops.dense_experts import dense_experts
    from mlx_sharding_tpu.ops.quant import linear

    @jax.custom_batching.custom_vmap
    def walk(x, weights, idx, stacks, first):
        ids, live = distinct_experts(idx, num_experts)
        block_i = dense_kernel_block(x, *stacks, interpret)
        if block_i is not None:
            coef = ((idx == ids[:, None, None]) * weights).sum(axis=-1)  # (T, N)
            with jax.named_scope("mst.moe.experts.matmul"):
                return dense_experts(
                    x, first + ids, live, coef, *stacks, block_i=block_i,
                    interpret=interpret,
                )

        def body(t, acc):
            e = ids[t]
            wg, wu, wd = jax.tree.map(  # w_gate None (no leaves): un-gated
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, first + e, 0, keepdims=False
                ),
                stacks,
            )
            coef = ((idx == e) * weights).sum(axis=-1)  # (N,) mass for e
            # linear() serves dense (in, out) slices and packed (out, in)
            # triples alike — full-width MXU matmuls, no sorting
            g = None if wg is None else linear(x, wg, gs, bits)
            y = linear(_activate(g, linear(x, wu, gs, bits)), wd, gs, bits)
            return acc + coef[:, None].astype(y.dtype) * y

        # a traced trip count: a ``while`` over the step's ``live`` experts
        return jax.lax.fori_loop(0, live[0], body, jnp.zeros_like(x))

    @walk.def_vmap
    def walk_lanes(axis_size, in_batched, *args):
        *rows_batched, stacks_batched, first_batched = in_batched
        if first_batched or any(jax.tree.leaves(stacks_batched)):
            # stacks or a layer of a lane's own (nothing served): lane by lane
            lane = lambda i: walk(*jax.tree.map(  # noqa: E731
                lambda a, b: a[i] if b else a, args, tuple(in_batched)
            ))
            return jax.lax.map(lane, jnp.arange(axis_size)), True
        x, weights, idx = (
            (a if b else jnp.broadcast_to(a, (axis_size, *a.shape))).reshape(
                -1, a.shape[-1]
            )
            for a, b in zip(args[:3], rows_batched)
        )
        out = walk(x, weights, idx, *args[3:])
        return out.reshape(axis_size, -1, out.shape[-1]), True

    return walk
