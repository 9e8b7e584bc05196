"""A decode step's dense routed experts as one Pallas call a MoE layer.

``ops/moe.py``'s loop over the distinct experts a step's rows picked is a
``while``: per visit an id load, a routing-mass reduce, a dynamic slice
and two or three matmul fusions, none of which can start the next
expert's read before this one's last matmul has ended. Here the step's
distinct ids are scalar-prefetched into the index maps of ONE call, as
``quant_matmul.quant_matmul_experts`` does for packed stacks: grid step
``(t, j)`` holds tile ``j`` of expert ``ids[t]``'s matrices, read straight
out of the stacks where they lie, while the pipeline already fetches the
next step's. The tiles run over the experts' width ``I``: tile ``j`` of the
gate and up projections gives columns ``j`` of the hidden activation, which
meet only rows ``j`` of the down projection, so the three matmuls of a tile
fuse and the activation never leaves VMEM. Every term lands in one
``(N, H)`` float32 accumulator, written once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows one call takes. They live in VMEM whole for the call's length: x and
# the result in the rows' dtype (double-buffered), the float32 accumulator
# and a tile's float32 gate, up and activation, 45 KB a row at the widest
# served expert (3072 x 3072 in tiles of 768): 128 rows are 5.6 MiB beside
# that shape's 27 MiB of weight tiles. A prefill chunk (256 rows and up)
# stays with the loop.
MAX_ROWS = 128
# What the call may take of a v5e core's 128 MiB of VMEM (Mosaic's default
# is 16 MiB), and the part of it the double-buffered weight tiles may fill.
VMEM_LIMIT_BYTES = 48 << 20
TILES_VMEM_BYTES = 28 << 20


def dense_experts_block(
    n: int, hidden: int, inter: int, dtype, gated: bool, *, hardware: bool = True
) -> int | None:
    """The tile of the experts' width ``I`` that :func:`dense_experts` runs
    ``n`` rows of ``dtype`` through ``(E, hidden, inter)`` / ``(E, inter,
    hidden)`` stacks of the same dtype with, or None where it does not
    serve the shape: bf16 only (float32 operands would take the MXU's
    multi-pass form, which the loop's XLA matmuls price better), at most
    ``MAX_ROWS`` rows, ``hidden`` and ``inter`` whole 128-lane columns. The
    tile is the widest divisor of ``inter`` in whole 128-lane columns — all
    of it where that fits — whose two or three matrices' tiles,
    double-buffered, fit ``TILES_VMEM_BYTES``: one DMA a matrix a grid
    step, as long as VMEM lets it be. ``hardware=False`` (interpret mode)
    waives the lane alignment. The dispatch (ops/moe.py) and the tests go
    through here."""
    dtype = jnp.dtype(dtype)
    if dtype != jnp.bfloat16 or not 0 < n <= MAX_ROWS:
        return None
    lane = 128 if hardware else 1
    if hidden % lane:
        return None
    mats = 3 if gated else 2
    fits = [
        b for b in range(lane, inter + 1, lane)
        if inter % b == 0
        and 2 * mats * hidden * b * dtype.itemsize <= TILES_VMEM_BYTES
    ]
    if not fits:
        return None
    block_i = fits[-1]
    tiles = 2 * mats * hidden * block_i * dtype.itemsize
    # x and the result (double-buffered), the accumulator, a tile's float32
    # gate, up and activation
    rows = n * (hidden * (4 * dtype.itemsize + 4) + 3 * block_i * 4)
    return block_i if tiles + rows <= VMEM_LIMIT_BYTES else None


def _kernel(
    ids_ref,  # (T,) SMEM: the stacks' rows of the step's distinct experts
    live_ref,  # (1,) SMEM: how many of them are real
    x_ref,  # (N, H)
    coef_ref,  # (N, 1) f32: the rows' routing mass for expert ids[t]
    *rest,  # [wg_ref (H, bi)], wu_ref (H, bi), wd_ref (bi, H), o_ref, acc_ref
    gated: bool,
):
    del ids_ref  # read by the index maps
    if gated:
        wg_ref, wu_ref, wd_ref, o_ref, acc_ref = rest
    else:
        wu_ref, wd_ref, o_ref, acc_ref = rest
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when((t == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < live_ref[0])
    def _expert():
        # the dots name their precision: bf16 operands take one exact MXU
        # pass, and Mosaic refuses them a process-wide "highest"
        dot = functools.partial(
            jnp.dot, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        x = x_ref[...]
        up = dot(x, wu_ref[...])
        if gated:
            h = jax.nn.silu(dot(x, wg_ref[...])) * up
        else:
            h = jnp.square(jax.nn.relu(up))
        acc_ref[...] += coef_ref[...] * dot(h.astype(x.dtype), wd_ref[...])

    @pl.when((t == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_i", "interpret"))
def dense_experts(
    x: jax.Array,  # (N, H)
    ids: jax.Array,  # (T,) int32: rows of the stacks, real ones first
    live: jax.Array,  # (1,) int32
    coef: jax.Array,  # (T, N) f32
    w_gate: jax.Array | None,  # (E, H, I), or None: un-gated relu^2 experts
    w_up: jax.Array,  # (E, H, I)
    w_down: jax.Array,  # (E, I, H)
    *,
    block_i: int,
    interpret: bool = False,
) -> jax.Array:
    """``sum_t coef[t][:, None] * (act(x @ w_gate[ids[t]], x @ w_up[ids[t]])
    @ w_down[ids[t]])`` over the first ``live[0]`` entries of ``ids``, (N, H)
    in x's dtype: operands as they are, float32 accumulation on the matrix
    unit, the activation rounded to x's dtype before the down projection,
    the terms summed in float32 and cast once. Entries past ``live`` repeat
    the last real id and hold its last tile, so the pipeline fetches
    nothing for them, and their compute is skipped; ``live == 0`` gives
    zeros."""
    n, hidden = x.shape
    inter = w_up.shape[-1]
    tiles = inter // block_i

    # a dead step stays on the last real step's blocks
    def tile(t, j, live):
        return jnp.where(t < live[0], j, tiles - 1)

    def mass(t, j, ids, live):
        return jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), 0, 0

    in_tile = pl.BlockSpec(
        (None, hidden, block_i),
        lambda t, j, ids, live: (ids[t], 0, tile(t, j, live)),
    )
    out_tile = pl.BlockSpec(
        (None, block_i, hidden),
        lambda t, j, ids, live: (ids[t], tile(t, j, live), 0),
    )
    rows = pl.BlockSpec((n, hidden), lambda t, j, ids, live: (0, 0))
    stacks = [w_up, w_down] if w_gate is None else [w_gate, w_up, w_down]
    return pl.pallas_call(
        functools.partial(_kernel, gated=w_gate is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(ids.shape[0], tiles),
            in_specs=[
                rows,
                pl.BlockSpec((None, n, 1), mass),
                *[in_tile] * (len(stacks) - 1),
                out_tile,
            ],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((n, hidden), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, hidden), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="dense_experts",
    )(ids, live, x, coef.astype(jnp.float32)[..., None], *stacks)
