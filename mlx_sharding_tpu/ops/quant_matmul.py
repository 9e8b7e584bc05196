"""Pallas fused dequant-matmul: 4-bit weights stay packed in HBM.

Round 1 dequantized MLX grouped-quant checkpoints to dense bf16 at load —
correct, but it forfeits the point of 4-bit weights on the decode path,
which is BANDWIDTH: decode is HBM-bound, and streaming 4-bit words + one
scale/bias pair per 64 weights moves ~4x fewer bytes than bf16 (SURVEY §7
"hard part (a)"; ROADMAP r1 queue item). This kernel keeps the packed
``{q, scales, biases}`` triple resident and fuses unpack → affine →
matmul inside VMEM.

Structure — shaped by what Mosaic actually compiles on a v5e (dynamic
lane-dim slices and lane-merging reshapes are both rejected by the layout
inference, so neither an in-kernel ``fori_loop`` over the reduction nor a
``(out, words, 8) → (out, in)`` unpack reshape can be used):

- 3-D grid (M tiles, OUT tiles, IN blocks); the IN axis is a sequential
  reduction dimension — partials accumulate into an fp32 VMEM scratch,
  written to the output tile on the last IN step. The last OUT tile may be
  partial (an OUT with no divisor that is a multiple of 128): Mosaic masks
  its write, and what it reads past the edge reaches no column that is kept.
- The unpack never materializes an (out, in) tile. Each uint32 word holds 8
  nibbles; the kernel processes 8 *nibble planes* ``(q >> 4j) & 0xF`` of
  shape (out, words) and runs one MXU sub-dot per plane against the
  matching activation plane. The activations arrive pre-permuted to
  word-major order (x_r[m, j, w] = x[m, 8w + j], a cheap XLA transpose
  traced into the surrounding program), so every sub-dot is a plain
  lane-contraction.
- Per-group scales/biases expand group→word lanes via a tiny iota-built
  0/1 matrix on the MXU (E[g, w] = [w//8 == g]) — broadcast+reshape lane
  expansion is exactly the shape cast Mosaic rejects. The bias term folds
  into one extra sub-dot against the plane-summed activations:
  ``out += Σ_j x_j @ (nib_j · s_w)ᵀ + (Σ_j x_j) @ b_wᵀ``.

Layout contract is exactly the checkpoint's (mlx.core.quantize,
ref shard/utils.py:54-65): ``q`` (out, in*bits/32) LSB-first nibbles,
``scales``/``biases`` (out, in/group_size) — validated bit-exactly by
tests/test_quant_golden.py.

Two kernels share that math:

- :func:`quant_matmul_pallas` — the 3-D-grid kernel above, for every packed
  projection at every row count (a prefill chunk, a decode step's slots, a
  single stream's one row).
- :func:`quant_matmul_experts` — the same grid with an expert axis, for
  the routed experts of a decode step (ops/moe.py): the weight operand is
  the whole (E, out, in*bits/32) stack as it lies in HBM, and a
  scalar-prefetched table of the step's distinct expert ids picks each
  grid step's tile, so each chosen expert's packed bytes are read once and
  no dense expert tensor is written to HBM. Scales and biases (and a
  packed leaf whose word count is not a multiple of 128) are read with OUT
  in the lanes, the orientation a TPU stores them in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_OUT = 128
# IN-blocks must keep the packed-word lane dim 128-aligned: 1024 inputs =
# 128 uint32 words. Smaller/indivisible IN dims run as one whole block.
DEFAULT_BLOCK_IN = 1024

# Per-program VMEM budget for the adaptive block picker. Decode-shape
# profiling on the v5e showed per-program overhead dominating at the old
# 128x128x1024 blocks (a (8192, 3072) matvec = 192 programs of ~72KB of
# packed bytes each ran 8x off the bandwidth roofline) — so blocks grow
# until the q tile + its fp32 expansion scratch fill a healthy VMEM slice.
_VMEM_BUDGET_BYTES = 6 * 1024 * 1024


def pick_block_in(in_dim: int, cap: int = 8192) -> int:
    """IN block: the whole (unpartitioned) dim is always lane-legal and
    maximizes bytes per program; partition only when the dim is too large,
    in 1024-input steps (128 uint32 word lanes)."""
    if in_dim <= cap or in_dim % DEFAULT_BLOCK_IN:
        return in_dim
    best = DEFAULT_BLOCK_IN
    d = DEFAULT_BLOCK_IN
    while d <= cap:
        if in_dim % d == 0:
            best = d
        d += DEFAULT_BLOCK_IN
    return best


def pick_block_out(out_dim: int, words: int, block_m: int = 1, per_word: int = 8) -> int:
    """Largest divisor of OUT (a multiple of 128, or the whole dim) whose
    working set fits the per-program VMEM budget: per out row ~16 bytes per
    word lane (q 4 + s_w/b_w 8 + one nibble plane 4), plus the activation
    tile and accumulator scaling with block_m. Where no multiple of 128
    divides OUT (DeepSeek-V2-Lite's 10944 = 64 x 171), the largest multiple
    of 128 that fits: :func:`quant_matmul_pallas` takes a ragged last tile,
    the one kernel that does."""
    fixed = block_m * (words * per_word + words) * 4  # x_r tile + x_sum
    limit = max((_VMEM_BUDGET_BYTES - fixed) // (16 * words + 4 * block_m), 128)
    if out_dim <= limit:
        return out_dim
    best = None
    d = 128
    while d <= limit:
        if out_dim % d == 0:
            best = d
        d += 128
    return best if best is not None else limit // 128 * 128


def _kernel(x_ref, q_ref, s_ref, b_ref, o_ref, acc_ref, *, bits, group_size):
    per_word = 32 // bits
    mask = (1 << bits) - 1
    bo, words = q_ref.shape
    gpb = s_ref.shape[-1]
    wpg = group_size // per_word  # words per quant group

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # group→word lane expansion on the MXU: E[g, w] = [w // wpg == g]
    gi = jax.lax.broadcasted_iota(jnp.int32, (gpb, words), 0)
    wi = jax.lax.broadcasted_iota(jnp.int32, (gpb, words), 1)
    expand = (wi // wpg == gi).astype(jnp.float32)
    dot = functools.partial(
        jax.lax.dot_general, preferred_element_type=jnp.float32
    )
    contract_last = (((1,), (1,)), ((), ()))
    s_w = dot(s_ref[0].astype(jnp.float32), expand, (((1,), (0,)), ((), ())))
    b_w = dot(b_ref[0].astype(jnp.float32), expand, (((1,), (0,)), ((), ())))

    wq = q_ref[...]  # (bo, words) uint32
    acc = acc_ref[...]
    x_sum = jnp.zeros((x_ref.shape[0], words), jnp.float32)
    for j in range(per_word):
        # nibbles are 0..15: the int32 detour is exact (no uint32→f32 cast
        # exists in Mosaic)
        nib = ((wq >> (j * bits)) & mask).astype(jnp.int32).astype(jnp.float32)
        xj = x_ref[:, j, :].astype(jnp.float32)  # (bm, words)
        acc = acc + dot(xj, nib * s_w, contract_last)
        x_sum = x_sum + xj
    acc_ref[...] = acc + dot(x_sum, b_w, contract_last)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "bits", "block_m", "block_out", "block_in",
                     "interpret"),
)
def quant_matmul_pallas(
    x: jax.Array,  # (M, IN)
    q: jax.Array,  # (OUT, IN * bits / 32) uint32
    scales: jax.Array,  # (OUT, IN / group_size)
    biases: jax.Array,  # (OUT, IN / group_size)
    *,
    group_size: int = 64,
    bits: int = 4,
    block_m: int = DEFAULT_BLOCK_M,
    block_out: int | None = None,
    block_in: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """x @ dequant(q, scales, biases).T without materializing the dense
    weight. M must divide by block_m and IN by block_in: a ragged IN block
    would add what lies past the edge into every column. OUT need not
    divide by block_out: an output column depends on its own weight row
    alone, so the rows the last tile reads past the end give columns that
    are never written back."""
    m, in_dim = x.shape
    out_dim = q.shape[0]
    per_word = 32 // bits
    block_m = min(block_m, m)
    if block_in is None:
        block_in = pick_block_in(in_dim)
    block_in = min(block_in, in_dim)
    if block_out is None:
        block_out = pick_block_out(out_dim, block_in // per_word, block_m, per_word)
    block_out = min(block_out, out_dim)
    if block_in % group_size or block_in % per_word:
        raise ValueError(
            f"block_in {block_in} must be a multiple of group_size "
            f"{group_size} and {per_word}"
        )
    if m % block_m or in_dim % block_in:
        raise ValueError(
            f"shapes (M={m}, IN={in_dim}) must divide block sizes "
            f"({block_m}, {block_in})"
        )

    n_in = in_dim // block_in
    gpb = block_in // group_size
    words = block_in // per_word
    # (M, IN) → word-major planes: x_r[m, j, W] = x[m, 8W + j]
    x_r = x.reshape(m, in_dim // per_word, per_word).transpose(0, 2, 1)
    # (OUT, G) → (n_in, OUT, groups_per_block): gives every grid step a
    # statically-addressed scale block (lane dim = gpb, whole → legal)
    s3 = scales.reshape(out_dim, n_in, gpb).transpose(1, 0, 2)
    b3 = biases.reshape(out_dim, n_in, gpb).transpose(1, 0, 2)

    grid = (m // block_m, pl.cdiv(out_dim, block_out), n_in)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, group_size=group_size),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, per_word, words), lambda mi, oi, ii: (mi, 0, ii)),
            pl.BlockSpec((block_out, words), lambda mi, oi, ii: (oi, ii)),
            pl.BlockSpec((1, block_out, gpb), lambda mi, oi, ii: (ii, oi, 0)),
            pl.BlockSpec((1, block_out, gpb), lambda mi, oi, ii: (ii, oi, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_out), lambda mi, oi, ii: (mi, oi)),
        out_shape=jax.ShapeDtypeStruct((m, out_dim), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_out), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="quant_matmul",
    )(x_r, q, s3, b3)


# ---------------------------------------------------------------------------
# Expert stacks: the same arithmetic with an expert axis on the grid.
# ---------------------------------------------------------------------------


def experts_q_transposed(out_dim: int, words: int) -> bool:
    """Whether the kernel reads an expert's packed words as (words, OUT).
    A TPU lays an array whose minor dimension is not a multiple of 128 out
    with a dimension that is in the lanes instead (DeepSeek-V2-Lite's
    ``w_down.q`` u32[E, 2048, 176] sits in HBM as [E, 176, 2048]; every
    scales leaf as [E, groups, OUT]). Asked for in that orientation, the
    operand is the leaf as it lies there and the transpose the wrapper
    writes is a bitcast; asked for row-major, XLA copies the stack into a
    lane-padded one first. Either way the result is the same."""
    return words % 128 != 0 and out_dim % 128 == 0


def experts_blocks(
    n: int, out_dim: int, in_dim: int, group_size: int = 64, bits: int = 4,
    *, hardware: bool = True,
) -> tuple[int, int] | None:
    """(block_out, block_in) of :func:`quant_matmul_experts` for ``n`` rows
    against (E, out_dim, in_dim) packed stacks, or None where its contract
    does not admit the shape: blocks divide the shape on quant-group and
    nibble-word boundaries and — on ``hardware``, which interpret mode
    waives — OUT tiles are whole 128-lane columns (scales, biases and the
    output carry OUT in their lanes) and an IN block is the whole dimension
    or whole 128-lane columns of words. The dispatch (ops/moe.py) and the
    tests go through here."""
    per_word = 32 // bits
    block_in = pick_block_in(in_dim)
    block_out = pick_block_out(out_dim, block_in // per_word, n, per_word)
    if (
        out_dim % block_out or in_dim % block_in
        or block_in % group_size or group_size % per_word
    ):
        return None
    if hardware and (
        block_out % 128
        or (block_in != in_dim and (block_in // per_word) % 128)
    ):
        return None
    return block_out, block_in


def _experts_kernel(
    ids_ref,  # (T,) SMEM: the step's distinct expert ids, padded with the last
    live_ref,  # (1,) SMEM: how many of them are real
    x_ref,  # (per_word, N, words) activation planes, x_ref[j][n, w] = x[n, 8w+j]
    q_ref,  # (block_out, words) packed words of expert ids[e]; (words, block_out) if q_t
    s_ref,  # (G, block_out) that expert's scales, OUT in the lanes
    b_ref,  # (G, block_out) its biases
    *rest,  # [coef_ref (N, 1)], o_ref (N, block_out), acc_ref (N, block_out) f32
    bits: int,
    group_size: int,
    q_t: bool,
    combine: bool,
):
    del ids_ref  # read by the index maps
    if combine:
        coef_ref, o_ref, acc_ref = rest
    else:
        o_ref, acc_ref = rest
    per_word = 32 // bits
    mask = (1 << bits) - 1
    words = q_ref.shape[0] if q_t else q_ref.shape[1]
    g_total = s_ref.shape[0]
    wpg = group_size // per_word
    e, ii = pl.program_id(1), pl.program_id(2)
    first, last = ii == 0, ii == pl.num_programs(2) - 1
    if combine:  # one output tile accumulates over every expert
        first = first & (e == 0)
        last = last & (e == pl.num_programs(1) - 1)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(e < live_ref[0])
    def _expert():
        xdt = x_ref.dtype
        # every dot names its precision: bf16 operands take one exact MXU
        # pass (and Mosaic refuses them a process-wide "highest"); f32 rows
        # ask for the f32 passes Mosaic's default would not make
        dot = functools.partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )
        plane_dot = dot if xdt == jnp.bfloat16 else functools.partial(
            dot, precision=jax.lax.Precision.HIGHEST
        )

        def pieces(a):
            """f32 (G, bo) as three bf16 pieces stacked on the group axis,
            hi + mid + lo == a exactly: against the 0/1 expansion matrix
            tiled three times they rebuild a's f32 values in ONE bf16 MXU
            pass (an f32 dot at Mosaic's default precision rounds them to
            bf16; K = 3 G still fits the array's depth)."""
            a = a.astype(jnp.float32)
            hi = a.astype(jnp.bfloat16)
            r = a - hi.astype(jnp.float32)
            mid = r.astype(jnp.bfloat16)
            lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
            return jnp.concatenate([hi, mid, lo], axis=0)

        # group→word expansion on the MXU as in _kernel, selecting this IN
        # block's groups out of the whole rows
        g0 = ii * (words // wpg)
        s3, b3 = pieces(s_ref[...]), pieces(b_ref[...])
        gi = jax.lax.broadcasted_iota(jnp.int32, (3 * g_total, words), 0)
        wi = jax.lax.broadcasted_iota(jnp.int32, (3 * g_total, words), 1)
        expand = (wi // wpg + g0 == gi % g_total).astype(jnp.bfloat16)
        over_groups = (((0,), (0,)), ((), ()))
        if q_t:  # everything with OUT in the lanes: plain (K, N) matmuls
            s_w = dot(expand, s3, over_groups)  # (words, bo)
            b_w = dot(expand, b3, over_groups)
            contract = (((1,), (0,)), ((), ()))
        else:
            s_w = dot(s3, expand, over_groups)  # (bo, words)
            b_w = dot(b3, expand, over_groups)
            contract = (((1,), (1,)), ((), ()))
        wq = q_ref[...]
        part = jnp.zeros(acc_ref.shape, jnp.float32)
        for j in range(per_word):
            nib = ((wq >> (j * bits)) & mask).astype(jnp.int32).astype(jnp.float32)
            # the dequantized plane in the activations' dtype, exactly as
            # ops.quant.dequantize makes it: bf16 planes take one MXU pass
            part = part + plane_dot(
                x_ref[j], (nib * s_w + b_w).astype(xdt), contract
            )
        if combine:
            part = part * coef_ref[...]
        acc_ref[...] += part

    @pl.when(last)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "bits", "interpret"),
)
def quant_matmul_experts(
    x_planes: jax.Array,  # (1 | T, per_word, N, IN/per_word): shared, or one per id
    ids: jax.Array,  # (T,) int32
    live: jax.Array,  # (1,) int32
    q: jax.Array,  # (E, OUT, IN * bits / 32) uint32
    scales: jax.Array,  # (E, OUT, IN / group_size)
    biases: jax.Array,  # (E, OUT, IN / group_size)
    coef: jax.Array | None = None,  # (T, N) f32
    *,
    group_size: int = 64,
    bits: int = 4,
    interpret: bool = False,
) -> jax.Array:
    """``x @ dequant(q[ids[t]], ...).T`` for the first ``live[0]`` entries of
    ``ids``, each expert's packed tile read straight out of the stack by
    its id (scalar-prefetched into the index maps) and unpacked in VMEM.
    Without ``coef``: (T, N, OUT) f32, zero rows past ``live``. With it: the
    (N, OUT) f32 sum over t of ``coef[t, n]`` times expert t's rows,
    accumulated across the expert axis of the grid. Entries past
    ``live`` repeat the last real id, so the pipeline fetches nothing new
    for them, and their compute is skipped."""
    lead, per_word, n, w_total = x_planes.shape
    t = ids.shape[0]
    out_dim = q.shape[1]
    in_dim = w_total * per_word
    blocks = experts_blocks(
        n, out_dim, in_dim, group_size, bits, hardware=not interpret
    )
    if (
        blocks is None or per_word != 32 // bits or lead not in (1, t)
        or q.shape[2] != w_total
    ):
        raise ValueError(
            f"quant_matmul_experts does not serve N={n}, OUT={out_dim}, "
            f"IN={in_dim}, planes {x_planes.shape} for {t} ids"
        )
    block_out, block_in = blocks
    words = block_in // per_word
    g_total = in_dim // group_size
    q_t = experts_q_transposed(out_dim, w_total)
    if q_t:
        q = jnp.swapaxes(q, -1, -2)
        q_spec = pl.BlockSpec(
            (None, words, block_out), lambda oi, e, ii, ids, live: (ids[e], ii, oi)
        )
    else:
        q_spec = pl.BlockSpec(
            (None, block_out, words), lambda oi, e, ii, ids, live: (ids[e], oi, ii)
        )
    row_spec = pl.BlockSpec(
        (None, g_total, block_out), lambda oi, e, ii, ids, live: (ids[e], 0, oi)
    )
    x_spec = pl.BlockSpec(
        (None, per_word, n, words),
        lambda oi, e, ii, ids, live: (e if lead > 1 else 0, 0, 0, ii),
    )
    in_specs = [x_spec, q_spec, row_spec, row_spec]
    operands = [
        x_planes, q, jnp.swapaxes(scales, -1, -2), jnp.swapaxes(biases, -1, -2)
    ]
    if coef is None:
        out_spec = pl.BlockSpec(
            (None, n, block_out), lambda oi, e, ii, ids, live: (e, 0, oi)
        )
        out_shape = jax.ShapeDtypeStruct((t, n, out_dim), jnp.float32)
    else:
        in_specs.append(pl.BlockSpec(
            (None, n, 1), lambda oi, e, ii, ids, live: (e, 0, 0)
        ))
        operands.append(coef.astype(jnp.float32)[..., None])
        out_spec = pl.BlockSpec(
            (n, block_out), lambda oi, e, ii, ids, live: (0, oi)
        )
        out_shape = jax.ShapeDtypeStruct((n, out_dim), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _experts_kernel, bits=bits, group_size=group_size, q_t=q_t,
            combine=coef is not None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(out_dim // block_out, t, in_dim // block_in),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((n, block_out), jnp.float32)],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="quant_matmul_experts",
    )(ids, live, *operands)
