"""Seeded weights: every tensor is a pure function of ``(seed, tensor name,
layer, expert)``.

One generator serves both sides of the correctness check. The launcher lays
the tensors out the way ``loading.load_model`` does today (stacked per layer
group; 4-bit group-64 triples in MLX ``(out, in/8)`` orientation with f32
scales and biases, router and ``kv_b_proj`` dense; or plain bf16 ``(in,
out)``), and the reference (``benchmarks/reference``) asks for one logical
matrix at a time. Nothing is read from a file and nothing passes through the
host.

A *unit* is one matrix of one layer (and, for routed experts, of one expert).
Its key is ``fold_in(fold_in(fold_in(key(seed), crc32(name)), layer),
expert)``, so a stacked leaf is a ``vmap`` of the unit generator over layers
and experts and the reference regenerates any unit alone, bit for bit
(threefry gives each key its own stream, batched or not).

Values: a 4-bit unit has uniform random nibbles, scales near ``in**-0.5 /
4.61`` (4.61 is the standard deviation of a uniform nibble) and biases near
``-7.5 * scale``, so the dequantized matrix has mean 0 and standard deviation
``in**-0.5`` like the dense ones: activations stay O(1) through 27 layers at
any width. Norm weights are 1 + 0.1 * normal, so a path that dropped one
would not agree with the reference.
"""

from __future__ import annotations

import functools
import zlib
import jax
import jax.numpy as jnp

from benchmarks.config import BITS, GROUP_SIZE, Unit, is_packed

PER_WORD = 32 // BITS
NIBBLE_STD = 4.61  # std of a uniform integer on 0..15


# --------------------------------------------------------------------------
# keys


def seed_key(seed: int):
    """``--seed`` may be a little over 2**31: split it so no part overflows."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be non-negative")
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def unit_key(skey, name: str, layer, expert=None):
    """The key of one unit. ``layer`` and ``expert`` may be traced."""
    k = jax.random.fold_in(skey, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    k = jax.random.fold_in(k, layer)
    return k if expert is None else jax.random.fold_in(k, expert)


# --------------------------------------------------------------------------
# one unit, in the program's layout


def packed_q(key, out: int, inn: int):
    return jax.random.bits(
        jax.random.fold_in(key, 0), (out, inn // PER_WORD), jnp.uint32
    )


def packed_scales(key, out: int, inn: int):
    s0 = inn ** -0.5 / NIBBLE_STD
    return s0 * jax.random.uniform(
        jax.random.fold_in(key, 1), (out, inn // GROUP_SIZE), jnp.float32,
        0.75, 1.25,
    )


def packed_biases(key, out: int, inn: int):
    return -7.5 * packed_scales(key, out, inn) * jax.random.uniform(
        jax.random.fold_in(key, 2), (out, inn // GROUP_SIZE), jnp.float32,
        0.9, 1.1,
    )


def dense_matrix(key, out: int, inn: int):
    """bf16, in the program's ``x @ W`` orientation ``(in, out)``."""
    return (
        jax.random.normal(key, (inn, out), jnp.float32) * inn ** -0.5
    ).astype(jnp.bfloat16)


def embed_matrix(key, out: int, inn: int):
    """bf16 embedding table ``(V, H)``: rows are what a token becomes."""
    return (
        jax.random.normal(key, (out, inn), jnp.float32) * inn ** -0.5
    ).astype(jnp.bfloat16)


def norm_vector(key, n: int):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(
        jnp.bfloat16
    )


LEAF_FNS = {
    "q": packed_q, "scales": packed_scales, "biases": packed_biases,
    "dense": dense_matrix, "embed": embed_matrix,
}


def unit_leaf(key, unit: Unit, leaf: str):
    if unit.kind == "norm":
        return norm_vector(key, unit.out)
    return LEAF_FNS[leaf](key, unit.out, unit.inn)


def round_nibbles(nib, bin_width):
    """The negative control: nibbles rounded to bins ``bin_width`` levels
    wide (2: three bits, 4: two), each reconstructed at the middle of its
    bin. A width of 1 changes nothing, exactly, so the width is a run-time
    value and the clean pass and the controls are one compiled program."""
    return jnp.floor(nib / bin_width) * bin_width + (bin_width - 1.0) / 2.0


def dequantize_rows(q, scales, biases):
    """``(rows, in/8)`` words → ``(rows, in)`` float32 ``scale * nibble +
    bias``, written from the MLX layout's definition (least-significant
    nibble first), not from the program's helper. For a few rows (the
    embedding lookup); matrices are multiplied by :func:`apply_linear`."""
    rows = q.shape[0]
    shifts = jnp.arange(PER_WORD, dtype=jnp.uint32) * BITS
    nib = ((q[:, :, None] >> shifts) & 0xF).reshape(rows, -1).astype(jnp.float32)
    g = nib.reshape(rows, nib.shape[1] // GROUP_SIZE, GROUP_SIZE)
    return (g * scales[:, :, None] + biases[:, :, None]).reshape(rows, -1)


def dense_logical(skey, unit: Unit, layer, expert=None):
    """A dense unit's float32 matrix ``M`` with ``y = x @ M``: its bf16
    values widened."""
    key = unit_key(skey, unit.name, layer, expert)
    return dense_matrix(key, unit.out, unit.inn).astype(jnp.float32)


def apply_linear(x, skey, unit: Unit, fmt: str, layer, expert=None,
                 bin_width=None):
    """``x @ M`` in float32 for the matrix ``M (in, out)`` the served
    weights stand for (bf16 values widened, or 4-bit values dequantized),
    ``x (T, in)``.

    For a 4-bit unit the product is taken nibble plane by nibble plane, in
    a loop: word ``w`` of a row holds inputs ``8w .. 8w+7``, so plane ``j``
    (the j-th nibble of every word) multiplies the inputs ``x[:, j::8]``,
    and all eight inputs of a word share a group (64 inputs = 8 words),
    hence a scale and a bias. The same numbers as ``x @ dequantized``,
    summed in another order, without ever reshaping a minor dimension of 8
    (on a TPU that relayout took 19 s a forward pass) and with one matrix
    product in the compiled program, not eight (unrolled, one reference
    layer was a 33 MB executable and the reference alone overflowed the
    machine's 192 MiB compile cache; my chip runs, PR 23)."""
    if not is_packed(unit, fmt):
        return x @ dense_logical(skey, unit, layer, expert)
    key = unit_key(skey, unit.name, layer, expert)
    q = packed_q(key, unit.out, unit.inn)
    words_per_group = GROUP_SIZE // PER_WORD
    scales = jnp.repeat(packed_scales(key, unit.out, unit.inn), words_per_group, axis=1)
    biases = jnp.repeat(packed_biases(key, unit.out, unit.inn), words_per_group, axis=1)
    # (8, T, in/8): plane j's inputs, laid out once
    planes = x.reshape(x.shape[0], unit.inn // PER_WORD, PER_WORD).transpose(2, 0, 1)

    def one_plane(y, args):
        xj, j = args
        nib = ((q >> (j * jnp.uint32(BITS))) & jnp.uint32(0xF)).astype(jnp.float32)
        if bin_width is not None:
            nib = round_nibbles(nib, bin_width)
        return y + xj @ (nib * scales + biases).T, None

    y, _ = jax.lax.scan(
        one_plane, jnp.zeros((x.shape[0], unit.out), jnp.float32),
        (planes, jnp.arange(PER_WORD, dtype=jnp.uint32)))
    return y


def logical_rows(skey, unit: Unit, fmt: str, ids):
    """Float32 embedding rows for ``ids``: ``(T, H)``."""
    key = unit_key(skey, unit.name, 0)
    if is_packed(unit, fmt):
        return dequantize_rows(
            packed_q(key, unit.out, unit.inn)[ids],
            packed_scales(key, unit.out, unit.inn)[ids],
            packed_biases(key, unit.out, unit.inn)[ids],
        )
    return embed_matrix(key, unit.out, unit.inn)[ids].astype(jnp.float32)


def logical_norm(skey, unit: Unit, layer):
    return norm_vector(unit_key(skey, unit.name, layer), unit.out).astype(
        jnp.float32
    )


# --------------------------------------------------------------------------
# stacked leaves for the program


@functools.partial(
    jax.jit, static_argnames=("unit", "leaf", "first_layer", "lo", "hi")
)
def _rows(skey, unit: Unit, leaf: str, first_layer: int, lo: int, hi: int):
    def one_layer(layer):
        if unit.experts:
            return jax.vmap(
                lambda e: unit_leaf(unit_key(skey, unit.name, layer, e), unit, leaf)
            )(jnp.arange(unit.experts))
        return unit_leaf(unit_key(skey, unit.name, layer), unit, leaf)

    return jax.vmap(one_layer)(jnp.arange(lo, hi) + first_layer)


class LazyStack:
    """A stacked layer leaf ``(layers, [experts,] …)`` that is generated on
    the device when the engine's placement slices it (``w[lo:hi]``,
    ``parallel/pipeline.split_stage_stacks``), so the tree the replaced
    ``load_model`` returns holds no device memory: the engine's own stacked
    copy is the only one. ``loading.load_model`` returns resident arrays
    here, which the placement then copies — twice a 10 GB model does not
    fit a 16 GB chip (PERF.md, program defects)."""

    def __init__(self, skey, unit: Unit, leaf: str, first_layer: int,
                 n_layers: int):
        self._skey, self._unit, self._leaf = skey, unit, leaf
        self._first, self._n = first_layer, n_layers
        one = jax.eval_shape(
            lambda k: _rows.__wrapped__(k, unit, leaf, first_layer, 0, 1), skey
        )
        self.shape = (n_layers, *one.shape[1:])
        self.dtype = one.dtype
        self.ndim = len(self.shape)

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.size * jnp.dtype(self.dtype).itemsize

    def __getitem__(self, idx):
        if not isinstance(idx, slice) or idx.step not in (None, 1):
            raise TypeError("a LazyStack is sliced along its layer axis only")
        lo, hi, _ = idx.indices(self._n)
        return _rows(self._skey, self._unit, self._leaf, self._first, lo,
                     max(lo, hi))

    def __jax_array__(self):
        return self[:]


def layer_stack(skey, unit: Unit, fmt: str, first_layer: int, n_layers: int):
    """The program's leaf for ``unit`` over a group's layers."""
    if is_packed(unit, fmt):
        return {
            leaf: LazyStack(skey, unit, leaf, first_layer, n_layers)
            for leaf in ("q", "scales", "biases")
        }
    return LazyStack(skey, unit, "dense", first_layer, n_layers)


def top_leaf(skey, unit: Unit, fmt: str):
    """Embedding, head or final norm, resident (a few hundred MB at most)."""
    if unit.kind == "norm":
        return _rows(skey, unit, "dense", 0, 0, 1)[0]
    if is_packed(unit, fmt):
        return {
            leaf: _rows(skey, unit, leaf, 0, 0, 1)[0]
            for leaf in ("q", "scales", "biases")
        }
    # bf16: embed is (V, H) rows, the untied head is (H, V)
    leaf = "embed" if unit.name == "embed" else "dense"
    return _rows(skey, unit, leaf, 0, 0, 1)[0]
