"""Golden fixture for MLX grouped-affine 4-bit compatibility.

SURVEY §7 hard-part (a): published ``*-4bit-mlx`` checkpoints must decode
bit-exactly. ``mlx`` itself is Apple-silicon-only and cannot run in this
environment, so the fixture below encodes the format contract *independently
of the implementation under test*, following mlx.core.quantize's documented
layout (MLX docs "Quantization"; mlx/ops.cpp::quantize; reference applies it
via nn.quantize at /root/reference/shard/utils.py:54-65):

- every ``32/bits`` consecutive elements along the input dim pack into one
  uint32, FIRST element in the LEAST significant bits;
- per ``group_size`` elements, ``value = q * scale + bias`` with
  scales/biases stored in the checkpoint dtype (fp16 for published 4-bit
  checkpoints).

The packed words are written as literal hex constants and the expected
dequantized values are computed by scalar arithmetic in this file — NOT by
calling the repo's own packer — so a nibble-order or group-mapping drift in
ops/quant.py fails these tests even if quantize/dequantize stay mutually
consistent.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.quick

from mlx_sharding_tpu.ops.quant import dequantize, quantize


def test_nibble_order_is_lsb_first():
    """q = [1,2,...,8] must pack to 0x87654321 (element 0 in the low nibble).
    An MSB-first implementation would produce 0x12345678 and corrupt every
    published checkpoint silently."""
    q = np.arange(1, 9, dtype=np.uint32)  # one uint32 worth of nibbles
    word = np.uint32(0)
    for k, v in enumerate(q):
        word |= np.uint32(v) << np.uint32(4 * k)
    assert word == np.uint32(0x87654321)

    # group_size=8 is not a real MLX option but isolates the packing check
    packed = np.array([[0x87654321]], np.uint32)
    scales = np.array([[1.0]], np.float16)
    biases = np.array([[0.0]], np.float16)
    got = np.asarray(
        dequantize(packed, scales, biases, group_size=8, bits=4, dtype=np.float32)
    )
    np.testing.assert_array_equal(got[0], q.astype(np.float32))


def test_golden_dequant_group64_fp16():
    """Full golden fixture at the published-checkpoint layout: group_size=64,
    bits=4, fp16 scales/biases, 2 output rows x 128 input dims (2 groups per
    row). Expected values computed by scalar affine math on the hand-chosen
    nibble sequence."""
    rng = np.random.RandomState(42)
    out_dim, in_dim, gs = 2, 128, 64
    q = rng.randint(0, 16, size=(out_dim, in_dim)).astype(np.uint32)

    # pack LSB-first, 8 nibbles per word — spelled out longhand
    packed = np.zeros((out_dim, in_dim // 8), np.uint32)
    for r in range(out_dim):
        for w in range(in_dim // 8):
            word = 0
            for k in range(8):
                word |= int(q[r, w * 8 + k]) << (4 * k)
            packed[r, w] = word

    scales = np.array([[0.5, 0.25], [0.125, 2.0]], np.float16)
    biases = np.array([[-1.0, 2.0], [0.5, -8.0]], np.float16)

    expected = np.empty((out_dim, in_dim), np.float32)
    for r in range(out_dim):
        for c in range(in_dim):
            g = c // gs
            expected[r, c] = float(q[r, c]) * float(scales[r, g]) + float(
                biases[r, g]
            )

    got = np.asarray(
        dequantize(packed, scales, biases, group_size=gs, bits=4, dtype=np.float32)
    )
    np.testing.assert_array_equal(got, expected)


def test_golden_dequant_8bit():
    """8-bit variant (MLX supports bits in {2,4,8}): 4 bytes per word,
    byte 0 in the low byte."""
    q = np.array([[7, 255, 0, 128, 1, 2, 3, 4]], np.uint32)
    packed = np.array(
        [[7 | 255 << 8 | 0 << 16 | 128 << 24, 1 | 2 << 8 | 3 << 16 | 4 << 24]],
        np.uint32,
    )
    scales = np.array([[0.5]], np.float16)
    biases = np.array([[-4.0]], np.float16)
    expected = q.astype(np.float32) * 0.5 - 4.0
    got = np.asarray(
        dequantize(packed, scales, biases, group_size=8, bits=8, dtype=np.float32)
    )
    np.testing.assert_array_equal(got, expected)


def test_packer_agrees_with_golden_layout():
    """The repo's own packer must produce the golden layout (it writes
    native-quantized shard checkpoints that MLX-side tooling should be able
    to read back)."""
    w = np.array([[float(v) for v in range(64)]], np.float32)  # one group
    packed, scales, biases = quantize(w, group_size=64, bits=4)
    # scale = (max-min)/15 = 63/15 = 4.2, bias = 0; q = round(v/4.2)
    assert scales.shape == (1, 1) and biases.shape == (1, 1)
    q_expected = np.clip(np.round(w / float(scales[0, 0])), 0, 15).astype(np.uint32)
    word0 = 0
    for k in range(8):
        word0 |= int(q_expected[0, k]) << (4 * k)
    assert int(packed[0, 0]) == word0
    # and the round trip through the independent dequant math is tight
    got = np.asarray(
        dequantize(packed, scales, biases, group_size=64, bits=4, dtype=np.float32)
    )
    assert np.abs(got - w).max() <= float(scales[0, 0]) / 2 + 1e-6


def test_dequant_rejects_non_uint32():
    with pytest.raises(ValueError, match="uint32"):
        dequantize(
            np.zeros((2, 4), np.int32), np.ones((2, 1)), np.zeros((2, 1)),
            group_size=16,
        )


def test_quantize_jax_matches_numpy_packer():
    """Device-side packer must produce the identical mlx-layout triple as the
    host packer (chip_smoke.py and tests both rely on it)."""
    import jax.numpy as jnp

    from mlx_sharding_tpu.ops.quant import quantize, quantize_jax

    rng = np.random.default_rng(5)
    w = rng.standard_normal((16, 128)).astype(np.float32)
    q_np, s_np, b_np = quantize(w, group_size=64, bits=4)
    q_j, s_j, b_j = quantize_jax(jnp.asarray(w), group_size=64, bits=4)
    np.testing.assert_array_equal(np.asarray(q_j), q_np)
    np.testing.assert_allclose(np.asarray(s_j), s_np.astype(np.float32), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(b_j), b_np.astype(np.float32), rtol=1e-3)


def test_quantize_jax_roundtrip():
    from mlx_sharding_tpu.ops.quant import dequantize, quantize_jax
    import jax.numpy as jnp

    rng = np.random.default_rng(6)
    w = rng.standard_normal((3, 8, 128)).astype(np.float32)  # stacked layers
    q, s, b = quantize_jax(jnp.asarray(w))
    back = np.asarray(dequantize(q, s, b, dtype=jnp.float32))
    # 4-bit grouped affine: max error is half a quantization step per group
    step = np.asarray(s)[..., None].repeat(64, -1).reshape(w.shape)
    assert (np.abs(back - w) <= step * 0.51 + 1e-6).all()
