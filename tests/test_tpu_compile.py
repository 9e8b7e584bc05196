"""Every Pallas kernel of ops/, compiled for a TPU v5e at the real widths.

Interpret mode checks a kernel's arithmetic; it cannot see what Mosaic
refuses — a block shape off the (8, 128) tiling, a DMA slice that is not
128-lane aligned, more VMEM than a kernel may use. The TPU compiler is
installed here and compiles for a chip that is described, not attached, so
these cases cost no chip time. The shapes are the ones chip_smoke.py runs on
the chip (Llama-3.2-3B widths) plus the MLA attention shapes and
DeepSeek-V2-Lite's packed projections and expert stacks. A compile that
passes is not a chip run; chip_smoke.py is.

The dispatch predicates ask ``jax.default_backend()``, which stays ``cpu``
here, so the test answers ``tpu`` in their place and compiles the
dispatcher itself: a shape the predicate admits and Mosaic refuses fails,
and so does a smoke shape the predicate sends to XLA.
"""

import functools
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mlx_sharding_tpu.ops import quant
from mlx_sharding_tpu.ops.attention import causal_attention
from mlx_sharding_tpu.ops.flash_attention import flash_attention
from mlx_sharding_tpu.ops.paged_attention import paged_attention

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip. The persistent compile cache is off while
    these run: an entry written for a described chip cannot be read back
    without one, and the next compile would warn and start over."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _flash(t, s, hq, hkv, dk, dv, via_dispatch=True, kernel="flash_attention"):
    # the dispatcher sends T=1 to the XLA path: the kernel's one-row tile
    # is compiled directly
    fn = causal_attention if via_dispatch else flash_attention
    return (
        functools.partial(fn, scale=dk ** -0.5),
        [((1, t, hq, dk), BF16), ((1, s, hkv, dk), BF16),
         ((1, s, hkv, dv), BF16), ((), I32)],
        kernel,
    )


def _paged(page, int8, slots=8, hq=24, hkv=8, d=128, max_seq=4096,
           pages=None, rank=None, window=None, merged=False, lead=0):
    """``rank``: MLA's latent layout — the values are the first ``rank``
    lanes of the ``d``-wide key rows (``values_from_k``) and V is the dummy
    ``(…, 1, 1)`` pool the kernel is never handed. ``window``: a sliding
    window known at trace time (the block index clamps to the visible
    pages). ``merged``: the pools keep a row's heads on the lane axis,
    ``(pages, page, 1, Hkv * D)`` (``kv_heads``). ``lead``: that many leading
    rows of each K/V head's query group are bounded by a second length
    (``lead_lengths``, one more scalar-prefetch operand)."""
    pages = pages or slots * max_seq // page + 1
    dtype = jnp.int8 if int8 else BF16
    k_pool = ((pages, page, 1, hkv * d) if merged else (pages, page, hkv, d), dtype)
    v_pool = k_pool if rank is None else ((pages, page, 1, 1), dtype)
    scales = [((pages, page, p[0][2], 1), F32) for p in (k_pool, v_pool)]

    def fn(q, k, v, tables, lengths, *more):
        ks, vs = more[-2:] if int8 else (None, None)
        return paged_attention(q, k, v, tables, lengths, d ** -0.5,
                               values_from_k=rank, k_scale=ks, v_scale=vs,
                               sliding_window=window,
                               kv_heads=hkv if merged else None,
                               **({"lead_lengths": more[0], "lead_rows": lead}
                                  if lead else {}))

    return (
        fn,
        [((slots, hq, d), BF16), k_pool, v_pool,
         ((slots, max_seq // page), I32), ((slots,), I32)]
        + ([((slots,), I32)] if lead else [])
        + (scales if int8 else []),
        "paged_attention",
    )


def _quant(m, out_dim, in_dim, kernel, scale_dtype=F32):
    """The packed-matmul DISPATCHER at one decode or prefill shape;
    ``kernel`` names what it must select (None: the XLA fallback)."""
    return (
        functools.partial(quant._quant_matmul, group_size=64, bits=4),
        [((m, in_dim), BF16), ((out_dim, in_dim // 8), jnp.uint32),
         ((out_dim, in_dim // 64), scale_dtype),
         ((out_dim, in_dim // 64), scale_dtype)],
        kernel,
    )


def _experts(n, k, e, hidden, width):
    """``ops.moe.apply_experts`` — the DISPATCHER — for ``n`` rows x top-``k``
    over packed (E, out, in) expert stacks with f32 scales and biases: it
    must select the expert-indexed kernel for all three projections."""
    from mlx_sharding_tpu.ops.moe import apply_experts

    def stack(out_dim, in_dim):
        return [((e, out_dim, in_dim // 8), jnp.uint32),
                ((e, out_dim, in_dim // 64), F32), ((e, out_dim, in_dim // 64), F32)]

    def fn(x, weights, idx, *leaves):
        gate, up, down = (
            dict(zip(("q", "scales", "biases"), leaves[i:i + 3])) for i in (0, 3, 6)
        )
        return apply_experts(x, weights, idx, gate, up, down)

    return (
        fn,
        [((n, hidden), BF16), ((n, k), F32), ((n, k), I32)]
        + stack(width, hidden) * 2 + stack(hidden, width),
        "quant_matmul_experts",
    )


def _dense_experts(n, k, held, hidden, width, gated=True, layers=2):
    """``ops.moe.apply_experts`` — the DISPATCHER — for a decode step's
    ``n`` rows x top-``k`` over dense bf16 ``(L, E, …)`` stacks under a
    resident range, read in place by ``layer``: it must select the dense
    expert-indexed kernel (ops/dense_experts.py), one call for the layer."""
    from mlx_sharding_tpu.ops.moe import apply_experts

    def fn(x, weights, idx, *stacks):
        if not gated:
            stacks = (None, *stacks)
        return apply_experts(x, weights, idx, *stacks, expert_base=held, layer=1)

    up, down = (layers, held, hidden, width), (layers, held, width, hidden)
    return (
        fn,
        [((n, hidden), BF16), ((n, k), F32), ((n, k), I32)]
        + [(up, BF16)] * (2 if gated else 1) + [(down, BF16)],
        "dense_experts",
    )


def _ssm_step(layers, slots, heads, groups, head_dim=64, state=128):
    """``ops.mamba2.ssm_pool_step`` on a ``(layers, slots + 1, H, P, N)``
    float32 state pool (the scratch row past the slots), the layer's rank an
    operand: the cell's decode step of one Mamba-2 layer."""
    from mlx_sharding_tpu.ops.mamba2 import ssm_pool_step

    return (
        ssm_pool_step,
        [((layers, slots + 1, heads, head_dim, state), F32), ((), I32),
         ((slots, heads), F32), ((slots, heads, head_dim), F32),
         ((slots, groups, state), F32), ((slots, groups, state), F32),
         ((heads,), F32), ((slots,), jnp.bool_)],
        "ssm_pool_step",
    )


def _kda_step(layers, slots, heads, head_dim=128, value_dim=None):
    """``ops.kda.kda_pool_step`` on a ``(layers, slots + 1, H, D, D)`` float32
    state pool (the scratch row past the slots), the layer's rank an operand:
    the cell's decode step of one KDA layer. With ``value_dim`` the tile is
    ``(D, value_dim)`` and the pool keeps ``ops.kda.lane_pack`` heads side by
    side on the lanes, ``(layers, slots + 1, H / P, D, P value_dim)``."""
    from mlx_sharding_tpu.ops.kda import kda_pool_step, lane_pack

    dv = value_dim or head_dim
    pack = lane_pack(heads, dv)
    vec = ((slots, heads, head_dim), F32)
    return (
        kda_pool_step,
        [((layers, slots + 1, heads // pack, head_dim, pack * dv), F32), ((), I32),
         vec, vec, ((slots, heads, dv), F32), vec, ((slots, heads), F32),
         ((slots,), jnp.bool_)],
        "kda_pool_step",
    )


def _kda_chunk(heads, head_dim=128, value_dim=None, per_channel=False):
    """``ops.kda.kda_chunked`` through its dispatcher on one sequence's
    512-row prefill chunk of one linear layer: the cell's chunked delta rule
    as the one Pallas pass ``kda_chunk_local``, ``n_valid`` an operand."""
    from mlx_sharding_tpu.ops.kda import CHUNK, kda_chunked

    dv = value_dim or head_dim
    vec = ((1, 512, heads, head_dim), F32)
    return (
        lambda q, k, v, g, beta, s, n: kda_chunked(q, k, v, g, beta, s, CHUNK, n),
        [vec, vec, ((1, 512, heads, dv), F32),
         vec if per_channel else ((1, 512, heads), F32), ((1, 512, heads), F32),
         ((1, heads, head_dim, dv), F32), ((), I32)],
        "kda_chunk_local",
    )


LLAMA_3B = [(8192, 3072), (3072, 8192), (128256, 3072)]
CASES = {
    # flash prefill chunk and T=1 at Llama-3B heads; the MLA shapes
    # (full mode 16/16/192/128, compressed 16/1/576/512)
    "flash-prefill-3b": _flash(256, 4096, 24, 8, 128, 128),
    "flash-decode-3b": _flash(1, 4096, 24, 8, 128, 128, via_dispatch=False),
    "decode-3b-takes-xla": _flash(1, 4096, 24, 8, 128, 128, kernel=None),
    "flash-prefill-mla-full": _flash(256, 4096, 16, 16, 192, 128),
    "flash-prefill-mla-compressed": _flash(256, 4096, 16, 1, 576, 512),
    # ragged paged decode at the server's default page (the prefill chunk)
    # and at 128, bf16 and int8 pools
    **{f"paged-{'int8' if q else 'bf16'}-page{p}": _paged(p, q)
       for p in (256, 128) for q in (False, True)},
    # ... and in MLA's latent layout (values_from_k): 16 slots of the
    # dsv2-lite-q4 cell, 16 query heads on the 576-lane latent head, a
    # 576-wide contraction and a [0:512] lane slice of the key block
    **{f"paged-mla-latent-{'int8-' if q else ''}page256": _paged(
        256, q, slots=16, hq=16, hkv=1, d=576, pages=145, rank=512)
       for q in (False, True)},
    # ... and with a window, at the trinity-large-bf16-ep16 cell's shapes:
    # 32 slots, 48 query heads on 8 K/V heads of 128, 512-token pages (a
    # 1 MB block a pool), a table 32 pages wide; the window layers' pool is
    # 33 rings of 10 pages
    # (with the scratch ring, four layers in one pool: 1320), the full
    # layer's 1024 pages and the scratch one; a row's heads merged on lanes
    "paged-window-ring-page512": _paged(
        512, False, slots=32, hq=48, max_seq=16384, pages=1320, window=4096,
        merged=True),
    "paged-full-page512": _paged(
        512, False, slots=32, hq=48, max_seq=16384, pages=1025, merged=True),
    # ... and at the zaya1-8b-bf16-pp2ep2 cell's: 24 slots, 8 query heads on
    # 2 K/V heads of 128 merged on 256 lanes (a 256 KB block a pool), a table
    # 24 pages wide, ALL 20 layers' pools viewed as one (20 x 577 pages)
    "paged-latent-gqa-page512": _paged(
        512, False, slots=24, hq=8, hkv=2, max_seq=12288, pages=20 * 577,
        merged=True),
    # the same cell's prefill chunk: 512 rows against a 12288-row view
    "flash-prefill-latent-gqa": _flash(512, 12288, 8, 2, 128, 128),
    # ... and at the granite4-h-micro-bf16 cell's: the first 64-wide heads.
    # 48 slots, 32 query heads (4 rows a head) on 8 K/V heads of 64 merged on
    # 512 lanes (odd heads start half a lane tile in), a table 9 pages wide,
    # the four attention layers' pools viewed as one (4 x 433 pages)
    "paged-gqa64-merged-page512": _paged(
        512, False, slots=48, hq=32, hkv=8, d=64, max_seq=4608, pages=4 * 433,
        merged=True),
    # the same cell's prefill chunk: 512 rows against a 4608-row view
    "flash-prefill-gqa64": _flash(512, 4608, 32, 8, 64, 64),
    # the one-pass Mamba-2 decode step on the state pool where it lies, at
    # the granite4-h-micro-bf16 cell's shapes (36 layers, 48 slots, 64 heads
    # of 64 on ONE group: a slot's 2 MB one block) and at the
    # nemotron3-super-bf16-ep4 cell's (5 layers, 32 slots, 128 heads on 8
    # groups: two blocks of 64 heads, four groups each)
    "ssm-step-granite": _ssm_step(36, 48, 64, 1),
    "ssm-step-nemotron3": _ssm_step(5, 32, 128, 8),
    # the one-pass gated delta-rule decode step at the
    # kimi-linear-48b-bf16-ep16 cell's shapes (20 layers, 40 slots, 32 heads
    # of 128 x 128: a slot's 2 MB one block), and that cell's latent
    # attention: 40 slots, 32 query heads on the 576-lane latent head, a
    # table 12 pages wide, the seven MLA layers' pools viewed as one
    "kda-step-kimi-linear": _kda_step(20, 40, 32),
    "paged-mla-latent-32-heads-page512": _paged(
        512, False, slots=40, hq=32, hkv=1, d=576, max_seq=6144, pages=7 * 481,
        rank=512),
    # the same two kernels at the qwen3-next-80b-bf16-ep4 cell's shapes: the
    # delta-rule step on 9 layers x 32 slots (the head's decay arrives
    # broadcast over its 128 key channels, so the operands are kimi-linear's),
    # and the ragged kernel at a shape it had not served: 8 query on each of
    # 2 K/V heads of 256, a row's two heads merged on the lanes, a table 15
    # pages wide, the three attention layers' pools viewed as one
    "kda-step-qwen3-next": _kda_step(9, 32, 32),
    "paged-gqa256-merged-page512": _paged(
        512, False, slots=32, hq=16, hkv=2, d=256, max_seq=7680, pages=3 * 481,
        merged=True),
    # ... and at the olmo-hybrid-7b-bf16-pp2 cell's: the delta-rule step on a
    # RECTANGULAR tile, 12 layers x 48 slots x 30 heads of 96 x 192 kept two
    # side by side on 384 lanes (15 lane groups, walked in blocks of 5), and
    # the ragged kernel at a query group of ONE on 30 K/V heads of 128 merged
    # on 3840 lanes: a 3.9 MB block a pool, 15.7 MB double-buffered, over
    # Mosaic's default 16 MiB with the body's own (the call states its limit);
    # a table 3 pages wide, the four attention layers' pools viewed as one
    "kda-step-olmo-hybrid": _kda_step(12, 48, 30, 96, 192),
    "paged-mha30-group1-merged-page512": _paged(
        512, False, slots=48, hq=30, hkv=30, max_seq=1536, pages=4 * 97,
        merged=True),
    # the three delta-rule cells' prefill chunk (512 rows, blocks of 64) as
    # one Pallas pass: a decay a key channel with the pairwise sums as
    # operands (kimi-linear), a decay a head (qwen3-next), and a decay a head
    # on keys 96 wide — three quarters of a lane tile, laid out in VMEM — and
    # values 192, 30 heads walked three a grid step (olmo-hybrid)
    "kda-chunk-kimi-linear": _kda_chunk(32, per_channel=True),
    "kda-chunk-qwen3-next": _kda_chunk(32),
    "kda-chunk-olmo-hybrid": _kda_chunk(30, 96, 192),
    # 4-bit projections of the 3B model: a prefill chunk's 256 rows, a
    # single stream's one row and 8 slots' rows, all on the one kernel
    **{f"quant-M{m}-{i}x{o}": _quant(m, o, i, "quant_matmul")
       for o, i in LLAMA_3B for m in (256, 1, 8)},
    # scales as a bf16 checkpoint stores them (fp16 widens to f32 at load)
    "quant-M1-3072x8192-bf16-scales": _quant(1, 8192, 3072, "quant_matmul", BF16),
    # DeepSeek-V2-Lite experts and dense MLP at one row: 1408 inputs are 176
    # word lanes (not 128-aligned), so they run as one whole IN block; 10944
    # rows (64 x 171) have no 128-row tiling, so the kernel takes them with a
    # ragged last OUT tile: a partial write of the output's lane dimension
    # and edge reads of q, scales and biases, which Mosaic must accept
    "quant-M1-dsv2-2048x1408": _quant(1, 1408, 2048, "quant_matmul"),
    "quant-M1-dsv2-1408x2048": _quant(1, 2048, 1408, "quant_matmul"),
    "quant-M1-dsv2-2048x10944": _quant(1, 10944, 2048, "quant_matmul"),
    # ... and at the served rows: a 16-slot decode step and a 256-row chunk,
    # gate/up (ragged OUT) and down (IN 10944 whole, 1368 word lanes)
    **{f"quant-M{m}-dsv2-{i}x{o}": _quant(m, o, i, "quant_matmul")
       for o, i in ((10944, 2048), (2048, 10944)) for m in (16, 256)},
    # the routed experts at decode, published widths: DeepSeek-V2-Lite's
    # (64, 1408, 256) / (64, 2048, 176) stacks at the cell's 16 rows x top-6
    # (the 176-word leaf is read transposed, as it lies in HBM), at one row,
    # and Mixtral-8x7B's 8 experts of 14336 x 4096, top-2 (two IN blocks)
    "experts-dsv2-16x6of64": _experts(16, 6, 64, 2048, 1408),
    "experts-dsv2-1x6of64": _experts(1, 6, 64, 2048, 1408),
    "experts-mixtral-16x2of8": _experts(16, 2, 8, 4096, 14336),
    # ... and a prefill chunk's 256 rows: the same kernel, one GROUP_TILE-row
    # block of its own per table entry (ops/moe.py's grouped path)
    "experts-dsv2-256x6of64": _experts(256, 6, 64, 2048, 1408),
    "experts-mixtral-256x2of8": _experts(256, 2, 8, 4096, 14336),
    # a decode step's dense bf16 experts under a resident range at the five
    # bf16 MoE cells' rows, top-k, held experts and published widths: the
    # tile over the width is all 512 (three 2 MB tiles), all 21 x 128 of the
    # un-gated 2688, all 1024, 1024 of 2048 and 768 of 3072 (the last two
    # over Mosaic's default 16 MiB of VMEM: the call states its limit); 24
    # and 40 rows are no whole bf16 sublane tiles of 16
    "dense-experts-qwen3-next-32x10of128": _dense_experts(32, 10, 128, 2048, 512),
    "dense-experts-nemotron3-32x22of128": _dense_experts(32, 22, 128, 1024, 2688, gated=False),
    "dense-experts-kimi-linear-40x8of16": _dense_experts(40, 8, 16, 2304, 1024),
    "dense-experts-zaya-24x1of8": _dense_experts(24, 1, 8, 2048, 2048),
    "dense-experts-trinity-32x4of16": _dense_experts(32, 4, 16, 3072, 3072),
    # ... and at the kernel's bound of 128 rows, and at one
    "dense-experts-trinity-128-rows": _dense_experts(128, 4, 16, 3072, 3072),
    "dense-experts-qwen3-next-1-row": _dense_experts(1, 10, 128, 2048, 512),
    # the sdar-30b-a3b-bf16-ep16 cell's decode block (diffusion over blocks
    # of 4: a forward is 32 slots x 4 rows): the ragged kernel with each K/V
    # head's 4 x 8 queries folded into a query group of 32 (128 "heads" on 4
    # K/V heads of 128 merged on 512 lanes, a table 4 pages wide, two layers'
    # pools viewed as one), and the 8 held experts of 768 x 2048 at the
    # kernel's bound of 128 rows, top-8
    "paged-sdar-group32-merged-page512": _paged(
        512, False, slots=32, hq=128, hkv=4, max_seq=2048, pages=2 * 129,
        merged=True),
    "dense-experts-sdar-128x8of8": _dense_experts(128, 8, 8, 2048, 768),
    # ... and its wide forward (two blocks a slot: the commit and the next
    # block's denoise): a group of 2 x 4 x 8 = 64, the leading 32 rows under
    # a length of their own
    "paged-sdar-group64-two-lengths-page512": _paged(
        512, False, slots=32, hq=256, hkv=4, max_seq=2048, pages=2 * 129,
        merged=True, lead=32),
}


@pytest.mark.parametrize("case", list(CASES))
def test_compiles_for_v5e(case, chip, monkeypatch):
    fn, shapes, kernel = CASES[case]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    # raises what the chip's compiler would raise
    text = jax.jit(fn).lower(*args).compile().as_text()
    if kernel is None:
        assert "tpu_custom_call" not in text
    else:
        assert "tpu_custom_call" in text and kernel in text


def _arrays_made(text: str, floor: int) -> list:
    """``(opcode, shape)`` of every instruction of a compiled module that
    makes a new array of ``floor`` bytes or more: parameters, tuple plumbing
    and bitcasts make none."""
    size = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2}
    made = []
    for dtype, dims, op in re.findall(
        r"= ([a-z]+[0-9]*)\[([0-9,]*)\]\S* ([a-z-]+)\(", text
    ):
        if op in ("parameter", "get-tuple-element", "bitcast"):
            continue
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n * size.get(dtype, 4) >= floor:
            made.append((op, f"{dtype}[{dims}]"))
    return made


def _loop_bodies(text: str) -> str:
    """The text of every computation of a compiled module that runs inside
    a ``while``: the loops' bodies and conditions and whatever they call."""
    comps = dict(re.findall(
        r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}", text, flags=re.M | re.S
    ))
    todo = re.findall(r"(?:body|condition)=%([\w.-]+)", text)
    assert todo and set(todo) <= set(comps), todo
    inside = set()
    while todo:
        name = todo.pop()
        if name not in inside:
            inside.add(name)
            todo += re.findall(r"=\{?%([\w.-]+)", comps[name])
    return "\n".join(comps[name] for name in sorted(inside & set(comps)))


@pytest.mark.parametrize("n", [16, 256], ids=["decode-16-rows", "chunk-256-rows"])
def test_experts_read_in_place_copy_nothing_of_stack_size(n, chip, monkeypatch):
    """A layer scan that carries the layer's index and calls
    ``apply_experts(layer=i)`` on DeepSeek-V2-Lite's ``(13, 64, …)`` packed
    stacks at the cell's 16 rows: the kernel's operands are the stacks where
    they lie, so nothing the program makes is as large as the smallest of a
    layer's expert leaves — the compile-time witness that no layer's stack
    is sliced or copied (the scanned-slice form needs a layer's 345 MB a
    step). What is left is XLA's own: ``w_down``'s scales and biases
    (f32[13,64,2048,22], 22 groups in the minor dimension) are laid out
    once a call, outside the loop, into the orientation the kernel reads.
    A chunk's 256 rows (the grouped path) read the stacks the same way; what
    the loop makes there is the rows' own: the gathered tiles and the
    kernel's float32 results (14 to 29 MB), never a packed word."""
    from mlx_sharding_tpu.ops.moe import apply_experts

    layers, e, hidden, width, k = 13, 64, 2048, 1408, 6
    fn, shapes, kernel = _experts(n, k, e, hidden, width)
    shapes = shapes[:3] + [((layers, *s), d) for s, d in shapes[3:]]

    def scanned(x, weights, idx, *leaves):
        gate, up, down = (
            dict(zip(("q", "scales", "biases"), leaves[i:i + 3])) for i in (0, 3, 6)
        )

        def body(h, i):
            return h + apply_experts(h, weights, idx, gate, up, down, layer=i), None

        return jax.lax.scan(body, x, jnp.arange(2))[0]

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(scanned).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3 and kernel in text
    assert f"u32[{layers * e},{width},{hidden // 8}]" in text  # the (L*E, …) view
    # a layer's smallest expert leaf (w_up's scales, 64 x 1408 x 32 f32) is
    # 11.5 MB: nothing of that size is made but the two relayouts named above
    relayout = ("copy", f"f32[{layers},{e},{hidden},{width // 64}]")
    made = _arrays_made(text, e * width * (hidden // 64) * 4)
    if n > 16:  # the chunk's own temporaries are rows, not leaves
        assert not [m for m in _arrays_made(text, 2**20) if m[1].startswith("u32")]
        made = [m for m in made if f"[{layers}," in m[1] or f"[{e}," in m[1]]
    assert set(made) <= {relayout} and len(made) <= 2, made
    # … and those two are made before the loop: inside it, nothing
    inside = _arrays_made(_loop_bodies(text), e * width * (hidden // 64) * 4)
    assert [m for m in inside if n == 16 or f"[{e}," in m[1]] == []
    once = 2 * layers * e * hidden * 24 * 4  # 22 groups on 24 sublanes
    assert compiled.memory_analysis().temp_size_in_bytes < once + (64 if n == 16 else 160) * 2**20


@pytest.mark.parametrize("cell", ["qwen3-next", "nemotron3"])
def test_dense_experts_read_in_place_copy_nothing_of_an_experts_size(
    cell, chip, monkeypatch
):
    """A layer scan that carries the layer's index and calls
    ``apply_experts(layer=i, expert_base=…)`` on the cell's dense bf16
    ``(L, 128, …)`` stacks at its 32 rows: the kernel's operands are the
    stacks where they lie (row-major, the ``(L*E, …)`` view a bitcast), so
    the program makes nothing as large as ONE matrix of one expert — no
    copy, transpose or slice of a stack, a layer or an expert — and there
    is no ``while`` but the layer scan's own: the walk's went."""
    from mlx_sharding_tpu.ops.moe import apply_experts

    layers = 3
    n, k, held, hidden, width, gated = {
        "qwen3-next": (32, 10, 128, 2048, 512, True),
        "nemotron3": (32, 22, 128, 1024, 2688, False),
    }[cell]
    _, shapes, kernel = _dense_experts(n, k, held, hidden, width, gated, layers)

    def scanned(x, weights, idx, *stacks):
        if not gated:
            stacks = (None, *stacks)

        def body(h, i):
            return h + apply_experts(
                h, weights, idx, *stacks, expert_base=0, layer=i), None

        return jax.lax.scan(body, x, jnp.arange(layers))[0]

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(scanned).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and kernel in text
    assert f"bf16[{layers * held},{hidden},{width}]" in text  # the (L*E, …) view
    assert len(re.findall(r" while\(", text)) == 1
    assert _arrays_made(text, hidden * width * 2) == []
    assert compiled.memory_analysis().temp_size_in_bytes < hidden * width * 2


def test_latent_attention_gathers_no_table_and_copies_no_pool(chip, monkeypatch):
    """The ragged decode body's write-then-attend in MLA's latent layout,
    scanned over a two-layer ``(L, 145, 1, 256, 1, 576)`` pool (16 slots,
    a table 16 pages wide): each layer scatters the slots' new rows into its
    pool and hands the pool to ``paged_attention(values_from_k=512)``. With
    the kernel the loop makes nothing as large as one gathered table
    (16 x 16 pages x 256 x 576 bf16, 75.5 MB: the fallback makes a gather,
    a transposed copy, a select and a value slice of it, per layer per
    step), and the only pool-sized thing in it is the scan's own in-place
    write of the layer back into its stack: no ``copy`` of the pool."""
    layers, pages, page, dk, rank, slots, hq, spg = 2, 145, 256, 576, 512, 16, 16, 16

    def step(q, k, v, rows, page_ids, row_pos, lengths):
        def layer(h, kv):
            kl, vl = kv[0][:, 0], kv[1][:, 0]  # drop the B == 1 axis
            kl = kl.at[page_ids, row_pos].set(h[:, :1])
            vl = vl.at[page_ids, row_pos].set(jnp.zeros((slots, 1, 1), vl.dtype))
            out = paged_attention(h, kl, vl, rows, lengths, dk ** -0.5,
                                  values_from_k=rank)
            h = h + jnp.pad(out, ((0, 0), (0, 0), (0, dk - rank)))
            return h, (kl[:, None], vl[:, None])

        return jax.lax.scan(layer, q, (k, v))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = (layers, pages, 1, page, 1)
    shapes = [((slots, hq, dk), BF16), ((*pool, dk), BF16), ((*pool, 1), BF16),
              ((slots, spg), I32), ((slots,), I32), ((slots,), I32), ((slots,), I32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(step, donate_argnums=(1, 2)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "paged_attention" in text
    whole = f"bf16[{layers},{pages},1,{page},1,{dk}]"
    made = _arrays_made(_loop_bodies(text), slots * spg * page * dk * 2)
    assert set(made) <= {("dynamic-update-slice", whole), ("fusion", whole)}, made
    assert not re.search(r"\[\d+,4096,", _loop_bodies(text))  # no max_seq-dense view


def test_kda_step_in_a_layer_scan_moves_nothing_but_its_blocks(chip):
    """The same for Kimi-Linear's state pool (20 x 41 rows of 32 x 128 x 128
    float32, 1.7 GB) under ``kda_pool_step`` at the scanned rank: no array
    as large as one layer's rows (84 MB) and no copy of the pool."""
    from mlx_sharding_tpu.ops.kda import kda_pool_step

    _, shapes, kernel = _kda_step(20, 40, 32)

    def walk(pool, _rank, q, k, v, g, beta, active):
        def layer(carry, rank):
            pool, acc = carry
            o, pool = kda_pool_step(pool, rank, q + acc, k, v, g, beta, active)
            return (pool, o), None

        return jax.lax.scan(layer, (pool, jnp.zeros_like(q)), jnp.arange(pool.shape[0]))[0]

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(walk, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(4, \{\}\)\}", text)
    rows = 40 * 32 * 128 * 128 * 4
    assert _arrays_made(text, rows) == []
    assert compiled.memory_analysis().temp_size_in_bytes < rows


def test_ssm_step_in_a_layer_scan_moves_nothing_but_its_blocks(chip):
    """A layer scan that carries granite-4.0-h-micro's whole state pool
    (36 x 49 rows of 64 x 64 x 128 float32, 3.7 GB) and calls
    ``ssm_pool_step`` at the scanned rank: the pool is the custom call's
    operand where it lies, aliased to its result — the program makes no
    array as large as one layer's rows (100 MB; a ``dynamic-slice``, a
    ``select`` or a ``dynamic-update-slice`` fusion of them is what the XLA
    formula compiles to) and no copy of the pool (3.7 GB of temporaries: the
    one way an aliased call goes wrong silently)."""
    from mlx_sharding_tpu.ops.mamba2 import ssm_pool_step

    _, shapes, kernel = _ssm_step(36, 48, 64, 1)

    def walk(pool, _rank, dt, x, b_mat, c_mat, a_head, active):
        def layer(carry, rank):
            pool, acc = carry
            y, pool = ssm_pool_step(pool, rank, dt, x + acc, b_mat, c_mat, a_head, active)
            return (pool, y), None

        return jax.lax.scan(layer, (pool, jnp.zeros_like(x)), jnp.arange(pool.shape[0]))[0]

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(walk, donate_argnums=0).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and kernel in text
    assert re.search(r"output_to_operand_aliasing=\{\{1\}: \(5, \{\}\)\}", text)
    rows = 48 * 64 * 64 * 128 * 4
    # (the call's own result is a tuple, which ``_arrays_made`` does not read)
    assert _arrays_made(text, rows) == []
    assert compiled.memory_analysis().temp_size_in_bytes < rows


@pytest.mark.parametrize("lanes", [1, 2], ids=["narrow", "wide"])
def test_the_diffusion_decode_block_compiles_for_v5e(lanes, chip, monkeypatch):
    """``sdar_moe``'s decode forward at the published widths, two layers, as
    the ragged body runs it (``parallel/pipeline.py`` at ``T = L = 4``): 32
    slots x 4 rows through ``sp_layer``; each layer scatters a slot's 4 rows
    into one page of the pool it is handed whole (two layers' pages viewed as
    one, a row's 4 K/V heads merged on 512 lanes), folds the 4 queries into
    the kernel's query group (Mosaic takes a group of 32) and multiplies its
    128 rows through the 8 held experts of 128 on ``dense_experts`` at the
    kernel's bound of rows. No copy of the pool, no gathered table. Wide: two
    lanes of 4 rows a slot, each scattered to a page of its own, a group of
    64 whose leading 32 rows see a block less, and the experts a lane a call
    (256 rows at once would leave the kernel for the loop)."""
    from mlx_sharding_tpu.models import build_model
    from mlx_sharding_tpu.models.base import scan_layers_carried
    from mlx_sharding_tpu.parallel.pipeline import fold_block_queries

    layers, slots, L, page, spg, pages = 2, 32, 4, 512, 4, 129
    model, cfg = build_model(dict(
        model_type="sdar_moe", vocab_size=19072, hidden_size=2048,
        num_hidden_layers=layers, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, intermediate_size=6144, moe_intermediate_size=768,
        num_experts=8, num_experts_per_tok=8, moe_expert_share=16,
        moe_expert_share_index=0, mask_token_id=0,
    ))
    kv = cfg.num_key_value_heads * cfg.head_dim

    def step(stack, h, k, v, rows, page_ids, row_pos, offsets):
        lengths = offsets + lanes * L
        two = {} if lanes == 1 else dict(lead_lengths=offsets + L, lead_rows=L * 8)
        # (slots, lanes): lane 2's page is the one behind lane 1's here
        ids = page_ids[:, None] + jnp.arange(lanes)[None, :]
        at = jnp.broadcast_to(
            row_pos[:, None, None] + jnp.arange(L)[None, None, :], (slots, lanes, L))

        def layer(h, p, k, v, row, keep):
            first = row * pages

            def attn_fn(q, k_new, v_new, kv_heads):
                put = lambda pool, new: pool.at[ids[..., None] + first, at].set(  # noqa: E731
                    new.reshape(slots, lanes, L, *new.shape[2:]))
                kl, vl = put(k, k_new), put(v, v_new)
                layer.done = kl, vl
                return fold_block_queries(
                    lambda q1: paged_attention(
                        q1, kl, vl, rows + first, lengths, model.scale,
                        kv_heads=kv_heads, **two,
                    ), q, kv_heads)

            h, _, _ = model.sp_layer(p, h, offsets, attn_fn)
            return h, *layer.done

        return scan_layers_carried(
            layer, h, stack, k, v, jnp.arange(layers),
            in_place=model.scan_in_place(None, stack),
        )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    stack = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))["layers"]
    pool = ((layers * pages, page, 1, kv), BF16)
    shapes = [((slots, lanes * L, cfg.hidden_size), BF16), pool, pool,
              ((slots, spg), I32), ((slots,), I32), ((slots,), I32), ((slots,), I32)]
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=chip)  # noqa: E731
    args = [jax.tree.map(lambda x: sds(x.shape, x.dtype), stack),
            *(sds(s, d) for s, d in shapes)]
    text = jax.jit(step, donate_argnums=(2, 3)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "paged_attention" in text and "dense_experts" in text
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"", _loop_bodies(text))) == 1 + lanes
    # the only pool-sized things in the loop: each pool's scatter of the
    # slots' rows (a fusion around a scatter, in place on the carry)
    whole = f"bf16[{layers * pages},{page},{kv}]"
    made = _arrays_made(_loop_bodies(text), slots * spg * page * kv * 2)
    assert set(made) <= {("scatter", whole), ("fusion", whole)}, made
    assert len(made) <= 4, made
