"""The gated delta rule, the mixer of two families' linear-attention layers:
Kimi Delta Attention (arXiv 2510.26692, ``models/kimi_linear.py``) with one
decay a KEY CHANNEL, and Gated DeltaNet (arXiv 2412.06464,
``models/qwen3_next.py``) with one decay a HEAD. One recurrence, three forms,
shared by both; the per-channel family first.

``[q, k, v] = silu(causal depthwise conv1d(qkv_proj(u)))``, ``H`` heads of
``D`` each; ``q = l2norm(q) * D**-0.5``, ``k = l2norm(k)``; the decay ``g =
-exp(A_log[h]) * softplus(f_b(f_a(u))[h, d] + dt_bias[h, d])`` (``alpha =
exp(g)`` in ``(0, 1)``, one a head and key channel), ``beta = sigmoid(b_proj(
u))[h]``. Per head, with ``S`` a ``(D, D)`` matrix from key to value channels:

    ``S' = alpha_t (.) S_{t-1}`` (rows scaled),
    ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``,   ``o_t = S_t^T q_t``;

``y = rmsnorm_over_D(o) * o_norm * sigmoid(g_b(g_a(u)))``; ``o_proj``. Per
sequence a layer keeps ``S (H, D, D)`` in float32 and the last ``taps - 1``
inputs of its convolution in the activation dtype.

Three forms of the recurrence, all float32. :func:`kda_sequential` is the
definition, one position at a time. A prompt chunk runs :func:`kda_chunked`:
blocks of ``chunk`` positions as matrix products (the WY form below), on a TPU
as ONE Pallas pass (:func:`kda_chunk_call`, further down). A decode
step runs the one-step form on the state pool ``(layers, rows, H, D, D)``
where it lies (:func:`kda_pool_step`, a Pallas kernel after
``ops.mamba2.ssm_pool_step``: one grid step a slot, read the slot's S, scale
its rows, ``k^T S'`` and ``S^T q`` from the block in VMEM, the rank-1 add, an
inactive slot's S kept, write over what was read, the pool aliased to the
result: each byte of S moves once each way). Unlike Mamba-2's step this one
is no multiply-add: ``k^T S'`` is a reduction over the state BEFORE the
write, so as array operations (:func:`_kda_step_xla`: a backend without the
kernel, the lanes of a ``jax.vmap``) it is a slice, a pass for ``k^T S'``, a
pass that writes ``S_t`` and reads it for ``o``, and the write-back.

The chunked form. With ``u_t = beta_t (v_t - S_{t-1}^T (alpha_t (.) k_t))``
the recurrence is ``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T``. Inside a block
with ``G_t = sum_{i<=t} g_i`` (a key channel's, ``<= 0``) and ``P_ti(x) =
sum_d x_td k_id exp(G_td - G_id)``:

    ``A_ti = beta_t P_ti(k)`` for ``i < t``;
    ``U = (I + A)^{-1} (beta (.) V) - [(I + A)^{-1} (beta (.) exp(G) (.) K)] S_0``;
    ``o_t = S_0^T (exp(G_t) (.) q_t) + sum_{i<=t} P_ti(q) u_i``;
    ``S_C = Diag(exp(G_C)) S_0 + sum_i (exp(G_C - G_i) (.) k_i) u_i^T``.

Both inverses' products are independent of ``S_0``, so every block's pairwise
sums and its unit lower triangular solve are made at once and only the three
products with ``S_0`` run block after block. ``exp(-G_i)`` ALONE is never
formed: at ``exp(A_log)`` = 16 it overflows float32 inside 64 positions;
every exponent here is a difference that is ``<= 0``.

The chunked form's kernel. As array operations (:func:`_kda_chunked_xla`: a
backend without the kernel, the lanes of a ``jax.vmap``) a layer's chunk is a
cumulative sum, two masked exponentials, two einsums, a concatenate, a
``triangular_solve`` custom call that walks each 64 x 64 system row after row
from HBM, three transposing copies in and one out, and a scan of three small
products a block: a dozen float32 operations each too small to fill the chip.
:func:`kda_chunk_call` runs a block of a few heads whole in VMEM, grid
``(sequence, heads / hb, blocks)`` with the blocks in order and ``S`` resident
in the result's block over them: the pairwise sums on the matrix unit (one
decay a head; with one a key channel they stay :func:`_chunk_pairwise_xla`'s
and come in as operands), ``(I + A) X = [beta exp(G) K | beta V]`` by
SUBSTITUTION, and the three products with ``S``. The substitution: ``A``'s 16 x
16 diagonal blocks are inverted row after row (row ``r`` of an inverse is
``e_r`` less ``A``'s row ``r`` times the rows above it; a head's four blocks
stacked on sublanes and advanced together), then ``X``'s four row blocks follow
one another, ``X_I = T_I (R_I - A_{I,<I} X_{<I})``, by products. No power of
``A`` is formed: at ``beta`` near 2 and repeated keys ``A`` is nearly 2
everywhere below the diagonal, its inverse is bounded (entries of about 2 in
alternating signs) and its powers grow by binomials to ``1e28`` before they
cancel, which no float32 Neumann or repeated-squaring product survives. Keys
96 wide (no lane tile) are laid out by the kernel in VMEM; heads go first in
HBM (``(B, H, T, …)``, a block of a head whole rows); ``n_valid`` rides in as a
scalar-prefetch operand and a block wholly past it costs neither arithmetic nor
a fetch (its block index repeats the last live block's) — a 512-row chunk of a
200-token prompt runs four of its eight blocks. Products are float32 at
``Precision.HIGHEST`` as the array form's are.

The scalar-decay case (:func:`gdn_mixer`). ``g = -exp(A_log[h]) * softplus(
in_proj_ba(u)[h] + dt_bias[h])`` is one number a position and VALUE head:
``alpha`` is constant over a head's key channels, and ``g`` comes ``(B, T,
H)``, one axis short; each form tells the two cases apart by that rank.
:func:`kda_sequential` and the one-step form take the head's decay broadcast
over its channels (the kernel's ``cols`` operand carries ``alpha`` a channel
either way; the state is the same ``(H, Dk, Dv)`` float32). The chunked form
is where it matters: ``P_ti(x) = (x_t . k_i) exp(G_t - G_i)`` is ONE ``(C,
Dk) x (Dk, C)`` product times a ``(C, C)`` matrix of exponentials (masked
before the exponential, every exponent ``<= 0`` as above), where the
per-channel form builds ``(C, C, Dk)`` exponentials a head — 128 times the
elements and no matrix unit. The solve and the three products with ``S_0``
are the same code. That family has fewer KEY heads than value heads (16 under
32 at the published sizes): ``q`` and ``k`` pass the convolution and the L2
norm as key heads and are then repeated (value head ``j`` reads key head ``j
// (Hv / Hk)``), so the recurrence itself sees ``Hv`` heads of each. Its one
convolution runs over ``[q, k, v]`` joined, its output gate ``z`` comes out of
the same projection as ``q``, ``k``, ``v`` and gates through ``silu``, and
``beta`` and the decay's input come out of one small projection.

A third family (``models/olmo_hybrid.py``) runs the scalar-decay case with
keys and values of DIFFERENT widths, a rectangular ``(Dk, Dv)`` state a head
(96 x 192 at the published sizes), and ``beta = 2 sigmoid(b)`` in ``(0, 2)``
(:func:`gdn_mixer`'s ``value_dim`` and ``beta_scale``): the step's ``I - beta
k k^T`` then has the eigenvalue ``1 - beta`` in ``(-1, 1)`` along ``k``, still
a contraction, and none of the three forms asks ``beta <= 1``. No form reads
``Dk == Dv`` off anything: the einsums and the kernel's blocks take each from
its operand. What the width of the values does decide is the state pool's
LAYOUT. A TPU tiles an array over its two minor dimensions in ``(8, 128)``
tiles, so a ``(…, 96, 192)`` pool lies in HBM padded to 256 lanes and every
step would move a third more bytes than the state has. The pool therefore
keeps :func:`lane_pack` heads side by side on its lane axis, ``(L, rows, H /
P, Dk, P Dv)``: at 192 two heads are 384 lanes, three whole tiles, and no byte
is padded (``P`` is 1 at 128, where a head's tile is whole). A lane's column
sum is its own head's, so the kernel differs only in its per-head columns
(``alpha``, ``k``, ``q``), which each lane takes from its own head; the forms
that want heads apart (:func:`kda_chunked`, :func:`_kda_step_xla`) view the
slots' rows of one layer through :func:`unpack_heads`. How many heads share a
lane group is read off the shapes (``q``'s heads over the pool's groups).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlx_sharding_tpu.ops.dispatch import DispatchCounter
from mlx_sharding_tpu.ops.mamba2 import keep_inactive, put_rows, take_rows

_HI = jax.lax.Precision.HIGHEST

# Which path a recurrence took, once per traced call (ops/dispatch.py).
# /metrics shows it as ``mst_kda_dispatch_total{path}``. A decode step:
# "kernel", or "xla" — above 0 on a chip it says some layer slices its rows
# of S out of the pool, passes over them twice and writes them back. A
# chunk's chunked form: "chunk_kernel", or "chunk_xla" — above 0 on a chip
# it says some layer's chunk ran as array operations around a triangular
# solve's custom call.
_DISPATCHED = DispatchCounter("kernel", "xla", "chunk_kernel", "chunk_xla")
dispatch_counts = _DISPATCHED.counts
_count_dispatch = _DISPATCHED.count

#: positions a block of :func:`kda_chunked` holds (the published kernel's)
CHUNK = 64
#: bytes of S one grid step of :func:`kda_pool_step` moves each way, at most
_STEP_BLOCK_BYTES = 2 << 20
#: positions a diagonal block of the chunk kernel's triangular system holds
_SUB = 16
#: heads one grid step of the chunk kernel holds, at most
_CHUNK_HEADS = 4


def kda_sequential(q, k, v, g, beta, state):
    """The recurrence one position at a time (``lax.scan``): the definition,
    what the other two forms must equal. ``q`` / ``k (B, T, H, Dk)`` already
    normalised and scaled, ``v (B, T, H, Dv)``, ``g (B, T, H, Dk)`` the
    log-decay (``<= 0``; 0 with ``beta`` 0 at a row that must not advance the
    state) or ``(B, T, H)`` one a head, ``beta (B, T, H)``, ``state (B, H,
    Dk, Dv)``; float32. Returns ``(o (B, T, H, Dv), state after the last
    row)``."""
    if g.ndim == beta.ndim:  # one decay a head: the same for its channels
        g = g[..., None]

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        r = (s * k_t[..., None]).sum(-2)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - r)[..., None, :]
        return s, (s * q_t[..., None]).sum(-2)

    t_first = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    state, o = jax.lax.scan(
        step, state, tuple(t_first(z) for z in (q, k, v, g, beta))
    )
    return jnp.moveaxis(o, 0, 1), state


def _split_blocks(z, chunk: int):
    """``(B, nc * chunk, H, …)`` as ``(nc, B, H, chunk, …)``: blocks first for
    the scan, positions beside channels."""
    b, t = z.shape[:2]
    return jnp.moveaxis(
        z.reshape(b, t // chunk, chunk, *z.shape[2:]), (1, 3), (0, 2)
    )


def _chunk_pairwise_xla(q, k, gc):
    """``(P_ti(k)`` for ``i < t``, ``P_ti(q)`` for ``i <= t)`` of every block
    as array operations: ``q`` / ``k (nc, B, H, C, Dk)``, ``gc`` the decay's
    running sum from each block's start, ``(nc, B, H, C, Dk)`` or, one decay
    a head, ``(nc, B, H, C)``. Both ``(nc, B, H, C, C)``."""
    chunk = k.shape[-2]
    before = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    upto = jnp.tril(jnp.ones((chunk, chunk), bool))
    if gc.ndim < k.ndim:
        # one decay a head: ``(x_t . k_i) exp(G_t - G_i)``, a (C, Dk) x (Dk,
        # C) product times (C, C) exponentials
        decay = jnp.exp(jnp.where(upto, gc[..., :, None] - gc[..., None, :], -jnp.inf))
        mm = functools.partial(jnp.einsum, "nbhtd,nbhid->nbhti", precision=_HI)
        return jnp.where(before, mm(k, k) * decay, 0.0), mm(q, k) * decay

    def pairwise(xs):
        """One block's: float32 products summed over the key channels, the
        exponent a difference masked BEFORE the exponential (one exponential
        serves both: on the diagonal it is 1, and ``P(k)`` leaves the
        diagonal out)."""
        q_c, k_c, g_c = xs  # (B, H, C, Dk)
        diff = g_c[..., :, None, :] - g_c[..., None, :, :]  # (B, H, C, C, Dk)
        decayed = k_c[..., None, :, :] * jnp.exp(
            jnp.where(upto[..., None], diff, -jnp.inf)
        )
        kk = jnp.where(before, (k_c[..., :, None, :] * decayed).sum(-1), 0.0)
        return kk, (q_c[..., :, None, :] * decayed).sum(-1)

    return jax.lax.map(pairwise, (q, k, gc))


def _chunk_local_xla(q, k, v, g, beta):
    """The block-local half of :func:`kda_chunked` as array operations, every
    operand split into blocks ``(nc, B, H, C, …)`` (``beta`` with a last axis
    of 1): what the block scan reads, ``(u0, w, exp(G) q, P(q), exp(G_C - G)
    k, exp(G_C))``."""
    chunk = k.shape[-2]
    gc = jnp.cumsum(g, axis=3)  # G_t, from the block's start
    kk, qk = _chunk_pairwise_xla(q, k, gc)
    if gc.ndim < k.ndim:
        gc = gc[..., None]  # what follows scales channels: one decay serves each
    eg = jnp.exp(gc)
    # (I + A)^{-1} [beta V | beta exp(G) K]: one unit lower triangular solve
    rhs = jnp.concatenate([beta * v, beta * eg * k], axis=-1)
    with jax.default_matmul_precision("highest"):
        solved = jax.scipy.linalg.solve_triangular(
            jnp.eye(chunk, dtype=jnp.float32) + beta * kk, rhs,
            lower=True, unit_diagonal=True,
        )
    u0, w = solved[..., : v.shape[-1]], solved[..., v.shape[-1] :]
    to_end = jnp.exp(gc[..., -1:, :] - gc) * k  # exp(G_C - G_i) k_i
    return u0, w, eg * q, qk, to_end, eg[..., -1, :]


def _chunk_scan_xla(state, local):
    """The three products with the carried state, block after block:
    ``(o (nc, B, H, C, Dv), state)`` from :func:`_chunk_local_xla`'s six."""

    def block(s, xs):
        u0_c, w_c, qe_c, qk_c, to_end_c, total_c = xs
        mm = functools.partial(jnp.einsum, precision=_HI)
        u = u0_c - mm("bhcd,bhdv->bhcv", w_c, s)
        o = mm("bhcd,bhdv->bhcv", qe_c, s) + mm("bhci,bhiv->bhcv", qk_c, u)
        s = total_c[..., None] * s + mm("bhcd,bhcv->bhdv", to_end_c, u)
        return s, o

    state, o = jax.lax.scan(block, state, local)
    return o, state


def _kda_chunked_xla(q, k, v, g, beta, state, chunk: int):
    """:func:`kda_chunked` as array operations (a backend without the
    kernel, the lanes of a ``jax.vmap``); ``T`` whole blocks."""
    _count_dispatch("chunk_xla")
    b, t, h, _ = k.shape
    split = functools.partial(_split_blocks, chunk=chunk)
    local = _chunk_local_xla(
        split(q), split(k), split(v), split(g), split(beta[..., None])
    )
    o, state = _chunk_scan_xla(state, local)
    return jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t, h, -1), state


def _dot(a, b, dims=((1,), (0,))):
    """A float32 product on the matrix unit inside a kernel, at the
    precision the array form's einsums ask for."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), precision=_HI, preferred_element_type=jnp.float32
    )


_NT = ((1,), (1,))  # a b^T
_TN = ((0,), (0,))  # a^T b


def _kda_chunk_kernel(nv_ref, *refs, per_head: bool):
    """One block of ``C`` positions of ``hb`` heads, the whole of
    :func:`kda_chunked`'s arithmetic for it in VMEM. Grid ``(B, H / hb,
    blocks)``, the blocks in order: ``s_ref (hb, Dk, Dv)``, the result's
    state, stays resident over them and carries S. ``q_ref`` / ``k_ref (hb,
    C, Dk)``, ``v_ref`` / ``o_ref (hb, C, Dv)``, ``beta_ref (hb, blocks, C)``
    every block's ``beta`` as rows. One decay a head (``per_head``): ``g_ref``
    as ``beta_ref``, and the pairwise sums are made here. One a key channel:
    ``g_ref (hb, C, Dk)`` and ``kk_ref`` / ``qk_ref (hb, C, C)`` the sums as
    :func:`_chunk_pairwise_xla` made them. ``x_ref (hb, C, Dk up to whole
    lane tiles + Dv)`` holds ``[beta exp(G) K | beta V]`` and then the
    system's solution in its place.

    ``(I + A) X = R`` by substitution: the ``_SUB``-wide diagonal blocks are
    inverted row after row (all of a head's at once, stacked on sublanes:
    row ``r`` of each inverse is ``e_r`` less ``A``'s row ``r`` times the
    rows above), then ``X``'s row blocks follow one another by products. No
    power of ``A`` is ever formed. A block at or past ``nv_ref[0]`` passes
    the state through: zeros to ``o_ref`` and no arithmetic."""
    if per_head:
        q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref, x_ref = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, beta_ref, kk_ref, qk_ref,
         s0_ref, o_ref, s_ref, x_ref) = refs
    c = pl.program_id(2)
    hb, chunk, dk = k_ref.shape
    dv = v_ref.shape[-1]
    at_v = x_ref.shape[-1] - dv  # the values' first lane in x_ref
    sub = _SUB if chunk % _SUB == 0 else 8
    f32, i32 = jnp.float32, jnp.int32

    @pl.when(c == 0)
    def _():
        s_ref[...] = s0_ref[...]

    live = c * chunk < nv_ref[0]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        row = jax.lax.broadcasted_iota(i32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(i32, (chunk, chunk), 1)
        eye = (row == col).astype(f32)
        same = row // sub == col // sub  # inside a diagonal block
        sub_row = jax.lax.broadcasted_iota(i32, (chunk, sub), 0) % sub
        # lane ``sub J + t`` to lane ``t``; and the inverses' start, identities
        pick = (sub_row == jax.lax.broadcasted_iota(i32, (chunk, sub), 1)).astype(f32)
        lane = jax.lax.broadcasted_iota(i32, (sub, chunk), 1)

        def solve(h, a):
            """``x_ref[h] <- (I + a)^{-1} x_ref[h]``, ``a`` strictly lower."""
            # the diagonal blocks TRANSPOSED and stacked, (C, sub): row r of
            # block J down a column, where the rows it multiplies lie
            dt = _dot(jnp.where(same, _dot(eye, a, _NT), 0.0), pick)
            inv = pick
            for r in range(1, sub):
                above = (dt[:, r : r + 1] * inv).reshape(chunk // sub, sub, sub)
                above = jnp.broadcast_to(
                    above.sum(axis=1, keepdims=True), above.shape
                ).reshape(chunk, sub)
                inv = jnp.where(sub_row == r, inv - above, inv)
            for i in range(chunk // sub):
                rows = slice(i * sub, (i + 1) * sub)
                rhs = x_ref[h, rows, :]
                if i:
                    left = jnp.where(lane < i * sub, a[rows], 0.0)
                    rhs = rhs - _dot(left, x_ref[h])
                x_ref[h, rows, :] = _dot(inv[rows], rhs)

        if at_v > dk:  # lanes no column of the system lies on
            x_ref[:, :, dk:at_v] = jnp.zeros((hb, chunk, at_v - dk), f32)
        for h in range(hb):
            q, k, v = q_ref[h], k_ref[h], v_ref[h]
            beta = beta_ref[h, pl.ds(c, 1), :]  # (1, C)
            beta = jnp.sum(jnp.where(row == col, beta, 0.0), axis=1, keepdims=True)
            if per_head:
                g = g_ref[h, pl.ds(c, 1), :]  # (1, C)
                upto = jnp.where(row >= col, g, 0.0)  # [t, j]: g_j, j <= t
                gc = jnp.sum(upto, axis=1, keepdims=True)  # G_t (C, 1)
                tail = jnp.sum(jnp.where(row < col, g, 0.0), axis=1, keepdims=True)
                total = jnp.exp(jnp.sum(g, axis=1, keepdims=True))  # (1, 1)
                # G_t - G_i as the sum it is, g_j over i < j <= t
                diff = _dot(upto, (row > col).astype(f32))
                decay = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
                kk = jnp.where(row > col, _dot(k, k, _NT) * decay, 0.0)
                qk = _dot(q, k, _NT) * decay
            else:
                g = g_ref[h]  # (C, Dk)
                gc = _dot((row >= col).astype(f32), g)  # G_t (C, Dk)
                tail = _dot((row < col).astype(f32), g)  # G_C - G_t
                # a block's whole decay down a column, as S's rows lie
                total = jnp.exp(_dot(g, jnp.ones((chunk, 128), f32), _TN)[:, :1])
                kk, qk = kk_ref[h], qk_ref[h]
            eg = jnp.exp(gc)
            x_ref[h, :, :dk] = beta * eg * k
            x_ref[h, :, at_v:] = beta * v
            solve(h, beta * kk)
            s = s_ref[h]
            u = x_ref[h, :, at_v:] - _dot(x_ref[h, :, :dk], s)
            o_ref[h] = _dot(eg * q, s) + _dot(qk, u)
            s_ref[h] = total * s + _dot(jnp.exp(tail) * k, u, _TN)


def _chunk_head_block(heads: int) -> int:
    """Heads of one grid step of :func:`kda_chunk_call`: the most that
    divide ``heads``, up to ``_CHUNK_HEADS`` (the body is unrolled over
    them: independent chains for the scheduler to interleave)."""
    return max(hb for hb in range(1, _CHUNK_HEADS + 1) if heads % hb == 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_chunk_call(q, k, v, g, beta, state, n_valid, *, chunk: int = CHUNK,
                   interpret: bool = False):
    """:func:`kda_chunked` over whole blocks as ONE Pallas pass
    (:func:`_kda_chunk_kernel`): ``T`` a multiple of ``chunk``, ``n_valid``
    an int32 scalar (may be traced): blocks wholly at or past it are not
    computed (zeros in ``o``, the state passed through) nor fetched (their
    block index repeats the last live one). Heads go first in HBM, ``(B, H,
    T, …)``, so a block of a head is whole rows. With a decay a key channel
    the pairwise sums stay array operations and come in as operands. Jitted:
    traced once for all the layers that call it."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    nc = t // chunk
    per_head = g.ndim == beta.ndim
    hb = _chunk_head_block(h)
    f32 = jnp.float32
    heads_first = lambda z: jnp.swapaxes(z.astype(f32), 1, 2)  # noqa: E731
    as_rows = lambda z: heads_first(z).reshape(b, h, nc, chunk)  # noqa: E731
    last = lambda nv: jnp.maximum(nv[0] - 1, 0) // chunk  # noqa: E731
    block = lambda width: pl.BlockSpec(  # noqa: E731
        (None, hb, chunk, width),
        lambda i, j, c, nv: (i, j, jnp.minimum(c, last(nv)), 0),
    )
    whole = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None, hb, *shape), lambda i, j, c, nv: (i, j, 0, 0)
    )
    operands = [heads_first(q), heads_first(k), heads_first(v)]
    in_specs = [block(dk), block(dk), block(dv)]
    if per_head:
        operands += [as_rows(g), as_rows(beta)]
        in_specs += [whole(nc, chunk), whole(nc, chunk)]
    else:
        split = functools.partial(_split_blocks, chunk=chunk)
        kk, qk = _chunk_pairwise_xla(split(q), split(k), jnp.cumsum(split(g), axis=3))
        pair = pl.BlockSpec(
            (None, None, hb, chunk, chunk),
            lambda i, j, c, nv: (jnp.minimum(c, last(nv)), i, j, 0, 0),
        )
        operands += [heads_first(g), as_rows(beta), kk, qk]
        in_specs += [block(dk), whole(nc, chunk), pair, pair]
    width = -(-dk // 128) * 128 + dv
    o, state = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, per_head=per_head),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb, nc),
            in_specs=[*in_specs, whole(dk, dv)],
            out_specs=[
                pl.BlockSpec((None, hb, chunk, dv), lambda i, j, c, nv: (i, j, c, 0)),
                whole(dk, dv),
            ],
            scratch_shapes=[pltpu.VMEM((hb, chunk, width), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, dv), f32),
            jax.ShapeDtypeStruct((b, h, dk, dv), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="kda_chunk_local",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), *operands, state.astype(f32))
    return jnp.swapaxes(o, 1, 2), state


def chunk_kernel_eligible(k, state, chunk: int, interpret: bool) -> bool:
    """:func:`kda_chunk_call` on a TPU backend (in interpret mode on any)
    for float32 operands and blocks of whole sublane tiles; the array
    operations otherwise. ``Dk`` and ``Dv`` are the kernel's to lay out."""
    return (
        (interpret or jax.default_backend() == "tpu")
        and k.dtype == jnp.float32
        and state.dtype == jnp.float32
        and chunk % 8 == 0
    )


def _kda_chunk_lanes(axis_size, in_batched, *args, chunk: int):
    """The kernel's call under ``jax.vmap``: the lanes take the array
    operations, as :func:`_kda_step_lanes` does for the step."""
    in_axes = jax.tree.map(lambda batched: 0 if batched else None, in_batched)
    lane = lambda q, k, v, g, beta, state, n_valid: _kda_chunked_xla(  # noqa: E731
        q, k, v, g, beta, state, chunk
    )
    return jax.vmap(lane, in_axes=in_axes)(*args), (True, True)


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK, n_valid=None,
                interpret: bool = False):
    """:func:`kda_sequential` over blocks of ``chunk`` positions as matrix
    products (the module docstring's WY form): same arguments and result, by
    the path the operands allow (:func:`chunk_kernel_eligible`). A ragged
    last block is padded with rows of ``g = 0``, ``beta = 0``, which pass the
    state through. ``n_valid`` (None: ``T``) promises that rows at or past
    it carry such ``g`` and ``beta`` already: the kernel then skips the
    blocks wholly past it, whose rows of ``o`` nobody may read."""
    t = k.shape[1]
    pad = -t % chunk
    if pad:
        padt = lambda z: jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))  # noqa: E731
        q, k, v, g, beta = padt(q), padt(k), padt(v), padt(g), padt(beta)
    if chunk_kernel_eligible(k, state, chunk, interpret):
        _count_dispatch("chunk_kernel")
        kernel = jax.custom_batching.custom_vmap(
            functools.partial(kda_chunk_call, chunk=chunk, interpret=interpret)
        )
        kernel.def_vmap(functools.partial(_kda_chunk_lanes, chunk=chunk))
        o, state = kernel(q, k, v, g, beta, state, t if n_valid is None else n_valid)
    else:
        o, state = _kda_chunked_xla(q, k, v, g, beta, state, chunk)
    return o[:, :t], state


def _kda_step_kernel(active_ref, rank_ref, cols_ref, rows_ref, s_ref, y_ref, o_ref):
    """One slot's block of ``hb`` lane groups, each ``P`` heads side by side
    on the lanes (``P`` 1: a group is a head): ``s_ref`` / ``o_ref (hb, Dk, P
    Dv)`` the same rows of the pool; ``cols_ref (Dk, 3 hb P)`` the heads'
    ``alpha``, ``k`` and ``q`` with the key channels on sublanes as in S;
    ``rows_ref (2 hb, P Dv)`` the groups' ``beta v`` and ``beta`` along the
    lanes. ``y_ref (hb, P Dv)``. The two reductions run over SUBLANES (vector
    adds and one fold), float32 throughout: a dot would round S to bf16. A
    lane's column sum is its own head's whatever shares the tile, so a group
    of ``P > 1`` differs only in its columns: each lane takes its head's
    (:func:`column`)."""
    del rank_ref
    slot = pl.program_id(0)
    hb = s_ref.shape[0]
    pack = cols_ref.shape[1] // (3 * hb)
    keep = active_ref[slot] != 0
    cols, rows = cols_ref[...], rows_ref[...]
    if pack > 1:
        dv = s_ref.shape[2] // pack
        lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape[1:], 1)

    def column(part, h):
        """Group ``h``'s ``alpha`` (``part`` 0), ``k`` (1) or ``q`` (2)."""
        first = (part * hb + h) * pack
        col = cols[:, first : first + 1]
        for j in range(1, pack):
            col = jnp.where(lane >= j * dv, cols[:, first + j : first + j + 1], col)
        return col

    for h in range(hb):
        old = s_ref[h]
        k_c = column(1, h)
        scaled = column(0, h) * old
        r = jnp.sum(scaled * k_c, axis=0, keepdims=True)  # k^T S' (1, P Dv)
        new = scaled + k_c * (rows[h : h + 1] - rows[hb + h : hb + h + 1] * r)
        y_ref[h : h + 1, :] = jnp.sum(new * column(2, h), axis=0, keepdims=True)
        o_ref[h] = jnp.where(keep, new, old)


def _head_block(heads: int, head_bytes: int) -> int:
    """Heads of one grid step: the most that divide ``heads`` and keep the
    block of S within ``_STEP_BLOCK_BYTES``."""
    return max(
        (hb for hb in range(1, heads + 1)
         if heads % hb == 0 and hb * head_bytes <= _STEP_BLOCK_BYTES),
        default=1,
    )


def lane_pack(heads: int, dv: int) -> int:
    """How many heads the state pool keeps side by side on the lane axis:
    the fewest that divide ``heads`` and make ``P dv`` whole 128-lane tiles
    (1 where ``dv`` already is, or where no such count exists). A TPU tiles
    an array over its two minor dimensions, so a ``(Dk, 192)`` head tile
    would lie in HBM padded to 256 lanes, a third more bytes every step; two
    such heads side by side are 384 lanes, three whole tiles."""
    return next(
        (p for p in range(1, heads + 1) if heads % p == 0 and (p * dv) % 128 == 0), 1
    )


def pack_heads(s, pack: int):
    """``(…, H, Dk, Dv)`` as the pool keeps it, ``(…, H / P, Dk, P Dv)``."""
    if pack == 1:
        return s
    *lead, h, dk, dv = s.shape
    with jax.named_scope("mst.state_pool.regroup"):
        s = s.reshape(*lead, h // pack, pack, dk, dv)
        return jnp.swapaxes(s, -3, -2).reshape(*lead, h // pack, dk, pack * dv)


def unpack_heads(s, pack: int):
    """:func:`pack_heads` undone: the pool's rows as ``(…, H, Dk, Dv)``."""
    if pack == 1:
        return s
    *lead, groups, dk, width = s.shape
    with jax.named_scope("mst.state_pool.regroup"):
        s = s.reshape(*lead, groups, dk, pack, width // pack)
        return jnp.swapaxes(s, -3, -2).reshape(*lead, groups * pack, dk, width // pack)


@functools.partial(jax.jit, static_argnames="interpret")
def kda_pool_step(pool, rank, q, k, v, g, beta, active=None, *,
                  interpret: bool = False):
    """The one-step recurrence on the state pool where it lies, ONE pass over
    the layer's rows of S: ``pool (L, rows, H / P, Dk, P Dv)`` float32, ``P``
    heads side by side on the lanes (:func:`lane_pack`; ``P`` 1 is ``(L,
    rows, H, Dk, Dv)``), ``rank`` the layer's row of it (may be traced), ``q``
    / ``k`` / ``g (B, H, Dk)``, ``v (B, H, Dv)``, ``Dk`` and ``Dv`` each their
    own, ``beta (B, H)``, ``active (B,)`` or None. The pool is
    aliased to the result: rows past ``B`` (an engine's scratch row) and the
    other layers' rows are never moved. Returns ``(o (B, H, Dv), pool)``.
    Jitted for the reason ``ops.mamba2.ssm_pool_step`` is: the body, unrolled
    over a block's heads, is traced once for all the layers that call it."""
    _, _, groups, dk, width = pool.shape
    b, nh = q.shape[:2]
    pack = nh // groups  # heads a lane group
    dv = width // pack
    hb = _head_block(groups, dk * width * 4)
    blocks = groups // hb
    f32 = jnp.float32
    # key channels on sublanes as in S, a block's heads side by side
    col = lambda z: jnp.swapaxes(  # noqa: E731
        z.astype(f32).reshape(b, blocks, hb * pack, dk), 2, 3
    )
    cols = jnp.concatenate([col(jnp.exp(g)), col(k), col(q)], axis=-1)
    beta = beta.astype(f32)[..., None]
    rows = jnp.concatenate([
        (beta * v).reshape(b, blocks, hb, width),
        jnp.broadcast_to(beta, (b, nh, dv)).reshape(b, blocks, hb, width),
    ], axis=2)
    if active is None:
        active = jnp.ones((b,), jnp.int32)
    state_spec = pl.BlockSpec(
        (None, None, hb, dk, width), lambda i, j, a, r: (r[0], i, j, 0, 0)
    )
    small = lambda *shape: pl.BlockSpec(  # noqa: E731
        (None, None, *shape), lambda i, j, *_: (i, j, 0, 0)
    )
    y, pool = pl.pallas_call(
        _kda_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, blocks),
            in_specs=[small(dk, 3 * hb * pack), small(2 * hb, width), state_spec],
            out_specs=[small(hb, width), state_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, blocks, hb, width), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the scalar-prefetch ones: the pool is the fifth
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # S in and out, double-buffered, and room for the small operands
            vmem_limit_bytes=4 * hb * dk * width * 4 + (8 << 20),
        ),
        interpret=interpret,
        name="kda_pool_step",
    )(
        active.astype(jnp.int32), jnp.asarray(rank, jnp.int32).reshape(1),
        cols, rows, pool,
    )
    return y.reshape(b, nh, dv), pool


def step_kernel_eligible(pool, interpret: bool) -> bool:
    """:func:`kda_pool_step` on a TPU backend (in interpret mode on any) for
    a float32 pool whose minor dimensions ``(Dk, P Dv)`` — a head's tile, or
    ``P`` heads' side by side (:func:`lane_pack`) — are whole sublane and
    lane tiles; the array operations otherwise."""
    if interpret:
        return True
    return (
        jax.default_backend() == "tpu"
        and pool.dtype == jnp.float32
        and pool.shape[-2] % 8 == 0
        and pool.shape[-1] % 128 == 0
    )


def _kda_step_xla(pool, rank, q, k, v, g, beta, active):
    """:func:`kda_pool_step`'s step as array operations, same arguments."""
    _count_dispatch("xla")
    pack = q.shape[1] // pool.shape[2]
    old = unpack_heads(take_rows(pool, rank, q.shape[0]), pack)
    with jax.named_scope("mst.kda.step"):
        scaled = jnp.exp(g)[..., None] * old
        # elementwise, not a dot: a TPU dot would round S to bf16
        r = (scaled * k[..., None]).sum(-2)
        new = scaled + (beta[..., None] * k)[..., None] * (v - r)[..., None, :]
        o = (new * q[..., None]).sum(-2)
        # the select stands INSIDE the scope: it is the root of the fusion
        # that updates the state, and a fusion's time is its root's scope's
        new = keep_inactive(active, new, old)
    return o, put_rows(pool, rank, pack_heads(new, pack))


def _kda_step_lanes(axis_size, in_batched, *args):
    """The kernel's call under ``jax.vmap`` (an engine's vectorized decode
    step, one sequence a lane with its own rows of the pool): the lanes take
    the array operations, as ``ops.mamba2._ssm_step_lanes`` says why."""
    in_axes = jax.tree.map(lambda batched: 0 if batched else None, in_batched)
    return jax.vmap(_kda_step_xla, in_axes=in_axes)(*args), (True, True)


def kda_step(pool, rank, q, k, v, g, beta, active, interpret: bool = False):
    """A decode step's recurrence on the pool ``(L, rows, H / P, Dk, P Dv)``
    by the path the operands allow: ``(o (B, H, Dv), pool)``. ``g (B, H)``,
    one decay a head, is broadcast over the head's key channels."""
    if g.ndim == beta.ndim:
        g = jnp.broadcast_to(g[..., None], k.shape)
    if not step_kernel_eligible(pool, interpret):
        return _kda_step_xla(pool, rank, q, k, v, g, beta, active)
    _count_dispatch("kernel")
    kernel = jax.custom_batching.custom_vmap(
        functools.partial(kda_pool_step, interpret=interpret)
    )
    kernel.def_vmap(_kda_step_lanes)
    with jax.named_scope("mst.kda.step"):
        return kernel(pool, rank, q, k, v, g, beta, active)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _conv_silu(qkv, tail, conv_w, taps: int, n_valid):
    """``silu`` of the causal depthwise convolution over ``[the last taps - 1
    inputs, this call's]`` (``conv_w (C, taps)``, float32 sums), and the next
    call's tail: the inputs that end at the last valid row."""
    t = qkv.shape[1]
    tail = tail.astype(qkv.dtype)
    seq = jnp.concatenate([tail, qkv], axis=1)
    w = conv_w.astype(jnp.float32)
    conv = sum(seq[:, j : j + t].astype(jnp.float32) * w[:, j] for j in range(taps))
    end = t if n_valid is None else n_valid
    return jax.nn.silu(conv), tail, jax.lax.dynamic_slice_in_dim(seq, end, taps - 1, axis=1)


def _advance(pool, rank, q, k, v, g, beta, tail, new_tail, n_valid, active,
             chunk: int, interpret: bool):
    """The recurrence over this call's rows by the form its length asks for:
    a decode step (``T == 1``) updates the pool where it lies
    (:func:`kda_step`), a chunk slices the layer's rows out (heads apart,
    whatever the pool's :func:`lane_pack`), runs :func:`kda_chunked` and
    writes them back. Rows past ``n_valid`` and
    sequences outside ``active`` advance neither the state nor the tail.
    Returns ``(o (B, T, H, Dv), pool, new_tail)``."""
    b, t = q.shape[:2]
    if t == 1:
        o, pool = kda_step(
            pool, rank, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], active,
            interpret,
        )
        o = o[:, None]
    else:
        pack = q.shape[2] // pool.shape[2]  # heads a lane group of the pool
        old = unpack_heads(take_rows(pool, rank, b), pack)
        with jax.named_scope("mst.kda.scan"):
            if n_valid is not None:
                live = (jnp.arange(t) < n_valid)[None, :, None]
                g = jnp.where(live if g.ndim == beta.ndim else live[..., None], g, 0.0)
                beta = jnp.where(live, beta, 0.0)
            o, s = kda_chunked(q, k, v, g, beta, old, chunk, n_valid, interpret)
            s = keep_inactive(active, s, old)  # inside the scope, as the step's
        pool = put_rows(pool, rank, pack_heads(s, pack))
    with jax.named_scope("mst.kda.step" if t == 1 else "mst.kda.scan"):
        new_tail = keep_inactive(active, new_tail, tail)
    return o, pool, new_tail


def _head_rms(o, eps: float):
    return o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)


def kda_mixer(
    linear, p, u, pool, rank, tail, n_valid, active, *,
    heads: int, head_dim: int, taps: int, eps: float, chunk: int = CHUNK,
    interpret: bool = False,
):
    """One KDA mixer. ``linear(x, w)``: the model's projection; ``p``: the
    layer's ``qkv_proj`` (hidden to ``[q, k, v]``, ``3 H D``), ``conv_w (3 H
    D, taps)``, ``gate_a`` (hidden to the two low-rank gates' inner widths,
    the decay's then the output's), ``f_b`` / ``g_b`` (inner to ``H D``),
    ``b_proj`` (hidden to ``H``), ``A_log (H,)``, ``dt_bias (H D,)``,
    ``o_norm (D,)``, ``o_proj``; ``u (B, T, hidden)`` the normed input;
    ``pool (L, rows, H, D, D)`` float32 every layer's state, this layer's at
    ``rank`` (may be traced), these ``B`` sequences' in its first ``B`` rows;
    ``tail (B, taps - 1, 3 H D)`` the convolution's last inputs. Rows past
    ``n_valid`` and sequences outside ``active`` advance neither
    (:func:`_advance`). Returns ``(out (B, T, hidden), pool, tail)``."""
    b, t, _ = u.shape
    nh, d = heads, head_dim
    width = nh * d
    f32 = jnp.float32
    with jax.named_scope("mst.kda.proj"):
        qkv = linear(u, p["qkv_proj"])
    with jax.named_scope("mst.kda.conv"):
        qkv_a, tail, new_tail = _conv_silu(qkv, tail, p["conv_w"], taps, n_valid)
    with jax.named_scope("mst.kda.gate"):
        heads_of = lambda z: z.reshape(b, t, nh, d)  # noqa: E731
        q = _l2norm(heads_of(qkv_a[..., :width])) * d**-0.5
        k = _l2norm(heads_of(qkv_a[..., width : 2 * width]))
        v = heads_of(qkv_a[..., 2 * width :])
        inner = linear(u, p["gate_a"])
        half = inner.shape[-1] // 2
        g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            heads_of(linear(inner[..., :half], p["f_b"]).astype(f32)
                     + p["dt_bias"].astype(f32))
        )
        beta = jax.nn.sigmoid(linear(u, p["b_proj"]).astype(f32))
        out_gate = jax.nn.sigmoid(linear(inner[..., half:], p["g_b"]).astype(f32))
    o, pool, new_tail = _advance(
        pool, rank, q, k, v, g, beta, tail, new_tail, n_valid, active, chunk, interpret
    )
    with jax.named_scope("mst.kda.out"):
        y = _head_rms(o, eps) * p["o_norm"].astype(f32) * heads_of(out_gate)
        out = linear(y.reshape(b, t, width).astype(u.dtype), p["o_proj"])
    return out, pool, new_tail


def gdn_mixer(
    linear, p, u, pool, rank, tail, n_valid, active, *,
    key_heads: int, value_heads: int, head_dim: int, taps: int, eps: float,
    value_dim: int | None = None, beta_scale: float = 1.0,
    chunk: int = CHUNK, interpret: bool = False,
):
    """One Gated DeltaNet mixer: the recurrence of :func:`kda_mixer` under one
    decay a head (the module docstring's scalar-decay case). Keys are
    ``head_dim`` wide (``Dk``), values ``value_dim`` (``Dv``; None: ``Dk``);
    ``beta = beta_scale * sigmoid(b)``: at 2 the step's ``I - beta k k^T`` has
    an eigenvalue in ``(-1, 1)`` where 1 keeps it in ``(0, 1)``. ``p``: the
    layer's ``qkvz_proj`` (hidden to ``[q, k, v, z]``: ``Hk Dk``, ``Hk Dk``,
    ``Hv Dv`` and the output gate's ``Hv Dv``), ``conv_w (2 Hk Dk + Hv Dv,
    taps)`` over ``[q, k, v]`` joined, ``ba_proj`` (hidden to ``[b, a]``,
    ``Hv`` each), ``A_log`` / ``dt_bias (Hv,)``, ``o_norm (Dv,)`` (a plain
    weight), ``o_proj``; ``pool (L, rows, Hv / P, Dk, P Dv)`` (:func:`lane_pack`
    heads side by side; ``P`` 1: ``(L, rows, Hv, Dk, Dv)``); ``tail (B, taps -
    1, 2 Hk Dk + Hv Dv)``. Everything else as there. Returns ``(out (B, T,
    hidden), pool, tail)``."""
    b, t, _ = u.shape
    d, f32 = head_dim, jnp.float32
    dv = d if value_dim is None else value_dim
    kw, vw = key_heads * d, value_heads * dv
    with jax.named_scope("mst.kda.proj"):
        qkvz = linear(u, p["qkvz_proj"])
        qkv, z = qkvz[..., : 2 * kw + vw], qkvz[..., 2 * kw + vw :]
    with jax.named_scope("mst.kda.conv"):
        qkv_a, tail, new_tail = _conv_silu(qkv, tail, p["conv_w"], taps, n_valid)
    with jax.named_scope("mst.kda.gate"):
        # normed as key heads, then value head j reads key head j // (Hv / Hk)
        spread = lambda x: jnp.repeat(  # noqa: E731
            _l2norm(x.reshape(b, t, key_heads, d)), value_heads // key_heads, axis=2
        )
        q = spread(qkv_a[..., :kw]) * d**-0.5
        k = spread(qkv_a[..., kw : 2 * kw])
        v = qkv_a[..., 2 * kw :].reshape(b, t, value_heads, dv)
        ba = linear(u, p["ba_proj"]).astype(f32)
        beta = jax.nn.sigmoid(ba[..., :value_heads])
        if beta_scale != 1.0:
            beta = beta_scale * beta
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., value_heads:] + p["dt_bias"].astype(f32)
        )
    o, pool, new_tail = _advance(
        pool, rank, q, k, v, g, beta, tail, new_tail, n_valid, active, chunk, interpret
    )
    with jax.named_scope("mst.kda.out"):
        y = _head_rms(o, eps) * p["o_norm"].astype(f32) * jax.nn.silu(
            z.astype(f32)
        ).reshape(b, t, value_heads, dv)
        out = linear(y.reshape(b, t, vw).astype(u.dtype), p["o_proj"])
    return out, pool, new_tail
