"""Mamba-2 (arXiv 2405.21060), the one implementation every family with
such layers calls (``models/nemotron_h.py``, ``models/granitemoehybrid.py``).

``[z, xBC, dt] = in_proj(u)``; ``xBC = silu(causal depthwise conv1d(xBC) +
bias)`` split into ``x (H, P)``, ``B (G, N)``, ``C (G, N)`` (head ``h`` reads
group ``h // (H / G)``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``;
``y = groupwise_rmsnorm(y * silu(z)) * norm_weight``; ``out_proj``. A prompt
chunk runs the chunked (SSD) form in float32 matrix products
(:func:`ssd_chunked`), a decode step the one-step recurrence; both give the
sequential recurrence's numbers (:func:`ssm_sequential`). Per sequence a layer
keeps the SSM state ``(H, P, N)`` in float32 and the last ``taps - 1`` inputs
of its convolution in the activation dtype.

The SSM state of every layer and sequence is ONE pool ``(layers, rows, H, P,
N)`` that the mixer is handed whole with the layer's rank. A decode step is
bound by the bytes of S it moves (2 MB a sequence a layer at granite's sizes,
100 MB a layer at 48 slots), so on a TPU it makes ONE pass over the layer's
rows where they lie (:func:`ssm_pool_step`, a Pallas kernel: read S, ``S' =
exp(dt A) S + (dt x) (x) B``, ``y = S' C`` from the ``S'`` still in VMEM, an
inactive slot's S kept, write ``S'`` over what was read; the pool aliased to
the result, so nothing else of it moves). Written as array operations the same
step compiles to a slice, a fusion that writes ``S'`` and a second one that
reads it again for ``y``: three passes where two are needed. That form stays
for a backend without the kernel and for the lanes of a ``jax.vmap``, and a
chunk, which reads and writes S once however long it is, slices the layer's
rows out and writes them back (:func:`take_rows`, :func:`put_rows`).

A family differs in sizes only: heads, head width, groups, state, taps and
chunk (nemotron3: 128, 64, 8, 128, 4, 128; granite-4.0-h: 64, 64, 1, 128, 4,
256), and in how it lays out the convolution's tails, which it slices and
writes itself (1.2 MB a layer).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mlx_sharding_tpu.ops.dispatch import DispatchCounter

_HI = jax.lax.Precision.HIGHEST

# Which path a decode step's recurrence took, once per traced call
# (ops/dispatch.py). /metrics shows it as ``mst_ssm_dispatch_total{path}``:
# "xla" above 0 on a chip says some layer slices its rows of S out of the
# pool, passes over them twice and writes them back (a call that chose the
# kernel and is then batched by ``jax.vmap`` counts under both).
_DISPATCHED = DispatchCounter("kernel", "xla")
dispatch_counts = _DISPATCHED.counts
_count_dispatch = _DISPATCHED.count

#: bytes of S one grid step of :func:`ssm_pool_step` moves each way, at most
_STEP_BLOCK_BYTES = 2 << 20


def ssd_chunked(x, dt, a_head, b_mat, c_mat, state, chunk: int):
    """Mamba-2's recurrence over a whole chunk of positions as matrix
    products (the SSD form), float32. ``x (B,T,H,P)``, ``dt (B,T,H)`` (0 at a
    row that must not advance the state), ``a_head (H,)`` negative, ``b_mat``
    / ``c_mat (B,T,H,N)`` already expanded from groups to heads, ``state
    (B,H,P,N)``. Returns ``(y (B,T,H,P) without the D term, state after the
    last row)``."""
    b, t, h, p = x.shape
    n = b_mat.shape[-1]
    pad = -t % chunk
    if pad:  # dt = 0 rows: decay 1, input 0 — the state passes through
        padt = lambda z: jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))  # noqa: E731
        x, dt, b_mat, c_mat = padt(x), padt(dt), padt(b_mat), padt(c_mat)
    nc = (t + pad) // chunk
    split = lambda z: z.reshape(b, nc, chunk, *z.shape[2:])  # noqa: E731
    x, dt, b_mat, c_mat = split(x), split(dt), split(b_mat), split(c_mat)
    acs = jnp.cumsum(dt * a_head, axis=2)  # (B,nc,Q,H) log-decay from chunk start
    # within a chunk: y_i += sum_{j<=i} exp(acs_i - acs_j) dt_j (C_i . B_j) x_j
    scores = jnp.einsum("bcqhn,bckhn->bchqk", c_mat, b_mat, precision=_HI)
    acs_h = jnp.moveaxis(acs, 3, 2)  # (B,nc,H,Q)
    seg = acs_h[..., :, None] - acs_h[..., None, :]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    w = scores * decay * jnp.moveaxis(dt, 3, 2)[..., None, :]
    y = jnp.einsum("bchqk,bckhp->bcqhp", w, x, precision=_HI)
    # what each chunk adds to the state at its own end
    to_end = jnp.exp(acs[:, :, -1:, :] - acs) * dt  # (B,nc,Q,H)
    add = jnp.einsum("bcqhn,bcqh,bcqhp->bchpn", b_mat, to_end, x, precision=_HI)
    total = jnp.exp(acs[:, :, -1, :])  # (B,nc,H) a chunk's whole decay

    def carry(s, xs):
        add_c, total_c = xs
        return total_c[..., None, None] * s + add_c, s  # ys: state at chunk start

    state, s_in = jax.lax.scan(
        carry, state, (jnp.moveaxis(add, 1, 0), jnp.moveaxis(total, 1, 0))
    )
    s_in = jnp.moveaxis(s_in, 0, 1)  # (B,nc,H,P,N)
    y = y + jnp.einsum(
        "bcqhn,bchpn,bcqh->bcqhp", c_mat, s_in, jnp.exp(acs), precision=_HI
    )
    return y.reshape(b, nc * chunk, h, p)[:, :t], state


def ssm_sequential(x, dt, a_head, b_mat, c_mat, state):
    """The recurrence one position at a time (``lax.scan``): what
    :func:`ssd_chunked` must equal. Same arguments, no chunk."""

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t * a_head)[..., None, None] * s + (
            (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :]
        )
        return s, (s * c_t[..., None, :]).sum(-1)

    t_first = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    state, y = jax.lax.scan(
        step, state, (t_first(x), t_first(dt), t_first(b_mat), t_first(c_mat))
    )
    return jnp.moveaxis(y, 0, 1), state


def _head_block(heads: int, per_group: int, head_bytes: int) -> int:
    """Heads of one grid step of :func:`ssm_pool_step`: the most that divide
    ``heads``, hold whole groups (or divide one) and keep the block of S
    within ``_STEP_BLOCK_BYTES`` — a step that moves little is all overhead."""
    fits = [
        hb for hb in range(1, heads + 1)
        if heads % hb == 0
        and (hb % per_group == 0 or per_group % hb == 0)
        and hb * head_bytes <= _STEP_BLOCK_BYTES
    ]
    return max(fits, default=1)


def _ssm_step_kernel(
    rank_ref, active_ref, decay_ref, dtx_ref, bc_ref, s_ref, y_ref, o_ref, *,
    heads: int, per_group: int,
):
    """One slot's block of ``hb`` heads: ``s_ref`` / ``o_ref (hb, P, N)`` the
    same rows of the pool, ``dtx_ref (P, hb)`` the heads' ``dt x`` with P on
    sublanes as in S, ``bc_ref (groups in the block, 2, N)`` B and C,
    ``decay_ref`` every slot's ``exp(dt A)`` flat in SMEM. ``y_ref (P, hb)``."""
    slot, j = pl.program_id(0), pl.program_id(1)
    hb, _, n = s_ref.shape
    keep = active_ref[slot] != 0
    first = slot * heads + j * hb  # of this block's heads in ``decay_ref``
    dtx = dtx_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
    ones = jnp.ones((n, dtx.shape[1]), jnp.float32)
    y = jnp.zeros_like(dtx)
    for h in range(hb):
        g = h // per_group
        old = s_ref[h]
        new = decay_ref[first + h] * old + dtx[:, h : h + 1] * bc_ref[g, 0:1, :]
        # S' C is a float32 product on the vector unit, as in the formula:
        # a dot of S' with C would round S' to bf16. Only the SUM over the
        # state runs through the matrix unit, against ones at HIGHEST: the
        # products' three bf16 pieces are exact, ones is exact, and they
        # add up in float32. A lane reduction instead costs a quarter of
        # the pass (chip, PR 45: 14.3 ms for granite's 36 layers, 11.5 so)
        y_h = jnp.dot(
            new * bc_ref[g, 1:2, :], ones, precision=_HI,
            preferred_element_type=jnp.float32,
        )
        y = jnp.where(lane == h, y_h, y)
        o_ref[h] = jnp.where(keep, new, old)
    y_ref[...] = y


@functools.partial(jax.jit, static_argnames="interpret")
def ssm_pool_step(pool, rank, dt, x, b_mat, c_mat, a_head, active=None, *,
                  interpret: bool = False):
    """The one-step recurrence on the state pool where it lies, ONE pass over
    the layer's rows of S: ``pool (L, rows, H, P, N)`` float32, ``rank`` the
    layer's row of it (may be traced), ``dt (B, H)``, ``x (B, H, P)``,
    ``b_mat`` / ``c_mat (B, G, N)`` NOT expanded to heads (a head block's
    index map picks its groups), ``a_head (H,)``, ``active (B,)`` or None. For
    each of the ``B`` sequences and each block of heads the kernel reads S,
    forms ``S' = exp(dt A) S + (dt x) (x) B`` and ``y = sum_n S' C`` from the
    ``S'`` still in VMEM, and writes ``S'`` (S as read where not ``active``)
    over what it read: the pool is aliased to the result, so rows past ``B``
    (an engine's scratch row) and the other layers' rows are never moved.
    All float32. Returns ``(y (B, H, P), pool)``. Jitted so that a process
    traces the kernel's body — unrolled over a block's heads, some 800
    operations — once for all the layers and programs that call it at the
    same shapes, not once a call: in a server with five unrolled layers a
    program that was a fifth of a warm start-up (chip, PR 45)."""
    _, _, nh, hp, n = pool.shape
    b, g = b_mat.shape[:2]
    per_group = nh // g
    hb = _head_block(nh, per_group, hp * n * 4)
    blocks, gb = nh // hb, max(hb // per_group, 1)
    f32 = jnp.float32
    decay = jnp.exp(dt * a_head).astype(f32)
    # P on sublanes as in S, a block's heads side by side on the lanes
    dtx = jnp.swapaxes((dt[..., None] * x).astype(f32).reshape(b, blocks, hb, hp), 2, 3)
    bc = jnp.stack([b_mat, c_mat], axis=2).astype(f32)  # (B, G, 2, N)
    if active is None:
        active = jnp.ones((b,), jnp.int32)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, blocks),
        in_specs=[
            pl.BlockSpec((None, None, hp, hb), lambda i, j, *_: (i, j, 0, 0)),
            pl.BlockSpec(
                (None, gb, 2, n),
                lambda i, j, *_: (i, j * hb // (per_group * gb), 0, 0),
            ),
            pl.BlockSpec(
                (None, None, hb, hp, n), lambda i, j, r, *_: (r[0], i, j, 0, 0)
            ),
        ],
        out_specs=[
            pl.BlockSpec((None, None, hp, hb), lambda i, j, *_: (i, j, 0, 0)),
            pl.BlockSpec(
                (None, None, hb, hp, n), lambda i, j, r, *_: (r[0], i, j, 0, 0)
            ),
        ],
    )
    y, pool = pl.pallas_call(
        functools.partial(
            _ssm_step_kernel, heads=nh, per_group=min(per_group, hb)
        ),
        grid_spec=spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, blocks, hp, hb), f32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the scalar-prefetch ones: the pool is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # S in and out, double-buffered, and room for the small operands
            vmem_limit_bytes=4 * hb * hp * n * 4 + (8 << 20),
        ),
        interpret=interpret,
        name="ssm_pool_step",
    )(
        jnp.asarray(rank, jnp.int32).reshape(1), active.astype(jnp.int32),
        decay.reshape(-1), dtx, bc, pool,
    )
    return jnp.swapaxes(y, 2, 3).reshape(b, nh, hp), pool


@jax.named_scope("mst.state_pool.regroup")
def take_rows(pool, rank, b: int):
    """The first ``b`` rows of layer ``rank`` of a state pool ``(L, rows, …)``."""
    at = (rank,) + (0,) * (pool.ndim - 1)
    return jax.lax.dynamic_slice(pool, at, (1, b, *pool.shape[2:]))[0]


@jax.named_scope("mst.state_pool.regroup")
def put_rows(pool, rank, new):
    """``new (b, …)`` over the first ``b`` rows of layer ``rank``, where the
    pool lies."""
    at = (rank,) + (0,) * (pool.ndim - 1)
    return jax.lax.dynamic_update_slice(pool, new[None].astype(pool.dtype), at)


def step_kernel_eligible(pool, interpret: bool) -> bool:
    """:func:`ssm_pool_step` on a TPU backend (in interpret mode on any, so
    that CPU tests run the kernel's own logic) for a float32 pool whose
    ``(P, N)`` head tiles are whole sublane and lane tiles; the XLA formula
    otherwise."""
    if interpret:
        return True
    return (
        jax.default_backend() == "tpu"
        and pool.dtype == jnp.float32
        and pool.shape[-2] % 8 == 0
        and pool.shape[-1] % 128 == 0
    )


def keep_inactive(active, new, old):
    """An inactive sequence keeps what it had."""
    if active is None:
        return new
    return jnp.where(
        active.reshape(-1, *([1] * (new.ndim - 1))), new, old.astype(new.dtype)
    )


def _expand(grp, heads: int):
    """B or C ``(…, G, N)`` from groups to heads."""
    with jax.named_scope("mst.ssm.conv"):
        return jnp.repeat(grp, heads // grp.shape[-2], axis=-2)


def _ssm_step_xla(pool, rank, dt, x, b_grp, c_grp, a_head, active):
    """:func:`ssm_pool_step`'s step as array operations, same arguments: the
    layer's rows sliced out, a fusion that writes ``S'``, a second one that
    reads it again for ``y``, the rows written back."""
    _count_dispatch("xla")
    b, nh = dt.shape
    ssm = take_rows(pool, rank, b)
    b_mat, c_mat = _expand(b_grp, nh), _expand(c_grp, nh)
    with jax.named_scope("mst.ssm.step"):
        s = jnp.exp(dt * a_head)[..., None, None] * ssm + (
            (dt[..., None] * x)[..., None] * b_mat[..., None, :]
        )
        # elementwise, not a dot: a TPU dot would round S to bf16
        y = (s * c_mat[..., None, :]).sum(-1)
        # the select stands INSIDE the scope: it is the root of the fusion
        # that updates the state, and a fusion's time is its root's scope's
        s = keep_inactive(active, s, ssm)
    return y, put_rows(pool, rank, s)


def _ssm_step_lanes(axis_size, in_batched, *args):
    """The kernel's call under ``jax.vmap`` (an engine's vectorized decode
    step: one sequence a lane, each with a copy of its own rows of the pool):
    pallas would loop over the lanes and copy every lane's whole pool in and
    out of each call, so the lanes take the formula, which batches as array
    operations."""
    in_axes = jax.tree.map(lambda batched: 0 if batched else None, in_batched)
    return jax.vmap(_ssm_step_xla, in_axes=in_axes)(*args), (True, True)


def _ssm_step(pool, rank, dt, x, b_grp, c_grp, a_head, active, interpret: bool):
    """A decode step's recurrence on the pool by the path the operands allow:
    ``(y (B, H, P), pool)``."""
    if not step_kernel_eligible(pool, interpret):
        return _ssm_step_xla(pool, rank, dt, x, b_grp, c_grp, a_head, active)
    _count_dispatch("kernel")
    kernel = jax.custom_batching.custom_vmap(
        functools.partial(ssm_pool_step, interpret=interpret)
    )
    kernel.def_vmap(_ssm_step_lanes)
    with jax.named_scope("mst.ssm.step"):
        return kernel(pool, rank, dt, x, b_grp, c_grp, a_head, active)


def mamba2_mixer(
    linear, p, u, pool, rank, tail, n_valid, active, *,
    heads: int, head_dim: int, groups: int, state: int, taps: int,
    chunk: int, eps: float, interpret: bool = False,
):
    """One Mamba-2 mixer. ``linear(x, w)``: the model's projection (dense or
    packed); ``p``: the layer's ``in_proj`` (to ``[z, xBC, dt]``, or to ``[z, xBC]``
    beside a ``dt_proj``), ``conv_w (C, taps)``, ``conv_b``,
    ``dt_bias``, ``A_log``, ``D``, ``ssm_norm``, ``out_proj``; ``u (B, T,
    hidden)`` the normed input; ``pool (L, rows, H, P, N)`` float32 every
    layer's SSM state, this layer's at ``rank`` (may be traced), these ``B``
    sequences' in its first ``B`` rows (rows past them, an engine's scratch
    row, are neither read nor written); ``tail (B, taps - 1, C)`` the
    layer's convolution tails of the ``B`` sequences. Rows past ``n_valid``
    and sequences outside ``active`` do not advance either. A decode step
    (``T == 1``) updates the pool where it lies (:func:`ssm_pool_step`,
    ``interpret`` for its tests off the chip); a chunk, and a decode step
    off the chip or under ``jax.vmap``, slices the layer's rows out and
    writes them back. Returns
    ``(out (B, T, hidden), pool, tail)``, the tail in the dtype of ``xBC``."""
    b, t, _ = u.shape
    nh, hp, g, n, k = heads, head_dim, groups, state, taps
    di = nh * hp
    conv_dim = di + 2 * g * n
    with jax.named_scope("mst.ssm.in_proj"):
        zxd = linear(u, p["in_proj"])
        z, xbc, dt = jnp.split(zxd, [di, di + conv_dim], axis=-1)
        if not dt.shape[-1]:
            # a family whose whole in_proj is no multiple of 128 wide keeps
            # the dt columns apart (models/granitemoehybrid.py says why)
            dt = linear(u, p["dt_proj"])
    with jax.named_scope("mst.ssm.conv"):
        # causal depthwise conv over [the last k-1 inputs, this call's]
        tail = tail.astype(xbc.dtype)  # (B,k-1,C)
        seq = jnp.concatenate([tail, xbc], axis=1).astype(jnp.float32)
        w = p["conv_w"].astype(jnp.float32)  # (C, k)
        conv = sum(seq[:, j : j + t] * w[:, j] for j in range(k))
        xbc_a = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
        # the next call's tail: the k-1 inputs that end at the last valid row
        end = t if n_valid is None else n_valid
        new_tail = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([tail, xbc], axis=1), end, k - 1, axis=1
        )
        x = xbc_a[..., :di].reshape(b, t, nh, hp)
        b_grp = xbc_a[..., di : di + g * n].reshape(b, t, g, n)
        c_grp = xbc_a[..., di + g * n :].reshape(b, t, g, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a_head = -jnp.exp(p["A_log"].astype(jnp.float32))

    if t == 1:
        y, pool = _ssm_step(
            pool, rank, dt[:, 0], x[:, 0], b_grp[:, 0], c_grp[:, 0], a_head,
            active, interpret,
        )
        y = y[:, None]
    else:
        ssm = take_rows(pool, rank, b)
        b_mat, c_mat = _expand(b_grp, nh), _expand(c_grp, nh)
        with jax.named_scope("mst.ssm.scan"):
            if n_valid is not None:
                dt = jnp.where((jnp.arange(t) < n_valid)[None, :, None], dt, 0.0)
            y, s = ssd_chunked(x, dt, a_head, b_mat, c_mat, ssm, chunk)
            s = keep_inactive(active, s, ssm)  # inside the scope, as the step's
        pool = put_rows(pool, rank, s)
    with jax.named_scope("mst.ssm.step" if t == 1 else "mst.ssm.scan"):
        new_tail = keep_inactive(active, new_tail, tail)
    with jax.named_scope("mst.ssm.out_proj"):
        y = y + p["D"].astype(jnp.float32)[:, None] * x
        y = y.reshape(b, t, di) * jax.nn.silu(z.astype(jnp.float32))
        yg = y.reshape(b, t, g, di // g)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + eps
        )
        y = yg.reshape(b, t, di) * p["ssm_norm"].astype(jnp.float32)
        out = linear(y.astype(u.dtype), p["out_proj"])
    return out, pool, new_tail
