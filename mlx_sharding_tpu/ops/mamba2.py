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

A family differs in sizes only: heads, head width, groups, state, taps and
chunk (nemotron3: 128, 64, 8, 128, 4, 128; granite-4.0-h: 64, 64, 1, 128, 4,
256), and in where it keeps the two pieces of state.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


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


def mamba2_mixer(
    linear, p, u, ssm, tail, n_valid, active, *,
    heads: int, head_dim: int, groups: int, state: int, taps: int,
    chunk: int, eps: float,
):
    """One Mamba-2 mixer. ``linear(x, w)``: the model's projection (dense or
    packed); ``p``: the layer's ``in_proj`` (to ``[z, xBC, dt]``, or to ``[z, xBC]``
    beside a ``dt_proj``), ``conv_w (C, taps)``, ``conv_b``,
    ``dt_bias``, ``A_log``, ``D``, ``ssm_norm``, ``out_proj``; ``u (B, T,
    hidden)`` the normed input; ``ssm (B, H, P, N)`` float32 and ``tail (B,
    taps - 1, C)`` the layer's state of these ``B`` sequences. Rows past
    ``n_valid`` and sequences outside ``active`` do not advance it. Returns
    ``(out (B, T, hidden), ssm, tail)``, the tail in the dtype of ``xBC``."""
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
        rep = nh // g
        b_mat = jnp.repeat(xbc_a[..., di : di + g * n].reshape(b, t, g, n), rep, axis=2)
        c_mat = jnp.repeat(xbc_a[..., di + g * n :].reshape(b, t, g, n), rep, axis=2)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
        a_head = -jnp.exp(p["A_log"].astype(jnp.float32))

    def frozen(s, new_tail):
        """An inactive sequence keeps what it had. Called INSIDE the
        scope: this select is the root of the fusion that updates the
        state, and a fusion's time is its root's scope's."""
        if active is None:
            return s, new_tail
        keep = lambda new, old: jnp.where(  # noqa: E731
            active.reshape(-1, *([1] * (new.ndim - 1))), new, old.astype(new.dtype)
        )
        return keep(s, ssm), keep(new_tail, tail)

    if t == 1:
        with jax.named_scope("mst.ssm.step"):
            dt1, x1 = dt[:, 0], x[:, 0]
            s = jnp.exp(dt1 * a_head)[..., None, None] * ssm + (
                (dt1[..., None] * x1)[..., None] * b_mat[:, 0][..., None, :]
            )
            # elementwise, not a dot: a TPU dot would round S to bf16
            y = (s * c_mat[:, 0][..., None, :]).sum(-1)[:, None]
            s, new_tail = frozen(s, new_tail)
    else:
        with jax.named_scope("mst.ssm.scan"):
            if n_valid is not None:
                dt = jnp.where((jnp.arange(t) < n_valid)[None, :, None], dt, 0.0)
            y, s = ssd_chunked(x, dt, a_head, b_mat, c_mat, ssm, chunk)
            s, new_tail = frozen(s, new_tail)
    with jax.named_scope("mst.ssm.out_proj"):
        y = y + p["D"].astype(jnp.float32)[:, None] * x
        y = y.reshape(b, t, di) * jax.nn.silu(z.astype(jnp.float32))
        yg = y.reshape(b, t, g, di // g)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(jnp.square(yg), axis=-1, keepdims=True) + eps
        )
        y = yg.reshape(b, t, di) * p["ssm_norm"].astype(jnp.float32)
        out = linear(y.astype(u.dtype), p["out_proj"])
    return out, s, new_tail
