#!/usr/bin/env python3
"""The chunked gated delta rule alone (``ops/kda.kda_chunked``), on the chip,
at the three delta-rule cells' shapes (ISSUE 61): one 512-row prefill chunk
of one sequence through the cell's linear layers (a ``lax.scan`` over layers
with operands of their own, as the served chunk program walks them), at
``n_valid`` 256 and 512.

Per shape, milliseconds a CHUNK (all the cell's linear layers) of

- ``xla.pairwise``  the array form up to the pairwise sums ``P(k)``, ``P(q)``;
- ``xla.local``     up to the block scan's operands (the sums, the unit lower
                    triangular solve, ``exp(G) q`` and the rest): the solve and
                    what is beside it are ``local - pairwise``;
- ``xla.whole``     the array form whole (``scan = whole - local``): what a
                    chunk ran up to PR 60 and what a backend without the
                    kernel still runs;
- ``kernel``        ``kda_chunk_call``, the one Pallas pass, with the layout
                    changes around it;

each whole form checked against ``kda_sequential`` on the first layer (the
largest absolute difference of the valid rows of ``o`` and of the state,
over the reference's largest magnitude); and a ``dispatch`` line a shape: the
paths ``ops.kda.kda_chunked`` itself counted for one traced call here
(``chunk_kernel`` 1 and ``chunk_xla`` 0 on a chip).

    python scripts/kda_chunk_probe.py --out chiprun_out/pr61/probe.jsonl
    JAX_PLATFORMS=cpu python scripts/kda_chunk_probe.py --tiny   # rehearsal

Produces no benchmark metric: a probe for PERF.md section 6.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from mlx_sharding_tpu.ops import kda  # noqa: E402

# cell -> the recurrence's shape in its linear layers (benchmarks/configs/)
CELLS = {
    "olmo-hybrid-7b": dict(layers=12, heads=30, dk=96, dv=192, per_head=True, beta_scale=2.0),
    "qwen3-next-80b": dict(layers=9, heads=32, dk=128, dv=128, per_head=True, beta_scale=1.0),
    "kimi-linear-48b": dict(layers=20, heads=32, dk=128, dv=128, per_head=False, beta_scale=1.0),
}
TINY = {
    "tiny-scalar": dict(layers=2, heads=3, dk=24, dv=48, per_head=True, beta_scale=2.0, tiny=True),
    "tiny-channel": dict(layers=2, heads=2, dk=16, dv=16, per_head=False, beta_scale=1.0, tiny=True),
}


def operands(cell, rows, n_valid, seed):
    """One chunk's operands for every layer, ``(layers, 1, rows, H, …)``, as
    ``ops.kda._advance`` hands them on: ``g`` and ``beta`` 0 past ``n_valid``."""
    shape = (cell["layers"], 1, rows, cell["heads"])
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = kda._l2norm(jax.random.normal(keys[0], (*shape, cell["dk"]))) * cell["dk"] ** -0.5
    k = kda._l2norm(jax.random.normal(keys[1], (*shape, cell["dk"])))
    v = jax.random.normal(keys[2], (*shape, cell["dv"]))
    # exp(A_log) in (0, 16) a head, the step dt log-uniform in (0.001, 0.1)
    rate = jax.random.uniform(keys[3], (shape[0], 1, 1, shape[3]), maxval=16.0)
    g_shape = shape if cell["per_head"] else (*shape, cell["dk"])
    dt = jnp.exp(jax.random.uniform(
        keys[4], g_shape, minval=np.log(1e-3), maxval=np.log(1e-1)))
    g = -(rate if cell["per_head"] else rate[..., None]) * dt
    beta = cell["beta_scale"] * jax.nn.sigmoid(jax.random.normal(keys[5], shape))
    live = (jnp.arange(rows) < n_valid)[None, None, :, None]
    g = jnp.where(live if cell["per_head"] else live[..., None], g, 0.0)
    beta = jnp.where(live, beta, 0.0)
    state = 0.1 * jax.random.normal(
        keys[6], (shape[0], 1, cell["heads"], cell["dk"], cell["dv"]))
    return q, k, v, g, beta, state


def forms(chunk, n_valid, interpret, head_caps=()):
    """name -> what one layer runs, ``(q, k, v, g, beta, state) -> arrays``.
    ``head_caps``: the kernel again under other bounds on the heads of a
    grid step (``kernel.hb<cap>``: ``ops.kda._CHUNK_HEADS`` swapped while
    the form is traced, the call's own ``jit`` passed by)."""
    split = functools.partial(kda._split_blocks, chunk=chunk)

    def pairwise(q, k, v, g, beta, state):
        return kda._chunk_pairwise_xla(split(q), split(k), jnp.cumsum(split(g), axis=3))

    def local(q, k, v, g, beta, state):
        return kda._chunk_local_xla(
            split(q), split(k), split(v), split(g), split(beta[..., None]))

    def whole(q, k, v, g, beta, state):
        return kda._kda_chunked_xla(q, k, v, g, beta, state, chunk)

    def kernel(q, k, v, g, beta, state):
        return kda.kda_chunk_call(
            q, k, v, g, beta, state, n_valid, chunk=chunk, interpret=interpret)

    def capped(cap):
        def form(q, k, v, g, beta, state):
            was, kda._CHUNK_HEADS = kda._CHUNK_HEADS, cap
            try:
                return kda.kda_chunk_call.__wrapped__(
                    q, k, v, g, beta, state, n_valid, chunk=chunk, interpret=interpret)
            finally:
                kda._CHUNK_HEADS = was

        return form

    return {"xla.pairwise": pairwise, "xla.local": local, "xla.whole": whole,
            "kernel": kernel, **{f"kernel.hb{cap}": capped(cap) for cap in head_caps}}


def over_layers(form):
    return jax.jit(lambda *xs: jax.lax.map(lambda x: form(*x), xs))


def timed(fn, xs, runs):
    out = jax.block_until_ready(fn(*xs))  # compiles
    jax.block_until_ready(fn(*xs))
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*xs))
        times.append(time.perf_counter() - t0)
    return out, 1e3 * float(np.median(times)), 1e3 * float(np.max(times) - np.min(times))


def measure(name, cell, args):
    tiny = bool(cell.get("tiny"))
    rows, chunk = (48, 16) if tiny else (args.rows, kda.CHUNK)
    lines = []
    for n_valid in ((20, rows) if tiny else args.n_valid):
        xs = operands(cell, rows, n_valid, args.seed)
        first = tuple(x[0] for x in xs)
        want_o, want_s = jax.jit(kda.kda_sequential)(*first)
        scale_o = float(jnp.abs(want_o[:, :n_valid]).max())
        scale_s = float(jnp.abs(want_s).max())
        for form, fn in forms(chunk, n_valid, tiny, args.head_caps).items():
            if form in args.skip or (form.startswith("xla.") and form != "xla.whole"
                                     and n_valid != rows):
                continue
            out, ms, spread = timed(over_layers(fn), xs, args.runs)
            line = dict(
                cell=name, form=form, n_valid=n_valid, rows=rows, chunk=chunk,
                layers=cell["layers"], heads=cell["heads"], dk=cell["dk"],
                dv=cell["dv"], decay="head" if cell["per_head"] else "channel",
                ms_a_chunk=ms, ms_spread=spread,
                device=jax.devices()[0].device_kind,
            )
            if form == "xla.whole" or form.startswith("kernel"):
                o, s = out
                line["o_err"] = float(
                    jnp.abs(o[0][:, :n_valid] - want_o[:, :n_valid]).max()) / scale_o
                line["state_err"] = float(jnp.abs(s[0] - want_s).max()) / scale_s
            print(json.dumps(line), flush=True)
            lines.append(line)
    # which path the dispatcher itself takes here, as /metrics would count it
    before = kda.dispatch_counts()
    jax.block_until_ready(jax.jit(kda.kda_chunked)(*first))
    line = dict(cell=name, form="dispatch", device=jax.devices()[0].device_kind, **{
        path: n - before[path] for path, n in kda.dispatch_counts().items()})
    print(json.dumps(line), flush=True)
    return lines + [line]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", nargs="*", default=None)
    ap.add_argument("--rows", type=int, default=512, help="rows of a chunk")
    ap.add_argument("--n-valid", type=int, nargs="*", default=[256, 512])
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--seed", type=int, default=61)
    ap.add_argument("--skip", nargs="*", default=[], help="forms to leave out")
    ap.add_argument("--head-caps", type=int, nargs="*", default=[],
                    help="time the kernel again at these bounds on a grid step's heads")
    ap.add_argument("--tiny", action="store_true",
                    help="interpret mode at toy shapes: a rehearsal off the chip")
    ap.add_argument("--out", default=None, help="a .jsonl file for the lines")
    args = ap.parse_args()
    cells = TINY if args.tiny else CELLS
    if not args.tiny and jax.default_backend() != "tpu":
        sys.exit("kda_chunk_probe: no TPU here (JAX_PLATFORMS=cpu ... --tiny rehearses)")
    lines = []
    for name in args.cells or cells:
        lines += measure(name, cells[name], args)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)


if __name__ == "__main__":
    main()
