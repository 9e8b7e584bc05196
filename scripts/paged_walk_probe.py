#!/usr/bin/env python3
"""The ragged paged-attention kernel alone, on the chip, at the benchmark
cells' shapes and under page tables as the default RESERVE admission writes
them (ISSUE 59): every page a stream will ever fill is in its row from its
first token, the streams at uniform progress through answers drawn from the
cell's traffic file.

For each cell, the same kernel body under each NAMING of the grid steps
past a slot's length (``ops/paged_attention.walk_page`` swapped out before
tracing; the outputs must come out bit-equal):

- ``row``       the table row as it stands — the walk up to PR 58: a claimed,
                empty page is fetched whole and its arithmetic skipped;
- ``backward``  a dead step repeats its slot's own last page: no fetch, but
                the next slot's first page is issued from a step with no
                arithmetic and waited for in full;
- ``forward``   the tree's walk: a dead step names the NEXT slot's first
                page, fetched under this slot's last live page's arithmetic;
- ``arith``     every step names one page: the arithmetic alone, no DMA but
                the call's first (its numbers are not compared).

Per cell and naming: milliseconds a call (``--layers`` layers' pools viewed
as one and walked by a scan, as the served block does, ``--reps`` times in
one program), blocks and bytes fetched (the walk replayed on the host: a
fetch where the block index differs from the step's before), GB/s on the
fetched bytes, and the bytes the slots HOLD (page-rounded) and MUST read
(rows up to the length).

    python scripts/paged_walk_probe.py --out chiprun_out/pr59/probe.jsonl
    JAX_PLATFORMS=cpu python scripts/paged_walk_probe.py --tiny   # rehearsal

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

from mlx_sharding_tpu.ops import paged_attention as paged_ops  # noqa: E402

# cell -> the ragged call's shape (tests/test_tpu_compile.py holds the same
# by name), the traffic file its streams come from, the layers that make
# the call in a decode step. ``rank``: the latent layout (values_from_k);
# ``lead``: the leading rows of a query group under a second length, a
# block of 4 behind (sdar's wide forward); ``block``: rows written a slot a
# forward, which the claim is rounded to
CELLS = {
    "olmo-hybrid-7b": dict(
        slots=48, hq=30, hkv=30, d=128, page=512, max_seq=1536, pool=96,
        traffic="shortchat-sat", kv_layers=4),
    "granite4-h-micro": dict(
        slots=48, hq=32, hkv=8, d=64, page=512, max_seq=4608, pool=432,
        traffic="reason-sat", kv_layers=4),
    "qwen3-next-80b": dict(
        slots=32, hq=16, hkv=2, d=256, page=512, max_seq=7680, pool=480,
        traffic="longgen-sat", kv_layers=3),
    "zaya1-8b": dict(
        slots=24, hq=8, hkv=2, d=128, page=512, max_seq=12288, pool=576,
        traffic="longctx8k-sat", kv_layers=20),
    "kimi-linear-48b": dict(
        slots=40, hq=32, hkv=1, d=576, rank=512, page=512, max_seq=5632,
        pool=440, traffic="reason1k-sat", kv_layers=7),
    "dsv2-lite-q4": dict(
        slots=16, hq=16, hkv=1, d=576, rank=512, page=256, max_seq=4096,
        pool=144, traffic="decode-sat", kv_layers=14),
    "sdar-30b-a3b": dict(
        slots=32, hq=128, hkv=4, d=128, page=512, max_seq=2048, pool=128,
        traffic="chatgen-sat", kv_layers=48, block=4),
    "sdar-30b-a3b.wide": dict(
        slots=32, hq=256, hkv=4, d=128, page=512, max_seq=2048, pool=128,
        traffic="chatgen-sat", kv_layers=48, block=4, lead=32),
}
TINY = dict(
    slots=5, hq=4, hkv=2, d=16, page=8, max_seq=48, pool=30,
    traffic="shortchat-sat", kv_layers=2, tiny=True)


def _own(ji, ln, page, window):
    """A step clamped to its slot's own first and last visible page."""
    first = 0 if window is None else jnp.maximum(ln - window, 0) // page
    return jnp.clip(ji, first, jnp.maximum(ln - 1, 0) // page)


def _row(mi, ji, t, ln, *, page_size, window=None):
    """The walk up to PR 58: the row as it stands, clamped under a window."""
    return t[mi, ji if window is None else _own(ji, ln[mi], page_size, window)]


def _backward(mi, ji, t, ln, *, page_size, window=None):
    return t[mi, _own(ji, ln[mi], page_size, window)]


def _arith(mi, ji, t, ln, **_):
    return t[0, 0] + 0 * ji


NAMINGS = {
    "row": _row, "backward": _backward, "forward": paged_ops.walk_page,
    "arith": _arith,
}


def reserve_tables(cell, rng):
    """(tables, lengths): each slot a stream of the cell's traffic at
    uniform progress, its whole prompt + answer claimed (RESERVE), distinct
    pool pages in the order the free list gave them, scratch past the
    claim."""
    with open(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)

    def draw(spec):
        if spec["dist"] == "fixed":
            return np.full(cell["slots"], spec["value"])
        return rng.integers(spec["min"], spec["max"] + 1, cell["slots"])

    prompt, answer = draw(mix["prompt_tokens"]), draw(mix["output_tokens"])
    if cell.get("tiny"):
        prompt, answer = prompt % 11 + 1, answer % 17 + 1
    block = cell.get("block", 1)
    page, spg = cell["page"], cell["max_seq"] // cell["page"]
    need = -(-(prompt + answer) // block) * block
    claimed = np.minimum(-(-need // page), spg)
    lengths = np.minimum(
        prompt + (rng.random(cell["slots"]) * answer).astype(np.int64) + block,
        claimed * page,
    )
    free = rng.permutation(cell["pool"])
    tables = np.full((cell["slots"], spg), cell["pool"], np.int32)
    at = 0
    for i, n in enumerate(claimed):
        tables[i, :n] = free[at:at + n]
        at += n
    assert at <= cell["pool"], (at, cell["pool"])
    return tables, lengths.astype(np.int32), claimed


def replay(walk, tables, lengths, page):
    """Pages a call fetches under the pipeline's rule, and how many of
    those fetches are issued from a step with no arithmetic."""
    mi, ji = np.divmod(np.arange(tables.size), tables.shape[1])
    pages = np.asarray(walk(mi, ji, tables, lengths, page_size=page, window=None))
    live = ji * page < lengths[mi]
    fetch = np.r_[True, pages[1:] != pages[:-1]]
    return int(fetch.sum()), int((fetch[1:] & ~live[:-1]).sum())


TRACED = []  # the namings whose walk a trace has called, in order


def traced(walk):
    def named(*a, **kw):
        TRACED.append(walk)
        return walk(*a, **kw)

    return named


def build(cell, layers, reps, interpret, rng):
    """The jitted program: ``reps`` walks over ``layers`` layers' pools viewed
    as one (the table offset by a traced layer index), and its operands."""
    hkv, d, page = cell["hkv"], cell["d"], cell["page"]
    rank, lead = cell.get("rank"), cell.get("lead", 0)
    n_pages = cell["pool"] + 1
    dtype = jnp.float32 if interpret else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 30))), 3)
    q = jax.random.normal(keys[0], (cell["slots"], cell["hq"], d), dtype)
    merged = rank is None
    k_shape = (layers * n_pages, page, 1, hkv * d) if merged else (layers * n_pages, page, hkv, d)
    k = jax.random.normal(keys[1], k_shape, dtype)
    v = jax.random.normal(keys[2], k_shape, dtype) if merged else jnp.zeros(
        (layers * n_pages, page, 1, 1), dtype)
    tables, lengths, claimed = reserve_tables(cell, rng)
    two = {}
    if lead:
        two = dict(
            lead_lengths=jnp.asarray(lengths - cell["block"]), lead_rows=lead)

    def program(q, k, v, tables, lengths):
        def layer(carry, l):
            out = paged_ops.paged_attention(
                q + carry, k, v, tables + l * n_pages, lengths, d ** -0.5,
                values_from_k=rank, kv_heads=hkv if merged else None,
                interpret=interpret, **two,
            )
            # the next call waits on this one's result; 0 * keeps the
            # numbers every layer's own
            return (0 * out[:, :1, :1]).astype(q.dtype), out

        def rep(carry, _):
            return jax.lax.scan(layer, carry, jnp.arange(layers))

        _, outs = jax.lax.scan(
            rep, jnp.zeros((cell["slots"], 1, 1), dtype), None, length=reps)
        return outs[-1]

    operands = (q, k, v, jnp.asarray(tables), jnp.asarray(lengths))
    return program, operands, tables, lengths, claimed


def measure(name, cell, args, rng):
    interpret = bool(cell.get("tiny"))
    layers = min(cell["kv_layers"], args.layers)
    hkv, d, page = cell["hkv"], cell["d"], cell["page"]
    itemsize = 4 if interpret else 2
    operands_a_page = 1 if cell.get("rank") else 2  # K alone in the latent layout
    page_bytes = page * hkv * d * itemsize
    program, operands, tables, lengths, claimed = build(
        cell, layers, args.reps, interpret, rng)
    held = int((-(-lengths // page)).sum())
    base = dict(
        cell=name, slots=cell["slots"], table_width=int(tables.shape[1]),
        page_bytes_an_operand=page_bytes, layers=layers, reps=args.reps,
        pages_claimed=int(claimed.sum()), pages_held=held,
        must_read_bytes=int(lengths.sum()) * hkv * d * itemsize * operands_a_page,
        held_bytes=held * page_bytes * operands_a_page,
        device=jax.devices()[0].device_kind,
    )
    want = None
    lines = []
    for naming in args.namings:
        # the walk is no argument of the kernel: it is swapped in the module,
        # and jit, which keys its traces on the function, is handed a new one
        paged_ops.walk_page = traced(NAMINGS[naming])
        fn = jax.jit(functools.partial(program))
        del TRACED[:]
        out = jax.block_until_ready(fn(*operands))  # compiles
        assert TRACED and all(w is NAMINGS[naming] for w in TRACED), naming
        jax.block_until_ready(fn(*operands))
        times = []
        for _ in range(args.runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(time.perf_counter() - t0)
        ms = 1e3 * float(np.median(times)) / (layers * args.reps)
        fetches, from_dead = replay(NAMINGS[naming], tables, lengths, page)
        fetched = fetches * page_bytes * operands_a_page
        line = dict(
            base, naming=naming, ms_a_call=ms,
            ms_spread=1e3 * float(np.max(times) - np.min(times)) / (layers * args.reps),
            fetches_a_call=fetches, fetches_issued_from_a_dead_step=from_dead,
            fetched_bytes=fetched, gb_s_fetched=fetched / ms / 1e6,
            gb_s_must=base["must_read_bytes"] / ms / 1e6,
        )
        if naming != "arith":
            got = np.asarray(out, np.float32)
            if want is None:
                want = got
            line["bit_equal"] = bool(np.array_equal(got, want))
            line["max_abs_diff"] = float(np.abs(got - want).max())
        lines.append(line)
        print(json.dumps(line), flush=True)
    return lines


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="all")
    ap.add_argument("--namings", default="row,backward,forward,arith")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--reps", type=int, default=25)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--seed", type=int, default=59)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    args.namings = args.namings.split(",")
    cells = {"tiny": TINY} if args.tiny else {
        n: c for n, c in CELLS.items() if args.cells in ("all", n) or n in args.cells.split(",")
    }
    if args.tiny:
        args.layers, args.reps, args.runs = 2, 2, 1
    elif jax.default_backend() != "tpu":
        sys.exit("no chip: a time comes only from a chip run (--tiny rehearses the control flow)")
    lines = []
    for name, cell in cells.items():
        lines += measure(name, cell, args, np.random.default_rng(args.seed))
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    print(f"{'cell':22s} {'naming':9s} ms/call  fetches dead  fetched MB  GB/s(fetched)  GB/s(must)")
    for x in lines:
        print(f"{x['cell']:22s} {x['naming']:9s} {x['ms_a_call']:7.3f}  "
              f"{x['fetches_a_call']:6d} {x['fetches_issued_from_a_dead_step']:4d}  "
              f"{x['fetched_bytes'] / 1e6:10.1f}  {x['gb_s_fetched']:13.1f}  {x['gb_s_must']:10.1f}")


if __name__ == "__main__":
    main()
