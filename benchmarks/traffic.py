"""The one traffic generator. A mix is a data file (``benchmarks/traffic/
<name>.json``): length distributions, the arrival process, the lead-in. A
cell's load is a data file too (``benchmarks/cells/<cell>.json``): a
``rate`` in requests a second for an open-loop mix, ``clients`` for a closed
loop.

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps — the distribution's quantiles at ``(i + 0.5) / n`` — in
another order, so the work of a run is fixed by the cell and only its order
by the seed. The order is shuffled in blocks of ``BLOCK`` requests, each
block holding an even spread of the quantiles (:func:`blocked_order`), so
no seed bunches the long requests or the short gaps into one stretch of the
window. The order is always the seed's: a check's runs then sample how
requests fall against the batcher's ticks, and a bound says how far that
alone moves a metric.

Open loop (``"arrivals": "poisson" | "uniform"``): every request has a due
time, whatever the server does. Closed loop (``"arrivals": "closed"``):
``clients`` clients each send their next request the moment the last one
ended, so the server is always full and tokens per second is its capacity;
requests have no due times, only an order, and the mix says how many to
hold ready per client (``requests_per_client``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Planned:
    due: float  # seconds from the start of the lead-in; closed loop: its turn
    prompt_tokens: int
    output_tokens: int
    prompt: str


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    lo, hi = spec.get("min", 1), spec.get("max", math.inf)
    return np.clip(np.rint(x), lo, hi).astype(int)


def arrival_gaps(kind: str, n: int) -> np.ndarray:
    """``n`` gaps with mean 1: the exponential's mid-quantiles (a Poisson
    process's gaps) or equal gaps."""
    if kind == "poisson":
        u = (np.arange(n) + 0.5) / n
        g = -np.log1p(-u)
        return g / g.mean()
    if kind == "uniform":
        return np.ones(n)
    raise ValueError(f"unknown arrival process {kind!r}")


BLOCK = 8


def blocked_order(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """The values in a seeded order that is even over the window: sorted,
    dealt round-robin into ``ceil(n / BLOCK)`` blocks (each block then holds
    one value from every stretch of the distribution), each block shuffled,
    the blocks shuffled, and laid end to end."""
    n = len(values)
    n_blocks = max(1, -(-n // BLOCK))
    ranked = np.sort(values)
    blocks = [rng.permutation(ranked[b::n_blocks]) for b in range(n_blocks)]
    return np.concatenate([blocks[b] for b in rng.permutation(n_blocks)])


def words(rng: np.random.Generator, n: int, vocab_size: int) -> str:
    """An n-token prompt as text (the launcher's tokenizer maps ``w<i>`` to
    id i)."""
    return " ".join(f"w{t}" for t in rng.integers(1, vocab_size, n))


def plan(mix: dict, load: dict, seconds: float, seed: int, vocab_size: int,
         max_context: int) -> tuple[list[Planned], float]:
    """``(requests, window_start)``: a lead-in under the cell's load (the
    system is in its steady state when the window opens), then the window.
    Times count from the start of the lead-in."""
    lead = float(mix.get("lead_in_s", 0.0))
    closed = mix.get("arrivals", "poisson") == "closed"
    if closed:
        parts = [(0.0, 0.0, int(load["clients"]) * int(mix["requests_per_client"]))]
    else:
        parts = [(t0, span, int(round(load["rate"] * span)))
                 for t0, span in ((0.0, lead), (lead, float(seconds)))]
    out: list[Planned] = []
    for part, (t0, span, n) in enumerate(parts):
        if n <= 0:
            continue
        rng = np.random.default_rng([int(seed), part])
        if closed:
            due = np.arange(n, dtype=float)
        else:
            gaps = blocked_order(arrival_gaps(mix.get("arrivals", "poisson"), n), rng)
            due = t0 + (np.cumsum(gaps) - gaps[0]) * (span / gaps.sum())
        p_len = blocked_order(quantiles(mix["prompt_tokens"], n), rng)
        o_len = blocked_order(quantiles(mix["output_tokens"], n), rng)
        for d, p, o in zip(due, p_len, o_len):
            if p + o > max_context:
                raise ValueError(
                    f"a request of {p}+{o} tokens exceeds the context {max_context}"
                )
            out.append(Planned(float(d), int(p), int(o), words(rng, int(p), vocab_size)))
    return out, lead
