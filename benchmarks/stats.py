"""Arithmetic the yardstick rests on: percentiles with failures ranked at
+inf, the per-request times and the token count of a window out of the
client's log, quantiles of a histogram's window delta, Prometheus text
parsing."""

from __future__ import annotations

import math
import re

#: printed where a percentile falls on a failed request (JSON has no inf)
DID_NOT_COMPLETE = 1e12


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of ``values``; a failed
    request is passed as ``math.inf`` and so ranks above every success."""
    xs = sorted(values)
    if not xs:
        return math.nan
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def finite(x: float) -> float:
    return DID_NOT_COMPLETE if math.isinf(x) else x


def ttft_ms(records) -> list[float]:
    """Per request, ms from the instant it was due to its first content
    chunk; a failed request ranks at +inf; one the window's end cut off
    before its first chunk is left out."""
    return [(r["first"] - r["due"]) * 1e3 if r["first"] is not None and not r["error"]
            else math.inf for r in records if not (r["cut"] and r["first"] is None)]


def tpot_ms(records) -> list[float]:
    """Per request that ran to its end, ``(t_last_chunk - t_first_chunk) /
    (n_out - 1)`` in ms; a failed one ranks at +inf; cut ones are left out."""
    return [(r["last"] - r["first"]) / (r["got"] - 1) * 1e3
            if r["ok"] and r["got"] > 1 else math.inf
            for r in records if not r["cut"]]


def tokens_in_window(records, w0: float, w1: float, spread: bool) -> float:
    """Output tokens of the window ``[w0, w1)``, whichever request they
    belong to. ``spread=False``: a chunk's tokens count at the instant the
    chunk arrived. ``spread=True``: they count evenly over the time since the
    stream's previous chunk — the time the server took to make them (the
    batcher hands a stream its tokens a block at a time, every slot at the
    same instant, so counting at arrival moves by a whole block of every
    slot with where the window's edge falls between two blocks). A stream's
    first chunk has no previous one and counts at its arrival."""
    total = 0.0
    for r in records:
        prev = None
        for t, n in r["chunks"]:
            if not spread or prev is None or t <= prev:
                total += n if w0 <= t < w1 else 0
            else:
                total += n * max(0.0, min(t, w1) - max(prev, w0)) / (t - prev)
            prev = t
    return total


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_prometheus(text: str) -> dict:
    """``{'name{labels}': value}`` for every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE.match(line.strip())
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


def scalar(samples: dict, name: str, default=None):
    """Sum of a family's samples over its label sets (a counter or gauge)."""
    vals = [v for k, v in samples.items() if k == name or k.startswith(name + "{")]
    return sum(vals) if vals else default


def histogram_delta(before: dict, after: dict, family: str) -> list[tuple[float, float]]:
    """``[(upper bound, count in the window)]`` per bucket (not cumulative),
    from two scrapes of a cumulative histogram."""
    pat = re.compile(re.escape(family) + r'_bucket\{.*?le="([^"]+)".*?\}$')
    cum = []
    for key, val in after.items():
        m = pat.match(key)
        if m:
            le = math.inf if m.group(1) in ("+Inf", "inf") else float(m.group(1))
            cum.append((le, val - before.get(key, 0.0)))
    cum.sort()
    out, prev = [], 0.0
    for le, c in cum:
        out.append((le, c - prev))
        prev = c
    return out


def histogram_quantile(buckets: list[tuple[float, float]], q: float):
    """Quantile (0..1) by linear interpolation inside the bucket, as
    Prometheus does; None for an empty window."""
    total = sum(c for _, c in buckets)
    if total <= 0:
        return None
    want, seen, lo = q * total, 0.0, 0.0
    for le, c in buckets:
        if c > 0 and seen + c >= want:
            if math.isinf(le):
                return lo
            return lo + (le - lo) * (want - seen) / c
        seen += c
        if not math.isinf(le):
            lo = le
    return lo
