"""Window deltas of the scheduler tick's cumulative counters
(``mst_tick_phase_seconds_total{phase}``, ``mst_ticks_total``,
``mst_decode_*_total``, ``mst_pipeline_drains_total{reason}``) from the two
``/metrics`` scrapes at the window's edges. A program from before these
counters exposes none of them: every function here then returns ``None``
and the reader leaves its metric out."""

from __future__ import annotations

import re


def by_label(samples: dict, family: str) -> dict:
    """``{label value: sample}`` of a family with one label; ``{"": v}`` for
    one with none; ``{}`` where the scrape does not have the family."""
    out = {}
    pat = re.compile(re.escape(family) + r'(?:\{[a-z_]+="([^"]*)"\})?$')
    for key, val in samples.items():
        m = pat.match(key)
        if m:
            out[m.group(1) or ""] = val
    return out


def delta(ctx: dict, family: str):
    """``{label value: after - before}``, or ``None`` without the family."""
    after = by_label(ctx["after"] or {}, family)
    if not after:
        return None
    before = by_label(ctx["before"] or {}, family)
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def total(ctx: dict, family: str):
    d = delta(ctx, family)
    return None if d is None else sum(d.values())


def print_account(ctx: dict) -> None:
    """One line of the run's log with the tick's whole account between the
    two scrapes (PERF.md section 5 is written from it), once per run."""
    secs = delta(ctx, "mst_tick_phase_seconds_total")
    if secs is None or ctx.get("_tick_account_printed"):
        return
    ctx["_tick_account_printed"] = True
    counts = {f[4:-6]: total(ctx, f) for f in (
        "mst_ticks_total", "mst_decode_blocks_dispatched_total",
        "mst_decode_blocks_harvested_total",
        "mst_decode_positions_computed_total",
        "mst_decode_tokens_emitted_total")}
    print("[tick] between the scrapes (window %.3f s); phase seconds: %s; entries: %s; %s; dropped: %s; drains: %s" % (
        ctx["w1"] - ctx["w0"],
        ", ".join(f"{k} {v:.3f}" for k, v in sorted(secs.items(), key=lambda kv: -kv[1])),
        ", ".join(f"{k} {v:.0f}" for k, v in sorted(
            (delta(ctx, "mst_tick_phase_total") or {}).items())),
        ", ".join(f"{k} {v}" for k, v in counts.items()),
        delta(ctx, "mst_decode_tokens_dropped_total"),
        delta(ctx, "mst_pipeline_drains_total")), flush=True)
