"""Window deltas of the device's account as the tick thread keeps it
(``mst_program_device_seconds_total{program}`` and its three sister
families: ``benchmarks/README.device.md``), from the two ``/metrics`` scrapes
at the window's edges, for the readers of ``prefill_chunk_ms.window``,
``decode_step_ms.window``, ``chunk_device_share`` and
``dispatch_exposed_share``. A program from before the families exposes none
of them: :func:`account` then returns ``None`` and each reader leaves its
metric out."""

from __future__ import annotations

from benchmarks import tick_counters

SECONDS = "mst_program_device_seconds_total"
EXPOSED = "mst_program_dispatch_exposed_seconds_total"
RUNS = "mst_program_runs_total"
LATE = "mst_program_late_total"
EMPTY = "mst_device_empty_seconds_total"


def account(ctx: dict):
    """``{"window", "seconds", "exposed", "runs", "late"}``, the last four
    by kind of program, between the two scrapes; ``None`` without the
    families. The first call of a run prints the ``[device]`` line: the
    whole account and its remainder, ``window - device seconds - empty
    seconds`` (every phase's, ``idle_wait``'s too: busy and empty partition
    the tick thread's clock), which is the account's own error — the
    scrapes' skew and the programs a window's edge cuts."""
    seconds = tick_counters.delta(ctx, SECONDS)
    if seconds is None:
        return None
    acc = {"window": ctx["w1"] - ctx["w0"], "seconds": seconds,
           "exposed": tick_counters.delta(ctx, EXPOSED) or {},
           "runs": tick_counters.delta(ctx, RUNS) or {},
           "late": tick_counters.delta(ctx, LATE) or {}}
    if not ctx.get("_device_account_printed"):
        ctx["_device_account_printed"] = True
        empty = tick_counters.total(ctx, EMPTY) or 0.0
        left = acc["window"] - sum(seconds.values()) - empty

        def by_kind(d, fmt):
            return ", ".join(f"{k} {fmt % v}" for k, v in sorted(d.items()))

        print("[device] between the scrapes (window %.3f s); device seconds: %s; runs: %s; late: %s; dispatch exposed seconds: %s; empty seconds %.3f; remainder %.3f s (%.2f %% of the window)" % (
            acc["window"], by_kind(seconds, "%.3f"), by_kind(acc["runs"], "%.0f"),
            by_kind(acc["late"], "%.0f"), by_kind(acc["exposed"], "%.3f"),
            empty, left, 100.0 * left / acc["window"]), flush=True)
    return acc


def ms_a_run(ctx: dict, kind: str, steps: int = 1):
    """Device milliseconds a run of ``kind`` (over ``steps`` where a run is
    several), or ``None`` where the window closed none."""
    acc = account(ctx)
    if acc is None or not acc["runs"].get(kind):
        return None
    return 1e3 * acc["seconds"][kind] / (acc["runs"][kind] * steps)


def window_share(ctx: dict, family: str, kinds=None):
    """Percent of the window in ``family``'s seconds (``"seconds"`` or
    ``"exposed"``), of ``kinds`` or of all."""
    acc = account(ctx)
    if acc is None:
        return None
    d = acc[family]
    return 100.0 * sum(v for k, v in d.items() if kinds is None or k in kinds) / acc["window"]

