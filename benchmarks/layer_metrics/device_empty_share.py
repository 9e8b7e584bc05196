"""Scheduler: the share of the window the device had nothing to run while
the tick thread was at work — the window's ``device_idle_share``, from the
side that causes it. Window delta of ``mst_device_empty_seconds_total{phase}``
(the part of each tick phase's seconds with no served program dispatched and
unread) summed over every phase but ``idle_wait`` (nothing to serve is not
capacity lost), over the window's length. Prints one ``[empty]`` line with
every phase's delta, largest first, beside the joins the window held, the
``prefill_chunk`` entries and the cumulative ``harvest_wait`` (0: nobody waits
on an empty device): ``PERF.md`` section 5 is written from it. A
program without the family exposes nothing and the metric is left out."""
from benchmarks import tick_counters

FAMILY = "mst_device_empty_seconds_total"


def empty_seconds(ctx):
    """``{phase: empty seconds in the window}`` without ``idle_wait``, or
    ``None`` on a program that does not keep the account."""
    by_phase = tick_counters.delta(ctx, FAMILY)
    if by_phase is None:
        return None
    return {k: v for k, v in by_phase.items() if k != "idle_wait"}


def read(ctx):
    by_phase = empty_seconds(ctx)
    if by_phase is None:
        return None
    window = ctx["w1"] - ctx["w0"]
    if not ctx.get("_empty_account_printed"):
        ctx["_empty_account_printed"] = True
        chunks = (tick_counters.delta(ctx, "mst_tick_phase_total") or {}).get("prefill_chunk")
        print("[empty] between the scrapes (window %.3f s); device-empty seconds by phase: %s; sum %.3f; idle_wait %.3f; joins %s; join seconds %s; prefill_chunk entries %s; harvest_wait since the start %.6f" % (
            window,
            ", ".join(f"{k} {v:.3f}" for k, v in sorted(by_phase.items(), key=lambda kv: -kv[1])),
            sum(by_phase.values()),
            tick_counters.delta(ctx, FAMILY).get("idle_wait", 0.0),
            tick_counters.total(ctx, "mst_join_seconds_count"),
            tick_counters.total(ctx, "mst_join_seconds_sum"),
            chunks,
            tick_counters.by_label(ctx["after"], FAMILY).get("harvest_wait", 0.0)), flush=True)
    return 100.0 * sum(by_phase.values()) / window
