"""Scheduler: host time a join leaves the device with nothing to run, ms —
the window's device-empty seconds (``device_empty_share``'s sum: every tick
phase but ``idle_wait``) over the joins that reached decode in it (window
delta of ``mst_join_seconds_count``). What a cheaper drain or a cheaper slot
claim moves, however many joins a faster step brings into the window.
``None`` without the families, and where the window held no join."""
from benchmarks import tick_counters
from benchmarks.layer_metrics import device_empty_share


def read(ctx):
    by_phase = device_empty_share.empty_seconds(ctx)
    joins = tick_counters.total(ctx, "mst_join_seconds_count")
    if by_phase is None or not joins:
        return None
    return 1e3 * sum(by_phase.values()) / joins
