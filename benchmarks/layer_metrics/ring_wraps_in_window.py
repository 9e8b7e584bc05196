"""Scheduler: ring pages overwritten inside the window: window delta of
``mst_kv_ring_wraps_total`` (a slot opened a page past its ring's first lap;
one count stands for every window layer's page). Above 0 says the window
layers ran on rings that had wrapped. A program without the counter (no
window layers, or a commit from before it) exposes nothing and the metric is
left out."""
from benchmarks import tick_counters


def read(ctx):
    return tick_counters.total(ctx, "mst_kv_ring_wraps_total")
