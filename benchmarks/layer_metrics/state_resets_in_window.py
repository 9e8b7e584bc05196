"""Scheduler: slots whose recurrent state was started from zero inside the
window (a request's first prefill chunk): window delta of
``mst_state_resets_total``. Each is one join. A program without the counter
(no recurrent state, or a commit from before it) exposes nothing and the
metric is left out."""
from benchmarks import tick_counters


def read(ctx):
    return tick_counters.total(ctx, "mst_state_resets_total")
