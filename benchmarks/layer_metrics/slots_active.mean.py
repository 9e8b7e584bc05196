"""Scheduler: mean of the gauge ``mst_batch_slots_active`` sampled from the
runner at 10 Hz over the window (a gauge is sound only as a sampled mean)."""


def read(ctx):
    xs = [s["slots_active"] for s in ctx["samples"] if s["slots_active"] is not None]
    return sum(xs) / len(xs) if xs else None
