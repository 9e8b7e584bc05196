"""Scheduler / diffusion: of the window's slot-forwards, the share that were
commits, percent: a committed block had exactly one, so window delta of
``mst_diffusion_blocks_committed_total`` over
``mst_diffusion_slot_forwards_total``. A commit forward computes no new token:
it stores the K/V of a block whose ids are final. What fusing it with the next
block's first denoise forward (2L rows a slot) would win. A program without
the counters exposes nothing and the metric is left out."""
from benchmarks import tick_counters


def read(ctx):
    forwards = tick_counters.total(ctx, "mst_diffusion_slot_forwards_total")
    blocks = tick_counters.total(ctx, "mst_diffusion_blocks_committed_total")
    if blocks is None or not forwards:
        return None
    return 100.0 * blocks / forwards
