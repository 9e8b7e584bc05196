"""Scheduler / diffusion: forwards a committed block took, the commit among
them: window delta of ``mst_diffusion_slot_forwards_total`` (forwards x live
slots of the harvested decode programs) over
``mst_diffusion_blocks_committed_total``. ``denoising_steps + 1`` under a
strategy that transfers by rank (3.0 at 2 steps); lower where
``low_confidence_dynamic`` passes positions by confidence, and a little
higher than the loop's own count where slots sit finished inside a program's
forwards. A program without the counters (another family, a commit from
before them) exposes nothing and the metric is left out."""
from benchmarks import tick_counters


def read(ctx):
    forwards = tick_counters.total(ctx, "mst_diffusion_slot_forwards_total")
    blocks = tick_counters.total(ctx, "mst_diffusion_blocks_committed_total")
    if forwards is None or not blocks:
        return None
    return forwards / blocks
