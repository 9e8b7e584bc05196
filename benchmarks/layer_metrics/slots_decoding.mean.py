"""Scheduler: slots that DECODE in a block, mean over the window's blocks —
window delta of ``mst_decode_positions_computed_total`` (steps x live rows,
counted at dispatch) over that of ``mst_decode_blocks_dispatched_total`` times
the decode steps of a block (``benchmarks/programs.json``).
``slots_active.mean`` counts claimed slots: one that waits for its prefill
chunks is claimed and decodes nothing."""
from benchmarks import tick_counters
from benchmarks.programs import PROGRAMS


def read(ctx):
    positions = tick_counters.total(ctx, "mst_decode_positions_computed_total")
    blocks = tick_counters.total(ctx, "mst_decode_blocks_dispatched_total")
    if positions is None or not blocks:
        return None
    return positions / (blocks * PROGRAMS["decode_steps_per_block"])
