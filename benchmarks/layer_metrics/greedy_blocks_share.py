"""Engine: the share of the window's decode blocks whose sampler had to run
the argmax only: window delta of ``mst_decode_blocks_total{sampler}``,
``greedy`` over all three classes. The scheduler classes a block at dispatch
from the live requests' ``temperature`` and ``top_p`` (``draw``: a sampled
row at ``top_p`` = 1, a Gumbel draw over the vocabulary; ``nucleus``: a
sampled row at ``top_p`` < 1, the vocabulary's sort besides), and the
program's batched sampler decides the same on the device: 100 in a cell whose
traffic is greedy, where ``scope_share.head_sample`` then holds no sort. A
program from before the counter exposes nothing and the metric is left out."""
from benchmarks import tick_counters


def read(ctx):
    blocks = tick_counters.delta(ctx, "mst_decode_blocks_total")
    if not blocks or not sum(blocks.values()):
        return None
    return 100.0 * blocks.get("greedy", 0.0) / sum(blocks.values())
