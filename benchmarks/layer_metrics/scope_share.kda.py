"""Engine: device self time under ``mst.kda.*`` (a gated delta-rule layer's q,
k, v projection, convolution, low-rank gates, chunked scan or one-step
recurrence, gated norm and output projection), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "mst.kda.")
