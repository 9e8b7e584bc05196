"""Engine: device self time under ``mst.ssm.*`` (a Mamba-2 layer's input
projection, convolution, chunked scan or one-step recurrence, gated norm and
output projection), percent of device busy time (``benchmarks/scope_reduce.py``:
the deepest ``mst.*`` component of each operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "mst.ssm.")
