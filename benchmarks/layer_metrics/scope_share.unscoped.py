"""Engine: device self time under no ``mst.*`` scope at all: how much the vocabulary still misses, percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=(scope_reduce.UNSCOPED,))
