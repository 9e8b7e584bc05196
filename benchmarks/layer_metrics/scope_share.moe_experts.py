"""Engine: device self time under ``mst.moe.experts`` and the scopes inside it (gather_dequant, matmul, scan), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "mst.moe.experts")
