"""Engine: device self time under ``mst.moe.shared``, ``mst.mlp.dense`` and ``mst.norm``: with the other ``scope_share.*`` the whole vocabulary, percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.moe.shared", "mst.mlp.dense", "mst.norm"))
