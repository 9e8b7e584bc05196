"""Engine: device self time under ``mst.attn.*`` (qkv, kv_write, core), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, "mst.attn.")
