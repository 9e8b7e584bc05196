"""Engine: device self time under ``mst.kv_ring.regroup`` (a window layer's ring taken out of and put back into the per-slot ring pool:
what ``mst.kv_pool.regroup`` is for the page pool; no part of ``scope_share.attn``), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.kv_ring.regroup",))
