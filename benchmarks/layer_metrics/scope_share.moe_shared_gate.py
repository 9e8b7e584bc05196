"""Engine: device self time under ``mst.moe.shared_gate`` (a shared expert's gate of its own: the sigmoid of one scalar a row, its
product with the expert's output and the sum with the routed part; a scope BESIDE ``mst.moe.shared``, whose operations
``scope_share.mlp_shared_norm`` reads, and outside ``scope_share.moe_experts``: a share of its own), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.moe.shared_gate",))
