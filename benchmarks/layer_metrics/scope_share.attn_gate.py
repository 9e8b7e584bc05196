"""Engine: device self time under ``mst.attn.gate`` (the attention output gate: its projection, the sigmoid and the product with
the attention output; a sub-share of ``scope_share.attn``, whose prefix it carries), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.attn.gate",))
