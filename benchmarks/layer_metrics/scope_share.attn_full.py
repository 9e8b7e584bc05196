"""Engine: device self time under ``mst.attn.full`` (the attention call of a full-attention layer: the ragged kernel over the slot's
full-length pages in a decode step, flash attention in a prefill chunk; a sub-share of
``scope_share.attn``, whose prefix it carries), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.attn.full",))
