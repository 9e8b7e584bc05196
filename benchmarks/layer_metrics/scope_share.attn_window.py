"""Engine: device self time under ``mst.attn.window`` (the attention call of a sliding-window layer: the ragged kernel over the ring
of window pages in a decode step, the ring attention of a prefill chunk; a sub-share of
``scope_share.attn``, whose prefix it carries), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.attn.window",))
