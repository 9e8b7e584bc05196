"""Engine: device self time under ``mst.diffusion.unmask`` (a decode forward's
epilogue where the family generates by diffusion over blocks: ranking the
masked positions' confidences, the transfer, the block's next ids and mask,
the commit's offset and repetition window; the sampling in front of it stays
under ``mst.sample``), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.diffusion.unmask",))
