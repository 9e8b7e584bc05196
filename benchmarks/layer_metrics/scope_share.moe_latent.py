"""Engine: device self time under ``mst.moe.latent`` (a latent expert layer's
projections into and out of the experts' space), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.moe.latent",))
