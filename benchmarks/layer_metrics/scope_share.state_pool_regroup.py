"""Engine: device self time under ``mst.state_pool.regroup`` (taking a
layer's rows out of the recurrent state pool and putting them back), percent
of device busy time (``benchmarks/scope_reduce.py``: the deepest ``mst.*``
component of each operation's ``tf_op``)."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.state_pool.regroup",))
