"""Engine: device self time under ``mst.attn.cca_mix`` (a model whose attention
runs in a compressed latent: the two causal convolutions over the packed q, k, the
q-k mean, the L2 norms and the key temperature, the value shift and the partial
rotary — everything between the projections and the attention call; a sub-share
of ``scope_share.attn``, whose prefix it carries), percent of device busy time
(``benchmarks/scope_reduce.py``: the deepest ``mst.*`` component of each
operation's ``tf_op``). A program without the scope reads 0 and one without any
``mst.*`` scope leaves the metric out."""
from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.share(ctx, exact=("mst.attn.cca_mix",))
