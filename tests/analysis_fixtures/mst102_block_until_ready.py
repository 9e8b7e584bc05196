"""MST102: a wait on a device array inside an annotated hot path."""


# mst: hot-path
def harvest(logits, outs):
    logits.block_until_ready()
    return outs
