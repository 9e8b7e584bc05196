"""Kernels: bytes XLA counts (``bytes_accessed``) for the operations under
``mst.moe.experts*`` over their device self time, GB/s: the bytes the program
DOES move there, to set beside ``decode_hbm_share``'s bytes it MUST. A rate,
not a share of a peak: XLA's count is of operands and results per
instruction, whatever the memory they sit in."""
from benchmarks import scope_reduce


def read(ctx):
    red = scope_reduce.for_run(ctx)
    if red is None or not red["scoped"]:
        return None
    cells = [c for s, c in red["scopes"].items() if s.startswith("mst.moe.experts")]
    secs = sum(c["self_s"] for c in cells)
    return sum(c["bytes"] for c in cells) / secs / 1e9 if secs else None
