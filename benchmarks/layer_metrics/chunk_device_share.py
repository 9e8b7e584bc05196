"""Scheduler: the share of the window the device spent on prefill chunks —
window delta of ``mst_program_device_seconds_total{program="chunk"}`` over
the window's length. Every decoding slot stands still for it: the most that
running chunks beside the decode blocks can give back."""
from benchmarks import device_account


def read(ctx):
    return device_account.window_share(ctx, "seconds", ("chunk",))
