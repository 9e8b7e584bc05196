"""Scheduler: the share of the window spent in dispatch calls made with the
device EMPTY — window delta of ``mst_program_dispatch_exposed_seconds_total``
(every kind: the part of each call-to-return that lay after the end of the
program before) over the window's length. The device waits for such a call;
``device_empty_share`` counts the device busy from the call's first moment,
so the two together bound ``device_idle_share`` from above as
``device_empty_share`` alone bounds it from below."""
from benchmarks import device_account


def read(ctx):
    return device_account.window_share(ctx, "exposed")
