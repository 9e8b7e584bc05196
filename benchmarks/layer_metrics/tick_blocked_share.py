"""Scheduler: of the time the tick thread was not idle, the share it spent
waiting on the chip (``mst.harvest_wait``, the harvest's ``device_get``),
percent, over the scheduler ticks that lie whole inside the trace. High is
good: the host is then never what the device waits for. Read from the spans,
as ``tick_host_ms.mean`` is, and for its reason."""
from benchmarks import scope_reduce


def read(ctx):
    spans = (scope_reduce.for_run(ctx) or {}).get("tick_spans")
    if not spans:
        return None
    busy = spans["tick_s"] - spans["phase_s"].get("mst.idle_wait", 0.0)
    return 100.0 * spans["phase_s"].get("mst.harvest_wait", 0.0) / busy if busy > 0 else None
