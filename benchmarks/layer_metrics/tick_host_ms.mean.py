"""Scheduler: host work per tick, ms — over the scheduler ticks that lie
whole inside the trace, the time of ``mst.tick`` less its two waits
(``mst.harvest_wait``: blocked on the chip, ``mst.idle_wait``: blocked on the
queue), per tick. Read from the spans (``scope_reduce.tick_spans``) and not
from the window delta of ``mst_tick_phase_seconds_total``: the runner's second
scrape comes when ``/profile/stop`` returns, in a traced chip run some 40 s
after the window, by when the closed loop's 16 streams have been cut and
reaped and the server has idled (the ``[tick]`` line of the run, printed by
``decode_delivered_share``'s reader, shows that delta whole)."""
from benchmarks import scope_reduce


def read(ctx):
    spans = (scope_reduce.for_run(ctx) or {}).get("tick_spans")
    if not spans:
        return None
    waits = sum(spans["phase_s"].get(n, 0.0) for n in ("mst.harvest_wait", "mst.idle_wait"))
    return 1e3 * (spans["tick_s"] - waits) / spans["ticks"]
