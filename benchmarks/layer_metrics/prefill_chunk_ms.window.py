"""Engine: device time of a prefill chunk over the WHOLE window, ms, as the
tick thread's waits show it — window delta of
``mst_program_device_seconds_total{program="chunk"}`` over that of
``mst_program_runs_total{program="chunk"}``. A join's last chunk ends at the
return of the first token's read and carries the first-token program behind
it; a middle chunk ends at the wait on its logits in front of the next
harvest. The host learns an end a wake-up late, so it reads a little above
``prefill_chunk_ms.p50`` (the profiler's, where its capture held a chunk).
Left out where the window closed no chunk or the program keeps no account."""
from benchmarks import device_account


def read(ctx):
    return device_account.ms_a_run(ctx, "chunk")
