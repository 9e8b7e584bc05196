"""Engine / model step: device time of a decode block over the WHOLE window
divided by the decode steps it runs (``benchmarks/programs.json``, as
``decode_step_ms.p50`` divides), ms — window delta of
``mst_program_device_seconds_total{program="block"}`` over that of
``mst_program_runs_total{program="block"}``. A block ends at the return of
its harvest's read; a harvest's lateness cancels between consecutive
harvests. A mean over every block, those with idle slots too, where
``decode_step_ms.p50`` is the capture's median."""
from benchmarks import device_account
from benchmarks.programs import PROGRAMS


def read(ctx):
    return device_account.ms_a_run(ctx, "block", PROGRAMS["decode_steps_per_block"])
