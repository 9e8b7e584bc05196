"""Kernels: the recurrent state a decode step MUST read and write (the
family's ``ssm_state_step_bytes`` at the window's mean of active slots) over
the device self time, per decode step, of the operations that read and write
it, GB/s: a rate to set beside the chip's peak (819 on a v5e), not a share of
it. Those operations are the decode block's under ``mst.ssm.step`` (the
one-step recurrence) and ``mst.state_pool.regroup``: the compiler fuses the
recurrence's multiply-add and the frozen-slot select INTO the pool's in-place
update, and a fusion carries its root's scope, the update's. Under
``mst.ssm.step`` alone the first chip run read 1497 GB/s, more than the chip
moves (PERF.md, PR 28). Steps: the decode blocks in the trace times the steps
of a block. Without either scope (another family, a commit from before them)
the metric is left out."""
from benchmarks import scope_reduce
from benchmarks.config import family, published_config
from benchmarks.programs import PROGRAMS, durations

SCOPES = ("mst.ssm.step", "mst.state_pool.regroup")


def read(ctx):
    red = scope_reduce.for_run(ctx)
    state_bytes = getattr(family(ctx["config"]), "ssm_state_step_bytes", None)
    slots = [s["slots_active"] for s in ctx["samples"] if s["slots_active"] is not None]
    if red is None or state_bytes is None or not slots:
        return None
    secs = sum(red["programs"].get(p, {}).get(scope, {}).get("self_s", 0.0)
               for p in PROGRAMS["decode_block"] for scope in SCOPES)
    steps = len(durations(ctx["trace"], "decode_block")) * PROGRAMS["decode_steps_per_block"]
    if not secs or not steps:
        return None
    need = state_bytes(published_config(ctx["config"]), sum(slots) / len(slots))
    return need / (secs / steps / red["devices"]) / 1e9
