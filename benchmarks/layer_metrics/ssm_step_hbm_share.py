"""Kernels: the recurrent state a decode step MUST read and write (the
family's ``ssm_state_step_bytes`` at the window's mean of active slots: per
slot and Mamba layer the float32 SSM state and the convolution's tail, once
in and once out) over the device self time a step of the operations that
read and write it, as a share of the chip's peak HBM bandwidth: the
recurrence's roofline share, it is bandwidth-bound. ``ssm_state_gb_s``'s
bytes and scopes — the decode block's operations under ``mst.ssm.step`` (the
one-step recurrence) and ``mst.state_pool.regroup`` (the pool's in-place
update: the compiler fuses the recurrence's multiply-add and the frozen-slot
select INTO it, and a fusion carries its root's scope; under
``mst.ssm.step`` alone the first reading was more than the chip moves,
PERF.md, PR 28) — on ``attn_core_hbm_share``'s clock: the two scopes' part
of the decode block's device self time, times the step (the median block
over its steps), so that a block the trace's edge cut in two is not counted
as a whole one. Without the family's function or either scope (another
family, a commit from before them) the metric is left out."""
from benchmarks import scope_reduce
from benchmarks.config import family, published_config
from benchmarks.peaks import device_peaks
from benchmarks.programs import PROGRAMS, decode_step_seconds

SCOPES = ("mst.ssm.step", "mst.state_pool.regroup")


def read(ctx):
    red = scope_reduce.for_run(ctx)
    state_bytes = getattr(family(ctx["config"]), "ssm_state_step_bytes", None)
    slots = [s["slots_active"] for s in ctx["samples"] if s["slots_active"] is not None]
    step_s = decode_step_seconds(ctx["trace"])
    if red is None or state_bytes is None or not slots or not step_s:
        return None
    blocks = [red["programs"].get(p, {}) for p in PROGRAMS["decode_block"]]
    secs = sum(b.get(scope, {}).get("self_s", 0.0) for b in blocks for scope in SCOPES)
    whole = sum(c.get("self_s", 0.0) for b in blocks for c in b.values())
    if not secs:
        return None
    need = state_bytes(published_config(ctx["config"]), sum(slots) / len(slots))
    peak = device_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / (step_s * secs / whole)
