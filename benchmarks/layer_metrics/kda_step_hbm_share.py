"""Kernels: the recurrent state a decode step MUST read and write (the
family's ``kda_state_step_bytes`` at the window's mean of active slots: per
slot and KDA layer the float32 state and the convolutions' tail, once in and
once out) over the device self time a step of the operations that read and
write it, as a share of the chip's peak HBM bandwidth: the gated delta rule's
roofline share, it is bandwidth-bound. ``ssm_step_hbm_share``'s arithmetic
and clock on this family's scopes: the decode block's operations under
``mst.kda.step`` (the one-step recurrence: the kernel ``kda_pool_step`` and
the small fusions that lay its operands out; without the kernel the passes
over the sliced rows) and ``mst.state_pool.regroup`` (the tails' slice and
update; without the kernel the state's too), as a part of the decode block's
device self time, times the step (the median block over its steps). The
bytes are the same whatever implements the step. Without the family's
function or either scope (another family, a commit from before them) the
metric is left out."""
from benchmarks import scope_reduce
from benchmarks.config import family, published_config
from benchmarks.peaks import device_peaks
from benchmarks.programs import PROGRAMS, decode_step_seconds

SCOPES = ("mst.kda.step", "mst.state_pool.regroup")


def read(ctx):
    red = scope_reduce.for_run(ctx)
    state_bytes = getattr(family(ctx["config"]), "kda_state_step_bytes", None)
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
