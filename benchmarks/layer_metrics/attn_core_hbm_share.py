"""Kernels: the K/V bytes a decode step's attention MUST read (the family's
``paged_attn_step_bytes``: per active slot its context's rows in every layer
that keeps pages) over the device self time a step of the ragged kernel's
calls, as a share of the chip's peak HBM bandwidth: the kernel's roofline
share, it is bandwidth-bound. ``paged_attn_hbm_share``'s arithmetic on the
scope a family uses that does not name its attention layer kinds: the calls
are the decode block's operations under ``mst.attn.core``, which for such a
family hold the ``paged_attention`` call and nothing else in a decode step
(its output projection sits with the other projections). The time a step:
the scope's part of the decode block's device self time, times the step
(the median block over its steps) — not the scope's seconds over the blocks
counted in the trace, which counts a block the trace's edge cut in two as a
whole one (two of 18 blocks of 169 ms in a 2.77 s trace: 9 % too fast).
Contexts and active slots as there. Without the family's function or the
scope (another family, a commit from before them) the metric is left out."""
from benchmarks import scope_reduce
from benchmarks.config import family, published_config
from benchmarks.layer_metrics.paged_attn_hbm_share import contexts_at
from benchmarks.peaks import device_peaks
from benchmarks.programs import PROGRAMS, decode_step_seconds

SCOPE = "mst.attn.core"


def read(ctx):
    red = scope_reduce.for_run(ctx)
    need_fn = getattr(family(ctx["config"]), "paged_attn_step_bytes", None)
    slots = [s["slots_active"] for s in ctx["samples"] if s["slots_active"] is not None]
    step_s = decode_step_seconds(ctx["trace"])
    if red is None or need_fn is None or not slots or not step_s:
        return None
    blocks = [red["programs"].get(p, {}) for p in PROGRAMS["decode_block"]]
    secs = sum(b.get(SCOPE, {}).get("self_s", 0.0) for b in blocks)
    whole = sum(c.get("self_s", 0.0) for b in blocks for c in b.values())
    lengths = [c for s in ctx["samples"] for c in contexts_at(ctx["all_records"], s["t"])]
    if not secs or not lengths:
        return None
    cfg = published_config(ctx["config"])
    # per slot: the mean over streams and instants of the rows it must read
    need = sum(need_fn(cfg, 1.0, c) for c in lengths) / len(lengths) * sum(slots) / len(slots)
    peak = device_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / (step_s * secs / whole)
