"""Kernels: the K/V bytes a decode step's attention MUST read (the family's
``paged_attn_step_bytes``: per active slot ``min(context, sliding_window)``
rows in each window layer and ``context`` rows in each full layer) over the
device self time a step of the ragged kernel's calls, as a share of the
chip's peak HBM bandwidth: the kernel's roofline share, it is bandwidth-bound.
The calls are the decode block's operations under ``mst.attn.window`` and
``mst.attn.full``, which in a decode step hold the ``paged_attention`` call
and nothing else. Steps: the decode blocks in the trace times the steps of a
block. Contexts: the streams' own lengths on the client's log, prompt plus
tokens received, averaged over the gauge samples' instants (the pool's
``pages_in_use`` would overstate them: pages are claimed for prompt +
max_tokens at admission); active slots: the window mean of the sampled gauge.
Without the family's function or the scopes (another family, a commit from
before them) the metric is left out."""
import bisect

from benchmarks import scope_reduce
from benchmarks.config import family, published_config
from benchmarks.peaks import device_peaks
from benchmarks.programs import PROGRAMS, durations

SCOPES = ("mst.attn.window", "mst.attn.full")


def contexts_at(records, t):
    """Context lengths of the streams open at ``t`` (client's clock)."""
    out = []
    for r in records:
        if r["first"] is None or r["first"] > t or (r["last"] or 0.0) < t:
            continue
        times = [c[0] for c in r["chunks"]]
        got = sum(n for _, n in r["chunks"][: bisect.bisect_right(times, t)])
        out.append(r["prompt_tokens"] + got)
    return out


def read(ctx):
    red = scope_reduce.for_run(ctx)
    need_fn = getattr(family(ctx["config"]), "paged_attn_step_bytes", None)
    slots = [s["slots_active"] for s in ctx["samples"] if s["slots_active"] is not None]
    if red is None or need_fn is None or not slots:
        return None
    secs = sum(red["programs"].get(p, {}).get(scope, {}).get("self_s", 0.0)
               for p in PROGRAMS["decode_block"] for scope in SCOPES)
    steps = len(durations(ctx["trace"], "decode_block")) * PROGRAMS["decode_steps_per_block"]
    lengths = [c for s in ctx["samples"] for c in contexts_at(ctx["all_records"], s["t"])]
    if not secs or not steps or not lengths:
        return None
    cfg = published_config(ctx["config"])
    # per slot: the mean over streams and instants of the rows it must read
    need = sum(need_fn(cfg, 1.0, c) for c in lengths) / len(lengths) * sum(slots) / len(slots)
    peak = device_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / peak / (secs / steps / red["devices"])
