"""Kernels / device: share of the chip's peak HBM bandwidth that a decode
step achieves on the bytes it must move (the model family's
``decode_step_bytes``: weights once, distinct routed experts of the active
slots, latent cache of the pages in use) — decode is bandwidth-bound, so this is its roofline
share; the name keeps the issue's. Active slots and pages in use are the
window means of the sampled gauges."""
from benchmarks.config import family, published_config, server_flag
from benchmarks.peaks import device_peaks
from benchmarks.programs import decode_step_seconds


def read(ctx):
    step_s = decode_step_seconds(ctx["trace"])
    slots = [s["slots_active"] for s in ctx["samples"] if s["slots_active"] is not None]
    pages = [s["pages_in_use"] for s in ctx["samples"] if s["pages_in_use"] is not None]
    if not step_s or not slots or not pages:
        return None
    cfg = ctx["config"]
    page_tokens = int(server_flag(cfg, "--prefill-chunk", 256))  # a page is a chunk
    need = family(cfg).decode_step_bytes(
        published_config(cfg), cfg["bench"]["weight_format"], sum(slots) / len(slots),
        sum(pages) / len(pages) * page_tokens)
    peak = device_peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["total"] / peak / step_s
