"""Kernels: traced calls that took a fallback where a kernel or an in-place
read exists — the second scrape's ``mst_quant_dispatch_total{path="xla"}``
(a packed weight dequantized in HBM every step) +
``mst_paged_attention_dispatch_total{path="xla"}`` (a layer gathering every
slot's whole table row) + ``mst_moe_dispatch_total{path="gather"}`` +
``{path="gather_packed"}`` (a step copying every pick's whole expert out of
the stacks). Each is counted once per traced call, at compile time, so the
value and not a window delta: 0 on a chip today, above 0 a layer is back on a
slow path nobody was told of. ``None`` without any of the three families."""
from benchmarks import tick_counters

SLOW = (
    ("mst_quant_dispatch_total", ("xla",)),
    ("mst_paged_attention_dispatch_total", ("xla",)),
    ("mst_moe_dispatch_total", ("gather", "gather_packed")),
)


def read(ctx):
    found = [(tick_counters.by_label(ctx["after"] or {}, family), paths)
             for family, paths in SLOW]
    if not any(by_path for by_path, _ in found):
        return None
    return sum(by_path.get(p, 0.0) for by_path, paths in found for p in paths)
