"""Load generator: how late requests left, 95th percentile over the window's
requests (actual send time minus due time, ms). A starved generator must
not be read as a fast server."""
from benchmarks import stats


def read(ctx):
    late = [(r["sent"] - r["due"]) * 1e3 for r in ctx["records"] if r["sent"] is not None]
    return stats.percentile(late, 95) if late else None
