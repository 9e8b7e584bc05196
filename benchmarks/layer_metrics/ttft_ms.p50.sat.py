"""Server, client side: median time to first token over the requests the
closed-loop clients sent inside the window; recorded, not judged (a few
requests start in a window, each waiting for the next block's edge)."""
from benchmarks import stats


def read(ctx):
    xs = stats.ttft_ms(ctx["records"])
    return stats.finite(stats.percentile(xs, 50)) if xs else None
