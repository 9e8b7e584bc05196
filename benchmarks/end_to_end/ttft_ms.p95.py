"""Time to first token, 95th percentile (nearest rank) over the requests due
in the window: a tail, so it wants some hundreds of requests a window."""
from benchmarks import stats


def read(ctx):
    return stats.finite(stats.percentile(stats.ttft_ms(ctx["records"]), 95))
