"""Time to first token, median over the requests due in the window."""
from benchmarks import stats


def read(ctx):
    return stats.finite(stats.percentile(stats.ttft_ms(ctx["records"]), 50))
