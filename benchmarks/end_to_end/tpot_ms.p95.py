"""Time per output token, 95th percentile over the requests due in the
window."""
from benchmarks import stats


def read(ctx):
    return stats.finite(stats.percentile(stats.tpot_ms(ctx["records"]), 95))
