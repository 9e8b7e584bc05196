"""Server, client side: median time per output token over the requests that
ended inside the window, whenever they began; recorded, not judged."""
from benchmarks import stats


def read(ctx):
    ended = [r for r in ctx["all_records"]
             if r["done"] is not None and ctx["w0"] <= r["done"] < ctx["w1"]]
    xs = stats.tpot_ms(ended)
    return stats.finite(stats.percentile(xs, 50)) if xs else None
