"""Server, client side: output tokens that *arrived* inside the window over
its length — ``out_tok_s`` without the spreading, so it moves by a block of
every slot with where the window's edge falls between two blocks."""
from benchmarks import stats


def read(ctx):
    return stats.tokens_in_window(ctx["all_records"], ctx["w0"], ctx["w1"],
                                  spread=False) / (ctx["w1"] - ctx["w0"])
