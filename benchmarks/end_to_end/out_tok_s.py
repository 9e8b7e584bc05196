"""Output tokens the server made for its clients inside the window, over the
window's length: every stream's tokens, whichever request they belong to
(lead-in included), each chunk's tokens spread over the time since that
stream's previous chunk (``stats.tokens_in_window``). All the work of all
the window's time; ``out_tok_s.arrived`` is the same count taken at arrival."""
from benchmarks import stats


def read(ctx):
    return stats.tokens_in_window(ctx["all_records"], ctx["w0"], ctx["w1"],
                                  spread=True) / (ctx["w1"] - ctx["w0"])
