"""Process start to the first instant of the measured window: weights on the
device, engine build, autotune, correctness check, warm-up, lead-in."""


def read(ctx):
    return ctx["setup_s"]
