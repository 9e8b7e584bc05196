"""Device: ``memory_stats()["peak_bytes_in_use"]`` after the window, worst
chip, GB."""


def read(ctx):
    return ctx["device"]["memory_peak_bytes"] / 1e9
