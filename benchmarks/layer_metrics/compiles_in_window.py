"""Engine: XLA compilations between the window's start and end, counted by
the launcher through ``jax.monitoring`` (backend compile events, so a
program served from the persistent cache does not count). Should read 0."""


def read(ctx):
    return float(ctx["compiles_in_window"])
