"""Device: 1 - union of device-operation intervals over the traced window,
percent; on several chips the mean over chips (each chip on an earlier
line of the run's output)."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
