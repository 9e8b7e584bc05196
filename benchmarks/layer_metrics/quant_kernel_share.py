"""Kernels: device time in the 4-bit kernels (``quant_gemv_pipelined`` and
``quant_matmul`` events) over device busy time, percent."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t["busy_s"]:
        return None
    ops = t["op_seconds"]
    kernel = ops.get("quant_gemv_pipelined", 0.0) + ops.get("quant_matmul", 0.0)
    return 100.0 * kernel / (t["busy_s"] * t["devices"])
