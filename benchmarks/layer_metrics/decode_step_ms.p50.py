"""Engine / model step: median device duration of the decode-block program
divided by the decode steps it runs (``benchmarks/programs.json``)."""
from benchmarks.programs import decode_step_seconds


def read(ctx):
    s = decode_step_seconds(ctx["trace"])
    return None if s is None else s * 1e3
