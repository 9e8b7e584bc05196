"""Engine: median device duration of the prefill-chunk program
(``jit_prefill_chunk`` on the ``XLA Modules`` line), ms; ``None`` when no
chunk ran inside the trace (a closed-loop cell joins a few times a window)
or the program does not name it so."""


def read(ctx):
    durs = sorted((ctx["trace"] or {}).get("module_seconds", {}).get("jit_prefill_chunk", []))
    return durs[len(durs) // 2] * 1e3 if durs else None
