"""Which compiled programs of the served path are which, by the name the
profiler gives them (``programs.json``), and their durations out of a trace
reduction (``trace_reduce.reduce``'s ``module_seconds``)."""

from __future__ import annotations

import json
from pathlib import Path

PROGRAMS = json.loads(Path(__file__).with_name("programs.json").read_text())


def durations(trace: dict | None, kind: str) -> list[float]:
    """Sorted seconds per execution of the programs of ``kind``."""
    out: list[float] = []
    for name, durs in (trace or {}).get("module_seconds", {}).items():
        if name in PROGRAMS[kind]:
            out += durs
    return sorted(out)


def median_seconds(trace: dict | None, kind: str):
    d = durations(trace, kind)
    return d[len(d) // 2] if d else None


def decode_step_seconds(trace: dict | None):
    block = median_seconds(trace, "decode_block")
    return None if block is None else block / PROGRAMS["decode_steps_per_block"]
