"""How a run fails, and how it runs a trace reduction: both reductions
(``trace_reduce.py``, ``scope_reduce.py``) are pure-Python passes over every
event of the ``.xplane.pb``, each in a process of its own (the runner never
imports JAX or TensorFlow), each under a stated limit."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class RunFailed(Exception):
    """The run has no result; the message starts with the stage that failed."""


def reduce_profile(stage: str, script: Path, profile_dir: Path, limit_s: float) -> tuple[dict, float]:
    """Run ``script <profile_dir>`` held to the CPU (the chip is free by now,
    and must stay so); returns the JSON object it prints last and the
    seconds it took."""
    t0 = time.monotonic()
    try:
        out = subprocess.run(
            [sys.executable, str(script), str(profile_dir)], cwd=ROOT,
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=limit_s,
        )
    except subprocess.TimeoutExpired:
        raise RunFailed(f"{stage}: not done after {limit_s:g} s") from None
    if out.returncode != 0:
        raise RunFailed(f"{stage}: exit code {out.returncode}\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1]), time.monotonic() - t0
