"""Reading a configuration file (``benchmarks/configs/<name>.json``): the
model's published keys sit at the top level, the benchmark's own under
``bench``. What belongs to one model family (its plain reference, the table
of its matrices, the tree its loader returns, the bytes a decode step moves)
is one module, ``benchmarks/reference/<model_type>.py``, found by the
config's ``model_type`` (:func:`family`). No JAX here: the runner imports
this."""

from __future__ import annotations

import importlib
from typing import NamedTuple

#: keys of a configuration file that are the benchmark's, not the model's
OWN_KEYS = ("name", "source", "reduced", "assumed", "deployment", "bench")

GROUP_SIZE = 64
BITS = 4


def published_config(config: dict) -> dict:
    """The model's published keys: the file without the benchmark's own."""
    return {k: v for k, v in config.items() if k not in OWN_KEYS}


def program_config(config: dict) -> dict:
    """The ``config.json`` the program reads: the published keys plus the
    quantization descriptor the 4-bit format implies."""
    cfg = published_config(config)
    if config["bench"]["weight_format"] == "q4":
        cfg["quantization"] = {"group_size": GROUP_SIZE, "bits": BITS}
    return cfg


def server_flag(config: dict, name: str, default=None):
    """The value that follows ``name`` in the configuration's server flags."""
    flags = [str(f) for f in config["bench"]["server_flags"]]
    return flags[flags.index(name) + 1] if name in flags else default


def family(config: dict):
    """The module of the configuration's model family. It exposes
    ``forward`` (the plain reference), ``FAULTS`` (its deliberately wrong
    variants), ``program_params`` (the tree ``load_model`` returns, from the
    seed), ``model_units`` and ``decode_step_bytes``. A new family adds one
    file and edits none."""
    return importlib.import_module(f"benchmarks.reference.{config['model_type']}")


class Unit(NamedTuple):
    """One named matrix (or norm vector) of the model."""

    name: str
    kind: str  # "linear" | "norm"
    out: int
    inn: int  # 0 for a norm
    experts: int = 0  # > 0: one matrix per routed expert
    keep_dense: bool = False  # stays bf16 under the 4-bit format


def is_packed(unit: Unit, fmt: str) -> bool:
    return fmt == "q4" and unit.kind == "linear" and not unit.keep_dense
