"""Tests run on CPU with 8 virtual devices so the full multi-stage mesh
machinery is exercised without TPU hardware (SURVEY §4 implication (b)).

The platform is forced both ways: the environment variables cover any
subprocess a test spawns, and ``jax.config.update`` covers this process even
when something imported jax before pytest did (backends initialize lazily,
so the update still lands)."""

import os

# For any subprocesses tests may spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_collection_modifyitems(items):
    """``run_last`` tests go to the end, in their own order. They are the
    long subprocess end-to-end runs (chip_smoke.py's rehearsal is a minute
    and a half); a run that its time limit cuts should lose those, not the
    sixty ordinary tests that would otherwise queue behind them."""
    items.sort(key=lambda item: item.get_closest_marker("run_last") is not None)
