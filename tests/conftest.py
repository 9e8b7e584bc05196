"""Tests run on CPU with 8 virtual devices so the full multi-stage mesh
machinery is exercised without TPU hardware (SURVEY §4 implication (b)).

The platform is forced both ways: the environment variables cover any
subprocess a test spawns, and ``jax.config.update`` covers this process even
when something imported jax before pytest did (backends initialize lazily,
so the update still lands)."""

import os
from collections import Counter

# For any subprocesses tests may spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    """Under xdist (``--dist loadfile``) files go to the workers in the order
    the collection below gives them: xdist's own reorder (by a file's count
    of tests, on by default) would override it. Without xdist the option is
    absent and nothing is done."""
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


@pytest.hookimpl(trylast=True)  # after ``-m`` has deselected
def pytest_collection_modifyitems(items):
    """The file that holds a ``run_first`` test goes first, every other file
    by its count of tests, largest first; tests inside a file keep their own
    order. ``run_first`` marks the long subprocess end-to-end runs
    (chip_smoke.py's rehearsal is close to four minutes, one test). Workers
    take whole files, so a file of three tests handed out among the last
    leaves one worker running it for minutes while the others stand idle;
    handed out first, it runs beside everything else."""
    first = {item.path for item in items if item.get_closest_marker("run_first")}
    count = Counter(item.path for item in items)
    items.sort(key=lambda item: (item.path not in first, -count[item.path]))
