"""The cells' configurations compiled for a *described* v5e at full size:
the prefill-chunk and decode-block programs of the program's own engine
must compile (Mosaic accepts the kernels' blocks) and fit a chip's HBM with
the configured pool. A compile that passes is not a chip run; what it
refuses costs no chip time (the full-depth configuration was refused here
first, and on the chip with the same numbers: PERF.md, PR 23).

The topology is described inside a module-scoped fixture, never at import:
only one process may load libtpu (on-chip-measurement guide, section 2).
"""
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HBM_BYTES = 15.75 * 2**30  # what the v5e compiler allows a program
CONFIGS = ["dsv2-lite-q4"]


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry written for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled(topo):
    cache = {}

    def get(name, monkeypatch):
        import jax

        from benchmarks import aot

        if name not in cache:
            # ops/ dispatch predicates ask jax.default_backend(), which is
            # "cpu" here: answer for the chip, as tests/test_tpu_compile.py does
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            config = json.loads((ROOT / f"benchmarks/configs/{name}.json").read_text())
            cache[name] = aot.compile_programs(config, topo.devices)
        return cache[name]

    return get


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_program_compiles_and_fits_one_chip(name, program, compiled, monkeypatch):
    from benchmarks import aot

    out = compiled(name, monkeypatch)
    need = aot.resident_bytes(out[program])
    # one tenth of the chip stays free for the other programs' temporaries
    # and fragmentation
    assert need < 0.9 * HBM_BYTES, f"{program}: {need / 2**30:.2f} GiB"
    text = out[program + "_text"]
    assert "tpu_custom_call" in text
    # at 16 slots the decode projections are M = 16 rows: past GEMV_MAX_M = 8,
    # so both programs run the 4-bit matmul kernel, not the GEMV
    assert "quant_matmul" in text, f"{program} does not run the 4-bit kernel"
    assert ("paged_attention" if program == "decode" else "flash_attention") in text
