"""How ``tiny_tpu.xplane.pb`` was recorded (one v5e chip, PR 23): two small
jitted programs run a few times under ``jax.profiler``, one inside a
``mst.decode_block`` annotation, with a sleep between them so that the
trace has an idle gap with a host span over it and one with none.

    python3 benchmarks/testdata/record.py <out dir>

``benchmarks/tests/test_trace_reduce.py`` reduces the recording and checks
the numbers this script prints beside it (``tiny_tpu.expected.json``).
"""
import glob
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: Path) -> None:
    out.mkdir(parents=True, exist_ok=True)

    @jax.jit
    def block(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.01
        return x

    @jax.jit
    def step(x):
        return (x * 2.0 + 1.0).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    block(x).block_until_ready()
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = out / "_profile"
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("mst.decode_block"):
            block(x).block_until_ready()
            time.sleep(0.004)
        step(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(str(tmp / "plugins/profile/*/*.xplane.pb"))[0]
    shutil.copy(src, out / "tiny_tpu.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    d = jax.devices()[0]
    (out / "tiny_tpu.expected.json").write_text(json.dumps({
        "platform": d.platform, "kind": d.device_kind,
        "executions": {"jit_block": 3, "jit_step": 3},
    }))
    print("recorded", (out / "tiny_tpu.xplane.pb").stat().st_size, "bytes")


if __name__ == "__main__":
    main(Path(sys.argv[1]))
