"""From the profiler's ``.xplane.pb`` to numbers: device busy time, time per
program and per operation, the longest idle gaps and what the host was doing
in each. ``python benchmarks/trace_reduce.py <profile dir | file>`` prints
one JSON object; ``benchmarks/run.py`` runs it in a process of its own.

What a trace of a TPU holds (looked at by hand, PERF.md §3): one plane per
chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<fingerprint>)``) and a line ``XLA
Ops`` (one event per HLO operation or kernel, the time the chip spent on
it); and ``/host:CPU`` with one line per thread, where
``jax.profiler.TraceAnnotation`` spans (the program's ``mst.*`` names, with
``--trace on --trace-profile``) and JAX's own dispatch events sit on the
same clock. A CPU trace (the tests) has no device plane: there the XLA
client's threads, whose events carry an ``hlo_op`` stat, stand for it.

Busy time is the union of the operation intervals of a chip; the window is
the profiler session, from the first to the last event of any plane.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP_N = 10

# kernel and program names as the program gives them (PR 21 named the Pallas
# kernels in the HLO); a name that matches none is reported as it is
KERNELS = ("quant_gemv_pipelined", "quant_matmul", "paged_attention", "flash_attention")


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals (ns in,
    seconds out) and the merged intervals themselves."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, [(s, e) for s, e in merged]


def op_label(name: str) -> str:
    """A stable name for an operation. The TPU's ``XLA Ops`` events carry
    the whole HLO instruction (``%fusion.485 = bf16[16,6,2048]{...}
    fusion(...)``): keep the instruction's name, give a kernel its own name
    where it has one, and drop the numbering (``fusion.123`` → ``fusion``)."""
    head = name.split(" = ")[0].split("(")[0].strip().lstrip("%")
    for k in KERNELS:
        if k in head:
            return k
    return re.sub(r"[.\d]+$", "", head) or head


def self_seconds(events: list[tuple[str, float, float]]) -> dict:
    """Seconds per label, each event counted for the time no event nested
    inside it covers (a ``while`` holds its body's operations on the same
    line: its own time is what is left)."""
    out: dict = {}
    stack: list[list] = []  # [label, end, child seconds]

    def close(item):
        label, start, end, children = item
        out[label] = out.get(label, 0.0) + max(0.0, (end - start) - children) / 1e9

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([op_label(name), s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def module_label(name: str) -> str:
    """``jit_block(1234567)`` → ``jit_block``."""
    return name.split("(")[0]


def read_planes(path: Path) -> dict:
    """``{"devices": {n: {"ops": [...], "modules": [...]}}, "host": [...],
    "span": (first ns, last ns)}`` with events as ``(name, start, end)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: dict = {}
    host: list = []
    cpu_ops: list = []
    first, last = None, None

    def see(s, e):
        nonlocal first, last
        first = s if first is None else min(first, s)
        last = e if last is None else max(last, e)

    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = dev["ops" if line.name == OPS_LINE else "modules"]
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    dest.append((ev.name, s, e))
                    see(s, e)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                is_xla_thread = line.name.startswith("tf_XLA")
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    see(s, e)
                    if e <= s:
                        continue
                    if is_xla_thread:
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            cpu_ops.append((ev.name, s, e, stats.get("hlo_module", "")))
                    else:
                        host.append((ev.name, s, e))
    if not devices and cpu_ops:
        # a CPU trace: the XLA client's threads stand for the device
        mods: dict = {}
        for name, s, e, mod in cpu_ops:
            a = mods.setdefault(mod, [s, e])
            a[0], a[1] = min(a[0], s), max(a[1], e)
        devices[0] = {
            "ops": [(n, s, e) for n, s, e, _ in cpu_ops],
            "modules": [(m, s, e) for m, (s, e) in mods.items()],
        }
    return {"devices": devices, "host": host, "span": (first or 0.0, last or 0.0)}


def covering_span(host: list, gap: tuple[float, float]) -> str:
    """The host span that covers most of an idle gap (of two that cover it
    equally, the shorter says more), or ``no-span``."""
    g0, g1 = gap
    best = None
    for name, s, e in host:
        cover = min(e, g1) - max(s, g0)
        if cover <= 0:
            continue
        key = (round(cover / (g1 - g0), 3), -(e - s))
        if best is None or key > best[0]:
            best = (key, name)
    return best[1] if best else "no-span"


def reduce(path: Path) -> dict:
    planes = read_planes(find_xplane(path))
    span0, span1 = planes["span"]
    window_s = (span1 - span0) / 1e9
    per_device = {}
    op_time: dict = {}
    module_durs: dict = {}
    worst_gaps: list = []
    for dev, lines in sorted(planes["devices"].items()):
        busy_s, merged = union_seconds([(s, e) for _, s, e in lines["ops"]])
        per_device[dev] = busy_s
        for label, secs in self_seconds(lines["ops"]).items():
            op_time[label] = op_time.get(label, 0.0) + secs
        for name, s, e in lines["modules"]:
            module_durs.setdefault(module_label(name), []).append((e - s) / 1e9)
        if dev == min(planes["devices"]):
            edges = [(span0, span0)] + merged + [(span1, span1)]
            gaps = [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
            worst_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP_N]
    n_dev = max(len(per_device), 1)
    busy_s = sum(per_device.values()) / n_dev
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "busy_s_per_device": per_device,
        "devices": n_dev,
        # summed over the chips traced, seconds
        "op_seconds": op_time,
        # {program: [seconds per execution]}, all chips
        "module_seconds": module_durs,
        "breakdown": {
            "device_ops": [
                [k, v / n_dev]
                for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP_N]
            ],
            "idle_gaps": [
                [covering_span(planes["host"], g), (g[1] - g[0]) / 1e9]
                for g in worst_gaps
            ],
        },
        "notes": [
            f"trace: {window_s:.3f} s, {n_dev} device plane(s), busy "
            + ", ".join(f"{d}: {b:.3f} s" for d, b in per_device.items()),
            "programs: " + ", ".join(
                f"{k} x{len(v)} median {sorted(v)[len(v) // 2] * 1e3:.2f} ms"
                for k, v in sorted(module_durs.items(), key=lambda kv: -sum(kv[1]))[:8]
            ),
        ],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(reduce(Path(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
