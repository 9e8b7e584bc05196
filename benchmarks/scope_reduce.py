"""Device time, bytes and flops per program and per ``mst.*`` scope, out of
the profiler's ``.xplane.pb``. ``python benchmarks/scope_reduce.py <profile
dir | file>`` prints one JSON object; the readers of the ``scope_share.*``
metrics get it through :func:`for_run`, which runs this file in a process of
its own (the runner never imports JAX, TensorFlow or the program) once per
run.

Why another reduction beside ``trace_reduce.py``: ``jax.profiler.ProfileData``
shows an ``XLA Ops`` event as the bare HLO instruction with its device offset
and duration. The same file parsed as an ``XSpace`` protobuf carries, on each
operation's *event metadata*, what XLA knew about the instruction: ``tf_op``
(the JAX ``op_name``, where a ``jax.named_scope`` path lands:
``jit(block)/.../mst.attn.core/dot_general``), ``program_id`` (the number in
the ``XLA Modules`` event's ``jit_block(<id>)``), ``hlo_category``, ``flops``
and ``bytes_accessed``. So the scopes the program writes
(``mlx_sharding_tpu.tracing.MODEL_SCOPES``) come out of the chip's trace per
operation, with XLA's own byte count beside them.

The rules. An operation's scope is the deepest ``mst.*`` component of its
``tf_op``; one with none is ``unscoped``. Time is self time, by the nesting
rule of ``trace_reduce.self_seconds``: a ``while`` holds its body's operations
on the same line and counts only what they leave. Bytes and flops are summed
over operations that hold no other (a ``while``'s own figures are its body's
again). XLA keeps ONE instruction's metadata for a fusion, so a fusion that
spans two scopes counts under one of them: nothing here can split it. What
*can* be seen is reported beside the result and not folded into it: for the
unscoped time, the scope of the operation it ran inside (``unscoped_under``:
a compiler-made copy in a scanned layer's ``while`` has no name of its own but
does sit inside the scan's), how much of all time ran inside a ``while``
(``inside_while_s``), and how much belongs to operations that are no
``while`` but carry a ``while``'s own name (``named_by_loop``: a fusion the
compiler made inside a loop body, out of several layers' pieces or of none,
gets the loop instruction's ``op_name`` and with it the scope the loop was
called in, whatever it computes).
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # run as a script, by for_run
    sys.path.insert(0, str(ROOT))

from benchmarks.reduction import reduce_profile  # noqa: E402

#: the wait for this file's own process in ``for_run``, seconds: 2.8 x the
#: 21.1 s of a chip run's 29 MB profile (20.6-22.5 s from 7 to 44 MB: most of
#: it is importing the protobuf's module; PERF.md section 7)
SCOPE_REDUCE_LIMIT_S = 60.0
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
UNSCOPED = "unscoped"
#: tried in this order; the first that imports parses the file
XPLANE_MODULES = (
    "tensorflow.tsl.profiler.protobuf.xplane_pb2",
    "xprof.protobuf.xplane_pb2",
    "tensorboard_plugin_profile.protobuf.xplane_pb2",
)


def xplane_pb2():
    import importlib

    errors = []
    for name in XPLANE_MODULES:
        try:
            return importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 — try the next one
            errors.append(f"{name}: {type(e).__name__}: {e}")
    raise ImportError(
        "no XSpace protobuf module imports, so the per-scope reduction cannot "
        "read the profile; tried " + "; ".join(errors))


def find_xplane(path: Path) -> Path:
    if path.is_file():
        return path
    found = sorted(path.glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def scope_of(tf_op: str) -> str:
    """``jit(block)/mst.kv_pool.regroup/while/body/mst.attn.core/dot_general:``
    → ``mst.attn.core``: the deepest ``mst.*`` component, or ``unscoped``."""
    for part in reversed(tf_op.split("/")):
        if part.startswith("mst."):
            return part.rstrip(":")
    return UNSCOPED


def module_label(name: str) -> tuple[str, str]:
    """``jit_block(1234567)`` → ``("jit_block", "1234567")``."""
    head, _, rest = name.partition("(")
    return head, rest.rstrip(")")


def is_loop(name: str) -> bool:
    """Is this ``XLA Ops`` event (a whole HLO instruction) a ``while``?"""
    return bool(re.match(r"^%?while[.\d]*(\s|=|$)", name))


def reduce_events(events: list[dict]) -> dict:
    """``events``: ``{"name", "start", "end", "scope", "program", "bytes",
    "flops"}`` of one device line (picoseconds). Returns ``{"cells":
    {(program, scope): {"self_s", "bytes", "flops", "events"}},
    "unscoped_under": {scope: s}, "inside_while_s": s, "named_by_loop":
    {scope: s}}``."""
    cells: dict = {}
    under: dict = {}
    by_loop: dict = {}
    inside_while = 0.0
    stack: list[dict] = []

    def close(item):
        nonlocal inside_while
        self_s = max(0.0, (item["end"] - item["start"]) - item["children"]) / 1e12
        c = cells.setdefault((item["program"], item["scope"]),
                             {"self_s": 0.0, "bytes": 0, "flops": 0, "events": 0})
        c["self_s"] += self_s
        c["events"] += 1
        if not item["children"]:  # a container's figures are its body's again
            c["bytes"] += item["bytes"]
            c["flops"] += item["flops"]
        if item["scope"] == UNSCOPED:
            under[item["outer"]] = under.get(item["outer"], 0.0) + self_s
        if item["in_while"]:
            inside_while += self_s
        if item.get("loop_name") and not is_loop(item["name"]):
            by_loop[item["scope"]] = by_loop.get(item["scope"], 0.0) + self_s

    for ev in sorted(events, key=lambda e: (e["start"], -e["end"])):
        while stack and stack[-1]["end"] <= ev["start"]:
            close(stack.pop())
        outer, in_while = "top level", False
        if stack:
            top = stack[-1]
            top["children"] += min(ev["end"], top["end"]) - ev["start"]
            # the nearest enclosing operation that has a scope of its own
            outer = top["scope"] if top["scope"] != UNSCOPED else top["outer"]
            in_while = top["in_while"] or is_loop(top["name"])
        stack.append(dict(ev, children=0.0, outer=outer, in_while=in_while))
    while stack:
        close(stack.pop())
    return {"cells": cells, "unscoped_under": under,
            "inside_while_s": inside_while, "named_by_loop": by_loop}


def _stat_value(stat, stat_names):
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return stat_names.get(stat.ref_value, "")
    return getattr(stat, kind) if kind else None


def tick_spans(space) -> dict:
    """The scheduler tick's spans on the host plane (``mst.tick`` and its
    ``mst.<phase>``), reduced over the ticks that lie whole inside the trace:
    ``{"ticks": n, "tick_s": s, "phase_s": {span name: s}}``; a span counts
    when a recorded ``mst.tick`` contains it, so the tick cut by the trace's
    start leaves nothing half counted. ``{}`` without any ``mst.tick``."""
    ticks, others = [], []
    for plane in space.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                if name.startswith("mst."):
                    start = line.timestamp_ns * 1000 + ev.offset_ps
                    (ticks if name == "mst.tick" else others).append(
                        (start, start + ev.duration_ps, name))
    if not ticks:
        return {}
    ticks.sort()
    phase_s: dict = {}
    i = 0
    for start, end, name in sorted(others):
        while i < len(ticks) and ticks[i][1] < start:
            i += 1
        if i < len(ticks) and ticks[i][0] <= start and end <= ticks[i][1]:
            phase_s[name] = phase_s.get(name, 0.0) + (end - start) / 1e12
    return {"ticks": len(ticks), "phase_s": phase_s,
            "tick_s": sum(e - s for s, e, _ in ticks) / 1e12}


def read_device_events(space) -> dict:
    """``{device: [event, ...]}`` from the ``XLA Ops`` line of every TPU
    plane, each event with its scope, program name, bytes and flops."""
    out: dict = {}
    for plane in space.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        programs: dict = {}  # program_id → jit_<name>
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                name, ident = module_label(plane.event_metadata[ev.metadata_id].name)
                programs[ident] = name
        meta: dict = {}  # metadata_id → what every event of it shares

        def describe(metadata_id):
            md = plane.event_metadata[metadata_id]
            stats = {stat_names.get(s.metadata_id, ""): _stat_value(s, stat_names)
                     for s in md.stats}
            ident = str(stats.get("program_id") or "")
            tf_op = str(stats.get("tf_op") or "")
            return {
                "name": md.name,
                "scope": scope_of(tf_op),
                # the op_name ends in a loop's own name, not an operation's
                "loop_name": tf_op.rstrip(":").rsplit("/", 1)[-1] == "while",
                "program": programs.get(ident, f"program {ident}" if ident else "no program"),
                "bytes": int(stats.get("bytes_accessed") or 0),
                "flops": int(stats.get("flops") or 0),
            }

        events = out.setdefault(int(m.group(1)), [])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if ev.metadata_id not in meta:
                    meta[ev.metadata_id] = describe(ev.metadata_id)
                events.append(dict(meta[ev.metadata_id], start=ev.offset_ps,
                                   end=ev.offset_ps + ev.duration_ps))
    return out


def reduce(path: Path) -> dict:
    """``{"devices", "total_s", "scopes": {scope: {self_s, bytes, flops}},
    "programs": {program: {scope: {...}}}, "unscoped_under", "inside_while_s",
    "named_by_loop", "scoped": any mst.* scope seen at all, "tick_spans",
    "notes"}``; seconds, bytes and
    flops summed over the chips traced."""
    space = xplane_pb2().XSpace()
    space.ParseFromString(find_xplane(path).read_bytes())
    per_device = read_device_events(space)
    spans = tick_spans(space)
    scopes: dict = {}
    programs: dict = {}
    under: dict = {}
    by_loop: dict = {}
    inside_while = 0.0
    for events in per_device.values():
        r = reduce_events(events)
        inside_while += r["inside_while_s"]
        for src, dest in ((r["unscoped_under"], under), (r["named_by_loop"], by_loop)):
            for k, v in src.items():
                dest[k] = dest.get(k, 0.0) + v
        for (program, scope), c in r["cells"].items():
            for dest in (scopes.setdefault(scope, {}),
                         programs.setdefault(program, {}).setdefault(scope, {})):
                for key, val in c.items():
                    dest[key] = dest.get(key, 0) + val
    total = sum(c["self_s"] for c in scopes.values())
    top = sorted(scopes.items(), key=lambda kv: -kv[1]["self_s"])
    return {
        "devices": max(len(per_device), 1),
        "total_s": total,
        "scopes": scopes,
        "programs": programs,
        "unscoped_under": under,
        "inside_while_s": inside_while,
        "named_by_loop": by_loop,
        "scoped": any(s != UNSCOPED for s in scopes),
        "tick_spans": spans,
        "notes": [
            f"tick spans: {spans['ticks']} whole ticks, {spans['tick_s']:.3f} s; " + ", ".join(
                f"{k} {v:.3f}" for k, v in sorted(spans["phase_s"].items(), key=lambda kv: -kv[1]))
            if spans else "tick spans: none",
            "scopes: " + ", ".join(
                f"{s} {100 * c['self_s'] / total:.2f}%" for s, c in top) if total else "scopes: none",
            "unscoped time ran inside: " + (", ".join(
                f"{s} {100 * v / total:.2f}%"
                for s, v in sorted(under.items(), key=lambda kv: -kv[1])) or "nothing"),
            f"inside a while: {100 * inside_while / total:.2f}% of device time"
            if total else "inside a while: nothing",
            "carrying a while's own name, not an operation's: " + (", ".join(
                f"{s} {100 * v / total:.2f}%"
                for s, v in sorted(by_loop.items(), key=lambda kv: -kv[1])) or "nothing"),
        ],
    }


# --------------------------------------------------------------------------
# for the readers of one run (benchmarks/layer_metrics/scope_share.*.py)

_RUNS: dict = {}  # profile directory → reduction, or None where there is none


def profile_dir(ctx: dict) -> Path:
    """Where ``run.py`` has the launcher put the traced run's profile:
    ``<checkout>/.bench_work/<cell name>/profile``."""
    return ROOT / ".bench_work" / ctx["cell"]["name"] / "profile"


def for_run(ctx: dict):
    """The reduction of this run's profile, made once and kept for the
    other readers; ``None`` where there is nothing to read (an untraced
    run, no profile on disk). A program from before the scopes and the
    tick spans has ``scoped`` false and ``tick_spans`` empty."""
    if not ctx.get("trace"):
        return None
    where = profile_dir(ctx)
    key = str(where)
    if key not in _RUNS:
        _RUNS[key] = None
        if sorted(where.glob("**/*.xplane.pb")):
            result, secs = reduce_profile("scope reduction", Path(__file__).resolve(),
                                          where, SCOPE_REDUCE_LIMIT_S)
            ctx.setdefault("reduce_seconds", {})["scope_reduce"] = secs
            for line in result["notes"]:
                print(f"[scope_reduce] {line}", flush=True)
            _RUNS[key] = result
    return _RUNS[key]


def share(ctx: dict, *prefixes: str, exact: tuple = ()):
    """Percent of device time in the scopes that start with one of
    ``prefixes`` or equal one of ``exact``; ``None`` with nothing to read
    (no profile, or a program that wrote no ``mst.*`` scope at all)."""
    red = for_run(ctx)
    if red is None or not red["scoped"] or not red["total_s"]:
        return None
    secs = sum(c["self_s"] for s, c in red["scopes"].items()
               if s in exact or any(s.startswith(p) for p in prefixes))
    return 100.0 * secs / red["total_s"]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(reduce(Path(argv[0]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
