"""Find an open-loop cell's knee in one call: ``python3 benchmarks/sweep.py --workload
<cell> --rates 1 2 3 4 --seconds 30``. A tool, not part of a run.

One server; the rates in ascending order, each for ``--seconds`` with the
cell's own traffic mix (lead-in included); stops at the first rate whose
backlog grows. A rate is sustained when no request failed, the scheduler's
backlog (queued plus active requests) is no more than 2 larger at the end of
the window than at its start, and the time to first token shows no rising
trend (the median over the window's second half is under 1.5 times that of
its first half plus half a second). Requests here live for ten seconds and
more, so "completed inside the window" is no criterion. The knee is the
highest sustained rate; the cell then runs at about four fifths of it.
Prints the table PERF.md records.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import run, stats, traffic  # noqa: E402
from benchmarks.config import server_flag  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--benchmark", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--keep-going", action="store_true",
                    help="do not stop at the first rate that is not sustained")
    args = ap.parse_args(argv)

    found = run.load_cell(Path(args.benchmark), args.workload)
    cell, config, mix = found["cell"], found["config"], found["mix"]
    max_context = int(server_flag(config, "--max-seq", 4096))

    launcher = run.Launcher(found["config_path"], args.seed, 0,
                            run.WORK / (cell["name"] + ".sweep"))
    rows = []
    try:
        launcher.wait_healthy()
        rng = np.random.default_rng([args.seed, 999])
        warm = traffic.Planned(0.0, 300, 20, traffic.words(rng, 300, config["vocab_size"]))
        if not run.stream_request(launcher.port, warm, time.monotonic())["ok"]:
            raise run.RunFailed("warm-up request failed")
        for i, rate in enumerate(sorted(args.rates)):
            planned, lead = traffic.plan(mix, {"rate": rate}, args.seconds, args.seed + i,
                                         config["vocab_size"], max_context)
            recs, _lead, obs, w0, w1 = run.drive(launcher, planned, lead, args.seconds, False)
            backlog = [stats.scalar(m, "mst_batch_slots_active", 0)
                       + stats.scalar(m, "mst_batch_queue_depth", 0)
                       for m in (obs.before, obs.after)]
            ttft = [(r["first"] - r["due"]) * 1e3 for r in recs if r["ok"]]
            half = (w1 - w0) / 2
            head = [(r["first"] - r["due"]) * 1e3 for r in recs if r["ok"] and r["due"] < w0 + half]
            tail = [(r["first"] - r["due"]) * 1e3 for r in recs if r["ok"] and r["due"] >= w0 + half]
            tpot = [(r["last"] - r["first"]) / (r["got"] - 1) * 1e3
                    for r in recs if r["ok"] and r["got"] > 1]
            row = {
                "rate": rate, "due": len(recs), "failed": sum(not r["ok"] for r in recs),
                "backlog_start": backlog[0], "backlog_end": backlog[1],
                "ttft_ms_p50": stats.percentile(ttft, 50) if ttft else None,
                "ttft_ms_p95": stats.percentile(ttft, 95) if ttft else None,
                "ttft_ms_p50_first_half": stats.percentile(head, 50) if head else None,
                "ttft_ms_p50_second_half": stats.percentile(tail, 50) if tail else None,
                "tpot_ms_p50": stats.percentile(tpot, 50) if tpot else None,
                "tpot_ms_p95": stats.percentile(tpot, 95) if tpot else None,
                "out_tok_s": stats.tokens_in_window(recs + _lead, w0, w1, True) / (w1 - w0),
                "queue_wait_ms_p50": (stats.histogram_quantile(stats.histogram_delta(
                    obs.before, obs.after, "mst_queue_wait_seconds"), 0.5) or 0.0) * 1e3,
            }
            row["sustained"] = bool(
                row["failed"] == 0 and backlog[1] <= backlog[0] + 2
                and (not head or not tail or row["ttft_ms_p50_second_half"]
                     < 1.5 * row["ttft_ms_p50_first_half"] + 500.0)
            )
            rows.append(row)
            print(json.dumps(row), flush=True)
            # let the backlog of a rate that was too high drain away
            while stats.scalar(launcher.metrics(), "mst_batch_slots_active", 0) or \
                    stats.scalar(launcher.metrics(), "mst_batch_queue_depth", 0):
                time.sleep(0.5)
            if not row["sustained"] and not args.keep_going:
                break
    finally:
        launcher.stop()
    sustained = [r["rate"] for r in rows if r["sustained"]]
    knee = max(sustained) if sustained else None
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "cell_rate": None if knee is None else round(0.8 * knee, 2)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
