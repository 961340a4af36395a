#!/usr/bin/env python3
"""Knee sweep of a cell's arrival rate, on the chip.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 2,4,6 --seconds 20 --seed 7

Serves the cell's traffic at each offered rate in turn (one process; the
programs compile once) and prints one JSON line per rate: delivered
tokens/s, the latency tails, and the backlog (requests due but without a
first token) at the window's open and close and its slope over the
window.  The knee is the highest rate whose backlog, and every lower
rate's, does not grow across the window (by more than a tenth of the
requests due in it, and more than two); the cell runs at four fifths of
it.  ``--apply`` writes that rate into the cell's traffic file.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def backlog(run, t: float) -> int:
    return sum(1 for r in run.requests
               if r.due <= t and not (r.token_times and r.token_times[0] <= t))


def knee(rows) -> float:
    """Highest rate below which no swept rate's backlog grew; 0 if the
    lowest already grew."""
    best = 0.0
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if row["grows"]:
            break
        best = row["rate_per_s"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--apply", action="store_true", help="write 0.8 x knee into the mix")
    args = ap.parse_args(argv)
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np

    from benchmarks.chip import harness, manifest

    cell = manifest.cell(args.workload)
    session = harness.Session(cell, reuse_engine=True)
    if session.error:
        print(f"sweep: {session.error}", file=sys.stderr)
        return 2
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = copy.deepcopy(cell.traffic)
        traffic["rate_per_s"] = rate
        run = harness.serve(session, args.seed, args.seconds, False, traffic=traffic)
        lo, hi = run.window
        ts = np.linspace(lo, hi, 11)
        b = [backlog(run, t) for t in ts]
        slope = float(np.polyfit(ts - lo, b, 1)[0])
        growth = slope * (hi - lo)
        row = {"workload": cell.name, "rate_per_s": rate, "backlog_open": b[0],
               "backlog_close": b[-1], "backlog_slope_per_s": slope,
               "grows": bool(growth > max(2.0, 0.1 * rate * (hi - lo)))}
        for m in cell.metrics(False):
            v = m.read(run)
            row[m.name] = v
        row["finished"] = sum(1 for r in run.requests if r.finish)
        rows.append(row)
        print(json.dumps(row), flush=True)
    k = knee(rows)
    rate = round(0.8 * k, 3)
    print(json.dumps({"workload": cell.name, "knee_per_s": k, "cell_rate_per_s": rate}),
          flush=True)
    if args.apply and rate > 0:
        bench = manifest.load_manifest()
        mix = {w["name"]: w for w in bench["workloads"]}[cell.name]["traffic"]
        path = manifest.HERE / "traffic" / f"{mix}.json"
        text = re.sub(r'"rate_per_s": [0-9.]+', f'"rate_per_s": {rate}', path.read_text(), 1)
        path.write_text(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
