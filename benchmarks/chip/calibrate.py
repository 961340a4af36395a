#!/usr/bin/env python3
"""Readings that set a cell's ``logit_gap`` limit, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 [--control]

For each seed, one process serves the cell (weights from that seed, its
traffic, a short window) and compares a sample of the finished requests
with the float32 reference, exactly as a benchmark run does.  With
``--control`` it also reads the float8 control on the same prompts and
served tokens: the gap of the token the control puts first.  One JSON
line per seed.  The limit lies above the program's largest reading over
a dozen seeds or more and below the control's smallest.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import compare, harness, manifest

    session = harness.Session(manifest.cell(args.workload), reuse_engine=True)
    if session.error:
        print(f"calibrate: {session.error}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.serve(session, seed, args.seconds, False)
        picked = compare.sample(run, seed)
        got = compare.gaps(run, seed, picked, control=args.control) if picked else {}
        print(json.dumps({"workload": args.workload, "seed": seed, **got,
                          "requests": len(picked),
                          "longest": max((r.prompt_len + len(r.tokens) for r in picked),
                                         default=0)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
