#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine that holds the chips the cell
asks for.  The cell, its configuration and traffic mix are found by name
through BENCHMARK.json.  The last line of standard output is the result
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last the numbers compared under
``checks``); set-up parts, generator lateness and the checks go to standard
error.  Without a TPU, or without the program's ``src/`` beside the
benchmark, it prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # this directory must not shadow modules by name; the checkout's root
    # and the program's src/ are what imports resolve against
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import harness, manifest

    t_start = harness.process_start_perf()
    try:
        import repro  # noqa: F401  the system under test
        cell = manifest.cell(args.workload)
    except (ImportError, OSError, KeyError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    return harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start=t_start)


if __name__ == "__main__":
    sys.exit(main())
