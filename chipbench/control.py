#!/usr/bin/env python3
"""The control of the correctness check: a lower-precision decision plane.

The reference is put in the program's place with every zone map and query
bound rounded to bfloat16 (the step below the deployment's float32), and
its trace is compared with the float32 reference's by the same comparison
and limits a run uses.  The control has to come out not correct: it shows
that the limits would catch such a change.  The benchmark's own runs do
not run it.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3 \\
        --seconds <s>
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run, tables  # noqa: E402


def readings(cell: "run.Cell", seed: int, seconds: float,
             rows: Optional[int] = None, processes: bool = True
             ) -> List[tuple]:
    """(name, value, limit) of the control against the reference, on the
    stream a run of ``cell`` with ``seed`` and ``seconds`` serves."""
    cfg = cell.config
    rows = cfg["rows"] if rows is None else rows
    tids = [f"t{k}" for k in range(cfg["tenants"])]
    lo, hi = None, None
    for k in range(cfg["tenants"]):
        data = tables.make(cfg["table"], rows, seed, cfg["tenants"], k)
        lo = data.min(axis=0) if lo is None else np.minimum(lo,
                                                           data.min(axis=0))
        hi = data.max(axis=0) if hi is None else np.maximum(hi,
                                                           data.max(axis=0))
        del data
    stream = run.make_stream(cell, tids, lo, hi, seconds, seed)
    want = run.reference_traces(cell, stream, seed, rows,
                                processes=processes)
    got = run.reference_traces(cell, stream, seed, rows,
                               precision="bfloat16", processes=processes)
    return run.compare(got, want, 0, cell.limits)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    failed = 0
    for seed in args.seeds:
        checks = readings(cell, seed, args.seconds, rows=args.rows)
        bad = [k for k, v, lim in checks if v > lim]
        failed += bool(bad)
        print(f"control seed={seed}: " + "; ".join(
            f"{k}={v!r} (limit {lim!r})" for k, v, lim in checks)
            + f" -> {'not correct' if bad else 'CORRECT'}", flush=True)
    print(f"control: not correct on {failed} of {len(args.seeds)} seeds")
    return 0 if failed == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
