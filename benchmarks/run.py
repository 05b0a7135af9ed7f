"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--quick`` shrinks query
counts ~4x for smoke runs; the full run reproduces the paper's Fig. 3/4/5/6
and Tables I/II at reduced (documented) scale plus kernel rooflines.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# Invoked as ``python benchmarks/run.py``, sys.path[0] is benchmarks/
# itself — put the repo root first so the ``benchmarks`` package resolves.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig3,table1")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (appendix_multicopy, bench_kernels,
                            fig3_end_to_end, fig4_gap_to_optimal,
                            fig5_alpha_sweep, fig6_epsilon_sweep,
                            table1_alpha, table2_ablations)
    suites = {
        "fig3": fig3_end_to_end.run,
        "fig4": fig4_gap_to_optimal.run,
        "fig5": fig5_alpha_sweep.run,
        "fig6": fig6_epsilon_sweep.run,
        "table1": table1_alpha.run,
        "table2": table2_ablations.run,
        "appendixD": appendix_multicopy.run,
        "kernels": bench_kernels.run,
    }
    if args.only:
        keep = set(args.only.split(","))
        suites = {k: v for k, v in suites.items() if k in keep}

    print("name,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for row in fn(quick=args.quick):
                print(row, flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception as e:  # keep the suite going; record the failure
            import traceback
            traceback.print_exc()
            print(f"{name}.FAILED,0,error={type(e).__name__}")


if __name__ == "__main__":
    main()
