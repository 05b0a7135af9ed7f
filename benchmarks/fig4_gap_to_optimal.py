"""Fig. 4: OREO vs MTS-Optimal (fixed precomputed state space) and
Offline-Optimal (full workload knowledge, switches at template boundaries).

Paper claims: OREO's query cost within ~14-17% of MTS-Optimal; 44-74% above
Offline-Optimal; comparable number of layout changes.
"""
from __future__ import annotations

from typing import List

from benchmarks import common


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    total = common.TOTAL_QUERIES // (4 if quick else 1)
    for ds in ("tpch", "tpcds"):
        data, stream = common.build_bench(ds, total_queries=total)
        res = common.run_methods(
            data, stream, "qdtree",
            methods=("OREO", "MTS Optimal", "Offline Optimal"))
        for method, r in res.items():
            rows.append(common.result_csv(
                f"fig4.{ds}.{method.replace(' ', '_')}", r, len(stream)))
        gap_mts = 100.0 * (res["OREO"].total_query_cost
                           / res["MTS Optimal"].total_query_cost - 1.0)
        gap_off = 100.0 * (res["OREO"].total_query_cost
                           / res["Offline Optimal"].total_query_cost - 1.0)
        rows.append(common.csv_row(f"fig4.{ds}.query_gap_vs_mts_opt_pct",
                                   0.0, f"value={gap_mts:.1f}"))
        rows.append(common.csv_row(f"fig4.{ds}.query_gap_vs_offline_pct",
                                   0.0, f"value={gap_off:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
