"""Fig. 3: total query + reorganization cost, OREO vs Static/Greedy/Regret,
on three datasets x two layout techniques (Qd-tree, Z-order).

Paper claims reproduced here: OREO beats the static optimized layout by up to
~32% (Qd-tree), sits between Greedy (min query cost, huge reorg cost) and
Regret (conservative), and stays dynamic under Z-order where Greedy/Regret
stop moving.
"""
from __future__ import annotations

from typing import List

from benchmarks import common


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    datasets = ("tpch", "tpcds", "telemetry")
    techniques = ("qdtree", "zorder")
    total = common.TOTAL_QUERIES // (4 if quick else 1)
    summary = {}
    for ds in datasets:
        data, stream = common.build_bench(ds, total_queries=total)
        for tech in techniques:
            res = common.run_methods(data, stream, tech)
            for method, r in res.items():
                rows.append(common.result_csv(
                    f"fig3.{ds}.{tech}.{method.replace(' ', '_')}", r,
                    len(stream)))
            static = res["Static"].total_cost
            oreo = res["OREO"].total_cost
            summary[(ds, tech)] = 100.0 * (static - oreo) / static
    for (ds, tech), imp in summary.items():
        rows.append(common.csv_row(
            f"fig3.{ds}.{tech}.improvement_vs_static_pct", 0.0,
            f"value={imp:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
