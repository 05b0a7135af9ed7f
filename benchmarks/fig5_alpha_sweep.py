"""Fig. 5: effect of the relative reorganization cost alpha.

Paper claims: total gains shrink as alpha grows; the number of layout changes
drops (35 @ alpha=10 -> 18 @ alpha=300 in the paper); the decrease in total
cost is non-monotonic because the algorithm adapts its switching strategy.
"""
from __future__ import annotations

from typing import List

from benchmarks import common


ALPHAS = (10.0, 40.0, 80.0, 170.0, 300.0)


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    total = common.TOTAL_QUERIES // (4 if quick else 1)
    data, stream = common.build_bench("tpch", total_queries=total)
    for alpha in ALPHAS:
        res = common.run_methods(data, stream, "qdtree", alpha=alpha,
                                 methods=("OREO", "Static"))
        r = res["OREO"]
        static = res["Static"]
        gain = 100.0 * (static.total_cost - r.total_cost) / static.total_cost
        rows.append(common.csv_row(
            f"fig5.alpha_{int(alpha)}",
            r.info.get("wall_seconds", 0) * 1e6 / len(stream),
            f"total={r.total_cost:.1f};moves={r.num_reorgs};"
            f"gain_vs_static_pct={gain:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
