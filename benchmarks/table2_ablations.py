"""Table II: transition-distribution gamma, sliding-window vs reservoir
candidate generation, and background-reorganization delay Delta.

Paper claims reproduced: gamma>0 cuts reorganization cost ~17-28% with flat
query cost; reservoir sampling raises query cost up to ~22% vs the sliding
window; Delta=alpha adds ~7-12% query cost with unchanged reorg cost.
"""
from __future__ import annotations

from typing import List

from benchmarks import common


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    total = common.TOTAL_QUERIES // (4 if quick else 2)
    datasets = ("tpch",) if quick else ("tpch", "tpcds", "telemetry")
    for ds in datasets:
        data, stream = common.build_bench(ds, total_queries=total)

        # gamma sweep (transition distribution; gamma=1 is the default row).
        for gamma in (0.0, 1.0, 2.0, 3.0):
            r = common.run_methods(data, stream, "qdtree", methods=("OREO",),
                                   gamma=gamma)["OREO"]
            rows.append(common.result_csv(f"table2.{ds}.gamma_{gamma}", r,
                                          len(stream)))

        # candidate-source ablation: SW vs RS vs SW+RS.
        for src in ("sw", "rs", "sw+rs"):
            r = common.run_methods(data, stream, "qdtree", methods=("OREO",),
                                   candidate_source=src)["OREO"]
            rows.append(common.result_csv(
                f"table2.{ds}.source_{src.replace('+', '_')}", r,
                len(stream)))

        # reorganization delay Delta (in queries; alpha=80 -> Delta=80 row).
        for delta in (0, 40, 80):
            r = common.run_methods(data, stream, "qdtree", methods=("OREO",),
                                   delta=delta)["OREO"]
            rows.append(common.result_csv(f"table2.{ds}.delta_{delta}", r,
                                          len(stream)))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
