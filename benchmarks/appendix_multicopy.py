"""Paper §VIII / Appendix-D extension: storage budget for kappa simultaneous
layout copies.  Queries are serviced by the cheapest held copy; movement
replaces one copy.  Measures the storage-for-query-cost tradeoff on the
TPC-H-like workload.
"""
from __future__ import annotations

from typing import List

from benchmarks import common
from repro.core import build_default_layout, layouts, make_generator
from repro.core.extensions import MultiCopyDUMTS


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    total = common.TOTAL_QUERIES // (4 if quick else 2)
    data, stream = common.build_bench("tpch", total_queries=total)
    gen = make_generator("qdtree")

    # Precompute a fixed state space (per-template layouts) so kappa is the
    # only variable.
    by_template = {}
    for q in stream.queries:
        by_template.setdefault(q.template_id, []).append(q)
    store = {}
    for tid, qs in sorted(by_template.items()):
        lay = gen(tid, data, qs[:150], common.PARTITIONS)
        lay.materialize(data)
        store[tid] = lay
    store[len(store)] = build_default_layout(len(store), data,
                                             common.PARTITIONS)

    for kappa in (1, 2, 3):
        d = MultiCopyDUMTS(alpha=common.ALPHA, initial_states=sorted(store),
                           kappa=kappa, seed=0)
        qcost = 0.0
        for q in stream.queries:
            costs = {sid: float(layouts.eval_cost(
                lay.serving_meta(), q.lo, q.hi))
                for sid, lay in store.items()}
            _, c = d.observe(costs)
            qcost += c
        total_cost = qcost + d.total_reorg_cost
        rows.append(common.csv_row(
            f"appendixD.kappa_{kappa}", 0.0,
            f"total={total_cost:.1f};query={qcost:.1f};"
            f"reorg={d.total_reorg_cost:.1f};moves={d.moves};"
            f"storage_copies={kappa}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
