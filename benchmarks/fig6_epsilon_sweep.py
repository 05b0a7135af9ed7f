"""Fig. 6: effect of the layout-admission distance threshold epsilon.

Paper claims: larger epsilon shrinks the dynamic state space and slightly
raises query cost; overall performance is not very sensitive to epsilon.
"""
from __future__ import annotations

from typing import List

from benchmarks import common
from repro.core import OreoConfig, build_default_layout, make_generator
from repro.core.layout_manager import LayoutManagerConfig
from repro.engine import InMemoryBackend, LayoutEngine, OreoPolicy

EPSILONS = (0.02, 0.05, 0.08, 0.15, 0.30)


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    total = common.TOTAL_QUERIES // (4 if quick else 1)
    data, stream = common.build_bench("tpch", total_queries=total)
    gen = make_generator("qdtree")
    for eps in EPSILONS:
        cfg = OreoConfig(alpha=common.ALPHA, gamma=1.0,
                         manager=LayoutManagerConfig(
                             target_partitions=common.PARTITIONS,
                             epsilon=eps))
        policy = OreoPolicy(data, build_default_layout(
            0, data, common.PARTITIONS), gen, cfg)
        res = LayoutEngine(policy, InMemoryBackend(data),
                           delta=cfg.delta).run(stream)
        rows.append(common.csv_row(
            f"fig6.epsilon_{eps}", 0.0,
            f"total={res.total_cost:.1f};query={res.total_query_cost:.1f};"
            f"reorg={res.total_reorg_cost:.1f};"
            f"admitted={res.info['candidates_admitted']};"
            f"max_states={res.info['max_state_space']}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
