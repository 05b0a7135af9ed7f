"""Table I: measured relative cost of reorganization vs a full-scan query.

The paper measures Spark+Parquet on local disk: alpha in 60-100x across file
sizes 16MB..4GB.  We measure the same two operations on this host's partition
store (numpy-compressed partitions on local disk): full table scan vs full
reorganization (read + re-route + re-compress + write), across table sizes.
The measured ratio feeds the cost model's alpha (config default 80).
"""
from __future__ import annotations

import tempfile
from typing import List

import numpy as np

from benchmarks import common
from repro.core import build_default_layout, make_generator, make_templates
from repro.data.partition_store import PartitionStore

SIZES_MB = (4, 16, 64)      # synthetic table sizes (npz-compressed scale)


def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    rng = np.random.default_rng(0)
    sizes = SIZES_MB[:2] if quick else SIZES_MB
    for mb in sizes:
        n_rows = int(mb * 1024 * 1024 / (12 * 8))      # 12 f64 cols
        data = rng.uniform(0, 100, (n_rows, 12))
        templates = make_templates(3, 12, rng)
        queries = [templates[0].sample(rng, data.min(0), data.max(0))
                   for _ in range(50)]
        with tempfile.TemporaryDirectory() as td:
            store = PartitionStore(td + "/table")
            init = build_default_layout(0, data, common.PARTITIONS)
            store.write(data, init)
            # Full-scan time (averaged).
            scans = [store.full_scan_seconds() for _ in range(3)]
            scan_s = float(np.median(scans))
            # Reorganization: read + BID update + shuffle + compress + write.
            gen = make_generator("qdtree")
            layout = gen(1, data, queries, common.PARTITIONS)
            reorg_s = store.reorganize(layout).seconds
            alpha = reorg_s / max(scan_s, 1e-9)
            rows.append(common.csv_row(
                f"table1.size_{mb}mb", scan_s * 1e6,
                f"query_s={scan_s:.3f};reorg_s={reorg_s:.2f};"
                f"alpha={alpha:.1f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("\n".join(run()))
