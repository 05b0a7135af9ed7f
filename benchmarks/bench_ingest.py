"""Ingest benchmark: clustering-debt-aware compaction vs the two naive arms.

The benchmark axis the streaming ingest plane opens
(:mod:`repro.engine.ingest`): for every registered ingest scenario
(:data:`repro.core.workload.INGEST_SCENARIOS`), a multi-tenant fleet of
OREO tenants runs the same interleaved read/write event stream three
times, differing only in the compaction policy:

* **never**  — ``IngestConfig(auto_compact=False)``: appended rows stay
  unclustered delta partitions forever; every overlapping scan keeps
  paying for them;
* **always** — ``IngestConfig(debt_threshold=0.0)``: recluster eagerly
  at the first scan after every append, paying the full α charge per
  compaction no matter how little debt the deltas have accrued;
* **debt**   — ``IngestConfig(debt_threshold=1.0)`` (the default):
  compact only once the *realized* excess scan cost over a
  hypothetically-compacted table has itself reached α — the same
  pay-for-itself discipline D-UMTS applies to drift reorganizations.

All three arms see identical events (queries AND appended batches) and
identical drift-reorg decisions up to the extra compaction charges; the
combined query+reorg cost difference isolates the compaction policy.
Costs are deterministic given the seeds, which is what lets
``check_regression.py`` gate on the ``cost_ratio_vs_debt_aware`` grid
(ratio > 1: the debt-aware arm wins).

``--smoke`` is the CI configuration; the checked-in ``ingest_smoke``
section of ``BENCH_ingest.json`` holds the baseline ratios the
regression gate compares against.
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from typing import Dict, List

import numpy as np

from repro.core import OreoConfig, build_default_layout, make_generator
from repro.core import layout_manager as lm
from repro.core.workload import INGEST_SCENARIOS, make_ingest_scenario
from repro.engine import (FleetEngine, InMemoryBackend, IngestConfig,
                          LayoutEngine, OreoPolicy, UnlimitedScheduler)
from repro.launch.compile_cache import enable_compile_cache

SCENARIOS = sorted(INGEST_SCENARIOS)

ARMS = {
    "never": IngestConfig(auto_compact=False),
    "always": IngestConfig(debt_threshold=0.0),
    "debt": IngestConfig(debt_threshold=1.0),
}


def make_tenant_data(num_tenants: int, rows: int, cols: int,
                     seed: int) -> Dict[str, np.ndarray]:
    return {f"t{t}": np.random.default_rng(seed + t).uniform(
        0, 100, size=(rows, cols)) for t in range(num_tenants)}


def tenant_engine(data: np.ndarray, alpha: float, delta: int,
                  partitions: int, ingest: IngestConfig) -> LayoutEngine:
    cfg = OreoConfig(
        alpha=alpha, seed=0, delta=delta,
        manager=lm.LayoutManagerConfig(target_partitions=partitions,
                                       window_size=80, gen_every=40))
    policy = OreoPolicy(data,
                        build_default_layout(0, data, partitions, sort_col=0),
                        make_generator("qdtree"), cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta,
                        ingest=ingest)


def fleet_ingest_stats(fleet: FleetEngine) -> Dict:
    appended = pending = compactions = 0
    debt = excess = 0.0
    for tid in fleet.tenant_ids:
        s = fleet.tenant(tid).ingest_stats()
        appended += s["ingested_rows"]
        pending += s["pending_rows"]
        compactions += len(s["compactions"])
        debt += s["clustering_debt"]
        excess += s["total_excess"]
    return {"rows_appended": appended, "rows_pending": pending,
            "compactions": compactions,
            "clustering_debt": round(debt, 3),
            "total_excess": round(excess, 3)}


def bench_cell(scenario: str, tenant_data, col_lo, col_hi,
               queries_per_tenant: int, alpha: float, delta: int,
               partitions: int, seed: int) -> Dict:
    fs = make_ingest_scenario(scenario, col_lo, col_hi,
                              num_tenants=len(tenant_data),
                              queries_per_tenant=queries_per_tenant,
                              seed=seed)
    row: Dict = {
        "scenario": scenario,
        "tenants": len(fs.tenant_ids),
        "events": len(fs),
        "queries_per_tenant": queries_per_tenant,
        "rows_appended": fs.total_appended_rows,
        "arms": {},
    }
    combined: Dict[str, float] = {}
    for arm, cfg in ARMS.items():
        fleet = FleetEngine(
            {tid: tenant_engine(tenant_data[tid], alpha, delta, partitions,
                                cfg)
             for tid in fs.tenant_ids}, UnlimitedScheduler())
        t0 = time.perf_counter()
        res = fleet.run(fs)
        wall = time.perf_counter() - t0
        stats = fleet_ingest_stats(fleet)
        combined[arm] = res.total_cost
        row["arms"][arm] = {
            "total_cost": round(res.total_cost, 3),
            "query_cost": round(res.total_query_cost, 3),
            "reorg_cost": round(res.total_reorg_cost, 3),
            "reorgs": res.num_reorgs,
            "events_per_sec": round(res.ticks / wall, 1),
            **stats,
        }
    # the never arm must end with every appended row still unclustered
    assert row["arms"]["never"]["compactions"] == 0
    row["cost_ratio_vs_debt_aware"] = {
        arm: round(combined[arm] / max(combined["debt"], 1e-12), 4)
        for arm in ("never", "always")}
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: all ingest scenarios, small fleet")
    ap.add_argument("--out", default="BENCH_ingest.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        tenants, rows, cols, qpt = 3, 2_000, 6, 200
        alpha, delta, partitions = 2.5, 5, 8
    else:
        tenants, rows, cols, qpt = 4, 8_000, 8, 1_000
        alpha, delta, partitions = 4.0, 10, 16

    tenant_data = make_tenant_data(tenants, rows, cols, seed=100)
    col_lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    col_hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)

    results: List[Dict] = []
    ratios: Dict[str, Dict[str, float]] = {}
    wins = {"never": 0, "always": 0}
    for scenario in SCENARIOS:
        row = bench_cell(scenario, tenant_data, col_lo, col_hi, qpt,
                         alpha, delta, partitions, seed=7)
        results.append(row)
        ratios[scenario] = row["cost_ratio_vs_debt_aware"]
        for arm in wins:
            if ratios[scenario][arm] > 1.0:
                wins[arm] += 1
        arms = row["arms"]
        print(f"{scenario:14s} "
              f"never={arms['never']['total_cost']:9.1f} "
              f"always={arms['always']['total_cost']:9.1f} "
              f"debt={arms['debt']['total_cost']:9.1f} "
              f"ratios: never x{ratios[scenario]['never']:.3f} "
              f"always x{ratios[scenario]['always']:.3f} "
              f"(compactions={arms['debt']['compactions']})", flush=True)
    print(f"debt-aware beats never in {wins['never']}/{len(SCENARIOS)} "
          f"and always in {wins['always']}/{len(SCENARIOS)} scenarios")
    # the headline claim the ingest plane ships under: debt-aware wins
    # the combined cost in at least 4/5 scenarios against BOTH arms
    assert wins["never"] >= 4 and wins["always"] >= 4, \
        f"debt-aware arm lost its edge: {wins}"

    payload = {
        "benchmark": "ingest",
        "units": "combined query+reorg cost (fraction-of-table + alpha per "
                 "reorg/compaction); ratio > 1 means debt-aware wins",
        "config": {
            "tenants": tenants, "rows": rows, "columns": cols,
            "queries_per_tenant": qpt, "alpha": alpha, "delta": delta,
            "partitions": partitions, "smoke": bool(args.smoke),
            "platform": platform.platform(), "numpy": np.__version__,
        },
        "results": results,
        "wins_vs_debt_aware": {**wins, "scenarios": len(SCENARIOS)},
        "cost_ratio_vs_debt_aware": ratios,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
