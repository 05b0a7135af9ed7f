"""Reorg benchmark: atomic vs incremental migration under shared budgets.

The new benchmark axis the incremental reorganization plane opens
(:mod:`repro.engine.reorg`): for every registered drift scenario, a
multi-tenant fleet of OREO tenants runs twice under the *same* shared
maintenance budget —

* **atomic-deferred** — today's wholesale semantics: a reorganization
  banks one whole budget grant (a token buys a full table rewrite) and
  the fleet serves the stale layout until the swap lands;
* **incremental** — ``incremental=True`` engines under the same budget
  denominated in *rows* (``TokenBucketScheduler(rows_per_token=...)``):
  micro-moves trickle at the equivalent row bandwidth, and hybrid-layout
  serving realizes skipping benefit move by move while the migration is
  still in flight.

Both arms make bit-identical decisions (decisions are metadata-only and
never read the serving layout), charge bit-identical reorganization cost
(α at decision time; each completed migration's charge ledger telescopes
to exactly α — asserted here), and get the same rows/tick of maintenance
bandwidth — so the combined query+reorg cost difference isolates the
value of serving hybrid layouts early.  Costs are deterministic given the
seeds, which is what lets ``check_regression.py`` gate on the
``cost_ratio_atomic_over_incremental`` grid (ratio > 1: incremental
wins).

An ``unlimited``-budget cell rides along as a self-check: with no budget
pressure the two arms must land bitwise-identical totals.

``--smoke`` is the CI configuration; the checked-in ``reorg_smoke``
section of ``BENCH_reorg.json`` holds the baseline ratios the regression
gate compares against.
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
from repro.core.workload import make_drift_scenario
from repro.engine import (FleetEngine, InMemoryBackend, LayoutEngine,
                          OreoPolicy, TokenBucketScheduler,
                          UnlimitedScheduler)
from repro.launch.compile_cache import enable_compile_cache

SCENARIOS = ["sudden_shift", "gradual_drift", "cyclic_diurnal",
             "flash_crowd", "template_churn"]


def make_tenant_data(num_tenants: int, rows: int, cols: int,
                     seed: int) -> Dict[str, np.ndarray]:
    return {f"t{t}": np.random.default_rng(seed + t).uniform(
        0, 100, size=(rows, cols)) for t in range(num_tenants)}


def tenant_engine(data: np.ndarray, alpha: float, delta: int,
                  partitions: int, incremental: bool) -> LayoutEngine:
    cfg = OreoConfig(
        alpha=alpha, seed=0, delta=delta,
        manager=lm.LayoutManagerConfig(target_partitions=partitions,
                                       window_size=80, gen_every=40))
    policy = OreoPolicy(data, build_default_layout(0, data, partitions),
                        make_generator("qdtree"), cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta,
                        incremental=incremental)


def budget_factories(label: str, rate: float, rows: int):
    """(atomic scheduler, incremental scheduler) under one shared budget.

    ``bucket``: the atomic arm banks one token per wholesale swap at
    ``rate`` tokens/tick; the incremental arm gets the row-denominated
    equivalent — ``rate * rows`` rows/tick, up to one banked migration —
    so both arms have the same maintenance bandwidth and the comparison
    isolates hybrid serving.
    """
    if label == "unlimited":
        return UnlimitedScheduler, UnlimitedScheduler
    if label == "bucket":
        return (lambda: TokenBucketScheduler(rate=rate, capacity=1.0,
                                             initial=0.0),
                lambda: TokenBucketScheduler(rate=rate * rows,
                                             capacity=float(rows),
                                             initial=0.0,
                                             rows_per_token=1.0))
    raise ValueError(label)


def ledger_stats(fleet: FleetEngine) -> Dict:
    migrations = completed = moves = rows_moved = 0
    charged = 0.0
    exact = True
    for tid in fleet.tenant_ids:
        ex = fleet.tenant(tid).reorg_executor
        if ex is None:
            continue
        for m in ex.migrations:
            migrations += 1
            rows_moved += m.moved_rows
            moves += m.moves_done
            charged += m.charged
            if m.completed_at >= 0:
                completed += 1
                exact = exact and (m.charged == m.alpha)
    return {"migrations": migrations, "completed": completed,
            "moves_done": moves, "rows_moved": rows_moved,
            "charged": round(charged, 6), "charge_exact": exact}


def bench_cell(scenario: str, budget: str, rate: float, tenant_data,
               col_lo, col_hi, queries_per_tenant: int, alpha: float,
               delta: int, partitions: int, rows: int, seed: int) -> Dict:
    fs = make_drift_scenario(scenario, col_lo, col_hi,
                             num_tenants=len(tenant_data),
                             queries_per_tenant=queries_per_tenant,
                             seed=seed)
    atomic_sched, incr_sched = budget_factories(budget, rate, rows)

    def fleet(incremental: bool) -> FleetEngine:
        factory = incr_sched if incremental else atomic_sched
        return FleetEngine(
            {tid: tenant_engine(tenant_data[tid], alpha, delta, partitions,
                                incremental)
             for tid in fs.tenant_ids}, factory())

    t0 = time.perf_counter()
    ra = fleet(False).run(fs)
    atomic_wall = time.perf_counter() - t0
    incr_fleet = fleet(True)
    t0 = time.perf_counter()
    ri = incr_fleet.run(fs)
    incr_wall = time.perf_counter() - t0
    ledger = ledger_stats(incr_fleet)
    assert ledger["charge_exact"], \
        f"{scenario}/{budget}: a completed migration's ledger != alpha"
    if budget == "unlimited":
        assert ra.total_cost == ri.total_cost, \
            f"{scenario}: unbudgeted atomic/incremental diverged"
    # Reorg charges are count * alpha in both arms (decisions identical);
    # any combined-cost difference is query cost realized earlier.
    assert ra.total_reorg_cost == ri.total_reorg_cost, \
        f"{scenario}/{budget}: reorg accounting diverged"
    return {
        "scenario": scenario,
        "budget": budget,
        "atomic_scheduler": ra.scheduler,
        "incremental_scheduler": ri.scheduler,
        "tenants": len(fs.tenant_ids),
        "events": ra.ticks,
        "atomic_total_cost": round(ra.total_cost, 3),
        "incremental_total_cost": round(ri.total_cost, 3),
        "atomic_query_cost": round(ra.total_query_cost, 3),
        "incremental_query_cost": round(ri.total_query_cost, 3),
        "reorg_cost": round(ra.total_reorg_cost, 3),
        "reorgs": ra.num_reorgs,
        "atomic_swaps_deferred": ra.swaps_deferred,
        "cost_ratio_atomic_over_incremental": round(
            ra.total_cost / max(ri.total_cost, 1e-12), 4),
        "incremental_ledger": ledger,
        "atomic_events_per_sec": round(ra.ticks / atomic_wall, 1),
        "incremental_events_per_sec": round(ri.ticks / incr_wall, 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: all scenarios x {unlimited, bucket}")
    ap.add_argument("--out", default="BENCH_reorg.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        tenants, rows, cols, qpt = 3, 2_000, 6, 150
        alpha, delta, partitions = 4.0, 10, 8
        rate = 0.005
    else:
        tenants, rows, cols, qpt = 4, 8_000, 8, 1_000
        alpha, delta, partitions = 10.0, 10, 16
        rate = 0.002

    tenant_data = make_tenant_data(tenants, rows, cols, seed=100)
    col_lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    col_hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)

    results: List[Dict] = []
    ratios: Dict[str, Dict[str, float]] = {}
    wins = 0
    for scenario in SCENARIOS:
        ratios[scenario] = {}
        for budget in ("unlimited", "bucket"):
            row = bench_cell(scenario, budget, rate, tenant_data, col_lo,
                             col_hi, qpt, alpha, delta, partitions, rows,
                             seed=7)
            results.append(row)
            ratio = row["cost_ratio_atomic_over_incremental"]
            ratios[scenario][budget] = ratio
            if budget == "bucket" and ratio > 1.0:
                wins += 1
            print(f"{scenario:16s} x {budget:10s} "
                  f"atomic={row['atomic_total_cost']:9.1f} "
                  f"incremental={row['incremental_total_cost']:9.1f} "
                  f"ratio={ratio:.3f} "
                  f"(moves={row['incremental_ledger']['moves_done']}, "
                  f"rows={row['incremental_ledger']['rows_moved']})",
                  flush=True)
    print(f"incremental beats atomic-deferred in {wins}/{len(SCENARIOS)} "
          f"scenarios under the tight bucket budget")

    payload = {
        "benchmark": "reorg",
        "units": "combined query+reorg cost (fraction-of-table + alpha per "
                 "reorg); ratio > 1 means incremental wins",
        "config": {
            "tenants": tenants, "rows": rows, "columns": cols,
            "queries_per_tenant": qpt, "alpha": alpha, "delta": delta,
            "partitions": partitions, "bucket_rate": rate,
            "row_bandwidth_per_tick": rate * rows,
            "smoke": bool(args.smoke),
            "platform": platform.platform(), "numpy": np.__version__,
        },
        "results": results,
        "bucket_wins": {"incremental": wins, "scenarios": len(SCENARIOS)},
        "cost_ratio_atomic_over_incremental": ratios,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
