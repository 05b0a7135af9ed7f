"""Fleet benchmark: drift scenarios x reorg schedulers, loop vs batched.

Two sections, both written to ``BENCH_fleet.json``:

* **Scenario grid** — a multi-tenant :class:`repro.engine.FleetEngine` of
  OREO-policy tenants through each registered drift scenario
  (``repro.core.workload.DRIFT_SCENARIOS``) under each reorganization
  scheduler, once through the stepwise loop (``fleet.run``) and once
  through the packed-plane batched path (``fleet.run_batched``).  Reports
  combined query+reorg cost, deferrals, both throughputs, and asserts the
  two paths land identical total costs (the golden trace tests in
  ``tests/test_fleet_matrix.py`` check bit-identity query by query).

* **Tenant sweep** (T=4..64) — the fleet-plane microbenchmark behind the
  CI speedup gate: per tenant a fixed state space of synthetic clustered
  layouts and a stateless argmin policy over ``backend.estimate_vector``
  (isolating the decision plane, exactly like ``bench_decision_loop``'s
  ScoringPolicy isolates the single-table plane), selective range queries
  on every column.  The batched side runs ``compute="pallas_fused"``
  (f64 operands, so the float32 guard routes scoring through the exact
  numpy fused pass) and, because the policy implements
  ``decide_frames``, resolves whole no-reorg frame regions through the
  bulk decide path instead of per-event Python.  Loop and batched runs
  are interleaved rep by rep and each side takes its best, so the
  reported ``speedup_batched_vs_loop`` ratio is machine-portable where
  raw events/sec are not.

``--smoke`` is the CI configuration; the checked-in ``fleet_smoke``
section of ``BENCH_fleet.json`` holds the baseline ratios the regression
gate (benchmarks/check_regression.py) compares against.
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from typing import Dict, List

import numpy as np

from repro.core import (OreoConfig, build_default_layout, layouts,
                        make_generator)
from repro.core import layout_manager as lm
from repro.core import workload as wl
from repro.core.workload import make_drift_scenario
from repro.engine import (Decision, FleetEngine, InMemoryBackend,
                          KConcurrentScheduler, LayoutEngine, OreoPolicy,
                          TokenBucketScheduler, UnlimitedScheduler)
from repro.launch.compile_cache import enable_compile_cache

SCENARIOS = ["sudden_shift", "gradual_drift", "cyclic_diurnal",
             "flash_crowd", "template_churn"]


def make_tenant_data(num_tenants: int, rows: int, cols: int,
                     seed: int) -> Dict[str, np.ndarray]:
    return {f"t{t}": np.random.default_rng(seed + t).uniform(
        0, 100, size=(rows, cols)) for t in range(num_tenants)}


def tenant_engine(data: np.ndarray, alpha: float, delta: int,
                  partitions: int, seed: int = 0) -> LayoutEngine:
    cfg = OreoConfig(
        alpha=alpha, seed=seed, delta=delta,
        manager=lm.LayoutManagerConfig(target_partitions=partitions,
                                       window_size=80, gen_every=40))
    policy = OreoPolicy(data, build_default_layout(0, data, partitions),
                        make_generator("qdtree"), cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta)


# ---------------------------------------------------------------------------
# Tenant sweep: fleet-plane throughput microbenchmark
# ---------------------------------------------------------------------------

def make_state_space(data: np.ndarray, num_states: int, partitions: int,
                     rng) -> List[layouts.Layout]:
    """S synthetic clustered layouts (same construction as
    bench_decision_loop): each sorts the table along a random projection and
    cuts it into equal partitions."""
    n = len(data)
    out = []
    for s in range(num_states):
        proj = data @ rng.normal(size=data.shape[1])
        assignment = np.empty(n, dtype=np.int64)
        assignment[np.argsort(proj, kind="stable")] = (
            np.arange(n) * partitions // n)
        meta = layouts.metadata_from_assignment(data, assignment, partitions)
        out.append(layouts.Layout(layout_id=s, name=f"synthetic-{s}",
                                  technique="synthetic", meta=meta))
    return out


class VectorScoringPolicy:
    """Minimal fixed-state decision layer: argmin over the per-slot cost
    vector, never reorganize.  Isolates fleet decision-plane throughput
    from switching/generation effects; identical decisions on the loop and
    batched paths because ``estimate_vector`` is bit-identical between
    them."""

    name = "VecScoring"
    alpha = 0.0

    def __init__(self, state_space: List[layouts.Layout]):
        self.state_space = state_space
        self.num = len(state_space)
        self.ids = [lay.layout_id for lay in state_space]
        self._ids_arr = np.asarray(self.ids, dtype=np.int64)
        # The engine consumes a Decision synchronously within the same
        # step, so a never-reorganizing policy can reuse one object.
        self._decision = Decision(state=self.ids[0])

    def bind(self, backend) -> int:
        for lay in self.state_space:
            backend.register(lay)
        return self.ids[0]

    def decide(self, index: int, query, backend) -> Decision:
        costs = backend.estimate_vector(query)
        dec = self._decision
        dec.state = self.ids[int(costs[:self.num].argmin())]
        return dec

    def decide_frames(self, costs: np.ndarray, backend):
        """Bulk form of :meth:`decide` (the BatchablePolicy contract):
        row-wise argmin over the candidate slots, never a reorg."""
        return self._ids_arr[costs[:, :self.num].argmin(axis=1)], None

    def info(self) -> dict:
        return {}


def selective_queries(col_lo: np.ndarray, col_hi: np.ndarray, n: int,
                      seed: int, selectivity: float = 0.1) -> List[wl.Query]:
    """Selective conjunctive range queries bounding *every* column — the
    regime where per-event column loops cost the loop path the most and
    the fused pass computes nothing it can skip."""
    rng = np.random.default_rng(seed)
    c = col_lo.shape[0]
    span = col_hi - col_lo
    width = span * selectivity
    out = []
    for _ in range(n):
        start = col_lo + rng.uniform(0, 1, c) * (span - width)
        out.append(wl.Query(lo=start, hi=start + width))
    return out


def bench_sweep_cell(num_tenants: int, rows: int, cols: int, num_states: int,
                     partitions: int, queries_per_tenant: int, reps: int,
                     seed: int) -> Dict:
    tenant_data = make_tenant_data(num_tenants, rows, cols, seed)
    tids = sorted(tenant_data)
    queries = {tid: selective_queries(tenant_data[tid].min(0),
                                      tenant_data[tid].max(0),
                                      queries_per_tenant, seed=seed + i)
               for i, tid in enumerate(tids)}
    events = []
    for k in range(queries_per_tenant):
        for tid in tids:
            events.append(wl.QueryEvent(tid, queries[tid][k]))

    def fresh_fleet() -> FleetEngine:
        return FleetEngine(
            {tid: LayoutEngine(
                VectorScoringPolicy(make_state_space(
                    tenant_data[tid], num_states, partitions,
                    np.random.default_rng(seed + 7 * i))),
                InMemoryBackend(tenant_data[tid]))
             for i, tid in enumerate(tids)},
            UnlimitedScheduler())

    # Interleave loop/batched reps so drift in machine load hits both
    # sides alike; each side keeps its best rep.
    best = {"loop": float("inf"), "batched": float("inf")}
    check = {}
    for _ in range(reps):
        for mode in ("loop", "batched"):
            fleet = fresh_fleet()
            t0 = time.perf_counter()
            res = (fleet.run(events) if mode == "loop"
                   else fleet.run_batched(events, compute="pallas_fused"))
            best[mode] = min(best[mode], time.perf_counter() - t0)
            check[mode] = res.total_cost
    assert check["loop"] == check["batched"], \
        f"loop/batched cost mismatch: {check}"
    loop_eps = len(events) / best["loop"]
    batched_eps = len(events) / best["batched"]
    return {
        "tenants": num_tenants, "S": num_states, "P": partitions,
        "C": cols, "events": len(events),
        "loop_events_per_sec": round(loop_eps, 1),
        "batched_events_per_sec": round(batched_eps, 1),
        "speedup": round(batched_eps / loop_eps, 2),
    }


# ---------------------------------------------------------------------------
# Scenario grid: OREO tenants under drift x schedulers
# ---------------------------------------------------------------------------

def bench_cell(scenario: str, scheduler_factory, tenant_data, col_lo, col_hi,
               queries_per_tenant: int, alpha: float, delta: int,
               partitions: int, seed: int) -> Dict:
    fs = make_drift_scenario(scenario, col_lo, col_hi,
                             num_tenants=len(tenant_data),
                             queries_per_tenant=queries_per_tenant, seed=seed)

    def fresh_fleet() -> FleetEngine:
        return FleetEngine(
            {tid: tenant_engine(tenant_data[tid], alpha, delta, partitions)
             for tid in fs.tenant_ids},
            scheduler_factory())

    fleet = fresh_fleet()
    t0 = time.perf_counter()
    res = fleet.run(fs)
    loop_wall = time.perf_counter() - t0
    batched = fresh_fleet()
    t0 = time.perf_counter()
    bres = batched.run_batched(fs)
    batched_wall = time.perf_counter() - t0
    assert res.total_cost == bres.total_cost, \
        f"{scenario}: loop/batched cost mismatch"
    return {
        "scenario": scenario,
        "scheduler": res.scheduler,
        "tenants": len(fs.tenant_ids),
        "events": res.ticks,
        "total_cost": round(res.total_cost, 3),
        "query_cost": round(res.total_query_cost, 3),
        "reorg_cost": round(res.total_reorg_cost, 3),
        "reorgs": res.num_reorgs,
        "swaps_deferred": res.swaps_deferred,
        "deferred_ticks": res.deferred_ticks,
        "scheduler_stats": res.scheduler_stats,
        "events_per_sec": round(res.ticks / loop_wall, 1),
        "batched_events_per_sec": round(bres.ticks / batched_wall, 1),
        "batched_speedup": round(loop_wall / batched_wall, 2),
        "wall_seconds": round(loop_wall, 3),
        # engine-aggregated breakdown, straight off the per-tenant traces
        "decide_seconds": round(res.decide_seconds, 3),
        "reorg_seconds": round(res.reorg_seconds, 3),
        "serve_seconds": round(res.serve_seconds, 3),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: all scenarios x 3 schedulers + sweep "
                         "to T=32, tiny")
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        tenants, rows, cols, qpt = 3, 2_000, 6, 150
        alpha, delta, partitions = 4.0, 10, 8
        schedulers = [
            ("unlimited", UnlimitedScheduler),
            ("k1", lambda: KConcurrentScheduler(1)),
            ("bucket", lambda: TokenBucketScheduler(rate=0.005, capacity=1.0,
                                                    initial=0.0)),
        ]
        sweep_tenants = [4, 8, 16, 32]
        sweep_cfg = dict(rows=2_000, cols=10, num_states=8, partitions=8,
                         queries_per_tenant=150, reps=5, seed=100)
    else:
        tenants, rows, cols, qpt = 4, 20_000, 8, 1_500
        alpha, delta, partitions = 20.0, 10, 16
        schedulers = [
            ("unlimited", UnlimitedScheduler),
            ("k1", lambda: KConcurrentScheduler(1)),
            ("bucket", lambda: TokenBucketScheduler(rate=0.002,
                                                    capacity=2.0)),
        ]
        sweep_tenants = [4, 8, 16, 32, 64]
        sweep_cfg = dict(rows=4_000, cols=10, num_states=8, partitions=8,
                         queries_per_tenant=300, reps=5, seed=100)

    tenant_data = make_tenant_data(tenants, rows, cols, seed=100)
    col_lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    col_hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)

    results: List[Dict] = []
    for scenario in SCENARIOS:
        for label, factory in schedulers:
            row = bench_cell(scenario, factory, tenant_data, col_lo, col_hi,
                             qpt, alpha, delta, partitions, seed=7)
            results.append(row)
            print(f"{scenario:16s} x {label:10s} "
                  f"total={row['total_cost']:9.1f} "
                  f"(reorgs={row['reorgs']:3d}, "
                  f"deferred={row['swaps_deferred']:3d} swaps/"
                  f"{row['deferred_ticks']:4d} ticks) "
                  f"{row['events_per_sec']:7.0f} ev/s loop / "
                  f"{row['batched_events_per_sec']:7.0f} batched "
                  f"(x{row['batched_speedup']:.2f})", flush=True)

    sweep: List[Dict] = []
    speedups: Dict[str, Dict[str, float]] = {}
    for t in sweep_tenants:
        row = bench_sweep_cell(num_tenants=t, **sweep_cfg)
        sweep.append(row)
        speedups[f"T{t}"] = {"batched_vs_loop": row["speedup"]}
        print(f"sweep T={t:3d}: loop={row['loop_events_per_sec']:8.0f} ev/s "
              f"batched={row['batched_events_per_sec']:8.0f} ev/s "
              f"speedup x{row['speedup']:.2f}", flush=True)

    payload = {
        "benchmark": "fleet",
        "units": "combined query+reorg cost (fraction-of-table + alpha "
                 "per reorg); events/sec wall-clock",
        "config": {
            "tenants": tenants, "rows": rows, "columns": cols,
            "queries_per_tenant": qpt, "alpha": alpha, "delta": delta,
            "partitions": partitions, "smoke": bool(args.smoke),
            "sweep": dict(sweep_cfg, tenants=sweep_tenants),
            "platform": platform.platform(), "numpy": np.__version__,
        },
        "results": results,
        "tenant_sweep": sweep,
        "speedup_batched_vs_loop": speedups,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
