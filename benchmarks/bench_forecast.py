"""Forecast benchmark: predictive vs reactive fleets over every scenario.

Runs the full scenario registry — the five drift scenarios
(``repro.core.workload.DRIFT_SCENARIOS``) and the five mixed read/write
ingest scenarios (``INGEST_SCENARIOS``, debt-aware compaction on in both
arms) — under each reorganization scheduler, twice per cell:

* **reactive** — the plain OREO fleet (identical construction to
  ``bench_fleet.py``): D-UMTS + LayoutManager, moving only once realized
  costs fill a counter;
* **forecast** — the same fleet with every tenant policy wrapped in
  :class:`repro.forecast.ForecastPolicy` at its default
  :class:`repro.forecast.ForecastConfig`: workload forecasting
  (period/trend), α-charged pre-positioning and online qd-tree growth.

The headline grid is ``forecast_vs_reactive``: combined query+reorg cost
of the reactive arm divided by the forecast arm (> 1 means forecasting
pays).  The registry's :data:`repro.core.workload.SCENARIO_INFO` marks
which scenarios carry a predictable signal (``forecastable``):
cyclic_diurnal and gradual_drift must *win* on aggregate, everything
else must stay within 5% of reactive — on the unpredictable scenarios
the forecaster goes silent and the trace is bitwise reactive, so those
ratios land at exactly 1.0.  A full (non ``--smoke``) run asserts this
acceptance envelope and refuses to write a payload that violates it.

``--smoke`` is the CI configuration; the checked-in ``forecast_smoke``
section of ``BENCH_forecast.json`` holds the baseline ratios the
regression gate (benchmarks/check_regression.py) compares against.  The
cost ratios are deterministic given the benchmark seeds, so any gate
trip is a behavioral regression, not machine noise.
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
from repro.core.workload import (SCENARIO_INFO, forecastable_scenarios,
                                 make_drift_scenario, make_ingest_scenario)
from repro.engine import (FleetEngine, InMemoryBackend, IngestConfig,
                          KConcurrentScheduler, LayoutEngine, OreoPolicy,
                          TokenBucketScheduler, UnlimitedScheduler)
from repro.forecast import ForecastConfig, ForecastPolicy
from repro.launch.compile_cache import enable_compile_cache

DRIFT = ["sudden_shift", "gradual_drift", "cyclic_diurnal", "flash_crowd",
         "template_churn"]
INGEST = ["trickle", "append_heavy", "mixed_rw", "ingest_burst", "bulk_load"]


def make_tenant_data(num_tenants: int, rows: int, cols: int,
                     seed: int) -> Dict[str, np.ndarray]:
    return {f"t{t}": np.random.default_rng(seed + t).uniform(
        0, 100, size=(rows, cols)) for t in range(num_tenants)}


def tenant_engine(data: np.ndarray, alpha: float, delta: int,
                  partitions: int, forecast: bool, ingest: bool,
                  seed: int = 0) -> LayoutEngine:
    cfg = OreoConfig(
        alpha=alpha, seed=seed, delta=delta,
        manager=lm.LayoutManagerConfig(target_partitions=partitions,
                                       window_size=80, gen_every=40))
    policy = OreoPolicy(data, build_default_layout(0, data, partitions),
                        make_generator("qdtree"), cfg)
    if forecast:
        policy = ForecastPolicy(policy, config=ForecastConfig())
    return LayoutEngine(
        policy, InMemoryBackend(data), delta=cfg.delta,
        ingest=IngestConfig(debt_threshold=1.0) if ingest else None)


def bench_cell(scenario: str, scheduler_factory, tenant_data, col_lo,
               col_hi, queries_per_tenant: int, alpha: float, delta: int,
               partitions: int, seed: int) -> Dict:
    family = SCENARIO_INFO[scenario].family
    maker = make_drift_scenario if family == "drift" else make_ingest_scenario
    fs = maker(scenario, col_lo, col_hi, num_tenants=len(tenant_data),
               queries_per_tenant=queries_per_tenant, seed=seed)

    def run(forecast: bool):
        fleet = FleetEngine(
            {tid: tenant_engine(tenant_data[tid], alpha, delta, partitions,
                                forecast=forecast, ingest=family == "ingest")
             for tid in fs.tenant_ids},
            scheduler_factory())
        t0 = time.perf_counter()
        res = fleet.run(fs)
        return res, time.perf_counter() - t0

    reactive, r_wall = run(forecast=False)
    forecasted, f_wall = run(forecast=True)
    infos = [forecasted.per_tenant[tid].info for tid in fs.tenant_ids]
    checks = sum(i["forecast_checks"] for i in infos)
    hits = sum(i["forecast_hits"] for i in infos)
    return {
        "scenario": scenario,
        "family": family,
        "forecastable": SCENARIO_INFO[scenario].forecastable,
        "scheduler": reactive.scheduler,
        "tenants": len(fs.tenant_ids),
        "reactive_total": round(reactive.total_cost, 3),
        "forecast_total": round(forecasted.total_cost, 3),
        "cost_ratio": round(reactive.total_cost / forecasted.total_cost, 6),
        "reactive_reorgs": reactive.num_reorgs,
        "forecast_reorgs": forecasted.num_reorgs,
        "prepositions": sum(i["prepositions"] for i in infos),
        "grown_admitted": sum(i["grown_admitted"] for i in infos),
        "forecasts": sum(i["forecasts"] for i in infos),
        "forecast_accuracy": round(hits / checks, 3) if checks else None,
        "wall_seconds": round(r_wall + f_wall, 3),
    }


def check_acceptance(ratios: Dict[str, Dict[str, float]],
                     aggregate: Dict[str, float]) -> List[str]:
    """The PR's acceptance envelope, evaluated on a full-size run."""
    failures = []
    for scenario in forecastable_scenarios():
        if aggregate[scenario] <= 1.0:
            failures.append(
                f"{scenario}: aggregate forecast-vs-reactive ratio "
                f"{aggregate[scenario]:.4f} <= 1.0 (forecasting must pay "
                f"on forecastable scenarios)")
    for scenario, row in ratios.items():
        for sched, ratio in row.items():
            if ratio < 0.95:
                failures.append(
                    f"{scenario} x {sched}: ratio {ratio:.4f} < 0.95 "
                    f"(the α-safety clamp must bound the damage)")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: all 10 scenarios x 3 schedulers, tiny")
    ap.add_argument("--out", default="BENCH_forecast.json")
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
    else:
        tenants, rows, cols, qpt = 4, 20_000, 8, 1_500
        alpha, delta, partitions = 20.0, 10, 16
        schedulers = [
            ("unlimited", UnlimitedScheduler),
            ("k1", lambda: KConcurrentScheduler(1)),
            ("bucket", lambda: TokenBucketScheduler(rate=0.002,
                                                    capacity=2.0)),
        ]

    tenant_data = make_tenant_data(tenants, rows, cols, seed=100)
    col_lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    col_hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)

    results: List[Dict] = []
    ratios: Dict[str, Dict[str, float]] = {}
    totals: Dict[str, List[float]] = {}
    for scenario in DRIFT + INGEST:
        for label, factory in schedulers:
            row = bench_cell(scenario, factory, tenant_data, col_lo, col_hi,
                             qpt, alpha, delta, partitions, seed=7)
            results.append(row)
            ratios.setdefault(scenario, {})[label] = row["cost_ratio"]
            agg = totals.setdefault(scenario, [0.0, 0.0])
            agg[0] += row["reactive_total"]
            agg[1] += row["forecast_total"]
            acc = row["forecast_accuracy"]
            print(f"{scenario:16s} x {label:10s} "
                  f"ratio={row['cost_ratio']:.4f} "
                  f"(pre={row['prepositions']:3d}, "
                  f"grown={row['grown_admitted']:2d}, "
                  f"acc={'-' if acc is None else f'{acc:.2f}'}) "
                  f"{row['wall_seconds']:7.1f}s", flush=True)

    aggregate = {s: round(r / f, 6) for s, (r, f) in totals.items()}
    for scenario in DRIFT + INGEST:
        tag = "forecastable" if SCENARIO_INFO[scenario].forecastable else " "
        print(f"aggregate {scenario:16s} {aggregate[scenario]:.4f} {tag}")

    failures = check_acceptance(ratios, aggregate)
    if args.smoke:
        # smoke sizes undershoot the period detector's history needs
        # (α=4 also makes every mistake cheap), so the envelope is only
        # asserted at full size; smoke ratios are regression-gate
        # baselines, compared against themselves.
        failures = []
    if failures:
        for msg in failures:
            print(f"ACCEPTANCE FAILURE: {msg}")
        raise SystemExit(1)

    payload = {
        "benchmark": "forecast",
        "units": "combined query+reorg cost (fraction-of-table + alpha per "
                 "reorg); ratio = reactive/forecast, > 1 means the "
                 "predictive plane wins",
        "config": {
            "tenants": tenants, "rows": rows, "columns": cols,
            "queries_per_tenant": qpt, "alpha": alpha, "delta": delta,
            "partitions": partitions, "smoke": bool(args.smoke),
            "forecast": dataclass_dict(ForecastConfig()),
            "platform": platform.platform(), "numpy": np.__version__,
        },
        "results": results,
        "forecast_vs_reactive": ratios,
        "scenario_aggregate_ratio": aggregate,
        "forecastable_scenarios": forecastable_scenarios(),
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


def dataclass_dict(cfg: ForecastConfig) -> Dict:
    import dataclasses
    return dataclasses.asdict(cfg)


if __name__ == "__main__":
    main()
