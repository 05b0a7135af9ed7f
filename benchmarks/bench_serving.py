"""Serving benchmark: the admission-controlled front end under load.

The benchmark axis the serving tier opens (:mod:`repro.serve.frontend`):
for the two stress scenarios — ``flash_crowd`` (drift: one tenant's
query rate spikes) and ``ingest_burst`` (streaming: appends arrive in
bursts between reads) — a multi-tenant OREO fleet serves the same event
stream two ways:

* **direct**   — ``FleetEngine.run(stream)``: the raw engine loop, no
  serving tier; its events/sec is the machine-local baseline;
* **frontend** — :class:`repro.serve.ServeFrontend` in a closed serving
  loop (submit → pump), with the bounded ingress queue, per-tenant
  admission, the circuit breaker, and the plane-versioned serve-cost
  cache all active.  Per-event wall latency (admission → completion) is
  stamped for the p50/p99 cells.

Both arms see identical events and must produce bit-identical traces
(asserted).  Raw QPS and raw milliseconds are machine-local, so the
regression gate checks **normalized** sections, both sides measured in
the same process:

* ``serving_qps_ratio``   (floor-gated): frontend QPS / direct QPS —
  overhead creep in the serving tier drags it down on any machine;
* ``latency_tail``        (ceiling-gated): p99 / p50 latency — tail
  amplification (a stall on a fraction of events) inflates it while
  leaving the QPS ratio nearly untouched.

An **overload** cell (flash_crowd through an undersized queue on a
K=1 scheduler) exercises the breaker and asserts the serving-tier
contract: >= 1 reorganization deferred, zero queries dropped, and the
per-tenant charge ledgers bitwise identical to the unshedded run.

``--chaos uniform|tail`` injects ``time.sleep`` into the dispatch path
(every event / every 50th event) to verify the gates trip: ``uniform``
must fail the ``serving_qps_ratio`` floor, ``tail`` the
``latency_tail`` ceiling.  See ``check_regression.py``.
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core import OreoConfig, build_default_layout, make_generator
from repro.core import layout_manager as lm
from repro.core.workload import make_drift_scenario, make_ingest_scenario
from repro.engine import (FleetEngine, InMemoryBackend, IngestConfig,
                          KConcurrentScheduler, LayoutEngine, OreoPolicy,
                          UnlimitedScheduler)
from repro.serve import FrontendConfig, ServeFrontend
from repro.launch.compile_cache import enable_compile_cache

SCENARIOS = ("flash_crowd", "ingest_burst")
INGEST_SCENARIOS = ("ingest_burst",)


def make_tenant_data(num_tenants: int, rows: int, cols: int,
                     seed: int) -> Dict[str, np.ndarray]:
    return {f"t{t}": np.random.default_rng(seed + t).uniform(
        0, 100, size=(rows, cols)) for t in range(num_tenants)}


def tenant_engine(data: np.ndarray, alpha: float, delta: int,
                  partitions: int,
                  ingest: Optional[IngestConfig]) -> LayoutEngine:
    cfg = OreoConfig(
        alpha=alpha, seed=0, delta=delta,
        manager=lm.LayoutManagerConfig(target_partitions=partitions,
                                       window_size=80, gen_every=40))
    policy = OreoPolicy(data,
                        build_default_layout(0, data, partitions, sort_col=0),
                        make_generator("qdtree"), cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta,
                        ingest=ingest)


def make_stream(scenario: str, col_lo, col_hi, num_tenants: int,
                queries_per_tenant: int, seed: int):
    if scenario in INGEST_SCENARIOS:
        return make_ingest_scenario(scenario, col_lo, col_hi,
                                    num_tenants=num_tenants,
                                    queries_per_tenant=queries_per_tenant,
                                    seed=seed)
    return make_drift_scenario(scenario, col_lo, col_hi,
                               num_tenants=num_tenants,
                               queries_per_tenant=queries_per_tenant,
                               seed=seed)


def build_fleet(fs, tenant_data, scenario, alpha, delta, partitions,
                scheduler_factory=UnlimitedScheduler) -> FleetEngine:
    ingest = IngestConfig() if scenario in INGEST_SCENARIOS else None
    return FleetEngine(
        {tid: tenant_engine(tenant_data[tid], alpha, delta, partitions,
                            ingest)
         for tid in fs.tenant_ids}, scheduler_factory())


class _ChaosFrontend(ServeFrontend):
    """Gate-verification aid: sleeps inside the dispatch path."""

    def __init__(self, fleet, config, mode: str, seconds: float):
        super().__init__(fleet, config)
        self._chaos_mode = mode
        self._chaos_seconds = seconds
        self._chaos_n = 0

    def _dispatch_one(self, ev, t0):
        self._chaos_n += 1
        if self._chaos_mode == "uniform" or \
                (self._chaos_mode == "tail" and self._chaos_n % 50 == 0):
            time.sleep(self._chaos_seconds)
        super()._dispatch_one(ev, t0)


def serving_config() -> FrontendConfig:
    # A shallow queue kept drained by the closed loop: latency stamps
    # measure dispatch, not open-loop queueing; the breaker stays armed
    # but never trips at this depth, so the trace is the direct one.
    return FrontendConfig(queue_capacity=64, overflow_policy="block",
                          pump_chunk=8, record_latency=True)


def assert_same_trace(a, b, scenario: str) -> None:
    for tid in a.per_tenant:
        x, y = a.per_tenant[tid], b.per_tenant[tid]
        assert np.array_equal(x.query_costs, y.query_costs), scenario
        assert x.reorg_indices == y.reorg_indices, scenario
        assert np.array_equal(x.state_seq, y.state_seq), scenario


def bench_cell(scenario: str, tenant_data, col_lo, col_hi,
               queries_per_tenant: int, alpha: float, delta: int,
               partitions: int, seed: int, chaos: str,
               chaos_seconds: float) -> Dict:
    fs = make_stream(scenario, col_lo, col_hi, len(tenant_data),
                     queries_per_tenant, seed)

    direct_fleet = build_fleet(fs, tenant_data, scenario, alpha, delta,
                               partitions)
    t0 = time.perf_counter()
    direct = direct_fleet.run(fs)
    direct_wall = time.perf_counter() - t0
    direct_qps = direct.ticks / direct_wall

    serve_fleet = build_fleet(fs, tenant_data, scenario, alpha, delta,
                              partitions)
    if chaos == "none":
        fe = ServeFrontend(serve_fleet, serving_config())
    else:
        fe = _ChaosFrontend(serve_fleet, serving_config(), chaos,
                            chaos_seconds)
    t0 = time.perf_counter()
    for event in fs:
        fe.submit_blocking(event)
        fe.pump()
    fe.flush()
    serve_wall = time.perf_counter() - t0
    got = fe.result()
    assert_same_trace(direct, got, scenario)

    stats = fe.stats()
    assert stats["processed"] == len(fs)
    lat_ms = np.asarray(fe.latencies) * 1e3
    p50, p99 = (float(np.percentile(lat_ms, p)) for p in (50, 99))
    qps = stats["processed"] / serve_wall
    return {
        "scenario": scenario,
        "tenants": len(fs.tenant_ids),
        "events": len(fs),
        "queries_per_tenant": queries_per_tenant,
        "direct": {"events_per_sec": round(direct_qps, 1)},
        "frontend": {
            "events_per_sec": round(qps, 1),
            "p50_ms": round(p50, 4),
            "p99_ms": round(p99, 4),
            "cache": stats["cache"],
            "breaker_opens": stats["breaker"]["opens"],
        },
        "qps_ratio": round(qps / direct_qps, 4),
        "p99_over_p50": round(p99 / max(p50, 1e-9), 4),
    }


OVERLOAD = dict(queue_capacity=48, overflow_policy="block",
                breaker_open_frac=0.5, breaker_close_frac=0.1,
                breaker_min_open_events=16, pump_chunk=4,
                record_latency=False)


def overload_cell(tenant_data, col_lo, col_hi, queries_per_tenant: int,
                  alpha: float, delta: int, partitions: int,
                  seed: int) -> Dict:
    """Flash crowd through an undersized queue on K=1: the breaker must
    shed reorg work while the serve path and the α-ledger stay exact."""
    fs = make_stream("flash_crowd", col_lo, col_hi, len(tenant_data),
                     queries_per_tenant, seed)
    ref = build_fleet(fs, tenant_data, "flash_crowd", alpha, delta,
                      partitions, lambda: KConcurrentScheduler(1)).run(fs)
    fleet = build_fleet(fs, tenant_data, "flash_crowd", alpha, delta,
                        partitions, lambda: KConcurrentScheduler(1))
    fe = ServeFrontend(fleet, FrontendConfig(**OVERLOAD))
    got = fe.run(fs)
    stats = fe.stats()

    dropped = sum(queries_per_tenant - len(got.per_tenant[t].query_costs)
                  for t in fs.tenant_ids)
    ledger_identical = all(
        got.per_tenant[t].reorg_indices == ref.per_tenant[t].reorg_indices
        and np.array_equal(got.per_tenant[t].state_seq,
                           ref.per_tenant[t].state_seq)
        for t in fs.tenant_ids)
    assert stats["breaker"]["opens"] >= 1, "overload never tripped breaker"
    assert stats["shed_count"] >= 1, "breaker deferred no reorg work"
    assert dropped == 0, f"{dropped} queries dropped under overload"
    assert ledger_identical, "shedding perturbed the charge ledger"
    return {
        "scenario": "flash_crowd",
        "queue_capacity": OVERLOAD["queue_capacity"],
        "scheduler": "k-concurrent(1)",
        "breaker_opens": stats["breaker"]["opens"],
        "breaker_closes": stats["breaker"]["closes"],
        "shed_count": stats["shed_count"],
        "shed_attempts": stats["shed_attempts"],
        "queries_dropped": dropped,
        "charge_ledger_identical": ledger_identical,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: small fleet, short streams")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--chaos", choices=("none", "uniform", "tail"),
                    default="none",
                    help="inject time.sleep into dispatch to verify the "
                         "gates trip (uniform -> QPS floor, tail -> p99 "
                         "ceiling); never use for a checked-in baseline")
    ap.add_argument("--chaos-seconds", type=float, default=0.002)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        tenants, rows, cols, qpt = 3, 2_000, 6, 150
        alpha, delta, partitions = 2.5, 5, 8
        overload_qpt = 120
    else:
        tenants, rows, cols, qpt = 4, 8_000, 8, 800
        alpha, delta, partitions = 4.0, 10, 16
        overload_qpt = 400

    tenant_data = make_tenant_data(tenants, rows, cols, seed=100)
    col_lo = np.min([d.min(0) for d in tenant_data.values()], axis=0)
    col_hi = np.max([d.max(0) for d in tenant_data.values()], axis=0)

    results: List[Dict] = []
    qps_ratios: Dict[str, Dict[str, float]] = {}
    tails: Dict[str, Dict[str, float]] = {}
    for scenario in SCENARIOS:
        row = bench_cell(scenario, tenant_data, col_lo, col_hi, qpt,
                         alpha, delta, partitions, seed=7,
                         chaos=args.chaos,
                         chaos_seconds=args.chaos_seconds)
        results.append(row)
        qps_ratios[scenario] = {"frontend_vs_direct": row["qps_ratio"]}
        tails[scenario] = {"p99_over_p50": row["p99_over_p50"]}
        print(f"{scenario:14s} direct={row['direct']['events_per_sec']:9.1f}/s "
              f"frontend={row['frontend']['events_per_sec']:9.1f}/s "
              f"(x{row['qps_ratio']:.3f}) "
              f"p50={row['frontend']['p50_ms']:.3f}ms "
              f"p99={row['frontend']['p99_ms']:.3f}ms "
              f"(tail x{row['p99_over_p50']:.2f})", flush=True)

    over = overload_cell(tenant_data, col_lo, col_hi, overload_qpt,
                         alpha, delta, partitions, seed=7)
    print(f"overload       breaker opens={over['breaker_opens']} "
          f"shed={over['shed_count']} dropped={over['queries_dropped']} "
          f"ledger_identical={over['charge_ledger_identical']}")

    payload = {
        "benchmark": "serving",
        "units": "events/sec (QPS) and wall-clock ms per event; the gated "
                 "sections are machine-normalized ratios",
        "config": {
            "tenants": tenants, "rows": rows, "columns": cols,
            "queries_per_tenant": qpt, "alpha": alpha, "delta": delta,
            "partitions": partitions, "smoke": bool(args.smoke),
            "chaos": args.chaos,
            "platform": platform.platform(), "numpy": np.__version__,
        },
        "results": results,
        "overload": over,
        "serving_qps_ratio": qps_ratios,
        "latency_tail": tails,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
