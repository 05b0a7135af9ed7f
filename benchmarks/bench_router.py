"""Router benchmark: shard-count scaling of the fleet-of-fleets.

The benchmark axis the routing plane opens
(:mod:`repro.engine.router`): T tenants' worth of drift traffic served
by a :class:`FleetRouter` at 1 / 2 / 4 / 8 shards.  Shards share no
mutable state, so the deployment-relevant number on an N-core box is
the **critical path**: every shard drains its own queue in parallel
and the slowest shard gates the fleet.  This process may have a single
core (CI runners often do), so each shard's drain is *timed
individually, run sequentially*, and

    critical-path events/sec = total events / max(per-shard drain wall)

which is exact for perfectly-parallel shards and deterministic given
the placement (consistent hashing fixes each shard's tenant set).  A
``parallel`` lane runs the same placement over real OS processes
(:class:`repro.launch.shard_host.ProcessShardSet`) and reports measured
wall — informative only, since its speedup is capped by
``os.cpu_count()``.

Correctness is asserted inside the benchmark, not just measured:

* the 1-shard router's merged trace is **bit-identical** to a plain
  ``FleetEngine.run`` on the same stream (the router is invisible);
* a live-migration cell moves tenants between shards mid-stream and
  must reproduce the unsharded per-tenant traces bitwise.

The regression gate checks the normalized section ``router_scaling``
(floor-gated): critical-path throughput at N shards divided by the
1-shard router on the same machine.  Routing-plane overhead creep, a
placement bug collapsing tenants onto one shard, or accidental
cross-shard serialization all drag it down wherever it runs.

``--chaos serialize`` migrates every tenant onto shard ``s0`` before
the stream (the placement-collapse failure mode): the critical path
degenerates to the 1-shard wall and the ``router_scaling`` floor must
trip.  Never use it for a checked-in baseline.  See
``check_regression.py``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import platform
import time
from typing import Dict, List

import numpy as np

from repro.core import OreoConfig, build_default_layout, make_generator
from repro.core import layout_manager as lm
from repro.core.workload import make_drift_scenario
from repro.engine import FleetEngine, FleetRouter, InMemoryBackend, \
    LayoutEngine, OreoPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.shard_host import ProcessShardSet

SCENARIO = "sudden_shift"
SHARD_COUNTS = (1, 2, 4, 8)


def tenant_engine(seed: int, rows: int, cols: int, alpha: float,
                  delta: int, partitions: int) -> LayoutEngine:
    """Module-level (and built from a picklable partial) so the same
    factory drives both the inline router and spawned shard workers."""
    data = np.random.default_rng(100 + seed).uniform(
        0, 100, size=(rows, cols))
    cfg = OreoConfig(
        alpha=alpha, seed=0, delta=delta,
        manager=lm.LayoutManagerConfig(target_partitions=partitions,
                                       window_size=80, gen_every=40))
    policy = OreoPolicy(data,
                        build_default_layout(0, data, partitions,
                                             sort_col=0),
                        make_generator("qdtree"), cfg)
    return LayoutEngine(policy, InMemoryBackend(data), delta=cfg.delta)


def make_factories(num_tenants: int, rows: int, cols: int, alpha: float,
                   delta: int, partitions: int) -> Dict:
    return {f"t{t}": functools.partial(tenant_engine, t, rows, cols,
                                       alpha, delta, partitions)
            for t in range(num_tenants)}


def make_stream(factories, rows: int, cols: int, qpt: int, seed: int):
    lo, hi = np.zeros(cols), np.full(cols, 100.0)
    return make_drift_scenario(SCENARIO, lo, hi,
                               num_tenants=len(factories),
                               queries_per_tenant=qpt, seed=seed)


def assert_same_traces(ref, got, label: str) -> None:
    for tid in ref.per_tenant:
        a, b = ref.per_tenant[tid], got.per_tenant[tid]
        assert np.array_equal(a.query_costs, b.query_costs), (label, tid)
        assert a.reorg_indices == b.reorg_indices, (label, tid)
        assert np.array_equal(a.state_seq, b.state_seq), (label, tid)


def sweep_cell(factories, fs, num_shards: int, chaos: str) -> Dict:
    """One shard count: submit everything, time each shard's drain
    individually (sequentially — see module docstring), merge."""
    router = FleetRouter({tid: f() for tid, f in factories.items()},
                         num_shards=num_shards)
    if chaos == "serialize" and num_shards > 1:
        # the placement-collapse failure mode the gate must catch
        for tid in router.tenant_ids:
            router.migrate_tenant(tid, "s0")
    t0 = time.perf_counter()
    for event in fs:
        router.submit(event)
    route_wall = time.perf_counter() - t0

    walls: Dict[str, float] = {}
    depths: Dict[str, int] = {}
    for sid in router.shard_ids:
        shard = router.shard(sid)
        depths[sid] = shard.queue_depth
        t0 = time.perf_counter()
        shard.drain()
        walls[sid] = time.perf_counter() - t0
    result = router.result()
    assert result.ticks == len(fs)

    critical = max(walls.values())
    total = sum(walls.values())
    return {
        "num_shards": num_shards,
        "events": len(fs),
        "events_per_shard": depths,
        "route_wall_s": round(route_wall, 4),
        "critical_path_wall_s": round(critical, 4),
        "serial_wall_s": round(total, 4),
        "critical_path_events_per_sec": round(len(fs) / critical, 1),
        "serial_events_per_sec": round(len(fs) / total, 1),
        "_result": result,
    }


def migration_cell(factories, fs) -> Dict:
    """Mid-stream live migration at 4 shards must keep every per-tenant
    trace bitwise equal to the unsharded fleet."""
    ref = FleetEngine({tid: f() for tid, f in factories.items()}).run(fs)
    router = FleetRouter({tid: f() for tid, f in factories.items()},
                         num_shards=4)
    events = list(fs)
    half = len(events) // 2
    for ev in events[:half]:
        router.submit(ev)
    router.drain()
    moved = 0
    for tid in list(router.tenant_ids)[::4]:
        src = router.shard_of(tid)
        dst = next(s for s in router.shard_ids if s != src)
        if router.migrate_tenant(tid, dst):
            moved += 1
    for ev in events[half:]:
        router.submit(ev)
    router.drain()
    assert_same_traces(ref, router.result(), "migration")
    return {
        "num_shards": 4,
        "tenants_migrated": moved,
        "directory_overrides": len(router.directory.overrides),
        "traces_bit_identical": True,
    }


def parallel_cell(factories, fs, num_shards: int) -> Dict:
    """The same placement over real worker processes — measured wall,
    informative only (speedup is capped by the core count)."""
    t0 = time.perf_counter()
    with ProcessShardSet(factories, num_shards=num_shards) as procs:
        spawn_wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        for ev in fs:
            procs.submit(ev)
        procs.drain(compute="numpy")    # workers never reach for a chip
        wall = time.perf_counter() - t0
        result = procs.result()
    assert result.ticks == len(fs)
    return {
        "num_shards": num_shards,
        "cpu_count": os.cpu_count(),
        "spawn_wall_s": round(spawn_wall, 4),
        "wall_s": round(wall, 4),
        "events_per_sec": round(len(fs) / wall, 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: small fleet, short streams")
    ap.add_argument("--out", default="BENCH_router.json")
    ap.add_argument("--chaos", choices=("none", "serialize"),
                    default="none",
                    help="serialize: migrate every tenant onto s0 before "
                         "the stream so the critical path collapses and "
                         "the router_scaling floor must trip; never use "
                         "for a checked-in baseline")
    ap.add_argument("--skip-parallel", action="store_true",
                    help="skip the process-parallel lane (informative "
                         "only; spawning workers is slow on tiny runners)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        tenants, rows, cols, qpt = 16, 1_500, 5, 100
        alpha, delta, partitions = 2.5, 5, 8
    else:
        tenants, rows, cols, qpt = 64, 4_000, 6, 64
        alpha, delta, partitions = 4.0, 8, 8

    factories = make_factories(tenants, rows, cols, alpha, delta,
                               partitions)
    fs = make_stream(factories, rows, cols, qpt, seed=7)

    # Smoke walls are tens of milliseconds; best-of-3 keeps scheduler
    # noise on small CI runners out of the gated ratios.
    repeats = 3 if args.smoke else 1
    results: List[Dict] = []
    by_shards: Dict[int, Dict] = {}
    for n in SHARD_COUNTS:
        row = sweep_cell(factories, fs, n, args.chaos)
        for _ in range(repeats - 1):
            again = sweep_cell(factories, fs, n, args.chaos)
            again.pop("_result")
            if again["critical_path_wall_s"] < row["critical_path_wall_s"]:
                again["_result"] = row.pop("_result")
                row = again
        by_shards[n] = row
        print(f"shards={n}  critical-path="
              f"{row['critical_path_events_per_sec']:9.1f}/s  "
              f"(slowest shard {row['critical_path_wall_s']:.3f}s of "
              f"{row['serial_wall_s']:.3f}s total)", flush=True)

    # the 1-shard router is bit-invisible over a plain fleet
    ref = FleetEngine({tid: f() for tid, f in factories.items()}).run(fs)
    assert_same_traces(ref, by_shards[1].pop("_result"), "one-shard")
    print("one-shard trace identity: ok", flush=True)
    for n in SHARD_COUNTS[1:]:
        by_shards[n].pop("_result")
    results = [by_shards[n] for n in SHARD_COUNTS]

    base = by_shards[1]["critical_path_events_per_sec"]
    scaling = {f"shards{n}_vs_1":
               round(by_shards[n]["critical_path_events_per_sec"] / base, 4)
               for n in SHARD_COUNTS[1:]}
    print("scaling vs 1 shard: " + ", ".join(
        f"{k}=x{v:.2f}" for k, v in scaling.items()), flush=True)
    if args.chaos == "none":
        assert scaling["shards4_vs_1"] >= 2.0, \
            f"4-shard critical path below 2x: {scaling['shards4_vs_1']}"

    migration = migration_cell(factories, fs)
    print(f"migration      moved={migration['tenants_migrated']} "
          f"overrides={migration['directory_overrides']} "
          f"bit_identical={migration['traces_bit_identical']}", flush=True)

    parallel = None
    if not args.skip_parallel:
        parallel = parallel_cell(factories, fs, num_shards=2)
        print(f"parallel(2p)   {parallel['events_per_sec']:9.1f}/s "
              f"measured on {parallel['cpu_count']} cpu(s)", flush=True)

    payload = {
        "benchmark": "router",
        "units": "events/sec; critical path = total events / slowest "
                 "shard's individually-timed drain (shards share no "
                 "state, so parallel deployment is gated by the slowest "
                 "shard); the gated section is a machine-normalized "
                 "ratio vs the 1-shard router",
        "config": {
            "scenario": SCENARIO, "tenants": tenants, "rows": rows,
            "columns": cols, "queries_per_tenant": qpt, "alpha": alpha,
            "delta": delta, "partitions": partitions,
            "smoke": bool(args.smoke), "chaos": args.chaos,
            "platform": platform.platform(), "numpy": np.__version__,
        },
        "results": results,
        "migration": migration,
        "parallel": parallel,
        "router_scaling": {SCENARIO: scaling},
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
