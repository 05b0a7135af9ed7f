#!/usr/bin/env python3
"""Chip smoke run: the served OREO decision path, end to end, on one TPU.

Deployment: four tenants, each a TPC-H ``lineitem``-like table
(:func:`repro.data.datasets.make_tpch_like`: 12 columns and 6,001,215 rows,
the SF1 ``lineitem`` row count) holding float32 values, every layout cut
into 1,024 partitions.  Traffic: the ``sudden_shift`` drift scenario, 500
queries per tenant, each query's bounds rounded outward to float32 — the
deployment's schema choice that makes the float32 kernel path exact.

Path: ``ServeFrontend(FrontendConfig(batched=True, compute="pallas_fused"))``
over a one-shard ``FleetRouter`` over a ``FleetEngine`` of ``OreoPolicy``
tenants on ``InMemoryBackend(compute="pallas_fused")``; every fused pass
scores its frames in one launch of the decision megakernel
(:mod:`repro.kernels.decision_fused`), compiled by Mosaic.

* Phase A: atomic tenants.
* Phase B: the same tenants and stream with incremental migrations under a
  finite row budget and ``reorg_compute="pallas_fused"``, so the
  micro-move planner runs the kernel's ``freq`` variant too.

Each phase replays the stream through ``FleetEngine.run`` with
``compute="numpy"`` and requires a bit-identical trace: total cost,
per-tenant query costs and reorg indices, and the alpha ledgers; every
tenant must reorganize.  A float32-guard fallback to the numpy pass is an
error.  Events per second are a smoke figure, not a benchmark.

Run on a TPU host::

    python3 chip_smoke.py

The last line is then one JSON object naming the device.  Without a TPU
the script exits nonzero before doing any work.  A CPU rehearsal at a
reduced row count runs the kernels in interpret mode and prints no such
line::

    JAX_PLATFORMS=cpu python3 chip_smoke.py --rows 20000
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import dataclasses
import json
import os
import sys
import time
import warnings

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

from repro.core import OreoConfig, build_default_layout, make_generator  # noqa: E402
from repro.core import layout_manager as lm  # noqa: E402
from repro.core import workload as wl  # noqa: E402
from repro.data.datasets import make_tpch_like  # noqa: E402
from repro.engine import (FleetEngine, FleetRouter, InMemoryBackend,  # noqa: E402
                          LayoutEngine, OreoPolicy)
from repro.serve import FrontendConfig, ServeFrontend  # noqa: E402

LINEITEM_SF1_ROWS = 6_001_215
TENANTS = 4
PARTITIONS = 1024
QUERIES_PER_TENANT = 500
ALPHA = 4.0
DELTA = 10
#: Phase B's per-tick migration budget, as a fraction of a table: a full
#: rewrite takes this many ticks.
MIGRATION_TICKS = 32
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class LaunchCounter:
    """Counts launches of the decision megakernel by variant and mode.

    Wraps the kernel's jitted call, which every entry point goes through
    once per ``pallas_call`` launch; the wrapped function stays reachable
    as :attr:`call` for lowering.  :attr:`shapes` counts launches per
    (variant, frame bounds shape, plane shape), one kernel compile each.
    :attr:`seconds` is the host-clock time from each launch to its outputs
    being ready (the caller reads them back at once), first-call compiles
    included.
    """

    def __init__(self, module, jax):
        self.call = module._fused_call
        self.counts = collections.Counter()
        self.shapes = collections.Counter()
        self.seconds = 0.0
        self._block = jax.block_until_ready
        module._fused_call = self

    def __call__(self, *args, emit_scan, emit_cost, emit_freq, interpret,
                 **kw):
        variant = "+".join(name for name, on in (
            ("scan", emit_scan), ("cost", emit_cost), ("freq", emit_freq))
            if on)
        self.counts[(variant, "interpret" if interpret else "compiled")] += 1
        self.shapes[(variant, args[0].shape, args[2].shape)] += 1
        t0 = time.perf_counter()
        out = self._block(self.call(
            *args, emit_scan=emit_scan, emit_cost=emit_cost,
            emit_freq=emit_freq, interpret=interpret, **kw))
        self.seconds += time.perf_counter() - t0
        return out

    @property
    def total(self) -> int:
        return sum(self.counts.values())


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's own events."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def make_tables(rows: int, seed: int) -> dict:
    """One seeded lineitem-like table per tenant, values rounded to
    float32 (kept in float64 arrays, as the engine stores tables)."""
    tables = {}
    for t in range(TENANTS):
        data, _ = make_tpch_like(rows, seed=seed + t)
        tables[f"t{t}"] = data.astype(np.float32).astype(np.float64)
    return tables


def round_out_f32(lo: np.ndarray, hi: np.ndarray):
    """Widen [lo, hi] to the nearest float32 bounds that contain it."""
    lo32, hi32 = lo.astype(np.float32), hi.astype(np.float32)
    lo32 = np.where(lo32 > lo, np.nextafter(lo32, np.float32(-np.inf)), lo32)
    hi32 = np.where(hi32 < hi, np.nextafter(hi32, np.float32(np.inf)), hi32)
    return lo32.astype(np.float64), hi32.astype(np.float64)


def make_events(tables: dict, seed: int) -> list:
    col_lo = np.min([d.min(axis=0) for d in tables.values()], axis=0)
    col_hi = np.max([d.max(axis=0) for d in tables.values()], axis=0)
    fs = wl.make_drift_scenario("sudden_shift", col_lo, col_hi,
                                num_tenants=TENANTS,
                                queries_per_tenant=QUERIES_PER_TENANT,
                                seed=seed)
    return [wl.QueryEvent(tid, wl.Query(*round_out_f32(q.lo, q.hi),
                                        template_id=q.template_id))
            for tid, q in fs.events]


def make_engines(tables: dict, *, compute: str, incremental: bool) -> dict:
    engines = {}
    for k, (tid, data) in enumerate(tables.items()):
        cfg = OreoConfig(alpha=ALPHA, seed=k, delta=DELTA,
                         manager=lm.LayoutManagerConfig(
                             target_partitions=PARTITIONS))
        policy = OreoPolicy(data, build_default_layout(0, data, PARTITIONS),
                            make_generator("qdtree", seed=k), cfg)
        engines[tid] = LayoutEngine(
            policy, InMemoryBackend(data, compute=compute), delta=cfg.delta,
            incremental=incremental,
            rows_per_tick=(-(-len(data) // MIGRATION_TICKS)
                           if incremental else None),
            reorg_compute=compute)
    return engines


def alpha_ledger(engine: LayoutEngine) -> list:
    """Every α charge: one per atomic reorganization, or each migration's
    whole amortization record in incremental mode."""
    executor = engine.reorg_executor
    if executor is None:
        return [(i, engine.alpha) for i in engine.result().reorg_indices]
    return [dataclasses.astuple(m) for m in executor.migrations]


def serve(engines: dict, events: list):
    """The served path: a frontend that pumps as requests arrive."""
    router = FleetRouter(engines, num_shards=1)
    frontend = ServeFrontend(router, FrontendConfig(batched=True,
                                                    compute="pallas_fused"))
    chunk = frontend.config.pump_chunk
    t0 = time.perf_counter()
    for ev in events:
        frontend.submit_blocking(ev)
        if frontend.queue_depth >= chunk:
            frontend.pump()
    frontend.flush()
    wall = time.perf_counter() - t0
    stats = frontend.stats()
    check(stats["processed"] == len(events), "frontend dropped events")
    check(stats["breaker"]["opens"] == 0 and stats["shed_count"] == 0,
          f"breaker opened: {stats['breaker']}")
    return frontend.result(), {tid: router.tenant(tid) for tid in engines}, \
        wall


def reference(tables: dict, events: list, incremental: bool):
    """The same stream through the numpy ``FleetEngine.run``."""
    engines = make_engines(tables, compute="numpy", incremental=incremental)
    t0 = time.perf_counter()
    result = FleetEngine(engines).run(events)
    return result, engines, time.perf_counter() - t0


def check_phase(name: str, got, got_engines: dict, want,
                want_engines: dict) -> dict:
    check(got.total_cost == want.total_cost,
          f"phase {name}: total_cost {got.total_cost!r} != "
          f"{want.total_cost!r}")
    reorgs = {}
    for tid in got_engines:
        a, b = got.per_tenant[tid], want.per_tenant[tid]
        check(a.query_costs.tobytes() == b.query_costs.tobytes(),
              f"phase {name}: {tid} query_costs differ")
        check(a.reorg_indices == b.reorg_indices,
              f"phase {name}: {tid} reorg_indices {a.reorg_indices} != "
              f"{b.reorg_indices}")
        check(alpha_ledger(got_engines[tid])
              == alpha_ledger(want_engines[tid]),
              f"phase {name}: {tid} alpha ledgers differ")
        check(len(a.reorg_indices) >= 1,
              f"phase {name}: {tid} never reorganized")
        reorgs[tid] = len(a.reorg_indices)
    return reorgs


def kernel_parity(seed: int) -> None:
    """All three outputs in one launch at the deployment's widths, against
    the jnp oracle: scan exactly, cost and freq to float32 rounding (the
    oracle sums in another order).  40 frames span two frame blocks, the
    second padded, so the freq block is revisited."""
    from repro.kernels.decision_fused import ops
    rng = np.random.default_rng(seed)
    b, s, w, c = 40, 8, 64, 12
    p_min = rng.uniform(0, 1, (TENANTS, s, PARTITIONS, c)).astype(np.float32)
    p_max = p_min + rng.uniform(0, 0.5, p_min.shape).astype(np.float32)
    q_lo = rng.uniform(0, 1, (b, TENANTS, c)).astype(np.float32)
    q_hi = q_lo + rng.uniform(0, 0.5, q_lo.shape).astype(np.float32)
    rows = rng.integers(1, 6000, (TENANTS, s, PARTITIONS)).astype(np.float32)
    inv = (1.0 / rows.sum(axis=-1)).astype(np.float32)
    w_lo = rng.uniform(0, 1, (w, c)).astype(np.float32)
    w_hi = w_lo + rng.uniform(0, 0.5, w_lo.shape).astype(np.float32)
    operands = (q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi)
    got = [np.asarray(x) for x in ops.fused_decision(*operands)]
    want = [np.asarray(x) for x in ops.fused_decision(*operands,
                                                      use_kernel=False)]
    check(np.array_equal(got[0], want[0]), "kernel scan != jnp oracle")
    check(np.allclose(got[1], want[1], rtol=1e-6, atol=1e-7),
          "kernel cost != jnp oracle")
    check(np.allclose(got[2], want[2], rtol=1e-6, atol=1e-7),
          "kernel freq != jnp oracle")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per table for a CPU rehearsal "
                         "(JAX_PLATFORMS=cpu, interpret mode); the chip run "
                         f"always uses {LINEITEM_SF1_ROWS:,}")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    import jax

    from repro.kernels.decision_fused import decision_fused
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    meter = CompileMeter(jax)
    kernel = LaunchCounter(decision_fused, jax)
    dev = jax.devices()[0]
    rehearsal = args.rows is not None
    if dev.platform != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r}); this run "
              f"only counts on the chip", file=sys.stderr)
        return 2
    if dev.platform == "tpu" and rehearsal:
        print("chip_smoke: --rows is for the CPU rehearsal only",
              file=sys.stderr)
        return 2
    rows = args.rows if rehearsal else LINEITEM_SF1_ROWS
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)
    # Any float32-guard fallback to the numpy pass fails the run.
    warnings.filterwarnings("error", message=r".*float32",
                            category=RuntimeWarning)

    t0 = time.perf_counter()
    tables = make_tables(rows, args.seed)
    events = make_events(tables, args.seed)
    print(f"setup: {TENANTS} tenants x {rows:,} rows x 12 columns, "
          f"{PARTITIONS} partitions per layout, {len(events)} sudden_shift "
          f"events ({time.perf_counter() - t0:.1f} s)", flush=True)

    # The numpy references run in a worker thread while this thread serves
    # through the chip: both are host-bound numpy over the same read-only
    # tables, and numpy releases the GIL in its heavy calls.
    phases = (("A", False), ("B", True))
    with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
        refs = {name: pool.submit(reference, tables, events, incremental)
                for name, incremental in phases}
        for name, incremental in phases:
            before, kernel_s = kernel.total, kernel.seconds
            got, got_engines, wall = serve(
                make_engines(tables, compute="pallas_fused",
                             incremental=incremental), events)
            launched = kernel.total - before
            kernel_s = kernel.seconds - kernel_s
            check(launched > 0, f"phase {name}: the megakernel never ran")
            want, want_engines, ref_wall = refs[name].result()
            reorgs = check_phase(name, got, got_engines, want, want_engines)
            print(f"phase {name} "
                  f"({'incremental' if incremental else 'atomic'}): trace "
                  f"bit-identical to the numpy FleetEngine.run reference "
                  f"(total_cost={got.total_cost!r}, reorgs={reorgs}); "
                  f"{launched} kernel launches taking {kernel_s:.2f} s "
                  f"(host clock, compiles included); served {len(events)} "
                  f"events in {wall:.2f} s = {len(events) / wall:.1f} events/s "
                  f"(smoke figure with the reference running alongside, not "
                  f"a benchmark; numpy reference {ref_wall:.2f} s)",
                  flush=True)

    kernel_parity(args.seed)
    print("kernel parity at the deployment's widths (scan+cost+freq, one "
          "launch) vs the jnp oracle: scan exact, cost and freq within "
          "float32 rounding", flush=True)

    launches = dict(kernel.counts)
    mode = "interpret" if rehearsal else "compiled"
    check(all(m == mode for _, m in launches),
          f"kernel launches in the wrong mode: {launches}")
    check(any(v.startswith("scan") for v, _ in launches),
          "the scan variant never ran")
    check(any(v == "freq" for v, _ in launches), "the freq variant never ran")
    print(f"kernel launches: {sum(launches.values())} "
          f"({', '.join(f'{v}/{m}={n}' for (v, m), n in launches.items())})")
    print(f"kernel shapes: {len(kernel.shapes)} "
          f"(variant, frames (B, T, C), plane (C, T, S, P): launches): "
          + "; ".join(f"{v} {q} {p}: {n}"
                      for (v, q, p), n in sorted(kernel.shapes.items())))
    print(f"compiles: {meter.compiles} in {meter.seconds:.2f} s "
          f"(persistent-cache hits: {meter.cache_hits})")
    print("float32-guard fallbacks: 0 (each would have raised)")
    if rehearsal:
        print("CPU rehearsal passed (interpret mode; not a chip result)")
        return 0

    sds = jax.ShapeDtypeStruct
    f32 = np.float32
    lowered = kernel.call.lower(
        sds((8, TENANTS, 12), f32), sds((8, TENANTS, 12), f32),
        sds((12, TENANTS, 8, PARTITIONS), f32),
        sds((12, TENANTS, 8, PARTITIONS), f32), None, None, None, None,
        emit_scan=True, emit_cost=False, emit_freq=False,
        bb=decision_fused.DEFAULT_BB, bp=decision_fused.DEFAULT_BP,
        interpret=False).as_text()
    check("tpu_custom_call" in lowered,
          "the lowered megakernel holds no tpu_custom_call")
    print("lowered megakernel: tpu_custom_call present")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
