#!/usr/bin/env python3
"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

A cell names a deployment (``configs/<config>.json``: tables, partitions,
OReO's settings), a traffic mix (``traffic/<mix>.json``) and, in
``cells/<cell>.json``, its offered rate and the limits of its correctness
checks.  Each metric is read by ``metrics/<name>.py``.  Nothing here lists
them: a new deployment, mix, cell or metric is new files and entries.

One run:

1. Set-up (``setup_s``): the tables and the stream are made from
   ``--seed``; the served path is built (``system.py``); a prefix of the
   stream is served until every tenant has built its first candidate
   layout and moved to it (``warmup_per_tenant``); every decision-kernel
   shape the window can use is compiled, or loaded from the persistent
   compile cache in ``.jax_cache/`` at the root of the checkout.
2. The window: an open loop in one thread.  ``rate * seconds`` events fall
   due over ``--seconds`` seconds (Poisson-like gaps); each is submitted to
   the front end once due, and the front end is pumped whenever events
   wait, so an event that falls due during a long pump waits behind it.
   Each event is timed from when it was due to when the pump that served
   it returned; the window ends when the last due event has been served.
   With ``--trace 1`` the window is traced by the JAX profiler and the
   harness's host spans are on.
3. The check: every event served, warm-up and window, is compared with a
   plain reference (``reference.py``) replaying each tenant's queries;
   each number compared is printed beside its limit.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``.  Without a TPU (or with fewer chips than
the cell asks for) the run exits nonzero and prints no such line.  A CPU
rehearsal at a reduced row count runs the kernels in interpret mode and
prints no result line either::

    JAX_PLATFORMS=cpu python3 chipbench/run.py --workload <cell> \\
        --seed 1 --seconds 5 --trace 0 --rows 20000

``--rate`` offers another rate than the cell's, for the sweep that sets
it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")
for _path in (os.path.join(ROOT, "src"), ROOT):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from chipbench import generator, reference, tables  # noqa: E402


class BenchError(Exception):
    """A run that cannot produce a result."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    chips: int
    config: dict
    mix: dict
    rate: float
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench: Optional[dict] = None,
              root: str = ROOT) -> Cell:
    """Resolve a cell of ``BENCHMARK.json`` and every file it names."""
    bench = bench if bench is not None else load_json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    params = load_json(root, "chipbench", "cells", f"{name}.json")

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", [name])
    return Cell(name=name, root=root, chips=int(entry["chips"]),
                config=load_json(root, conf["file"]),
                mix=load_json(root, "chipbench", "traffic",
                              f"{entry['traffic']}.json"),
                rate=float(params["rate_per_s"]), limits=params["limits"],
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def make_stream(cell: Cell, tids: List[str], col_lo: np.ndarray,
                col_hi: np.ndarray, seconds: float, seed: int
                ) -> generator.Stream:
    """The stream one run of ``cell`` serves."""
    return generator.make_stream(
        cell.mix, tids, col_lo, col_hi, rate=cell.rate, seconds=seconds,
        warmup_per_tenant=warmup_per_tenant(cell.config), seed=seed,
        traffic_dir=os.path.join(cell.root, "chipbench", "traffic"))


def warmup_per_tenant(cfg: dict) -> int:
    """Queries a tenant serves before the window: up to its layout
    manager's first candidate build (the first multiple of ``gen_every``
    at which the window is at least half full), then two Δ-delays more.
    The first candidate replaces the arrival-order layout, which every
    query scans whole, within a few queries, and its rewrite lands δ
    queries after the charge; so set-up, not the window, pays for it.
    Under incremental reorganization that rewrite is a migration of up to
    ``migration_steps`` steps, and the warm-up takes one step more than
    that, so the migration completes in set-up and its hybrid planes are
    scored, and their kernel shapes compiled, there."""
    mgr = cfg["manager"]
    half = mgr["window_size"] // 2
    n = (mgr["gen_every"] * max(1, -(-half // mgr["gen_every"]))
         + 2 * cfg["delta"])
    if cfg["reorg"] == "incremental":
        n += cfg["migration_steps"] + 1
    return n


# -- the run record the metric readers see --------------------------------

@dataclasses.dataclass
class Run:
    window_s: float                 # window start .. last due event served
    setup_s: float
    latencies_s: np.ndarray         # per window event, due -> served
    queue_waits_s: np.ndarray       # per window event, due -> pump start
    lateness_s: np.ndarray          # per window event, due -> submitted
    engine_delta: Dict[str, float]  # engine timers' growth over the window
    compiles_in_window: int
    device_kind: str
    spans: object = None            # probes.Spans, traced runs only
    launches: list = dataclasses.field(default_factory=list)  # probes.Work
    trace: object = None            # trace_reduce.TraceSummary


def reader(root: str, name: str):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = os.path.join(root, "chipbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(root: str, entries: List[dict], run: Run
                 ) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = reader(root, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- the served path --------------------------------------------------------

def warm_kernel_shapes(frontend, planes: set) -> int:
    """Compile (or load) the fused pass at every shape the window can use.

    ``planes`` holds the ``(C, T, S, P)`` planes the warm-up scored.  A
    pump scores up to ``pump_chunk`` frames in one launch, and the program
    pads the frame count, so every count from 1 to ``pump_chunk`` is run on
    each plane; a tenant whose primed scores went stale scores one query
    over its own ``(C, 1, S, P)`` plane.  Returns the number of calls.
    """
    from repro.engine import compute
    shapes = {(b, t, c, s, p) for c, t, s, p in planes
              for b in range(1, frontend.config.pump_chunk + 1)}
    shapes |= {(1, 1, c, s, p) for c, _, s, p in planes}
    for b, t, c, s, p in sorted(shapes):
        q = np.zeros((b, t, c))
        plane = np.zeros((c, t, s, p))
        compute.fused_frames_scan(q, q, plane, plane)
    return len(shapes)


def serve_window(frontend, requests: list, due: np.ndarray, spans,
                 annotate, stalls: Dict[str, float]) -> tuple:
    """The open loop.  Returns (start, done, started, submitted): the
    window's start and per-event pump-return, pump-start and submit times
    (``time.perf_counter`` seconds).

    Between events the loop polls the clock rather than sleeping: a
    sleep on the chip's host returned up to 100 ms late, which an event
    would have waited as latency.  ``stalls`` receives the longest submit,
    pump and pause (seconds); a pause is a gap between two clock reads of
    the idle loop, in which the process did not run."""
    n = len(requests)
    done = np.full(n, np.nan)
    started = np.full(n, np.nan)
    submitted = np.full(n, np.nan)
    base = frontend.processed
    stamped = 0

    def stamp(t_begin: float, t_end: float) -> None:
        nonlocal stamped
        k = frontend.processed - base
        started[stamped:k] = t_begin
        done[stamped:k] = t_end
        stamped = k

    with annotate("window"):
        t0 = time.perf_counter()
        i = 0
        while stamped < n:
            now = time.perf_counter()
            while i < n and t0 + due[i] <= now:
                t_sub = time.perf_counter()
                frontend.submit(requests[i])     # may pump on a full queue
                submitted[i] = t_sub
                t_end = time.perf_counter()
                stamp(t_sub, t_end)
                stalls["submit"] = max(stalls["submit"], t_end - t_sub)
                i += 1
            if frontend.queue_depth:
                with spans.span("pump"):
                    t_pump = time.perf_counter()
                    frontend.pump()
                    t_end = time.perf_counter()
                    stamp(t_pump, t_end)
                stalls["pump"] = max(stalls["pump"], t_end - t_pump)
            elif i < n:
                until, last = t0 + due[i], time.perf_counter()
                while last < until:
                    now = time.perf_counter()
                    stalls["pause"] = max(stalls["pause"], now - last)
                    last = now
    return t0, done, started, submitted



# -- the check --------------------------------------------------------------

def reference_traces(cell: Cell, stream, seed: int, rows: int,
                     precision: str = "float32", processes: bool = True
                     ) -> Dict[str, reference.Trace]:
    """Every tenant's reference replay, one process per tenant when
    ``processes`` (each makes its own copy of its table from the seed)."""
    cfg = cell.config
    tids = [f"t{k}" for k in range(cfg["tenants"])]
    jobs = []
    for k, tid in enumerate(tids):
        evs = stream.tenant_events(tid)
        jobs.append({"table": cfg["table"], "rows": rows, "seed": seed,
                     "tenants": cfg["tenants"], "k": k, "cfg": cfg,
                     "precision": precision,
                     "lo": np.stack([e.lo for e in evs]),
                     "hi": np.stack([e.hi for e in evs])})
    if not processes:
        return dict(zip(tids, map(reference.replay, jobs)))
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(len(jobs), os.cpu_count() or 1)) as pool:
        out = pool.map(reference.replay, jobs)
        pool.close()
        pool.join()
    return dict(zip(tids, out))


def compare(got: dict, want: Dict[str, reference.Trace], unserved: int,
            limits: Dict[str, float]) -> List[tuple]:
    """(name, value, limit) of every number compared.

    * ``cost_gap``: the widest gap between a served query's cost and the
      reference's, over every tenant and query;
    * ``state_mismatches``: queries whose decision state differs;
    * ``reorg_mismatches``: reorganizations charged at one side only;
    * ``ledger_mismatches``: entries of the α ledgers that differ, or are
      on one side only: one per reorganization (index, amount) if atomic,
      one per migration step that moved rows (index, rows, amount) if
      incremental;
    * ``unserved``: events due in the window that were never served.

    A tenant whose trace is shorter on one side counts every missing query
    as a cost gap of 1 and a state mismatch.
    """
    cost_gap, states, reorgs, ledger = 0.0, 0, 0, 0
    for tid, ref in want.items():
        g = got[tid]
        n = min(len(g.costs), len(ref.costs))
        missing = abs(len(g.costs) - len(ref.costs))
        if n:
            cost_gap = max(cost_gap,
                           float(np.max(np.abs(g.costs[:n] - ref.costs[:n]))))
        if missing:
            cost_gap = max(cost_gap, 1.0)
        states += int(np.sum(g.states[:n] != ref.states[:n])) + missing
        reorgs += len(set(g.reorgs) ^ set(ref.reorgs))
        ledger += sum(a != b for a, b in zip(g.alpha_ledger,
                                             ref.alpha_ledger))
        ledger += abs(len(g.alpha_ledger) - len(ref.alpha_ledger))
    values = {"cost_gap": cost_gap, "state_mismatches": states,
              "reorg_mismatches": reorgs, "ledger_mismatches": ledger,
              "unserved": unserved}
    return [(k, values[k], limits[k]) for k in values]


# -- one run ------------------------------------------------------------------

def window_counts(before: dict, after: dict, cfg: dict
                  ) -> Dict[str, Dict[str, int]]:
    """Per tenant, what the window's mechanism did: candidate builds,
    reorganizations charged and swaps (table rewrites, or migrations)
    falling due; under incremental reorganization also the migration
    steps that moved rows and charged their share of α."""
    out: Dict[str, Dict[str, int]] = {
        "candidate_builds": {}, "reorganizations_charged": {},
        "swaps_due": {}}
    incremental = cfg["reorg"] == "incremental"
    if incremental:
        out["migration_steps_charged"] = {}
    for tid in after:
        lo, hi = len(before[tid].costs), len(after[tid].costs)
        a = after[tid]
        out["candidate_builds"][tid] = a.builds - before[tid].builds
        out["reorganizations_charged"][tid] = sum(
            lo <= r < hi for r in a.reorgs)
        out["swaps_due"][tid] = sum(lo <= r + cfg["delta"] < hi
                                    for r in a.reorgs)
        if incremental:
            out["migration_steps_charged"][tid] = sum(
                lo <= entry[0] < hi for entry in a.alpha_ledger)
    return out


@dataclasses.dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: Dict[str, dict]
    device: dict
    breakdown: Optional[dict]
    checks: List[tuple]
    notes: List[str]
    counts: Dict[str, Dict[str, int]]

    def line(self) -> str:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = {k: {"value": v, "limit": lim}
                         for k, v, lim in self.checks}
        return json.dumps(out)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             rows: Optional[int] = None) -> Result:
    """One run of ``cell``; ``rows`` overrides the table size (rehearsal:
    the reference then replays in this process)."""
    import jax

    from chipbench import probes, system, trace_reduce
    from repro.engine import compute
    from repro.engine.fleet_matrix import FleetMatrix
    from repro.engine.state_matrix import StateMatrix
    from repro.kernels.decision_fused import decision_fused

    meter = probes.CompileMeter(jax)
    spans = probes.Spans(jax)
    kernels = probes.KernelLaunches(compute, decision_fused)
    if trace:
        probes.install_spans(spans)
    dev = jax.devices()[0]
    cfg = cell.config
    rows = cfg["rows"] if rows is None else rows
    tids = [f"t{k}" for k in range(cfg["tenants"])]

    tabs = {tid: tables.make(cfg["table"], rows, seed, cfg["tenants"], k)
            for k, tid in enumerate(tids)}
    col_lo = np.min([d.min(axis=0) for d in tabs.values()], axis=0)
    col_hi = np.max([d.max(axis=0) for d in tabs.values()], axis=0)
    stream = make_stream(cell, tids, col_lo, col_hi, seconds, seed)
    frontend = system.make_frontend(system.make_engines(tabs, cfg))
    del tabs
    if trace:
        kernels.watch(FleetMatrix, StateMatrix, {
            tid: frontend.fleet.tenant(tid).backend.state_matrix
            for tid in tids})
    warm = system.requests(stream.warmup)
    chunk = frontend.config.pump_chunk
    for req in warm:
        frontend.submit(req)
        if frontend.queue_depth >= chunk:
            frontend.pump()
    frontend.flush()
    before = system.traces(frontend)
    warm_calls = warm_kernel_shapes(frontend, kernels.planes)
    requests = system.requests(stream.window)
    gc.collect()
    setup_s = time.perf_counter() - T_START

    # -- the window ----------------------------------------------------------
    timers0 = system.engine_seconds(frontend)
    compiles0 = meter.compiles
    stalls = dict.fromkeys(("submit", "pump", "pause"), 0.0)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        spans.on = kernels.on = True
    t0, done, started, submitted = serve_window(
        frontend, requests, stream.due, spans, jax.profiler.TraceAnnotation,
        stalls)
    if trace:
        spans.on = kernels.on = False
        jax.profiler.stop_trace()
    compiles = meter.compiles - compiles0
    timers1 = system.engine_seconds(frontend)
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    due_abs = t0 + stream.due
    served = ~np.isnan(done)
    run = Run(window_s=float(np.nanmax(done) - t0),
              setup_s=setup_s,
              latencies_s=(done - due_abs)[served],
              queue_waits_s=(started - due_abs)[served],
              lateness_s=(submitted - due_abs)[served],
              engine_delta={k: timers1[k] - timers0[k] for k in timers0},
              compiles_in_window=compiles, device_kind=dev.device_kind,
              spans=spans if trace else None, launches=kernels.launches)
    after = system.traces(frontend)
    fe_stats = frontend.stats()
    del frontend
    gc.collect()

    notes = [f"device: platform={dev.platform} kind={dev.device_kind} "
             f"count={len(jax.devices())}",
             f"set-up: {cfg['tenants']} tenants x {rows:,} rows x "
             f"{len(col_lo)} columns, {cfg['partitions']} partitions; "
             f"{len(warm)} warm-up events; {warm_calls} kernel shape calls; "
             f"{meter.compiles} compiles in {meter.seconds:.2f} s, "
             f"{meter.cache_hits} persistent-cache hits; "
             f"setup_s={setup_s:.3f}"]
    counts = window_counts(before, after, cfg)
    late = run.lateness_s * 1e3
    lat = np.percentile(run.latencies_s * 1e3, [50, 90, 95, 99, 100])
    notes.append(
        f"window: {len(requests)} events due over {seconds} s at "
        f"{cell.rate}/s, last served {run.window_s:.3f} s after the start; "
        + "; ".join(f"{k.replace('_', ' ')} {v}" for k, v in counts.items())
        + f"; compiles {compiles}; latency ms p50/p90/p95/p99/max "
        + "/".join(f"{x:.3f}" for x in lat)
        + "; generator lateness ms "
        f"p50={np.percentile(late, 50):.3f} p95={np.percentile(late, 95):.3f} "
        f"max={late.max():.3f}; longest "
        + ", ".join(f"{k} {v * 1e3:.3f}" for k, v in stalls.items())
        + f" ms; front end processed "
        f"{fe_stats['processed']}, queue depth {fe_stats['queue_depth']}")

    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    if trace:
        summary = trace_reduce.reduce_trace(trace_reduce.find_xplane(TRACE_DIR))
        run.trace = summary
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": [list(x) for x in summary.device_ops],
                         "idle_gaps": [list(x) for x in summary.idle_by_host]}
            notes.append(
                f"trace: window {summary.window_s:.3f} s, {summary.devices} "
                f"device(s), busy {summary.busy_s:.6f} s, decision_fused "
                f"{summary.kernel_events} events {summary.kernel_s:.6f} s; "
                f"{len(run.launches)} launches recorded; spans "
                + ", ".join(f"{k}={v:.3f}s/{spans.counts[k]}"
                            for k, v in sorted(spans.seconds.items())))
    metrics = read_metrics(cell.root,
                           cell.per_layer if trace else cell.end_to_end, run)

    # -- the check (after the window, outside set-up) ------------------------
    t_ref = time.perf_counter()
    want = reference_traces(cell, stream, seed, rows,
                            processes=rows == cfg["rows"])
    unserved = int(np.sum(~served))
    checks = compare(after, want, unserved, cell.limits)
    notes.append(f"reference replay: {time.perf_counter() - t_ref:.2f} s")
    correct = all(v <= lim for _, v, lim in checks)
    return Result(correct=correct, attempted=len(requests), failed=unserved,
                  metrics=metrics, device=device, breakdown=breakdown,
                  checks=checks, notes=notes, counts=counts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="rows per table for a CPU rehearsal "
                         "(JAX_PLATFORMS=cpu, interpret mode)")
    ap.add_argument("--rate", type=float, default=None,
                    help="offered events/s in place of the cell's own, for "
                         "a sweep that finds a cell's rate")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if args.rate is not None:
        cell = dataclasses.replace(cell, rate=args.rate)
    # The cache is the checkout's own, unbounded (no eviction bookkeeping).
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    devices = jax.devices()
    rehearsal = args.rows is not None
    if rehearsal != (devices[0].platform != "tpu"):
        print(f"run.py: platform {devices[0].platform!r}: the measured run "
              f"needs a TPU, and --rows is for the CPU rehearsal only",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    enable_compile_cache()
    # Every program of the served path, however small, goes to the cache,
    # so a second run in the same checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      rows=args.rows)
    for note in result.notes:
        print(note, file=sys.stderr)
    for name, value, limit in result.checks:
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    sys.stderr.flush()
    if rehearsal:
        print(f"CPU rehearsal: correct={result.correct} metrics="
              f"{json.dumps(result.metrics)} (not a chip result)")
        return 0 if result.correct else 1
    print(result.line(), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(2)
