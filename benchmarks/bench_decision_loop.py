"""Decision-loop throughput: per-query re-padding vs. the StateMatrix plane.

Measures queries/sec through the online loop at a fixed state space of S
layouts with P partitions each, isolating the metadata plane (layout
*generation* is excluded — candidates are prebuilt — because it costs the
same on every path and would only dilute the comparison):

* ``step/reference``  — ``engine.step`` with the original per-query
  ``eval_cost_states`` re-padding estimate path (``compute="reference"``),
  the "before" number;
* ``step/statematrix`` — ``engine.step`` over the persistent packed
  StateMatrix plane (``compute="numpy"``), bit-identical decisions/costs;
* ``run/batched``     — ``engine.run``'s fast path on the same plane:
  pre-stacked query bounds, serve costs evaluated in blocks.

Writes ``BENCH_decision_loop.json``; the checked-in file tracks the perf
trajectory (acceptance: >= 5x step-loop throughput at S=8, P=256, C=8).
"""
from __future__ import annotations

import argparse
import json
import platform
import time
from typing import Dict, List

import numpy as np

from repro.core import layouts, make_templates, generate_workload
from repro.core import workload as wl
from repro.engine import Decision, InMemoryBackend, LayoutEngine
from repro.launch.compile_cache import enable_compile_cache


def make_state_space(data: np.ndarray, num_states: int,
                     partitions: int, rng) -> List[layouts.Layout]:
    """S synthetic clustered layouts: each sorts the table along a random
    projection and cuts it into equal partitions (tight zone maps, like the
    generators produce, but cheap enough to sweep)."""
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


class ScoringPolicy:
    """Minimal fixed-state decision layer: score every state per query,
    follow the argmin, never reorganize.  Isolates metadata-plane
    throughput from switching/generation effects."""

    name = "Scoring"
    alpha = 0.0

    def __init__(self, state_space: List[layouts.Layout]):
        self.state_space = state_space
        self.ids = [lay.layout_id for lay in state_space]

    def bind(self, backend) -> int:
        for lay in self.state_space:
            backend.register(lay)
        return self.ids[0]

    def decide(self, index: int, query, backend) -> Decision:
        costs = backend.estimate_costs(self.ids, query)
        return Decision(state=min(costs, key=costs.get))

    def info(self) -> dict:
        return {}


def _time_once(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def bench_config(data: np.ndarray, queries: List[wl.Query], num_states: int,
                 partitions: int, reps: int, rng) -> List[Dict]:
    state_space = make_state_space(data, num_states, partitions, rng)
    rows = []

    def fresh_engine(compute: str) -> LayoutEngine:
        space = [layouts.Layout(layout_id=lay.layout_id, name=lay.name,
                                technique=lay.technique, meta=lay.meta)
                 for lay in state_space]
        return LayoutEngine(ScoringPolicy(space), InMemoryBackend(
            data, compute=compute))

    def measure(mode: str, make_fn) -> Dict:
        secs = min(_time_once(make_fn()) for _ in range(reps))
        return {
            "S": num_states, "P": partitions, "C": int(data.shape[1]),
            "queries": len(queries), "mode": mode,
            "qps": round(len(queries) / secs, 1),
            "us_per_query": round(secs / len(queries) * 1e6, 2),
        }

    def step_loop(compute):
        engine = fresh_engine(compute)
        engine.start()

        def go():
            for q in queries:
                engine.step(q)
        return go

    def batched_run():
        engine = fresh_engine("numpy")
        engine.start()
        return lambda: engine.run(queries)

    rows.append(measure("step/reference", lambda: step_loop("reference")))
    rows.append(measure("step/statematrix", lambda: step_loop("numpy")))
    rows.append(measure("run/batched", lambda: batched_run()))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="only the S=8, P=256 acceptance point")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes, CI sanity only")
    ap.add_argument("--out", default="BENCH_decision_loop.json")
    args = ap.parse_args()
    enable_compile_cache()

    rng = np.random.default_rng(0)
    if args.smoke:
        # Sized for the CI regression gate: big enough that the
        # StateMatrix-vs-reference speedup ratio is stable run to run
        # (see benchmarks/check_regression.py), small enough to finish in
        # a few seconds on any runner.
        n_rows, n_queries, reps = 8_000, 400, 5
        sweep = [(4, 64)]
    elif args.quick:
        n_rows, n_queries, reps = 40_000, 1_000, 3
        sweep = [(8, 256)]
    else:
        n_rows, n_queries, reps = 40_000, 1_500, 3
        sweep = [(2, 64), (2, 256), (8, 64), (8, 256), (8, 1024),
                 (32, 256), (32, 1024)]
    c = 8
    data = rng.uniform(0, 100, size=(n_rows, c))
    templates = make_templates(6, c, rng)
    stream = generate_workload(templates, data.min(0), data.max(0),
                               total_queries=n_queries, seed=1,
                               segment_length=(200, 400))
    queries = list(stream.queries)

    results: List[Dict] = []
    for num_states, partitions in sweep:
        results.extend(bench_config(data, queries, num_states, partitions,
                                    reps, rng))
        print(f"S={num_states} P={partitions}: " + "  ".join(
            f"{r['mode']}={r['qps']:.0f}q/s" for r in results[-3:]),
            flush=True)

    speedups = {}
    by_key = {(r["S"], r["P"], r["mode"]): r for r in results}
    for num_states, partitions in sweep:
        ref = by_key[(num_states, partitions, "step/reference")]
        sm = by_key[(num_states, partitions, "step/statematrix")]
        run = by_key[(num_states, partitions, "run/batched")]
        speedups[f"S{num_states}_P{partitions}"] = {
            "step": round(sm["qps"] / ref["qps"], 2),
            "batched_run": round(run["qps"] / ref["qps"], 2),
        }

    payload = {
        "benchmark": "decision_loop",
        "units": "queries/sec (best of reps)",
        "config": {"rows": n_rows, "columns": c, "queries": n_queries,
                   "reps": reps, "platform": platform.platform(),
                   "numpy": np.__version__},
        "results": results,
        "speedup_vs_reference": speedups,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")
    for key, s in speedups.items():
        print(f"  {key}: step x{s['step']}, batched run x{s['batched_run']}")


if __name__ == "__main__":
    main()
