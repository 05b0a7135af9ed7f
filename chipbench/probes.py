"""What the harness reads from the running program, from the outside.

* :class:`CompileMeter`: backend compiles and persistent-cache hits, from
  JAX's own monitoring events.
* :class:`Spans`: named host spans around calls into the program's layers
  (frontend pump, fleet plane pass, policy decide, candidate build, due
  swaps, layout materialization).  Each is a ``jax.profiler.TraceAnnotation``
  in the profiler's trace, so device idle gaps can be named by what the
  host was doing, and its seconds are summed here for the span shares.
* :class:`KernelLaunches`: the plane shapes the fused pass runs on (so
  set-up can compile every shape the window will use), and the work each
  decision-kernel launch needs, for the kernel's roofline: the queries,
  states and partitions it scores, read from the tenants' ``StateMatrix``
  at the call, not from the padded plane the program hands the kernel.

Spans and launch work are recorded only in a traced run (``--trace 1``).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Counts backend compiles (and their seconds) and cache hits."""

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
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


class Spans:
    """Host spans by name: seconds summed while :attr:`on`, and a
    ``TraceAnnotation`` of the same name for the profiler."""

    def __init__(self, jax):
        self._annotation = jax.profiler.TraceAnnotation
        self.seconds: Dict[str, float] = collections.defaultdict(float)
        self.counts: Dict[str, int] = collections.defaultdict(int)
        self.on = False

    @contextlib.contextmanager
    def span(self, name: str):
        with self._annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if self.on:
                    self.seconds[name] += time.perf_counter() - t0
                    self.counts[name] += 1

    def wrap(self, owner, attr: str, name: str) -> None:
        """Put a span of ``name`` around ``owner.attr`` (a function or
        method looked up on ``owner`` at call time); a missing one is left
        out, and so is its span."""
        inner = getattr(owner, attr, None)
        if inner is None:
            return

        @functools.wraps(inner)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return inner(*args, **kwargs)
        setattr(owner, attr, wrapped)


@dataclasses.dataclass
class Work:
    """What one scoring call needs, counted from the states it scores and
    not from the plane's padded capacity: ``items`` (frame, tenant) queries
    from tenants with registered states; over those items, the states and
    the partitions of those states; and, once per distinct tenant, the
    partitions of its states (the part of the plane the call reads)."""

    items: int
    item_states: int
    item_partitions: int
    plane_partitions: int
    columns: int


class KernelLaunches:
    """Watches the decision kernel: the plane shapes its fused pass is
    called with (always), and, while :attr:`on`, the work each launch
    needs (None for a launch made outside a watched scoring call)."""

    def __init__(self, compute_module, kernel_module):
        self.launches: List[Optional[Work]] = []
        self.planes: set = set()        # (C, T, S, P) of fused passes
        self.on = False
        self._work: Optional[Work] = None
        scan = compute_module.fused_frames_scan
        kernel = kernel_module.fused_decision_pallas

        @functools.wraps(scan)
        def frames_scan(q_lo, q_hi, minsT, *args, **kwargs):
            self.planes.add(tuple(minsT.shape))
            return scan(q_lo, q_hi, minsT, *args, **kwargs)

        @functools.wraps(kernel)
        def launch(*args, **kwargs):
            if self.on:
                self.launches.append(self._work)
            return kernel(*args, **kwargs)

        compute_module.fused_frames_scan = frames_scan
        kernel_module.fused_decision_pallas = launch

    @contextlib.contextmanager
    def _scoring(self, work: Work):
        outer, self._work = self._work, work
        try:
            yield
        finally:
            self._work = outer

    def watch(self, fleet_matrix_cls, state_matrix_cls, matrices) -> None:
        """Record the work of the fleet's fused pass and of a tenant's own
        pass.  ``matrices`` maps a tenant id to its ``StateMatrix``."""

        def states(sm) -> Tuple[int, int]:
            ids = sm.state_ids
            return len(ids), sum(sm.metadata(s).mins.shape[0] for s in ids)

        fleet_pass = fleet_matrix_cls.estimate_frames
        own_pass = state_matrix_cls.estimate

        @functools.wraps(fleet_pass)
        def estimate_frames(fm, frames, *args, **kwargs):
            if not self.on:
                return fleet_pass(fm, frames, *args, **kwargs)
            per_tenant: Dict[str, Tuple[int, int]] = {}
            items = n_states = n_parts = 0
            for frame in frames:
                for item in frame:
                    tid = item[0]
                    if tid not in per_tenant:
                        sm = matrices.get(tid)
                        per_tenant[tid] = ((0, 0) if sm is None
                                           or tid not in fm else states(sm))
                    s, p = per_tenant[tid]
                    if s:
                        items += 1
                        n_states += s
                        n_parts += p
            work = Work(items, n_states, n_parts,
                        sum(p for _, p in per_tenant.values()),
                        fm.num_columns or 0)
            with self._scoring(work):
                return fleet_pass(fm, frames, *args, **kwargs)

        @functools.wraps(own_pass)
        def estimate(sm, *args, **kwargs):
            if not self.on:
                return own_pass(sm, *args, **kwargs)
            s, p = states(sm)
            with self._scoring(Work(1, s, p, p, sm.num_columns or 0)):
                return own_pass(sm, *args, **kwargs)

        fleet_matrix_cls.estimate_frames = estimate_frames
        state_matrix_cls.estimate = estimate


def install_spans(spans: Spans) -> None:
    """Span the program's layer boundaries (module attributes looked up at
    call time by the program, so the wrappers sit on its path)."""
    from repro.core import layouts, qdtree
    from repro.engine import LayoutEngine, OreoPolicy
    from repro.engine.fleet_matrix import FleetMatrix
    spans.wrap(FleetMatrix, "estimate_frames", "plane_pass")
    spans.wrap(OreoPolicy, "decide", "decide")
    spans.wrap(qdtree, "build_qdtree_layout", "candidate_build")
    spans.wrap(LayoutEngine, "_apply_due_swaps", "reorg_swaps")
    spans.wrap(layouts.Layout, "materialize", "materialize")
