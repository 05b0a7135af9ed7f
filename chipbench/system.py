"""The system under test, built from a deployment's configuration file.

The served path is the program's: ``ServeFrontend(batched=True,
compute="pallas_fused")`` over a one-shard ``FleetRouter`` over a
``FleetEngine`` of ``OreoPolicy`` tenants on ``InMemoryBackend(compute=
"pallas_fused")``, every fused pass one launch of the decision megakernel.
This module only assembles it; the timing and the checks are the
harness's.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from repro.core import OreoConfig, build_default_layout, make_generator
from repro.core import layout_manager as lm
from repro.core import workload as wl
from repro.engine import FleetRouter, InMemoryBackend, LayoutEngine, OreoPolicy
from repro.serve import FrontendConfig, ServeFrontend

COMPUTE = "pallas_fused"


def make_engines(tables: Dict[str, np.ndarray], cfg: dict,
                 compute: str = COMPUTE) -> Dict[str, LayoutEngine]:
    """One engine per tenant; tenant ``k``'s draws are seeded with ``k``.
    Reorganization is atomic: a swap rewrites the whole table."""
    if cfg["reorg"] != "atomic":
        raise ValueError(f"unsupported reorg mode {cfg['reorg']!r}")
    engines = {}
    mgr = cfg["manager"]
    for k, (tid, data) in enumerate(tables.items()):
        oreo = OreoConfig(
            alpha=cfg["alpha"], seed=k, delta=cfg["delta"], gamma=cfg["gamma"],
            manager=lm.LayoutManagerConfig(
                window_size=mgr["window_size"], gen_every=mgr["gen_every"],
                epsilon=mgr["epsilon"], max_states=mgr["max_states"],
                rtbs_size=mgr["rtbs_size"], rtbs_lambda=mgr["rtbs_lambda"],
                target_partitions=cfg["partitions"]))
        policy = OreoPolicy(data, build_default_layout(0, data,
                                                       cfg["partitions"]),
                            make_generator("qdtree", seed=k), oreo)
        engines[tid] = LayoutEngine(
            policy, InMemoryBackend(data, compute=compute), delta=cfg["delta"])
    return engines


def make_frontend(engines: Dict[str, LayoutEngine]) -> ServeFrontend:
    """The served path over the engines.  The circuit breaker is off, so
    no decision depends on wall time."""
    return ServeFrontend(FleetRouter(engines, num_shards=1),
                         FrontendConfig(batched=True, compute=COMPUTE,
                                        breaker_open_frac=None))


def requests(events) -> List[wl.QueryEvent]:
    """The generator's events as the program's request type."""
    return [wl.QueryEvent(e.tenant, wl.Query(e.lo, e.hi,
                                             template_id=e.template))
            for e in events]


@dataclasses.dataclass
class TenantTrace:
    """What the program produced for one tenant, per query."""

    costs: np.ndarray
    states: np.ndarray
    reorgs: List[int]
    alpha_ledger: list              # (index, charge) entries, in order
    builds: int


def traces(frontend: ServeFrontend) -> Dict[str, TenantTrace]:
    out = {}
    for tid, r in frontend.result().per_tenant.items():
        engine = frontend.fleet.tenant(tid)
        out[tid] = TenantTrace(
            costs=np.asarray(r.query_costs, dtype=np.float64),
            states=np.asarray(r.state_seq, dtype=np.int64),
            reorgs=list(r.reorg_indices),
            alpha_ledger=[(i, engine.alpha) for i in r.reorg_indices],
            builds=int(r.info.get("candidates_generated", 0)))
    return out


def engine_seconds(frontend: ServeFrontend) -> Dict[str, float]:
    """The engines' own host timers, summed over tenants: policy decide
    (candidate builds included), reorg (charge, due swaps) and serve."""
    r = frontend.result()
    return {"decide": r.decide_seconds, "reorg": r.reorg_seconds,
            "serve": r.serve_seconds}
