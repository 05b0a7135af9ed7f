"""The one traffic generator: a mix file's parameters -> a seeded stream.

A mix (``traffic/<name>.json``) is data:

* ``templates``: a pool of range-query templates, each a list of
  ``columns`` with one ``selectivity`` per column;
* ``tenants``: one schedule per tenant index; tenant ``i`` of ``T`` follows
  ``tenants[i % len(tenants)]``, so a mix serves any number of tenants.  A
  schedule has ``segments``, each naming a template of the pool and where
  it starts: the first has no start, a later one starts either at the
  tenant's ``query`` index (warm-up queries count from 0, the window's
  follow) or at the ``window_fraction`` of the window's seconds (its first
  window event due at or after that second).  An optional ``weight``
  (default 1) sets the tenant's share of the window's events;
* ``arrivals``: ``"poisson"``.

A mix that data cannot express names its own module instead:
``"generator": "<module>"`` runs ``make_stream`` of
``traffic/<module>.py`` with the same arguments.

A cell (``cells/<name>.json``) fixes the offered rate; the run fixes
``--seconds`` and ``--seed``.  Every seed gets the same work: the same
number of events per tenant, the same inter-arrival gaps (the quantiles of
an exponential distribution at the offered rate, so the arrivals are
Poisson-like with exactly the configured mean) and the same templates.  The
seed only draws the order of the gaps, the order of the tenants and the
positions of the query ranges.

Nothing here imports the program: events are plain arrays, which the
harness wraps into the program's request type and the reference reads as
they are.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
from typing import List, Sequence, Tuple

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


@dataclasses.dataclass
class Event:
    tenant: str
    lo: np.ndarray              # (C,) float64, float32-representable
    hi: np.ndarray
    template: int               # index into the mix's template pool


@dataclasses.dataclass
class Stream:
    warmup: List[Event]         # served before the window, as fast as it goes
    window: List[Event]         # served open-loop at ``due``
    due: np.ndarray             # (len(window),) seconds after window start

    def tenant_events(self, tenant: str) -> List[Event]:
        """One tenant's events in service order (warm-up, then window)."""
        return [e for e in self.warmup + self.window if e.tenant == tenant]


def round_out_f32(lo: np.ndarray, hi: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Widen ``[lo, hi]`` to the nearest float32 bounds that contain it."""
    lo32, hi32 = lo.astype(np.float32), hi.astype(np.float32)
    lo32 = np.where(lo32 > lo, np.nextafter(lo32, np.float32(-np.inf)), lo32)
    hi32 = np.where(hi32 < hi, np.nextafter(hi32, np.float32(np.inf)), hi32)
    return lo32.astype(np.float64), hi32.astype(np.float64)


def sample_query(template: dict, rng: np.random.Generator,
                 col_lo: np.ndarray, col_hi: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """One range query of a template: each template column gets a window
    of ``selectivity`` times the column's span at a uniform position; every
    other column is unbounded.  Bounds are rounded outward to float32."""
    c = col_lo.shape[0]
    lo = np.full(c, -np.inf)
    hi = np.full(c, np.inf)
    for col, sel in zip(template["columns"], template["selectivities"]):
        span = col_hi[col] - col_lo[col]
        width = span * sel
        start = col_lo[col] + rng.uniform(0.0, max(span - width, 1e-12))
        lo[col] = start
        hi[col] = start + width
    return round_out_f32(lo, hi)


def poisson_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the midpoint quantiles of Exp(rate)."""
    k = np.arange(n)
    return -np.log1p(-(k + 0.5) / n) / rate


def shares(n: int, weights: Sequence[float]) -> List[int]:
    """``n`` split by ``weights``: floors, then the remainder one each to
    the largest fractions (ties to the lower index)."""
    w = np.asarray(weights, dtype=np.float64)
    exact = n * w / w.sum()
    out = np.floor(exact).astype(int)
    for i in sorted(range(len(w)), key=lambda i: -(exact[i] - out[i])
                    )[:n - int(out.sum())]:
        out[i] += 1
    return [int(x) for x in out]


class Schedule:
    """One tenant's segments: which template serves its ``q``-th query,
    due at ``due`` seconds into the window (None in warm-up)."""

    def __init__(self, spec: dict, seconds: float):
        self.starts: List[Tuple[str, float, int]] = []
        for k, seg in enumerate(spec["segments"]):
            if k == 0:
                self.starts.append(("query", 0, seg["template"]))
            elif "query" in seg:
                self.starts.append(("query", seg["query"], seg["template"]))
            else:
                self.starts.append(("due", seconds * seg["window_fraction"],
                                    seg["template"]))

    def template(self, q: int, due) -> int:
        out = self.starts[0][2]
        for kind, at, tm in self.starts[1:]:
            if (q >= at) if kind == "query" else (due is not None
                                                  and due >= at):
                out = tm
        return out


def load_module(name: str, root: str = TRAFFIC_DIR):
    path = os.path.join(root, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_traffic_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make_stream(mix: dict, tenants: Sequence[str], col_lo: np.ndarray,
                col_hi: np.ndarray, *, rate: float, seconds: float,
                warmup_per_tenant: int, seed: int,
                traffic_dir: str = TRAFFIC_DIR) -> Stream:
    """The cell's stream for one run.

    ``round(rate * seconds)`` events arrive in the window, the last one due
    at ``seconds``; the tenants share them by weight.  Each tenant serves
    ``warmup_per_tenant`` queries before the window.
    """
    if "generator" in mix:
        return load_module(mix["generator"], traffic_dir).make_stream(
            mix, tenants, col_lo, col_hi, rate=rate, seconds=seconds,
            warmup_per_tenant=warmup_per_tenant, seed=seed)
    if mix.get("arrivals") != "poisson":
        raise ValueError(f"unsupported arrivals {mix.get('arrivals')!r}")
    n_cols = col_lo.shape[0]
    templates = mix["templates"]
    for tm in templates:
        if max(tm["columns"]) >= n_cols:
            raise ValueError(f"mix template {tm} names a column beyond "
                             f"the table's {n_cols}")
    t = len(tenants)
    specs = [mix["tenants"][i % len(mix["tenants"])] for i in range(t)]
    plans = [Schedule(s, seconds) for s in specs]
    n = max(int(round(rate * seconds)), t)
    order = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    gaps = order.permutation(poisson_gaps(n, rate))
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    counts = shares(n, [s.get("weight", 1.0) for s in specs])
    labels = order.permutation(np.repeat(np.arange(t), counts))
    warm_labels = order.permutation(np.repeat(np.arange(t),
                                              warmup_per_tenant))
    rngs = [np.random.default_rng(s)
            for s in np.random.SeedSequence([seed, 3]).spawn(t)]
    served = [0] * t

    def draw(i: int, at) -> Event:
        which = plans[i].template(served[i], at)
        served[i] += 1
        lo, hi = sample_query(templates[which], rngs[i], col_lo, col_hi)
        return Event(tenants[i], lo, hi, which)

    warmup = [draw(int(i), None) for i in warm_labels]
    window = [draw(int(i), float(d)) for i, d in zip(labels, due)]
    return Stream(warmup=warmup, window=window, due=due)
