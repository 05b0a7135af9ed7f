"""Plain reference of the served semantics: one tenant, numpy, float64.

This is the OReO online loop written out directly from its description
(paper Alg. 1-5, Fig. 1, §VI-A1, §VI-D5) with the settings and seeded
draws the deployment states, and with nothing imported from the program:

* the layout manager keeps a sliding window and an R-TBS sample of the
  query stream; every ``gen_every`` queries it builds a greedy qd-tree
  candidate on a ``qdtree_sample_frac`` row sample from the window's
  predicates, admits it iff its cost vector over the R-TBS sample is at
  least ``epsilon`` (mean L1) from every stored state's, and evicts down to
  ``max_states`` the non-current state closest to another;
* D-UMTS (median mid-phase admission, predictor-biased jumps with exponent
  ``gamma``, stay at phase start) picks the decision state of every query
  from the estimated costs of all known states;
* a charged reorganization (cost ``alpha``) swaps the serving layout
  ``delta`` queries later, rewriting the whole table (exact zone maps) --
  or, under ``reorg: "incremental"``, begins there a migration that moves
  the table a few partitions a step (:class:`Migrations`);
* each query is served at the fraction of rows in the partitions of the
  serving layout whose zone maps overlap it.

Costs are computed over each layout's own partitions with the same
row-weighted sum (``einsum``) at every call site, so a run is
deterministic to the bit.  ``precision="bfloat16"`` is the control: every
zone map and query bound is rounded to bfloat16 before the overlap tests,
as a lower-precision decision plane would.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from fractions import Fraction
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

PRECISIONS = ("float32", "bfloat16")


@dataclasses.dataclass
class Meta:
    """Zone maps of one layout: ``mins``/``maxs`` (P, C), ``rows`` (P,)."""

    mins: np.ndarray
    maxs: np.ndarray
    rows: np.ndarray

    @property
    def total(self) -> int:
        return max(int(round(float(self.rows.sum()))), 1)


def zone_maps(data: np.ndarray, assign: np.ndarray, k: int,
              row_scale: float = 1.0) -> Meta:
    """Per-partition min, max and (scaled) row count; empty partitions keep
    ``[+inf, -inf]`` and 0 rows."""
    order = np.argsort(assign, kind="stable")
    bounds = np.searchsorted(assign[order], np.arange(k + 1))
    meta = run_maps(np.ascontiguousarray(data[order].T), bounds[:-1],
                    bounds[1:])
    meta.rows *= row_scale
    return meta


def run_maps(columns: np.ndarray, starts: np.ndarray, ends: np.ndarray
             ) -> Meta:
    """Zone maps of the row runs ``[starts[k], ends[k])`` of ``columns``
    (C, N), rows along the second axis; the runs lie in order and do not
    overlap, and an empty run keeps ``[+inf, -inf]`` and 0 rows."""
    k, c = len(starts), columns.shape[0]
    mins = np.full((k, c), np.inf)
    maxs = np.full((k, c), -np.inf)
    full = ends > starts
    if full.any():
        # Reduce over [start, end) and [end, next start) alike; keep the
        # first of each pair.
        cuts = np.stack([starts[full], ends[full]], axis=1).ravel()
        if cuts[-1] == columns.shape[1]:
            cuts = cuts[:-1]
        mins[full] = np.minimum.reduceat(columns, cuts, axis=1)[:, ::2].T
        maxs[full] = np.maximum.reduceat(columns, cuts, axis=1)[:, ::2].T
    return Meta(mins, maxs, (ends - starts).astype(np.float64))


class Costing:
    """The overlap test and the row-weighted cost at one precision."""

    def __init__(self, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}")
        self.precision = precision

    def _round(self, x: np.ndarray) -> np.ndarray:
        if self.precision == "float32":
            return x
        import ml_dtypes
        return x.astype(ml_dtypes.bfloat16).astype(np.float64)

    def scanned(self, meta: Meta, lo: np.ndarray, hi: np.ndarray
                ) -> np.ndarray:
        """bool (P,) for (C,) bounds, (Q, P) for (Q, C)."""
        r = self._round
        lo2, hi2 = np.atleast_2d(r(lo))[:, None], np.atleast_2d(r(hi))[:, None]
        out = ((r(meta.mins)[None] <= hi2)
               & (r(meta.maxs)[None] >= lo2)).all(axis=-1)
        return out[0] if lo.ndim == 1 else out

    def cost(self, meta: Meta, lo: np.ndarray, hi: np.ndarray) -> float:
        return float(np.einsum("p,p->", self.scanned(meta, lo, hi),
                               meta.rows) / meta.total)

    def cost_vector(self, meta: Meta, q_lo: np.ndarray,
                    q_hi: np.ndarray) -> np.ndarray:
        return (np.einsum("qp,p->q", self.scanned(meta, q_lo, q_hi),
                          meta.rows) / meta.total)


# -- layouts -------------------------------------------------------------

class Layout:
    def __init__(self, layout_id: int, meta: Meta, route):
        self.layout_id = layout_id
        self.meta = meta            # estimate the decisions are made on
        self.route = route          # rows -> partition ids
        self.exact: Optional[Meta] = None

    def materialize(self, data: np.ndarray) -> Meta:
        """Zone maps of the table rewritten under this layout."""
        if self.exact is None:
            self.exact = zone_maps(data, self.route(data),
                                   self.meta.mins.shape[0])
        return self.exact


def arrival_order_layout(data: np.ndarray, k: int) -> Layout:
    """The starting layout: ``k`` contiguous chunks in row order."""
    n = len(data)
    assign = np.minimum((np.arange(n) * k) // n, k - 1)
    meta = zone_maps(data, assign, k)

    def route(rows: np.ndarray) -> np.ndarray:
        m = len(rows)
        return np.minimum((np.arange(m) * k) // m, k - 1)
    return Layout(0, meta, route)


def _best_cut(sample, row_idx, box_lo, box_hi, q_lo, q_hi, min_leaf,
              max_cuts=64):
    """Greedy qd-tree cut: the query-bound cut of the node's box that
    maximizes sample rows skipped, summed over overlapping queries."""
    hit = ((q_lo <= box_hi[None]) & (q_hi >= box_lo[None])).all(axis=1)
    if not hit.any():
        return -1.0, -1, 0.0
    nrows = len(row_idx)
    best = (-1.0, -1, 0.0)
    for col in range(sample.shape[1]):
        lo_b, hi_b = q_lo[hit, col], q_hi[hit, col]
        vs = np.concatenate([lo_b, hi_b])
        vs = np.unique(vs[(vs > box_lo[col]) & (vs < box_hi[col])
                          & np.isfinite(vs)])
        if vs.size == 0:
            continue
        if vs.size > max_cuts:
            vs = vs[np.linspace(0, vs.size - 1, max_cuts).astype(int)]
        vals = np.sort(sample[row_idx, col])
        n_l = np.searchsorted(vals, vs, side="right")
        n_r = nrows - n_l
        skip_l = lo_b.size - np.searchsorted(np.sort(lo_b), vs, side="right")
        skip_r = np.searchsorted(np.sort(hi_b), vs, side="left")
        gains = np.where((n_l >= min_leaf) & (n_r >= min_leaf),
                         skip_l * n_l + skip_r * n_r, -1.0)
        j = int(np.argmax(gains))
        if gains[j] > best[0]:
            best = (float(gains[j]), col, float(vs[j]))
    return best


def qdtree_layout(layout_id: int, data: np.ndarray, q_lo: np.ndarray,
                  q_hi: np.ndarray, k: int, sample_frac: float, seed: int,
                  min_sample_rows: int = 2048, min_leaf: int = 8) -> Layout:
    """Greedy qd-tree of at most ``k`` leaves, built on a row sample:
    split the largest splittable leaf first, at its best query-bound cut,
    or at the median of the most-queried column when no cut helps."""
    rng = np.random.default_rng(seed)
    n, c = data.shape
    m = min(max(int(n * sample_frac), min(n, min_sample_rows)), n)
    sample = data[rng.choice(n, size=m, replace=False)]
    # node: [box_lo, box_hi, sample row ids, col, threshold, left, right]
    nodes = [[sample.min(axis=0) - 1e-9, sample.max(axis=0) + 1e-9,
              np.arange(m), -1, 0.0, -1, -1]]
    heap: List[Tuple[int, int, int]] = [(-m, 0, 0)]
    tiebreak, leaves = 1, 1
    queried = (np.isfinite(q_lo) | np.isfinite(q_hi)).sum(axis=0)
    while leaves < k and heap:
        _, _, ni = heapq.heappop(heap)
        box_lo, box_hi, idx = nodes[ni][:3]
        if len(idx) < 2 * min_leaf:
            continue
        _, col, v = _best_cut(sample, idx, box_lo, box_hi, q_lo, q_hi,
                              min_leaf)
        if col < 0:
            col = (int(np.argmax(queried)) if queried.sum()
                   else int(np.argmax(box_hi - box_lo)))
            vals = sample[idx, col]
            v = float(np.median(vals))
            below = int((vals <= v).sum())
            if not (box_lo[col] < v < box_hi[col]) or below in (0, len(idx)):
                continue
        left = sample[idx, col] <= v
        hi_l, lo_r = box_hi.copy(), box_lo.copy()
        hi_l[col] = v
        lo_r[col] = v
        nodes[ni][3:] = [col, v, len(nodes), len(nodes) + 1]
        nodes.append([box_lo.copy(), hi_l, idx[left], -1, 0.0, -1, -1])
        nodes.append([lo_r, box_hi.copy(), idx[~left], -1, 0.0, -1, -1])
        for child in (len(nodes) - 2, len(nodes) - 1):
            heapq.heappush(heap, (-len(nodes[child][2]), tiebreak, child))
            tiebreak += 1
        leaves += 1
    cols = np.array([nd[3] for nd in nodes], dtype=np.int64)
    thr = np.array([nd[4] for nd in nodes])
    lefts = np.array([nd[5] for nd in nodes], dtype=np.int64)
    rights = np.array([nd[6] for nd in nodes], dtype=np.int64)
    leaf_id = np.cumsum(cols < 0) - 1

    def route(rows: np.ndarray) -> np.ndarray:
        at = np.zeros(len(rows), dtype=np.int64)
        live = cols[at] >= 0
        while live.any():
            cur = at[live]
            at[live] = np.where(rows[live, cols[cur]] <= thr[cur],
                                lefts[cur], rights[cur])
            live = cols[at] >= 0
        return leaf_id[at]

    n_leaves = int((cols < 0).sum())
    return Layout(layout_id, zone_maps(sample, route(sample), n_leaves,
                                       row_scale=n / m), route)


# -- the decision layer --------------------------------------------------

class RTBS:
    """Reservoir-based time-biased sample of the query stream: a full
    reservoir accepts a newcomer with a probability set by its weight
    against the mean retained weight ``exp(-lam * age)``, evicting
    inversely to weight."""

    def __init__(self, size: int, lam: float, seed: int):
        self.size, self.lam = size, lam
        self.rng = np.random.default_rng(seed)
        self.items: list = []
        self.arrival: List[int] = []
        self.t = 0

    def add(self, item) -> None:
        self.t += 1
        if len(self.items) < self.size:
            self.items.append(item)
            self.arrival.append(self.t)
            return
        w = np.exp(-self.lam * (self.t - np.asarray(self.arrival, float)))
        p = 1.0 / (1.0 + w.mean() * (self.size - 1) / self.size)
        if self.rng.random() < min(max(p * 2.0, 1.0 / self.size), 1.0):
            inv = 1.0 / np.maximum(w, 1e-12)
            j = int(self.rng.choice(self.size, p=inv / inv.sum()))
            self.items[j] = item
            self.arrival[j] = self.t


class DUMTS:
    """Dynamic uniform metrical task system over a changing state set."""

    def __init__(self, alpha: float, initial: int, seed: int, gamma: float):
        self.alpha, self.gamma = float(alpha), gamma
        self.rng = np.random.default_rng(seed)
        self.states = {initial}
        self.counters: Dict[int, float] = {initial: 0.0}
        self.active = {initial}
        self.current = int(self.rng.choice([initial]))
        self.moves = 0
        self.last_avg: Dict[int, float] = {}
        self.phase_cost: Dict[int, float] = {initial: 0.0}
        self.phase_n: Dict[int, int] = {initial: 0}

    def add(self, s: int) -> None:
        if s in self.states:
            return
        act = [self.counters[a] for a in self.active]
        init = float(np.median(act)) if act else 0.0
        self.states.add(s)
        self.counters[s] = init
        self.phase_cost[s] = init
        self.phase_n.setdefault(s, 0)
        if init < self.alpha:
            self.active.add(s)

    def remove(self, s: int) -> None:
        if s not in self.states:
            return
        self.states.discard(s)
        self.active.discard(s)
        self.counters[s] = self.alpha
        if not self.active:
            self._new_phase()
        if s == self.current:
            self._jump()

    def observe(self, costs: Dict[int, float]) -> int:
        for s in list(self.active):
            c = float(costs[s])
            if not 0.0 <= c <= 1.0 + 1e-9:
                raise ValueError(f"cost out of [0, 1]: state {s} -> {c}")
            self.counters[s] += c
            self.phase_cost[s] = self.phase_cost.get(s, 0.0) + c
            self.phase_n[s] = self.phase_n.get(s, 0) + 1
        self.active = {s for s in self.active if self.counters[s] < self.alpha}
        if self.current not in self.active:
            if not self.active:
                self._new_phase()           # stay at phase start
            else:
                self._jump()
        return self.current

    def _new_phase(self) -> None:
        self.last_avg = {s: self.phase_cost[s] / max(self.phase_n.get(s, 0), 1)
                         for s in self.phase_cost if self.phase_n.get(s, 0) > 0}
        self.phase_cost = {s: 0.0 for s in self.states}
        self.phase_n = {s: 0 for s in self.states}
        self.counters = {s: 0.0 for s in self.states}
        self.active = set(self.states)

    def _jump(self) -> None:
        # Weight of a state: fraction of rows it skipped per query last
        # phase (1 for states unseen then), raised to gamma.
        w = {s: 1.0 - min(self.last_avg.get(s, 0.0), 1.0) for s in self.active}
        if self.gamma != 0.0 and w:
            powered = {s: max(v, 1e-6) ** self.gamma for s, v in w.items()}
            total = sum(powered.values())
            probs = {s: v / total for s, v in powered.items()}
        else:
            probs = {s: 1.0 / len(w) for s in w}
        keys = sorted(probs)
        p = np.array([max(probs[s], 0.0) for s in keys])
        p = p / p.sum() if p.sum() > 0 else np.full(len(keys), 1 / len(keys))
        self.current = int(self.rng.choice(keys, p=p))
        self.moves += 1


# -- incremental reorganization --------------------------------------------

def closing_charges(charged: float, alpha: float) -> List[float]:
    """The last charges of a migration, whose left-to-right float sum
    after ``charged`` is ``alpha`` to the bit.

    One charge where a float closes the sum: of the two floats next to
    the exact remainder ``alpha - charged``, the nearer one that closes.
    Where neither does, the term is split in two: the nearer float, then
    what is left, which is exact because the sum is then within a few
    units in the last place of ``alpha``."""
    exact = Fraction(alpha) - Fraction(charged)
    near = alpha - charged
    away = math.nextafter(near, math.inf if Fraction(near) < exact
                          else -math.inf)
    for inc in (near, away):
        if charged + inc == alpha:
            return [inc]
    return [near, alpha - (charged + near)]


@dataclasses.dataclass
class Migration:
    """One migration from the serving layout to ``target``.

    ``moves`` are the target partitions that move, in move order, and
    ``move_rows`` their rows.  The table's rows are sorted once by
    (source partition, rank of their target partition's move), so that at
    any step the rows a source partition still holds are one contiguous
    run at the end of its rows."""

    target: Layout
    moves: List[int]
    move_rows: List[int]
    num_sources: int
    columns: np.ndarray             # (C, N) rows sorted by (source, rank)
    keys: np.ndarray                # their keys, source * (M + 1) + rank
    done_at: np.ndarray             # target partition -> move rank (or M)
    done: int = 0                   # moves completed (a prefix)
    bank: int = 0                   # rows granted and not yet spent
    moved: int = 0
    charged: float = 0.0

    @property
    def total(self) -> int:
        return sum(self.move_rows)

    def hybrid(self, data: np.ndarray) -> Meta:
        """Zone maps of the table as it lies now: one residual partition
        per source partition, holding its rows not yet moved, then one per
        target partition, holding its rows once its move is done."""
        m = len(self.moves)
        src = np.arange(self.num_sources) * (m + 1)
        res = run_maps(self.columns,
                       np.searchsorted(self.keys, src + self.done),
                       np.searchsorted(self.keys, src + m + 1))
        tgt = self.target.materialize(data)
        moved = (self.done_at < self.done)[:, None]
        return Meta(np.concatenate([res.mins,
                                    np.where(moved, tgt.mins, np.inf)]),
                    np.concatenate([res.maxs,
                                    np.where(moved, tgt.maxs, -np.inf)]),
                    np.concatenate([res.rows,
                                    np.where(moved[:, 0], tgt.rows, 0.0)]))


class Migrations:
    """Incremental reorganization (``reorg: "incremental"``), one tenant.

    * Charged swaps fall due ``delta`` queries after their charge and
      begin in charge order, one migration in flight at a time; a due swap
      waits behind the one in flight.  A swap whose state was evicted
      before it begins is skipped.
    * A migration moves the layout diff: every non-empty target partition
      except those identical to a source partition (all their rows come
      from one source partition that sends rows nowhere else).  Moves are
      ordered by (-benefit per row, target partition), the benefit of a
      move being the scans it saves per row over the last ``window``
      queries (the current one included): its rows leave source
      partitions read at their scan frequency for a target partition read
      at its own.
    * Each step grants up to ``rows_per_tick`` rows, banked: a move
      completes once the bank covers its rows.  When a migration
      completes, the step's unspent rows go to the next due swap, which
      begins in the same step.
    * Until it completes, queries are served on the hybrid table
      (:meth:`Migration.hybrid`); then on the target's exact zone maps.
    * A step that completes moves charges ``alpha * (moved / total) -
      charged``; the completing step closes the migration's ledger on
      ``alpha`` (:func:`closing_charges`).

    The frequencies are exact (the program's ``reorg_compute="numpy"``),
    and every row asked for is granted (the fleet's ``unlimited``
    scheduler): the reference holds under those two only.
    """

    def __init__(self, data: np.ndarray, costing: Costing, alpha: float,
                 rows_per_tick: int, window: int, serving: Layout):
        self.data, self.costing, self.alpha = data, costing, alpha
        self.rows_per_tick, self.window = rows_per_tick, window
        self.layout = serving           # what the table is laid out as
        self.active: Optional[Migration] = None
        #: (index, rows, charge) of every migration step that moved rows,
        #: migrations in begin order.
        self.ledger: list = []

    def step(self, i: int, pending: List[Tuple[int, int]],
             known: Dict[int, Layout], recent: Sequence[Tuple[np.ndarray,
                                                               np.ndarray]],
             serving: Meta) -> Meta:
        """Spend step ``i``'s rows; returns the zone maps to serve on."""
        room = self.rows_per_tick
        while True:
            if self.active is not None:
                room, serving = self._advance(i, room, serving)
                if self.active is not None:
                    return serving
            if not (pending and pending[0][0] <= i):
                return serving
            _, sid = pending.pop(0)
            if sid in known:
                self.active = self._plan(known[sid], recent)

    def _plan(self, target: Layout, recent) -> Migration:
        data, costing = self.data, self.costing
        src_meta = self.layout.materialize(data)
        tgt_meta = target.materialize(data)
        p_s, p_t = src_meta.mins.shape[0], tgt_meta.mins.shape[0]
        a_s = self.layout.route(data)
        a_t = target.route(data)
        block = np.bincount(a_s * p_t + a_t, minlength=p_s * p_t
                            ).reshape(p_s, p_t).astype(np.float64)
        src_rows, tgt_rows = block.sum(axis=1), block.sum(axis=0)
        feeds = block > 0
        feeder = feeds.argmax(axis=0)
        identical = ((feeds.sum(axis=0) == 1)
                     & (block[feeder, np.arange(p_t)] == src_rows[feeder]))
        diff = [j for j in range(p_t) if tgt_rows[j] > 0 and not identical[j]]
        q_lo = np.stack([q[0] for q in recent])
        q_hi = np.stack([q[1] for q in recent])
        f_src = costing.scanned(src_meta, q_lo, q_hi).mean(axis=0)
        f_tgt = costing.scanned(tgt_meta, q_lo, q_hi).mean(axis=0)
        # Moves whose float64 benefits tie keep target order.
        gain = block.T @ f_src - tgt_rows * f_tgt
        benefit = np.divide(gain, tgt_rows, out=np.zeros(p_t),
                            where=tgt_rows > 0)
        moves = sorted(diff, key=lambda j: (-benefit[j], j))
        done_at = np.full(p_t, len(moves))
        done_at[moves] = np.arange(len(moves))
        keys = a_s * (len(moves) + 1) + done_at[a_t]
        order = np.argsort(keys, kind="stable")
        return Migration(target, moves, [int(tgt_rows[j]) for j in moves],
                         p_s, np.ascontiguousarray(data[order].T),
                         keys[order], done_at)

    def _advance(self, i: int, room: int, serving: Meta
                 ) -> Tuple[int, Meta]:
        mig = self.active
        want = max(min(sum(mig.move_rows[mig.done:]) - mig.bank, room), 0)
        mig.bank += want
        rows = 0
        start = mig.done
        while (mig.done < len(mig.moves)
               and mig.bank >= mig.move_rows[mig.done]):
            mig.bank -= mig.move_rows[mig.done]
            rows += mig.move_rows[mig.done]
            mig.done += 1
        if mig.done == start and mig.done < len(mig.moves):
            return room - want, serving
        mig.moved += rows
        if mig.done == len(mig.moves):
            charges = closing_charges(mig.charged, self.alpha)
            self.ledger.append((i, rows, charges[0]))
            self.ledger += [(i, 0, c) for c in charges[1:]]
            self.layout = mig.target
            self.active = None
            return room - want, mig.target.materialize(self.data)
        inc = self.alpha * (mig.moved / max(mig.total, 1)) - mig.charged
        mig.charged = mig.charged + inc
        self.ledger.append((i, rows, inc))
        return room - want, mig.hybrid(self.data)


@dataclasses.dataclass
class Trace:
    """What one tenant's run produced, per query in service order."""

    costs: np.ndarray               # served cost (fraction of rows read)
    states: np.ndarray              # decision state
    reorgs: List[int]               # query indices charged alpha
    alpha_ledger: list              # see run_tenant


def run_tenant(data: np.ndarray, queries: Sequence[Tuple[np.ndarray,
                                                         np.ndarray]],
               cfg: dict, seed: int, precision: str = "float32") -> Trace:
    """Replay one tenant's queries through the OReO loop of ``cfg`` (the
    deployment's configuration file); ``seed`` seeds its draws.

    ``reorg: "atomic"``: the serving layout is swapped whole at the due
    step, and the α ledger holds one ``(index, alpha)`` entry per
    reorganization.  ``reorg: "incremental"``: the due step begins a
    migration of at most ``ceil(rows / migration_steps)`` rows a step,
    ordered over the last ``reorg_window`` queries (:class:`Migrations`),
    and the α ledger holds its ``(index, rows, charge)`` steps.
    """
    if cfg["reorg"] not in ("atomic", "incremental"):
        raise ValueError(f"unsupported reorg mode {cfg['reorg']!r}")
    costing = Costing(precision)
    mgr = cfg["manager"]
    k = cfg["partitions"]
    alpha = float(cfg["alpha"])
    initial = arrival_order_layout(data, k)
    store: Dict[int, Layout] = {0: initial}     # insertion-ordered
    known: Dict[int, Layout] = {0: initial}     # states with zone maps
    next_id = 1
    window: List[Tuple[np.ndarray, np.ndarray]] = []
    rtbs = RTBS(mgr["rtbs_size"], mgr["rtbs_lambda"], seed + 2)
    dumts = DUMTS(alpha, 0, seed, cfg["gamma"])
    serving = initial.materialize(data)
    pending: List[Tuple[int, int]] = []
    costs, states, reorgs = [], [], []
    migrations = None
    if cfg["reorg"] == "incremental":
        migrations = Migrations(data, costing, alpha,
                                -(-len(data) // cfg["migration_steps"]),
                                cfg["reorg_window"], initial)

    def vectors(layouts: Dict[int, Layout]) -> Dict[int, np.ndarray]:
        """Cost vectors over the R-TBS sample (empty while it is)."""
        if not rtbs.items:
            return {i: np.zeros(0) for i in layouts}
        q_lo = np.stack([q[0] for q in rtbs.items])
        q_hi = np.stack([q[1] for q in rtbs.items])
        return {i: costing.cost_vector(lay.meta, q_lo, q_hi)
                for i, lay in layouts.items()}

    def distance(a: np.ndarray, b: np.ndarray) -> float:
        if len(a) == 0 or len(b) == 0:
            return float("inf")
        return float(np.abs(a - b).mean())

    for i, (lo, hi) in enumerate(queries):
        # Layout manager.
        window.append((lo, hi))
        del window[:-mgr["window_size"]]
        rtbs.add((lo, hi))
        added, removed = [], []
        if (i + 1) % mgr["gen_every"] == 0 and \
                len(window) >= mgr["window_size"] // 2:
            w_lo = np.stack([q[0] for q in window])
            w_hi = np.stack([q[1] for q in window])
            cand = qdtree_layout(next_id, data, w_lo, w_hi, k,
                                 cfg["qdtree_sample_frac"],
                                 seed=seed + next_id)
            vecs = vectors({**store, cand.layout_id: cand})
            cv = vecs.pop(cand.layout_id)
            if cv.size and all(distance(cv, v) >= mgr["epsilon"]
                               for v in vecs.values()):
                store[cand.layout_id] = cand
                added.append(cand.layout_id)
                next_id += 1
                while len(store) > mgr["max_states"]:
                    ids = [s for s in store if s != dumts.current]
                    if not ids:
                        break
                    vecs = vectors(store)
                    best, best_d = None, np.inf
                    for s in ids:
                        d = min(distance(vecs[s], vecs[j])
                                for j in store if j != s)
                        if d < best_d:
                            best, best_d = s, d
                    best = max(ids) if best is None else best
                    del store[best]
                    removed.append(best)
        for s in added:
            dumts.add(s)
        for s in removed:
            dumts.remove(s)
        for s in added:
            if s in store:
                known[s] = store[s]
        for s in removed:
            known.pop(s, None)
        # Decision.
        est = {s: costing.cost(known[s].meta, lo, hi)
               for s in dumts.states if s in known}
        moves = dumts.moves
        state = dumts.observe({s: est.get(s, 1.0) for s in dumts.states})
        if dumts.moves > moves:
            reorgs.append(i)
            pending.append((i + cfg["delta"], state))
        # Delta-delayed swaps, then serve.
        if migrations is None:
            while pending and pending[0][0] <= i:
                _, sid = pending.pop(0)
                if sid in known:
                    serving = known[sid].materialize(data)
        else:
            recent = queries[max(0, i + 1 - migrations.window):i + 1]
            serving = migrations.step(i, pending, known, recent, serving)
        costs.append(costing.cost(serving, lo, hi))
        states.append(state)
    ledger = ([(i, alpha) for i in reorgs] if migrations is None
              else migrations.ledger)
    return Trace(np.asarray(costs), np.asarray(states, dtype=np.int64),
                 reorgs, ledger)


def replay(job: dict) -> Trace:
    """One tenant's replay as a picklable job: the tenant's table is made
    anew from the run's seed, and its queries are given in service order."""
    from chipbench import tables
    data = tables.make(job["table"], job["rows"], job["seed"], job["tenants"],
                       job["k"])
    return run_tenant(data, list(zip(job["lo"], job["hi"])), job["cfg"],
                      seed=job["k"], precision=job["precision"])
