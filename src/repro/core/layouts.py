"""Data layouts and partition-level metadata.

A *layout* is a mapping from rows of a table to partitions (the paper's BID
column).  OREO never needs the mapping itself at decision time -- only the
per-partition metadata (min/max per column, row counts), which is what
``eval_skipped`` consumes.  This mirrors the paper's design: cost estimation is
metadata-only and never touches row data.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class PartitionMetadata:
    """Per-partition zone maps: ``mins``/``maxs`` are (P, C); ``rows`` is (P,)."""

    mins: np.ndarray
    maxs: np.ndarray
    rows: np.ndarray

    def __post_init__(self):
        assert self.mins.shape == self.maxs.shape
        assert self.mins.shape[0] == self.rows.shape[0]

    @property
    def num_partitions(self) -> int:
        return int(self.mins.shape[0])

    @property
    def num_columns(self) -> int:
        return int(self.mins.shape[1])

    @property
    def total_rows(self) -> int:
        # Rounded, not truncated: sample-estimated metadata scales counts by
        # a non-integer n/m, so the float sum lands within rounding noise of
        # n on either side, and truncating n - 1e-6 to n - 1 would price a
        # full scan above 1.0.
        return int(round(float(self.rows.sum())))


def metadata_from_assignment(data: np.ndarray, assignment: np.ndarray,
                             num_partitions: int,
                             row_scale: float = 1.0) -> PartitionMetadata:
    """Compute zone maps for ``data`` (N, C) under partition ``assignment`` (N,).

    ``row_scale`` scales row counts when ``data`` is a sample standing in for
    a larger table (the paper builds layouts and estimates metadata from
    0.1-1% samples; the full table is only touched on reorganization).

    The per-partition min/max reduction runs as one ``np.minimum.reduceat`` /
    ``np.maximum.reduceat`` pair over the sorted row order — no Python loop
    over partitions on the reorganization path.  Empty partitions keep the
    [+inf, -inf] identity bounds and zero rows; rows assigned outside
    ``[0, num_partitions)`` are ignored.
    """
    n, c = data.shape
    mins = np.full((num_partitions, c), np.inf)
    maxs = np.full((num_partitions, c), -np.inf)
    rows = np.zeros(num_partitions, dtype=np.float64)
    order = np.argsort(assignment, kind="stable")
    sorted_assign = assignment[order]
    bounds = np.searchsorted(sorted_assign, np.arange(num_partitions + 1))
    starts, ends = bounds[:-1], bounds[1:]
    nonempty = ends > starts
    if nonempty.any():
        # Rows with in-range assignments, grouped contiguously by partition.
        # reduceat segment i spans [start_i, start_{i+1}) over the non-empty
        # starts, which equals [start_i, end_i) because empty partitions have
        # zero width; the final segment ends exactly at the slice boundary.
        grouped = data[order[bounds[0]:bounds[-1]]]
        seg = starts[nonempty] - bounds[0]
        mins[nonempty] = np.minimum.reduceat(grouped, seg, axis=0)
        maxs[nonempty] = np.maximum.reduceat(grouped, seg, axis=0)
        rows[nonempty] = (ends[nonempty] - starts[nonempty]) * row_scale
    return PartitionMetadata(mins=mins, maxs=maxs, rows=rows)


@dataclasses.dataclass
class Layout:
    """A data layout: an assignment function plus its partition metadata.

    ``route`` maps a (N, C) array of rows to partition ids; it is retained so
    a *reorganization* (full rewrite of the table under this layout) can be
    materialized.  ``meta`` is the *estimated* metadata (built from the data
    sample the generator saw) used for decision making; ``true_meta`` is the
    exact metadata of the materialized table, filled in lazily the first time
    the layout is actually reorganized to (:meth:`materialize`).
    """

    layout_id: int
    name: str
    technique: str                      # "qdtree" | "zorder" | "default" | ...
    meta: PartitionMetadata
    route: Optional[Callable[[np.ndarray], np.ndarray]] = None
    info: dict = dataclasses.field(default_factory=dict)
    true_meta: Optional[PartitionMetadata] = None

    @property
    def num_partitions(self) -> int:
        return self.meta.num_partitions

    def materialize(self, data: np.ndarray) -> PartitionMetadata:
        """Reorganize the full table under this layout; exact zone maps."""
        if self.true_meta is None:
            if self.route is None:
                self.true_meta = self.meta
            else:
                assignment = self.route(data)
                self.true_meta = metadata_from_assignment(
                    data, assignment, self.num_partitions)
        return self.true_meta

    def serving_meta(self) -> PartitionMetadata:
        """Metadata of the physically materialized table (falls back to the
        estimate if never materialized -- e.g. the initial default layout)."""
        return self.true_meta if self.true_meta is not None else self.meta


# ---------------------------------------------------------------------------
# Query cost evaluation ("eval_skipped")
# ---------------------------------------------------------------------------
#
# Every cost path below reduces the scan matrix with the SAME contiguous
# einsum contraction (``scanned_dot``).  numpy's einsum uses one
# sum-of-products inner kernel for the 'p,p->', 'qp,p->q' and 'sp,sp->s'
# signatures on contiguous operands, so single-query, batched-query, and
# batched-state evaluation (including the engine's packed StateMatrix plane)
# are bit-identical by construction — unlike mixing ``@``/BLAS dots, whose
# accumulation order differs from einsum's on some shapes.


def scanned_dot(scanned: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Deterministic ``scanned · rows`` shared by all cost paths.

    ``scanned`` is bool (P,) or (Q, P); ``rows`` is float64 (P,).  Operands
    must be contiguous along P (freshly computed scan matrices always are).
    """
    if scanned.ndim == 1:
        return np.einsum("p,p->", scanned, rows)
    return np.einsum("qp,p->q", scanned, rows)


def partitions_scanned(meta: PartitionMetadata, q_lo: np.ndarray,
                       q_hi: np.ndarray) -> np.ndarray:
    """Which partitions a conjunctive range query must scan.

    ``q_lo``/``q_hi`` are (C,) or (Q, C).  A partition is scanned iff every
    column's [min, max] range overlaps the query's [lo, hi] range.
    Returns bool (P,) or (Q, P).
    """
    lo = np.atleast_2d(q_lo)[:, None, :]       # (Q, 1, C)
    hi = np.atleast_2d(q_hi)[:, None, :]
    overlap = (meta.mins[None] <= hi) & (meta.maxs[None] >= lo)  # (Q, P, C)
    scanned = overlap.all(axis=-1)
    if q_lo.ndim == 1:
        return scanned[0]
    return scanned


def eval_cost(meta: PartitionMetadata, q_lo: np.ndarray,
              q_hi: np.ndarray) -> np.ndarray:
    """Fraction of data records accessed: the paper's service cost c(s, q).

    Returns float (Q,) (or scalar for a single query), each in [0, 1].
    """
    scanned = partitions_scanned(meta, q_lo, q_hi)
    total = max(meta.total_rows, 1)
    cost = scanned_dot(scanned, self_rows(meta)) / total
    return cost


def self_rows(meta: PartitionMetadata) -> np.ndarray:
    return meta.rows.astype(np.float64)


def eval_skipped(meta: PartitionMetadata, q_lo: np.ndarray,
                 q_hi: np.ndarray) -> np.ndarray:
    """Fraction of data records *skipped* (1 - cost)."""
    return 1.0 - eval_cost(meta, q_lo, q_hi)


def cost_vector(meta: PartitionMetadata, q_lo: np.ndarray,
                q_hi: np.ndarray) -> np.ndarray:
    """Cost vector of a layout over a query sample -- used for ε-admission."""
    return np.atleast_1d(eval_cost(meta, q_lo, q_hi))


def layout_distance(cv_a: np.ndarray, cv_b: np.ndarray) -> float:
    """Normalized L1 distance between two cost vectors (paper §V-B).

    Zero-length vectors (an empty query sample) carry no evidence that two
    layouts are similar, so the distance is *infinite*: admission treats the
    pair as distinct-but-unverifiable (callers reject separately) and
    eviction/pruning never merges states on the basis of an empty sample.
    """
    if len(cv_a) == 0 or len(cv_b) == 0:
        return float("inf")
    return float(np.abs(cv_a - cv_b).mean())


def eval_cost_states(metas: Sequence[PartitionMetadata], q_lo: np.ndarray,
                     q_hi: np.ndarray) -> np.ndarray:
    """Service cost of a *single* query under many candidate layouts at once.

    The partition-overlap test — the O(S * P * C) bulk of the work — runs as
    one vectorized comparison over all states (padded to the widest partition
    count; padding rows use [+inf, -inf] bounds and zero rows so they are
    never scanned).  The final per-state dot products intentionally reuse each
    state's exact (P,) arrays so the result is bit-identical to calling
    :func:`eval_cost` on every state individually — the online decision loop
    relies on this when comparing the engine against the legacy runner.

    Returns float (S,), one cost in [0, 1] per state.
    """
    if not metas:
        return np.zeros(0)
    counts = [m.num_partitions for m in metas]
    p_max = max(counts)
    s, c = len(metas), metas[0].num_columns
    mins = np.full((s, p_max, c), np.inf)
    maxs = np.full((s, p_max, c), -np.inf)
    for i, m in enumerate(metas):
        mins[i, :counts[i]] = m.mins
        maxs[i, :counts[i]] = m.maxs
    scanned = ((mins <= q_hi) & (maxs >= q_lo)).all(axis=-1)     # (S, P_max)
    out = np.empty(s)
    for i, m in enumerate(metas):
        total = max(m.total_rows, 1)
        out[i] = scanned_dot(scanned[i, :counts[i]], self_rows(m)) / total
    return out
