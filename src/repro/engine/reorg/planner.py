"""Micro-move planning: diff two layouts into budgetable partition moves.

A *migration* replaces the serving (source) layout with a target layout.
Atomically that is one rewrite of every partition; incrementally it is a
sequence of :class:`MicroMove`\\ s, one per target partition whose row set
actually differs from the source layout (identical partitions never move —
the same diff the skip-aware :meth:`repro.data.partition_store.
PartitionStore.reorganize` applies on disk).

The plan also carries the *block decomposition* the hybrid serving state
is maintained from: block ``(i, j)`` holds the rows routed from source
partition ``i`` to target partition ``j``, with exact per-block zone maps.
After any subset ``D`` of moves has completed, the physically hybrid table
is exactly

* one partition per **done** target ``j ∈ D`` (exact target zone maps),
* one **residual** partition per source ``i`` holding its not-yet-moved
  rows — zone maps are the elementwise min/max over blocks ``(i, j)`` with
  ``j ∉ D``,

and :meth:`MigrationPlan.hybrid_meta` materializes those
``P_s + P_t``-partition zone maps for any done mask in one masked
reduction over the precomputed block tensors.

Move *ordering* is greedy by estimated skipping-benefit-per-row under the
recent query distribution: completing move ``j`` relocates each block
``(i, j)`` from a partition scanned with the source partition's observed
frequency to one scanned with the target partition's frequency.  The
per-partition scan frequencies are one ``(S=2, P, C)`` pass over both
layouts' zone maps — exact numpy by default, or the
:mod:`repro.kernels.move_score` Pallas kernel (float32) with
``compute="pallas"``.  Ordering is an estimation heuristic only: the move
*set* is always exactly the layout diff, whatever the ordering says.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core import layouts as L
from repro.core import workload as wl


@dataclasses.dataclass(frozen=True)
class MicroMove:
    """One budgetable unit of migration: materialize one target partition.

    ``rows`` is the number of rows relocated (the move's cost in the row
    budget); ``source_partitions`` the partitions those rows leave;
    ``benefit_per_row`` the greedy ordering key (estimated rows of scan
    saved per query, per row moved — 0.0 when no recent queries were
    available at planning time).
    """

    target_partition: int
    rows: int
    source_partitions: Tuple[int, ...]
    benefit_per_row: float = 0.0


@dataclasses.dataclass
class MigrationPlan:
    """Everything the executor and the hybrid backends need for one
    migration: the ordered moves, the block decomposition, and both
    layouts' row-level assignments."""

    source_id: int
    target: L.Layout
    moves: List[MicroMove]
    total_move_rows: int
    num_source_partitions: int
    num_target_partitions: int
    #: (N,) row -> source / target partition assignments over the table.
    source_assignment: np.ndarray
    target_assignment: np.ndarray
    #: (P_s, P_t, C) / (P_s, P_t) exact per-block zone maps; empty blocks
    #: carry the [+inf, -inf] identity bounds and zero rows.
    block_mins: np.ndarray
    block_maxs: np.ndarray
    block_rows: np.ndarray
    #: Exact zone maps of the fully-materialized target table.
    target_meta: L.PartitionMetadata
    #: target partition j -> identical source partition i (row set
    #: unchanged between the layouts; such partitions never move).
    identical: dict

    @property
    def num_moves(self) -> int:
        return len(self.moves)

    def target_partition_rows(self, data: np.ndarray, j: int) -> np.ndarray:
        """The physical rows of target partition ``j`` (stable row order)."""
        return data[self.target_assignment == j]

    def source_moved_mask(self, i: int, done: np.ndarray) -> np.ndarray:
        """Per-row moved flags for source partition ``i``'s rows (in their
        original, file-stable order) given the ``(P_t,)`` done mask."""
        return done[self.target_assignment[self.source_assignment == i]]

    def hybrid_meta(self, done: np.ndarray) -> L.PartitionMetadata:
        """Exact zone maps of the hybrid table after the ``done`` moves.

        Partition order is ``[residual sources (P_s), targets (P_t)]``;
        fully-drained sources and not-yet-done targets carry the
        [+inf, -inf] identity bounds and zero rows, so they are never
        scanned and contribute exactly 0.0 to any cost reduction.
        """
        p_s = self.num_source_partitions
        c = self.block_mins.shape[2]
        not_done = ~done
        if not_done.any():
            res_mins = self.block_mins[:, not_done, :].min(axis=1)
            res_maxs = self.block_maxs[:, not_done, :].max(axis=1)
            res_rows = self.block_rows[:, not_done].sum(axis=1)
        else:
            res_mins = np.full((p_s, c), np.inf)
            res_maxs = np.full((p_s, c), -np.inf)
            res_rows = np.zeros(p_s)
        tgt_mins = np.where(done[:, None], self.target_meta.mins, np.inf)
        tgt_maxs = np.where(done[:, None], self.target_meta.maxs, -np.inf)
        tgt_rows = np.where(done, self.target_meta.rows, 0.0)
        return L.PartitionMetadata(
            mins=np.concatenate([res_mins, tgt_mins]),
            maxs=np.concatenate([res_maxs, tgt_maxs]),
            rows=np.concatenate([res_rows, tgt_rows]))


def _assignment(layout: L.Layout, data: np.ndarray) -> np.ndarray:
    """Row -> partition assignment, matching what a physical write of the
    layout produces (``route`` when present; partition 0 otherwise, which
    is exactly how :meth:`PartitionStore.write` routes route-less
    layouts)."""
    if layout.route is None:
        return np.zeros(len(data), dtype=np.int64)
    return np.asarray(layout.route(data), dtype=np.int64)


def scan_frequencies(metas: Sequence[L.PartitionMetadata],
                     q_lo: np.ndarray, q_hi: np.ndarray,
                     compute: str = "numpy") -> List[np.ndarray]:
    """Mean scan frequency of every partition of every layout under a query
    sample: ``(Q, C)`` bounds x S layouts -> one ``(P_s,)`` float vector
    per layout.

    ``compute="numpy"`` is the exact float64 path; ``"pallas"`` stacks
    the layouts into one padded ``(S, P_max, C)`` plane and scores all
    (state, partition) move candidates in a single
    :func:`repro.kernels.move_score.ops.move_scan_frequencies` launch;
    ``"pallas_fused"`` routes the same plane, column-major, through the
    decision megakernel's ``freq`` output.  Both compare in float32 —
    ordering heuristic only, never cost accounting — and the fused path
    recovers exact window counts, so on float32-exact bounds it equals
    the numpy path bit for bit.
    """
    if compute in ("pallas", "pallas_fused"):
        counts = [m.num_partitions for m in metas]
        p_max = max(counts) if counts else 0
        s, c = len(metas), metas[0].num_columns
        if compute == "pallas_fused":
            # The megakernel's freq output over a single-tenant plane
            # (T=1, S layouts, P_max partitions), column-major: the (Q, C)
            # sample is the recent-query window, and the same launch could
            # also carry the scoring outputs for the planning tenant.
            from repro.kernels.decision_fused import decision_fused
            mins = np.full((c, 1, s, p_max), np.inf, dtype=np.float32)
            maxs = np.full((c, 1, s, p_max), -np.inf, dtype=np.float32)
            for k, m in enumerate(metas):
                mins[:, 0, k, :counts[k]] = m.mins.T
                maxs[:, 0, k, :counts[k]] = m.maxs.T
            dummy = np.zeros((1, 1, c), dtype=np.float32)
            _, _, freq = decision_fused.fused_decision_pallas(
                dummy + 1.0, dummy,          # empty frame query: unused
                mins, maxs,
                w_lo=q_lo.astype(np.float32), w_hi=q_hi.astype(np.float32),
                emit_scan=False)
            # The float32 mean is within an ulp of count / Q whatever the
            # device's division, so rounding recovers the exact count and
            # the float64 mean the numpy path computes.
            n_q = len(q_lo)
            freq = np.rint(np.asarray(freq)[0].astype(np.float64) * n_q) / n_q
        else:
            mins = np.full((s, p_max, c), np.inf, dtype=np.float32)
            maxs = np.full((s, p_max, c), -np.inf, dtype=np.float32)
            for k, m in enumerate(metas):
                mins[k, :counts[k]] = m.mins
                maxs[k, :counts[k]] = m.maxs
            from repro.kernels.move_score import ops as ms_ops
            freq = np.asarray(ms_ops.move_scan_frequencies(
                q_lo.astype(np.float32), q_hi.astype(np.float32), mins,
                maxs))
        return [freq[k, :counts[k]].astype(np.float64) for k in range(s)]
    out = []
    for m in metas:
        scanned = L.partitions_scanned(m, q_lo, q_hi)       # (Q, P)
        out.append(np.atleast_2d(scanned).mean(axis=0))
    return out


def plan_migration(data: np.ndarray, source: L.Layout, target: L.Layout,
                   recent_queries: Sequence[wl.Query] = (),
                   compute: str = "numpy",
                   source_assignment: Optional[np.ndarray] = None,
                   source_meta: Optional[L.PartitionMetadata] = None,
                   ) -> MigrationPlan:
    """Diff ``source`` -> ``target`` into greedily-ordered micro-moves.

    The move set is exactly the layout diff: one move per non-empty target
    partition whose row set is not already held verbatim by some source
    partition.  ``recent_queries`` drives the greedy
    benefit-per-row-moved ordering; with an empty sample the diff is
    ordered by target partition id (benefit 0).

    ``source_assignment`` / ``source_meta`` (always passed together)
    override the physical source partitioning — the hook the streaming
    ingest plane uses to plan *compactions*: the source is then the
    hybrid delta-bearing state (clustered base partitions plus one
    pseudo-partition per delta batch), so a compaction's move set is
    exactly the delta-touched target partitions and untouched clustered
    partitions are skipped as identical.
    """
    if (source_assignment is None) != (source_meta is None):
        raise ValueError("source_assignment and source_meta go together")
    if source_assignment is None:
        a_s = _assignment(source, data)
        src_meta = source.serving_meta()
    else:
        a_s = np.asarray(source_assignment, dtype=np.int64)
        src_meta = source_meta
    a_t = _assignment(target, data)
    p_s = src_meta.num_partitions
    p_t = target.num_partitions
    target_meta = target.materialize(data)

    # Exact per-block zone maps in one grouped reduction over the combined
    # (source, target) assignment key.
    key = a_s * p_t + a_t
    block = L.metadata_from_assignment(data, key, p_s * p_t)
    block_mins = block.mins.reshape(p_s, p_t, -1)
    block_maxs = block.maxs.reshape(p_s, p_t, -1)
    block_rows = block.rows.reshape(p_s, p_t)

    src_counts = block_rows.sum(axis=1)                  # (P_s,)
    tgt_counts = block_rows.sum(axis=0)                  # (P_t,)
    feeders = block_rows > 0                             # (P_s, P_t)

    # A target partition is *identical* iff all its rows come from one
    # source partition that contributes nothing anywhere else.
    identical = {}
    single_feeder = feeders.sum(axis=0) == 1
    for j in np.nonzero(single_feeder & (tgt_counts > 0))[0]:
        i = int(np.nonzero(feeders[:, j])[0][0])
        if block_rows[i, j] == src_counts[i] == tgt_counts[j]:
            identical[int(j)] = i

    diff = [int(j) for j in range(p_t)
            if tgt_counts[j] > 0 and int(j) not in identical]

    benefit_per_row = np.zeros(p_t)
    if recent_queries and diff:
        q_lo, q_hi = wl.stack_queries(list(recent_queries))
        freq_src, freq_tgt = scan_frequencies(
            [src_meta, target_meta], q_lo, q_hi,
            compute=compute)
        # Completing move j relocates block (i, j) from a partition read
        # with frequency freq_src[i] to one read with freq_tgt[j].
        gain = block_rows.T @ freq_src - tgt_counts * freq_tgt   # (P_t,)
        benefit_per_row = np.divide(gain, tgt_counts,
                                    out=np.zeros(p_t),
                                    where=tgt_counts > 0)

    order = sorted(diff, key=lambda j: (-benefit_per_row[j], j))
    moves = [MicroMove(target_partition=j,
                       rows=int(tgt_counts[j]),
                       source_partitions=tuple(
                           int(i) for i in np.nonzero(feeders[:, j])[0]),
                       benefit_per_row=float(benefit_per_row[j]))
             for j in order]
    return MigrationPlan(
        source_id=source.layout_id,
        target=target,
        moves=moves,
        total_move_rows=int(sum(m.rows for m in moves)),
        num_source_partitions=p_s,
        num_target_partitions=p_t,
        source_assignment=a_s,
        target_assignment=a_t,
        block_mins=block_mins,
        block_maxs=block_maxs,
        block_rows=block_rows,
        target_meta=target_meta,
        identical=identical,
    )


def plan_is_permutation_of_diff(plan: MigrationPlan) -> bool:
    """True iff the plan's move order is a permutation of the layout diff
    (every differing non-empty target partition exactly once) — the
    invariant the property tests pin down."""
    tgt_counts = plan.block_rows.sum(axis=0)
    diff = {int(j) for j in range(plan.num_target_partitions)
            if tgt_counts[j] > 0 and int(j) not in plan.identical}
    moved = [m.target_partition for m in plan.moves]
    return len(moved) == len(set(moved)) and set(moved) == diff


__all__ = ["MicroMove", "MigrationPlan", "plan_migration",
           "plan_is_permutation_of_diff", "scan_frequencies"]
