"""Pallas TPU megakernel: the whole per-tick decision plane in one pass.

Every tick the engine needs three products of the same packed fleet plane:
the candidate-state scan matrix for cost scoring, the serve-shadow score
(the shadow state's lane of that same matrix), and — for
migration-planning tenants — per-partition scan frequencies over the
recent-query window.  Run as three separate kernels
(:mod:`repro.kernels.pruning`, :mod:`repro.kernels.fleet_scan`,
:mod:`repro.kernels.move_score`) the bounds tensors stream from HBM three
times per tick; this kernel reads them once and emits all three outputs.

Layout.  The plane is taken *column-major*, ``(C, T, S, P)`` — the twin
:class:`repro.engine.fleet_matrix.FleetMatrix` and
:class:`repro.engine.state_matrix.StateMatrix` already keep — so
partitions ride the 128-wide lane axis and candidate states the sublanes.
The column loop is a static Python loop over the leading axis and every
vector op works on an ``(S, BP)`` tile; the scalar query bounds live in
SMEM, one tenant's frame block per program.

  grid = (T, P/BP, B/BB), frame blocks innermost.  Each program holds one
  ``(C, 1, S, BP)`` bounds tile per side in VMEM — its block index ignores
  the frame axis, so the pipeline fetches it once per (tenant, partition
  block) — loops over its BB frames, and writes

  * ``scan`` (B, T, S, P) — exactly 0/1: the frame's query for tenant t
    overlaps partition p of candidate state s;
  * ``cost`` (B, T, S) — scanned-row fraction ``sum_p scan * rows *
    inv_totals``; each program emits its partition block's row sums and
    the wrapper adds the P/BP partial sums and scales by ``inv_totals``;
  * ``freq`` (T, S, P) — mean overlap over the (W, C) recent-query window,
    the move planner's ordering signal; computed at the first frame block
    and held in VMEM while the frame axis revisits it.

Like the three kernels it fuses, this is VPU-bound and memory-bound (~C
compares per bound); the win is one HBM pass over the bounds per tick
instead of three, and one launch for all B frames.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._backend import resolve_interpret

DEFAULT_BB = 32      # frames per program
DEFAULT_BP = 512     # partitions (lanes) per program; a multiple of 128
LANES = 128
SMEM_TILE = 1024    # elements per tile of a flat f32 array


def _overlap(lo_ref, hi_ref, base, pmin_ref, pmax_ref, n_cols):
    """(S, BP) bool: bounds tile vs the query at SMEM offset ``base``."""
    acc = None
    for c in range(n_cols):
        term = ((pmin_ref[c, 0] <= hi_ref[base + c])
                & (pmax_ref[c, 0] >= lo_ref[base + c]))
        acc = term if acc is None else acc & term
    return acc


def _make_kernel(*, n_cols, bb, n_window, emit_scan, emit_cost,
                 emit_freq):
    def kernel(*refs):
        it = iter(refs)
        qlo_ref, qhi_ref, pmin_ref, pmax_ref = (next(it) for _ in range(4))
        rows_ref = wlo_ref = whi_ref = None
        if emit_cost:
            rows_ref = next(it)
        if emit_freq:
            wlo_ref, whi_ref = next(it), next(it)
        outs = list(it)
        scan_ref = outs.pop(0) if emit_scan else None
        cost_ref = outs.pop(0) if emit_cost else None
        freq_ref = outs.pop(0) if emit_freq else None
        fb = pl.program_id(2)

        if emit_scan or emit_cost:
            @pl.loop(0, bb)
            def _frame(b):
                base = b * n_cols
                hit = _overlap(qlo_ref, qhi_ref, base, pmin_ref, pmax_ref,
                               n_cols)
                scan = jnp.where(hit, 1.0, 0.0)               # (S, BP)
                if emit_scan:
                    scan_ref[b, 0] = scan
                if emit_cost:
                    cost_ref[0, 0, b] = jnp.sum(scan * rows_ref[0], axis=-1,
                                                keepdims=True)  # (S, 1)

        if emit_freq:
            @pl.when(fb == 0)
            def _freq():
                def body(w, count):
                    hit = _overlap(wlo_ref, whi_ref, w * n_cols, pmin_ref,
                                   pmax_ref, n_cols)
                    return count + jnp.where(hit, 1.0, 0.0)
                count = jax.lax.fori_loop(
                    0, n_window, body,
                    jnp.zeros(freq_ref.shape[1:], jnp.float32))
                freq_ref[0] = count / n_window
    return kernel


def fused_decision_pallas(q_lo: jax.Array, q_hi: jax.Array,
                          p_min: jax.Array, p_max: jax.Array,
                          rows: Optional[jax.Array] = None,
                          inv_totals: Optional[jax.Array] = None,
                          w_lo: Optional[jax.Array] = None,
                          w_hi: Optional[jax.Array] = None,
                          *, emit_scan: bool = True, mask: bool = False,
                          bb: int = DEFAULT_BB, bp: int = DEFAULT_BP,
                          interpret: Optional[bool] = None,
                          ) -> Tuple[Optional[jax.Array],
                                     Optional[jax.Array],
                                     Optional[jax.Array]]:
    """(B, T, C) frame queries x (C, T, S, P) plane -> (scan, cost, freq).

    ``p_min``/``p_max`` are the column-major plane (``ref`` takes the same
    bounds as ``(T, S, P, C)``); output semantics match
    :func:`repro.kernels.decision_fused.ref.fused_decision`.  Each element
    of the returned triple is ``None`` when its inputs were not supplied
    (``cost`` needs ``rows`` (T, S, P) and ``inv_totals`` (T, S);
    ``freq`` needs the (W, C) window bounds) or, for ``scan``, when
    ``emit_scan=False``.  ``mask=True`` returns ``scan`` as the bool
    ``scan > 0.5``, thresholded inside the same compiled program, so a
    caller that wants the mask makes one device round trip.  ``bp`` must
    be a multiple of 128 (a plane of at most ``bp`` partitions is one
    whole block).  ``interpret=None`` auto-selects via
    :func:`repro.kernels._backend.resolve_interpret`.
    """
    emit_cost = rows is not None
    emit_freq = w_lo is not None
    if not (emit_scan or emit_cost or emit_freq):
        raise ValueError("fused_decision_pallas: nothing to emit")
    if bp % LANES:
        raise ValueError(f"bp={bp} must be a multiple of {LANES}")
    return _fused_call(q_lo, q_hi, p_min, p_max, rows, inv_totals, w_lo,
                       w_hi, emit_scan=emit_scan, mask=mask,
                       emit_cost=emit_cost, emit_freq=emit_freq, bb=bb, bp=bp,
                       interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("emit_scan", "mask",
                                             "emit_cost", "emit_freq", "bb",
                                             "bp", "interpret"))
def _fused_call(q_lo, q_hi, p_min, p_max, rows, inv_totals, w_lo, w_hi, *,
                emit_scan, mask, emit_cost, emit_freq, bb, bp, interpret):
    B, T, C = q_lo.shape
    _, _, S, P = p_min.shape
    bp = P if P <= bp else bp
    bb = min(bb, B) if (emit_scan or emit_cost) else B
    pad_b = (-B) % bb
    pad_p = (-P) % bp
    if pad_p:
        # Padded partition slots get empty bounds: never scanned.
        p_min = jnp.pad(p_min, ((0, 0), (0, 0), (0, 0), (0, pad_p)),
                        constant_values=jnp.inf)
        p_max = jnp.pad(p_max, ((0, 0), (0, 0), (0, 0), (0, pad_p)),
                        constant_values=-jnp.inf)
        if emit_cost:
            rows = jnp.pad(rows, ((0, 0), (0, 0), (0, pad_p)))
    Bp, Pp = B + pad_b, P + pad_p
    nj, nb = Pp // bp, Bp // bb
    # Frame-query bounds go to SMEM one (tenant, frame block) chunk per
    # program (SMEM holds 1 MiB); a 1-D block must be a whole number of
    # the 1024-element tiles XLA lays the flat array out in.  Padded
    # frames are sliced away below.
    chunk = pl.cdiv(bb * C, SMEM_TILE) * SMEM_TILE
    q_lo, q_hi = (
        jnp.pad(jnp.pad(jnp.swapaxes(q, 0, 1), ((0, 0), (0, pad_b), (0, 0)))
                .reshape(T * nb, bb * C),
                ((0, 0), (0, chunk - bb * C))).reshape(-1)
        for q in (q_lo, q_hi))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    frames = pl.BlockSpec((chunk,), lambda t, j, f: (t * nb + f,),
                          memory_space=pltpu.SMEM)
    plane = pl.BlockSpec((C, 1, S, bp), lambda t, j, f: (0, t, 0, j))
    arrays = [q_lo, q_hi, p_min, p_max]
    in_specs = [frames, frames, plane, plane]
    if emit_cost:
        arrays.append(rows)
        in_specs.append(pl.BlockSpec((1, S, bp), lambda t, j, f: (t, 0, j)))
    n_window = 0
    if emit_freq:
        n_window = w_lo.shape[0]
        arrays += [w_lo.reshape(-1), w_hi.reshape(-1)]
        in_specs += [smem, smem]
    out_specs, out_shapes = [], []
    if emit_scan:
        out_specs.append(pl.BlockSpec((bb, 1, S, bp),
                                      lambda t, j, f: (f, t, 0, j)))
        out_shapes.append(jax.ShapeDtypeStruct((Bp, T, S, Pp), jnp.float32))
    if emit_cost:
        # Per-partition-block row sums; (S, 1) keeps S on the sublanes.
        out_specs.append(pl.BlockSpec((1, 1, bb, S, 1),
                                      lambda t, j, f: (t, j, f, 0, 0)))
        out_shapes.append(jax.ShapeDtypeStruct((T, nj, Bp, S, 1),
                                               jnp.float32))
    if emit_freq:
        out_specs.append(pl.BlockSpec((1, S, bp), lambda t, j, f: (t, 0, j)))
        out_shapes.append(jax.ShapeDtypeStruct((T, S, Pp), jnp.float32))

    outs = pl.pallas_call(
        _make_kernel(n_cols=C, bb=bb, n_window=n_window,
                     emit_scan=emit_scan, emit_cost=emit_cost,
                     emit_freq=emit_freq),
        grid=(T, nj, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        # The frame axis revisits the freq block, so it stays sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="decision_fused",
    )(*arrays)
    outs = list(outs)
    scan = outs.pop(0)[:B, :, :, :P] if emit_scan else None
    if mask and emit_scan:
        scan = scan > 0.5
    cost = (jnp.swapaxes(outs.pop(0).sum(axis=1)[..., 0], 0, 1)[:B]
            * inv_totals[None] if emit_cost else None)
    freq = outs.pop(0)[:, :, :P] if emit_freq else None
    return scan, cost, freq
