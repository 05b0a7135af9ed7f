"""Pluggable compute backends for the metadata plane's scan matrix.

Everything the decision loop evaluates — service-cost estimates over all
candidate states, cost vectors over the R-TBS sample — reduces to the
(Q, P) interval-overlap *scan matrix* over C columns.  This module is the
single entry point for computing it:

* ``numpy`` (default): exact float64 comparisons; bit-identical to
  :func:`repro.core.layouts.partitions_scanned`.
* ``pallas``: the TPU kernel :func:`repro.kernels.pruning.scan_matrix_pallas`
  (compiled on TPU/GPU, interpreter on CPU — auto-selected).  Operands are
  cast to float32 on the way in; when any bound would not survive that
  cast exactly the call warns and falls back to the exact numpy path
  (:func:`float32_exact` is the check), so the kernel path never silently
  changes results.
* ``pallas_fused``: the decision megakernel
  (:func:`repro.kernels.decision_fused.decision_fused.fused_decision_pallas`)
  — the same overlap semantics, but one operand pass produces the scan
  matrix for a whole block of query frames (plus cost and move-frequency
  outputs for callers that want them).  Same float32 guard as ``pallas``.
"""
from __future__ import annotations

import warnings
from typing import Optional

import numpy as np

from repro import obs

BACKENDS = ("numpy", "pallas", "pallas_fused")


def float32_exact(*arrays: np.ndarray) -> bool:
    """True iff every value survives a float64 -> float32 round-trip.

    ``±inf`` round-trips exactly; a finite bound like ``nextafter(1, 2)``
    does not — the Pallas kernels cast operands to float32, so only
    float32-exact inputs keep the kernel paths bit-identical to the
    float64 numpy comparisons.
    """
    for a in arrays:
        a = np.asarray(a)
        if a.dtype == np.float32:
            continue
        if not np.array_equal(a, a.astype(np.float32).astype(a.dtype)):
            return False
    return True


def _f32_guard(name: str, *arrays: np.ndarray) -> bool:
    """Warn and return False when a kernel path must fall back to numpy."""
    if float32_exact(*arrays):
        return True
    obs.count("plane.fallbacks")
    warnings.warn(
        f"{name}: bounds are not exactly float32-representable; the pallas "
        f"kernel's float32 cast would silently change the scan matrix — "
        f"falling back to the exact numpy path",
        RuntimeWarning, stacklevel=3)
    return False


def scan_matrix(q_lo: np.ndarray, q_hi: np.ndarray, mins: np.ndarray,
                maxs: np.ndarray, backend: str = "numpy") -> np.ndarray:
    """(Q, C) query bounds x (P, C) partition bounds -> (Q, P) bool.

    ``out[q, p]`` is True iff partition p must be scanned for query q, i.e.
    every column's [min, max] zone overlaps the query's [lo, hi] range.
    """
    if backend == "numpy":
        overlap = ((mins[None, :, :] <= q_hi[:, None, :])
                   & (maxs[None, :, :] >= q_lo[:, None, :]))
        return overlap.all(axis=-1)
    if backend in ("pallas", "pallas_fused"):
        if not _f32_guard("scan_matrix", q_lo, q_hi, mins, maxs):
            return scan_matrix(q_lo, q_hi, mins, maxs, backend="numpy")
        if backend == "pallas":
            return _scan_matrix_pallas(q_lo, q_hi, mins, maxs)
        return _scan_matrix_fused(q_lo, q_hi, mins, maxs)
    raise ValueError(f"unknown compute backend: {backend!r} "
                     f"(expected one of {BACKENDS})")


def masked_overlap(minsT: np.ndarray, maxsT: np.ndarray, q_lo: np.ndarray,
                   q_hi: np.ndarray) -> np.ndarray:
    """Exact overlap test over column-major bounds, one query at a time.

    ``minsT``/``maxsT`` are ``(C, ..., P)`` (leading column axis; the rest
    broadcasts — ``(C, P)`` for a single layout, ``(C, S, P)`` for a packed
    plane).  Columns whose query bound is infinite are skipped outright:
    ``min <= +inf`` and ``max >= -inf`` are identically True, so skipping
    cannot change the result — it is bit-identical to the full comparison.
    This is the single implementation behind StateMatrix estimation and
    InMemoryBackend serving; their cross-path bit-identity rests on it.
    """
    acc: Optional[np.ndarray] = None
    for c in (q_hi != np.inf).nonzero()[0].tolist():
        term = minsT[c] <= q_hi[c]
        acc = term if acc is None else np.logical_and(acc, term, out=acc)
    for c in (q_lo != -np.inf).nonzero()[0].tolist():
        term = maxsT[c] >= q_lo[c]
        acc = term if acc is None else np.logical_and(acc, term, out=acc)
    if acc is None:     # fully unbounded query: every partition is scanned
        acc = np.ones(minsT.shape[1:], dtype=bool)
    return acc


def fleet_masked_overlap(minsT: np.ndarray, maxsT: np.ndarray,
                         q_lo: np.ndarray, q_hi: np.ndarray) -> np.ndarray:
    """Exact overlap test for a whole fleet, one query *per tenant*.

    ``minsT``/``maxsT`` are ``(C, T, S, P)`` — the transposed packed fleet
    plane, each column of one tenant a contiguous ``(S, P)`` block
    compared against that tenant's scalar bound (long contiguous runs keep
    numpy's fast comparison loops engaged) — and ``q_lo``/``q_hi`` are
    ``(T, C)`` or ``(B, T, C)``: one bound pair per tenant row, optionally
    for a block of B query *frames*.  Returns bool ``(T, S, P)`` (or
    ``(B, T, S, P)``): tenant t's ``[..., t, :, :]`` slice is bit-identical
    to :func:`masked_overlap` over t's own ``(C, S, P)`` plane with t's
    query, because a column only ever adds ``min <= +inf`` /
    ``max >= -inf`` terms (identically True) for tenants unbounded on it,
    and columns unbounded for *every* tenant and frame are skipped
    outright.
    """
    single = q_lo.ndim == 2
    if single:
        q_lo = q_lo[None]
        q_hi = q_hi[None]
    flat_hi = q_hi.reshape(-1, q_hi.shape[-1])
    flat_lo = q_lo.reshape(-1, q_lo.shape[-1])
    acc: Optional[np.ndarray] = None
    for c in np.nonzero(~(flat_hi == np.inf).all(axis=0))[0].tolist():
        term = minsT[c][None] <= q_hi[:, :, c, None, None]
        acc = term if acc is None else np.logical_and(acc, term, out=acc)
    for c in np.nonzero(~(flat_lo == -np.inf).all(axis=0))[0].tolist():
        term = maxsT[c][None] >= q_lo[:, :, c, None, None]
        acc = term if acc is None else np.logical_and(acc, term, out=acc)
    if acc is None:     # every tenant fully unbounded: scan everything
        acc = np.ones((q_lo.shape[0],) + minsT.shape[1:], dtype=bool)
    return acc[0] if single else acc


def fleet_scan_matrix(q_lo: np.ndarray, q_hi: np.ndarray, mins: np.ndarray,
                      maxs: np.ndarray, backend: str = "numpy") -> np.ndarray:
    """(T, C) per-tenant bounds x (T, N, C) packed bounds -> (T, N) bool.

    The fused fleet-wide scan: every tenant's candidate states are scored
    against that tenant's query in one pass.  ``numpy`` is exact float64;
    ``pallas`` routes through :func:`repro.kernels.fleet_scan.fleet_scan.
    scan_fleet_pallas` (float32 — see the module docstring caveat).
    """
    if backend == "numpy":
        overlap = ((mins <= q_hi[:, None, :]) & (maxs >= q_lo[:, None, :]))
        return overlap.all(axis=-1)
    if backend in ("pallas", "pallas_fused"):
        if not _f32_guard("fleet_scan_matrix", q_lo, q_hi, mins, maxs):
            return fleet_scan_matrix(q_lo, q_hi, mins, maxs,
                                     backend="numpy")
        if backend == "pallas":
            return _fleet_scan_pallas(q_lo, q_hi, mins, maxs)
        return fused_frames_scan(
            q_lo[None], q_hi[None], np.moveaxis(mins, -1, 0)[:, :, None],
            np.moveaxis(maxs, -1, 0)[:, :, None])[0, :, 0, :]
    raise ValueError(f"unknown compute backend: {backend!r} "
                     f"(expected one of {BACKENDS})")


def _fleet_scan_pallas(q_lo, q_hi, mins, maxs) -> np.ndarray:
    import jax.numpy as jnp

    from repro.kernels.fleet_scan import fleet_scan

    out = fleet_scan.scan_fleet_pallas(
        jnp.asarray(q_lo, jnp.float32), jnp.asarray(q_hi, jnp.float32),
        jnp.asarray(mins, jnp.float32), jnp.asarray(maxs, jnp.float32))
    return np.asarray(out) > 0.5


def _scan_matrix_pallas(q_lo, q_hi, mins, maxs) -> np.ndarray:
    import jax.numpy as jnp

    from repro.kernels.pruning import pruning

    out = pruning.scan_matrix_pallas(
        jnp.asarray(q_lo, jnp.float32), jnp.asarray(q_hi, jnp.float32),
        jnp.asarray(mins, jnp.float32), jnp.asarray(maxs, jnp.float32))
    return np.asarray(out) > 0.5


def fused_frames_scan(q_lo: np.ndarray, q_hi: np.ndarray, minsT: np.ndarray,
                      maxsT: np.ndarray) -> np.ndarray:
    """(B, T, C) frame bounds x (C, T, S, P) plane -> (B, T, S, P) bool.

    One megakernel launch scores every frame of a batched pass for every
    tenant — the ``pallas_fused`` replacement for B separate
    :func:`fleet_scan_matrix` calls.  The plane is column-major, the twin
    the packed planes keep.  Operands are cast to float32; callers owning
    the bit-identity contract must check :func:`float32_exact` first (see
    ``FleetMatrix._scanned_all``).  The frame count is padded up to a
    power of two, so passes of varying size compile a handful of kernel
    shapes rather than one per size; the padded frames are computed and
    read back, and sliced away on the host.

    ``minsT``/``maxsT`` may instead be float32 device arrays, a plane kept
    resident on the device (``FleetMatrix``): they go to the kernel as
    they are.  The numpy operands are cast to float32 on the host and
    handed to the compiled program as they are, so their copy to the
    device rides its dispatch.  The program thresholds the scan itself,
    so a pass is one dispatch and one blocking readback of the mask.

    Traced (:mod:`repro.obs`) as the spans ``plane.stage`` (frame
    padding), ``plane.upload`` (the host's float32 cast of the numpy
    operands), ``plane.kernel`` (the one dispatch, which carries the
    numpy operands to the device), ``plane.readback`` (the wait for the
    mask and its copy back) and the counters ``plane.passes``,
    ``plane.h2d_bytes`` (bytes copied up) and ``plane.d2h_bytes`` (bytes
    of the padded mask copied back).
    """
    import jax

    from repro.kernels.decision_fused import decision_fused

    b = q_lo.shape[0]
    with obs.span("plane.stage"):
        pad = ((0, (1 << (b - 1).bit_length()) - b), (0, 0), (0, 0))
        q_lo, q_hi = np.pad(q_lo, pad), np.pad(q_hi, pad)
    with obs.span("plane.upload"):
        operands = [a if isinstance(a, jax.Array) else np.asarray(
            a, np.float32) for a in (q_lo, q_hi, minsT, maxsT)]
    with obs.span("plane.kernel"):
        mask, _, _ = decision_fused.fused_decision_pallas(*operands,
                                                          mask=True)
    with obs.span("plane.readback"):
        out = np.asarray(mask)
    if obs.enabled():
        obs.count("plane.passes")
        obs.count("plane.h2d_bytes", sum(
            a.nbytes for a in operands if isinstance(a, np.ndarray)))
        obs.count("plane.d2h_bytes", out.nbytes)
    return out[:b]


def _scan_matrix_fused(q_lo, q_hi, mins, maxs) -> np.ndarray:
    # (Q, C) x (P, C) through the megakernel: Q query frames of a single
    # tenant whose plane has one state of P partitions.
    out = fused_frames_scan(q_lo[:, None, :], q_hi[:, None, :],
                            mins.T[:, None, None, :], maxs.T[:, None, None, :])
    return out[:, 0, 0, :]
