"""Jit'd public wrapper for the fused decision megakernel.

Dispatches to the Pallas megakernel on accelerator backends (compiled) /
interpret mode on CPU, and to the jnp oracle when the kernel is bypassed
(`use_kernel=False`) — the oracle is one fused XLA computation, so it is
also the compiled lane the kernel benchmark times on CPU-only hosts.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decision_fused import decision_fused, ref


def fused_decision(q_lo, q_hi, p_min, p_max, rows=None, inv_totals=None,
                   w_lo=None, w_hi=None, use_kernel: bool = True,
                   **block_kw) -> Tuple[Optional[jax.Array],
                                        Optional[jax.Array],
                                        Optional[jax.Array]]:
    """(B, T, C) x (T, S, P, C) -> (scan, cost, freq), one operand pass.

    ``cost`` requires ``rows`` (T, S, P) and ``inv_totals`` (T, S);
    ``freq`` requires the (W, C) recent-query window bounds.  Elements of
    the triple not requested come back ``None``.  The kernel takes the
    plane column-major, so the bounds are transposed on the way in.
    """
    if not use_kernel:
        return _ref_call(q_lo, q_hi, p_min, p_max, rows, inv_totals,
                         w_lo, w_hi)
    return decision_fused.fused_decision_pallas(
        q_lo, q_hi, jnp.moveaxis(p_min, -1, 0), jnp.moveaxis(p_max, -1, 0),
        rows, inv_totals, w_lo, w_hi, **block_kw)


@functools.partial(jax.jit, static_argnames=())
def _ref_call(q_lo, q_hi, p_min, p_max, rows, inv_totals, w_lo, w_hi):
    return ref.fused_decision(q_lo, q_hi, p_min, p_max, rows, inv_totals,
                              w_lo, w_hi)
