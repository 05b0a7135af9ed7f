"""Kernel-perf lane: per-kernel µs/call + the fused-vs-separate ratio.

Successor of the old ``kernel_perf`` CSV module (its pruning / zorder /
flash-attention roofline rows still come out of :func:`run` for
``benchmarks/run.py``), promoted to a first-class BENCH family writing
``BENCH_kernels.json`` with three lanes:

* **fused_vs_separate** — the gated ratio.  The decision megakernel's
  dataflow (one pass over the packed ``(T, S, P, C)`` bounds plane
  emitting frame scan matrix + per-state costs + window scan
  frequencies) timed against the pre-megakernel dataflow it replaced
  (B separate per-frame ``fleet_scan`` launches + a reduction pass +
  T per-tenant ``move_score`` launches).  Both sides run the compiled
  XLA oracles so the lane is meaningful on CPU-only runners — the ratio
  isolates the *dataflow* win (one launch, one operand read) from
  Mosaic codegen, and a regression in either fused plumbing or the
  launch structure drags it below the gate.
* **interpret** — the Pallas megakernel in interpret mode on tiny
  shapes: not a speed measurement (interpret mode is a correctness
  vehicle) but proof on every runner that the kernel executes and
  matches its oracle bitwise.
* **compiled_pallas** — the megakernel compiled via Mosaic vs the three
  compiled separate kernels.  Skipped with an explicit reason on
  CPU-only runners (no Mosaic target); runs on TPU/GPU CI.

``--smoke`` is the CI configuration; the checked-in ``kernels_smoke``
section of ``BENCH_kernels.json`` holds the baseline ratio the
regression gate (benchmarks/check_regression.py) compares against.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# Invoked as ``python benchmarks/bench_kernels.py``, sys.path[0] is
# benchmarks/ itself — put the repo root first so the package resolves.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common
from repro.kernels.decision_fused import ops as df_ops
from repro.kernels.fleet_scan import ref as fs_ref
from repro.kernels.move_score import ref as ms_ref
from repro.kernels.pruning import ref as prune_ref
from repro.kernels.zorder import ref as z_ref
from repro.launch import roofline
from repro.launch.compile_cache import enable_compile_cache

# The analytic rooflines of run() are for the modelled chip, whatever the
# host.
_PEAKS = roofline.device_peaks(roofline.TARGET_DEVICE_KIND)
PEAK_FLOPS = _PEAKS["bf16_flops"]
HBM_BW = _PEAKS["hbm_bytes_per_s"]


def _time(f, *args, iters: int = 5, **kw):
    """Best-of-iters wall seconds; compiles/warns on the warmup call."""
    out = f(*args, **kw)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = f(*args, **kw)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _fused_operands(B: int, T: int, S: int, P: int, C: int, W: int,
                    seed: int = 0):
    """float32 fleet plane + frame queries + recent-query window."""
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    q_lo = jnp.asarray(rng.uniform(0, 1, (B, T, C)), f32)
    q_hi = q_lo + 0.15
    p_min = jnp.asarray(rng.uniform(0, 1, (T, S, P, C)), f32)
    p_max = p_min + 0.2
    rows = jnp.asarray(rng.integers(100, 1000, (T, S, P)), f32)
    inv = 1.0 / rows.sum(axis=-1)
    w_lo = jnp.asarray(rng.uniform(0, 1, (W, C)), f32)
    w_hi = w_lo + 0.15
    return q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi


# ---------------------------------------------------------------------------
# Lane 1 (gated): fused dataflow vs the separate-pass dataflow it replaced
# ---------------------------------------------------------------------------

@jax.jit
def _reduce_cost(scan, rows, inv):
    return (scan * rows[None]).sum(axis=-1) * inv[None]


def _separate_passes(q_lo, q_hi, p_min, p_max, rows, inv, w_lo, w_hi):
    """The pre-megakernel per-tick dataflow: one ``fleet_scan`` launch per
    frame over the flattened plane, a reduction pass for costs, and one
    ``move_score`` launch per tenant for window frequencies — three reads
    of the bounds tensors and B + T + 1 launches."""
    B = q_lo.shape[0]
    T, S, P, C = p_min.shape
    pm2 = p_min.reshape(T, S * P, C)
    px2 = p_max.reshape(T, S * P, C)
    scans = [fs_ref.scan_fleet(q_lo[b], q_hi[b], pm2, px2) for b in range(B)]
    scan = jnp.stack(scans).reshape(B, T, S, P)
    cost = _reduce_cost(scan, rows, inv)
    freq = jnp.stack([ms_ref.move_scores(w_lo, w_hi, p_min[t], p_max[t])
                      for t in range(T)])
    return scan, cost, freq


def bench_fused_vs_separate(B: int, T: int, S: int, P: int, C: int, W: int,
                            reps: int, seed: int = 0) -> Dict:
    ops = _fused_operands(B, T, S, P, C, W, seed)

    def fused(*a):
        return df_ops.fused_decision(*a, use_kernel=False)

    fused_s = _time(fused, *ops, iters=reps)
    sep_s = _time(_separate_passes, *ops, iters=reps)

    # Same operands, same outputs: parity guards the measurement.
    f_scan, f_cost, f_freq = fused(*ops)
    s_scan, s_cost, s_freq = _separate_passes(*ops)
    assert np.array_equal(np.asarray(f_scan), np.asarray(s_scan))
    assert np.allclose(np.asarray(f_cost), np.asarray(s_cost), atol=1e-6)
    assert np.array_equal(np.asarray(f_freq), np.asarray(s_freq))

    return {
        "B": B, "T": T, "S": S, "P": P, "C": C, "W": W,
        "fused_us": round(fused_s * 1e6, 1),
        "separate_us": round(sep_s * 1e6, 1),
        "ratio": round(sep_s / fused_s, 2),
    }


# ---------------------------------------------------------------------------
# Lane 2 (always runs): megakernel in interpret mode, tiny shapes
# ---------------------------------------------------------------------------

def bench_interpret_lane(seed: int = 0) -> Dict:
    B, T, S, P, C, W = 2, 3, 2, 8, 4, 4
    ops = _fused_operands(B, T, S, P, C, W, seed)

    def kernel(*a):
        return df_ops.fused_decision(*a, use_kernel=True, bb=2,
                                     interpret=True)

    s = _time(kernel, *ops, iters=2)
    k_scan, k_cost, k_freq = kernel(*ops)
    o_scan, o_cost, o_freq = df_ops.fused_decision(*ops, use_kernel=False)
    assert np.array_equal(np.asarray(k_scan), np.asarray(o_scan))
    assert np.allclose(np.asarray(k_cost), np.asarray(o_cost), atol=1e-6)
    assert np.array_equal(np.asarray(k_freq), np.asarray(o_freq))
    return {
        "B": B, "T": T, "S": S, "P": P, "C": C, "W": W,
        "us_per_call": round(s * 1e6, 1),
        "parity_vs_oracle": "exact",
    }


# ---------------------------------------------------------------------------
# Lane 3 (accelerator only): megakernel compiled via Mosaic
# ---------------------------------------------------------------------------

def bench_compiled_pallas_lane(B: int, T: int, S: int, P: int, C: int, W: int,
                               reps: int, seed: int = 0) -> Dict:
    backend = jax.default_backend()
    if backend == "cpu":
        return {
            "skipped": True,
            "reason": "compiled Pallas lane needs an accelerator backend "
                      "(jax.default_backend() == 'cpu': Mosaic codegen "
                      "unavailable, interpret lane covers correctness)",
        }
    ops = _fused_operands(B, T, S, P, C, W, seed)

    def kernel(*a):
        return df_ops.fused_decision(*a, use_kernel=True, interpret=False)

    fused_s = _time(kernel, *ops, iters=reps)
    sep_s = _time(_separate_passes, *ops, iters=reps)
    return {
        "backend": backend,
        "B": B, "T": T, "S": S, "P": P, "C": C, "W": W,
        "fused_kernel_us": round(fused_s * 1e6, 1),
        "separate_us": round(sep_s * 1e6, 1),
        "ratio": round(sep_s / fused_s, 2),
    }


# ---------------------------------------------------------------------------
# CSV entry point for benchmarks/run.py (legacy kernel_perf lanes + fused)
# ---------------------------------------------------------------------------

def run(quick: bool = False) -> List[str]:
    rows: List[str] = []
    # Pruning matrix: Q x P x C interval-overlap (paper's eval_skipped).
    Q, P, C = (2048, 512, 32) if not quick else (512, 128, 16)
    rng = np.random.default_rng(0)
    q_lo = jnp.asarray(rng.uniform(0, 1, (Q, C)), jnp.float32)
    q_hi = q_lo + 0.2
    p_min = jnp.asarray(rng.uniform(0, 1, (P, C)), jnp.float32)
    p_max = p_min + 0.2
    f = jax.jit(prune_ref.scan_matrix)
    s = _time(f, q_lo, q_hi, p_min, p_max)
    flops = 4.0 * Q * P * C                   # 2 cmp + 1 and + reduce
    bytes_ = 4.0 * (Q * C * 2 + P * C * 2 + Q * P)
    ai = flops / bytes_
    tpu_bound_us = max(flops / PEAK_FLOPS, bytes_ / HBM_BW) * 1e6
    rows.append(common.csv_row(
        f"kernel.pruning.{Q}x{P}x{C}", s * 1e6,
        f"flops={flops:.2e};bytes={bytes_:.2e};arith_intensity={ai:.2f};"
        f"tpu_roofline_us={tpu_bound_us:.1f};bound=memory"))

    # Z-order keys.
    N, m, bits = (1_000_000, 3, 10) if not quick else (100_000, 3, 10)
    vals = jnp.asarray(rng.uniform(0, 1, (N, m)), jnp.float32)
    lo = vals.min(0)
    hi = vals.max(0)
    f = jax.jit(lambda v: z_ref.zorder_keys(v, lo, hi, bits))
    s = _time(f, vals)
    bytes_ = 4.0 * N * m + 4.0 * N
    ops = float(N * m * bits * 3)
    rows.append(common.csv_row(
        f"kernel.zorder.{N}x{m}", s * 1e6,
        f"int_ops={ops:.2e};bytes={bytes_:.2e};"
        f"tpu_roofline_us={bytes_ / HBM_BW * 1e6:.1f};bound=memory"))

    # Flash attention jnp path (CPU) + analytic TPU roofline.
    B, H, T, dh = (1, 8, 1024, 64) if quick else (2, 8, 2048, 64)
    from repro.models import layers as L
    q = jax.random.normal(jax.random.PRNGKey(0), (B, T, H, dh), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, T, H, dh), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, T, H, dh), jnp.float32)
    f = jax.jit(lambda a, b, c: L.flash_attention(a, b, c, causal=True))
    s = _time(f, q, k, v, iters=3)
    flops = 4.0 * B * H * T * T * dh / 2      # causal halves the work
    bytes_ = 2.0 * (3 * B * T * H * dh + B * T * H * dh)
    rows.append(common.csv_row(
        f"kernel.flash_attention.{B}x{H}x{T}x{dh}", s * 1e6,
        f"flops={flops:.2e};bytes={bytes_:.2e};"
        f"tpu_roofline_us={max(flops / PEAK_FLOPS, bytes_ / HBM_BW) * 1e6:.1f};"
        f"bound=compute"))

    # Fused decision megakernel dataflow vs the three separate passes.
    shape = (8, 8, 8, 64, 8, 32) if quick else (16, 16, 8, 128, 12, 64)
    cell = bench_fused_vs_separate(*shape, reps=3)
    rows.append(common.csv_row(
        "kernel.decision_fused."
        f"B{shape[0]}xT{shape[1]}xS{shape[2]}xP{shape[3]}", cell["fused_us"],
        f"separate_us={cell['separate_us']};"
        f"fused_vs_separate=x{cell['ratio']:.2f}"))
    return rows


# ---------------------------------------------------------------------------
# JSON entry point: the BENCH_kernels.json family
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: one fused-vs-separate cell + interpret "
                         "lane, small")
    ap.add_argument("--out", default="BENCH_kernels.json")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        cells = [dict(B=16, T=8, S=8, P=64, C=8, W=32)]
        reps = 5
    else:
        cells = [dict(B=16, T=8, S=8, P=64, C=8, W=32),
                 dict(B=32, T=16, S=8, P=128, C=12, W=64),
                 dict(B=32, T=32, S=8, P=128, C=12, W=64)]
        reps = 7

    grid: List[Dict] = []
    ratios: Dict[str, Dict[str, float]] = {}
    for cfg in cells:
        cell = bench_fused_vs_separate(reps=reps, **cfg)
        grid.append(cell)
        key = f"B{cfg['B']}_T{cfg['T']}_S{cfg['S']}_P{cfg['P']}"
        ratios[key] = {"fused_vs_separate": cell["ratio"]}
        print(f"{key:24s} fused={cell['fused_us']:9.1f}us "
              f"separate={cell['separate_us']:9.1f}us "
              f"x{cell['ratio']:.2f}", flush=True)

    interp = bench_interpret_lane()
    print(f"interpret lane: {interp['us_per_call']:.1f}us/call "
          f"({interp['parity_vs_oracle']} vs oracle)", flush=True)
    big = cells[-1]
    compiled = bench_compiled_pallas_lane(reps=reps, **big)
    if compiled.get("skipped"):
        print(f"compiled pallas lane: SKIPPED ({compiled['reason']})",
              flush=True)
    else:
        print(f"compiled pallas lane ({compiled['backend']}): "
              f"fused={compiled['fused_kernel_us']:.1f}us "
              f"x{compiled['ratio']:.2f}", flush=True)

    payload = {
        "benchmark": "kernels",
        "units": "us per call (best of reps, block_until_ready); "
                 "fused_vs_separate = separate-passes wall / fused wall on "
                 "identical operands, compiled XLA",
        "config": {
            "cells": cells, "reps": reps, "smoke": bool(args.smoke),
            "platform": platform.platform(), "numpy": np.__version__,
            "jax": jax.__version__, "jax_backend": jax.default_backend(),
        },
        "results": grid,
        "fused_vs_separate": ratios,
        "interpret_lane": interp,
        "compiled_pallas_lane": compiled,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
