"""Roofline analysis over the dry-run records (§Roofline of EXPERIMENTS.md).

Per (arch x shape x mesh) cell, derives the three per-device roofline terms
from the trip-count-weighted HLO analysis (hlo_cost.py):

    compute    = flops_per_device     / peak bf16 FLOP/s
    memory     = bytes_per_device     / peak HBM bytes/s
    collective = coll_bytes_per_device/ ICI bytes/s per link

with the peaks of the modelled chip (:data:`TARGET_DEVICE_KIND`, looked up
in :data:`DEVICE_PEAKS`),

plus MODEL_FLOPS (6*N*D train / 2*N*D inference, N = active params) and the
useful-compute ratio MODEL_FLOPS / (HLO flops x chips), which catches remat
recompute, MoE capacity waste, padding, and replicated compute.

Caveat recorded in every report: the module is compiled by XLA:CPU, which
promotes bf16 compute to f32 (extra converts/copies) -- the memory term is
therefore an upper bound, up to ~2x pessimistic vs a TPU build.

Usage:  PYTHONPATH=src python -m repro.launch.roofline \
            --dryrun experiments/dryrun --out experiments/roofline
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

from repro.configs.base import SHAPES, get_arch

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
#: 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 ICI
#: links (50 GB/s each).  A device not listed here has no peaks:
#: :func:`device_peaks` raises rather than assume another chip's.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_link_bytes_per_s": 50e9},
}

#: The chip the dry-run meshes model (a v5e pod slice).
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind`` (KeyError if unknown)."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to DEVICE_PEAKS with "
                       f"their source") from None


def model_flops(arch: str, shape_name: str) -> float:
    """Analytic useful FLOPs per step (global, forward(+backward))."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.num_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence; attention reads the KV cache but does
    # negligible extra matmul FLOPs relative to 2N.
    return 2.0 * n_active * shape.global_batch


def ideal_bytes(arch: str, shape_name: str, opt_dtype: str = "float32"
                ) -> float:
    """Analytic minimal HBM traffic per step (global bytes).

    train:   params read twice (fwd+bwd) + grad write + optimizer m/v
             read+write + param write.
    prefill: params read + KV cache write.
    decode:  active params read + KV cache read (the serving floor).
    """
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    n = cfg.num_params()
    n_active = cfg.num_active_params()
    opt_b = 2 if opt_dtype == "bfloat16" else 4
    kv_per_tok = 2 * cfg.n_kv_heads * cfg.head_dim * 2   # k+v bf16
    n_attn_layers = (0 if cfg.family == "ssm" else
                     (cfg.n_layers // cfg.attn_every if cfg.family == "hybrid"
                      else cfg.n_layers))
    if shape.kind == "train":
        return n * 2 * 3 + n * 4 + n * opt_b * 4          # bf16 p, f32 grads
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return n_active * 2 + tokens * kv_per_tok * n_attn_layers
    kv_read = shape.global_batch * shape.seq_len * kv_per_tok * n_attn_layers
    state = 0.0
    if cfg.family in ("ssm", "hybrid") and cfg.ssm is not None:
        d_in = cfg.ssm.expand * cfg.d_model
        state = (shape.global_batch * cfg.n_layers
                 * (d_in // cfg.ssm.head_dim) * cfg.ssm.head_dim
                 * cfg.ssm.d_state * 4)
    if cfg.family == "ssm":
        dh = cfg.rwkv_head_dim
        state = (shape.global_batch * cfg.n_layers
                 * (cfg.d_model // dh) * dh * dh * 4)
    return n_active * 2 + kv_read + state


def analyze_record(rec: Dict) -> Dict:
    peaks = device_peaks(TARGET_DEVICE_KIND)
    flops_peak, hbm_bw = peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    hc = rec["hlo_cost"]
    chips = rec["num_devices"]
    compute_s = hc["flops_per_device"] / flops_peak
    memory_s = hc["bytes_per_device"] / hbm_bw
    coll_s = hc["collective_bytes_per_device"] / peaks["ici_link_bytes_per_s"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    hlo_flops_global = hc["flops_per_device"] * chips
    useful_ratio = mf / max(hlo_flops_global, 1.0)
    opt_dtype = rec.get("options", {}).get("opt_state_dtype", "float32")
    ib = ideal_bytes(rec["arch"], rec["shape"], opt_dtype)
    # The achievable step-time floor is the max of the compute ideal and the
    # memory ideal; roofline fraction = floor / modeled dominant term.
    ideal_s = max(mf / chips / flops_peak, ib / chips / hbm_bw)
    roofline_fraction = ideal_s / max(max(terms.values()), 1e-12)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec["kind"],
        "compute_s": compute_s, "memory_s": memory_s, "collective_s": coll_s,
        "dominant": dominant,
        "model_flops": mf,
        "ideal_bytes": ib,
        "ideal_s": ideal_s,
        "hlo_flops_global": hlo_flops_global,
        "useful_ratio": useful_ratio,
        "roofline_fraction": roofline_fraction,
        "collective_by_type": hc["collective_bytes_by_type"],
        "options": rec.get("options", {}),
        "memory_analysis": rec.get("memory_analysis", {}),
        "compile_seconds": rec.get("compile_seconds"),
    }


_NOTES = {
    "compute": ("dominant term is MXU compute; lower it by cutting remat "
                "recompute (useful_ratio < 0.75 means recompute/waste) or "
                "removing padded/replicated matmul work"),
    "memory": ("dominant term is HBM traffic; lower it with bf16-resident "
               "states, fused elementwise chains, larger attention blocks "
               "(fewer re-reads), or fewer optimizer passes"),
    "collective": ("dominant term is interconnect; lower it by re-sharding "
                   "to cut all-gathers (FSDP prefetch), overlapping "
                   "collectives with compute, or compressing gradients"),
}


def to_markdown(rows: List[Dict]) -> str:
    out = ["| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | MODEL_FLOPS | useful ratio | roofline frac |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.3f} | {r['memory_s']:.3f} "
            f"| {r['collective_s']:.3f} | **{r['dominant']}** "
            f"| {r['model_flops']:.2e} | {r['useful_ratio']:.2f} "
            f"| {r['roofline_fraction']:.3f} |")
    out.append("")
    out.append("Bottleneck notes (per dominant term):")
    for k, v in _NOTES.items():
        out.append(f"- **{k}**: {v}.")
    return "\n".join(out)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline")
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    args = ap.parse_args()
    rows = []
    for path in sorted(glob.glob(os.path.join(args.dryrun, "*.json"))):
        if path.endswith(".failed"):
            continue
        with open(path) as f:
            rec = json.load(f)
        if args.mesh != "both":
            want = "16x16" if args.mesh == "single" else "2x16x16"
            if rec["mesh"] != want:
                continue
        rows.append(analyze_record(rec))
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, f"roofline_{args.mesh}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    md = to_markdown(rows)
    with open(os.path.join(args.out, f"roofline_{args.mesh}.md"), "w") as f:
        f.write(md)
    print(md)


if __name__ == "__main__":
    main()
