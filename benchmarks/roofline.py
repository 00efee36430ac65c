"""Roofline analysis from the dry-run's compiled artifacts.

For each (arch x shape x mesh) record in results/dryrun_*.json:

  compute term    = HLO_FLOPs/device   / peak bf16 FLOP/s
  memory term     = HLO_bytes/device   / peak HBM bytes/s
  collective term = coll_bytes/device  / ICI bytes/s per link

with the peaks of the chip the meshes model (``PEAKS``, keyed by JAX's
``device_kind``; the dry run's meshes are v5e pods).

HLO_FLOPs and HLO_bytes come from compiled.cost_analysis() (per-partition
module); collective bytes from the trip-count-aware HLO parser in
launch/dryrun.py.  MODEL_FLOPS is the analytic 6*N*D (train) / 2*N*D
(prefill/decode), N = active params, D = tokens — the ratio against
HLO_FLOPs*chips exposes remat/dispatch waste (>1x expected with per-layer
remat: ~1.33x recompute, MoE capacity overcompute, attention not in 6ND).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

# Published per-chip peaks keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of inter-chip
# interconnect over the chip's 4 ICI links.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_link_bytes_per_s": 1600e9 / 8 / 4},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"      # the chip the dry-run meshes model


def peaks_for(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip; a kind with no published entry is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

SHAPES_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,
    "long_500k": 1,
}


def model_flops(rec: Dict) -> float:
    toks = SHAPES_TOKENS[rec["shape"]]
    n = rec["active_params"]
    if rec["shape"] == "train_4k":
        mult = 6 * rec.get("fed", {}).get("local_steps", 1)
    else:
        mult = 2
    return float(mult * n * toks)


def analyze(rec: Dict, chips: int, device_kind: str) -> Optional[Dict]:
    peaks = peaks_for(device_kind)
    if "cost" not in rec or "collectives" not in rec:
        return None
    # prefer the trip-count-aware estimates (XLA cost_analysis counts while
    # bodies once; scanned stacks undercount by ~n_layers)
    flops_dev = rec["cost"].get("flops_trip_aware") or \
        rec["cost"].get("flops", 0.0)
    bytes_dev = rec["cost"].get("bytes_trip_aware") or \
        rec["cost"].get("bytes accessed", 0.0)
    coll_dev = rec["collectives"].get("total", 0.0)
    t_c = flops_dev / peaks["bf16_flops"]
    t_m = bytes_dev / peaks["hbm_bytes_per_s"]
    t_n = coll_dev / peaks["ici_link_bytes_per_s"]
    terms = {"compute": t_c, "memory": t_m, "collective": t_n}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec)
    ratio = mf / max(flops_dev * chips, 1.0)
    bound = max(terms.values())
    mfu_bound = ((mf / chips / peaks["bf16_flops"]) / bound if bound > 0
                 else 0.0)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "step": rec.get("step"),
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_n,
        "dominant": dominant,
        "model_flops": mf, "hlo_flops_per_dev": flops_dev,
        "useful_ratio": ratio,
        "mfu_upper_bound": mfu_bound,
        "temp_bytes": rec.get("memory", {}).get("temp_size_in_bytes"),
    }


def advice(row: Dict) -> str:
    d = row["dominant"]
    if d == "collective":
        return ("shrink exchanged bytes (lower p_q, sparsify on the wire) or "
                "switch schedule gather->reduce-scatter")
    if d == "memory":
        return ("cut activation/logit footprint (bf16 logits, chunked vocab "
                "loss, tighter remat policy)")
    return ("raise arithmetic intensity (larger per-device batch, fuse "
            "elementwise chains, avoid recompute)")


def load(paths: List[str]) -> List[Dict]:
    out = []
    for p in paths:
        if os.path.exists(p):
            with open(p) as f:
                out.extend(json.load(f))
    return out


def to_markdown(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | 6ND/HLO | MFU bound |\n|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.2e} | {r['memory_s']:.2e} "
            f"| {r['collective_s']:.2e} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} | {r['mfu_upper_bound']*100:.1f}% |")
    return hdr + "\n".join(lines) + "\n"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--inputs", nargs="*", default=[
        "results/dryrun_single.json"])
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()

    rows = []
    for rec in load(args.inputs):
        if "error" in rec:
            continue
        chips = 512 if rec["mesh"] == "2x16x16" else 256
        row = analyze(rec, chips, DRYRUN_DEVICE_KIND)
        if row:
            row["advice"] = advice(row)
            rows.append(row)
    rows.sort(key=lambda r: (r["mesh"], r["arch"], r["shape"]))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    if args.markdown:
        print(to_markdown(rows))
    else:
        for r in rows:
            print(f"roofline/{r['arch']}_{r['shape']}_{r['mesh']},"
                  f"{max(r['compute_s'], r['memory_s'], r['collective_s'])*1e6:.1f},"
                  f"dom={r['dominant']}")


if __name__ == "__main__":
    main()
