"""§Perf hillclimb driver: lower named variants of the three chosen
(arch x shape) pairs and record their roofline terms.

Pairs (chosen from the baseline roofline table):
  A. jamba-v0.1-52b  x train_4k   — worst roofline fraction AND most
     collective-bound (fed exchange of 52B MoE params).
  B. granite-34b     x decode_32k — memory-bound serving (MQA kv=1: KV cache
     unshardable over heads).
  C. qwen3-1.7b      x train_4k   — most representative of the paper's
     technique (compressed model exchange on an FL-plausible model size).

Usage:  PYTHONPATH=src python -m benchmarks.hillclimb --pair C
Results append to results/perf/hillclimb.json.
"""
import argparse
import json
import os
import sys

from repro.launch.cache import enable_compile_cache

VARIANTS = {
    # pair C (and A): fed-exchange schedule ladder, + memory lever
    "C": [
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="gather_f32"),
         "tea_fed_f32_gather (paper TEA-Fed baseline, no compression)"),
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="gather_q", p_q=8),
         "teasq_int8_gather (paper-faithful TEASQ wire)"),
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="gather_q", p_q=4),
         "beyond: int4 wire (s4 gather, 8x vs f32)"),
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="psum"),
         "beyond: weighted reduce (ring all-reduce) instead of gather"),
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="gather_q", p_q=8,
                                        loss_chunk=256),
         "beyond: + chunked-vocab loss (memory term)"),
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="gather_q", p_q=8,
                                        group_parallelism="dp"),
         "beyond: group-internal DP instead of TP (model fits per chip)"),
        ("qwen3_1_7b", "train_4k", dict(fed_schedule="gather_q", p_q=8,
                                        group_parallelism="dp",
                                        loss_chunk=256),
         "beyond: group-DP + chunked loss (final config)"),
    ],
    "A": [
        ("jamba_v0_1_52b", "train_4k", dict(fed_schedule="gather_f32"),
         "tea_fed_f32_gather"),
        ("jamba_v0_1_52b", "train_4k", dict(fed_schedule="gather_q", p_q=8),
         "teasq_int8_gather"),
        ("jamba_v0_1_52b", "train_4k", dict(fed_schedule="psum"),
         "beyond: weighted reduce"),
        ("jamba_v0_1_52b", "train_4k", dict(fed_schedule="psum",
                                            loss_chunk=256),
         "beyond: psum + chunked loss"),
    ],
    "B": [
        ("granite_34b", "decode_32k", dict(), "baseline bf16 full KV"),
        ("granite_34b", "decode_32k", dict(kv_quant=True),
         "paper-themed: int8-quantized KV cache"),
        ("granite_34b", "decode_32k", dict(seq_shard_kv=True),
         "beyond: sequence-sharded KV + flash-merge psum"),
        ("granite_34b", "decode_32k", dict(seq_shard_kv=True, kv_quant=False),
         "(dup guard)"),
    ],
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=True, choices=["A", "B", "C"])
    ap.add_argument("--out", default="results/perf/hillclimb.json")
    args = ap.parse_args()
    enable_compile_cache()

    from repro.launch.dryrun import run_one  # sets XLA_FLAGS on import

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    results = []
    if os.path.exists(args.out):
        results = json.load(open(args.out))
    seen = {(r["arch"], r["shape"], r.get("variant")) for r in results}

    for arch, shape, kw, label in VARIANTS[args.pair]:
        if label == "(dup guard)":
            continue
        key = (arch, shape, label)
        if key in seen:
            print(f"[hillclimb] skip {label} (done)")
            continue
        rec = run_one(arch, shape, variant=label, **kw)
        results.append(rec)
        json.dump(results, open(args.out, "w"), indent=1)
        c = rec.get("collectives", {})
        cost = rec.get("cost", {})
        print(f"[hillclimb {args.pair}] {label}\n"
              f"    flops(trip)={cost.get('flops_trip_aware', 0):.3e} "
              f"bytes(trip)={cost.get('bytes_trip_aware', 0):.3e} "
              f"coll={c.get('total', 0):.3e}B "
              f"temp={rec.get('memory', {}).get('temp_size_in_bytes', 0)/1e9:.1f}GB")


if __name__ == "__main__":
    main()
