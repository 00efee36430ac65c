"""Codec throughput: encode/decode MB/s + bytes-on-wire per registered codec.

Runs every codec in ``repro.core.codecs.CODECS`` on the FMNIST CNN pytree
(the paper's model) across the compression grid p_s x p_q, measuring wall
encode/decode throughput against the dense f32 payload size and the metered
wire bytes (for ``PackedBitstreamCodec`` this is ``len()`` of the actual
byte string; the packed codec must price identically to the analytic
``expected_pytree_wire_bytes``).

On top of the registry codecs, two explicit packed-codec variants pin the
fused-emitter speedup (the ISSUE-8 tentpole):

* ``packed_fused``  — ``PackedBitstreamCodec(fused=True)``, deterministic
  rounding: the one-pass fused emitter (``repro.kernels.fused_pack``);
* ``packed_host``   — ``fused=False``, deterministic rounding: the
  multi-pass ``compress_tensor`` -> ``pack_segments`` oracle pipeline.

(The plain ``packed`` row keeps stochastic-QSGD encode with the shared RNG
— the engines' configuration — so its numbers stay comparable across
revisions.)  Each ``packed_fused`` measurement also asserts the fused byte
stream is bit-identical to the oracle's and that ``len(bytes)`` equals the
analytic price, so the benchmark cannot report a fast-but-wrong emitter.

Results MERGE into results/codec_throughput.json keyed by
``(codec, p_s, p_q)`` — same idea as ``_merge_results`` in
``benchmarks.engine_scale`` — so a partial re-run (one codec, one grid
point) does not clobber the rest of the table.

  PYTHONPATH=src python -m benchmarks.codec_throughput [--reps 3]
      [--host-tuning] [--host-devices N]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from benchmarks.common import host_tuning_active, maybe_reexec_host_tuned
from repro.core.codecs import (CODECS, Codec, PackedBitstreamCodec,
                               resolve_codec)
from repro.core.compression import (expected_pytree_wire_bytes,
                                    pytree_dense_bytes)
from repro.models.cnn import init_cnn
from repro.launch.cache import enable_compile_cache

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                            "codec_throughput.json")
GRID_PS = (0.1, 0.25, 0.5)
GRID_PQ = (2, 4, 8)

# non-registry benchmark variants: name -> (codec factory, stochastic rng?)
VARIANTS: Dict[str, Callable[[float, int], Codec]] = {
    "packed_fused": lambda p_s, p_q: PackedBitstreamCodec(p_s, p_q, fused=True),
    "packed_host": lambda p_s, p_q: PackedBitstreamCodec(p_s, p_q, fused=False),
}


def _sync(tree: Any) -> Any:
    """Force any pending device computation (threshold codec is lazy jnp)."""
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return tree


def bench_codec(name: str, tree: Any, p_s: float, p_q: int,
                reps: int = 3) -> Dict[str, Any]:
    if name in VARIANTS:
        codec = VARIANTS[name](p_s, p_q)
        rng = None             # deterministic: exercises the fused seam
    else:
        codec = resolve_codec(name, p_s, p_q)
        rng = np.random.RandomState(0)
    dense_mb = pytree_dense_bytes(tree) / 1e6

    wire = codec.encode(tree, rng=rng)     # warmup (jit compiles)
    _sync(codec.decode(wire))
    # identity/threshold decode just returns the (already materialized)
    # payload — timing that no-op would report timer-resolution "MB/s"
    passthrough = codec.decode(wire) is wire.payload

    row: Dict[str, Any] = {
        "codec": name, "resolved": codec.name, "p_s": p_s, "p_q": p_q}
    if name == "packed_fused":
        # a fast emitter only counts if it is the SAME stream: bit-identical
        # to the multi-pass oracle, length == the analytic price
        oracle = VARIANTS["packed_host"](p_s, p_q).encode(tree)
        assert wire.payload == oracle.payload, (p_s, p_q)
        assert len(wire.payload) == expected_pytree_wire_bytes(tree, p_s, p_q)
        row["bit_identical_to_host"] = True

    enc_s, dec_s = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        wire = codec.encode(tree, rng=rng)
        _sync(wire.payload)
        enc_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _sync(codec.decode(wire))
        dec_s.append(time.perf_counter() - t0)

    row.update({
        "wire_bytes": wire.nbytes,
        "expected_bytes": expected_pytree_wire_bytes(tree, codec.p_s,
                                                     codec.p_q),
        "dense_bytes": pytree_dense_bytes(tree),
        "compression_x": round(pytree_dense_bytes(tree) / wire.nbytes, 2),
        "encode_mbps": round(dense_mb / min(enc_s), 2),
        "decode_mbps": (None if passthrough
                        else round(dense_mb / min(dec_s), 2)),
        "host_tuned": host_tuning_active(),
    })
    return row


def _merge_rows(path: str, rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Merge new rows into the existing results file keyed by
    ``(codec, p_s, p_q)`` — the list-of-rows analogue of
    ``benchmarks.engine_scale._merge_results`` — so partial re-runs update
    their grid points in place instead of clobbering the whole table."""
    merged: Dict[tuple, Dict[str, Any]] = {}
    if os.path.exists(path):
        with open(path) as f:
            for r in json.load(f):
                merged[(r["codec"], r["p_s"], r["p_q"])] = r
    for r in rows:
        merged[(r["codec"], r["p_s"], r["p_q"])] = r
    return [merged[k] for k in sorted(merged)]


def run(reps: int = 3, grid_ps: Sequence[float] = GRID_PS,
        grid_pq: Sequence[int] = GRID_PQ,
        codecs: Optional[Sequence[str]] = None,
        out_path: Optional[str] = RESULTS_PATH) -> List[Dict[str, Any]]:
    tree = init_cnn(jax.random.PRNGKey(0))
    rows = []
    names = (codecs if codecs is not None
             else sorted(CODECS) + sorted(VARIANTS))
    for name in names:
        for p_s in grid_ps:
            for p_q in grid_pq:
                row = bench_codec(name, tree, p_s, p_q, reps=reps)
                rows.append(row)
                dec = (f"{row['decode_mbps']:8.1f}MB/s"
                       if row['decode_mbps'] is not None else "     n/a")
                print(f"[{row['codec']:12s}] p_s={p_s:4.2f} p_q={p_q:2d} "
                      f"wire={row['wire_bytes']:8d}B "
                      f"({row['compression_x']:5.1f}x) "
                      f"enc={row['encode_mbps']:8.1f}MB/s "
                      f"dec={dec}", flush=True)
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        merged = _merge_rows(out_path, rows)
        with open(out_path, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"[codec_throughput] {len(rows)} rows "
              f"({len(merged)} total) -> {out_path}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=RESULTS_PATH)
    ap.add_argument("--host-tuning", action="store_true",
                    help="re-exec with tcmalloc LD_PRELOAD (same setup as "
                         "the engine bench; see benchmarks.common)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="with --host-tuning: partition the host CPU into N "
                         "logical XLA devices")
    args = ap.parse_args()
    maybe_reexec_host_tuned(args.host_tuning, args.host_devices)
    enable_compile_cache()
    run(reps=args.reps, out_path=args.out)


if __name__ == "__main__":
    main()
