"""Serving throughput: continuous batching vs. serial one-at-a-time decode.

Drives the same request workload (``requests`` random prompts, ``gen``
greedy tokens each) through the FL->serve front door twice on the tiny
FL transformer LM (``repro.fl.tasks`` ``transformer_lm``):

* ``serial``     — batch=1 ``repro.launch.serve.generate`` per request,
  back to back: the no-batching baseline a naive server would run.  Note
  ``generate``'s loop samples host-side every step, so the gap measures
  the whole serving stack (batching + the batcher's sync-free device
  loop), not batching alone;
* ``continuous`` — one ``ContinuousBatcher`` with ``batch`` decode
  slots, admitting queued requests into free slots every step.

Both paths produce identical greedy tokens (tests/test_serve.py pins
that), so the comparison is pure scheduling: tokens/s plus p50/p99
per-request completion latency (submit-at-t0 to last token).  The
continuous row records ``speedup_x`` over the serial baseline; the
ROADMAP target is >= 1.5x at batch >= 4.

Results MERGE into results/serve_bench.json keyed by
``(mode, batch, requests, prompt_len, gen)`` so re-runs at one batch
size update their row in place.

  PYTHONPATH=src python -m benchmarks.serve_bench [--batch 4]
      [--requests 8] [--gen 16]
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl.tasks import get_task
from repro.launch.cache import enable_compile_cache
from repro.launch.serve import ContinuousBatcher, generate

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                            "serve_bench.json")


def _percentiles(lat_s: List[float]) -> Dict[str, float]:
    ms = np.asarray(lat_s) * 1e3
    return {"p50_ms": round(float(np.percentile(ms, 50)), 2),
            "p99_ms": round(float(np.percentile(ms, 99)), 2)}


def _bench_serial(params, cfg, prompts: List[np.ndarray], gen: int
                  ) -> Dict[str, Any]:
    """One request at a time, batch=1 ``generate`` — every request's
    latency includes all the requests queued ahead of it."""
    # warmup: compile prefill + decode step outside the timed region
    generate(params, cfg, jnp.asarray(prompts[0][None]), gen)
    t0 = time.perf_counter()
    lat = []
    for p in prompts:
        generate(params, cfg, jnp.asarray(p[None]), gen)
        lat.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t0
    return {"seconds": dt, "tokens_per_s": len(prompts) * gen / dt,
            **_percentiles(lat)}


def _bench_continuous(params, cfg, prompts: List[np.ndarray], gen: int,
                      batch: int, cache_len: int) -> Dict[str, Any]:
    # warmup batcher of the same geometry: compile prefill, slot insert
    # and the batched decode step outside the timed region
    warm = ContinuousBatcher(params, cfg, slots=batch, cache_len=cache_len)
    warm.run(prompts[:batch], min(gen, 2))
    cb = ContinuousBatcher(params, cfg, slots=batch, cache_len=cache_len)
    t0 = time.perf_counter()
    outs, lat = cb.run(prompts, gen)
    dt = time.perf_counter() - t0
    toks = sum(len(o) for o in outs)
    return {"seconds": dt, "tokens_per_s": toks / dt,
            "decode_steps": cb.steps, **_percentiles(lat)}


def _merge_rows(path: str, rows: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """Keyed row merge (same idea as ``benchmarks.codec_throughput``):
    partial re-runs update their rows in place."""
    key = lambda r: (r["mode"], r["batch"], r["requests"],
                     r["prompt_len"], r["gen"])
    merged: Dict[tuple, Dict[str, Any]] = {}
    if os.path.exists(path):
        with open(path) as f:
            for r in json.load(f):
                merged[key(r)] = r
    for r in rows:
        merged[key(r)] = r
    return [merged[k] for k in sorted(merged)]


def run(batch: int = 4, requests: int = 16, prompt_len: int = 8,
        gen: int = 32, task: str = "transformer_lm", seed: int = 0,
        out_path: Optional[str] = RESULTS_PATH) -> List[Dict[str, Any]]:
    t = get_task(task)
    cfg = t.model_cfg
    assert cfg is not None, f"task {task!r} has no ModelConfig to serve"
    params = t.init_params(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab, prompt_len).astype(np.int32)
               for _ in range(requests)]
    base = {"task": task, "model": cfg.name, "batch": batch,
            "requests": requests, "prompt_len": prompt_len, "gen": gen}

    serial = _bench_serial(params, cfg, prompts, gen)
    cont = _bench_continuous(params, cfg, prompts, gen, batch,
                             prompt_len + gen)
    speedup = cont["tokens_per_s"] / serial["tokens_per_s"]
    rows = [
        {**base, "mode": "serial", "batch": 1,
         **{k: round(v, 2) if isinstance(v, float) else v
            for k, v in serial.items()}},
        {**base, "mode": "continuous",
         **{k: round(v, 2) if isinstance(v, float) else v
            for k, v in cont.items()},
         "speedup_x": round(speedup, 2)},
    ]
    for r in rows:
        print(f"[{r['mode']:10s}] batch={r['batch']} requests={requests} "
              f"gen={gen} {r['tokens_per_s']:8.1f} tok/s "
              f"p50={r['p50_ms']:7.1f}ms p99={r['p99_ms']:7.1f}ms",
              flush=True)
    print(f"[serve_bench] continuous speedup over serial: {speedup:.2f}x")
    if out_path:
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        merged = _merge_rows(out_path, rows)
        with open(out_path, "w") as f:
            json.dump(merged, f, indent=1)
        print(f"[serve_bench] {len(rows)} rows ({len(merged)} total) "
              f"-> {out_path}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--task", default="transformer_lm")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=RESULTS_PATH)
    args = ap.parse_args()
    enable_compile_cache()
    run(batch=args.batch, requests=args.requests, prompt_len=args.prompt_len,
        gen=args.gen, task=args.task, seed=args.seed, out_path=args.out)


if __name__ == "__main__":
    main()
