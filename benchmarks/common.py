"""Shared benchmark infrastructure.

Every benchmark module maps to one paper table/figure, runs the protocol
simulator on the synthetic Fashion-MNIST-like dataset, and prints CSV rows
``name,us_per_call,derived`` where ``us_per_call`` is wall microseconds per
simulated aggregation round and ``derived`` carries the figure's headline
quantity (accuracy / time-to-target / bytes).

Scale: ``--full`` reproduces the paper's setting (100 devices, 60k samples);
the default quick scale (40 devices, 12k samples) preserves every relative
comparison at ~10x less wall time.  Results also land in
results/paper_bench.json for EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import contextlib
import cProfile
import functools
import io
import json
import os
import pstats
import sys
import time
from typing import Dict, List, Optional

from repro.core.dynamic import make_schedule
from repro.fl.protocols import (best_acc_within, make_setup,
                                profile_compression, run_method, time_to_acc,
                                train_global)

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                            "paper_bench.json")

# the standard Linux locations of gperftools' malloc (the olmax/HomebrewNLP
# JAX training scripts LD_PRELOAD it for large-N host workloads); absent
# libraries are skipped, the tuning degrades gracefully
TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)
_HOST_TUNED_MARKER = "_REPRO_HOST_TUNED"


def maybe_reexec_host_tuned(enable: bool, host_devices: int = 0) -> bool:
    """Re-exec the current process with olmax-style host tuning applied:
    ``LD_PRELOAD`` tcmalloc (a loader setting — it cannot be enabled from
    inside a running process, hence the ``os.execve``) and, when
    ``host_devices > 0``, ``XLA_FLAGS=--xla_force_host_platform_device_count``
    so XLA partitions the host CPU into that many logical devices.

    Call this first thing in a benchmark ``main()``: it raises if a JAX
    backend is already initialized (the process would re-exec while holding
    the accelerator, and the flag could no longer take effect), and the
    re-exec'd process refuses ``host_devices > 0`` when its backend is a
    TPU, where host devices have no meaning.  Returns ``False`` when tuning
    is disabled or already applied (the re-exec'd process carries the
    ``_REPRO_HOST_TUNED`` marker, which both prevents an exec loop and tells
    the benchmark the run is host-tuned); on success the call does not
    return at all."""
    if os.environ.get(_HOST_TUNED_MARKER):
        if host_devices > 0:
            import jax
            if jax.default_backend() == "tpu":
                raise ValueError("--host-devices partitions the host CPU; "
                                 "it has no meaning on a TPU backend")
        return False
    if not enable:
        return False
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("maybe_reexec_host_tuned must run before any JAX "
                           "backend is initialized")
    env = dict(os.environ, **{_HOST_TUNED_MARKER: "1"})
    for path in TCMALLOC_PATHS:
        if os.path.exists(path):
            env["LD_PRELOAD"] = path
            # silence tcmalloc's large-alloc warnings for big numpy buffers
            env["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = "60000000000"
            break
    if host_devices > 0:
        flag = f"--xla_force_host_platform_device_count={host_devices}"
        env["XLA_FLAGS"] = " ".join(
            x for x in (flag, os.environ.get("XLA_FLAGS", "")) if x)
    # sys.orig_argv keeps the real command line (incl. `-m benchmarks.x`)
    argv = list(getattr(sys, "orig_argv", None)
                or [sys.executable] + sys.argv)
    os.execve(sys.executable, argv, env)
    return True   # unreachable; keeps the signature honest for linters


def host_tuning_active() -> bool:
    """True inside a process re-exec'd by :func:`maybe_reexec_host_tuned`."""
    return bool(os.environ.get(_HOST_TUNED_MARKER))


@contextlib.contextmanager
def profiled(enable: bool, out_path: str, top: int = 20):
    """cProfile the with-block when ``enable`` is set and dump the top-
    ``top`` cumulative rows (plus the same slice re-sorted by total self
    time) as a pstats text report at ``out_path`` — benchmarks pass a path
    next to their results JSON so the profile that explains a recorded
    number travels with it.  Disabled, the context is free, so call sites
    can wrap their timed region unconditionally.  Note the profiled region
    itself runs ~1.3-2x slower under cProfile's tracing; profile runs are
    for attribution, not for the recorded ms_per_task."""
    if not enable:
        yield None
        return
    prof = cProfile.Profile()
    prof.enable()
    try:
        yield prof
    finally:
        prof.disable()
        buf = io.StringIO()
        stats = pstats.Stats(prof, stream=buf)
        stats.sort_stats("cumulative").print_stats(top)
        stats.sort_stats("tottime").print_stats(top)
        os.makedirs(os.path.dirname(os.path.abspath(out_path)),
                    exist_ok=True)
        with open(out_path, "w") as f:
            f.write(buf.getvalue())
        print(f"profile: top-{top} rows (cumulative + tottime) -> "
              f"{out_path}", flush=True)


class Scale:
    def __init__(self, full: bool = False, backend: str = "engine",
                 cohort_size: int = 0):
        self.full = full
        # which simulator runs the protocol: the strategy-based engine
        # (default) or the legacy monolithic FLSimulator; cohort_size > 0
        # additionally switches the engine to vectorized cohort training
        self.backend = backend
        self.cohort_size = cohort_size
        # keep the paper's N=100 devices even at quick scale — the
        # C-fraction/cache dynamics (10 parallel, K=10) depend on it;
        # quick mode shrinks per-device data instead (120 samples/device)
        self.n_devices = 100
        self.n_train = 60000 if full else 12000
        self.n_test = 10000 if full else 2500
        self.budget = 300.0 if full else 45.0
        # non-IID learning is ~2x slower (paper: 600s vs 300s budgets)
        self.budget_noniid = 600.0 if full else 90.0
        self.eval_every = 2 if full else 6
        self.epochs = 2 if full else 3

    def budget_for(self, iid: bool) -> float:
        return self.budget if iid else self.budget_noniid


@functools.lru_cache(maxsize=4)
def cached_setup(n_devices: int, iid: bool, n_train: int, n_test: int,
                 seed: int = 0):
    return make_setup(n_devices=n_devices, iid=iid, seed=seed,
                      n_train=n_train, n_test=n_test)


def simulate(scale: Scale, method: str, iid: bool = True, seed: int = 0,
             **kw) -> Dict:
    data, parts, w0 = cached_setup(scale.n_devices, iid, scale.n_train,
                                   scale.n_test, seed)
    t0 = time.time()
    hist = run_method(method, data, parts, w0, iid=iid,
                      time_budget=kw.pop("time_budget", scale.budget_for(iid)),
                      eval_every=kw.pop("eval_every", scale.eval_every),
                      epochs=kw.pop("epochs", scale.epochs), seed=seed,
                      backend=scale.backend,
                      cohort_size=kw.pop("cohort_size", scale.cohort_size),
                      **kw)
    wall = time.time() - t0
    rounds = max(hist[-1].round, 1)
    return {
        "method": method, "iid": iid, "kw": {k: str(v) for k, v in kw.items()},
        "wall_s": wall, "rounds": rounds,
        "us_per_round": wall / rounds * 1e6,
        "history": [[h.time, h.round, h.accuracy, h.bytes_up, h.bytes_down,
                     h.max_model_bytes_up, h.max_model_bytes_down]
                    for h in hist],
    }


_POINTS_CACHE = {}


def compression_points(scale: Scale, iid: bool = True, theta: float = 0.02,
                       total_rounds: int = 60):
    """Algorithm 5 end-to-end: brief training -> greedy search -> decay
    schedule.  Returns {"static": (p_s, p_q), "schedule": ...} — the static
    point is what TEAStatic/TEAS/TEAQ use (the paper derives them the same
    way)."""
    key = (scale.full, iid, theta)
    if key in _POINTS_CACHE:
        return _POINTS_CACHE[key]
    from repro.core.dynamic import DEFAULT_SET_Q, DEFAULT_SET_S
    data, parts, w0 = cached_setup(scale.n_devices, iid, scale.n_train,
                                   scale.n_test)
    # profile on a briefly-TRAINED model (Alg. 5 uses a trained model;
    # a random init is insensitive to compression and the greedy search
    # would pick maximum compression)
    w_warm = train_global(data, parts, w0, time_budget=35.0, epochs=3)
    si, qi, trace = profile_compression(w_warm, data, theta=theta)
    out = {"static": (DEFAULT_SET_S[si], DEFAULT_SET_Q[qi]),
           "schedule": make_schedule(si, qi, total_rounds=total_rounds),
           "trace_len": len(trace)}
    _POINTS_CACHE[key] = out
    return out


def teasq_schedule(scale: Scale, iid: bool = True, theta: float = 0.02,
                   total_rounds: int = 60):
    return compression_points(scale, iid, theta, total_rounds)["schedule"]


def record(table: str, rows: List[Dict]) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(RESULTS_PATH)), exist_ok=True)
    db = {}
    if os.path.exists(RESULTS_PATH):
        with open(RESULTS_PATH) as f:
            db = json.load(f)
    db[table] = rows
    with open(RESULTS_PATH, "w") as f:
        json.dump(db, f, indent=1)


def print_csv(table: str, rows: List[Dict], derived_key: str = "final_acc"):
    for r in rows:
        name = f"{table}/{r['method']}" + ("_iid" if r["iid"] else "_noniid")
        extra = "_".join(f"{k}{v}" for k, v in r.get("kw", {}).items()
                         if k in ("c_fraction", "mu", "alpha", "p_s", "p_q"))
        if extra:
            name += "_" + extra
        acc = r["history"][-1][2]
        print(f"{name},{r['us_per_round']:.1f},{acc:.4f}")


def std_argparser(desc: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=desc)
    ap.add_argument("--full", action="store_true",
                    help="paper scale (100 devices, 60k samples, 300s)")
    ap.add_argument("--backend", choices=("engine", "legacy"),
                    default="engine",
                    help="protocol runner: strategy engine or legacy sim")
    ap.add_argument("--cohort", type=int, default=0,
                    help="engine cohort size (>0 = vectorized training)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile the benchmark region and dump the top-20"
                         " cumulative rows next to the results JSON")
    return ap


def scale_from_args(args) -> Scale:
    return Scale(args.full, backend=getattr(args, "backend", "engine"),
                 cohort_size=getattr(args, "cohort", 0))
