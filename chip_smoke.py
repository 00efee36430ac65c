"""Smoke run of the system's main paths on a TPU, through the entry points
a user calls.  It proves the program runs and computes the right thing on
the chip; its wall-clock prints are not speed measurements.

    python chip_smoke.py              # one chip: fl, codec, serve
    python chip_smoke.py --chips 4    # four chips: sharded aggregation only

Phases (each prints its own checks; any failed check exits non-zero):

* ``fl`` — TEASQ-Fed on the paper's §5.1 CNN at its published size:
  ``make_setup`` + ``run_method`` with 100 devices, 60,000 / 10,000
  synthetic samples, the engine's cohort trainer and the ``single``
  server.  Checks every aggregated model is finite, one aggregation
  against a float64 numpy Eqs. 6-10 fold of the same cache, one
  ``_cohort_round`` on the TPU against the same call on the host CPU, and
  that training beats chance and round 0.
* ``codec`` — the packed wire encode of the CNN's update at the paper's
  (p_s, p_q) = (0.1, 8) through ``fused_wire_encode`` on the native
  Pallas kernel: byte-identical to the numpy twin, exact expected length.
* ``serve`` — ``qwen3-1.7b`` at its published width (random f32 weights
  from the seed) answers 4 requests (prompt 32, gen 16) through
  ``generate`` and ``ContinuousBatcher``; the greedy tokens must agree,
  and the prefill logits must match a full forward pass.
* ``sharded`` (``--chips 4`` only) — TEASQ with ``server="sharded"``,
  ``server_shards=4``, each sharded aggregation against the single-chip
  aggregation of the same cache.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  Without a TPU the script
exits non-zero before running any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

# fl: the paper's §5.1 setting; cohort = ceil(N * C) at C = 0.1
N_DEVICES, N_TRAIN, N_TEST, COHORT = 100, 60000, 10000, 10
FL_POINT = (0.25, 8)          # TEASQ (p_s, p_q) of the training run
# virtual seconds: ~40 aggregations.  On this synthetic task the compressed
# run's accuracy peaks near there (0.127 after 40 aggregations in a CPU
# rehearsal at this seed) and drifts back towards chance by 300 s
FL_BUDGET = 150.0
FL_EVAL_EVERY = 10            # evaluate every 10th aggregation
MIN_AGGREGATIONS = 5
CHANCE = 0.1                  # 10 classes
CODEC_POINT = (0.1, 8)        # the paper's headline compression point
SERVE_ARCH, SERVE_REQUESTS, PROMPT, GEN = "qwen3-1.7b", 4, 32, 16

# Eqs. 6-10 in f32 on the chip vs a float64 numpy fold: each element is a
# sum of K = 10 same-signed weighted terms plus one merge (a few f32
# roundings, ~1e-7 each) and two pow() calls on the TPU's f32
# transcendental units (~1e-6 relative); 1e-5 of the model's L2 norm
# leaves that headroom, while a wrong weight or a dropped cache entry moves
# the result by the spread between the devices' models (~1e-3 and up).
AGG_RTOL = 1e-5
# _cohort_round, TPU (default f32 matmul precision: bf16 passes) vs the
# host CPU at "highest": the gradients differ by ~2^-8 relative, so after
# 30 SGD steps a few weights near a quantization boundary move one level
# (1/127 of their leaf's max-abs) and a few near the Top-K threshold flip
# in or out.  Both are small in L2 against the whole cohort's weights;
# 2e-2 bounds them and still catches a wrong leaf, layout or codec point
# (O(1e-1) and up).  It cannot see one lost SGD step: a whole 30-step round
# moves the model by only ~5e-3 (printed beside the check).
COHORT_RTOL = 2e-2
# prefill logits vs a full forward pass: two programs of the same math
# at the TPU's default matmul precision (bf16 passes, f32 accumulation),
# which differ only in how XLA fuses and orders the f32 accumulation
LOGITS_RTOL = 1e-2
# sharded (4 chips) vs single-chip stacked aggregation of the same cache:
# the same per-element program on every shard (tensordot over K, then the
# Eq. 10 merge), so only XLA's grouping of the fused multiply-adds may
# differ — a few ulp of f32
SHARDED_RTOL = 1e-6


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_l2(got, ref) -> float:
    """||got - ref|| / ||ref|| over every leaf of two pytrees, in float64."""
    import jax
    num = den = 0.0
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        g = np.asarray(g, np.float64)
        r = np.asarray(r, np.float64)
        num += float(np.sum((g - r) ** 2))
        den += float(np.sum(r ** 2))
    return float(np.sqrt(num / den))


def eqs_6_10_f64(w_global, cache, t: int, alpha: float, a: float):
    """Plain numpy float64 Eqs. 6-10 over ``(w_c, h_c, n_c)`` entries."""
    import jax
    st = np.asarray([t - h for _, h, _ in cache], np.float64)
    n = np.asarray([n_c for _, _, n_c in cache], np.float64)
    wts = (st + 1.0) ** (-a) * n                  # Eqs. 6-7
    wts = wts / wts.sum()
    a_t = alpha * (st.mean() + 1.0) ** (-a)       # Eqs. 8-9

    def fold(wg, *locals_):
        u = sum(c * np.asarray(l, np.float64) for c, l in zip(wts, locals_))
        return a_t * u + (1.0 - a_t) * np.asarray(wg, np.float64)  # Eq. 10

    return jax.tree.map(fold, w_global, *(c[0] for c in cache))


@contextlib.contextmanager
def recorded_aggregations(server_cls):
    """Watch every Eqs. 6-10 aggregation a ``server_cls`` server runs:
    count them, check each new global model is finite, and keep the inputs
    and output of the latest one."""
    import jax
    log = {"count": 0, "all_finite": True, "last": None}
    inner = server_cls._aggregate

    def watched(self):
        w_new = inner(self)
        log["count"] += 1
        log["all_finite"] &= all(bool(np.isfinite(np.asarray(l)).all())
                                 for l in jax.tree.leaves(w_new))
        log["last"] = (self.w, list(self.cache), self.t, self.cfg, w_new)
        return w_new

    server_cls._aggregate = watched
    try:
        yield log
    finally:
        server_cls._aggregate = inner


def _cohort_args(w, parts, data, seed: int):
    """Arguments of one engine-shaped ``_cohort_round`` call: one model
    version, the first ``COHORT`` devices, their prox-SGD minibatches."""
    import jax
    import jax.numpy as jnp
    from repro.fl.simulator import SimConfig
    cfg = SimConfig()
    n_max = max(len(p) for p in parts)
    x = data["x_train"]
    xs = np.zeros((len(parts), n_max) + x.shape[1:], x.dtype)
    ys = np.zeros((len(parts), n_max), np.int32)
    for k, idx in enumerate(parts):
        xs[k, :len(idx)] = x[idx]
        ys[k, :len(idx)] = data["y_train"][idx]
    rng = np.random.RandomState(seed)
    bs = cfg.batch_size
    rows = []
    for k in range(COHORT):
        n_k = len(parts[k])
        per = []
        for _ in range(cfg.epochs):
            order = rng.permutation(n_k)
            per += [order[s * bs:(s + 1) * bs] for s in range(n_k // bs)]
        rows.append(per)
    steps = len(rows[0])
    t_pad = 1 << (steps - 1).bit_length()       # the engine's pow2 bucket
    bidx = np.zeros((t_pad, COHORT, bs), np.int32)
    valid = np.zeros((t_pad, COHORT), np.float32)
    bidx[:steps] = np.swapaxes(np.asarray(rows, np.int32), 0, 1)
    valid[:steps] = 1.0
    w_versions = jax.tree.map(lambda a: jnp.asarray(a)[None], w)
    args = (w_versions, jnp.zeros(COHORT, jnp.int32), jnp.asarray(xs),
            jnp.asarray(ys), jnp.arange(COHORT, dtype=jnp.int32),
            jnp.asarray(bidx), jnp.asarray(valid))
    kw = dict(lr=cfg.lr, mu=cfg.mu, p_s=FL_POINT[0], p_q=FL_POINT[1],
              iters=cfg.cohort_channel_iters)
    return args, kw, steps


def phase_fl(seed: int):
    import jax
    from repro.core.server import TeasqServer
    from repro.fl.engine import _cohort_round
    from repro.fl.protocols import make_setup, run_method
    from repro.fl.tasks import get_task

    data, parts, w0 = make_setup(n_devices=N_DEVICES, iid=True, seed=seed,
                                 n_train=N_TRAIN, n_test=N_TEST,
                                 task="fmnist_cnn")
    n_params = sum(int(np.size(l)) for l in jax.tree.leaves(w0))
    print(f"[fl] fmnist_cnn {n_params} params, {N_DEVICES} devices, "
          f"{N_TRAIN}/{N_TEST} samples, cohort {COHORT}", flush=True)
    t0 = time.time()
    with recorded_aggregations(TeasqServer) as agg:
        hist = run_method("teasq", data, parts, w0, iid=True,
                          time_budget=FL_BUDGET, seed=seed,
                          p_s=FL_POINT[0], p_q=FL_POINT[1],
                          eval_every=FL_EVAL_EVERY, backend="engine",
                          cohort_size=COHORT, server="single")
    wall = time.time() - t0
    acc0, acc = hist[0].accuracy, hist[-1].accuracy
    print(f"[fl] {agg['count']} aggregations in {FL_BUDGET:g} virtual s "
          f"({wall:.1f} wall s, compiles included); accuracy "
          f"{acc0:.4f} -> {acc:.4f}", flush=True)
    check(agg["count"] >= MIN_AGGREGATIONS,
          f"{agg['count']} aggregations < {MIN_AGGREGATIONS}")
    check(agg["all_finite"], "an aggregated global model is not finite")
    print("[fl] check: every aggregated global model is finite")

    w_prev, cache, t, scfg, w_new = agg["last"]
    err = rel_l2(w_new, eqs_6_10_f64(w_prev, cache, t, scfg.alpha, scfg.a))
    print(f"[fl] check: aggregation t={t} (K={len(cache)}) vs float64 "
          f"Eqs. 6-10: rel-L2 {err:.3e} (bound {AGG_RTOL:g})")
    check(err <= AGG_RTOL, f"aggregation rel-L2 {err:.3e} > {AGG_RTOL}")

    args, kw, steps = _cohort_args(w_new, parts, data, seed)
    loss = get_task("fmnist_cnn").cohort_loss
    out_tpu = _cohort_round(*args, cohort_loss=loss, **kw)
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        out_cpu = _cohort_round(*jax.device_put(args, cpu),
                                cohort_loss=loss, **kw)
    err = rel_l2(out_tpu, out_cpu)
    flips = sum(int(np.sum((np.asarray(a) != 0) != (np.asarray(b) != 0)))
                for a, b in zip(jax.tree.leaves(out_tpu),
                                jax.tree.leaves(out_cpu)))
    moved = rel_l2(out_cpu, jax.tree.map(
        lambda a: np.broadcast_to(np.asarray(a), (COHORT,) + np.shape(a)),
        w_new))
    print(f"[fl] check: _cohort_round ({COHORT} devices x {steps} steps) "
          f"TPU vs host CPU at highest precision: rel-L2 {err:.3e} "
          f"(bound {COHORT_RTOL:g}; zero/nonzero flips {flips} of "
          f"{COHORT * n_params}; the round moved the model by rel-L2 "
          f"{moved:.3e})")
    check(err <= COHORT_RTOL, f"cohort rel-L2 {err:.3e} > {COHORT_RTOL}")

    print(f"[fl] check: final accuracy {acc:.4f} > chance {CHANCE} and > "
          f"round-0 {acc0:.4f}")
    check(acc > CHANCE and acc > acc0, "training did not beat chance/round 0")
    return jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b), w_new, w0)


def lowers_to_mosaic(leaf, p_s: float, p_q: int) -> bool:
    """True when the fused_pack program for ``leaf`` holds a Mosaic kernel
    (the native Pallas lowering, not the interpreter)."""
    import jax.numpy as jnp
    from repro.core.compression import topk_count
    from repro.kernels import fused_pack
    hlo = fused_pack._fused_pack_call.lower(
        jnp.asarray(leaf, jnp.float32).reshape(-1),
        k=topk_count(int(np.size(leaf)), p_s), p_q=p_q,
        interpret=False).as_text()
    return "tpu_custom_call" in hlo


def phase_codec(update):
    import jax
    from repro.core.compression import expected_pytree_wire_bytes
    from repro.kernels import fused_pack
    from repro.kernels.ops import fused_wire_encode

    p_s, p_q = CODEC_POINT
    leaves = jax.tree.leaves(update)
    big = max(leaves, key=np.size)
    check(lowers_to_mosaic(big, p_s, p_q), "fused_pack is not on Mosaic")
    t0 = time.time()
    stream = fused_wire_encode(update, p_s, p_q)
    wall = time.time() - t0
    host = fused_pack.pack_leaves_host(leaves, p_s, p_q)
    want = expected_pytree_wire_bytes(update, p_s, p_q)
    print(f"[codec] CNN update, {len(leaves)} leaves (largest "
          f"{int(np.size(big))}), (p_s, p_q) = {CODEC_POINT}: {len(stream)} "
          f"bytes from the native kernel ({wall:.2f} wall s, compiles "
          f"included)")
    print(f"[codec] check: stream == host twin: {stream == host}; length "
          f"{len(stream)} == expected {want}: {len(stream) == want}")
    check(stream == host, "native stream differs from the host twin")
    check(len(stream) == want, "stream length differs from the wire model")


def phase_serve(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.launch.serve import ContinuousBatcher, _prefill_jit, generate
    from repro.models import transformer as T

    cfg = get_config(SERVE_ARCH)
    t0 = time.time()
    params = T.init_model(jax.random.PRNGKey(seed), cfg)
    n_params = sum(int(np.size(l)) for l in jax.tree.leaves(params))
    rng = np.random.RandomState(seed)
    prompts = rng.randint(0, cfg.vocab, (SERVE_REQUESTS, PROMPT)).astype(
        np.int32)
    seqs = np.asarray(generate(params, cfg, jnp.asarray(prompts), GEN))
    cb = ContinuousBatcher(params, cfg, slots=SERVE_REQUESTS,
                           cache_len=PROMPT + GEN)
    outs, _ = cb.run(list(prompts), GEN)
    wall = time.time() - t0
    print(f"[serve] {cfg.name}: {n_params} f32 params, {SERVE_REQUESTS} "
          f"requests x (prompt {PROMPT}, gen {GEN}) through generate and "
          f"ContinuousBatcher ({wall:.1f} wall s, compiles included)")
    gen_toks = seqs[:, PROMPT:]
    check(seqs.shape == (SERVE_REQUESTS, PROMPT + GEN), f"shape {seqs.shape}")
    check(bool(((gen_toks >= 0) & (gen_toks < cfg.vocab)).all()),
          "generated token outside the vocabulary")
    same = [list(map(int, g)) == o for g, o in zip(gen_toks, outs)]
    print(f"[serve] check: batcher tokens == generate tokens for "
          f"{sum(same)}/{len(same)} requests; request 0: {outs[0]}")
    check(all(same), "continuous batcher diverged from generate")

    logits, _ = _prefill_jit(cfg)(params, jnp.asarray(prompts))
    full, _ = T.forward(params, {"tokens": jnp.asarray(prompts)}, cfg)
    last, ref = np.asarray(logits[:, -1]), np.asarray(full[:, -1])
    check(bool(np.isfinite(last).all()), "prefill logits not finite")
    err = float(np.abs(last - ref).max() / np.abs(ref).max())
    print(f"[serve] check: prefill last-position logits vs full forward: "
          f"max-abs rel {err:.3e} (bound {LOGITS_RTOL:g})")
    check(err <= LOGITS_RTOL, f"prefill logits rel {err:.3e}")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[serve] peak device memory: "
          f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}")


def phase_sharded(seed: int, chips: int):
    import jax
    from repro.core.server import ShardedTeasqServer
    from repro.core.staleness import aggregate_cache_stacked
    from repro.fl.protocols import make_setup, run_method

    data, parts, w0 = make_setup(n_devices=N_DEVICES, iid=True, seed=seed,
                                 n_train=N_TRAIN, n_test=N_TEST,
                                 task="fmnist_cnn")
    t0 = time.time()
    with recorded_aggregations(ShardedTeasqServer) as agg:
        hist = run_method("teasq", data, parts, w0, iid=True,
                          time_budget=FL_BUDGET / 5, seed=seed,
                          p_s=FL_POINT[0], p_q=FL_POINT[1],
                          eval_every=FL_EVAL_EVERY, backend="engine",
                          cohort_size=COHORT, server="sharded",
                          server_shards=chips)
    print(f"[sharded] TEASQ, server_shards={chips}: {agg['count']} "
          f"aggregations ({time.time() - t0:.1f} wall s, compiles "
          f"included); accuracy {hist[0].accuracy:.4f} -> "
          f"{hist[-1].accuracy:.4f}")
    check(agg["count"] >= MIN_AGGREGATIONS,
          f"{agg['count']} aggregations < {MIN_AGGREGATIONS}")
    check(agg["all_finite"], "a sharded aggregate is not finite")
    w_prev, cache, t, scfg, w_new = agg["last"]
    single = aggregate_cache_stacked(w_prev, cache, t, scfg.alpha, scfg.a)
    err = rel_l2(w_new, single)
    ref_err = rel_l2(w_new, eqs_6_10_f64(w_prev, cache, t, scfg.alpha,
                                         scfg.a))
    print(f"[sharded] check: {chips}-chip aggregation t={t} vs single-chip "
          f"of the same cache: rel-L2 {err:.3e} (bound {SHARDED_RTOL:g}); "
          f"vs float64 Eqs. 6-10: {ref_err:.3e} (bound {AGG_RTOL:g})")
    check(err <= SHARDED_RTOL, f"sharded vs single rel-L2 {err:.3e}")
    check(ref_err <= AGG_RTOL, f"sharded vs float64 rel-L2 {ref_err:.3e}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-aggregation phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.cache import enable_compile_cache
    print(f"[smoke] {len(devices)} x {devices[0].device_kind}; compile "
          f"cache {enable_compile_cache()}", flush=True)

    t0 = time.time()
    if args.chips == 4:
        phase_sharded(args.seed, args.chips)
    else:
        update = phase_fl(args.seed)
        phase_codec(update)
        phase_serve(args.seed)
    print(f"[smoke] all phases passed in {time.time() - t0:.1f} wall s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
