"""FL driver: TEASQ-Fed simulation through the program's ``make_sim``.

Set-up makes the images, the partition and the initial CNN from the seed,
builds one simulator and drives it through its first aggregations (which
compiles every program the window runs).  The window drives that same
simulator's ``run`` in slices of ``slice_aggregations`` until the time is
up; ``fl_updates_per_s`` is the device updates folded into the global
model over the window's whole wall time.

The comparison covers one cohort round and one aggregation that the window
ran, each drawn from the seed: the round against the plain prox-SGD and
channel reference (``bench/reference/cnn_round.py``), as the share of
weights it puts on another quantization level; the aggregation against
the float64 Eqs. 6-10 fold (``bench/reference/eqs.py``), as rel-L2.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from bench.traffic.fmnist import make_images, partition_iid
from bench.weights import cnn_params, sub_seed

FAR_FUTURE = 1e15          # virtual seconds: slices stop on rounds, not time


class State:
    pass


def _host(tree) -> Dict[str, np.ndarray]:
    return {k: np.array(v, np.float32) for k, v in tree.items()}


def setup(spec: Dict, seed: int, probe) -> State:
    import jax
    from repro.fl import engine as engine_mod
    from repro.fl.protocols import make_sim
    from repro.fl.simulator import SimConfig

    cfg, mix, cell = spec["config"], spec["mix"], spec["cell"]
    if "matmul_precision" in cfg:
        # the configuration's float32 arithmetic; on a TPU the default
        # multiplies float32 operands in one bfloat16 pass
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    st = State()
    st.spec, st.seed = spec, seed
    t = time.perf_counter()
    st.data = make_images(mix["n_train"], mix["n_test"], sub_seed(seed, 1))
    st.parts = partition_iid(mix["n_train"], mix["n_devices"], sub_seed(seed, 2))
    w0 = cnn_params(cfg["model"], sub_seed(seed, 3))
    p = cfg["protocol"]
    sim_cfg = SimConfig(
        method=p["method"], task=cfg["task"], n_devices=mix["n_devices"],
        c_fraction=mix["c_fraction"], gamma=mix["gamma"], alpha=p["alpha"],
        a=p["a"], mu=p["mu"], epochs=p["epochs"], batch_size=p["batch_size"],
        lr=p["lr"], p_s=p["p_s"], p_q=p["p_q"],
        cohort_channel_iters=p["channel_iters"], seed=sub_seed(seed, 4),
        cohort_size=cell["cohort_size"], scheduler=cell["scheduler"],
        handler_mode=cell["handler_mode"], server=cell["server"])
    st.t_data = time.perf_counter() - t
    st.sim = make_sim(st.data, st.parts, w0, sim_cfg)
    st.t_sim = time.perf_counter() - t - st.t_data
    st.k = st.sim.server.cfg.cache_size

    # one cohort round and one aggregation of the window, drawn from the seed
    rng = np.random.default_rng(sub_seed(seed, 5))
    st.round_at, st.agg_at = int(rng.integers(1, 21)), int(rng.integers(1, 6))
    st.rounds = st.aggs = 0
    st.in_window = False
    st.round_capture = st.agg_capture = None

    def on_round(args, kwargs, out):
        if not st.in_window:
            return
        st.rounds += 1
        if st.rounds == st.round_at:
            w_versions, vidx, _xs, _ys, didx, bidx, valid = args
            st.round_capture = (
                _host(w_versions), np.asarray(vidx), np.asarray(didx),
                np.asarray(bidx), np.asarray(valid),
                _host(out), dict(kwargs))

    def capturing(inner):
        def aggregate():
            if st.in_window:
                st.aggs += 1
                if st.aggs == st.agg_at:
                    srv = st.sim.server
                    before = (_host(srv.w),
                              [(_host(w), h, n) for w, h, n in srv.cache], srv.t)
                    out = inner()
                    st.agg_capture = before + (_host(out),)
                    return out
            return inner()
        return aggregate

    probe.wrap(engine_mod, "_cohort_round", "cohort_round", after=on_round)
    for attr in ("_aggregate", "_aggregate_stacked"):   # serial, wave mode
        setattr(st.sim.server, attr, capturing(getattr(st.sim.server, attr)))
        probe.wrap(st.sim.server, attr, "aggregate")
    probe.wrap(st.sim.trainer, "flush", "flush")
    probe.wrap(st.sim, "evaluate", "evaluate")
    bs = p["batch_size"]

    def on_submit(args, kwargs, task):
        probe.count("samples_trained", task.bidx.shape[0] * bs)
    probe.wrap(st.sim.trainer, "submit", "submit", after=on_submit)

    t = time.perf_counter()
    st.sim.run(time_budget=FAR_FUTURE, max_rounds=cell["warmup_aggregations"],
               eval_every=cell["eval_every"])
    print(f"fl: set-up stages (s): data {st.t_data:.3f}, simulator "
          f"{st.t_sim:.3f}, warm-up {time.perf_counter() - t:.3f}",
          file=sys.stderr)
    return st


def _slice(st: State) -> None:
    cell = st.spec["cell"]
    st.sim.run(time_budget=FAR_FUTURE,
               max_rounds=st.sim.server.t + cell["slice_aggregations"],
               eval_every=cell["eval_every"])


def window(st: State, seconds: float, probe) -> Dict:
    import jax
    t_first = st.sim.server.t
    st.in_window = True
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        _slice(st)
    jax.block_until_ready(st.sim.server.w)
    elapsed = time.perf_counter() - t0
    st.in_window = False
    updates = (st.sim.server.t - t_first) * st.k
    probe.counters["updates"] = updates
    probe.counters["aggregations"] = st.sim.server.t - t_first
    finite = all(bool(np.isfinite(np.asarray(v)).all())
                 for v in jax.tree.leaves(st.sim.server.w))
    return {"end_to_end": {"fl_updates_per_s": updates / elapsed},
            "window_s": elapsed, "attempted": updates,
            "failed": 0 if finite else updates}


def readings(st: State, control: bool = False) -> Dict[str, float]:
    """The compared numbers: ``round_mismatch`` (worst device of the
    captured round, ``cnn_round.mismatch_share``) and ``agg_rel_err``.
    With ``control``, also the control's under ``control_`` (the round:
    the reference at ``high`` precision, three bfloat16 passes; the
    aggregation: the fold computed in bfloat16), the round's reference
    in bfloat16, a planted fault's (half of each batch left out, the mean
    taken over the rest) and the round's ``round_change_gap`` (worst leaf
    of ``cnn_round.change_gaps``).  Frees the program's state first."""
    from bench.reference import cnn_round, eqs
    import jax.numpy as jnp

    p = st.spec["config"]["protocol"]
    st.sim = None
    out = {"round_mismatch": float("nan"), "agg_rel_err": float("nan")}
    if st.round_capture is not None:
        w_versions, vidx, didx, bidx, valid, got, kw = st.round_capture
        m = np.flatnonzero(valid.sum(0) > 0)

        def run(dtype=jnp.float32, b=bidx, precision="highest"):
            return cnn_round.cohort_round(
                w_versions, vidx[m], didx[m], b[:, m], valid[:, m], st.data,
                st.parts, lr=p["lr"], mu=p["mu"], p_s=kw["p_s"], p_q=kw["p_q"],
                iters=kw["iters"], dtype=dtype, precision=precision)

        def member(tree, i):
            return {k: v[i] for k, v in tree.items()}

        def mismatch(result, ref, rows):
            return max(cnn_round.mismatch_share(member(result, r),
                                                member(ref, i), kw["p_q"])
                       for i, r in enumerate(rows))

        recv, ref = run()
        out["round_mismatch"] = mismatch(got, ref, m)
        if control:
            own = range(len(m))
            _, ctl = run(precision="high")
            out["control_round_mismatch"] = mismatch(ctl, ref, own)
            _, bf16 = run(dtype=jnp.bfloat16)
            out["bf16_round_mismatch"] = mismatch(bf16, ref, own)
            _, half = run(b=bidx[:, :, :bidx.shape[2] // 2])
            out["fault_half_batch_mismatch"] = mismatch(half, ref, own)
            out["round_change_gap"] = max(max(cnn_round.change_gaps(
                member(got, r), member(ref, i), member(recv, i)).values())
                for i, r in enumerate(m))
    if st.agg_capture is not None:
        w, cache, t, got = st.agg_capture
        ref = eqs.fold(w, cache, t, p["alpha"], p["a"])
        out["agg_rel_err"] = eqs.rel_l2(got, ref)
        if control:
            out["control_agg_rel_err"] = eqs.rel_l2(
                eqs.fold(w, cache, t, p["alpha"], p["a"],
                         dtype=jnp.dtype(jnp.bfloat16).type),
                ref)
    return out


def check(st: State) -> List[Tuple[str, float, float]]:
    from bench.harness import checks_of
    return checks_of(readings(st), st.spec["cell"]["limits"])
