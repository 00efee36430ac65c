"""Engine scale demo: 1000-device TEASQ via the vectorized cohort path vs
the legacy per-device Python loop at 100 devices, same dataset and virtual
30 s budget — for any registered model family (``--task``).

The comparison partitions one fixed dataset either 100 or 1000 ways (so
total sample throughput per virtual second is comparable), runs TEASQ
(p_s=0.25, p_q=8) under a 200 kHz cell, and reports wall-clock, completed
tasks, and aggregation rounds.  The vectorized run executes many times the
protocol tasks of the legacy run; the acceptance bar is that it still
finishes in less wall-clock.  Results merge into
results/engine_scale.json keyed per task, so the perf trajectory covers
multiple model families side by side.

``--tiered`` switches to the tier-aware codec-policy demo: a heterogeneous
three-tier fleet where the ``tier_aware`` policy gives slow-bandwidth tiers
aggressively packed updates while full-rate tiers stay near-dense; per-tier
uplink totals are metered exactly and logged under the task's
``tier_aware`` key.

``--fleet`` runs the multi-task fleet acceptance demo
(``repro.fl.fleet.MultiTaskEngine``): four heterogeneous FL jobs —
fmnist_cnn/teasq, transformer_lm/teastatic, moe_lm/fedasync,
ssm_lm/teasq — co-training over ONE shared 10^4-device fleet under the
batched scheduler, once with the statically partitioned ``weighted``
assigner and once with the FedAST-style ``adaptive`` one, same virtual
budget.  Logs per-task completions, rounds, ms_per_task and wire bytes
under the top-level ``fleet`` key; the acceptance bar is the adaptive
assigner completing >= 1.2x the aggregate protocol tasks of the static
partition (it reallocates grant probability toward jobs with free
admission slots / slower-converging loss curves, so capacity a small
C-fraction gate strands is immediately reused).

``--scheduler batched`` switches the engine's event loop to
``repro.fl.engine.BatchedEngine`` (resident per-device event arrays,
vectorized next-K selection — bit-identical histories, see
tests/test_batched_engine.py) and runs it solo: at 10^4-10^5 devices the
quantity of interest is the per-task dispatch cost (``ms_per_task``), logged
under the task's ``batched`` key, against the heap rows already in the
results file.  ``--host-tuning`` re-execs with the olmax-style host setup
(tcmalloc LD_PRELOAD when present, optional
``--xla_force_host_platform_device_count`` via ``--host-devices``).

  PYTHONPATH=src python -m benchmarks.engine_scale [--budget 30] [--devices 1000]
  PYTHONPATH=src python -m benchmarks.engine_scale --task transformer_lm
  PYTHONPATH=src python -m benchmarks.engine_scale --tiered --devices 120 --samples 6000 --budget 6
  PYTHONPATH=src python -m benchmarks.engine_scale --scheduler batched \\
      --devices 100000 --samples 100000 --cohort 256 --budget 8 --host-tuning
  PYTHONPATH=src python -m benchmarks.engine_scale --fleet --devices 10000 --budget 3
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

from benchmarks.common import (host_tuning_active, maybe_reexec_host_tuned,
                               profiled)

import jax

from repro.core.latency import WirelessConfig
from repro.data.synthetic import partition_iid
from repro.fl.protocols import make_sim
from repro.fl.simulator import ScenarioConfig, SimConfig, TierSpec
from repro.fl.tasks import TASKS, get_task
from repro.launch.cache import enable_compile_cache

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "..", "results",
                            "engine_scale.json")


def scale_config(n_devices: int, *, batch_size: int = 8, seed: int = 0,
                 cohort_size: int = 0, task: str = "fmnist_cnn",
                 scheduler: str = "heap",
                 handler_mode: str = "serial") -> SimConfig:
    """TEASQ at N devices with a constant K=10 aggregation cache and a
    200 kHz cell (longer rounds keep the demo's virtual-task count sane)."""
    return SimConfig(
        method="teasq", task=task, n_devices=n_devices, c_fraction=0.1,
        gamma=10.0 / n_devices, epochs=1, batch_size=batch_size,
        p_s=0.25, p_q=8, seed=seed,
        wireless=WirelessConfig(bandwidth_hz=2e5),
        cohort_size=cohort_size, cohort_channel_iters=6,
        scheduler=scheduler, handler_mode=handler_mode)


def run_one(data, n_train: int, n_devices: int, backend: str,
            cohort_size: int, budget: float, seed: int = 0,
            task: str = "fmnist_cnn", scheduler: str = "heap",
            handler_mode: str = "serial") -> dict:
    parts = partition_iid(n_train, n_devices, seed)
    w0 = get_task(task).init_params(jax.random.PRNGKey(seed))
    cfg = scale_config(n_devices, seed=seed, cohort_size=cohort_size,
                       task=task, scheduler=scheduler,
                       handler_mode=handler_mode)
    sim = make_sim(data, parts, w0, cfg, backend=backend)
    t0 = time.perf_counter()
    hist = sim.run(time_budget=budget, eval_every=10 ** 9)
    wall = time.perf_counter() - t0
    stats = getattr(sim, "stats", None)
    tasks = stats.completions if stats is not None else None
    return {
        "task": task, "backend": backend, "scheduler": scheduler,
        "handler_mode": handler_mode, "n_devices": n_devices,
        "cohort_size": cohort_size, "wall_s": wall, "budget": budget,
        "rounds": hist[-1].round, "accuracy": hist[-1].accuracy,
        "bytes_up_mb": hist[-1].bytes_up / 1e6,
        "tasks": tasks,
        "ms_per_task": wall * 1e3 / tasks if tasks else None,
        "flushes": stats.flushes if stats is not None else None,
        "host_tuning": host_tuning_active(),
    }


def tier_scenario() -> ScenarioConfig:
    """The demo fleet: a quarter full-rate, the rest on progressively
    slower links/compute — the heterogeneity the tier_aware policy prices
    per device."""
    return ScenarioConfig(tiers=[
        TierSpec(0.25, compute_scale=1.0, bandwidth_scale=1.0, name="fast"),
        TierSpec(0.375, compute_scale=1.5, bandwidth_scale=0.5, name="mid"),
        TierSpec(0.375, compute_scale=2.5, bandwidth_scale=0.125,
                 name="slow"),
    ])


def run_tiered(data, n_train: int, n_devices: int, budget: float,
               seed: int = 0, task: str = "fmnist_cnn") -> dict:
    """Tier-aware codec-policy run: heterogeneous bandwidth tiers, a
    per-device codec from the ``tier_aware`` policy, and per-tier uplink
    metering (``ChannelMeter.tier_up``).  The acceptance property logged
    here: the slowest bandwidth tier's metered uplink bytes are strictly
    below the fastest tier's, both in total and per transfer."""
    parts = partition_iid(n_train, n_devices, seed)
    w0 = get_task(task).init_params(jax.random.PRNGKey(seed))
    cfg = dataclasses.replace(
        scale_config(n_devices, seed=seed, cohort_size=0, task=task),
        scenario=tier_scenario(), codec_policy="tier_aware")
    sim = make_sim(data, parts, w0, cfg, backend="engine")
    t0 = time.perf_counter()
    hist = sim.run(time_budget=budget, eval_every=10 ** 9)
    wall = time.perf_counter() - t0
    per_tier = []
    for i, t in enumerate(cfg.scenario.tiers):
        sel = sim.devices.tier == i
        n_tier = int(sel.sum())
        if n_tier:   # tiny fleets can round a tier down to zero devices
            codec = sim.strategy.channel_for(0, device_id=int(sel.argmax()))
            p_s, p_q = codec.p_s, codec.p_q
            per_upload = codec.wire_bytes(w0)
        else:
            p_s = p_q = per_upload = None
        per_tier.append({
            "tier": t.name, "bandwidth_scale": t.bandwidth_scale,
            "devices": n_tier,
            "p_s": p_s, "p_q": p_q,
            "bytes_per_upload": per_upload,
            "uplink_bytes": sim.channel.tier_up.get(i, 0),
            "downlink_bytes": sim.channel.tier_down.get(i, 0),
            "completions": int(sim.stats.completed_per_device[sel].sum()),
        })
    return {
        "task": task, "n_devices": n_devices, "budget": budget,
        "wall_s": wall, "rounds": hist[-1].round,
        "accuracy": hist[-1].accuracy,
        "bytes_up_mb": hist[-1].bytes_up / 1e6, "per_tier": per_tier,
    }


def fleet_specs(n_devices: int, cohort: int) -> list:
    """The four heterogeneous acceptance jobs.  Every job's Alg. 1 gate
    admits MORE concurrent devices than its static quarter-share (0.25*N)
    can supply — except the SSM job, whose tiny ceil(0.004*N) gate strands
    almost all of its share in the waiting queue.  The static partition
    therefore tops out near 0.754*N busy devices, and the stranding hits
    hardest on the transformer job: its small wire footprint gives it the
    fastest round turnaround (it dominates aggregate completions), and its
    wide 0.5*N gate means it can productively absorb every device the
    other gates cannot hold.  The adaptive assigner routes each freed
    device to whichever job still has an open slot (aggregate gate
    capacity 1.064*N > N), so the slow jobs fill their 0.28*N gates and
    the whole remaining fleet pools in the transformer job — that
    occupancy gap is the >= 1.2x aggregate-tasks acceptance bar."""
    common = dict(n_devices=n_devices, gamma=10.0 / n_devices, epochs=1,
                  batch_size=8, cohort_size=cohort, cohort_channel_iters=6,
                  wireless=WirelessConfig(bandwidth_hz=2e5))
    return [
        SimConfig(method="teasq", task="fmnist_cnn", c_fraction=0.28,
                  p_s=0.25, p_q=8, **common),
        SimConfig(method="teastatic", task="transformer_lm",
                  c_fraction=0.5, p_s=0.25, p_q=8, **common),
        SimConfig(method="fedasync", task="moe_lm", c_fraction=0.28,
                  p_s=1.0, p_q=32, **common),
        SimConfig(method="teasq", task="ssm_lm", c_fraction=0.004,
                  p_s=0.25, p_q=8, **common),
    ]


def run_fleet_once(n_devices: int, budget: float, assigner: str,
                   cohort: int, seed: int = 0) -> dict:
    from repro.fl.fleet import FleetConfig, build_fleet
    cfg = FleetConfig(tasks=fleet_specs(n_devices, cohort),
                      n_devices=n_devices, seed=seed, scheduler="batched",
                      assigner=assigner,
                      wireless=WirelessConfig(bandwidth_hz=2e5))
    # one sample per device per job: local compute stays near zero, so
    # completions measure scheduling/occupancy, not the model families
    fleet = build_fleet(cfg, n_train=n_devices, n_test=200)
    t0 = time.perf_counter()
    hists = fleet.run(time_budget=budget, eval_every=10 ** 9)
    wall = time.perf_counter() - t0
    per_task = []
    for spec, rt, hist in zip(cfg.tasks, fleet.runtimes, hists):
        per_task.append({
            "task": spec.task, "method": spec.method,
            "c_fraction": spec.c_fraction,
            "completions": rt.stats.completions,
            "rounds": hist[-1].round,
            "bytes_up_mb": rt.channel.bytes_up / 1e6,
            "bytes_down_mb": rt.channel.bytes_down / 1e6,
        })
    total = sum(r["completions"] for r in per_task)
    return {"assigner": assigner, "wall_s": wall, "tasks_total": total,
            "ms_per_task": wall * 1e3 / total if total else None,
            "per_task": per_task}


def run(scale) -> list:
    """Suite entry point: full scale = the 30 s acceptance demo; quick scale
    shortens the budget to 10 s (same 1000-vs-100 device comparison)."""
    budget = 30.0 if scale.full else 10.0
    task = get_task("fmnist_cnn")
    data = task.make_data(12000, 1000, 0)
    rows = [run_one(data, 12000, 100, "legacy", 0, budget),
            run_one(data, 12000, 1000, "engine", 32, budget)]
    return rows


def _merge_results(path: str, task: str, entry: dict) -> dict:
    """Keep one entry per task so the CNN acceptance numbers, any other
    family's runs, and the tier-aware policy run live side by side in the
    same results file.  ``entry`` keys merge into the task's existing dict
    (so a scale run does not clobber a logged ``tier_aware`` run and vice
    versa)."""
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            out = json.load(f)
    # legacy layout (pre per-task keys) was the CNN run at top level
    if "rows" in out:
        out = {"fmnist_cnn": {k: out[k] for k in ("rows", "speedup", "budget")
                              if k in out}}
    out[task] = {**out.get(task, {}), **entry}
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", type=float, default=30.0)
    ap.add_argument("--devices", type=int, default=1000)
    ap.add_argument("--legacy-devices", type=int, default=100)
    ap.add_argument("--cohort", type=int, default=32)
    ap.add_argument("--samples", type=int, default=12000)
    ap.add_argument("--task", choices=sorted(TASKS), default="fmnist_cnn",
                    help="model family to scale (default: %(default)s)")
    ap.add_argument("--fleet", action="store_true",
                    help="multi-task fleet acceptance demo: 4 heterogeneous "
                         "jobs (CNN + transformer + MoE + SSM) co-training "
                         "on one shared --devices fleet, batched scheduler, "
                         "weighted vs adaptive assigner in the same virtual "
                         "budget (logged under the top-level 'fleet' key)")
    ap.add_argument("--tiered", action="store_true",
                    help="run the tier_aware codec-policy demo instead of "
                         "the scale race: heterogeneous bandwidth tiers, "
                         "per-device codecs, per-tier uplink metering "
                         "(logged under the task's 'tier_aware' key)")
    ap.add_argument("--scheduler", choices=("heap", "batched"),
                    default="heap",
                    help="engine event loop (SimConfig.scheduler); 'batched'"
                         " runs solo and logs ms_per_task under the task's "
                         "'batched' key")
    ap.add_argument("--handler-mode", choices=("serial", "wave"),
                    default="serial",
                    help="batched-scheduler event handling "
                         "(SimConfig.handler_mode): 'serial' is the pinned "
                         "bit-parity path, 'wave' dispatches same-kind "
                         "event runs as vectorized waves (relaxed parity; "
                         "rows are keyed *_wave_n<N>)")
    ap.add_argument("--profile", action="store_true",
                    help="cProfile each timed run; the top-20 cumulative "
                         "rows land next to results/engine_scale.json")
    ap.add_argument("--host-tuning", action="store_true",
                    help="re-exec with tcmalloc LD_PRELOAD (when installed) "
                         "and optional XLA host-device partitioning before "
                         "jax initializes")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="with --host-tuning: value for "
                         "--xla_force_host_platform_device_count (0 = "
                         "leave XLA_FLAGS untouched)")
    ap.add_argument("--dispatch-bench", action="store_true",
                    help="dispatch-isolated microbenchmark: heap@1000 vs "
                         "batched@--devices on a compute-light TEASQ "
                         "workload (fmnist_mlp, one sample per device = "
                         "zero local minibatches), so ms_per_task measures "
                         "the scheduler, not the model; logs the pair + "
                         "cost ratio under fmnist_mlp's 'dispatch' key")
    args = ap.parse_args()
    maybe_reexec_host_tuned(args.host_tuning, args.host_devices)
    enable_compile_cache()

    if args.fleet:
        runs = {}
        for assigner in ("weighted", "adaptive"):
            r = run_fleet_once(args.devices, args.budget, assigner,
                               args.cohort)
            runs[assigner] = r
            detail = " ".join(f"{p['task']}={p['completions']}"
                              for p in r["per_task"])
            print(f"engine_scale/fleet/{assigner}_n{args.devices},"
                  f"{r['tasks_total']},"
                  f"wall={r['wall_s']:.1f}s ms_per_task="
                  f"{r['ms_per_task']:.3f} {detail}", flush=True)
        ratio = (runs["adaptive"]["tasks_total"]
                 / max(runs["weighted"]["tasks_total"], 1))
        print(f"engine_scale/fleet/adaptive_vs_weighted,{ratio:.2f},"
              f"aggregate tasks, same {args.budget}s virtual budget",
              flush=True)
        entry = {"n_devices": args.devices, "budget": args.budget,
                 "scheduler": "batched", "cohort_size": args.cohort,
                 "tasks": [s.task for s in
                           fleet_specs(args.devices, args.cohort)],
                 "assigners": runs, "adaptive_vs_weighted_tasks": ratio}
        os.makedirs(os.path.dirname(os.path.abspath(RESULTS_PATH)),
                    exist_ok=True)
        merged = _merge_results(RESULTS_PATH, "fleet", entry)
        with open(RESULTS_PATH, "w") as f:
            json.dump(merged, f, indent=1)
        return

    if args.dispatch_bench:
        # Training and Eqs. 6-10 aggregation are bit-identical work under
        # both schedulers, so an end-to-end ms_per_task at a real model
        # mostly measures the model.  This pair holds per-task protocol
        # compute near zero and varies only (scheduler, N): wall/tasks is
        # then the per-task dispatch cost the ROADMAP item targets.
        task = "fmnist_mlp"
        rows = {}
        if args.handler_mode == "wave":
            # wave rows ride on the serial baselines already in the file;
            # only the batched wave run itself is timed
            runs = [("batched", args.devices, args.budget)]
        else:
            # heap@1000 and batched@N get full budgets; heap@N gets a
            # short one (it exists to price the heap at the same N, not
            # to run long)
            runs = [("heap", 1000, 20.0),
                    ("heap", args.devices, min(args.budget, 0.6)),
                    ("batched", args.devices, args.budget)]
        prof_dir = os.path.dirname(os.path.abspath(RESULTS_PATH))
        for scheduler, n, budget in runs:
            key = (f"batched_wave_n{n}" if args.handler_mode == "wave"
                   else f"{scheduler}_n{n}")
            data = get_task(task).make_data(n, 1000, 0)
            with profiled(args.profile, os.path.join(
                    prof_dir, f"engine_scale_dispatch_{key}.profile.txt")):
                r = run_one(data, n, n, "engine", args.cohort, budget,
                            task=task, scheduler=scheduler,
                            handler_mode=args.handler_mode)
            rows[key] = r
            print(f"engine_scale/{task}/dispatch_{key},"
                  f"{(r['ms_per_task'] or 0) * 1e3:.1f},"
                  f"wall={r['wall_s']:.1f}s tasks={r['tasks']} "
                  f"ms_per_task={r['ms_per_task']:.3f}", flush=True)
        # merge into the existing dispatch dict — a wave run must not
        # clobber the serial baselines (and vice versa)
        prev = {}
        if os.path.exists(RESULTS_PATH):
            with open(RESULTS_PATH) as f:
                prev = json.load(f).get(task, {}).get("dispatch", {})
        dispatch = {**prev, **rows}
        if args.handler_mode == "wave":
            base = dispatch.get(f"batched_n{args.devices}")
            if base and base.get("ms_per_task"):
                ratio = (base["ms_per_task"]
                         / dispatch[f"batched_wave_n{args.devices}"]
                         ["ms_per_task"])
                dispatch[f"wave_vs_serial_n{args.devices}"] = ratio
                print(f"engine_scale/{task}/dispatch_wave_vs_serial,"
                      f"{ratio:.2f},batched serial vs wave @ "
                      f"N={args.devices}")
        else:
            same_n = (rows[f"heap_n{args.devices}"]["ms_per_task"]
                      / rows[f"batched_n{args.devices}"]["ms_per_task"])
            dispatch["same_n_ratio"] = same_n
            print(f"engine_scale/{task}/dispatch_same_n_ratio,"
                  f"{same_n:.2f},heap vs batched @ N={args.devices}")
        os.makedirs(os.path.dirname(os.path.abspath(RESULTS_PATH)),
                    exist_ok=True)
        merged = _merge_results(RESULTS_PATH, task, {"dispatch": dispatch})
        with open(RESULTS_PATH, "w") as f:
            json.dump(merged, f, indent=1)
        return

    data = get_task(args.task).make_data(args.samples, 1000, 0)

    if args.scheduler == "batched" and not args.tiered:
        # solo batched run: the heap rows in the results file are the
        # baseline; re-running the legacy loop at 10^5 devices would take
        # hours for a number the file already has
        key = ("batched_wave" if args.handler_mode == "wave"
               else "batched")
        prof = os.path.join(
            os.path.dirname(os.path.abspath(RESULTS_PATH)),
            f"engine_scale_{args.task}_{key}.profile.txt")
        with profiled(args.profile, prof):
            r = run_one(data, args.samples, args.devices, "engine",
                        args.cohort, args.budget, task=args.task,
                        scheduler="batched",
                        handler_mode=args.handler_mode)
        ms = r["ms_per_task"] or float("nan")
        print(f"engine_scale/{args.task}/{key}_n{args.devices},"
              f"{ms * 1e3:.1f},"
              f"wall={r['wall_s']:.1f}s tasks={r['tasks']} "
              f"rounds={r['rounds']} ms_per_task={ms:.3f}", flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(RESULTS_PATH)),
                    exist_ok=True)
        merged = _merge_results(RESULTS_PATH, args.task, {key: r})
        with open(RESULTS_PATH, "w") as f:
            json.dump(merged, f, indent=1)
        return

    if args.tiered:
        r = run_tiered(data, args.samples, args.devices, args.budget,
                       task=args.task)
        for row in r["per_tier"]:
            print(f"engine_scale/{args.task}/tier_{row['tier']},"
                  f"{row['uplink_bytes']},"
                  f"bw={row['bandwidth_scale']} point=({row['p_s']},"
                  f"{row['p_q']}) per_upload={row['bytes_per_upload']}B "
                  f"completions={row['completions']}", flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(RESULTS_PATH)),
                    exist_ok=True)
        merged = _merge_results(RESULTS_PATH, args.task, {"tier_aware": r})
        with open(RESULTS_PATH, "w") as f:
            json.dump(merged, f, indent=1)
        return
    rows = []
    for name, n, backend, cohort in [
            ("legacy", args.legacy_devices, "legacy", 0),
            ("engine_cohort", args.devices, "engine", args.cohort)]:
        r = run_one(data, args.samples, n, backend, cohort, args.budget,
                    task=args.task)
        rows.append(r)
        print(f"engine_scale/{args.task}/{name}_n{n},"
              f"{r['wall_s'] * 1e6 / max(r['rounds'], 1):.1f},"
              f"wall={r['wall_s']:.1f}s rounds={r['rounds']} "
              f"tasks={r['tasks']} acc={r['accuracy']:.3f}", flush=True)

    speedup = rows[0]["wall_s"] / rows[1]["wall_s"]
    print(f"engine_scale/{args.task}/speedup,{speedup:.2f},"
          f"vec@{args.devices} vs legacy@{args.legacy_devices}")
    os.makedirs(os.path.dirname(os.path.abspath(RESULTS_PATH)), exist_ok=True)
    merged = _merge_results(RESULTS_PATH, args.task,
                            {"rows": rows, "speedup": speedup,
                             "budget": args.budget})
    with open(RESULTS_PATH, "w") as f:
        json.dump(merged, f, indent=1)


if __name__ == "__main__":
    main()
