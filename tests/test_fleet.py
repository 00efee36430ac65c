"""Multi-task fleet suite (repro.fl.fleet) + simulator checkpoint/resume.

Layers:

* **smoke** — assigner registry/units, a small heterogeneous 4-family
  fleet run (CNN + transformer + MoE + SSM co-training on one device
  pool), and the ``load_pytree`` validation contract; wired into
  scripts/tier1.sh via ``-m smoke``.
* **degenerate parity** — a single-task fleet must be bit-identical to
  the standalone engine on BOTH schedulers (the fleet loop replays the
  exact RNG draw order), including under an active failure/tier scenario;
  plus the pinned single-task-fleet fixtures in
  tests/data/pinned_histories.json (``runs_fleet`` / ``histories_fleet``,
  regenerated by scripts/dump_pinned_histories.py).
* **byte accounting** — each task's private ChannelMeter prices its own
  wire traffic at its own codec point, checked exactly against
  ``expected_pytree_wire_bytes``.
* **checkpoint/resume** — ``state_dict``/``load_state`` on engine and
  fleet through ``repro.checkpoint.io.save_blob``: save at ``t``, restore
  into a fresh instance, run to ``T`` — bit-identical to an uninterrupted
  ``run(T)`` on both schedulers, serial and cohort trainers.
"""
import dataclasses
import json
from types import SimpleNamespace

from conftest import (PINNED_PATH, TINY_RUN_KW, TINY_SETUP,
                      assert_engine_state_equal, assert_histories_equal,
                      run_tiny)
import numpy as np
import pytest

from repro.checkpoint.io import load_blob, load_pytree, save_blob, save_pytree
from repro.core.compression import expected_pytree_wire_bytes
from repro.fl.fleet import (ASSIGNERS, AdaptiveAssigner, FleetConfig,
                            MultiTaskEngine, RoundRobinAssigner,
                            WeightedAssigner, build_fleet, make_assigner)
from repro.fl.protocols import make_setup, make_sim
from repro.fl.simulator import ScenarioConfig, SimConfig, TierSpec


def tiny_spec(method, n_devices, **kw):
    """A per-task SimConfig with ``run_method``'s defaults — so a
    degenerate fleet run is config-identical to ``conftest.run_tiny``."""
    return SimConfig(method=method, n_devices=n_devices, c_fraction=0.1,
                     mu=0.01, alpha=0.6, p_s=kw.pop("p_s", 0.25),
                     p_q=kw.pop("p_q", 8), **kw)


def run_tiny_fleet(method, setup, **kw):
    """One degenerate single-task-fleet run of the canonical parity
    workload — the fleet analog of ``conftest.run_tiny``.  Returns
    (history, fleet)."""
    data, parts, w0 = setup
    merged = {**TINY_RUN_KW, **kw}
    time_budget = merged.pop("time_budget")
    scheduler = merged.pop("scheduler", "heap")
    scenario = merged.pop("scenario", None)
    spec = tiny_spec(method, len(parts), **merged)
    fleet = MultiTaskEngine([data], [parts], [w0], FleetConfig(
        tasks=[spec], n_devices=len(parts), seed=spec.seed,
        scheduler=scheduler, scenario=scenario))
    return fleet.run(time_budget=time_budget)[0], fleet


# ----------------------------------------------------------------------
# smoke: assigner units + registry plumbing
# ----------------------------------------------------------------------
def _dummy_fleet(n_tasks=3, n_devices=12, shares=None,
                 accs=(0.2, 0.5, 0.9), active=(0, 0, 0), max_parallel=2):
    """The minimal fleet surface an Assigner touches: cfg + per-task
    (server.active, server.cfg.max_parallel, history[-1].accuracy)."""
    cfg = FleetConfig(tasks=[SimConfig(method="teasq")] * n_tasks,
                      n_devices=n_devices, shares=shares)
    rts = [SimpleNamespace(
        server=SimpleNamespace(
            active=a, cfg=SimpleNamespace(max_parallel=max_parallel)),
        history=[SimpleNamespace(accuracy=acc)])
        for a, acc in zip(active, accs)]
    return SimpleNamespace(cfg=cfg, runtimes=rts)


@pytest.mark.smoke
def test_assigner_registry_and_validation():
    assert set(ASSIGNERS) == {"round_robin", "weighted", "adaptive"}
    with pytest.raises(ValueError, match="unknown assigner"):
        make_assigner("fifo", _dummy_fleet())


@pytest.mark.smoke
def test_round_robin_cycles_live_tasks():
    a = RoundRobinAssigner(_dummy_fleet())
    assert [a.assign(k, [0, 1, 2]) for k in range(5)] == [0, 1, 2, 0, 1]
    # a finished job drops out of `live`: the cycle continues over the rest
    assert [a.assign(k, [0, 2]) for k in range(3)] == [2, 0, 2]


@pytest.mark.smoke
def test_weighted_assigner_partitions_by_shares():
    a = WeightedAssigner(_dummy_fleet(shares=[0.5, 0.25, 0.25]))
    owner = [a.assign(k, [0, 1, 2]) for k in range(12)]
    assert owner == [0] * 6 + [1] * 3 + [2] * 3     # contiguous blocks
    # home job finished -> fall back to cycling the live jobs
    assert [a.assign(0, [1, 2]) for _ in range(3)] == [1, 2, 1]
    # uniform shares when unset
    b = WeightedAssigner(_dummy_fleet())
    counts = np.bincount([b.assign(k, [0, 1, 2]) for k in range(12)])
    assert counts.tolist() == [4, 4, 4]


@pytest.mark.smoke
def test_adaptive_assigner_prefers_slow_converging_free_tasks():
    # task 0 saturated (active == max_parallel): never a candidate
    fleet = _dummy_fleet(accs=(0.2, 0.5, 0.9), active=(2, 0, 0))
    a = AdaptiveAssigner(fleet)
    picks = np.bincount([a.assign(k, [0, 1, 2]) for k in range(400)],
                        minlength=3)
    assert picks[0] == 0
    # loss proxy 1-acc: task 1 (0.5) ~5x the grant mass of task 2 (0.1)
    assert picks[1] > 3 * picks[2] > 0
    # everyone saturated -> falls back to all live jobs
    sat = AdaptiveAssigner(_dummy_fleet(active=(2, 2, 2)))
    assert set(sat.assign(k, [0, 1, 2]) for k in range(50)) == {0, 1, 2}
    # one candidate short-circuits without touching the RNG stream
    state0 = a.rng.get_state()[1].copy()
    assert a.assign(0, [2]) == 2
    assert np.array_equal(a.rng.get_state()[1], state0)


@pytest.mark.smoke
def test_assigner_state_roundtrip():
    fleet = _dummy_fleet()
    for name in ASSIGNERS:
        a = make_assigner(name, fleet)
        for k in range(7):
            a.assign(k, [0, 1, 2])
        b = make_assigner(name, fleet)
        b.load_state(a.state_dict())
        assert [a.assign(k % 12, [0, 1, 2]) for k in range(20)] == \
               [b.assign(k % 12, [0, 1, 2]) for k in range(20)]


@pytest.mark.smoke
def test_fleet_config_validation(tiny_setup):
    data, parts, w0 = tiny_setup
    n = len(parts)
    with pytest.raises(ValueError, match="tasks is empty"):
        MultiTaskEngine([], [], [], FleetConfig(tasks=[], n_devices=n))
    with pytest.raises(ValueError, match="unknown scheduler"):
        MultiTaskEngine([data], [parts], [w0], FleetConfig(
            tasks=[tiny_spec("teasq", n)], n_devices=n, scheduler="fifo"))
    with pytest.raises(ValueError, match="not event-driven"):
        MultiTaskEngine([data], [parts], [w0], FleetConfig(
            tasks=[tiny_spec("fedavg", n)], n_devices=n))


# ----------------------------------------------------------------------
# smoke: heterogeneous co-training — one fleet, four model families
# ----------------------------------------------------------------------
@pytest.mark.smoke
def test_heterogeneous_fleet_smoke():
    """CNN + transformer + MoE + SSM jobs training simultaneously over one
    shared 8-device fleet (batched scheduler, adaptive assigner): every
    job must make progress and meter its own wire bytes."""
    cfg = FleetConfig(
        tasks=[SimConfig(method="teasq", epochs=1, p_s=0.25, p_q=8),
               SimConfig(method="fedasync", task="transformer_lm", epochs=1),
               SimConfig(method="fedasync", task="moe_lm", epochs=1),
               SimConfig(method="teasq", task="ssm_lm", epochs=1,
                         p_s=0.25, p_q=8)],
        n_devices=8, seed=0, scheduler="batched", assigner="adaptive")
    fleet = build_fleet(cfg, n_train=320, n_test=128)
    hists = fleet.run(time_budget=2.0)
    assert len(hists) == 4
    for hist, rt in zip(hists, fleet.runtimes):
        assert rt.server.t >= 1
        assert hist[-1].bytes_down > 0
        assert np.isfinite(hist[-1].accuracy)
    # the four jobs really shared one fleet: one registry, disjoint grants
    total = sum(rt.stats.dispatches for rt in fleet.runtimes)
    assert total >= 8
    assert all(rt.devices is fleet.devices for rt in fleet.runtimes)


# ----------------------------------------------------------------------
# degenerate parity: single-task fleet == standalone engine, bit-for-bit
# ----------------------------------------------------------------------
SCENARIO = ScenarioConfig(
    dropout_prob=0.2, failure_prob=0.3, retry_backoff=0.1,
    tiers=[TierSpec(0.5, compute_scale=1.0, bandwidth_scale=1.0,
                    name="fast"),
           TierSpec(0.5, compute_scale=2.0, bandwidth_scale=0.25,
                    name="slow")])


@pytest.mark.parametrize("scheduler", ["heap", "batched"])
@pytest.mark.parametrize("method,kw", [
    ("teasq", {}),
    ("fedasync", {}),
    ("teasq", dict(cohort_size=4, codec="packed")),
    ("teasq", dict(scenario=SCENARIO)),
], ids=["teasq", "fedasync", "teasq_cohort", "teasq_scenario"])
def test_single_task_fleet_matches_engine(method, kw, scheduler,
                                          tiny_setup):
    """The tentpole invariant: a one-task fleet replays the standalone
    engine's RNG draws in the identical order, so history, byte meters,
    stats and liveness are bit-identical on both schedulers."""
    kw = dict(kw)
    scenario = kw.pop("scenario", None)
    h_eng = run_tiny(method, tiny_setup, scheduler=scheduler,
                     scenario=scenario, **kw)
    h_fleet, fleet = run_tiny_fleet(method, tiny_setup, scheduler=scheduler,
                                    scenario=scenario, **kw)
    assert_histories_equal(h_eng, h_fleet)
    # beyond the history: rebuild the engine to compare full state
    data, parts, w0 = tiny_setup
    cfg = tiny_spec(method, len(parts), scheduler=scheduler,
                    scenario=scenario, epochs=TINY_RUN_KW["epochs"],
                    seed=TINY_RUN_KW["seed"], **kw)
    eng = make_sim(data, parts, w0, cfg)
    eng.run(time_budget=TINY_RUN_KW["time_budget"])
    assert_engine_state_equal(eng, fleet.runtimes[0])


@pytest.mark.parametrize("scheduler", ["heap", "batched"])
def test_pinned_single_task_fleet_histories(scheduler, tiny_setup):
    """Replay the frozen single-task-fleet fixtures — catches a refactor
    that drifts engine and fleet together (which fleet-vs-engine equality
    cannot see)."""
    with open(PINNED_PATH) as f:
        pinned = json.load(f)
    assert pinned["setup"] == TINY_SETUP
    for method, kw in pinned["runs_fleet"].items():
        hist, _ = run_tiny_fleet(method, tiny_setup,
                                 scheduler=scheduler, **kw)
        got = [dataclasses.asdict(h) for h in hist]
        assert got == pinned["histories_fleet"][scheduler][method], \
            f"fleet/{scheduler}/{method} drifted from the pinned fixture"
    # the fleet fixtures must sit ON the engine fixtures: same configs,
    # so bit-parity makes them literally equal records
    assert pinned["histories_fleet"]["heap"]["teasq"] == \
        pinned["histories"]["teasq"]
    assert pinned["histories_fleet"]["heap"]["fedasync"] == \
        pinned["histories"]["fedasync"]
    assert pinned["histories_fleet"]["batched"]["fedasync"] == \
        pinned["histories_batched"]["fedasync"]


# ----------------------------------------------------------------------
# per-task wire-byte accounting
# ----------------------------------------------------------------------
def test_per_task_channel_meters_are_exact(tiny_setup):
    """Each job's private ChannelMeter prices that job's traffic at that
    job's codec point: a compressed TEASQ job and a dense-f32 FedAsync job
    sharing one fleet keep exact, independent byte totals."""
    data, parts, w0 = tiny_setup
    n = len(parts)
    cfg = FleetConfig(
        tasks=[tiny_spec("teasq", n, epochs=1, seed=3),
               tiny_spec("fedasync", n, epochs=1, seed=3,
                         p_s=1.0, p_q=32)],
        n_devices=n, seed=3, scheduler="batched", assigner="round_robin")
    fleet = MultiTaskEngine([data, data], [parts, parts], [w0, w0], cfg)
    fleet.run(time_budget=2.0)
    per = [expected_pytree_wire_bytes(w0, 0.25, 8),
           expected_pytree_wire_bytes(w0, 1.0, 32)]
    assert per[0] < per[1]                      # the points really differ
    for rt, p in zip(fleet.runtimes, per):
        assert rt.stats.dispatches > 0
        # no scenario: every grant meters exactly one down + one up
        # transfer at the job's static point
        assert rt.channel.bytes_down == rt.stats.dispatches * p
        assert rt.channel.bytes_up == rt.stats.dispatches * p
        assert rt.channel.max_down == p
        assert rt.channel.max_up == p


# ----------------------------------------------------------------------
# checkpoint/resume: save at t, restore fresh, run to T == run(T)
# ----------------------------------------------------------------------
@pytest.mark.smoke
def test_load_pytree_validates_against_like(tmp_path):
    """Satellite contract for checkpoint/io.py: a stored pytree only loads
    into a ``like`` with the identical treedef, dtypes and shapes."""
    path = str(tmp_path / "w.msgpack")
    tree = {"a": np.ones((3, 2), np.float32), "b": np.arange(4, dtype=np.int32)}
    save_pytree(path, tree)
    out = load_pytree(path, tree)
    assert np.array_equal(np.asarray(out["a"]), tree["a"])
    assert np.array_equal(np.asarray(out["b"]), tree["b"])
    with pytest.raises(ValueError, match="treedef mismatch"):
        load_pytree(path, {"a": tree["a"], "c": tree["b"]})
    with pytest.raises(ValueError, match="shape mismatch"):
        load_pytree(path, {"a": np.ones((2, 3), np.float32), "b": tree["b"]})
    with pytest.raises(ValueError, match="dtype mismatch"):
        load_pytree(path, {"a": tree["a"],
                           "b": np.arange(4, dtype=np.int64)})


@pytest.mark.smoke
def test_blob_roundtrip(tmp_path):
    path = str(tmp_path / "blob.msgpack")
    obj = {"arrays": [np.arange(5), np.ones((2, 2), np.float32)],
           "scalar": 3, "f": 0.5, "s": "tag", "none": None,
           "nested": {"flag": True}}
    save_blob(path, obj)
    back = load_blob(path)
    assert np.array_equal(back["arrays"][0], obj["arrays"][0])
    assert back["arrays"][1].dtype == np.float32
    assert (back["scalar"], back["f"], back["s"], back["none"]) == \
        (3, 0.5, "tag", None)
    assert back["nested"] == {"flag": True}


@pytest.mark.parametrize("scheduler", ["heap", "batched"])
@pytest.mark.parametrize("cohort", [0, 4, 1],
                         ids=["serial", "cohort", "cohort1"])
def test_engine_checkpoint_resume_bit_parity(scheduler, cohort, tiny_setup,
                                             tmp_path):
    """run(2) -> state_dict -> save_blob -> fresh engine -> load -> run(4)
    must be bit-identical to an uninterrupted run(4), including the
    deferred cohort buffer, the rounds in flight and every RNG stream."""
    data, parts, w0 = tiny_setup
    cfg = tiny_spec("teasq", len(parts), epochs=1, seed=3,
                    scheduler=scheduler, cohort_size=cohort)
    full = make_sim(data, parts, w0, cfg)
    h_full = full.run(time_budget=4.0)
    a = make_sim(data, parts, w0, cfg)
    a.run(time_budget=2.0)
    path = str(tmp_path / "engine.msgpack")
    save_blob(path, a.state_dict())
    b = make_sim(data, parts, w0, cfg)
    b.load_state(load_blob(path))
    h_res = b.run(time_budget=4.0)
    assert_histories_equal(h_full, h_res)
    assert_engine_state_equal(full, b)


@pytest.mark.parametrize("scheduler", ["heap", "batched"])
def test_fleet_checkpoint_resume_bit_parity(scheduler, tiny_setup,
                                            tmp_path):
    data, parts, w0 = tiny_setup
    n = len(parts)

    def fresh():
        return MultiTaskEngine([data, data], [parts, parts], [w0, w0],
                               FleetConfig(
            tasks=[tiny_spec("teasq", n, epochs=1, seed=3),
                   tiny_spec("fedasync", n, epochs=1, seed=3)],
            n_devices=n, seed=3, scheduler=scheduler, assigner="adaptive"))

    full = fresh()
    h_full = full.run(time_budget=3.0)
    a = fresh()
    a.run(time_budget=1.5)
    path = str(tmp_path / "fleet.msgpack")
    save_blob(path, a.state_dict())
    b = fresh()
    b.load_state(load_blob(path))
    h_res = b.run(time_budget=3.0)
    for h_f, h_r in zip(h_full, h_res):
        assert_histories_equal(h_f, h_r)
    for rt_f, rt_r in zip(full.runtimes, b.runtimes):
        assert_engine_state_equal(rt_f, rt_r)


def test_engine_checkpoint_version_guard(tiny_setup):
    data, parts, w0 = tiny_setup
    eng = make_sim(data, parts, w0, tiny_spec("teasq", len(parts), seed=3))
    state = eng.state_dict()
    state["version"] = 99
    with pytest.raises(ValueError, match="unknown engine checkpoint"):
        eng.load_state(state)
