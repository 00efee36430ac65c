"""The cohort trainer's flush returns before its round ends: a round is
launched, its result starts on its way to the host, and it is collected
when first asked for, oldest round first.  Collecting late changes no
value: histories and weights equal, bit for bit, those of a run that
collects every round as soon as it is launched."""
import jax
import numpy as np
import pytest

from repro.fl import engine as engine_mod
from repro.fl.fleet import FleetConfig, MultiTaskEngine
from repro.fl.protocols import make_setup, make_sim
from repro.fl.simulator import SimConfig

from conftest import assert_histories_equal


@pytest.fixture(scope="module")
def fl_setup():
    return make_setup(n_devices=8, iid=True, seed=3, n_train=320, n_test=160)


def _sim(setup, cohort, **kw):
    data, parts, w0 = setup
    cfg = SimConfig(method="teasq", n_devices=len(parts), epochs=1,
                    batch_size=kw.pop("batch_size", 8), seed=2,
                    c_fraction=0.5, gamma=0.25, cohort_size=cohort, **kw)
    return make_sim(data, parts, w0, cfg)


def _count_launches(monkeypatch, name):
    launches = []
    fn = getattr(engine_mod, name)

    def counting(*args, **kwargs):
        launches.append(1)
        return fn(*args, **kwargs)

    monkeypatch.setattr(engine_mod, name, counting)
    return launches


@pytest.mark.parametrize("cohort", [1, 4])
def test_submit_leaves_the_round_in_flight(fl_setup, monkeypatch, cohort):
    sim = _sim(fl_setup, cohort)
    tr = sim.trainer
    calls = []
    round_fn = engine_mod._cohort_round

    def recording(*args, **kwargs):
        calls.append((args, kwargs))
        return round_fn(*args, **kwargs)

    monkeypatch.setattr(engine_mod, "_cohort_round", recording)
    tasks = []
    for k in (3, 0, 6, 1)[:cohort]:
        w_t, t0 = sim.server.try_dispatch()
        tasks.append(tr.submit(k, w_t, t0, sim.cfg.p_s, sim.cfg.p_q))
    assert not tr.pending and len(calls) == 1
    assert all(t.result is None for t in tasks)
    assert [rows for _, _, rows in tr.in_flight] == \
        [[(t, i) for i, t in enumerate(tasks)]]
    assert tr.rounds_collected == 0

    results = [tr.result(t) for t in tasks]
    assert not tr.in_flight and tr.rounds_collected == 1
    assert 0 <= tr.rounds_ready <= 1
    args, kwargs = calls[0]
    want = jax.block_until_ready(round_fn(*args, **kwargs))
    for i, (t, (w_local, n_k)) in enumerate(zip(tasks, results)):
        assert n_k == len(sim.partitions[t.k])
        assert sorted(want) == sorted(w_local)
        for name, leaf in want.items():
            assert np.array_equal(np.asarray(leaf)[i], w_local[name])


def test_result_collects_older_rounds_first(fl_setup):
    sim = _sim(fl_setup, cohort=1)
    tr = sim.trainer
    tasks = []
    for k in range(3):
        w_t, t0 = sim.server.try_dispatch()
        tasks.append(tr.submit(k, w_t, t0, sim.cfg.p_s, sim.cfg.p_q))
    assert len(tr.in_flight) == 3
    tr.result(tasks[1])
    assert tasks[0].result is not None and tasks[2].result is None
    assert [rows[0][0] for _, _, rows in tr.in_flight] == [tasks[2]]
    assert tr.rounds_collected == 2


@pytest.mark.parametrize("cohort, kw, program", [
    (1, {}, "_cohort_round"),
    (4, {}, "_cohort_round"),
    (4, dict(scheduler="batched", handler_mode="wave", batch_size=64),
     "_zero_step_round"),
], ids=["cohort1", "cohort4", "wave-zero-step"])
def test_late_collection_matches_collecting_at_once(fl_setup, monkeypatch,
                                                    cohort, kw, program):
    def run(collect_at_once):
        sim = _sim(fl_setup, cohort, **dict(kw))
        tr = sim.trainer
        depth = []
        submit, collect = tr.submit, tr.collect

        def submit_and_collect(*args, **kwargs):
            task = submit(*args, **kwargs)
            tr.collect()
            return task

        def recording(task=None):
            depth.append(len(tr.in_flight))
            collect(task)

        if collect_at_once:
            tr.submit = submit_and_collect
        tr.collect = recording
        launches = _count_launches(monkeypatch, program)
        sim.run(time_budget=1e9, max_rounds=3, eval_every=1)
        sim.run(time_budget=1e9, max_rounds=6, eval_every=1)
        assert not tr.in_flight     # run returns with its rounds collected
        assert tr.rounds_collected == len(launches) > 0
        assert 0 <= tr.rounds_ready <= tr.rounds_collected
        return sim, max(depth)

    late, depth_late = run(collect_at_once=False)
    once, depth_once = run(collect_at_once=True)
    assert_histories_equal(late.history, once.history)
    for a, b in zip(jax.tree.leaves(late.server.w),
                    jax.tree.leaves(once.server.w)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert late.stats.flushes == once.stats.flushes
    assert depth_once <= 1
    if cohort == 1:
        # results wait on the device until their arrival asks for them
        assert depth_late > 1


@pytest.mark.parametrize("scheduler", ["heap", "batched"])
def test_fleet_run_collects_its_rounds(fl_setup, scheduler):
    """A one-task fleet at cohort 1 ends each ``run`` with no round in
    flight and matches the standalone engine bit for bit."""
    data, parts, w0 = fl_setup
    eng = _sim(fl_setup, 1, scheduler=scheduler)
    fleet = MultiTaskEngine([data], [parts], [w0], FleetConfig(
        tasks=[eng.cfg], n_devices=len(parts), seed=eng.cfg.seed,
        scheduler=scheduler))
    for budget in (1.0, 2.0):
        h_eng = eng.run(time_budget=budget)
        h_fleet = fleet.run(time_budget=budget)[0]
        tr = fleet.runtimes[0].trainer
        assert not tr.in_flight and not eng.trainer.in_flight
        assert tr.rounds_collected > 0
        assert_histories_equal(h_eng, h_fleet)
