"""The program's spans (``repro.spans``) and the names of its device
programs, read back from a profiler trace on the CPU: a small FL
simulation at cohort 1 and 4, and a continuous batcher."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import spans as spans_mod
from repro.fl import engine as engine_mod
from repro.fl.protocols import make_setup, make_sim
from repro.fl.simulator import SimConfig
from repro.fl.tasks import get_task
from repro.launch.serve import ContinuousBatcher, generate

FL_SPANS = {"repro.fl.run", "repro.fl.flush", "repro.fl.flush.stage",
            "repro.fl.flush.launch", "repro.fl.flush.wait",
            "repro.fl.flush.copy", "repro.fl.aggregate", "repro.fl.evaluate"}


def _profile(trace_dir, fn):
    """Run ``fn`` under the profiler: ``repro.*`` spans as (name, start,
    end, args, thread), sorted by start, and the modules named by ops."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    found, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = {k: v for k, v in ev.stats}
                if ev.name.startswith("repro."):
                    found.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns, stats,
                                  (plane.name, line.name)))
                if "hlo_module" in stats:
                    modules.add(str(stats["hlo_module"]))
    return sorted(found, key=lambda s: s[1]), modules


def _inside(child, parent):
    return (child[4] == parent[4] and parent[1] <= child[1]
            and child[2] <= parent[2])


@pytest.fixture(scope="module")
def fl_setup():
    return make_setup(n_devices=8, iid=True, seed=3, n_train=320, n_test=160)


@pytest.mark.parametrize("cohort", [1, 4])
def test_fl_spans_nest_and_count_bytes(fl_setup, cohort, tmp_path,
                                       monkeypatch):
    data, parts, w0 = fl_setup
    cfg = SimConfig(method="teasq", n_devices=len(parts), epochs=1,
                    batch_size=8, seed=2, c_fraction=0.5, gamma=0.25,
                    cohort_size=cohort)
    sim = make_sim(data, parts, w0, cfg)
    sim.run(time_budget=1e9, max_rounds=1, eval_every=1)      # compiles
    results = []
    round_fn = engine_mod._cohort_round

    def recording(*args, **kwargs):
        out = round_fn(*args, **kwargs)
        results.append(spans_mod.nbytes(out))
        return out

    monkeypatch.setattr(engine_mod, "_cohort_round", recording)
    found, modules = _profile(tmp_path, lambda: sim.run(
        time_budget=1e9, max_rounds=4, eval_every=1))
    names = {s[0] for s in found}
    assert FL_SPANS <= names
    assert "jit__cohort_round" in modules
    by = {n: [s for s in found if s[0] == n] for n in FL_SPANS}
    for flush in by["repro.fl.flush"]:
        assert flush[3]["tasks"] >= 1
        for part in ("stage", "launch", "wait", "copy"):
            assert any(_inside(s, flush) for s in by["repro.fl.flush." + part])
        # the index arrays at least go to the device
        assert sum(s[3]["nbytes"] for s in by["repro.fl.flush.stage"]
                   if _inside(s, flush)) > 0
    for launch in by["repro.fl.flush.launch"] + by["repro.fl.flush.wait"]:
        assert launch[3]["program"] == "jit__cohort_round"
    # the copy moves exactly the round's result to the host
    assert [s[3]["nbytes"] for s in by["repro.fl.flush.copy"]] == results
    # each fold moves K host-resident updates to the device
    model = spans_mod.nbytes(w0)
    k = sim.server.cfg.cache_size
    assert [s[3]["nbytes"] for s in by["repro.fl.aggregate"]] == \
        [k * model] * len(by["repro.fl.aggregate"])
    test_bytes = data["x_test"].nbytes + data["y_test"].nbytes
    assert all(s[3]["nbytes"] == test_bytes for s in by["repro.fl.evaluate"])


def test_serving_spans_share_the_request_id(tmp_path):
    task = get_task("transformer_lm")
    params = task.init_params(jax.random.PRNGKey(0))
    cfg = task.model_cfg
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, 8).astype(np.int32)
               for _ in range(3)]
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=16)

    def serve():
        rids = [cb.submit(p, 4) for p in prompts]
        while cb.pending():
            cb.step()
        generate(params, cfg, jnp.asarray(prompts[0][None]), 2)
        return rids

    found, modules = _profile(tmp_path, serve)
    assert {"jit_prefill", "jit_extend_cache", "jit_step",
            "jit_serial_step"} <= modules
    by_rid = {}
    for name, _, _, args, _ in found:
        if "rid" in args:
            by_rid.setdefault(int(args["rid"]), []).append(name)
    assert sorted(by_rid) == [0, 1, 2]
    for names in by_rid.values():
        assert sorted(set(names)) == ["repro.serve.admit",
                                      "repro.serve.first_token",
                                      "repro.serve.prefill",
                                      "repro.serve.splice"]
        assert names.count("repro.serve.prefill") == 2
    steps = [s for s in found if s[0] == "repro.serve.step"]
    decodes = [s for s in found if s[0] == "repro.serve.decode"]
    assert len(decodes) == cb.steps and len(steps) >= cb.steps
    assert all(d[3]["path"] == "inplace" for d in decodes)
    assert all(any(_inside(d, s) for s in steps) for d in decodes)
    admits = [s for s in found if s[0] == "repro.serve.admit"]
    assert all(s[3]["prompt_len"] == 8 and s[3]["queued_ms"] >= 0
               for s in admits)
