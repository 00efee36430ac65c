"""The reduction of the program's own spans and scopes
(``bench/program_trace.py``) and the per-layer metrics that read it, on
synthetic events and on the recorded probe trace."""
import json
import os
import subprocess
import sys

import pytest

from bench import harness, program_trace as pt, trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def host(name, start, dur, thread="python3", **args):
    return pt.Event("/host:CPU", thread, name, start, dur, args=args)


def dev(line, name, start, dur, scope="", plane="/device:TPU:0"):
    return pt.Event(plane, line, name, start, dur, scope=scope)


def planted(offset):
    """Three launches of ``jit_f``: each host launch span is 10 ns long,
    the program runs 30 ns on the device from 5 ns after the launch ends,
    and the host's wait span ends 5 ns after the device's end; device
    times read ``offset`` ahead of the host's."""
    ev = [host("bench.window", 0, 1000)]
    for k in range(3):
        t = 100 + 300 * k
        ev += [host("repro.fl.flush.launch", t, 10, program="jit_f"),
               host("repro.fl.flush.wait", t + 10, 40, program="jit_f"),
               dev("XLA Modules", f"jit_f({k})", t + 15 + offset, 30),
               dev("XLA Ops", "%fusion", t + 15 + offset, 30)]
    return ev


@pytest.mark.parametrize("offset", [0.0, 1200.0, -800.0])
def test_a_planted_offset_is_recovered_inside_its_interval(offset):
    d, lo, hi, pairs = pt.clock_offset(planted(offset), "/device:TPU:0")
    assert pairs == 6
    # launch: the device starts at most 15 ns after; wait: it ends 5 ns
    # before the wait does
    assert (lo, hi) == (offset - 5, offset + 15)
    assert lo <= offset <= hi and d == pytest.approx(offset + 5)
    s = pt.summarize(planted(offset))
    assert s["clock_offset_ns"] == d
    assert s["clock_offset_interval_ns"] == [lo, hi]


def test_an_empty_interval_is_reported_and_no_offset_applied():
    ev = planted(0.0)
    # a wait that ended before its program did on the device
    ev.append(host("repro.serve.first_token", 2000, 1, program="jit_g"))
    ev.append(dev("XLA Modules", "jit_g(1)", 2100, 50))
    d, lo, hi, _ = pt.clock_offset(ev, "/device:TPU:0")
    assert lo > hi and d == 0.0


def nested():
    """``bench.inner`` inside ``bench.outer``, over a gap of the device."""
    return [host("bench.window", 0, 100),
            dev("XLA Ops", "a", 10, 30), dev("XLA Ops", "b", 20, 30),
            dev("XLA Ops", "c", 90, 50),
            dev("XLA Modules", "jit_f(12)", 10, 40),
            host("bench.outer", 50, 45), host("bench.inner", 60, 10)]


def test_idle_self_splits_a_gap_across_nested_spans():
    idle = pt.idle_self(nested(), "/device:TPU:0", 0, 100, 0.0)
    # gaps [0, 10) and [50, 90); inside the second, inner holds [60, 70)
    assert idle == {"host": pytest.approx(10e-9),
                    "bench.outer": pytest.approx(30e-9),
                    "bench.inner": pytest.approx(10e-9)}
    s = pt.summarize(nested())
    assert sum(v for _, v in s["breakdown"]["idle_self"]) == pytest.approx(
        s["window_s"] - s["busy_s"])


def test_idle_self_reads_the_device_on_the_host_clock():
    # the device runs 30 ns ahead: its op [40, 70) ran at [10, 40) on the
    # host's clock, inside the span [0, 50), which so holds [0, 10) and
    # [40, 50) of idle time (on the device's clock it would hold 40 ns)
    ev = [host("bench.window", 0, 100), dev("XLA Ops", "a", 40, 30),
          host("repro.fl.flush", 0, 50)]
    idle = pt.idle_self(ev, "/device:TPU:0", 0, 100, 30.0)
    assert idle == {"repro.fl.flush": pytest.approx(20e-9),
                    "host": pytest.approx(50e-9)}


def test_spans_count_self_time_and_sum_numeric_args():
    ev = [host("bench.window", 0, 1000),
          host("repro.fl.flush", 100, 100, tasks=2),
          host("repro.fl.flush.stage", 110, 20, nbytes=64),
          host("bench.cohort_round", 135, 5),
          host("repro.fl.flush.copy", 150, 30, nbytes=800),
          host("repro.fl.flush", 300, 50, tasks=1),
          host("repro.fl.flush.stage", 310, 10, nbytes=32),
          host("repro.fl.flush.stage", 400, 10, thread="other", nbytes=1),
          dev("XLA Ops", "a", 0, 10)]
    spans = pt.span_table(ev, 0, 1000)
    assert spans["repro.fl.flush"]["count"] == 2
    assert spans["repro.fl.flush"]["seconds"] == pytest.approx(150e-9)
    # less its repro.* children on its thread; bench.* spans are no child
    assert spans["repro.fl.flush"]["self_s"] == pytest.approx(90e-9)
    assert spans["repro.fl.flush"]["args"] == {"tasks": 3}
    assert spans["repro.fl.flush.stage"]["count"] == 3
    assert spans["repro.fl.flush.stage"]["args"] == {"nbytes": 97}
    assert "bench.cohort_round" not in spans


def test_nested_scoped_ops_count_once():
    ev = [host("bench.window", 0, 1000),
          dev("XLA Modules", "jit_f(3)", 0, 120),
          dev("XLA Ops", "%while", 0, 100, scope="prox_sgd/while"),
          dev("XLA Ops", "%dot", 10, 20, scope="prox_sgd/while/body/dot"),
          dev("XLA Ops", "%fusion", 100, 10, scope="codec_up/vmap/max"),
          dev("XLA Ops", "%copy", 110, 5),
          dev("XLA Modules", "jit_g(4)", 200, 10),
          dev("XLA Ops", "%fusion.1", 200, 10, scope="codec_up/abs")]
    assert pt.scope_seconds(ev, 0, 1000) == {
        "jit_f": {"prox_sgd": pytest.approx(100e-9),
                  "codec_up": pytest.approx(10e-9), "": pytest.approx(5e-9)},
        "jit_g": {"codec_up": pytest.approx(10e-9)}}


def test_scope_of_drops_the_jit_parts():
    assert pt.scope_of("jit(_cohort_round)/jit(main)/codec_down/vmap(f)/max") \
        == "codec_down/vmap(f)/max"
    assert pt.scope_of("") == ""


def test_the_old_keys_are_unchanged_by_program_events():
    with open(os.path.join(DATA, "trace_v5e_probe.json")) as f:
        probe = [tr.Event(*e) for e in json.load(f)]
    old = tr.summarize(probe)
    t0 = min(e.start_ns for e in probe)
    extra = [pt.Event(*e, scope="prox_sgd") for e in probe] + [
        host("repro.fl.flush", t0 + 1e6, 5e6, tasks=1),
        host("repro.fl.flush.launch", t0 + 2e6, 1e5, program="jit__lambda")]
    new = pt.summarize(extra)
    for k in ("busy_s", "window_s", "devices", "programs"):
        assert new[k] == old[k]
    for k in ("device_ops", "idle_gaps"):
        assert new["breakdown"][k] == old["breakdown"][k]


# -- the per-layer metrics that read the program's spans and scopes -------
def ctx_of(**trace):
    return {"counters": {"updates": 10, "admitted": 4}, "spans": {},
            "trace": dict({"programs": {}}, **trace), "window_s": 1.0}


def span(seconds, self_s=None, **args):
    return {"count": 1, "seconds": seconds,
            "self_s": seconds if self_s is None else self_s, "args": args}


READINGS = [
    ("fl_flush_stage_ms_per_update",
     dict(spans={"repro.fl.flush.stage": span(0.05, 0.04)}), 4.0),
    ("fl_flush_copy_ms_per_update",
     dict(spans={"repro.fl.flush.copy": span(0.02)}), 2.0),
    ("fl_codec_device_ms_per_update",
     dict(scopes={"jit__cohort_round": {"codec_down": 0.003, "codec_up": 0.002,
                                        "prox_sgd": 0.04}}), 0.5),
    ("fl_host_copy_mb_per_update",
     dict(spans={"repro.fl.flush.stage": span(0.1, nbytes=1e6),
                 "repro.fl.flush.copy": span(0.1, nbytes=8e6),
                 "repro.fl.aggregate": span(0.1, nbytes=8e6),
                 "repro.fl.evaluate": span(0.1, nbytes=3e6)}), 2.0),
    ("serve_prefill_device_ms_per_req",
     dict(programs={"jit_prefill": (0.04, 4), "jit_extend_cache": (0.004, 4),
                    "jit_step": (1.0, 20)}), 11.0),
    ("serve_first_token_wait_ms_per_req",
     dict(spans={"repro.serve.first_token": span(0.048)}), 12.0),
]


@pytest.mark.parametrize("name,trace,value", READINGS,
                         ids=[r[0] for r in READINGS])
def test_program_metric_readers(name, trace, value):
    spec = {"root": ROOT}
    assert harness.read_metric(spec, name, ctx_of(**trace)) == \
        pytest.approx(value)
    # a trace of the parent, without the program's spans and scopes
    assert harness.read_metric(spec, name, ctx_of()) is None


def test_without_a_tpu_the_trace_report_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/trace_report.py", "--workload",
                        "cnn-teasq-paper-c1", "--seed", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_reduction_stands_in_for_bench_trace_summarize(monkeypatch):
    # bench/trace_report.py puts summarize in bench.trace's place
    monkeypatch.setattr(tr, "summarize", pt.summarize)
    assert tr.summarize(nested())["breakdown"]["idle_self"]


def test_scopes_come_from_the_traced_programs_hlo(tmp_path):
    """On the host CPU: the trace's metadata plane names each executed op's
    instruction with its ``op_name``, whose first part is the scope."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    def f(x):
        with jax.named_scope("codec_down"):
            x = jnp.sin(x) * 2
        with jax.named_scope("prox_sgd"):
            x, _ = jax.lax.scan(lambda c, _: (c * 1.01 + 1, None), x, None,
                                length=3)
        return x

    f = jax.jit(f)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = [os.path.join(d, n) for d, _, ns in os.walk(tmp_path)
             for n in ns if n.endswith(".xplane.pb")]
    with open(path, "rb") as fh:
        raw = fh.read()
    names = pt.hlo_op_names(raw)
    program, = [k for k in names if tr.program_name(k) == "jit_f"]
    scopes = set()
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        for line in plane.lines:
            for ev in line.events:
                stats = {k: v for k, v in ev.stats}
                if stats.get("hlo_module") == "jit_f":
                    op_name = names[program].get(pt.instruction_of(ev.name), "")
                    scopes.add(pt.scope_of(op_name).split("/")[0])
    assert {"codec_down", "prox_sgd"} <= scopes


def test_a_recorded_v5e_flush_trace():
    """Three cohort-1 flushes of the paper CNN, recorded on one v5e chip
    under the program's spans (``bench/trace_report.py --keep``, cell
    ``cnn-teasq-paper-c1``, seed 3000007011) and cut to those flushes and
    a window from the first flush's start to the fourth's.  Op names are
    cut to their HLO instruction, and ops inside an op of the same scope
    are left out: neither changes a number of the reduction but the names
    in ``device_ops``."""
    with open(os.path.join(DATA, "trace_v5e_flush.json")) as f:
        ev = [pt.Event(*e[:6], args=e[6], scope=e[7]) for e in json.load(f)]
    s = pt.summarize(ev)
    lo, hi = s["clock_offset_interval_ns"]
    assert lo < hi and lo <= s["clock_offset_ns"] <= hi
    assert {k: v["count"] for k, v in s["spans"].items()} == {
        "repro.fl.run": 1, "repro.fl.flush": 3, "repro.fl.flush.stage": 6,
        "repro.fl.flush.launch": 3, "repro.fl.flush.wait": 3,
        "repro.fl.flush.copy": 3}
    round_ = s["scopes"]["jit__cohort_round"]
    assert round_["codec_down"] > 0 and round_["codec_up"] > 0
    # the codec is a small part of the round; the prox-SGD scan the rest
    assert round_["prox_sgd"] > 10 * (round_["codec_down"] + round_["codec_up"])
    assert s["programs"]["jit__cohort_round"][1] == 3
    idle = dict(s["breakdown"]["idle_self"])
    assert {"repro.fl.flush.stage", "repro.fl.flush.wait",
            "repro.fl.flush.copy"} <= set(idle)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"],
                                               rel=0.01)
    # stage and copy hold most of the device's idle time
    assert idle["repro.fl.flush.stage"] + idle["repro.fl.flush.copy"] > \
        0.5 * sum(idle.values())
