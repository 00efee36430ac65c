"""The trace reduction, against a small trace recorded on one v5e chip:
inside a ``bench.window`` span, three rounds of a bf16 matmul program
(under a ``bench.work`` span) and a scaling program, with a 2 ms sleep
between them.  The expected numbers are summed by hand from the file."""
import json
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_v5e_probe.json")
WINDOW = (44188537.0, 44188537.0 + 13142099.0)


@pytest.fixture(scope="module")
def probe():
    with open(DATA) as f:
        return [tr.Event(*e) for e in json.load(f)]


def test_busy_is_the_union_of_ops_inside_the_window(probe):
    # the XLA Ops of the five launches inside the window; the first
    # launch's three ops lie before it on the device clock
    inside = [4821, 13, 3295, 11856, 4827, 13, 3292, 11856, 4817]
    s = tr.summarize(probe)
    assert s["window_s"] == pytest.approx((WINDOW[1] - WINDOW[0]) * 1e-9)
    assert s["busy_s"] == pytest.approx(sum(inside) * 1e-9)
    assert s["devices"] == 1


def test_per_program_time_and_launches(probe):
    s = tr.summarize(probe)
    secs, n = tr.program_seconds(s, r"^jit__lambda$")
    assert n == 5
    assert secs == pytest.approx((4823 + 15169 + 4830 + 15167 + 4822) * 1e-9)
    assert tr.program_seconds(s, "no_such_program") == (0.0, 0)


def test_breakdown_lists_ops_and_attributes_idle_time(probe):
    s = tr.summarize(probe)
    ops = s["breakdown"]["device_ops"]
    assert ops[0][0].startswith("%fusion") and ops[0][1] == pytest.approx(
        2 * 11856e-9)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    idle = dict(s["breakdown"]["idle_gaps"])
    assert set(idle) == {"bench.work", "host"}
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])
    # each of the three gaps before a matmul overlaps its bench.work span
    assert idle["bench.work"] > idle["host"]


def test_overlapping_ops_count_once_and_devices_average():
    ev = [tr.Event("/host:CPU", "python3", "bench.window", 0, 100),
          tr.Event("/device:TPU:0", "XLA Ops", "a", 10, 30),
          tr.Event("/device:TPU:0", "XLA Ops", "b", 20, 30),     # overlaps a
          tr.Event("/device:TPU:0", "XLA Ops", "c", 90, 50),     # clipped
          tr.Event("/device:TPU:1", "XLA Ops", "a", 0, 20),
          tr.Event("/device:TPU:0", "XLA Modules", "jit_f(12)", 10, 40),
          tr.Event("/host:CPU", "python3", "bench.outer", 50, 45),
          tr.Event("/host:CPU", "python3", "bench.inner", 60, 10)]
    s = tr.summarize(ev)
    assert s["busy_s"] == pytest.approx((50 + 20) / 2 * 1e-9)
    # program time is averaged over the devices too: 40 ns on one of two
    assert s["programs"] == {"jit_f": (pytest.approx(20e-9), 1)}
    idle = dict(s["breakdown"]["idle_gaps"])
    # gaps of TPU:0: [0, 10) host, [50, 90) mostly under bench.outer
    assert idle == {"host": pytest.approx(10e-9),
                    "bench.outer": pytest.approx(40e-9)}


def test_a_trace_without_a_tpu_plane_is_refused():
    with pytest.raises(ValueError):
        tr.summarize([tr.Event("/host:CPU", "python3", "bench.window", 0, 1)])
