"""The ``serve_mla`` driver end to end at smoke size, on the host CPU
through the harness's test path, in a checkout root of its own (the smoke
cells of ``smoke_cells.py`` and one latent-attention MoE cell), with its
control."""
import copy

import jax
import pytest

from bench import control
from bench.tests import smoke_cells as sc
from bench.tests.test_moonlight import MLA

BENCH = copy.deepcopy(sc.BENCH)
BENCH["configs"].append({"name": "mla-smoke", "source": "test",
                         "file": "bench/configs/mla-smoke.json",
                         "reduced": ["n_routed_experts"], "why": "test"})
BENCH["workloads"].append({"name": "serve-mla-smoke", "config": "mla-smoke",
                           "traffic": "chat-smoke", "chips": 1, "why": "test"})
for _m in BENCH["end_to_end"]:
    if "serve-smoke" in _m.get("workloads", []):
        _m["workloads"].append("serve-mla-smoke")
CELLS = dict(sc.CELLS, **{"serve-mla-smoke": dict(
    sc.CELLS["serve-smoke"], driver="serve_mla")})


@pytest.fixture(scope="module")
def mla_root(tmp_path_factory):
    return sc.write_root(str(tmp_path_factory.mktemp("checkout")), bench=BENCH,
                         cells=CELLS, configs=(sc.CNN, sc.LM, MLA))


def test_serve_mla_driver_end_to_end(mla_root, run_smoke):
    line = run_smoke(mla_root, "serve-mla-smoke")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "ttft_p95_ms", "itl_p95_ms",
                                    "serve_tokens_per_s"}
    assert line["attempted"] > 10 and line["failed"] == 0
    assert set(line["checks"]) == {"logit_gap", "mean_gap"}


def test_the_mla_control_is_not_correct(mla_root):
    limits = CELLS["serve-mla-smoke"]["limits"]
    for r in control.readings("serve-mla-smoke", [11], 2.0, root=mla_root,
                              devices=jax.devices("cpu")):
        assert r["correct"] is True and r["control_correct"] is False
        assert r["mean_gap"] <= limits["mean_gap"] < r["control_mean_gap"]
