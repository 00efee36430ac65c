"""The command and both drivers end to end at smoke size, on the host CPU
through the harness's test path; the command's refusal without a TPU;
and a cell added as files alone."""
import copy
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp

from bench import harness
from bench.tests import smoke_cells as sc

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_without_a_tpu_the_command_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-serve-chat", "--seed", "1", "--seconds", "1"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def _check_line(line, metrics):
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert m["value"] > 0


def test_fl_driver_end_to_end(smoke_root, run_smoke):
    line = run_smoke(smoke_root, "fl-smoke", seconds=3.0)
    _check_line(line, {"setup_s", "fl_updates_per_s"})
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {"round_mismatch", "agg_rel_err"}


def test_serve_driver_end_to_end(smoke_root, run_smoke):
    line = run_smoke(smoke_root, "serve-smoke")
    _check_line(line, {"setup_s", "ttft_p95_ms", "itl_p95_ms",
                       "serve_tokens_per_s"})
    assert line["correct"] is True
    assert line["attempted"] > 10 and line["failed"] == 0


def test_benchmark_weights_match_the_program_layout():
    from repro.models import transformer as T
    from bench.drivers.serve import model_config
    from bench.weights import lm_params
    hf = dict(sc.LM)
    cfg = model_config(hf)
    want = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg,
                                               jnp.bfloat16))
    got = jax.eval_shape(lambda: lm_params(hf, 0, jnp.bfloat16))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape,
                                        want, got)) == [True] * len(
        jax.tree.leaves(want))


def test_a_cell_added_as_files_alone(tmp_path, run_smoke):
    """A new configuration, traffic mix, cell and per-layer metric, each a
    new file plus new entries in BENCHMARK.json: no file of the harness is
    edited."""
    bench = copy.deepcopy(sc.BENCH)
    lm2 = dict(sc.LM, name="lm-other", num_key_value_heads=4,
               num_hidden_layers=1)
    bench["configs"].append({"name": "lm-other", "source": "test",
                             "file": "bench/configs/lm-other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "serve-other", "config": "lm-other",
                               "traffic": "short-only", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "serve-smoke" in m["workloads"]:
            m["workloads"].append("serve-other")
    bench["per_layer"].append({"name": "requests_seen", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "admission", "moves": "ttft_p95_ms",
                               "workloads": ["serve-other"]})
    mixes = dict(sc.MIXES, **{"short-only": dict(
        sc.MIXES["chat-smoke"], prompt={"dist": "choice", "values": [8]})})
    cells = dict(sc.CELLS, **{"serve-other": sc.CELLS["serve-smoke"]})
    root = sc.write_root(str(tmp_path), bench=bench, cells=cells,
                         configs=(sc.CNN, sc.LM, lm2), mixes=mixes)
    (tmp_path / "bench" / "metrics" / "requests_seen.py").write_text(
        "def read(ctx):\n    return ctx['counters'].get('requests')\n")
    line = run_smoke(root, "serve-other")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "ttft_p95_ms", "itl_p95_ms",
                                    "serve_tokens_per_s"}
    spec = harness.load_cell("serve-other", root)
    assert [m["name"] for m in spec["per_layer"]] == ["requests_seen"]
    assert harness.read_metric(spec, "requests_seen",
                               {"counters": {"requests": 7}}) == 7.0
    assert harness.read_metric(spec, "requests_seen", {"counters": {}}) is None


def test_the_committed_benchmark_names_existing_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        spec = harness.load_cell(w["name"], REPO)
        assert spec["cell"]["driver"] in ("fl", "serve")
        for m in spec["per_layer"]:
            assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                               m["name"] + ".py"))
