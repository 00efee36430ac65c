"""Datacenter fed round (core/fed_step.py): math, schedules, mesh equivalence."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.fed_step import (FedConfig, approx_topk_threshold,
                                 compress_delta, decompress_delta,
                                 fed_wire_bytes, make_fed_train_step)
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def _setup(schedule="gather_q", n_groups=4, local_steps=2):
    cfg = get_smoke_config("smollm_135m")
    params = T.init_model(KEY, cfg)
    loss_fn = lambda p, b: T.lm_loss(p, b, cfg)[0]
    fed = FedConfig(n_groups=n_groups, local_steps=local_steps, lr=1e-2,
                    schedule=schedule)
    step = jax.jit(make_fed_train_step(loss_fn, fed))
    rng = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab, (n_groups * local_steps * 2, 32)), jnp.int32)}
    stale = jnp.zeros((n_groups,), jnp.int32)
    return params, step, batch, stale


def test_fed_round_reduces_loss():
    params, step, batch, stale = _setup()
    p, losses = params, []
    for _ in range(6):
        p, m = step(p, batch, stale)
        losses.append(float(m["local_loss"]))
    assert losses[-1] < losses[0] - 0.02


def test_staleness_shrinks_mixing():
    params, step, batch, _ = _setup()
    _, m_fresh = step(params, batch, jnp.zeros(4, jnp.int32))
    _, m_stale = step(params, batch, jnp.full(4, 8, jnp.int32))
    assert float(m_stale["alpha_t"]) < float(m_fresh["alpha_t"])
    np.testing.assert_allclose(float(m_fresh["alpha_t"]), 0.6, atol=1e-5)
    np.testing.assert_allclose(float(m_stale["alpha_t"]), 0.6 * 9 ** -0.5,
                               atol=1e-5)


def test_schedules_agree_up_to_quantization():
    params, step_q, batch, stale = _setup("gather_q")
    _, step_f, _, _ = _setup("gather_f32")
    _, step_p, _, _ = _setup("psum")
    pq, _ = step_q(params, batch, stale)
    pf, _ = step_f(params, batch, stale)
    pp, _ = step_p(params, batch, stale)
    # exact: psum == gather_f32 (same math)
    for a, b in zip(jax.tree.leaves(pf), jax.tree.leaves(pp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    # lossy: gather_q within quantization error of f32
    for a, b in zip(jax.tree.leaves(pq), jax.tree.leaves(pf)):
        assert float(jnp.abs(a - b).max()) < 5e-4


def test_approx_topk_threshold_accuracy():
    x = jnp.abs(jnp.asarray(np.random.RandomState(1).randn(100000)
                            .astype(np.float32)))
    for p_s in (0.05, 0.25, 0.5):
        thr = approx_topk_threshold(x, p_s, iters=16)
        frac = float((x >= thr).mean())
        assert abs(frac - p_s) < 0.01


def test_compress_delta_roundtrip_error():
    fed = FedConfig(p_s=0.5, p_q=8)
    x = jnp.asarray(np.random.RandomState(2).randn(4096).astype(np.float32))
    lv, sc = compress_delta(x, fed)
    assert lv.dtype == jnp.int8
    y = decompress_delta(lv, sc, fed, jnp.float32)
    kept = np.abs(np.asarray(x)) >= np.quantile(np.abs(np.asarray(x)), 0.5)
    np.testing.assert_allclose(np.asarray(y)[kept], np.asarray(x)[kept],
                               atol=float(sc) / 127 + 1e-5)


def test_wire_bytes_math():
    params = {"w": jnp.zeros((1000,))}
    wb = fed_wire_bytes(params, FedConfig(p_s=0.25, p_q=8), n_groups=8)
    assert wb["dense_f32"] == 4 * 1000 * 8
    assert wb["dense_quant"] == 1000 * 8
    assert wb["compression_x"] > 5


MESH_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_smoke_config
from repro.models import transformer as T
from repro.core.fed_step import FedConfig, make_fed_train_step
from repro.launch.mesh import make_host_mesh
from repro.sharding.rules import Rules, use_rules, param_shardings
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

cfg = get_smoke_config("phi3_5_moe_42b")  # exercises MoE EP path too
params = T.init_model(jax.random.PRNGKey(0), cfg)
loss_fn = lambda p, b: T.lm_loss(p, b, cfg)[0]
fed = FedConfig(n_groups=2, local_steps=1, lr=1e-2, schedule="gather_q")
step = make_fed_train_step(loss_fn, fed)
rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (4, 32)), jnp.int32)}
stale = jnp.asarray([0, 2], jnp.int32)

# no-mesh reference
p_ref, m_ref = jax.jit(step)(params, batch, stale)

# 2x2 mesh (data=fed groups, model=TP/EP)
mesh = make_host_mesh(data=2, model=2)
rules = Rules(mesh)
with use_rules(rules), mesh:
    p_mesh, m_mesh = jax.jit(step)(params, batch, stale)

errs = [float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_mesh))]
print("MAXERR", max(errs))
print("LOSSDIFF", abs(float(m_ref["local_loss"]) - float(m_mesh["local_loss"])))
assert max(errs) < 5e-3, errs
# The loss *metric* is looser than the params: the EP path drops tokens at
# finite expert capacity while _moe_dense_ref routes every token (no drops),
# so at smoke scale (128 tokens) the reported local_loss differs by ~1e-2
# even though the trained params agree to ~5e-5 above.
assert abs(float(m_ref["local_loss"]) - float(m_mesh["local_loss"])) < 2e-2
print("OK")
"""


def test_mesh_equivalence_subprocess():
    """The sharded fed round (shard_map gather + MoE EP) must match the
    no-mesh reference.  Runs in a subprocess because the 4-device host
    platform flag must be set before jax initializes."""
    r = subprocess.run([sys.executable, "-c", MESH_EQUIV_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
