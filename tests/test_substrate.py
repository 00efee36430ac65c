"""Substrate tests: optimizers, data partitioners, checkpointing, sharding
rules, latency model."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro.checkpoint import load_pytree, save_pytree
from repro.core.latency import WirelessConfig, comm_latency, device_rates
from repro.data import (make_fmnist_like, partition_dirichlet, partition_iid,
                        partition_noniid_classes)
from repro.launch.mesh import make_host_mesh
from repro.optim import adamw, apply_updates, clip_by_global_norm, sgd
from repro.sharding.rules import Rules, logical_axes_for


# -- optimizers -----------------------------------------------------------
def _rosenbrock_ish(params):
    return jnp.sum((params["a"] - 1.0) ** 2) + jnp.sum(params["b"] ** 2)


def test_sgd_and_adamw_converge():
    for opt in (sgd(0.1, momentum=0.9), adamw(0.1)):
        params = {"a": jnp.zeros(3), "b": jnp.ones(2)}
        state = opt.init(params)
        for _ in range(200):
            g = jax.grad(_rosenbrock_ish)(params)
            upd, state = opt.update(g, state, params)
            params = apply_updates(params, upd)
        assert float(_rosenbrock_ish(params)) < 1e-3


def test_clip_by_global_norm():
    g = {"a": jnp.full(4, 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(norm), 20.0)
    total = float(jnp.sqrt(jnp.sum(jnp.square(clipped["a"]))))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)


# -- data -----------------------------------------------------------------
def test_fmnist_like_is_learnable_and_separable():
    d = make_fmnist_like(2000, 500, seed=0)
    assert d["x_train"].shape == (2000, 28, 28, 1)
    # nearest-class-mean classifier must beat chance by a wide margin
    means = np.stack([d["x_train"][d["y_train"] == c].mean(0).ravel()
                      for c in range(10)])
    xt = d["x_test"].reshape(len(d["y_test"]), -1)
    pred = np.argmin(((xt[:, None] - means[None]) ** 2).sum(-1), axis=1)
    acc = (pred == d["y_test"]).mean()
    assert acc > 0.3, acc


def test_partitions_cover_and_disjoint_iid():
    parts = partition_iid(1000, 10, seed=0)
    allidx = np.concatenate(parts)
    assert len(allidx) == 1000 and len(set(allidx.tolist())) == 1000


def test_noniid_two_class_property():
    d = make_fmnist_like(5000, 100, seed=1)
    parts = partition_noniid_classes(d["y_train"], 20, 2, seed=1)
    for p in parts:
        classes = set(d["y_train"][p].tolist())
        assert len(classes) <= 2


@settings(max_examples=10, deadline=None)
@given(st.floats(0.05, 5.0))
def test_dirichlet_partition_valid(alpha):
    labels = np.random.RandomState(0).randint(0, 10, 2000)
    parts = partition_dirichlet(labels, 8, alpha, seed=3)
    allidx = np.concatenate([p for p in parts if len(p)])
    assert len(allidx) == len(set(allidx.tolist()))


# -- checkpoint -----------------------------------------------------------
def test_checkpoint_roundtrip():
    tree = {"layers": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
            "step": jnp.int32(7)}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.msgpack")
        save_pytree(path, tree)
        out = load_pytree(path, tree)
    np.testing.assert_array_equal(np.asarray(out["layers"]["w"]),
                                  np.asarray(tree["layers"]["w"]))
    assert int(out["step"]) == 7


# -- compile cache -----------------------------------------------------------
def test_compile_cache_dir(monkeypatch):
    """An exported JAX_COMPILATION_CACHE_DIR is left alone; otherwise the
    cache goes to the fixed <checkout>/.jax_cache."""
    from repro.launch import cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cache.ENV_VAR, "/elsewhere/cache")
        assert cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv(cache.ENV_VAR)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cache.enable_compile_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == cache.DEFAULT_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# -- sharding rules --------------------------------------------------------
def test_spec_drops_nondivisible_axes():
    mesh = make_host_mesh(data=1, model=1)
    rules = Rules(mesh, mapping={"heads": "model"})
    # 9 heads on 1-way model axis: divisible, kept
    assert rules.spec(("batch", "heads"), (4, 9))[1] == "model"

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 4, "model": 16}
    r2 = Rules.__new__(Rules)
    r2.mesh = FakeMesh()
    r2.mapping = dict({"batch": "data", "heads": "model"})
    spec = r2.spec(("batch", "heads"), (8, 9))
    assert spec[1] is None          # 9 % 16 != 0 -> dropped
    spec2 = r2.spec(("batch", "heads"), (8, 48))
    assert spec2[1] == "model"


def test_logical_axes_for_param_names():
    assert logical_axes_for("layers/attn/wq", 3)[0] == "stack"
    assert logical_axes_for("layers/moe/e_gate", 4) == \
        ("stack", "experts", None, None)
    assert logical_axes_for("embed", 2) == ("vocab", "d_model")


# -- latency model ----------------------------------------------------------
def test_wireless_rates_monotone_in_radius():
    rng = np.random.RandomState(0)
    near = device_rates(500, WirelessConfig(radius_m=100.0), rng)[1].mean()
    far = device_rates(500, WirelessConfig(radius_m=1000.0),
                       np.random.RandomState(0))[1].mean()
    assert near > far


def test_comm_latency_scales_with_bytes():
    assert comm_latency(2e6, 1e6) == 2.0
    assert comm_latency(1e6, 1e6) < comm_latency(4e6, 1e6)
