"""Sharded staleness-weighted aggregation (``SERVERS["sharded"]``).

Parity contract under test, layer by layer:

* ``aggregate_cache_sharded_ref`` (the mesh-free column-block reference)
  computes the SAME per-element program as the single-host stacked kernel
  — weights and the mixing factor are recomputed identically inside every
  block — so it must match ``aggregate_cache_stacked`` to <= 1 ulp.  The
  observed difference is 0 ulp on this container; the 1-ulp allowance
  only covers XLA re-fusing the identical scalar program differently
  across compiler versions, not any real reassociation.
* Against the *serial* K-tuple kernel (``aggregate_cache``) the stacked
  reduction legitimately reassociates (tensordot vs sequential adds), so
  the comparison is allclose — the same tolerance the wave-mode
  ``receive_many`` unit test uses.
* ``ShardedTeasqServer`` on ONE device builds no mesh and delegates to
  the parent's exact kernels, so ``server="sharded"`` on a single-device
  process replays the pinned history fixture bit-for-bit.
* On a real multi-device host mesh (``--xla_force_host_platform_
  device_count``, set before jax init, hence the subprocess) the
  ``shard_map`` path must hold the same <= 1-ulp bound against the
  stacked kernel across mesh sizes {1, 2, 4}, and end-to-end engine runs
  with ``server="sharded"`` must keep the event timeline (rounds, times,
  byte meters) exactly while weights stay allclose.
"""
import dataclasses
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import (PINNED_PATH, TINY_SETUP, assert_histories_equal,
                      run_tiny)
from repro.core.server import (SERVERS, ServerConfig, ShardedTeasqServer,
                               TeasqServer, make_server)
from repro.core.staleness import (aggregate_cache, aggregate_cache_sharded_ref,
                                  aggregate_cache_stacked)

try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:        # the fixed grid below still pins the parity
    HAVE_HYPOTHESIS = False


def max_ulp_diff(a, b):
    """Largest per-element distance in float32 units-in-the-last-place.

    Bit patterns are mapped to a monotonic integer ordering of the reals
    (negative floats mirrored below zero, -0.0 == +0.0), so adjacent
    representable floats differ by exactly 1 and the comparison is scale-
    free — unlike an epsilon, 1 ulp means "the same computation modulo
    one final rounding", which is the strongest cross-compiler statement
    short of bit equality."""
    ia = np.asarray(a, np.float32).ravel().view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).ravel().view(np.int32).astype(np.int64)
    la = np.where(ia >= 0, ia, np.int64(-2 ** 31) - ia)
    lb = np.where(ib >= 0, ib, np.int64(-2 ** 31) - ib)
    return int(np.abs(la - lb).max()) if la.size else 0


def _tree_ulp(t_a, t_b):
    return max(max_ulp_diff(a, b) for a, b in
               zip(jax.tree.leaves(t_a), jax.tree.leaves(t_b)))


def _rand_tree(rng, shapes=((13, 7), (5,))):
    return {f"l{i}": rng.randn(*sh).astype(np.float32)
            for i, sh in enumerate(shapes)}


def _rand_cache(rng, size, shapes=((13, 7), (5,))):
    return [(_rand_tree(rng, shapes), int(rng.randint(0, 5)),
             int(rng.randint(1, 200))) for _ in range(size)]


# ----------------------------------------------------------------------
# registry + construction
# ----------------------------------------------------------------------
@pytest.mark.smoke
def test_servers_registry():
    assert SERVERS["single"] is TeasqServer
    assert SERVERS["sharded"] is ShardedTeasqServer
    cfg = ServerConfig(n_devices=10)
    w0 = {"w": np.zeros(3, np.float32)}
    assert type(make_server("single", w0, cfg)) is TeasqServer
    srv = make_server("sharded", w0, cfg, shards=1)
    assert type(srv) is ShardedTeasqServer
    with pytest.raises(ValueError, match="unknown server"):
        make_server("bogus", w0, cfg)


@pytest.mark.smoke
def test_degenerate_sharded_has_no_mesh():
    """shards=1 (or a single-device process) must build no mesh and route
    both aggregation hooks to the parent's exact kernels."""
    srv = make_server("sharded", {"w": np.zeros(3, np.float32)},
                      ServerConfig(n_devices=10), shards=1)
    assert srv.n_shards == 1
    assert srv.mesh is None and srv._agg is None


@pytest.mark.smoke
def test_sharded_rejects_more_shards_than_devices():
    """A mesh wider than the process's devices is refused, not capped."""
    want = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"server_shards={want}"):
        make_server("sharded", {"w": np.zeros(3, np.float32)},
                    ServerConfig(n_devices=10), shards=want)


def test_engine_rejects_unknown_server(tiny_setup):
    from repro.fl.protocols import make_sim
    from repro.fl.simulator import SimConfig
    data, parts, w0 = tiny_setup
    cfg = SimConfig(n_devices=len(parts), server="bogus")
    with pytest.raises(ValueError, match="unknown server"):
        make_sim(data, parts, w0, cfg)


# ----------------------------------------------------------------------
# kernel parity: mesh-free column-block reference vs the pinned kernels
# ----------------------------------------------------------------------
@pytest.mark.smoke
@pytest.mark.parametrize("cache_size", [1, 2, 3, 5, 8])
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_sharded_ref_matches_stacked_kernel(cache_size, n_shards):
    """Column-block sharding vs the single-host stacked kernel: <= 1 ulp
    (0 observed — see the module docstring), every (cache, mesh) size
    including ones that force zero-padding of the flat vector."""
    rng = np.random.RandomState(cache_size * 10 + n_shards)
    w0 = _rand_tree(rng)
    cache = _rand_cache(rng, cache_size)
    want = aggregate_cache_stacked(w0, cache, t=6, alpha=0.6, a=0.5)
    got = aggregate_cache_sharded_ref(w0, cache, t=6, alpha=0.6, a=0.5,
                                      n_shards=n_shards)
    assert _tree_ulp(got, want) <= 1


@pytest.mark.smoke
def test_sharded_ref_close_to_serial_kernel():
    """Against the serial K-tuple kernel the permitted divergence is the
    stacked tensordot reassociation — allclose at the receive_many
    tolerance."""
    rng = np.random.RandomState(0)
    w0 = _rand_tree(rng)
    cache = _rand_cache(rng, 4)
    a = aggregate_cache(w0, cache, t=6, alpha=0.6, a=0.5)
    b = aggregate_cache_sharded_ref(w0, cache, t=6, alpha=0.6, a=0.5,
                                    n_shards=3)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-5, atol=1e-6)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(data=st.data(),
           cache_size=st.integers(min_value=1, max_value=8),
           n_shards=st.integers(min_value=1, max_value=4),
           t=st.integers(min_value=0, max_value=30),
           alpha=st.floats(min_value=0.1, max_value=1.0),
           seed=st.integers(min_value=0, max_value=99))
    def test_sharded_ref_property(data, cache_size, n_shards, t, alpha,
                                  seed):
        """Property form of the grid: hypothesis explores cache sizes,
        staleness vectors, leaf shapes (odd sizes exercise the padding
        path) and mesh widths; a violation shrinks to a minimal cache."""
        rng = np.random.RandomState(seed)
        shapes = ((data.draw(st.integers(1, 9), label="rows"),
                   data.draw(st.integers(1, 9), label="cols")),
                  (data.draw(st.integers(1, 7), label="bias"),))
        w0 = _rand_tree(rng, shapes)
        cache = [(_rand_tree(rng, shapes),
                  data.draw(st.integers(0, t), label=f"h{i}"),
                  data.draw(st.integers(1, 500), label=f"n{i}"))
                 for i in range(cache_size)]
        want = aggregate_cache_stacked(w0, cache, t=t, alpha=alpha, a=0.5)
        got = aggregate_cache_sharded_ref(w0, cache, t=t, alpha=alpha,
                                          a=0.5, n_shards=n_shards)
        assert _tree_ulp(got, want) <= 1


# ----------------------------------------------------------------------
# degenerate mesh: server="sharded" on one device is the pinned machine
# ----------------------------------------------------------------------
_single_device = pytest.mark.skipif(
    len(jax.devices()) > 1,
    reason="degenerate-mesh bit-parity needs a single-device process")


@_single_device
@pytest.mark.parametrize("method", ["teasq", "fedasync"])
def test_engine_degenerate_sharded_bit_identical(method, tiny_setup):
    """End-to-end: the engine with ``server="sharded"`` on one device must
    replay the default server's history bit-for-bit (no mesh -> parent
    kernels)."""
    h_single = run_tiny(method, tiny_setup)
    h_sharded = run_tiny(method, tiny_setup, server="sharded")
    assert_histories_equal(h_single, h_sharded)


@_single_device
def test_degenerate_sharded_repins_fixture(tiny_setup):
    """Directly against the recorded fixture: the sharded backend on one
    device stays on the pinned-history manifold."""
    with open(PINNED_PATH) as f:
        pinned = json.load(f)
    assert pinned["setup"] == TINY_SETUP
    kw = pinned["runs_batched"]["teasq"]
    hist = run_tiny("teasq", tiny_setup, task="fmnist_cnn",
                    **pinned["run_kw"],
                    **{**kw, "scheduler": "batched", "server": "sharded"})
    got = [dataclasses.asdict(h) for h in hist]
    assert got == pinned["histories_batched"]["teasq"]


# ----------------------------------------------------------------------
# real host mesh: shard_map parity across mesh sizes + protocols
# ----------------------------------------------------------------------
MESH_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from repro.core.server import ServerConfig, TeasqServer, make_server
from repro.fl.protocols import make_setup, run_method

assert len(jax.devices()) == 4, jax.devices()

def ulp(t_a, t_b):
    worst = 0
    for a, b in zip(jax.tree.leaves(t_a), jax.tree.leaves(t_b)):
        ia = np.asarray(a, np.float32).ravel().view(np.int32).astype(np.int64)
        ib = np.asarray(b, np.float32).ravel().view(np.int32).astype(np.int64)
        la = np.where(ia >= 0, ia, np.int64(-2 ** 31) - ia)
        lb = np.where(ib >= 0, ib, np.int64(-2 ** 31) - ib)
        worst = max(worst, int(np.abs(la - lb).max()))
    return worst

rng = np.random.RandomState(0)
def tree():
    return {"w1": rng.randn(13, 7).astype(np.float32),
            "b": rng.randn(5).astype(np.float32)}
def copy(t):
    return {k: v.copy() for k, v in t.items()}

# server-level parity: identical entry streams through every mesh width,
# both receive paths, vs single-host servers
cfg = ServerConfig(n_devices=10, gamma=0.3)          # K = 3
w0 = tree()
entries = [(tree(), max(0, i % 4 - 1), 10 + 3 * i) for i in range(8)]
for mesh in (1, 2, 4):
    for wave in (False, True):
        srv = make_server("sharded", copy(w0), cfg, shards=mesh)
        assert srv.n_shards == mesh
        ref = TeasqServer(copy(w0), cfg)              # single-host control
        srv.active = ref.active = len(entries)
        if wave:
            done_s = srv.receive_many(list(entries))
            done_r = ref.receive_many(list(entries))
        else:
            done_s = [srv.receive(*e) for e in entries]
            done_r = [ref.receive(*e) for e in entries]
        assert done_s == done_r and srv.t == ref.t
        if mesh == 1:
            # degenerate: parent kernels, bit-identical on both paths
            assert ulp(srv.w, ref.w) == 0, (wave, ulp(srv.w, ref.w))
        elif wave:
            # flat sharded kernel vs the stacked kernel: same per-element
            # program -> <= 1 ulp (0 observed)
            assert ulp(srv.w, ref.w) <= 1, ulp(srv.w, ref.w)
        else:
            # serial control used the K-tuple kernel: reassociation only
            for a, b in zip(jax.tree.leaves(srv.w), jax.tree.leaves(ref.w)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-6)
            # vs a stacked-kernel control fed the same stream: <= 1 ulp
            ctl = TeasqServer(copy(w0), cfg)
            ctl.active = len(entries)
            ctl.receive_many(list(entries))
            assert ulp(srv.w, ctl.w) <= 1, ulp(srv.w, ctl.w)
print("SERVER-PARITY OK")

# engine-level: full runs per protocol — the event timeline (rounds,
# times, byte meters) must not move when the aggregation is sharded;
# weights/accuracy may differ by the kernel reassociation only
data, parts, w0 = make_setup(n_devices=8, iid=True, seed=3, n_train=320,
                             n_test=160)
for method in ("teasq", "fedasync"):
    runs = {}
    for server in ("single", "sharded"):
        runs[server] = run_method(method, data, parts, w0, time_budget=2.0,
                                  seed=3, epochs=1, server=server,
                                  server_shards=4)
    h_a, h_b = runs["single"], runs["sharded"]
    assert len(h_a) == len(h_b) and len(h_a) >= 2, (method, len(h_a))
    for a, b in zip(h_a, h_b):
        assert (a.time, a.round, a.bytes_up, a.bytes_down) == \
               (b.time, b.round, b.bytes_up, b.bytes_down), method
        assert abs(a.accuracy - b.accuracy) <= 0.05, (method, a, b)
    print(f"ENGINE {method} OK rounds={h_a[-1].round}")
print("OK")
"""


def test_mesh_parity_subprocess():
    """The shard_map aggregation on a real 4-device host mesh: <= 1-ulp
    server parity across mesh sizes {1, 2, 4} on both receive paths, and
    timeline-exact end-to-end engine runs for teasq + fedasync.  Runs in
    a subprocess because the host-device-count flag must be set before
    jax initializes (same pattern as tests/test_fed_step.py)."""
    r = subprocess.run([sys.executable, "-c", MESH_PARITY_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SERVER-PARITY OK" in r.stdout
    assert "OK" in r.stdout.splitlines()[-1]
