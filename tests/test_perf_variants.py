"""Correctness of the §Perf optimization variants: int8 KV cache,
sequence-sharded MQA decode, chunked-vocab loss."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ["granite_34b", "qwen3_1_7b"])
def test_int8_kv_cache_decode_close(arch):
    cfg = get_smoke_config(arch)
    params = T.init_model(KEY, cfg)
    S = 10
    toks = jnp.asarray(np.random.RandomState(1).randint(0, cfg.vocab, (2, S)),
                       jnp.int32)
    full, _ = T.forward(params, {"tokens": toks}, cfg)
    cache = T.init_decode_state(cfg, 2, S, dtype=jnp.float32, quantized=True)
    errs = []
    for t in range(S):
        dl, cache = T.decode_step(params, toks[:, t:t + 1], jnp.int32(t),
                                  cfg, cache)
        errs.append(float(jnp.abs(dl[:, 0] - full[:, t]).max()))
    # int8 KV: quantization-level tolerance, far tighter than logit scale
    scale = float(jnp.abs(full).max())
    assert max(errs) < 0.05 * scale


def test_chunked_loss_matches_dense():
    cfg = get_smoke_config("qwen3_1_7b")
    params = T.init_model(KEY, cfg)
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab, (2, 33)), jnp.int32)}
    l_dense, _ = T.lm_loss(params, batch, cfg)
    l_chunk, _ = T.lm_loss(params, batch, cfg, loss_chunk=8)  # ragged: 32/8
    np.testing.assert_allclose(float(l_dense), float(l_chunk), rtol=1e-5)
    # gradients agree too
    g1 = jax.grad(lambda p: T.lm_loss(p, batch, cfg)[0])(params)
    g2 = jax.grad(lambda p: T.lm_loss(p, batch, cfg, loss_chunk=8)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_chunked_loss_vlm():
    cfg = get_smoke_config("internvl2_2b")
    params = T.init_model(KEY, cfg)
    rng = np.random.RandomState(3)
    batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (2, 17)), jnp.int32),
             "patches": jnp.asarray(rng.randn(2, cfg.n_patches, cfg.d_model),
                                    jnp.float32)}
    l_dense, _ = T.lm_loss(params, batch, cfg)
    l_chunk, _ = T.lm_loss(params, batch, cfg, loss_chunk=4)
    np.testing.assert_allclose(float(l_dense), float(l_chunk), rtol=1e-5)


SEQSHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.sharding.rules import Rules, use_rules

cfg = get_smoke_config("granite_34b")
params = T.init_model(jax.random.PRNGKey(0), cfg)
S = 16
toks = jnp.asarray(np.random.RandomState(1).randint(0, cfg.vocab, (4, S)), jnp.int32)
full, _ = T.forward(params, {"tokens": toks}, cfg)
mesh = make_host_mesh(data=2, model=2)
cache = T.init_decode_state(cfg, 4, S, dtype=jnp.float32)
errs = []
with use_rules(Rules(mesh)), mesh:
    step = jax.jit(lambda p, t, pos, c: T.decode_step(p, t, pos, cfg, c, seq_shard_kv=True))
    for t in range(S):
        dl, cache = step(params, toks[:, t:t+1], jnp.int32(t), cache)
        errs.append(float(jnp.abs(dl[:,0]-full[:,t]).max()))
assert max(errs) < 5e-4, max(errs)
print("OK")
"""


def test_seqshard_decode_subprocess():
    r = subprocess.run([sys.executable, "-c", SEQSHARD_SCRIPT],
                       capture_output=True, text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
