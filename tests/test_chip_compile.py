"""Compiles for a TPU v5e chip that is described, not attached.

Every Pallas kernel under ``src/repro/kernels/``, the cohort trainer's
jitted round and the serving decode step are compiled by the TPU compiler at the main path's real
sizes, so a kernel the chip would refuse (an unaligned block, a primitive
Mosaic cannot lower, more VMEM than a kernel may hold) fails here, on the
CPU.  A compile that passes is not a chip run: nothing executes.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library.
"""
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.compression import topk_count
from repro.fl.engine import _cohort_round
from repro.fl.simulator import SimConfig
from repro.fl.tasks import get_task
from repro.kernels import fused_pack, ssd_scan, topk_quant
from repro.launch import serve
from repro.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FC1 = 200704                    # fmnist_cnn's largest leaf (fc1 weight)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compile cache off: an
    executable compiled for an absent chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [FC1, 1000])
def test_fused_pack_compiles(one_chip, n):
    """The CNN's largest leaf, and a small leaf that is not a whole (8, 128)
    tile (the padded-tail path)."""
    compiled = fused_pack._fused_pack_call.lower(
        _spec(one_chip, (n,)), k=topk_count(n, 0.1), p_q=8,
        interpret=False).compile()
    assert _has_kernel(compiled)


def test_topk_quant_compiles(one_chip):
    compiled = jax.jit(lambda x: topk_quant.topk_quant(
        x, p_s=0.1, bits=8, interpret=False)).lower(
            _spec(one_chip, (FC1,))).compile()
    assert _has_kernel(compiled)


def test_ssd_scan_compiles(one_chip):
    """mamba2-370m's SSD widths: 32 heads of 64, state 128, chunk 256."""
    B, S, H, P, N = 1, 512, 32, 64, 128
    f = jax.jit(lambda xh, b, c, dt, la: ssd_scan.ssd_chunked_pallas(
        xh, b, c, dt, la, 256, interpret=False))
    compiled = f.lower(_spec(one_chip, (B, S, H, P)),
                       _spec(one_chip, (B, S, N)), _spec(one_chip, (B, S, N)),
                       _spec(one_chip, (B, S, H)),
                       _spec(one_chip, (B, S, H))).compile()
    assert _has_kernel(compiled)


def test_cohort_round_compiles_at_paper_size(one_chip):
    """fmnist_cnn at §5.1 scale: 100 devices x 600 samples, a 16-device
    cohort over 4 model versions, 32 prox-SGD steps of batch 40."""
    cfg = SimConfig()
    task = get_task("fmnist_cnn")
    V, C, T, N, n = 4, 16, 32, 100, 600
    w = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
    w_versions = jax.tree.map(
        lambda a: _spec(one_chip, (V,) + a.shape, a.dtype), w)
    compiled = _cohort_round.lower(
        w_versions, _spec(one_chip, (C,), jnp.int32),
        _spec(one_chip, (N, n, 28, 28, 1)), _spec(one_chip, (N, n), jnp.int32),
        _spec(one_chip, (C,), jnp.int32),
        _spec(one_chip, (T, C, cfg.batch_size), jnp.int32),
        _spec(one_chip, (T, C)), cohort_loss=task.cohort_loss, lr=cfg.lr,
        mu=cfg.mu, p_s=0.25, p_q=8, iters=cfg.cohort_channel_iters).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


def test_batched_decode_updates_the_cache_in_place(one_chip):
    """qwen3-1.7b's batched decode step at the chat cell's 32 slots of
    1536 positions: the output cache aliases the donated input, and the
    step needs no temporary as large as one layer's K slab (the vmap of
    the scalar-position step copied slabs and needed 101.9 MB)."""
    cfg = get_config("qwen3-1.7b")
    B, S = 32, 1536
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg,
                                            jnp.bfloat16)))
    kv = _spec(one_chip, (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim),
               jnp.bfloat16)
    compiled = serve._batched_step(cfg).lower(
        params, _spec(one_chip, (B, 1), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), {"k": kv, "v": kv}).compile()
    mem = compiled.memory_analysis()
    cache_bytes = 2 * math.prod(kv.shape) * 2
    slab_bytes = B * S * cfg.n_kv_heads * cfg.head_dim * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < slab_bytes
    assert compiled.as_text().startswith("HloModule jit_step,")


def test_moonlight_decode_updates_the_latent_cache_in_place(one_chip):
    """Moonlight-16B-A3B on one chip's share (experts 0-7 of 64) at its
    cell's 64 slots of 1536 positions: the output latent cache aliases the
    donated input, the step needs no temporary as large as one layer's
    latent slab, and the held experts run as grouped matmuls."""
    cfg = get_config("moonshot_v1_16b")
    B, S = 64, 1536
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg,
                                            jnp.bfloat16)))
    cache = {"c_kv": _spec(one_chip, (cfg.n_layers, B, S, cfg.kv_lora_rank),
                           jnp.bfloat16),
             "k_pe": _spec(one_chip, (cfg.n_layers, B, S, cfg.qk_rope_dim),
                           jnp.bfloat16)}
    compiled = serve._batched_step(cfg).lower(
        params, _spec(one_chip, (B, 1), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), cache,
        _spec(one_chip, (2,), jnp.uint32),
        _spec(one_chip, (B,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(math.prod(c.shape) * 2 for c in cache.values())
    slab_bytes = B * S * cfg.kv_lora_rank * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < slab_bytes
    assert compiled.as_text().startswith("HloModule jit_step,")
    assert _has_kernel(compiled)


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke script exits non-zero and prints no result."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr
