"""Per-architecture smoke tests (deliverable f).

Each assigned architecture is instantiated in its REDUCED variant (<=2
layers after any leading dense ones, d_model<=512, <=4 experts held) and
runs one forward + one train step on CPU, asserting output shapes and absence of NaNs.  Full-scale configs are
only exercised through the dry-run (ShapeDtypeStruct, no allocation).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config, get_smoke_config
from repro.models import transformer as T

TRANSFORMER_ARCHS = [a for a in ARCH_IDS if a != "fmnist_cnn"]


def _batch_for(cfg, B=2, S=32, seed=0):
    rng = np.random.RandomState(seed)
    batch = {"tokens": jnp.asarray(
        rng.randint(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.randn(B, cfg.enc_seq, cfg.d_model), jnp.float32)
    if cfg.n_patches:
        batch["patches"] = jnp.asarray(
            rng.randn(B, cfg.n_patches, cfg.d_model), jnp.float32)
    return batch


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_smoke_forward_shapes_no_nans(arch, key):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers - cfg.first_k_dense <= 2 and cfg.d_model <= 512
    if cfg.is_moe:
        lo, hi = cfg.held_experts
        assert hi - lo <= 4
    params = T.init_model(key, cfg)
    batch = _batch_for(cfg)
    logits, aux = T.forward(params, batch, cfg)
    B, S = batch["tokens"].shape
    assert logits.shape == (B, S, cfg.vocab)
    assert logits.dtype == jnp.float32
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_smoke_train_step(arch, key):
    """One SGD step must produce finite loss and changed, finite params."""
    cfg = get_smoke_config(arch)
    params = T.init_model(key, cfg)
    batch = _batch_for(cfg)

    @jax.jit
    def step(p):
        loss, grads = jax.value_and_grad(
            lambda q: T.lm_loss(q, batch, cfg)[0])(p)
        return loss, jax.tree.map(lambda a, g: a - 1e-2 * g, p, grads)

    loss0, params1 = step(params)
    loss1, _ = step(params1)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params1)):
        assert np.all(np.isfinite(np.asarray(b)))
    # embedding must have moved
    assert float(jnp.abs(params1["embed"] - params["embed"]).max()) > 0


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_smoke_decode_step(arch, key):
    cfg = get_smoke_config(arch)
    params = T.init_model(key, cfg)
    cache = T.init_decode_state(cfg, batch=2, cache_len=16, dtype=jnp.float32)
    tok = jnp.ones((2, 1), jnp.int32)
    logits, cache2 = T.decode_step(params, tok, jnp.int32(0), cfg, cache)
    assert logits.shape == (2, 1, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())
    # cache structure is preserved
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(cache2)


@pytest.mark.parametrize("arch", TRANSFORMER_ARCHS)
def test_full_config_matches_assignment(arch):
    """Spot-check the full-scale configs against the assignment sheet."""
    cfg = get_config(arch)
    expected = {
        "phi3_5_moe_42b": (32, 4096, 32, 8, 6400, 32064, 16, 2),
        "jamba_v0_1_52b": (32, 4096, 32, 8, 14336, 65536, 16, 2),
        "smollm_135m": (30, 576, 9, 3, 1536, 49152, 0, 0),
        "internvl2_2b": (24, 2048, 16, 8, 8192, 92553, 0, 0),
        "whisper_tiny": (4, 384, 6, 6, 1536, 51865, 0, 0),
        "mamba2_370m": (48, 1024, 16, 16, 0, 50280, 0, 0),
        "llama4_scout_17b": (48, 5120, 40, 8, 8192, 202048, 16, 1),
        "moonshot_v1_16b": (27, 2048, 16, 16, 1408, 163840, 64, 6),
        "granite_34b": (88, 6144, 48, 1, 24576, 49152, 0, 0),
        "qwen3_1_7b": (28, 2048, 16, 8, 6144, 151936, 0, 0),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.expert_d_ff, cfg.vocab, cfg.n_experts, cfg.moe_top_k)
    assert got == expected


def test_mamba2_has_assigned_state():
    assert get_config("mamba2_370m").ssm_state == 128


def test_qwen3_has_qk_norm():
    assert get_config("qwen3_1_7b").qk_norm


def test_param_counts_in_expected_range():
    """Analytic param counts should be in the ballpark of the model names."""
    checks = {
        "smollm_135m": (0.10e9, 0.20e9),
        "qwen3_1_7b": (1.2e9, 2.4e9),
        "mamba2_370m": (0.25e9, 0.50e9),
        "granite_34b": (30e9, 40e9),
        "phi3_5_moe_42b": (38e9, 46e9),
        "moonshot_v1_16b": (15.9e9, 16.1e9),     # all 64 routed experts
    }
    for arch, (lo, hi) in checks.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:.3e} not in [{lo:.1e},{hi:.1e}]"
