"""Pallas kernel validation: shape/dtype sweeps against pure-jnp oracles.

On the CPU backend the kernels run under the Pallas interpreter; the bodies
are the same code that lowers to TPU pallas_call + BlockSpec
(tests/test_chip_compile.py compiles them for a v5e chip).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.fused_pack import fused_pack_leaf, pack_leaves_host
from repro.kernels.ssd_scan import ssd_chunked_pallas, ssd_intra_chunk
from repro.kernels.topk_quant import dequant, topk_quant
from repro.models.ssm import ssd_chunked


# ----------------------------------------------------------------------
# topk_quant
# ----------------------------------------------------------------------
@pytest.mark.parametrize("block", [256, 1024, 4096])
@pytest.mark.parametrize("p_s", [0.05, 0.25, 0.5])
@pytest.mark.parametrize("bits", [8, 4])
def test_topk_quant_matches_oracle(block, p_s, bits):
    rng = np.random.RandomState(hash((block, int(p_s * 100), bits)) % 2**31)
    x = jnp.asarray(rng.randn(4 * block).astype(np.float32))
    lv, sc = topk_quant(x, p_s=p_s, bits=bits, block=block)
    lv_ref, sc_ref = ref.topk_quant_ref(x.reshape(4, block), p_s, bits)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv_ref))
    np.testing.assert_allclose(np.asarray(sc), np.asarray(sc_ref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_quant_dtypes(dtype):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2048).astype(np.float32)).astype(dtype)
    lv, sc = topk_quant(x, p_s=0.25, bits=8, block=1024)
    assert lv.dtype == jnp.int8 and sc.dtype == jnp.float32
    kept = float((lv != 0).mean())
    assert abs(kept - 0.25) < 0.05


def test_topk_quant_keep_fraction_accuracy():
    """Binary-search threshold keeps ~p_s of entries (within 2^-16 + ties)."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(65536).astype(np.float32))
    for p_s in (0.01, 0.1, 0.33):
        lv, _ = topk_quant(x, p_s=p_s, bits=8, block=16384)
        kept = float((lv != 0).mean())
        assert abs(kept - p_s) < 0.02, (p_s, kept)


def test_topk_quant_padding():
    """Non-multiple-of-block sizes are zero-padded, zeros stay zero."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1500).astype(np.float32))
    lv, sc = topk_quant(x, p_s=0.5, bits=8, block=1024)
    y = dequant(lv, sc, 8, 1500, (1500,))
    assert y.shape == (1500,)
    # top values survive the round trip with quantization error only
    idx = np.argsort(-np.abs(np.asarray(x)))[:100]
    scale = np.abs(np.asarray(x)).max()
    np.testing.assert_allclose(np.asarray(y)[idx], np.asarray(x)[idx],
                               atol=scale / 127 * 1.01)


def test_block_topk_vs_global_topk_error_bounded():
    """Block-local Top-K (TPU adaptation) approximates global Top-K: the kept
    mass is close to the globally-optimal kept mass."""
    rng = np.random.RandomState(4)
    x = rng.randn(8, 4096).astype(np.float32) * rng.uniform(0.5, 2.0, (8, 1))
    flat = jnp.asarray(x.reshape(-1))
    lv, sc = topk_quant(flat, p_s=0.25, bits=32 if False else 8, block=4096)
    y = np.asarray(dequant(lv, sc, 8, flat.size, (flat.size,)))
    kept_mass = np.abs(y).sum()
    k = int(0.25 * flat.size)
    global_mass = np.sort(np.abs(x.reshape(-1)))[-k:].sum()
    assert kept_mass >= 0.85 * global_mass


# ----------------------------------------------------------------------
# fused_pack: the one-pass sparsify+quantize+pack emitter.  Always-run
# deterministic grid (the hypothesis suite lives in tests/test_fused_pack);
# interpret mode exercises the exact body that lowers to TPU pallas_call.
# ----------------------------------------------------------------------
@pytest.mark.smoke
@pytest.mark.parametrize("n", [1, 7, 100, 1500, 4097])
@pytest.mark.parametrize("p_s", [0.05, 0.25, 1.0])
@pytest.mark.parametrize("p_q", [2, 8, 32])
def test_fused_pack_kernel_matches_host_twin(n, p_s, p_q):
    """Kernel stream == numpy-twin stream, bit for bit, across odd sizes,
    the k==n dense fallback (p_s=1.0) and raw-f32 values (p_q=32)."""
    rng = np.random.RandomState(hash((n, int(p_s * 100), p_q)) % 2**31)
    x = rng.randn(n).astype(np.float32)
    payload, nbits = fused_pack_leaf(x, p_s, p_q, interpret=True)
    assert payload == pack_leaves_host([x], p_s, p_q)
    assert len(payload) == (nbits + 7) // 8


@pytest.mark.smoke
def test_fused_pack_kernel_tie_and_zero_regimes():
    """Degenerate magnitudes: all-zero tensors (threshold 0, scale floor)
    and heavily-tied data must still match the host twin exactly."""
    for x in (np.zeros(300, np.float32),
              np.tile(np.float32([0.5, -0.5, 0.0]), 100),
              np.full(129, -0.25, np.float32)):
        for p_s in (0.1, 0.5):
            payload, _ = fused_pack_leaf(x, p_s, 8, interpret=True)
            assert payload == pack_leaves_host([x], p_s, 8)


# ----------------------------------------------------------------------
# ssd_scan
# ----------------------------------------------------------------------
def _ssd_inputs(B, S, H, P, N, seed=0):
    rng = np.random.RandomState(seed)
    xh = jnp.asarray(rng.randn(B, S, H, P).astype(np.float32))
    b = jnp.asarray(rng.randn(B, S, N).astype(np.float32)) * 0.3
    c = jnp.asarray(rng.randn(B, S, N).astype(np.float32)) * 0.3
    dt = jnp.abs(jnp.asarray(rng.randn(B, S, H).astype(np.float32))) * 0.1
    la = -jnp.abs(jnp.asarray(rng.randn(B, S, H).astype(np.float32))) * 0.05
    return xh, b, c, dt, la


@pytest.mark.parametrize("chunk", [32, 64, 128])
@pytest.mark.parametrize("N", [16, 32, 128])
def test_ssd_kernel_matches_model(chunk, N):
    xh, b, c, dt, la = _ssd_inputs(2, 256, 2, 64, N)
    y_ref, h_ref = ssd_chunked(xh, b, c, dt, la, chunk)
    y_k, h_k = ssd_chunked_pallas(xh, b, c, dt, la, chunk)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_k),
                               atol=1e-5, rtol=1e-5)


def test_ssd_intra_chunk_matches_chunk_oracle():
    rng = np.random.RandomState(7)
    L, P, N = 64, 32, 16
    xb = jnp.asarray(rng.randn(3, L, P).astype(np.float32))
    b = jnp.asarray(rng.randn(3, L, N).astype(np.float32))
    c = jnp.asarray(rng.randn(3, L, N).astype(np.float32))
    cum = jnp.cumsum(-jnp.abs(jnp.asarray(
        rng.randn(3, L).astype(np.float32))) * 0.1, axis=1)
    y, s, a = ssd_intra_chunk(xb, b, c, cum[:, None, :])
    for g in range(3):
        y_r, s_r, a_r = ref.ssd_chunk_ref(xb[g], b[g], c[g], cum[g])
        np.testing.assert_allclose(np.asarray(y[g]), np.asarray(y_r),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(s[g]), np.asarray(s_r).T
                                   if s_r.shape != s[g].shape else
                                   np.asarray(s_r), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(float(a[g, 0]), float(a_r), rtol=1e-6)


def test_ssd_chunk_size_invariance():
    """SSD output must not depend on the chunking."""
    xh, b, c, dt, la = _ssd_inputs(1, 128, 2, 64, 32, seed=9)
    y1, h1 = ssd_chunked_pallas(xh, b, c, dt, la, 32)
    y2, h2 = ssd_chunked_pallas(xh, b, c, dt, la, 128)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=2e-5, rtol=2e-5)


def test_ssd_kernel_bf16():
    xh, b, c, dt, la = _ssd_inputs(1, 128, 2, 64, 32, seed=11)
    y32, _ = ssd_chunked_pallas(xh, b, c, dt, la, 64)
    y16, _ = ssd_chunked_pallas(xh.astype(jnp.bfloat16), b, c, dt, la, 64)
    assert y16.dtype == jnp.bfloat16
    rel = float(jnp.abs(y32 - y16.astype(jnp.float32)).max()
                / (jnp.abs(y32).max() + 1e-9))
    assert rel < 0.05
