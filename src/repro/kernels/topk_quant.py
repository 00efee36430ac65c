"""Pallas TPU kernel: fused block Top-K sparsification + int8/int4 quantization.

The paper's wire-compression hot spot (Alg. 3), TPU-adapted: instead of a
global sort (hostile to the VPU/MXU), each VMEM block finds its magnitude
threshold with a fixed-iteration binary search (vector compares + reductions
only), masks, and quantizes with a per-block max-abs scale.  Block-local K
approximates global Top-K; the approximation error is bounded by inter-block
magnitude skew and measured in tests/test_kernels.py.

Layout: x is reshaped to (M, BLOCK) with M padded to a multiple of 8;
grid = (M / 8,); each program compresses eight BLOCK-sized rows resident in
VMEM, one threshold and scale per row (the (8, 128) TPU tile: BLOCK must be
a multiple of 128).  Outputs: int8 levels (M, BLOCK) and f32 scales (M, 1).

In the FL stack this kernel is subsumed by the codec seam
(``repro.core.codecs``): ``ThresholdGraphCodec`` applies the same
binary-search threshold channel in-graph for the vectorized cohort trainer,
and ``PackedBitstreamCodec`` + ``repro.kernels.bitpack`` serialize the
quantized stream into actual wire bytes.  ``topk_quant`` remains the
block-local TPU formulation used by ``repro.kernels.ops.compress_roundtrip``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret

DEFAULT_BLOCK = 16384          # 64 KiB f32 per block — comfortably in VMEM
_ROWS = 8                      # sublanes of one TPU tile: rows per program


def _kernel(x_ref, levels_ref, scale_ref, *, p_s: float, bits: int,
            iters: int):
    x = x_ref[...].astype(jnp.float32)              # (_ROWS, BLOCK)
    ax = jnp.abs(x)
    hi0 = jnp.max(ax, axis=1, keepdims=True) + 1e-12
    lo0 = jnp.zeros_like(hi0)

    def body(_, lh):
        lo, hi = lh
        mid = 0.5 * (lo + hi)
        frac = jnp.mean((ax >= mid).astype(jnp.float32), axis=1,
                        keepdims=True)
        keep = frac > p_s
        return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo0, hi0))
    thr = 0.5 * (lo + hi)
    kept = jnp.where(ax >= thr, x, 0.0)
    L = 2 ** (bits - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(kept), axis=1, keepdims=True), 1e-12)
    levels = jnp.clip(jnp.round(kept / scale * L), -L, L)
    levels_ref[...] = levels.astype(jnp.int8)
    scale_ref[...] = scale


def topk_quant(x: jax.Array, *, p_s: float = 0.25, bits: int = 8,
               iters: int = 16, block: int = DEFAULT_BLOCK,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Compress a flat array: -> (levels int8 (M,block), scales f32 (M,1)).

    Pads x up to a multiple of ``block``; ``block`` must be a multiple of
    128 (the TPU lane width).  ``interpret=None`` runs the Pallas
    interpreter on the CPU backend and the native kernel elsewhere.
    """
    if block % 128:
        raise ValueError(f"block must be a multiple of 128, got {block}")
    return _topk_quant_call(x, p_s=p_s, bits=bits, iters=iters, block=block,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("p_s", "bits", "iters", "block",
                                    "interpret"))
def _topk_quant_call(x: jax.Array, *, p_s: float, bits: int, iters: int,
                     block: int, interpret: bool
                     ) -> Tuple[jax.Array, jax.Array]:
    n = x.size
    m = -(-n // block)
    mp = -(-m // _ROWS) * _ROWS
    xp = jnp.zeros((mp * block,), x.dtype).at[:n].set(x.reshape(-1))
    xp = xp.reshape(mp, block)

    kern = functools.partial(_kernel, p_s=p_s, bits=bits, iters=iters)
    levels, scales = pl.pallas_call(
        kern,
        grid=(mp // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((_ROWS, block), lambda i: (i, 0)),
                   pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, block), jnp.int8),
                   jax.ShapeDtypeStruct((mp, 1), jnp.float32)],
        interpret=interpret,
    )(xp)
    return levels[:m], scales[:m]


def dequant(levels: jax.Array, scales: jax.Array, bits: int,
            n: int, shape) -> jax.Array:
    L = 2 ** (bits - 1) - 1
    flat = (levels.astype(jnp.float32) * scales / L).reshape(-1)[:n]
    return flat.reshape(shape)
