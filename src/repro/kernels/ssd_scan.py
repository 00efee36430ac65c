"""Pallas TPU kernel: Mamba2 SSD intra-chunk scan.

The quadratic within-chunk part of state-space duality is three MXU matmuls
per (batch, head, chunk) cell:
    CB   = C @ B^T                       (L x L)
    y    = (CB ∘ decay ∘ tril) @ X̄      (L x P)
    S_c  = (B ∘ decay_to_end)^T @ X̄     (N x P)
All operands for one grid cell live in VMEM (L=256, P=64, N<=128 =>
< 400 KiB).  The sequential inter-chunk recurrence (h = a h + S_c) stays in
a jax.lax.scan around the kernel — it is O(nc * N * P) and bandwidth-trivial.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_interpret


def _kernel(xb_ref, b_ref, c_ref, row_ref, col_ref, y_ref, s_ref):
    xb = xb_ref[0].astype(jnp.float32)              # (L, P)
    b = b_ref[0].astype(jnp.float32)                # (L, N)
    c = c_ref[0].astype(jnp.float32)                # (L, N)
    # cumulative log-decay twice, as a (1, L) row and an (L, 1) column:
    # the kernel then needs no in-register transpose
    row = row_ref[0].astype(jnp.float32)            # (1, L)
    col = col_ref[0].astype(jnp.float32)            # (L, 1)
    L_ = xb.shape[0]

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # (L, L)
    ii = jax.lax.broadcasted_iota(jnp.int32, (L_, L_), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L_, L_), 1)
    # mask the exponent (upper triangle overflows exp -> inf -> nan grads)
    diff = jnp.where(ii >= jj, col - row, -jnp.inf)
    m = jnp.exp(diff)
    y = (cb * m) @ xb                                          # (L, P)

    d2e = jnp.exp(col[L_ - 1:, :] - col)                       # (L, 1)
    s = jax.lax.dot_general(b * d2e, xb,
                            (((0,), (0,)), ((), ())))          # (N, P)
    y_ref[0] = y.astype(y_ref.dtype)
    s_ref[0] = s.astype(s_ref.dtype)


def ssd_intra_chunk(xb: jax.Array, b: jax.Array, c: jax.Array,
                    cum: jax.Array, *, interpret: Optional[bool] = None
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched intra-chunk SSD.

    xb: (G, L, P) dt-scaled inputs (G = B*H*nc grid cells)
    b, c: (G, L, N); cum: (G, 1, L) cumulative log-decay.
    -> (y (G,L,P) f32, states (G,N,P) f32, chunk_decay (G,1) f32)
    ``interpret=None`` runs the Pallas interpreter on the CPU backend and
    the native kernel elsewhere.
    """
    return _ssd_intra_chunk_call(xb, b, c, cum,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssd_intra_chunk_call(xb, b, c, cum, *, interpret: bool):
    G, L, P = xb.shape
    N = b.shape[-1]
    y, s = pl.pallas_call(
        _kernel,
        grid=(G,),
        in_specs=[pl.BlockSpec((1, L, P), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, L, N), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, L, N), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, L), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, L, 1), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, L, P), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, N, P), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((G, L, P), jnp.float32),
                   jax.ShapeDtypeStruct((G, N, P), jnp.float32)],
        interpret=interpret,
    )(xb, b, c, cum, jnp.swapaxes(cum, 1, 2))
    # the chunk decay is one exp per cell: XLA, not a (1, 1) kernel block
    return y, s, jnp.exp(cum[:, 0, -1:])


def ssd_chunked_pallas(xh, b, c, dt, la, chunk: int, *,
                       interpret: Optional[bool] = None):
    """Drop-in replacement for models.ssm.ssd_chunked using the kernel for
    the intra-chunk quadratic part.  Shapes as in ssd_chunked."""
    B, S, H, P = xh.shape
    N = b.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L

    xb = (xh.astype(jnp.float32) * dt[..., None]).reshape(B, nc, L, H, P)
    cum = jnp.cumsum(la.reshape(B, nc, L, H), axis=2)          # (B,nc,L,H)

    # -> grid cells (B, H, nc, ...)
    xg = jnp.transpose(xb, (0, 3, 1, 2, 4)).reshape(B * H * nc, L, P)
    bg = jnp.broadcast_to(b.reshape(B, 1, nc, L, N),
                          (B, H, nc, L, N)).reshape(-1, L, N)
    cg = jnp.broadcast_to(c.reshape(B, 1, nc, L, N),
                          (B, H, nc, L, N)).reshape(-1, L, N)
    cumg = jnp.transpose(cum, (0, 3, 1, 2)).reshape(-1, 1, L)

    y_i, s_c, a_c = ssd_intra_chunk(xg, bg, cg, cumg, interpret=interpret)
    y_i = y_i.reshape(B, H, nc, L, P)
    s_c = s_c.reshape(B, H, nc, N, P)
    a_c = a_c.reshape(B, H, nc)

    # inter-chunk recurrence (sequential, tiny)
    def scan_body(hprev, inp):
        s_ci, a_ci = inp                                       # (B,H,N,P),(B,H)
        hnew = a_ci[..., None, None] * hprev + s_ci
        return hnew, hprev

    hfin, hprevs = jax.lax.scan(
        scan_body, jnp.zeros((B, H, N, P), jnp.float32),
        (jnp.moveaxis(s_c, 2, 0), jnp.moveaxis(a_c, 2, 0)))
    hprevs = jnp.moveaxis(hprevs, 0, 2)                        # (B,H,nc,N,P)

    cc = c.reshape(B, nc, L, N).astype(jnp.float32)
    y_inter = jnp.einsum("bcln,bhcnp,bclh->bhclp", cc, hprevs,
                         jnp.exp(cum))
    y = (y_i + y_inter)                                        # (B,H,nc,L,P)
    y = jnp.transpose(y, (0, 2, 3, 1, 4)).reshape(B, S, H, P)
    return y.astype(xh.dtype), jnp.swapaxes(hfin, -1, -2)      # state (B,H,P,N)
