"""Public jit'd wrappers for the Pallas kernels.

Where a kernel runs follows the default JAX backend
(``repro.kernels.backend.resolve_interpret``): natively lowered on a TPU,
under the Pallas interpreter (bit-accurate, same kernel body) only on the
CPU backend.  ``interpret=True``/``False`` forces one or the other.
"""
from __future__ import annotations

from typing import Any, Optional

import jax

from repro.kernels import fused_pack, ref
from repro.kernels.ssd_scan import ssd_chunked_pallas
from repro.kernels.topk_quant import DEFAULT_BLOCK, dequant, topk_quant


def fused_wire_encode(tree: Any, p_s: float, p_q: int,
                      backend: Optional[str] = None) -> bytes:
    """One-pass packed wire encode of a pytree (Alg. 3 serialization).

    Bit-identical to ``PackedBitstreamCodec``'s host oracle pipeline with
    deterministic rounding; ``len(result) == expected_pytree_wire_bytes``.

    ``backend``:
      * ``None`` — auto: the vectorized numpy twin on the CPU backend (there
        the twin is the fast path — per-leaf pallas_call dispatch costs ~ms
        on host, the same trade ``bitpack`` makes for its jnp kernels), the
        native Pallas kernel on any other backend;
      * ``"host"`` — force the numpy twin;
      * ``"interpret"`` — force the Pallas kernel under the interpreter
        (bit-accurate kernel body on CPU; what CI exercises);
      * ``"native"`` — force real TPU lowering.
    """
    if backend is None:
        backend = "host" if jax.default_backend() == "cpu" else "native"
    leaves = jax.tree.leaves(tree)
    if backend == "host":
        return fused_pack.pack_leaves_host(leaves, p_s, p_q)
    if backend not in ("interpret", "native"):
        raise ValueError(f"unknown fused_wire_encode backend {backend!r}")
    return fused_pack.pack_leaves_pallas(leaves, p_s, p_q,
                                         interpret=backend == "interpret")


def compress_roundtrip(x: jax.Array, p_s: float = 0.25, bits: int = 8,
                       block: int = DEFAULT_BLOCK,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Kernel-backed lossy compress->decompress of an arbitrary tensor."""
    levels, scales = topk_quant(x.reshape(-1), p_s=p_s, bits=bits,
                                block=block, interpret=interpret)
    return dequant(levels, scales, bits, x.size, x.shape).astype(x.dtype)


def ssd(xh, b, c, dt, la, chunk: int, use_pallas: bool = True,
        interpret: Optional[bool] = None):
    """Mamba2 SSD: kernel-backed or pure-jnp reference."""
    if use_pallas:
        return ssd_chunked_pallas(xh, b, c, dt, la, chunk,
                                  interpret=interpret)
    return ref.ssd_full_ref(xh, b, c, dt, la, chunk)
