"""Pluggable wire codecs: one seam for the paper's compression (Algs. 3-4).

The paper's headline contribution is wire compression for asynchronous FL:

* **Algorithm 3 (compress)** — keep the top ``p_s`` fraction of each tensor
  by magnitude (``k = max(1, round(p_s * n))`` values), quantize the kept
  values to ``p_q`` bits with a QSGD-style symmetric uniform quantizer
  (levels in ``[-L, L]``, ``L = 2**(p_q-1) - 1``, one f32 max-abs scale per
  tensor), and transmit ``(scale, values, indices)`` — zeros are not sent.
* **Algorithm 4 (decompress)** — dequantize ``level * scale / L`` and
  scatter the values back to their indices in a zero tensor.
* **Wire size** (the analytic price): per tensor
  ``bits = k * (min(p_q, 32) + [k < n] * ceil(log2 n)) + 32``, and a pytree
  travels as ONE bit-level concatenated stream of ``ceil(sum_bits / 8)``
  bytes (``repro.core.compression.expected_pytree_wire_bytes``).

Every consumer — ``FLEngine``, the legacy ``FLSimulator``, the Alg. 5
profiler, benchmarks — goes through the :class:`Codec` interface instead of
hand-picking one of the underlying implementations:

* :class:`IdentityCodec` — no compression; prices the dense f32 payload.
  ``resolve_codec`` returns it at the uncompressed point ``(p_s >= 1,
  p_q >= 32)`` for every family (the simulator's historical fast path).
* :class:`DenseRefCodec` — the faithful reference codec (Algs. 3-4 exactly,
  optional stochastic QSGD rounding): payload is the per-tensor
  ``{values, indices, scale}`` dict of ``compress_pytree``; byte accounting
  is the packed-stream price.  This is the protocol simulators' default.
* :class:`ThresholdGraphCodec` — the jit/SPMD-safe in-graph channel used by
  the vectorized cohort trainer: binary-search threshold sparsification
  (approximate Top-K, kept fraction within ~2**-iters of ``p_s``) +
  deterministic quantization, applied as a dense masked round trip inside
  the compiled program.  Bytes are priced shape-only.
* :class:`PackedBitstreamCodec` — the REAL wire format: values bit-packed at
  ``p_q`` bits plus delta-coded sorted indices at ``ceil(log2 n)`` bits,
  serialized by the ``repro.kernels.bitpack`` kernels into a single byte
  string whose ``len()`` equals the analytic price *exactly*.  Encode
  selection/quantization is shared with :class:`DenseRefCodec` (same mask,
  same levels, same scale — and the same RNG draw order under stochastic
  rounding), so the two codecs decode to bit-identical trees.  Subsumes the
  orphaned block-local Pallas kernel ``repro.kernels.topk_quant`` as the
  FL stack's packed path.

Protocols pick a codec family by name via ``SimConfig.codec`` and the
``ProtocolStrategy.channel_for(t, device_id=None)`` seam; ``CODECS`` is the registry (new
codec = one subclass + one entry), ``resolve_codec`` binds a family name to
the round's ``(p_s, p_q)`` operating point — per device when an adaptive
policy (``repro.fl.policies``) is active.

The normative bit-layout spec of the packed stream — field order,
offset-binary values, delta-coded indices, and how ``len(bytes)`` ties to
``expected_pytree_wire_bytes`` — is **docs/WIRE_FORMAT.md**.
"""
from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, ClassVar, Dict, List, Optional, Tuple, Type

import jax
import numpy as np

from repro.core.compression import (FLOAT_BITS, compress_pytree,
                                    compress_tensor, decompress_pytree,
                                    decompress_tensor,
                                    expected_pytree_wire_bytes,
                                    expected_tensor_wire_bits, index_bits,
                                    pytree_dense_bytes, pytree_wire_bytes,
                                    sparsify_quantize_threshold, topk_count)
from repro.kernels.bitpack import BitReader, pack_segments


@dataclasses.dataclass
class Wire:
    """One encoded transmission.

    ``payload`` is codec-specific (a pytree, a compressed-dict tree, or raw
    ``bytes`` for the packed codec); ``nbytes`` is the metered wire size.
    ``meta`` carries receiver-known framing (treedef / leaf shapes) that is
    protocol-static and therefore not billed to the channel.
    """
    codec: str
    payload: Any
    nbytes: int
    meta: Any = None


class Codec(abc.ABC):
    """encode/decode/price interface every wire implementation satisfies.

    ``p_s``/``p_q`` expose the operating point (1.0/32 = uncompressed) so
    engines can group work by compression parameters (the cohort trainer
    jit-specializes on them).
    """

    name: ClassVar[str] = ""
    p_s: float = 1.0
    p_q: int = FLOAT_BITS

    @abc.abstractmethod
    def encode(self, tree: Any, *,
               rng: Optional[np.random.RandomState] = None) -> Wire:
        """Compress ``tree`` for transmission.  ``rng`` enables stochastic
        (unbiased QSGD) rounding where the codec supports it."""

    @abc.abstractmethod
    def decode(self, wire: Wire) -> Any:
        """Reconstruct the (lossy) tree from a :class:`Wire`."""

    @abc.abstractmethod
    def wire_bytes(self, tree: Any) -> int:
        """Transmitted size for ``tree`` — shape-only (value-independent for
        every registered codec), so schedulers can price a transfer before
        training has produced the update."""

    def roundtrip(self, tree: Any, *,
                  rng: Optional[np.random.RandomState] = None
                  ) -> Tuple[Any, int]:
        """The lossy channel: encode -> wire bytes -> decode."""
        wire = self.encode(tree, rng=rng)
        return self.decode(wire), wire.nbytes


@dataclasses.dataclass(frozen=True)
class IdentityCodec(Codec):
    """No compression: dense f32 on the wire (TEA-Fed / FedAvg / FedAsync)."""

    name: ClassVar[str] = "identity"

    def encode(self, tree, *, rng=None) -> Wire:
        return Wire(self.name, tree, pytree_dense_bytes(tree))

    def decode(self, wire: Wire):
        return wire.payload

    def wire_bytes(self, tree) -> int:
        return pytree_dense_bytes(tree)


@dataclasses.dataclass(frozen=True)
class DenseRefCodec(Codec):
    """Reference Algs. 3-4 codec over ``compress_pytree``/``decompress_pytree``
    (exact global Top-K, optional stochastic rounding); the payload keeps the
    per-tensor dict layout but is *priced* as the packed bitstream."""

    p_s: float = 1.0
    p_q: int = FLOAT_BITS

    name: ClassVar[str] = "dense"

    def encode(self, tree, *, rng=None) -> Wire:
        ctree = compress_pytree(tree, self.p_s, self.p_q, rng)
        return Wire(self.name, ctree, pytree_wire_bytes(ctree))

    def decode(self, wire: Wire):
        return decompress_pytree(wire.payload)

    def wire_bytes(self, tree) -> int:
        return _packed_price(tree, self.p_s, self.p_q)


@dataclasses.dataclass(frozen=True)
class ThresholdGraphCodec(Codec):
    """jit/SPMD-safe in-graph channel: binary-search threshold sparsification
    + deterministic quantization (``sparsify_quantize_threshold``), the
    operator the vectorized cohort trainer fuses into its scan.  ``encode``
    applies the lossy round trip eagerly; inside a jitted program use
    :meth:`apply` / :meth:`apply_tree` directly."""

    p_s: float = 1.0
    p_q: int = FLOAT_BITS
    iters: int = 12               # threshold binary-search iterations

    name: ClassVar[str] = "threshold"

    def apply(self, x: jax.Array) -> jax.Array:
        """The in-graph lossy operator (traceable, shape-preserving)."""
        return sparsify_quantize_threshold(x, self.p_s, self.p_q, self.iters)

    def apply_tree(self, tree: Any) -> Any:
        return jax.tree.map(self.apply, tree)

    def encode(self, tree, *, rng=None) -> Wire:
        # the eager path is host-dispatch-bound (dozens of small ops per
        # leaf); one jitted program per codec instance fixes that, while
        # in-graph callers (the cohort scan) keep using apply/apply_tree
        return Wire(self.name, _jitted_apply_tree(self)(tree),
                    self.wire_bytes(tree))

    def decode(self, wire: Wire):
        return wire.payload

    def wire_bytes(self, tree) -> int:
        return expected_pytree_wire_bytes(tree, self.p_s, self.p_q)


@functools.lru_cache(maxsize=256)
def _jitted_apply_tree(codec: "ThresholdGraphCodec"):
    return jax.jit(codec.apply_tree)


def _packed_price(tree: Any, p_s: float, p_q: int) -> int:
    """Shape-only price of the packed stream WITHOUT the dense fast path of
    ``expected_pytree_wire_bytes``: the stream always carries the per-tensor
    f32 scale, so at the uncompressed point the packed codecs cost
    ``dense + 4 * n_leaves`` bytes, and ``wire_bytes`` must agree with what
    ``encode`` actually emits.  (Engines never see that point — ``resolve_codec``
    short-circuits it to :class:`IdentityCodec` — but directly constructed
    codecs stay self-consistent.)"""
    return (sum(expected_tensor_wire_bits(x.size, p_s, p_q)
                for x in jax.tree.leaves(tree)) + 7) // 8


@dataclasses.dataclass(frozen=True)
class PackedBitstreamCodec(Codec):
    """The real bit-packed wire format (Alg. 3 serialization).

    Per tensor, in stream order: ``[scale: 32b f32] [k values at
    min(p_q, 32) bits] [k delta-coded sorted indices at ceil(log2 n) bits,
    omitted when k == n]``.  Quantized levels travel offset-binary
    (``level + L``); uncompressed values travel as raw f32 bit patterns.
    Tensors are concatenated bit-level (no per-tensor byte padding) and the
    single trailing partial byte is zero-filled, so

        ``len(encode(tree).payload) == expected_pytree_wire_bytes(tree)``

    holds exactly.  Selection and quantization reuse ``compress_tensor``
    verbatim, making the decode bit-identical to :class:`DenseRefCodec` for
    the same ``(p_s, p_q, rng)``.  Full layout spec: docs/WIRE_FORMAT.md.

    **Fused fast path**: with ``fused=True`` (the default), deterministic
    encodes (``rng is None``) go through the one-pass fused emitter
    ``repro.kernels.ops.fused_wire_encode`` — the ``fused_pack`` Pallas
    kernel where JAX runs on a TPU, its vectorized numpy twin on the CPU
    backend — which writes the packed words directly at dense-codec speed.
    Stochastic (rng) encodes always take the multi-pass ``compress_tensor``
    pipeline: engines pass the shared sim RNG, so protocol histories keep
    the exact legacy draw order regardless of ``fused``.  ``fused=False``
    keeps the host pipeline as the parity oracle (the way the ``heap``
    scheduler anchors ``batched``); tests/test_fused_pack pins
    fused-vs-oracle stream bit-equality."""

    p_s: float = 1.0
    p_q: int = FLOAT_BITS
    fused: bool = True

    name: ClassVar[str] = "packed"

    def __post_init__(self):
        if not (2 <= self.p_q):
            raise ValueError(f"p_q must be >= 2, got {self.p_q}")

    # -- encode -----------------------------------------------------------
    def encode(self, tree, *, rng=None) -> Wire:
        leaves, treedef = jax.tree.flatten(tree)
        shapes = [np.shape(x) for x in leaves]
        if self.fused and rng is None:
            # imported at call time: repro.kernels.ops pulls in the fused
            # kernel, which needs repro.core.compression — a top-level
            # import here would close that cycle when repro.kernels loads
            # first
            from repro.kernels.ops import fused_wire_encode
            payload = fused_wire_encode(leaves, self.p_s, self.p_q)
        else:
            segments: List[Tuple[np.ndarray, int]] = []
            for x in leaves:
                c = compress_tensor(np.asarray(x), self.p_s, self.p_q, rng)
                segments.extend(self._tensor_segments(c))
            payload = pack_segments(segments)
        return Wire(self.name, payload, len(payload), meta=(treedef, shapes))

    @staticmethod
    def _tensor_segments(c: Dict[str, Any]) -> List[Tuple[np.ndarray, int]]:
        n, p_q = c["n"], c["p_q"]
        values, indices = c["values"], c["indices"]
        k = len(values)
        vbits = min(p_q, FLOAT_BITS)
        scale = np.asarray(c["scale"], np.float32).reshape(1).view(np.uint32)
        # sort by index for delta coding; the scatter in Alg. 4 is
        # order-invariant, so reordering values alongside is lossless
        order = np.argsort(indices, kind="stable")
        idx_s = np.asarray(indices)[order]
        vals_s = np.asarray(values)[order]
        if p_q < FLOAT_BITS:
            L = 2 ** (p_q - 1) - 1
            u_vals = (vals_s.astype(np.int64) + L).astype(np.uint32)
        else:
            u_vals = vals_s.astype(np.float32).view(np.uint32)
        segs = [(scale, FLOAT_BITS), (u_vals, vbits)]
        if k < n:
            deltas = np.empty(k, np.uint32)
            deltas[0] = idx_s[0]
            deltas[1:] = np.diff(idx_s)
            segs.append((deltas, index_bits(n)))
        return segs

    # -- decode -----------------------------------------------------------
    def decode(self, wire: Wire):
        treedef, shapes = wire.meta
        reader = BitReader(wire.payload)
        leaves = [self._read_tensor(reader, shape) for shape in shapes]
        return jax.tree.unflatten(treedef, leaves)

    def _read_tensor(self, reader: BitReader, shape) -> np.ndarray:
        n = int(np.prod(shape)) if shape else 1
        k = topk_count(n, self.p_s)
        vbits = min(self.p_q, FLOAT_BITS)
        scale = float(reader.read(1, FLOAT_BITS).view(np.float32)[0])
        u_vals = reader.read(k, vbits)
        if self.p_q < FLOAT_BITS:
            L = 2 ** (self.p_q - 1) - 1
            values = (u_vals.astype(np.int64) - L).astype(np.int32)
        else:
            values = u_vals.view(np.float32)
        if k < n:
            indices = np.cumsum(reader.read(k, index_bits(n)).astype(np.int64))
        else:
            indices = np.arange(n, dtype=np.int64)
        return decompress_tensor({"values": values, "indices": indices,
                                  "scale": scale, "shape": tuple(shape),
                                  "p_q": self.p_q, "n": n})

    def wire_bytes(self, tree) -> int:
        return _packed_price(tree, self.p_s, self.p_q)


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
CODECS: Dict[str, Type[Codec]] = {
    cls.name: cls for cls in (IdentityCodec, DenseRefCodec,
                              ThresholdGraphCodec, PackedBitstreamCodec)
}


@functools.lru_cache(maxsize=256)
def _make_codec(name: str, p_s: float, p_q: int, iters: int) -> Codec:
    if name == "threshold":
        return ThresholdGraphCodec(p_s, p_q, iters)
    return CODECS[name](p_s, p_q) if name != "identity" else IdentityCodec()


def resolve_codec(name: str, p_s: float = 1.0, p_q: int = FLOAT_BITS,
                  iters: int = 12) -> Codec:
    """Bind a codec family name to an ``(p_s, p_q)`` operating point.

    The uncompressed point short-circuits to :class:`IdentityCodec` for
    every family — that is the simulators' historical dense fast path, and
    it keeps byte accounting (and RNG draw order) identical across codec
    selections when a protocol round happens to be uncompressed.
    Instances are cached: codecs are frozen/stateless, so sharing is safe.
    """
    if name not in CODECS:
        raise ValueError(
            f"unknown codec {name!r}; expected one of {sorted(CODECS)}")
    if p_s >= 1.0 and p_q >= FLOAT_BITS:
        return _make_codec("identity", 1.0, FLOAT_BITS, iters)
    return _make_codec(name, float(p_s), int(p_q), int(iters))
