"""Fused sparsify + quantize + bit-pack in ONE jitted program (Alg. 3).

The packed wire encode used to be a multi-pass host pipeline (per-leaf
``compress_tensor`` -> argsort -> delta-code -> ``pack_segments``), making
the paper's headline codec the slowest path in the stack.  This module runs
the whole of Algorithm 3 as one jitted program per leaf that writes the
packed uint32 stream words directly:

1. **exact Top-K selection** (Pallas kernel) — a fixed-iteration (31-step)
   greedy binary search over the int32 bit patterns of ``|x|`` (IEEE-754
   non-negative floats order like integers, the trick behind the
   fixed-iteration search in ``topk_quant``; here run to completion so the
   threshold is the *exact* k-th largest magnitude, not an approximation).
   Ties at the threshold keep the smallest flat indices — the canonical
   rule shared with ``repro.core.compression.compress_tensor``
   (WIRE_FORMAT.md, "Determinism").
2. **quantize** (same kernel) — offset-binary QSGD levels
   ``round(x / scale * L) + L`` (deterministic nearest-even rounding; f32
   max-abs scale over survivors), or raw f32 bit patterns at ``p_q >= 32``.
   The quotient is corrected to the IEEE-rounded one (``_round_quotient``),
   since the TPU's f32 division is a refined reciprocal.
3. **pack** (XLA around the kernel) — survivor ranks from an exclusive
   prefix sum over the keep mask give every field its absolute bit offset
   in the stream (``32 + rank*vbits`` for values, ``32 + k*vbits +
   rank*ibits`` for the delta-coded indices, scale at bit 0); each field
   spans at most two big-endian uint32 words, emitted with a shift/OR
   scatter-add (bit-disjoint contributions, so integer add == bitwise OR).
   Deltas come from ``cummax`` over survivor positions — no sort.  Mosaic
   has no lowering for cumsum, cummax or scatter, so this stage stays in
   the XLA program that wraps the ``pallas_call``.

The emitted stream is **bit-identical** to ``PackedBitstreamCodec``'s host
pipeline (docs/WIRE_FORMAT.md stays normative) and ``len(bytes) ==
expected_pytree_wire_bytes`` exactly.

Three executions of the same math:

* ``fused_pack_leaf(..., interpret=True)`` — the Pallas kernel body run by
  the interpreter (bit-accurate; the default on the CPU backend);
* ``fused_pack_leaf(..., interpret=False)`` — native TPU lowering (the
  default on any other backend);
* ``pack_leaves_host`` — a vectorized numpy twin (partition + one word-level
  ``pack_segments`` pass).  On CPU the twin IS the production path: per-leaf
  pallas_call dispatch costs ~ms on host, same reason ``bitpack`` keeps
  numpy twins of its jnp kernels.

All quantization arithmetic is f32 with the IEEE-rounded quotient
``x / scale`` times ``L`` in all three, so they agree bit-for-bit; the host
oracle ``compress_tensor`` computes the identical f32 expression (numpy
keeps f32 for array-op-python-scalar), pinned by tests/test_fused_pack.

VMEM note: the kernel holds one whole (padded) leaf in VMEM with its codes
and about nine more leaf-sized temporaries, ~42 bytes per element.  Under
v5e's default 16 MiB scoped-VMEM limit that compiles up to n = 300,000 and
is refused from n = 400,000 (compiled against a described v5e topology;
tests/test_chip_compile.py pins the CNN's largest leaf, 200,704 elements).
Larger leaves would need a grid-blocked variant with per-block threshold
counts; the host twin has no such limit.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.compression import (FLOAT_BITS, expected_tensor_wire_bits,
                                    index_bits, topk_count)
from repro.kernels.backend import resolve_interpret
from repro.kernels.bitpack import pack_segments, words_to_bytes

_LANES = 128                   # TPU vreg tile: (8 sublanes, 128 lanes)
_SUBLANES = 8


# ----------------------------------------------------------------------
# Pallas kernel: exact Top-K threshold + scale + quantized codes
# ----------------------------------------------------------------------
_SPLIT = 4097.0                # 2^12 + 1: Veltkamp split constant for f32


def _two_prod(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dekker's exact product: ``a * b == p + e`` with ``p = fl(a * b)``,
    from f32 multiplies and adds alone (no FMA needed)."""
    def split(v):
        t = _SPLIT * v
        hi = t - (t - v)
        return hi, v - hi

    p = a * b
    ah, al = split(a)
    bh, bl = split(b)
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


def _round_quotient(ax: jax.Array, s: jax.Array, q: jax.Array) -> jax.Array:
    """Correctly rounded ``ax / s`` (round-half-even) from an estimate ``q``
    within two ulps of it; ``ax >= 0``, ``s > 0``.

    The TPU has no divide unit: its f32 division is a refined reciprocal,
    which can land one ulp from the IEEE quotient that numpy (the host twin)
    computes.  Each pass tries the two neighbouring f32 patterns of the
    estimate and keeps the one whose exact residual ``ax - c * s`` (Dekker
    product) is smallest, ties to the even pattern.  Where the estimate is
    already exact, as on the CPU, both passes keep it."""
    qi = jax.lax.bitcast_convert_type(q, jnp.int32)

    def resid(ci):
        c = jax.lax.bitcast_convert_type(ci, jnp.float32)
        p, e = _two_prod(c, s)
        return jnp.abs((ax - p) - e)

    for _ in range(2):
        best, best_r = qi, resid(qi)
        for ci in (jnp.maximum(qi - 1, 0), qi + 1):
            r = resid(ci)
            take = (r < best_r) | ((r == best_r) & ((ci & 1) == 0))
            best = jnp.where(take, ci, best)
            best_r = jnp.where(take, r, best_r)
        qi = best
    return jax.lax.bitcast_convert_type(qi, jnp.float32)


def _select_quant_kernel(x_ref, codes_ref, meta_ref, *, n: int, k: int,
                         p_q: int):
    """One (rows, 128) f32 tile holding the whole padded leaf ->
    per-element codes (offset-binary levels, or raw f32 bits at p_q >= 32)
    and ``meta[0, 0] = threshold pattern``, ``meta[0, 1] = scale bits``."""
    x = x_ref[...]
    rows, lanes = x.shape
    idx = (jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) * lanes
           + jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1))
    valid = idx < n
    ax = jnp.abs(x)
    # int32 patterns of |x| order like the magnitudes (IEEE-754, sign 0)
    bits = jnp.where(valid, jax.lax.bitcast_convert_type(ax, jnp.int32), 0)

    if k < n:
        # exact k-th largest magnitude: greedily set pattern bits MSB->LSB,
        # keeping a bit iff >= k magnitudes still clear the candidate.
        # 31 iterations (sign bit of |x| is 0); thr ends as the exact pattern.
        def step(i, t):
            cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
            cnt = jnp.sum((bits >= cand).astype(jnp.int32))
            return jnp.where(cnt >= k, cand, t)

        thr = jax.lax.fori_loop(0, 31, step, jnp.int32(0))
        # survivors are every magnitude above thr plus >= 1 tie at thr, so
        # their max-abs is the max over bits >= thr
        kept = bits >= thr
    else:
        thr = jnp.int32(0)
        kept = valid

    if p_q < FLOAT_BITS:
        L = 2 ** (p_q - 1) - 1
        scale = jnp.maximum(jnp.max(jnp.where(kept, ax, 0.0)), 1e-12)
        sv = jnp.full(x.shape, scale, jnp.float32)
        q = _round_quotient(ax, sv, ax / sv)
        levels = jnp.clip(jnp.round(q * L), 0, L).astype(jnp.int32)
        codes = jnp.where(x < 0, L - levels, L + levels)
    else:
        scale = jnp.float32(1.0)
        codes = jax.lax.bitcast_convert_type(x, jnp.int32)
    codes_ref[...] = codes

    lane = jax.lax.broadcasted_iota(jnp.int32, meta_ref.shape, 1)
    scale_bits = jax.lax.bitcast_convert_type(
        jnp.full(meta_ref.shape, scale, jnp.float32), jnp.int32)
    meta_ref[...] = jnp.where(lane == 0, thr,
                              jnp.where(lane == 1, scale_bits, 0))


# ----------------------------------------------------------------------
# word emission (XLA around the kernel: prefix scans + scatter)
# ----------------------------------------------------------------------
def _scatter_field(words: jax.Array, vals: jax.Array, offsets: jax.Array,
                   width: int) -> jax.Array:
    """OR ``width``-bit fields into the uint32 word vector.

    ``vals`` must already be zero for dead lanes (their offsets may then
    point anywhere in range — adding zero is a no-op; out-of-range lanes
    are dropped by the scatter mode).  In-word shift ``32 - off%32 - width``
    < 0 means the field straddles into the next word.
    """
    w = offsets >> 5
    sh = 32 - (offsets & 31) - width
    hi = jnp.left_shift(jnp.right_shift(vals, jnp.maximum(-sh, 0).astype(jnp.uint32)),
                        jnp.maximum(sh, 0).astype(jnp.uint32))
    lo = jnp.where(sh < 0,
                   jnp.left_shift(vals, jnp.clip(sh + 32, 0, 31).astype(jnp.uint32)),
                   jnp.uint32(0))
    words = words.at[w].add(hi, mode="drop")
    words = words.at[w + 1].add(lo, mode="drop")
    return words


@functools.partial(jax.jit, static_argnames=("k", "p_q", "interpret"))
def _fused_pack_call(flat: jax.Array, k: int, p_q: int,
                     interpret: bool) -> jax.Array:
    """(n,) f32 leaf -> its packed stream words, (ceil(nbits / 32),) uint32."""
    n = flat.shape[0]
    rows = -(-n // (_SUBLANES * _LANES)) * _SUBLANES
    xp = jnp.pad(flat, (0, rows * _LANES - n)).reshape(rows, _LANES)
    codes, meta = pl.pallas_call(
        functools.partial(_select_quant_kernel, n=n, k=k, p_q=p_q),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
                   jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.int32)],
        interpret=interpret,
    )(xp)
    thr, scale_bits = meta[0, 0], meta[0, 1]
    field = codes.reshape(-1)[:n].astype(jnp.uint32)
    idx = jnp.arange(n, dtype=jnp.int32)
    if k < n:
        bits = jax.lax.bitcast_convert_type(jnp.abs(flat), jnp.int32)
        above = bits > thr
        tie = bits == thr
        # boundary ties keep the smallest flat indices (WIRE_FORMAT.md)
        tie_rank = jnp.cumsum(tie.astype(jnp.int32)) - tie.astype(jnp.int32)
        mask = above | (tie & (tie_rank < k - jnp.sum(above.astype(jnp.int32))))
    else:
        mask = jnp.ones(n, bool)
    mf = mask.astype(jnp.uint32)

    vbits = min(p_q, FLOAT_BITS)
    nbits = k * (vbits + (index_bits(n) if k < n else 0)) + FLOAT_BITS
    # survivor rank = exclusive prefix sum of the keep mask -> bit offsets
    rank = jnp.cumsum(mask.astype(jnp.int32)) - mask.astype(jnp.int32)
    words = jnp.zeros((nbits + 31) // 32, jnp.uint32)
    words = words.at[0].set(scale_bits.astype(jnp.uint32))
    words = _scatter_field(words, field * mf, FLOAT_BITS + rank * vbits, vbits)
    if k < n:
        # delta-coded survivor indices without a sort: the previous
        # survivor's position is the running max of masked iota, shifted by
        # one (first survivor's "previous" is 0, so its delta is its
        # absolute index — matching the host serializer's deltas[0]).
        pm = jax.lax.cummax(jnp.where(mask, idx, 0), axis=0)
        prev = jnp.where(idx == 0, 0, jnp.roll(pm, 1))
        delta = (idx - prev).astype(jnp.uint32) * mf
        words = _scatter_field(words, delta,
                               FLOAT_BITS + k * vbits + rank * index_bits(n),
                               index_bits(n))
    return words


def fused_pack_leaf(x, p_s: float, p_q: int,
                    interpret: Optional[bool] = None) -> Tuple[bytes, int]:
    """Kernel-encode ONE tensor -> (its packed wire segment, its bit length).

    The returned bytes are the tensor's stream slice zero-padded to a whole
    byte; ``concat_bitstreams`` re-joins slices at bit granularity.
    ``interpret=None`` runs the Pallas interpreter on the CPU backend and
    the native kernel elsewhere.
    """
    flat = jnp.asarray(x, jnp.float32).reshape(-1)
    n = int(flat.size)
    k = topk_count(n, p_s)
    nbits = expected_tensor_wire_bits(n, p_s, p_q)
    words = _fused_pack_call(flat, k, int(p_q), resolve_interpret(interpret))
    return words_to_bytes(np.asarray(words), nbits), nbits


def pack_leaves_pallas(leaves: Sequence, p_s: float, p_q: int,
                       interpret: Optional[bool] = None) -> bytes:
    """Whole-pytree fused encode through the Pallas kernel."""
    return concat_bitstreams([fused_pack_leaf(x, p_s, p_q, interpret)
                              for x in leaves])


# ----------------------------------------------------------------------
# numpy twin (the production CPU path)
# ----------------------------------------------------------------------
def _select_topk_idx(flat: np.ndarray, k: int) -> np.ndarray:
    """Sorted flat indices of the ``k`` largest ``|flat|``; boundary ties
    keep the smallest flat indices (the canonical rule, WIRE_FORMAT.md).

    Selection runs on the uint32 bit patterns of ``|x|`` (non-negative
    IEEE-754 floats order like unsigned ints — the same trick the Pallas
    kernel's binary search uses): integer introselect is measurably faster
    than f32, and ``argpartition`` hands back the survivor indices
    directly, skipping the full-length boolean compaction
    (``np.flatnonzero`` over ``n`` elements) that dominated the mask-based
    route.  ``argpartition``'s pick among tied magnitudes is arbitrary, so
    an ambiguous boundary (selected tie count != total tie count) falls
    back to the canonical strictly-greater + smallest-index-ties path.
    """
    n = flat.size
    b = flat.view(np.uint32) & np.uint32(0x7FFFFFFF)
    ip = np.argpartition(b, n - k)
    kth = b[ip[n - k]]
    sel = ip[n - k:]
    if np.count_nonzero(b[sel] == kth) != np.count_nonzero(b == kth):
        mask = b > kth
        t = k - int(np.count_nonzero(mask))
        mask[np.flatnonzero(b == kth)[:t]] = True
        return np.flatnonzero(mask)
    return np.sort(sel.astype(np.int32))


def pack_leaves_host(leaves: Sequence, p_s: float, p_q: int) -> bytes:
    """Vectorized numpy twin of the fused kernel: partition-select, quantize,
    delta-code, then ONE word-level ``pack_segments`` pass for all leaves.

    Bit-identical to both the Pallas kernel and the ``compress_tensor`` ->
    ``PackedBitstreamCodec._tensor_segments`` oracle pipeline (deterministic
    rounding): the quantizer is the same f32 expression ``(v / scale) * L``
    with round-half-even, and selection uses the same canonical tie rule.
    """
    vbits = min(p_q, FLOAT_BITS)
    segs: List[Tuple[np.ndarray, int]] = []
    for x in leaves:
        flat = np.asarray(x, np.float32).reshape(-1)
        n = flat.size
        k = topk_count(n, p_s)
        if k < n:
            idx = _select_topk_idx(flat, k)     # index-sorted
            vals = flat[idx]
        else:
            idx = None
            vals = flat
        if p_q < FLOAT_BITS:
            L = 2 ** (p_q - 1) - 1
            scale = max(float(np.max(np.abs(vals))), 1e-12)
            y = np.clip(np.round(vals / scale * L), -L, L)
            u_vals = (y.astype(np.int64) + L).astype(np.uint32)
        else:
            scale = 1.0
            u_vals = vals.astype(np.float32).view(np.uint32)
        segs.append((np.asarray(scale, np.float32).reshape(1).view(np.uint32),
                     FLOAT_BITS))
        segs.append((u_vals, vbits))
        if idx is not None:
            deltas = np.empty(k, np.int64)
            deltas[0] = idx[0]
            np.subtract(idx[1:], idx[:-1], out=deltas[1:])
            segs.append((deltas.astype(np.uint32), index_bits(n)))
    return pack_segments(segs)


# ----------------------------------------------------------------------
# bit-level stream concatenation
# ----------------------------------------------------------------------
def concat_bitstreams(parts: Sequence[Tuple[bytes, int]]) -> bytes:
    """Join per-tensor (payload, nbits) slices into one bit-level stream.

    Each payload's bits past its ``nbits`` must be zero (true for
    ``fused_pack_leaf`` / ``pack_segments`` output).  A slice lands at an
    arbitrary bit offset, so each of its words contributes to two output
    words; both contributions come from one uint64 shift and the output
    accumulates with |=.
    """
    total = sum(nb for _, nb in parts)
    if total == 0:
        return b""
    nw = (total + 31) // 32
    out = np.zeros(nw + 1, np.uint64)
    pos = 0
    for payload, nbits in parts:
        if nbits == 0:
            continue
        pad = (-len(payload)) % 4
        w = np.frombuffer(payload + b"\x00" * pad, dtype=">u4").astype(np.uint64)
        base, s = pos >> 5, pos & 31
        comb = w << np.uint64(32 - s)        # s=0 -> shift 32, still < 64
        out[base:base + w.size] |= comb >> np.uint64(32)
        out[base + 1:base + 1 + w.size] |= comb & np.uint64(0xFFFFFFFF)
        pos += nbits
    return words_to_bytes(out[:nw], total)
