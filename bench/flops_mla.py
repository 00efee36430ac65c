"""Analytic operation and byte counts of a DeepSeek-V3-style latent-attention
MoE model on one chip's share of its routed experts (``bench/weights_mla``
shapes), from its configuration file.

Counts are of the work the algorithm needs, as in ``bench/flops.py``: a
multiply-add is 2 FLOPs; padding and positions beyond a request's context
do not count.  Prefill runs MLA with the latent expanded into per-head
keys and values; decode runs it absorbed, over the cached latent and rope
key.  The routed experts' work is counted per token-expert pair computed
here, which the program counts (``held_pairs``); everything else per
token.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

from bench.weights_mla import ROUTER_DTYPE, dims, mla_shapes


def attn_matmul_params(hf: Dict) -> int:
    """Weights one token multiplies through in one layer's MLA: wq,
    wkv_a, wkv_b (in decode, its key half folded into the query and its
    value half into the output: the same count), wo."""
    m = dims(hf)
    return (m["d"] * m["H"] * (m["dn"] + m["dr"]) + m["d"] * (m["r"] + m["dr"])
            + m["r"] * m["H"] * (m["dn"] + m["dv"]) + m["H"] * m["dv"] * m["d"])


def token_matmul_flops(hf: Dict) -> int:
    """FLOPs of one token through every layer's matrix products, less the
    routed experts and attention's own products: MLA projections; the
    dense SwiGLU in the leading layers; router and shared experts in the
    MoE layers."""
    m = dims(hf)
    dense = 3 * m["d"] * m["ff"]
    moe = m["d"] * m["E"] + 3 * m["d"] * m["shared"] * m["fe"]
    return 2 * (m["L"] * attn_matmul_params(hf) + m["K"] * dense
                + (m["L"] - m["K"]) * moe)


def expert_pair_flops(hf: Dict) -> int:
    """One token through one routed expert (SwiGLU of the expert width)."""
    m = dims(hf)
    return 2 * 3 * m["d"] * m["fe"]


def prefill_pair_flops(hf: Dict) -> int:
    """One query-key pair of prefill, every layer: scores over the nope and
    rope widths and the value sum, per head."""
    m = dims(hf)
    return m["L"] * 2 * m["H"] * (m["dn"] + m["dr"] + m["dv"])


def decode_position_flops(hf: Dict) -> int:
    """One cached position attended by one decode token, every layer
    (absorbed): scores over the latent and the rope key, and the latent
    sum, per head."""
    m = dims(hf)
    return m["L"] * 2 * m["H"] * (2 * m["r"] + m["dr"])


def head_flops(hf: Dict) -> int:
    m = dims(hf)
    return 2 * m["d"] * m["V"]


def served_flops(hf: Dict, c: Dict) -> float:
    """FLOPs of the prompts and generated tokens a serving run counted:
    ``requests``, ``prompt_tokens``, ``prompt_pairs`` (sum of S(S+1)/2),
    ``decode_tokens``, ``decode_ctx_positions`` and ``held_pairs`` (the
    token-expert pairs the held experts computed, in prefill and decode)."""
    return (token_matmul_flops(hf) * (c["prompt_tokens"] + c["decode_tokens"])
            + prefill_pair_flops(hf) * c["prompt_pairs"]
            + decode_position_flops(hf) * c["decode_ctx_positions"]
            + head_flops(hf) * (c["requests"] + c["decode_tokens"])
            + expert_pair_flops(hf) * c["held_pairs"])


def expected_held_pairs(hf: Dict, tokens: float) -> float:
    """Pairs ``tokens`` send to the held experts under uniform routing."""
    m = dims(hf)
    return tokens * (m["L"] - m["K"]) * m["k"] * m["held"] / m["E"]


def weight_bytes(hf: Dict, dtype_bytes: int = 2) -> int:
    """Bytes of every weight held here: routers in ``ROUTER_DTYPE``, the
    rest ``dtype_bytes`` each."""
    router_bytes = np.dtype(ROUTER_DTYPE).itemsize

    def walk(t, name=""):
        if isinstance(t, tuple):
            return math.prod(t) * (router_bytes if name.startswith("router")
                                   else dtype_bytes)
        return sum(walk(v, k) for k, v in t.items())

    return walk(mla_shapes(hf))


def latent_bytes_per_position(hf: Dict, cache_bytes: int = 2) -> int:
    """Cached latent and rope key of one position, every layer."""
    m = dims(hf)
    return m["L"] * (m["r"] + m["dr"]) * cache_bytes


def decode_step_bytes(hf: Dict, ctx_positions: float, dtype_bytes: int = 2,
                      cache_bytes: int = 2) -> float:
    """HBM bytes one batched decode step needs: every weight held here once
    but the embedding, whose rows are gathered, and the cached rows of
    ``ctx_positions`` positions of the active requests."""
    m = dims(hf)
    return (weight_bytes(hf, dtype_bytes) - m["V"] * m["d"] * dtype_bytes
            + latent_bytes_per_position(hf, cache_bytes) * ctx_positions)
