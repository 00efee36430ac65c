"""Plain reference forward of a DeepSeek-V3-style decoder (Moonlight-16B-A3B's
``config.json``) on one chip's share of its routed experts.

Per layer: RMSNorm; multi-head latent attention written out for every
token (no absorption): queries ``x @ wq`` split into a part without rope
and a rope part; ``x @ wkv_a`` split into a latent, RMS-normed, and one
rope key shared by the heads; ``latent @ wkv_b`` gives each head's key
part without rope and its value; rotary embedding of interleaved pairs
(base ``rope_theta``), causal softmax at scale 1/sqrt(nope + rope width),
output projection, residual.  RMSNorm, then for the leading
``first_k_dense_replace`` layers a SwiGLU MLP, and for the others the MoE:
sigmoid scores of a float32 router over every published expert, the top
``num_experts_per_tok`` of score + bias selected, their scores (without
the bias) normalised to sum 1 and times ``routed_scaling_factor``; of the
routed experts only those in ``experts_held`` are computed (each on every
token, weighted by its routing weight, zero where not selected), and the
shared experts, one SwiGLU of ``n_shared_experts`` x the expert width,
once on every token; residual.  Final RMSNorm and the untied head.

Written from that description in plain ``jax.numpy``; it imports nothing
of the program.  It runs in float32 at ``highest`` matmul precision, one
request at a time, with the layers under a scan so that only one layer's
weights are upcast at a time.  ``fp8="e4m3"`` is the control of the
benchmark's comparison, as in ``bench/reference/qwen3.py``: the operands
of every matrix product are rounded to float8 with a scale per row of the
activations and per output column of the weights.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.qwen3 import HIGHEST, PAD_TO, ROWS, _fp8, _mm, _rms

KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "rope_theta",
        "num_experts_per_tok", "routed_scaling_factor", "experts_held")


def _rope_pairs(x, theta):
    """x: (S, heads, width), position = row index; pairs (x[2i], x[2i+1])
    rotate by the angle of frequency i, and come out as (evens, odds)."""
    s, _, w = x.shape
    half = w // 2
    x1, x2 = x[..., 0::2], x[..., 1::2]
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / w)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, a, hf, fp8):
    f = lambda t: t.astype(jnp.float32)
    s = h.shape[0]
    H, r = hf["num_attention_heads"], hf["kv_lora_rank"]
    dn, dr, dv = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"], hf["v_head_dim"]
    q = _mm(h, f(a["wq"]), fp8).reshape(s, H, dn + dr)
    kv = _mm(h, f(a["wkv_a"]), fp8)
    latent = _rms(kv[:, :r], f(a["kv_norm"]), hf["rms_norm_eps"])
    k_pe = _rope_pairs(kv[:, None, r:], hf["rope_theta"])          # (s,1,dr)
    kvb = _mm(latent, f(a["wkv_b"]), fp8).reshape(s, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], hf["rope_theta"])],
                        -1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_pe, (s, H, dr))], -1)
    v = kvb[..., dn:]
    if fp8:
        q, k, v = _fp8(q, -1, fp8), _fp8(k, -1, fp8), _fp8(v, -1, fp8)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1, fp8)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(s, H * dv)
    return _mm(o, f(a["wo"]), fp8)


def _swiglu(x, gate, up, down, fp8):
    f = lambda t: t.astype(jnp.float32)
    g = jax.nn.silu(_mm(x, f(gate), fp8)) * _mm(x, f(up), fp8)
    return _mm(g, f(down), fp8)


def routing(x, router, bias, hf, fp8=False):
    """-> the held experts' routing weights (S, held), zero where an expert
    is not selected."""
    lo, hi = hf["experts_held"]
    k = hf["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(x, router.astype(jnp.float32), fp8))
    _, top = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, top, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * hf["routed_scaling_factor"]
    held = (top[..., None] == jnp.arange(lo, hi)).astype(jnp.float32)
    return jnp.einsum("ske,sk->se", held, w)


def moe(x, m, hf, fp8=False):
    """The MoE of one layer on x (S, d), float32: the held experts' part of
    the routed result plus the shared experts."""
    w = routing(x, m["router"], m["router_bias"], hf, fp8)
    y = _swiglu(x, m["shared"]["w_gate"], m["shared"]["w_up"],
                m["shared"]["w_down"], fp8)
    for e in range(w.shape[1]):
        y = y + w[:, e:e + 1] * _swiglu(x, m["e_gate"][e], m["e_up"][e],
                                        m["e_down"][e], fp8)
    return y


def _layer(h, lw, hf, fp8):
    """-> h after the layer."""
    f = lambda t: t.astype(jnp.float32)
    eps = hf["rms_norm_eps"]
    h = h + _attention(_rms(h, f(lw["norm1"]["scale"]), eps), lw["attn"], hf,
                       fp8)
    x = _rms(h, f(lw["norm2"]["scale"]), eps)
    if "moe" in lw:
        return h + moe(x, lw["moe"], hf, fp8)
    m = lw["ffn"]
    return h + _swiglu(x, m["w_gate"], m["w_up"], m["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("hf_items", "fp8"))
def _hidden(params, tokens, *, hf_items, fp8):
    """-> the final hidden states."""
    hf = dict(hf_items)
    h = params["embed"][tokens].astype(jnp.float32)
    for name in ("dense_layers", "layers"):
        if name in params:
            h, _ = jax.lax.scan(lambda c, lw: (_layer(c, lw, hf, fp8), None),
                                h, params[name])
    return h


@functools.partial(jax.jit, static_argnames=("hf_items", "fp8"))
def _head(params, h, rows, *, hf_items, fp8):
    hf = dict(hf_items)
    x = _rms(h[rows], params["final_norm"]["scale"].astype(jnp.float32),
             hf["rms_norm_eps"])
    return _mm(x, params["lm_head"].astype(jnp.float32), fp8)


def logits(params: Dict, hf: Dict, tokens: Sequence[int], rows: Sequence[int],
           fp8=False) -> np.ndarray:
    """Logits (len(rows), vocab) at positions ``rows`` of ``tokens``.
    Sequences are padded to a multiple of ``PAD_TO`` and rows to one of
    ``ROWS``, so that a few shapes compile."""
    n, r = len(tokens), len(rows)
    padded = np.zeros(-(-n // PAD_TO) * PAD_TO, np.int32)
    padded[:n] = tokens
    rows_p = np.zeros(-(-r // ROWS) * ROWS, np.int32)
    rows_p[:r] = rows
    with jax.default_matmul_precision("highest"):
        h = _hidden(params, jnp.asarray(padded), hf_items=_items(hf),
                    fp8=fp8)
        out = _head(params, h, jnp.asarray(rows_p), hf_items=_items(hf),
                    fp8=fp8)
    return np.asarray(out)[:r]


def _items(hf):
    return tuple((k, tuple(hf[k]) if isinstance(hf[k], list) else hf[k])
                 for k in KEYS)


def served_gaps(params: Dict, hf: Dict, prompt: Sequence[int],
                served: Sequence[int], fp8=False) -> np.ndarray:
    """For each served token, by how much its reference logit lies below
    the reference's best at that position.  With ``fp8`` the token read is
    the one the float8 forward puts first instead of the served one."""
    served = list(served)
    seq = list(prompt) + served[:-1]
    rows = list(range(len(prompt) - 1, len(seq)))
    ref = logits(params, hf, seq, rows)
    pick = np.asarray(served)
    if fp8:
        pick = logits(params, hf, seq, rows, fp8=fp8).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(rows)), pick]
