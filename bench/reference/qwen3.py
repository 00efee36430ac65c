"""Plain reference forward of a dense Qwen3-style decoder (Qwen3-1.7B's
``config.json``): token embedding; per layer RMSNorm, grouped-query
attention with per-head RMSNorm on queries and keys (qk-norm), rotary
position embedding (rotate-half, base ``rope_theta``), causal softmax,
output projection, residual; RMSNorm, SwiGLU MLP, residual; final RMSNorm
and the (tied) head.

Written from that description in plain ``jax.numpy``; it imports nothing
of the program.  It runs in float32 at ``highest`` matmul precision, one
request at a time, with the layers under one scan so that only one
layer's weights are upcast at a time.  ``fp8=True`` is the control of the
benchmark's comparison (``fp8="e4m3"`` or ``"e5m2"``): the operands of
every matrix product are rounded to that float8 type with a scale per row
of the activations and per output column of the weights.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD_TO = 512          # sequences are padded up to a multiple of this
ROWS = 512            # and the rows whose logits are read to one of this


FP8 = {"e4m3": (jnp.float8_e4m3fn, 448.0), "e5m2": (jnp.float8_e5m2, 57344.0)}


def _fp8(x: jax.Array, axis: int, kind: str) -> jax.Array:
    dtype, top = FP8[kind]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


def _mm(x, w, fp8):
    if fp8:
        x, w = _fp8(x, -1, fp8), _fp8(w, 0, fp8)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, heads, hd), position = row index."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, lw, hf, fp8):
    f = lambda a: a.astype(jnp.float32)
    H, G = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, eps = hf["head_dim"], hf["rms_norm_eps"]
    s = h.shape[0]
    x = _rms(h, f(lw["norm1"]["scale"]), eps)
    a = lw["attn"]
    q = _mm(x, f(a["wq"]), fp8).reshape(s, H, hd)
    k = _mm(x, f(a["wk"]), fp8).reshape(s, G, hd)
    v = _mm(x, f(a["wv"]), fp8).reshape(s, G, hd)
    q = _rope(_rms(q, f(a["q_norm"]), eps), hf["rope_theta"])
    k = _rope(_rms(k, f(a["k_norm"]), eps), hf["rope_theta"])
    k = jnp.repeat(k, H // G, axis=1)
    v = jnp.repeat(v, H // G, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1, fp8), _fp8(k, -1, fp8), _fp8(v, -1, fp8)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1, fp8)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(s, H * hd)
    h = h + _mm(o, f(a["wo"]), fp8)
    m = lw["ffn"]
    x = _rms(h, f(lw["norm2"]["scale"]), eps)
    g = jax.nn.silu(_mm(x, f(m["w_gate"]), fp8)) * _mm(x, f(m["w_up"]), fp8)
    return h + _mm(g, f(m["w_down"]), fp8)


@functools.partial(jax.jit, static_argnames=("hf_items", "fp8"))
def _hidden(params, tokens, *, hf_items, fp8):
    hf = dict(hf_items)
    h = params["embed"][tokens].astype(jnp.float32)
    h, _ = jax.lax.scan(lambda c, lw: (_layer(c, lw, hf, fp8), None), h,
                        params["layers"])
    return h


@functools.partial(jax.jit, static_argnames=("hf_items", "fp8"))
def _head(params, h, rows, *, hf_items, fp8):
    hf = dict(hf_items)
    x = _rms(h[rows], params["final_norm"]["scale"].astype(jnp.float32),
             hf["rms_norm_eps"])
    head = (params["embed"].T if hf.get("tie_word_embeddings")
            else params["lm_head"]).astype(jnp.float32)
    return _mm(x, head, fp8)


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
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings")
    hf_items = tuple((k, hf[k]) for k in keys)
    with jax.default_matmul_precision("highest"):
        h = _hidden(params, jnp.asarray(padded), hf_items=hf_items, fp8=fp8)
        out = _head(params, h, jnp.asarray(rows_p), hf_items=hf_items, fp8=fp8)
    return np.asarray(out)[:r]


def served_gaps(params: Dict, hf: Dict, prompt: Sequence[int],
                served: Sequence[int], fp8=False) -> np.ndarray:
    """For each served token, by how much its reference logit lies below
    the reference's best at that position.  With ``fp8`` (a float8 type)
    the token read is the one the float8 forward puts first instead of the
    served one."""
    served = list(served)
    seq = list(prompt) + served[:-1]
    rows = list(range(len(prompt) - 1, len(seq)))
    ref = logits(params, hf, seq, rows)
    pick = np.asarray(served)
    if fp8:
        pick = logits(params, hf, seq, rows, fp8=fp8).argmax(-1)
    return ref.max(-1) - ref[np.arange(len(rows)), pick]
