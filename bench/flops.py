"""Analytic operation and byte counts, from a configuration's shapes.

Counts are of the work the algorithm needs: padding, masked steps and
positions the program reads beyond a request's context do not count.
A multiply-add is 2 FLOPs.
"""
from __future__ import annotations

from typing import Dict, Iterable


# -- the paper's CNN (conv 2x2 SAME + pool, twice; fc; fc) ------------------
def cnn_layer_macs(cfg: Dict) -> Dict[str, int]:
    """Multiply-adds of one forward pass of one image, per layer."""
    side, ch, k = cfg["image_side"], cfg["channels"], cfg["kernel"]
    c_in = cfg["image_channels"]
    h1 = side                      # conv1 runs at full resolution
    h2 = side // 2                 # after the first 2x2 pool
    flat = (side // 4) ** 2 * ch   # after the second pool
    return {
        "conv1": h1 * h1 * ch * k * k * c_in,
        "conv2": h2 * h2 * ch * k * k * ch,
        "fc1": flat * cfg["fc_width"],
        "fc2": cfg["fc_width"] * cfg["n_classes"],
    }


def cnn_train_flops_per_sample(cfg: Dict) -> int:
    """Forward, weight gradients and input gradients of one sample.  The
    first layer needs no input gradient."""
    macs = cnn_layer_macs(cfg)
    fwd = sum(macs.values())
    return 2 * (fwd + fwd + (fwd - macs["conv1"]))


def cnn_params(cfg: Dict) -> int:
    side, ch, k = cfg["image_side"], cfg["channels"], cfg["kernel"]
    c_in, fc, ncls = cfg["image_channels"], cfg["fc_width"], cfg["n_classes"]
    flat = (side // 4) ** 2 * ch
    return (k * k * c_in * ch + ch + k * k * ch * ch + ch
            + flat * fc + fc + fc * ncls + ncls)


# -- decoder-only LM with GQA, SwiGLU, tied or untied head ------------------
def lm_dims(hf: Dict) -> Dict[str, int]:
    """The sizes below read from a Hugging Face style ``config.json``."""
    d = hf["hidden_size"]
    return {"L": hf["num_hidden_layers"], "d": d,
            "H": hf["num_attention_heads"], "G": hf["num_key_value_heads"],
            "hd": hf.get("head_dim", d // hf["num_attention_heads"]),
            "ff": hf["intermediate_size"], "V": hf["vocab_size"],
            "tied": bool(hf.get("tie_word_embeddings", False))}


def lm_layer_matmul_params(hf: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    m = lm_dims(hf)
    attn = m["d"] * m["H"] * m["hd"] * 2 + m["d"] * m["G"] * m["hd"] * 2
    return attn + 3 * m["d"] * m["ff"]


def lm_params(hf: Dict) -> int:
    m = lm_dims(hf)
    layer = lm_layer_matmul_params(hf) + 2 * m["d"] + 2 * m["hd"] * bool(
        hf.get("qk_norm", True))
    emb = m["V"] * m["d"] * (1 if m["tied"] else 2)
    return m["L"] * layer + emb + m["d"]


def lm_prefill_flops(hf: Dict, s: int) -> int:
    """A prompt of ``s`` tokens with causal attention; the program computes
    the logits of the last position only."""
    m = lm_dims(hf)
    per_layer = (2 * lm_layer_matmul_params(hf) * s
                 + 4 * m["H"] * m["hd"] * s * (s + 1) // 2)
    return m["L"] * per_layer + 2 * m["d"] * m["V"]


def lm_decode_flops(hf: Dict, ctx: int) -> int:
    """One generated token attending ``ctx`` positions (itself included)."""
    m = lm_dims(hf)
    return (m["L"] * (2 * lm_layer_matmul_params(hf)
                      + 4 * m["H"] * m["hd"] * ctx)
            + 2 * m["d"] * m["V"])


def lm_kv_bytes_per_position(hf: Dict, kv_bytes: int = 2) -> int:
    m = lm_dims(hf)
    return 2 * m["L"] * m["G"] * m["hd"] * kv_bytes


def lm_decode_step_bytes(hf: Dict, ctxs: Iterable[int],
                         weight_bytes: int = 2, kv_bytes: int = 2) -> int:
    """HBM bytes one batched decode step needs: every weight once, and the
    keys and values of each active request's context."""
    return (lm_params(hf) * weight_bytes
            + lm_kv_bytes_per_position(hf, kv_bytes) * sum(ctxs))


def lm_served_flops(hf: Dict, c: Dict) -> float:
    """FLOPs of the prompts and generated tokens a serving run counted:
    ``requests``, ``prompt_tokens`` (sum of S), ``prompt_pairs`` (sum of
    S(S+1)/2), ``decode_tokens`` and ``decode_ctx_positions`` (sum of the
    positions each generated token attended)."""
    m = lm_dims(hf)
    mat = 2 * lm_layer_matmul_params(hf) * m["L"]
    att = 4 * m["H"] * m["hd"] * m["L"]
    head = 2 * m["d"] * m["V"]
    return (mat * c["prompt_tokens"] + att * c["prompt_pairs"]
            + head * c["requests"]
            + (mat + head) * c["decode_tokens"]
            + att * c["decode_ctx_positions"])
