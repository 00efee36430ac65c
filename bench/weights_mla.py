"""Random weights from a seed for a DeepSeek-V3-style latent-attention MoE
model (Moonlight-16B-A3B's ``config.json``), made on the device in one
jitted call, in the layout the program under test takes: the leading
dense layers under ``dense_layers``, the MoE layers under ``layers``, and
of each MoE layer's routed experts only those this chip holds
(``experts_held``).

The plain reference (``bench/reference/moonlight.py``) builds the same
weights with these functions from the same seed; it takes nothing the
program made.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.weights import jax_key

BIAS = 0.05           # e_score_correction_bias is uniform in +-BIAS
ROUTER_DTYPE = jnp.float32   # router weight and bias, as the program keeps them


def dims(hf: Dict) -> Dict[str, int]:
    """The sizes below, read from the configuration file."""
    lo, hi = hf["experts_held"]
    return {"L": hf["num_hidden_layers"], "K": hf["first_k_dense_replace"],
            "d": hf["hidden_size"], "H": hf["num_attention_heads"],
            "r": hf["kv_lora_rank"], "dn": hf["qk_nope_head_dim"],
            "dr": hf["qk_rope_head_dim"], "dv": hf["v_head_dim"],
            "ff": hf["intermediate_size"], "fe": hf["moe_intermediate_size"],
            "E": hf["published"]["n_routed_experts"], "held": hi - lo,
            "shared": hf["n_shared_experts"], "k": hf["num_experts_per_tok"],
            "V": hf["vocab_size"]}


def mla_shapes(hf: Dict) -> Dict:
    """Leaf shapes in the program's layout."""
    m = dims(hf)
    d, H, r = m["d"], m["H"], m["r"]

    def layers(n, moe):
        t = {"norm1": {"scale": (n, d)}, "norm2": {"scale": (n, d)},
             "attn": {"wq": (n, d, H * (m["dn"] + m["dr"])),
                      "wkv_a": (n, d, r + m["dr"]), "kv_norm": (n, r),
                      "wkv_b": (n, r, H * (m["dn"] + m["dv"])),
                      "wo": (n, H * m["dv"], d)}}
        if moe:
            fe, eh, fs = m["fe"], m["held"], m["shared"] * m["fe"]
            t["moe"] = {"router": (n, d, m["E"]), "router_bias": (n, m["E"]),
                        "e_gate": (n, eh, d, fe), "e_up": (n, eh, d, fe),
                        "e_down": (n, eh, fe, d),
                        "shared": {"w_gate": (n, d, fs), "w_up": (n, d, fs),
                                   "w_down": (n, fs, d)}}
        else:
            t["ffn"] = {"w_gate": (n, d, m["ff"]), "w_up": (n, d, m["ff"]),
                        "w_down": (n, m["ff"], d)}
        return t

    tree = {"embed": (m["V"], d), "final_norm": {"scale": (d,)},
            "lm_head": (d, m["V"]), "layers": layers(m["L"] - m["K"], True)}
    if m["K"]:
        tree["dense_layers"] = layers(m["K"], False)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def mla_params(hf: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Uniform weights scaled by 1/sqrt(fan-in) (the embedding by
    1/sqrt(d)); norm scales uniform in [0.8, 1.2], so that a norm that is
    left out shows; the routers' selection bias uniform in +-``BIAS``, so
    that a selection without it shows.  Routers in float32, the rest in
    ``dtype``."""
    shapes = mla_shapes(hf)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes,
                                                         is_leaf=_is_shape)

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shape) in zip(keys, flat):
            name = str(path[-1].key)
            if name in ("scale", "kv_norm"):
                v = jax.random.uniform(k, shape, jnp.float32, 0.8, 1.2)
            elif name == "router_bias":
                v = jax.random.uniform(k, shape, jnp.float32, -BIAS, BIAS)
            else:
                fan_in = shape[-1] if name == "embed" else shape[-2]
                s = 1.0 / math.sqrt(fan_in)
                v = jax.random.uniform(k, shape, jnp.float32, -s, s)
            out.append(v.astype(ROUTER_DTYPE if name.startswith("router")
                                else dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax_key(seed))
