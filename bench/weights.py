"""Random weights from a seed, made on the device in one jitted call, in
the layout the program under test takes and in the type it serves.

The plain references (``bench/reference``) build the same weights with
these functions from the same seed; they take nothing the program made.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0]) >> 1), int(s[1]) >> 1)


def sub_seed(seed: int, purpose: int) -> int:
    """A 31-bit seed for ``purpose``, drawn from ``seed``."""
    return int(np.random.SeedSequence([int(seed), purpose]).generate_state(1)[0]
               >> 2)


def lm_shapes(hf: Dict) -> Dict:
    """Leaf shapes of a dense decoder-only LM in the program's layout."""
    d, L = hf["hidden_size"], hf["num_hidden_layers"]
    H, G = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, ff, V = hf["head_dim"], hf["intermediate_size"], hf["vocab_size"]
    tree = {
        "embed": (V, d),
        "final_norm": {"scale": (d,)},
        "layers": {
            "norm1": {"scale": (L, d)}, "norm2": {"scale": (L, d)},
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, G * hd),
                     "wv": (L, d, G * hd), "wo": (L, H * hd, d),
                     "q_norm": (L, hd), "k_norm": (L, hd)},
            "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },
    }
    if not hf.get("tie_word_embeddings", False):
        tree["lm_head"] = (d, V)
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def lm_params(hf: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """Uniform weights scaled by 1/sqrt(fan-in) (the embedding by
    1/sqrt(d)); norm scales uniform in [0.8, 1.2], so that a norm that is
    left out shows."""
    shapes = lm_shapes(hf)
    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=_is_shape)[0]]

    def make(key):
        leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shape, path in zip(keys, leaves, paths):
            name = str(path[-1].key)
            if name in ("scale", "q_norm", "k_norm"):
                v = jax.random.uniform(k, shape, jnp.float32, 0.8, 1.2)
            else:
                fan_in = shape[-1] if name == "embed" else shape[-2]
                s = 1.0 / math.sqrt(fan_in)
                v = jax.random.uniform(k, shape, jnp.float32, -s, s)
            out.append(v.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(jax_key(seed))


def cnn_params(cfg: Dict, seed: int) -> Dict:
    """The paper's CNN in the program's layout, f32: uniform weights scaled
    by 1/sqrt(fan-in), biases uniform in +-0.01."""
    k, ch, c_in = cfg["kernel"], cfg["channels"], cfg["image_channels"]
    flat = (cfg["image_side"] // 4) ** 2 * ch
    fc, ncls = cfg["fc_width"], cfg["n_classes"]
    shapes = {"conv1": (k, k, c_in, ch), "b1": (ch,),
              "conv2": (k, k, ch, ch), "b2": (ch,),
              "fc1": (flat, fc), "bf1": (fc,), "fc2": (fc, ncls),
              "bf2": (ncls,)}

    def make(key):
        keys = jax.random.split(key, len(shapes))
        out = {}
        for kk, (name, shape) in zip(keys, sorted(shapes.items())):
            if len(shape) == 1:
                s = 0.01
            else:
                s = 1.0 / math.sqrt(int(np.prod(shape[:-1])))
            out[name] = jax.random.uniform(kk, shape, jnp.float32, -s, s)
        return out

    return jax.jit(make)(jax_key(seed))
