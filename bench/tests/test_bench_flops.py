"""Analytic counts against hand counts."""
import json
import os

import jax
import jax.numpy as jnp

from bench import flops, weights

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_cnn_counts_by_hand():
    m = _config("fmnist_cnn")["model"]
    macs = flops.cnn_layer_macs(m)
    # conv1: 28*28 outputs x 32 channels x 2*2*1 taps; conv2 at 14*14 with
    # 2*2*32 taps; fc1 7*7*32 -> 128; fc2 128 -> 10
    assert macs == {"conv1": 100352, "conv2": 802816, "fc1": 200704,
                    "fc2": 1280}
    fwd = 100352 + 802816 + 200704 + 1280
    assert flops.cnn_train_flops_per_sample(m) == 2 * (3 * fwd - 100352)
    assert flops.cnn_params(m) == m["parameters"] == 206410
    w = weights.cnn_params(m, 0)
    assert sum(int(v.size) for v in w.values()) == 206410


def test_one_qwen3_layer_by_hand():
    hf = _config("qwen3-1.7b")
    # wq, wo: 2048 x 2048 each; wk, wv: 2048 x 1024 each; SwiGLU 3 x 2048 x 6144
    per_layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert flops.lm_layer_matmul_params(hf) == per_layer == 50331648
    # plus two RMSNorms of 2048 and qk-norms of 128, 28 layers, the tied
    # 151,936 x 2048 embedding and the final norm
    assert flops.lm_params(hf) == 28 * (per_layer + 4096 + 256) + \
        151936 * 2048 + 2048 == 1720574976
    # one decode token at context 100: every layer's matmuls, QK and PV
    # over 100 positions for 16 heads of 128, and the head
    assert flops.lm_decode_flops(hf, 100) == 28 * (
        2 * per_layer + 4 * 16 * 128 * 100) + 2 * 2048 * 151936
    # a prompt of 3 tokens: 6 query-key pairs under the causal mask
    assert flops.lm_prefill_flops(hf, 3) == 28 * (
        2 * per_layer * 3 + 4 * 16 * 128 * 6) + 2 * 2048 * 151936
    # keys and values of one position: 2 x 28 layers x 8 heads x 128 x 2 B
    assert flops.lm_kv_bytes_per_position(hf) == 114688
    counts = {"requests": 1, "prompt_tokens": 3, "prompt_pairs": 6,
              "decode_tokens": 1, "decode_ctx_positions": 100}
    assert flops.lm_served_flops(hf, counts) == (
        flops.lm_prefill_flops(hf, 3) + flops.lm_decode_flops(hf, 100))


def test_weight_shapes_count_the_parameters():
    hf = _config("qwen3-1.7b")
    shapes = jax.eval_shape(lambda: weights.lm_params(hf, 0, jnp.bfloat16))
    n = sum(int(jnp.prod(jnp.asarray(a.shape))) for a in jax.tree.leaves(shapes))
    assert n == flops.lm_params(hf)
