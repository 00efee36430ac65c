"""The latent-attention MoE cell's pieces at smoke size, on the host CPU:
the program against the plain reference (``bench/reference/moonlight.py``)
on seeded random weights (``bench/weights_mla.py``), the share test, the
analytic counts against hand counts and the two new metric readers.  The
``serve_mla`` driver's own run is in ``test_serve_mla.py``."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_mla, weights_mla
from bench.reference import moonlight as ref

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")

# Moonlight's shape, small: 1 dense + 2 MoE layers, latent 32, rope 16,
# 16 routed experts of which 4 held, top-4, 1 shared
MLA = {
    "name": "mla-smoke", "source": "test", "reduced": ["n_routed_experts"],
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "moe_intermediate_size": 32, "n_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 16, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "torch_dtype": "float32", "v_head_dim": 16, "vocab_size": 256,
    "published": {"n_routed_experts": 16}, "experts_held": [0, 4],
}
# float32 on the CPU: program and reference differ in the order of their
# sums (absorbed decode, grouped expert matmuls), by a few ulps of logits
# of order 1
F32_TOL = dict(atol=1e-4, rtol=1e-4)


def _hf(**held):
    return dict(MLA, **held)


def _cfg(hf):
    from bench.drivers.serve_mla import model_config
    return model_config(hf)


def test_weights_match_the_program_layout():
    from repro.models import transformer as T
    for hf in (MLA, _config("moonlight-16b-a3b")):
        cfg = _cfg(hf)
        want = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg,
                                                   jnp.bfloat16))
        got = jax.eval_shape(lambda: weights_mla.mla_params(hf, 0))
        assert jax.tree.structure(want) == jax.tree.structure(got)
        assert jax.tree.leaves(jax.tree.map(
            lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype), want,
            got)) == [True] * len(jax.tree.leaves(want))


def test_batcher_logits_match_the_reference():
    """Requests served through the continuous batcher, and the logits of
    its prefill and in-place decode programs along each served sequence,
    against the reference over the prompt and its served tokens."""
    from repro.launch import serve
    from repro.launch.serve import ContinuousBatcher
    from repro.models import transformer as T
    cfg = _cfg(MLA)
    params = weights_mla.mla_params(MLA, 5, jnp.float32)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (5, 11, 8)]
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=24)
    outs, _ = cb.run(prompts, 9)
    step = jax.jit(lambda p, t, q, c: T.decode_step_rows(p, t, q, cfg, c)[:2])
    for prompt, served in zip(prompts, outs):
        seq = list(prompt) + served[:-1]
        rows = list(range(len(prompt) - 1, len(seq)))
        want = ref.logits(params, MLA, seq, rows)
        logits, cache = serve._prefill_jit(cfg)(params, jnp.asarray(prompt[None]))
        cache = T.extend_cache(cache, 24)
        got = [np.asarray(logits[0, -1])]
        for i, tok in enumerate(served[:-1]):
            lg, cache = step(params, jnp.asarray([[tok]], jnp.int32),
                             jnp.asarray([len(prompt) + i], jnp.int32), cache)
            got.append(np.asarray(lg[0, -1]))
        np.testing.assert_allclose(np.stack(got), want, **F32_TOL)
        assert served == want.argmax(-1).tolist()


def test_the_shares_sum_to_the_uncut_layer():
    """One MoE layer over 16 routed experts: the program's four shares of
    4 experts each, with the shared experts counted once, add up to the
    reference's uncut layer (every expert held)."""
    from repro.models import moe as M
    from repro.models.layers import mlp
    uncut = _hf(n_routed_experts=16, experts_held=[0, 16])
    m = jax.tree.map(lambda a: a[0],
                     weights_mla.mla_params(uncut, 7, jnp.float32)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, 64))
    total = mlp(m["shared"], x)
    for lo in range(0, 16, 4):
        cfg = _cfg(_hf(experts_held=[lo, lo + 4]))
        share = dict(m, **{k: m[k][lo:lo + 4]
                           for k in ("e_gate", "e_up", "e_down")})
        del share["shared"]
        y, _ = jax.jit(lambda p, v: M.held_moe_apply(p, v, cfg))(share, x)
        total = total + y
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v, p: ref.moe(v, p, uncut))(x[0], m)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _config(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_moonlight_counts_by_hand():
    hf = _config("moonlight-16b-a3b")
    # wq 2048 x 16*(128+64); wkv_a 2048 x (512+64); wkv_b 512 x 16*(128+128);
    # wo 16*128 x 2048
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert flops_mla.attn_matmul_params(hf) == attn == 13762560
    # 27 layers of MLA; layer 0's SwiGLU of 11,264; 26 MoE layers with a
    # router over 64 and two shared experts of 1,408
    assert flops_mla.token_matmul_flops(hf) == 2 * (
        27 * attn + 3 * 2048 * 11264 + 26 * (2048 * 64 + 3 * 2048 * 2816))
    assert flops_mla.expert_pair_flops(hf) == 2 * 3 * 2048 * 1408
    assert flops_mla.prefill_pair_flops(hf) == 27 * 2 * 16 * (192 + 128)
    assert flops_mla.decode_position_flops(hf) == 27 * 2 * 16 * (1024 + 64)
    # held: 26 x (MLA + norms + router and bias + 8 experts + shared), the
    # dense layer, embedding, head, final norm; routers in float32
    moe = 2048 * 64 + 64
    held = 8 * 3 * 2048 * 1408 + 3 * 2048 * 2816
    per_layer = attn + 512 + 2 * 2048
    params = (26 * (per_layer + held) + per_layer + 3 * 2048 * 11264
              + 2 * 163840 * 2048 + 2048)
    assert flops_mla.weight_bytes(hf) == 2 * params + 4 * 26 * moe
    assert 3.36e9 < params + 26 * moe < 3.37e9
    # the cached latent and rope key: 27 x 576 x 2 B per position
    assert flops_mla.latent_bytes_per_position(hf) == 31104
    assert flops_mla.decode_step_bytes(hf, 100) == (
        flops_mla.weight_bytes(hf) - 163840 * 2048 * 2 + 31104 * 100)
    assert flops_mla.expected_held_pairs(hf, 64) == 64 * 26 * 6 * 8 / 64
    c = {"requests": 1, "prompt_tokens": 3, "prompt_pairs": 6,
         "decode_tokens": 2, "decode_ctx_positions": 9, "held_pairs": 10}
    assert flops_mla.served_flops(hf, c) == (
        flops_mla.token_matmul_flops(hf) * 5 + 27 * 2 * 16 * 320 * 6
        + 27 * 2 * 16 * 1088 * 9 + 2 * 2048 * 163840 * 3
        + 2 * 3 * 2048 * 1408 * 10)


def test_the_new_readers_by_hand():
    from bench import harness
    hf = _config("moonlight-16b-a3b")
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    c = {"decode_steps": 10, "decode_tokens": 400,
         "decode_ctx_positions": 100000, "requests": 5, "prompt_tokens": 1000,
         "prompt_pairs": 200000, "held_pairs": 3000}
    ctx = {"counters": c, "config": hf, "peaks": peaks, "window_s": 2.0,
           "trace": {"programs": {"jit_step": (0.15, 10),
                                  "jit_prefill": (0.5, 5)}}}
    spec = {"root": os.path.dirname(os.path.dirname(CONFIGS))}
    least = flops_mla.decode_step_bytes(hf, 10000) / 819e9
    assert harness.read_metric(spec, "moe_mla_decode_hbm_roofline", ctx) == \
        pytest.approx(least / 0.015 * 100)
    assert harness.read_metric(spec, "moe_mla_serve_mfu", ctx) == \
        pytest.approx(flops_mla.served_flops(hf, c) / (2.0 * 197e12) * 100)
    # a run of a program without the counter reports neither
    qwen = dict(ctx, config=_config("qwen3-1.7b"),
                counters={k: v for k, v in c.items() if k != "held_pairs"})
    assert harness.read_metric(spec, "moe_mla_decode_hbm_roofline", qwen) is None
    assert harness.read_metric(spec, "moe_mla_serve_mfu", qwen) is None
