"""Latent attention (MLA) and the held-expert MoE layer at smoke size, on
the CPU in float32: the absorbed decode agrees with the un-absorbed
forward, prefill then decode included; the held-expert layer drops no
token; the continuous batcher serves the MLA stack in place and counts
the pairs its held experts compute.  The comparison with the plain
reference, and the share test, are in ``bench/tests/test_moonlight.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.launch import serve
from repro.launch.serve import ContinuousBatcher, generate
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.layers import mlp

CFG = get_smoke_config("moonshot_v1_16b")
F32_TOL = dict(atol=2e-5, rtol=2e-5)


@pytest.fixture(scope="module")
def params():
    p = jax.jit(lambda k: T.init_model(k, CFG))(jax.random.PRNGKey(0))
    # a selection bias that moves the routing, as the published one does
    bias = 0.05 * jax.random.uniform(jax.random.PRNGKey(1),
                                     p["layers"]["moe"]["router_bias"].shape,
                                     minval=-1.0, maxval=1.0)
    p["layers"]["moe"]["router_bias"] = bias
    return p


def test_absorbed_decode_matches_unabsorbed_forward(params):
    """Prefill a prompt per row, then decode through the stacked latent
    cache with each row at its own position (absorbed MLA): every logit
    equals the full-sequence forward's (the latent expanded per head)."""
    B, S, n = 2, 14, (5, 9)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, CFG.vocab, (B, S)),
                       jnp.int32)
    full, _ = jax.jit(lambda p, t: T.forward(p, {"tokens": t}, CFG))(params,
                                                                       toks)
    prefill = jax.jit(lambda p, t: T.prefill_counted(p, {"tokens": t}, CFG))
    caches = []
    for b in range(B):
        logits, c, _ = prefill(params, toks[b:b + 1, :n[b]])
        np.testing.assert_allclose(np.asarray(logits[0, 0]),
                                   np.asarray(full[b, n[b] - 1]), **F32_TOL)
        caches.append(T.extend_cache(c, S))
    cache = jax.tree.map(lambda *a: jnp.concatenate(a, axis=1), *caches)
    assert cache["c_kv"].shape == (CFG.n_layers, B, S, CFG.kv_lora_rank)
    assert cache["k_pe"].shape == (CFG.n_layers, B, S, CFG.qk_rope_dim)
    pos = jnp.asarray(n, jnp.int32)
    step = jax.jit(lambda p, t, q, c: T.decode_step_rows(p, t, q, CFG, c))
    for _ in range(S - max(n)):
        logits, cache, _ = step(params, toks[jnp.arange(B), pos][:, None], pos,
                                cache)
        want = full[jnp.arange(B), pos]
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(want),
                                   **F32_TOL)
        pos = pos + 1


def _dense_held_layer(mp, x, cfg):
    """Every held expert on every token, weighted by its routing weight
    (zero where not selected), plus the shared experts."""
    lo, hi = cfg.held_experts
    w, e = M.route_sigmoid(mp["router"], mp["router_bias"], x, cfg.moe_top_k,
                           cfg.routed_scaling)
    y = mlp(mp["shared"], x)
    for j in range(hi - lo):
        wj = jnp.sum(jnp.where(e == lo + j, w, 0.0), axis=-1, keepdims=True)
        h = jax.nn.silu(x @ mp["e_gate"][j]) * (x @ mp["e_up"][j])
        y = y + wj * (h @ mp["e_down"][j])
    return y


def test_held_experts_drop_no_token(params):
    """A bias that sends every token to held expert 2: all 64 tokens are
    computed there (a capacity of 1.25 x the mean load would keep 20), and
    the layer equals every held expert computed densely."""
    mp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    mp["router_bias"] = mp["router_bias"].at[2].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, CFG.d_model))
    y, pairs = jax.jit(lambda p, v: M.held_moe_apply(p, v, CFG))(mp, x)
    xt = x.reshape(64, -1)
    _, e = M.route_sigmoid(mp["router"], mp["router_bias"], xt, CFG.moe_top_k,
                           CFG.routed_scaling)
    lo, hi = CFG.held_experts
    assert bool(jnp.all(jnp.any(e == 2, axis=-1)))
    assert pairs.shape == (2, 32)
    assert int(pairs.sum()) == int(jnp.sum((e >= lo) & (e < hi))) >= 64
    np.testing.assert_allclose(np.asarray(y.reshape(64, -1)),
                               np.asarray(_dense_held_layer(mp, xt, CFG)),
                               **F32_TOL)


def test_held_layer_routes_over_every_expert(params):
    """A bias that selects experts held on another chip for every token:
    this share computes no pair and adds nothing."""
    mp = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    del mp["shared"]
    mp["router_bias"] = jnp.zeros_like(mp["router_bias"]).at[4:8].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16, CFG.d_model))
    y, pairs = jax.jit(lambda p, v: M.held_moe_apply(p, v, CFG))(mp, x)
    assert int(pairs.sum()) == 0
    assert float(jnp.abs(y).max()) == 0.0


def test_batcher_serves_mla_in_place_and_counts_pairs(params):
    """Solo tokens through shared slots on the ``mla`` decode path; the
    on-device counter holds the pairs of every prefill and of the decode
    steps' live rows: as many as one prefill of each request's prompt and
    served tokens, though a slot idles while the last request runs."""
    assert serve._decode_path(CFG) == "mla"
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, CFG.vocab, 8).astype(np.int32) for _ in range(3)]
    solo = [np.asarray(generate(params, CFG, jnp.asarray(p[None]), 6)
                       )[0, 8:].tolist() for p in prompts]
    cb = ContinuousBatcher(params, CFG, slots=2, cache_len=14)
    outs, _ = cb.run(prompts, 6)
    assert outs == solo
    prefill = jax.jit(lambda p, t: T.prefill_counted(p, {"tokens": t}, CFG))
    want = sum(int(prefill(params, jnp.asarray(
        np.concatenate([p, o[:-1]])[None]))[2].sum())
        for p, o in zip(prompts, outs))
    assert cb.held_pairs() == want > 0
    cfg_plain = dataclasses.replace(CFG, moe_router="softmax", n_experts=0,
                                    kv_lora_rank=0)
    assert not serve._counts_held_pairs(cfg_plain)


def test_pair_counter_carries_into_its_high_word():
    held = jnp.asarray([2**32 - 5, 7], jnp.uint32)
    held = serve._add_pairs(held, jnp.int32(10))
    assert np.asarray(held).tolist() == [5, 8]
