"""Serve driver for a DeepSeek-V3-style latent-attention MoE model
(Moonlight-16B-A3B) on one chip's share of its routed experts: open-loop
traffic through the program's ``ContinuousBatcher``, as in
``bench/drivers/serve.py``, whose window and sample it uses.

Set-up makes the weights on the device from the seed
(``bench/weights_mla.py``), builds one batcher and sends it one short
request per prompt length the mix can draw (which compiles every program
the window runs).  Besides ``serve.window``'s counters the window counts
``held_pairs``: the token-expert pairs the held experts computed for the
requests (free slots left out), read from the batcher's device counter
before and after the window.

The comparison runs the plain reference (``bench/reference/moonlight.py``)
over each sampled prompt with its served tokens: the widest and the mean
gap by which a served token's reference logit lies below the reference's
best.
"""
from __future__ import annotations

import gc
import sys
from typing import Dict, List, Tuple

import numpy as np

from bench.drivers.serve import State, prompt_lengths, sample
from bench.drivers.serve import window as serve_window
from bench.weights import sub_seed
from bench.weights_mla import mla_params


def model_config(hf: Dict):
    """The program's ``ModelConfig`` for a DeepSeek-V3-style config.json
    whose ``n_routed_experts`` is the share held here."""
    from repro.configs.base import ModelConfig
    if hf["q_lora_rank"] is not None or hf["n_group"] != 1 or \
            hf["scoring_func"] != "sigmoid" or not hf["norm_topk_prob"]:
        raise ValueError("the program runs MLA without a query latent and "
                         "sigmoid routing in one group, normalised")
    lo, hi = hf["experts_held"]
    if hi - lo != hf["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count of experts_held")
    return ModelConfig(
        name=hf["name"], family="moe", source=hf["source"],
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab=hf["vocab_size"], tie_embeddings=hf["tie_word_embeddings"],
        n_experts=hf["published"]["n_routed_experts"],
        moe_top_k=hf["num_experts_per_tok"], moe_router="sigmoid",
        moe_d_ff=hf["moe_intermediate_size"],
        n_shared_experts=hf["n_shared_experts"],
        routed_scaling=float(hf["routed_scaling_factor"]),
        experts_held=(lo, hi), first_k_dense=hf["first_k_dense_replace"],
        kv_lora_rank=hf["kv_lora_rank"], qk_nope_dim=hf["qk_nope_head_dim"],
        qk_rope_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        rope_theta=float(hf["rope_theta"]), norm_eps=float(hf["rms_norm_eps"]))


def setup(spec: Dict, seed: int, probe) -> State:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import ContinuousBatcher
    from repro.models import transformer as T

    hf, cell = spec["config"], spec["cell"]
    dtype = jnp.dtype(hf["torch_dtype"])
    st = State()
    st.spec, st.seed, st.hf = spec, seed, hf
    st.cfg = model_config(hf)
    st.weight_seed = sub_seed(seed, 1)
    params = mla_params(hf, st.weight_seed, dtype)
    want = jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), st.cfg, dtype))
    if (jax.tree.structure(want) != jax.tree.structure(params) or
            jax.tree.leaves(jax.tree.map(lambda a, b: a.shape != b.shape
                                         or a.dtype != b.dtype, want, params))
            .count(True)):
        raise ValueError("the benchmark's weights do not match the program's "
                         "layout")
    st.cb = ContinuousBatcher(params, st.cfg, slots=cell["slots"],
                              cache_len=cell["cache_len"])
    del params
    probe.wrap(st.cb, "_admit", "admit")
    # warm up: one request per prompt length, through the same batcher
    rng = np.random.default_rng(sub_seed(seed, 3))
    for n in prompt_lengths(spec["mix"]):
        st.cb.submit(rng.integers(0, hf["vocab_size"], n).astype(np.int32), 3)
    while st.cb.pending():
        st.cb.step()
        if st.cb._trace:
            np.asarray(st.cb._trace[-1])
    if st.cb.held_pairs() is None:
        raise ValueError("the batcher counts no held-expert pairs")
    return st


def window(st: State, seconds: float, probe) -> Dict:
    before = st.cb.held_pairs()
    out = serve_window(st, seconds, probe)
    probe.count("held_pairs", st.cb.held_pairs() - before)
    return out


def readings(st: State, control: bool = False) -> Dict[str, float]:
    """Over the sampled served tokens: ``logit_gap``, the widest gap;
    ``mean_gap``, the mean gap; ``token_mismatch``, the share that are not
    the reference's first choice.  With ``control``, the same of the
    tokens that the reference in float8 e4m3 puts first at those
    positions, under ``control_``.  Frees the program's state first."""
    import jax.numpy as jnp
    from bench.reference import moonlight

    by_index = {i: rid for rid, i in st.rid_of.items()}
    served = {i: st.cb.result(by_index[i]) for i in range(len(st.sched))
              if st.done[i]}
    picked = sample(st, served)
    st.cb = None
    gc.collect()
    if not picked:
        return {}
    params = mla_params(st.hf, st.weight_seed, jnp.dtype(st.hf["torch_dtype"]))
    kinds = [("", False)] + ([("control_", "e4m3")] if control else [])
    gaps = {name: [] for name, _ in kinds}
    for i in picked:
        for name, fp8 in kinds:
            gaps[name].append(moonlight.served_gaps(
                params, st.hf, st.sched[i][1], served[i], fp8=fp8))
    out = {"checked_tokens": float(sum(len(g) for g in gaps[""]))}
    for name, g in gaps.items():
        g = np.concatenate(g)
        out[name + "logit_gap"] = float(g.max())
        out[name + "token_mismatch"] = float(np.mean(g > 0))
        out[name + "mean_gap"] = float(g.mean())
    return out


def check(st: State) -> List[Tuple[str, float, float]]:
    from bench.harness import checks_of
    r = readings(st)
    print(f"serve_mla: compared {r.get('checked_tokens', 0):.0f} served "
          f"tokens", file=sys.stderr)
    return checks_of(r, st.spec["cell"]["limits"])
