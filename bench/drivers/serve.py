"""Serve driver: open-loop traffic through the program's
``ContinuousBatcher``.

Set-up makes the weights on the device from the seed, in the configured
dtype, builds one batcher and sends it one short request per prompt
length the mix can draw (which compiles every program the window runs).
The window submits each request of the seed's schedule when it is due,
steps the batcher, and reads each step's tokens on the host, as a
streaming server must.  After the window it steps on until every request
that arrived inside it has been admitted (has its first token), and
stops there.

* ``ttft_p95_ms``: from each request's scheduled arrival to the end of
  the step that admitted it (its first token is on the host then), p95
  over all requests that arrived in the window.
* ``itl_p95_ms``: the gaps between consecutive tokens of a request on the
  host, p95 over all gaps of those requests until the run stops.
* ``serve_tokens_per_s``: tokens delivered inside the window over its
  length.

The comparison samples finished requests from the seed, the longest among
them, and runs the plain reference over each prompt with its served
tokens (``bench/reference/qwen3.py``): the widest and the mean gap by
which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from bench.traffic.arrivals import schedule
from bench.weights import lm_params, sub_seed

class State:
    pass


def model_config(hf: Dict):
    """The program's ``ModelConfig`` for a dense Qwen3-style config.json."""
    from repro.configs.base import ModelConfig
    cfg = ModelConfig(
        name=hf["name"], family="dense", source=hf["source"],
        n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        vocab=hf["vocab_size"], qk_norm=True,
        tie_embeddings=hf["tie_word_embeddings"],
        rope_theta=float(hf["rope_theta"]), norm_eps=float(hf["rms_norm_eps"]))
    if cfg.head_dim != hf["head_dim"]:
        raise ValueError(f"head_dim {hf['head_dim']} is not d_model / heads "
                         f"({cfg.head_dim}); the program cannot run it")
    return cfg


def prompt_lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can draw."""
    p = mix["prompt"]
    if p["dist"] == "choice":
        return sorted(p["values"])
    return sorted(p["round_up_to"])


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
    params = lm_params(hf, st.weight_seed, dtype)
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
    probe.wrap(st.cb, "_admit", "admit",
               after=lambda a, k, out: probe.count("admit_calls"))
    # warm up: one request per prompt length, through the same batcher
    rng = np.random.default_rng(sub_seed(seed, 3))
    for n in prompt_lengths(spec["mix"]):
        st.cb.submit(rng.integers(0, hf["vocab_size"], n).astype(np.int32), 3)
    while st.cb.pending():
        st.cb.step()
        if st.cb._trace:
            np.asarray(st.cb._trace[-1])
    return st


def window(st: State, seconds: float, probe) -> Dict:
    cb = st.cb
    sched = schedule(st.spec["mix"], seconds, st.hf["vocab_size"],
                     sub_seed(st.seed, 2))
    rid_of: Dict[int, int] = {}          # batcher rid -> schedule index
    first = np.full(len(sched), np.nan)
    last = np.full(len(sched), np.nan)
    done = np.zeros(len(sched), bool)
    gaps: List[float] = []
    late: List[float] = []
    seen = set(cb._first)
    in_window_tokens = 0
    nxt = 0
    longest = (0.0, 0.0, 0)       # the longest step: seconds, at, admitted
    # the longest pass of this loop: seconds, at, its step's and token read's
    slowest, t_step_s, t_read_s = (0.0, 0.0, 0.0, 0.0), 0.0, 0.0
    t0 = time.perf_counter()
    end = t0 + seconds
    closed = False
    t_pass = t0
    while True:
        now = time.perf_counter()
        if now - t_pass > slowest[0]:
            slowest = (now - t_pass, t_pass - t0, t_step_s, t_read_s)
        t_pass, t_step_s, t_read_s = now, 0.0, 0.0
        if not closed and now >= end:
            closed = True
            st.queue_at_close = len(cb._queue)
        while nxt < len(sched) and t0 + sched[nxt][0] <= now:
            _, prompt, gen = sched[nxt]
            rid_of[cb.submit(prompt, gen)] = nxt
            late.append(now - (t0 + sched[nxt][0]))
            nxt += 1
        if now >= end and nxt >= len(sched) and not cb._queue:
            break                     # every arrival has its first token
        if not cb.pending():
            if nxt >= len(sched):
                break
            time.sleep(max(0.0, min(t0 + sched[nxt][0] - now, 1e-3)))
            continue
        n_trace = len(cb._trace)
        t_step = time.perf_counter()
        finished = cb.step()
        t_admit = time.perf_counter()
        admitted = set(cb._first) - seen
        t_step_s = t_admit - t_step
        if t_step_s > longest[0]:
            longest = (t_step_s, t_step - t0, len(admitted))
        probe.count("admitted", len(admitted))
        for rid in admitted:
            seen.add(rid)
            i = rid_of[rid]
            first[i] = last[i] = t_admit
            in_window_tokens += t_admit <= end
        if len(cb._trace) > n_trace:
            np.asarray(cb._trace[-1])             # this step's tokens
            t_tok = time.perf_counter()
            t_read_s = t_tok - t_admit
            active = [r for r in cb._rid if r >= 0] + list(finished)
            for rid in active:
                i = rid_of[rid]
                gaps.append(t_tok - last[i])
                last[i] = t_tok
            probe.count("decode_steps")
            probe.count("decode_ctx_positions", sum(
                len(sched[rid_of[r]][1]) + len(cb._slots_of[r]) for r in active))
            probe.count("decode_tokens", len(active))
            in_window_tokens += len(active) * (t_tok <= end)
        for rid in finished:
            done[rid_of[rid]] = True
    elapsed = time.perf_counter() - t0
    st.sched, st.rid_of, st.done = sched, rid_of, done
    n = len(sched)
    lens = np.asarray([len(p) for _, p, _ in sched], np.float64)
    probe.counters.update(requests=float(n), prompt_tokens=float(lens.sum()),
                          prompt_pairs=float((lens * (lens + 1) / 2).sum()))
    print(f"serve: {n} requests in {seconds:g} s, drained at {elapsed:.3f} s; "
          f"generator late by at most {max(late, default=0.0) * 1e3:.3f} ms; "
          f"longest step {longest[0] * 1e3:.3f} ms at {longest[1]:.3f} s, "
          f"admitting {longest[2]}; longest pass of the loop "
          f"{slowest[0] * 1e3:.3f} ms at {slowest[1]:.3f} s (its step "
          f"{slowest[2] * 1e3:.3f} ms, token read {slowest[3] * 1e3:.3f} ms)",
          file=sys.stderr)
    ttft = (first - np.asarray([t0 + s[0] for s in sched])) * 1e3
    return {"end_to_end": {
                "ttft_p95_ms": float(np.percentile(ttft[~np.isnan(ttft)], 95)),
                "itl_p95_ms": float(np.percentile(np.asarray(gaps) * 1e3, 95)),
                "serve_tokens_per_s": in_window_tokens / seconds},
            "window_s": elapsed, "attempted": n,
            "failed": int(np.isnan(first).sum())}


def sample(st: State, served: Dict[int, List[int]]) -> List[int]:
    """Schedule indices of the finished requests the comparison reads: the
    longest, then others drawn from the seed, until ``sample_tokens``
    served tokens are covered or ``sample_max`` requests are taken."""
    cell = st.spec["cell"]
    idx = [i for i in range(len(st.sched)) if st.done[i]]
    if not idx:
        return []
    longest = max(idx, key=lambda i: (len(st.sched[i][1]) + st.sched[i][2], -i))
    rng = np.random.default_rng(sub_seed(st.seed, 4))
    order = [longest] + [i for i in rng.permutation(idx) if i != longest]
    out, tokens = [], 0
    for i in order:
        out.append(int(i))
        tokens += st.sched[i][2]
        if tokens >= cell["sample_tokens"] or len(out) >= cell["sample_max"]:
            break
    return out


def readings(st: State, control: bool = False) -> Dict[str, float]:
    """Over the sampled served tokens: ``logit_gap``, the widest gap;
    ``mean_gap``, the mean gap; ``token_mismatch``, the share that are not
    the reference's first choice.  With ``control``, the same of the
    tokens that the reference in float8 e4m3 puts first at those
    positions, under ``control_``.  Frees the program's state first."""
    import jax.numpy as jnp
    from bench.reference import qwen3

    by_index = {i: rid for rid, i in st.rid_of.items()}
    served = {i: st.cb.result(by_index[i]) for i in range(len(st.sched))
              if st.done[i]}
    picked = sample(st, served)
    st.cb = None
    gc.collect()
    if not picked:
        return {}
    params = lm_params(st.hf, st.weight_seed, jnp.dtype(st.hf["torch_dtype"]))
    kinds = [("", False)] + ([("control_", "e4m3")] if control else [])
    gaps = {name: [] for name, _ in kinds}
    for i in picked:
        for name, fp8 in kinds:
            gaps[name].append(qwen3.served_gaps(params, st.hf, st.sched[i][1],
                                                served[i], fp8=fp8))
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
    print(f"serve: compared {r.get('checked_tokens', 0):.0f} served tokens",
          file=sys.stderr)
    return checks_of(r, st.spec["cell"]["limits"])
