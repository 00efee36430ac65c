"""Serving front door: continuous batching + the FL -> serve bridge.

Three layers:

* **continuous-batching correctness** — greedy tokens out of a
  ``ContinuousBatcher`` slot must equal a solo ``generate`` of the same
  prompt, including requests admitted mid-flight into a slot another
  request just freed (the admission splice may not perturb resident
  rows, and a recycled slot's stale cache beyond the new prompt must be
  invisible behind the position mask).
* **checkpoint -> serve roundtrip** — weights pulled out of an engine or
  fleet ``state_dict`` blob via ``load_sim_params`` must equal the live
  server's weights leaf-for-leaf, and validation must reject non-LM
  tasks, bad task indices and non-checkpoint blobs loudly.
* **benchmark harness smoke** — ``benchmarks.serve_bench.run`` on a tiny
  workload without writing results, plus the merge-not-clobber
  discipline of results/serve_bench.json (tier1.sh ``-m smoke`` slice).
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.io import load_sim_params, save_blob
from repro.configs.base import get_smoke_config
from repro.fl.protocols import make_setup, make_sim
from repro.fl.simulator import SimConfig
from repro.fl.tasks import get_task
from repro.launch import serve
from repro.launch.serve import ContinuousBatcher, generate, load_task_params
from repro.models import transformer as T

P_LEN, GEN = 8, 6


@pytest.fixture(scope="module")
def lm():
    """(params, cfg, prompts, solo-greedy reference tokens) on the tiny
    FL transformer LM."""
    task = get_task("transformer_lm")
    params = task.init_params(jax.random.PRNGKey(0))
    cfg = task.model_cfg
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, P_LEN).astype(np.int32)
               for _ in range(5)]
    solo = [np.asarray(generate(params, cfg, jnp.asarray(p[None]), GEN)
                       )[0, P_LEN:].tolist() for p in prompts]
    return params, cfg, prompts, solo


# ----------------------------------------------------------------------
# continuous batching
# ----------------------------------------------------------------------
@pytest.mark.smoke
def test_batcher_matches_solo_generate(lm):
    """5 requests through 2 slots: every request's greedy tokens equal its
    solo decode — including the ones admitted only after earlier requests
    freed a slot."""
    params, cfg, prompts, solo = lm
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + GEN)
    outs, lat = cb.run(prompts, GEN)
    assert outs == solo
    assert len(lat) == len(prompts) and all(l > 0 for l in lat)
    # 5 requests over 2 slots need at least ceil(5/2) * (GEN-1) decode
    # steps; well under the serial 5 * (GEN-1) (the point of batching)
    assert cb.steps < 5 * (GEN - 1)


@pytest.mark.smoke
def test_mid_flight_admission_decodes_solo_tokens(lm):
    """A request admitted while another is mid-decode (slot recycled, the
    resident row several tokens in) still produces its solo token
    sequence, and the resident request is undisturbed."""
    params, cfg, prompts, solo = lm
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + GEN)
    r0 = cb.submit(prompts[0], GEN)
    for _ in range(3):                    # r0 is now mid-flight
        cb.step()
    r1 = cb.submit(prompts[1], GEN)
    while cb.pending():
        cb.step()
    assert cb.result(r1) == solo[1]
    assert cb.result(r0) == solo[0]


def test_slot_recycling_is_masked(lm):
    """Drive enough requests through one slot that every admission lands
    on a cache full of the previous request's state — tokens must stay
    the solo sequences (stale positions hidden by the decode mask)."""
    params, cfg, prompts, solo = lm
    cb = ContinuousBatcher(params, cfg, slots=1, cache_len=P_LEN + GEN)
    outs, _ = cb.run(prompts, GEN)
    assert outs == solo


def test_gen_one_and_validation(lm):
    params, cfg, prompts, solo = lm
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + GEN)
    outs, _ = cb.run([prompts[0]], 1)     # prefill-only request
    assert outs[0] == solo[0][:1]
    with pytest.raises(ValueError, match="gen"):
        cb.submit(prompts[0], 0)
    with pytest.raises(ValueError, match="cache_len"):
        cb.submit(prompts[0], GEN + 100)


def test_batcher_serves_moe_lm():
    """The batcher is family-generic over the stacked (L, B, ...) cache
    layout: the MoE LM decodes its solo tokens through shared slots."""
    task = get_task("moe_lm")
    params = task.init_params(jax.random.PRNGKey(1))
    cfg = task.model_cfg
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab, P_LEN).astype(np.int32)
               for _ in range(3)]
    solo = [np.asarray(generate(params, cfg, jnp.asarray(p[None]), GEN)
                       )[0, P_LEN:].tolist() for p in prompts]
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + GEN)
    outs, _ = cb.run(prompts, GEN)
    assert outs == solo


# ----------------------------------------------------------------------
# the batched decode step: in place for plain attention stacks
# ----------------------------------------------------------------------
def _vmap_step(cfg):
    """The batched decode as a vmap of the scalar-position ``decode_step``
    over the batch axis (axis 1 of the stacked cache): the reference for
    the in-place path.  Returns (last-position logits (B, V), cache)."""

    def one(params, tok, pos, c):
        c1 = jax.tree.map(lambda a: a[:, None], c)
        logits, c1 = T.decode_step(params, tok[None, :], pos, cfg, c1)
        return logits[0, -1], jax.tree.map(lambda a: a[:, 0], c1)

    def step(params, toks, poss, cache):
        return jax.vmap(one, in_axes=(None, 0, 0, 1),
                        out_axes=(0, 1))(params, toks, poss, cache)

    return jax.jit(step)


def _backend_donates() -> bool:
    x = jnp.zeros(4)
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


BF16_TOL = dict(rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("task_name", ["transformer_lm", "moe_lm"])
def test_inplace_step_matches_vmap_reference(task_name):
    """Slots at different positions, a free slot whose position runs past
    the cache, and a slot recycled for a shorter request: the in-place
    step's greedy tokens equal the vmap reference's, and its logits and
    every active slot's cache rows agree within bf16 tolerance."""
    task = get_task(task_name)
    cfg = task.model_cfg
    assert serve._decode_path(cfg) == "inplace"
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          task.init_params(jax.random.PRNGKey(3)))
    S, B = 16, 4
    rng = np.random.RandomState(4)
    ins = serve._slot_insert(cfg)

    def admit(states, s, n):
        logits, one = serve._prefill_jit(cfg)(
            params, jnp.asarray(rng.randint(0, cfg.vocab, (1, n)), jnp.int32))
        one = serve._extend_jit(cfg, S)(one)
        first = jnp.int32(jnp.argmax(logits[0, -1]))
        return [ins(cache, one, tok, pos, jnp.int32(s), first, jnp.int32(n))
                for cache, tok, pos in states]

    shape = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
    key = jax.random.PRNGKey(5)
    stale = jax.random.normal(key, shape).astype(jnp.bfloat16)
    state = ({"k": stale, "v": -stale}, jnp.zeros((B, 1), jnp.int32),
             jnp.array([0, 0, 0, S + 2], jnp.int32))   # slot 3: free, past S
    for s, n in enumerate([3, 9, 6]):
        state, = admit([state], s, n)
    ref, new = state, state
    step, ref_step = serve._batched_step(cfg), _vmap_step(cfg)
    rows = jax.jit(lambda p, t, q, c: T.decode_step_rows(p, t, q, cfg, c)[0])
    for k in range(6):
        if k == 3:                        # slot 1 frees; a shorter request
            ref, new = admit([ref, new], 1, 2)
        cache, tok, pos = new
        ref_logits, ref_cache = ref_step(params, ref[1], ref[2], ref[0])
        np.testing.assert_allclose(                  # slots 0-2 are active
            np.asarray(rows(params, tok, pos, cache)[:3, -1]),
            np.asarray(ref_logits[:3]), **BF16_TOL)
        ref = (ref_cache, ref_logits.argmax(-1).astype(jnp.int32)[:, None],
               ref[2] + 1)
        tok, pos, cache = step(params, tok, pos,
                               jax.tree.map(jnp.copy, cache))
        new = (cache, tok, pos)
        assert np.asarray(tok)[:3].tolist() == np.asarray(ref[1])[:3].tolist()
        assert np.asarray(pos).tolist() == np.asarray(ref[2]).tolist()
        for name in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(cache[name][:, :3], np.float32),
                np.asarray(ref[0][name][:, :3], np.float32), **BF16_TOL)


def test_decode_donates_the_cache_and_keeps_its_name(lm):
    """One batcher step consumes the previous cache's buffers, and the
    decode program is still ``jit_step`` (the name its trace is read by)."""
    if not _backend_donates():
        pytest.skip(f"the {jax.default_backend()} backend does not donate "
                    "buffers, so no decode can update its cache in place")
    params, cfg, prompts, _ = lm
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + GEN)
    cb.submit(prompts[0], GEN)
    cb.step()
    before = cb._cache
    cb.step()
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(cb._cache))
    compiled = serve._batched_step(cfg).lower(
        params, cb._tok, cb._pos, cb._cache).compile()
    assert compiled.as_text().startswith("HloModule jit_step,")


@pytest.mark.parametrize("arch", ["mamba2-370m", "jamba-v0.1-52b"])
def test_ssm_and_hybrid_serve_through_vmap(arch):
    """Families with SSM state keep the vmap of the scalar-position step,
    and the batcher still decodes their solo tokens."""
    cfg = get_smoke_config(arch)
    assert serve._decode_path(cfg) == "vmap"
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, P_LEN).astype(np.int32)
               for _ in range(3)]
    solo = [np.asarray(generate(params, cfg, jnp.asarray(p[None]), 4)
                       )[0, P_LEN:].tolist() for p in prompts]
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + 4)
    outs, _ = cb.run(prompts, 4)
    assert outs == solo


# ----------------------------------------------------------------------
# checkpoint -> serve bridge
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def lm_engine_blob(tmp_path_factory):
    """A short transformer_lm engine run checkpointed to disk; returns
    (blob path, live engine) for weight comparison."""
    data, parts, w0 = make_setup(n_devices=8, iid=True, seed=3,
                                 n_train=160, n_test=64,
                                 task="transformer_lm")
    cfg = SimConfig(method="teasq", task="transformer_lm", n_devices=8,
                    c_fraction=0.25, gamma=0.25, epochs=1, batch_size=8,
                    seed=3)
    eng = make_sim(data, parts, w0, cfg)
    eng.run(time_budget=2.0, eval_every=1)
    path = str(tmp_path_factory.mktemp("serve") / "lm_engine.msgpack")
    save_blob(path, eng.state_dict())
    return path, eng


@pytest.mark.smoke
def test_checkpoint_to_serve_roundtrip(lm_engine_blob):
    """Trained weights out of the blob equal the live server's weights
    leaf-for-leaf, and the restored model serves requests through the
    continuous-batching loop."""
    path, eng = lm_engine_blob
    assert eng.server.t >= 1          # the checkpoint holds TRAINED weights
    params, cfg = load_task_params(path, "transformer_lm")
    live = jax.tree.leaves(eng.server.w)
    got = jax.tree.leaves(params)
    assert len(live) == len(got)
    for a, b in zip(live, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    cb = ContinuousBatcher(params, cfg, slots=2, cache_len=P_LEN + GEN)
    rng = np.random.RandomState(0)
    outs, _ = cb.run([rng.randint(0, cfg.vocab, P_LEN).astype(np.int32)
                      for _ in range(3)], GEN)
    assert all(len(o) == GEN for o in outs)
    assert all(0 <= t < cfg.vocab for o in outs for t in o)


def test_fleet_blob_task_selection(tmp_path):
    """``--from-sim`` on a fleet checkpoint: ``task`` indexes the job list
    and each job's weights round-trip independently."""
    from repro.fl.fleet import FleetConfig, build_fleet
    n = 8
    spec = SimConfig(method="teasq", task="transformer_lm",
                     c_fraction=0.25, gamma=0.25, epochs=1, batch_size=8)
    fleet = build_fleet(FleetConfig(tasks=[spec, spec], n_devices=n,
                                    seed=3), n_train=160, n_test=64)
    fleet.run(time_budget=1.5)
    path = str(tmp_path / "fleet.msgpack")
    save_blob(path, fleet.state_dict())
    task = get_task("transformer_lm")
    like = task.init_params(jax.random.PRNGKey(0))
    for j, rt in enumerate(fleet.runtimes):
        params = load_sim_params(path, like, task=j)
        for a, b in zip(jax.tree.leaves(rt.server.w),
                        jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="out of range"):
        load_sim_params(path, like, task=2)


def test_bridge_validation(lm_engine_blob, tmp_path):
    path, _ = lm_engine_blob
    # non-LM task: no ModelConfig to serve
    with pytest.raises(ValueError, match="not an LM"):
        load_task_params(path, "fmnist_cnn")
    # wrong template structure fails loudly, not by position
    bad_like = {"just": np.zeros(3, np.float32)}
    with pytest.raises(ValueError, match="leaves"):
        load_sim_params(path, bad_like)
    # a non-checkpoint blob is rejected by discriminator
    other = str(tmp_path / "other.msgpack")
    save_blob(other, {"hello": 1})
    task = get_task("transformer_lm")
    like = task.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="engine or fleet"):
        load_sim_params(other, like)


# ----------------------------------------------------------------------
# benchmark harness smoke
# ----------------------------------------------------------------------
@pytest.mark.smoke
def test_serve_bench_smoke():
    from benchmarks.serve_bench import run
    rows = run(batch=2, requests=4, prompt_len=4, gen=4, out_path=None)
    assert {r["mode"] for r in rows} == {"serial", "continuous"}
    for r in rows:
        assert r["tokens_per_s"] > 0
        assert r["p99_ms"] >= r["p50_ms"] > 0
    cont = next(r for r in rows if r["mode"] == "continuous")
    assert cont["batch"] == 2 and "speedup_x" in cont
    assert cont["decode_steps"] > 0


@pytest.mark.smoke
def test_serve_bench_merges_instead_of_clobbering(tmp_path):
    from benchmarks.serve_bench import run
    out = tmp_path / "serve_bench.json"
    run(batch=2, requests=4, prompt_len=4, gen=4, out_path=str(out))
    run(batch=4, requests=4, prompt_len=4, gen=4, out_path=str(out))
    rows = json.loads(out.read_text())
    # batch=2 and batch=4 continuous rows coexist; serial rows dedupe
    assert {(r["mode"], r["batch"]) for r in rows} == \
        {("serial", 1), ("continuous", 2), ("continuous", 4)}
