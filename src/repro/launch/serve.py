"""Serving front door: batched decode plus a continuous-batching loop.

Two entry styles:

* Architecture demo — init random weights for a registry config and run
  the one-shot batched ``generate``::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
        --batch 4 --prompt-len 32 --gen 16

* FL -> serve bridge — load the trained global model out of a simulator
  checkpoint blob (``FLEngine.state_dict()`` or a fleet blob saved with
  ``repro.checkpoint.io.save_blob``) for an LM task and serve requests
  through the continuous-batching loop::

    PYTHONPATH=src python -m repro.launch.serve --from-sim ckpt.msgpack \
        --task transformer_lm --job 0 --batch 4 --requests 8 --gen 16

``ContinuousBatcher`` holds a fixed number of decode slots; each step it
admits queued requests into free slots (prefill one row, splice its KV —
or MLA latent — cache into the batched cache) and advances every active
slot one token —
the maxtext-style admission loop, so short requests free their slot for
the queue instead of waiting for the longest sequence in the batch.
"""
from __future__ import annotations

import argparse
import collections
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.base import get_config, get_smoke_config
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T


# ----------------------------------------------------------------------
# jit caches — keyed on the (frozen, hashable) ModelConfig so repeated
# generate()/ContinuousBatcher calls over the same config reuse the
# compiled step instead of re-tracing per call.  Each jits a named
# function, so that its program has a stable name in a profiler trace
# (``jit_serial_step``, ``jit_prefill``, ``jit_extend_cache``, ``jit_step``).
# ----------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _serial_step(cfg):
    """(params, tok (B,1), pos scalar, cache) -> (logits, cache)."""

    def serial_step(p, t, pos, c):
        return T.decode_step(p, t, pos, cfg, c)

    return jax.jit(serial_step)


@functools.lru_cache(maxsize=None)
def _prefill_jit(cfg):
    """Jitted decoder-only prefill (eager ``T.prefill`` costs hundreds of
    ms per call on the host — far more than the whole decode).  Shared by
    ``generate`` and ``ContinuousBatcher`` so a batcher admission runs the
    exact compiled program a solo generate does (token-parity).  One
    compile per (batch, prompt_len) shape.  Given the device counter
    ``held`` it also returns ``held`` plus the token-expert pairs its held
    experts computed (``_add_pairs``)."""

    def prefill(p, toks, held=None):
        logits, cache, pairs = T.prefill_counted(p, {"tokens": toks}, cfg)
        return (logits, cache) if held is None else (
            logits, cache, _add_pairs(held, pairs.sum()))

    return jax.jit(prefill)


@functools.lru_cache(maxsize=None)
def _extend_jit(cfg, cache_len):
    """Jitted ``extend_cache`` — zero-pads the sequence axis out to the
    resident ``cache_len``, same values as the eager path."""
    del cfg

    def extend_cache(c):
        return T.extend_cache(c, cache_len)

    return jax.jit(extend_cache)


def _decode_path(cfg) -> str:
    """Which batched decode ``_batched_step`` compiles for ``cfg``: in
    place for a plain attention stack (``mla`` for the latent-attention
    stack, ``inplace`` for K/V), else the vmap."""
    if cfg.is_encoder_decoder or cfg.is_hybrid or cfg.is_ssm_only:
        return "vmap"
    return "mla" if cfg.is_mla else "inplace"


def _counts_held_pairs(cfg) -> bool:
    """Whether ``cfg``'s MoE layers are the held-expert layer, whose
    computed token-expert pairs the batcher counts on the device."""
    return cfg.is_moe and cfg.moe_router == "sigmoid"


def _add_pairs(held, n):
    """The 64-bit device counter ``held`` — uint32 words (low, high) —
    plus ``n`` (< 2**32) pairs, carrying into the high word."""
    lo = held[0] + n.astype(jnp.uint32)
    return jnp.stack([lo, held[1] + (lo < held[0]).astype(jnp.uint32)])


@functools.lru_cache(maxsize=None)
def _batched_step(cfg):
    """Per-row decode: tok (B,1) int32, pos (B,) int32 — each row advances
    at its OWN absolute position (slots hold requests of different ages).
    Returns (next greedy token (B,1), pos + 1, cache); the cache argument
    is donated, so the output cache takes over its buffers.  Given the
    device counter ``held`` and the rows that hold a request (``live``,
    (B,) bool) it also returns ``held`` plus the token-expert pairs the
    held experts computed for those rows in the step.

    A plain attention stack runs ``decode_step_rows``, which writes each
    row's new K/V (or MLA latent) into the resident cache in place.  Other
    families wrap the scalar-position ``decode_step`` in a vmap over the
    batch axis (axis 1 of the stacked (L, B, ...) cache leaves), re-adding
    the size-1 batch dim inside."""

    def one(params, tok, pos, c):
        c1 = jax.tree.map(lambda a: a[:, None], c)
        logits, c1 = T.decode_step(params, tok[None, :], pos, cfg, c1)
        return logits[0, -1], jax.tree.map(lambda a: a[:, 0], c1)

    def step(params, toks, poss, cache, held=None, live=None):
        pairs = None
        if _decode_path(cfg) == "vmap":
            logits, cache = jax.vmap(one, in_axes=(None, 0, 0, 1),
                                     out_axes=(0, 1))(params, toks, poss,
                                                      cache)
        else:
            logits, cache, pairs = T.decode_step_rows(params, toks, poss, cfg,
                                                      cache)
            logits = logits[:, -1]
        # pos advances for every slot on-device; a free slot harmlessly
        # decodes garbage past its request until it is re-admitted
        out = (logits.argmax(-1).astype(jnp.int32)[:, None], poss + 1, cache)
        if held is None:
            return out
        return out + (_add_pairs(held, jnp.where(live, pairs, 0).sum()),)

    return jax.jit(step, donate_argnums=3)


@functools.lru_cache(maxsize=None)
def _slot_insert(cfg):
    """Splice a freshly prefilled (extended) one-row cache into slot ``s``
    of the batched cache (axis 1), casting to the resident dtype, and set
    the slot's next-token / position registers — one dispatch per
    admission."""
    del cfg  # keyed per config only so unrelated models don't share

    def ins(cache, one, tok, pos, s, first, start):
        cache = jax.tree.map(
            lambda f, o: jax.lax.dynamic_update_slice_in_dim(
                f, o.astype(f.dtype), s, axis=1), cache, one)
        return cache, tok.at[s, 0].set(first), pos.at[s].set(start)

    return jax.jit(ins)


def generate(params, cfg, prompts: jnp.ndarray, gen: int, frames=None,
             temperature: float = 0.0, seed: int = 0):
    """prompts: (B, S) -> (B, S+gen) greedy/temperature sampling."""
    B, S = prompts.shape
    if cfg.is_encoder_decoder:
        logits, cache = T.encdec_prefill(
            params, {"tokens": prompts, "frames": frames}, cfg, cache_len=S)
    else:
        logits, cache = _prefill_jit(cfg)(params, prompts)
    cache = T.extend_cache(cache, S + gen)

    step = _serial_step(cfg)
    key = jax.random.PRNGKey(seed)
    out = [prompts]

    def sample(lg, key):
        if temperature <= 0:
            return lg.argmax(-1).astype(jnp.int32)
        return jax.random.categorical(key, lg / temperature, axis=-1).astype(jnp.int32)

    tok = sample(logits[:, -1], key)[:, None]
    for i in range(gen):
        out.append(tok)
        key, sub = jax.random.split(key)
        logits, cache = step(params, tok, jnp.int32(S + i), cache)
        tok = sample(logits[:, -1], sub)[:, None]
    return jnp.concatenate(out, axis=1)


# ----------------------------------------------------------------------
# Continuous batching
# ----------------------------------------------------------------------

class ContinuousBatcher:
    """Fixed-slot greedy decode loop with per-step request admission.

    ``submit`` queues a request; each ``step`` first admits queued
    requests into free slots (one-row prefill -> ``extend_cache`` ->
    dynamic-slice splice into the batched cache) and then advances every
    active slot one greedy token at its own position.  A slot frees the
    moment its request reaches ``gen`` tokens, so the queue drains
    continuously instead of in lock-step batches.  Greedy only: the
    tokens of a request admitted mid-flight match a solo ``generate`` of
    the same prompt (tests/test_serve.py pins this)."""

    def __init__(self, params, cfg, slots: int = 4, cache_len: int = 64):
        self.params = params
        self.cfg = cfg
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self._queue: collections.deque = collections.deque()
        self._next_rid = 0
        self._rid = [-1] * self.slots            # request id per slot
        self._remaining = np.zeros(self.slots, np.int64)
        # decode registers live on-device so the loop never syncs per step
        self._tok = jnp.zeros((self.slots, 1), jnp.int32)
        self._pos = jnp.zeros(self.slots, jnp.int32)
        self._cache = None                       # built on first admission
        self._trace: List[Any] = []              # per-step (B,1) token arrays
        self._first: Dict[int, int] = {}         # rid -> prefill argmax token
        self._slots_of: Dict[int, List[Tuple[int, int]]] = {}
        self._results: Dict[int, List[int]] = {}  # materialized on demand
        self._submitted: Dict[int, float] = {}   # rid -> submit time (s)
        self.steps = 0                           # decode steps taken
        # token-expert pairs the held experts computed for requests, on
        # the device (``_add_pairs``)
        self._held = (jnp.zeros(2, jnp.uint32) if _counts_held_pairs(cfg)
                      else None)

    # -- request intake --------------------------------------------------
    def submit(self, prompt: np.ndarray, gen: int) -> int:
        """Queue a request; returns its id.  ``prompt`` is a 1-D int32
        token array; ``gen`` >= 1 tokens will be generated."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if gen < 1:
            raise ValueError("gen must be >= 1")
        if prompt.size + gen > self.cache_len:
            raise ValueError(f"prompt ({prompt.size}) + gen ({gen}) exceeds "
                             f"cache_len ({self.cache_len})")
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, prompt, int(gen)))
        self._submitted[rid] = time.perf_counter()
        return rid

    def result(self, rid: int) -> List[int]:
        """Generated tokens so far for request ``rid`` (length ``gen``
        once the request has completed).  Token values are pulled off the
        device lazily here; the decode loop itself never syncs."""
        if rid not in self._results:
            toks = [self._first[rid]]
            toks += [int(np.asarray(self._trace[k])[s, 0])
                     for k, s in self._slots_of[rid]]
            if not any(r == rid for r in self._rid):   # completed: freeze
                self._results[rid] = toks
            return toks
        return list(self._results[rid])

    def pending(self) -> bool:
        return bool(self._queue) or any(r >= 0 for r in self._rid)

    def held_pairs(self) -> Optional[int]:
        """Token-expert pairs the held experts have computed for requests
        in this batcher's prefills and decode steps (the rows of free
        slots left out), or None for a model without a held-expert layer.
        Waits for the device."""
        if self._held is None:
            return None
        lo, hi = np.asarray(self._held).tolist()
        return hi << 32 | lo

    # -- the loop --------------------------------------------------------
    def _admit(self) -> List[int]:
        """Fill free slots from the queue.  Returns rids that completed
        at admission (gen == 1: the prefill token is the whole answer)."""
        done = []
        for s in range(self.slots):
            if self._rid[s] >= 0 or not self._queue:
                continue
            rid, prompt, gen = self._queue.popleft()
            queued_ms = (time.perf_counter() - self._submitted.pop(rid)) * 1e3
            with spans.span("serve.admit", rid=rid, prompt_len=prompt.size,
                            queued_ms=queued_ms):
                if self._admit_one(s, rid, prompt, gen):
                    done.append(rid)
        return done

    def _admit_one(self, s: int, rid: int, prompt: np.ndarray,
                   gen: int) -> bool:
        """Prefill request ``rid`` into slot ``s``; True if it completed
        at admission (its prefill token is the whole answer)."""
        with spans.span("serve.prefill", rid=rid, program="jit_prefill"):
            toks = jnp.asarray(prompt[None, :])
            if self._held is None:
                logits, one = _prefill_jit(self.cfg)(self.params, toks)
            else:
                logits, one, self._held = _prefill_jit(self.cfg)(
                    self.params, toks, self._held)
        with spans.span("serve.prefill", rid=rid, program="jit_extend_cache"):
            one = _extend_jit(self.cfg, self.cache_len)(one)
        # the host waits here for the prefill: the request's first token
        with spans.span("serve.first_token", rid=rid, program="jit__argmax"):
            first = int(jnp.argmax(logits[0, -1]))
        self._first[rid] = first
        self._slots_of[rid] = []
        if gen == 1:
            return True
        with spans.span("serve.splice", rid=rid):
            if self._cache is None:
                self._cache = jax.tree.map(
                    lambda a: jnp.zeros(
                        a.shape[:1] + (self.slots,) + a.shape[2:], a.dtype),
                    one)
            self._cache, self._tok, self._pos = _slot_insert(self.cfg)(
                self._cache, one, self._tok, self._pos, jnp.int32(s),
                jnp.int32(first), jnp.int32(prompt.size))
        self._rid[s] = rid
        self._remaining[s] = gen - 1
        return False

    def step(self) -> List[int]:
        """Admit from the queue, then advance every active slot one
        token.  Returns the rids that completed this step."""
        on = spans.enabled()
        with spans.span("serve.step",
                        active=sum(r >= 0 for r in self._rid) if on else 0,
                        queued=len(self._queue)):
            return self._step()

    def _step(self) -> List[int]:
        done = self._admit()
        if not any(r >= 0 for r in self._rid):
            return done
        with spans.span("serve.decode", step=self.steps,
                        path=_decode_path(self.cfg)):
            step = _batched_step(self.cfg)
            if self._held is None:
                self._tok, self._pos, self._cache = step(
                    self.params, self._tok, self._pos, self._cache)
            else:
                self._tok, self._pos, self._cache, self._held = step(
                    self.params, self._tok, self._pos, self._cache,
                    self._held, np.asarray(self._rid) >= 0)
        self._trace.append(self._tok)
        k = self.steps
        self.steps += 1
        for s in range(self.slots):
            if self._rid[s] < 0:
                continue  # free slot decodes garbage harmlessly
            self._slots_of[self._rid[s]].append((k, s))
            self._remaining[s] -= 1
            if self._remaining[s] == 0:
                done.append(self._rid[s])
                self._rid[s] = -1
        return done

    def run(self, prompts, gen: int) -> Tuple[List[List[int]], List[float]]:
        """Drive a workload to completion: submit every prompt up front,
        step until the queue drains.  Returns (per-request token lists,
        per-request wall-clock completion latencies in seconds, both in
        submit order).  Latency stamps block on the completing step's
        device values, so they measure computed tokens, not dispatches."""
        rids = [self.submit(p, gen) for p in prompts]
        t0 = time.time()
        lat: Dict[int, float] = {}
        while self.pending():
            finished = self.step()
            if finished:
                if self._trace:
                    jax.block_until_ready(self._trace[-1])
                now = time.time() - t0
                for rid in finished:
                    lat[rid] = now
        return [self.result(r) for r in rids], [lat[r] for r in rids]


# ----------------------------------------------------------------------
# FL -> serve bridge
# ----------------------------------------------------------------------

def load_task_params(path: str, task_name: str, job: int = 0):
    """Rebuild a trained LM's weights from a simulator checkpoint blob.

    Resolves ``task_name`` in the FL task registry for the treedef
    template and the transformer ``ModelConfig``, then pulls the global
    weights out of the engine/fleet blob at ``path`` (``job`` picks the
    task slot inside a fleet blob).  Returns ``(params, cfg)``."""
    from repro.checkpoint.io import load_sim_params
    from repro.fl.tasks import get_task
    task = get_task(task_name)
    if task.model_cfg is None:
        raise ValueError(f"task {task_name!r} is not an LM family — "
                         "it has no transformer ModelConfig to serve")
    like = task.init_params(jax.random.PRNGKey(0))
    params = load_sim_params(path, like, task=job)
    return params, task.model_cfg


def serve_from_sim(path: str, task_name: str, job: int, batch: int,
                   requests: int, prompt_len: int, gen: int,
                   seed: int = 0) -> None:
    params, cfg = load_task_params(path, task_name, job)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab, prompt_len).astype(np.int32)
               for _ in range(requests)]
    cb = ContinuousBatcher(params, cfg, slots=batch,
                           cache_len=prompt_len + gen)
    t0 = time.time()
    outs, lat = cb.run(prompts, gen)
    dt = time.time() - t0
    toks = sum(len(o) for o in outs)
    print(f"[serve] {cfg.name} from {path}: {requests} requests x gen={gen} "
          f"over {batch} slots in {dt:.2f}s ({toks / dt:.1f} tok/s, "
          f"p50 latency {np.percentile(lat, 50) * 1e3:.0f} ms)")
    print("[serve] first request tokens:", outs[0])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--from-sim", default=None, metavar="CKPT",
                    help="serve trained weights from an engine/fleet "
                         "checkpoint blob instead of random --arch init")
    ap.add_argument("--task", default="transformer_lm",
                    help="FL task registry name behind --from-sim")
    ap.add_argument("--job", type=int, default=0,
                    help="task slot inside a fleet checkpoint blob")
    ap.add_argument("--requests", type=int, default=8,
                    help="workload size for the continuous-batching loop")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.from_sim is not None:
        serve_from_sim(args.from_sim, args.task, args.job, args.batch,
                       args.requests, args.prompt_len, args.gen, args.seed)
        return

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.RandomState(args.seed)
    prompts = jnp.asarray(
        rng.randint(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    frames = None
    if cfg.is_encoder_decoder:
        frames = jnp.asarray(
            rng.randn(args.batch, cfg.enc_seq, cfg.d_model), jnp.float32)

    t0 = time.time()
    seqs = generate(params, cfg, prompts, args.gen, frames,
                    args.temperature, args.seed)
    dt = time.time() - t0
    print(f"[serve] {cfg.name}: batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen} in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("[serve] first sequence tail:", np.asarray(seqs[0, -8:]).tolist())


if __name__ == "__main__":
    main()
