"""Architecture assembly: dense / MoE / SSM / hybrid decoders, enc-dec, VLM,
and the latent-attention (MLA) stack of DeepSeek-V3-style models.

All stacks use ``lax.scan`` over stacked layer parameters so the HLO stays
small at 88 layers.  The hybrid (Jamba) stack scans over *groups* of
``attn_every`` layers (7 mamba + 1 attention per group, FFN alternating
dense/MoE) since the layer pattern repeats at that period.

Public entry points:
  init_model(key, cfg, dtype)             -> params
  forward(params, batch, cfg)             -> (logits, aux)   # train / prefill
  init_decode_state(cfg, batch, cache_len, dtype, rolling)   -> cache pytree
  decode_step(params, tokens, pos, cfg, cache)  -> (logits, new cache)
  decode_step_rows(params, tokens, pos, cfg, cache)
                                          -> (logits, new cache, held pairs)
                                          # per-row positions, in place
  prefill_counted(params, batch, cfg)     -> (logits, cache, held pairs)
                                          # held pairs: (B,) int32 per row
  lm_loss(params, batch, cfg)             -> (loss, aux)
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (dense_init, embed_init, embed_lookup, lm_head,
                                 mlp, mlp_init, rmsnorm, rmsnorm_init)
from repro.sharding.rules import shard


# ======================================================================
# init
# ======================================================================
def _init_uniform_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    p = {"norm1": rmsnorm_init(cfg.d_model, dtype),
         "norm2": rmsnorm_init(cfg.d_model, dtype)}
    if cfg.is_ssm_only:
        p["ssm"] = ssm_mod.ssm_init(k1, cfg, dtype)
        del p["norm2"]
        return p
    p["attn"] = attn.attn_init(k1, cfg, dtype)
    if cfg.is_moe:
        p["moe"] = moe_mod.moe_init(k2, cfg, dtype)
    elif cfg.d_ff > 0:
        p["ffn"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype,
                            gated=cfg.gated_mlp)
    else:
        del p["norm2"]
    return p


def _init_hybrid_group(key, cfg, dtype):
    """One Jamba group: (attn_every-1) mamba + 1 attn; FFN dense/MoE alternating."""
    ae = cfg.attn_every
    n_moe = ae // cfg.moe_every
    n_dense = ae - n_moe
    keys = jax.random.split(key, 4)
    ssm_keys = jax.random.split(keys[0], ae - 1)
    dense_keys = jax.random.split(keys[2], max(n_dense, 1))
    moe_keys = jax.random.split(keys[3], max(n_moe, 1))
    g = {
        "ssm": jax.vmap(lambda k: ssm_mod.ssm_init(k, cfg, dtype))(ssm_keys),
        "attn": attn.attn_init(keys[1], cfg, dtype),
        "norm1": jax.vmap(lambda _: rmsnorm_init(cfg.d_model, dtype))(
            jnp.arange(ae)),
        "norm2": jax.vmap(lambda _: rmsnorm_init(cfg.d_model, dtype))(
            jnp.arange(ae)),
    }
    if n_dense:
        g["ffn"] = jax.vmap(
            lambda k: mlp_init(k, cfg.d_model, cfg.d_ff, dtype,
                               gated=cfg.gated_mlp))(dense_keys)
    if n_moe:
        g["moe"] = jax.vmap(
            lambda k: moe_mod.moe_init(k, cfg, dtype))(moe_keys)
    return g


def _init_encdec_layer(key, cfg, dtype, decoder: bool):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"norm1": rmsnorm_init(cfg.d_model, dtype),
         "attn": attn.attn_init(k1, cfg, dtype),
         "norm_ffn": rmsnorm_init(cfg.d_model, dtype),
         "ffn": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype,
                         gated=cfg.gated_mlp)}
    if decoder:
        p["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["xattn"] = attn.attn_init(k2, cfg, dtype, cross=True)
    return p


def _init_mla_layer(key, cfg, dtype, moe: bool):
    """One layer of the MLA stack: MLA, then a dense SwiGLU FFN or the
    held-expert MoE (``moe_router="sigmoid"``)."""
    if moe and cfg.moe_router != "sigmoid":
        raise ValueError("the MLA stack's MoE layers route as DeepSeek-V3 "
                         "does: moe_router must be 'sigmoid'")
    k1, k2 = jax.random.split(key)
    p = {"norm1": rmsnorm_init(cfg.d_model, dtype),
         "attn": attn.mla_init(k1, cfg, dtype),
         "norm2": rmsnorm_init(cfg.d_model, dtype)}
    if moe:
        p["moe"] = moe_mod.held_moe_init(k2, cfg, dtype)
    else:
        p["ffn"] = mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_model(key, cfg, dtype=jnp.float32) -> Dict[str, Any]:
    ke, kl, kh, kp = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": embed_init(ke, cfg.vocab, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kh, cfg.d_model, cfg.vocab, dtype)

    if cfg.is_encoder_decoder:
        enc_keys = jax.random.split(kl, cfg.n_enc_layers + 1)
        dec_keys = jax.random.split(enc_keys[-1], cfg.n_layers)
        params["enc_layers"] = jax.vmap(
            lambda k: _init_encdec_layer(k, cfg, dtype, False))(enc_keys[:-1])
        params["layers"] = jax.vmap(
            lambda k: _init_encdec_layer(k, cfg, dtype, True))(dec_keys)
        params["enc_norm"] = rmsnorm_init(cfg.d_model, dtype)
    elif cfg.is_hybrid:
        n_groups = cfg.n_layers // cfg.attn_every
        gkeys = jax.random.split(kl, n_groups)
        params["layers"] = jax.vmap(
            lambda k: _init_hybrid_group(k, cfg, dtype))(gkeys)
    elif cfg.is_mla:
        k = cfg.first_k_dense
        lkeys = jax.random.split(kl, cfg.n_layers)
        if k:
            params["dense_layers"] = jax.vmap(
                lambda kk: _init_mla_layer(kk, cfg, dtype, moe=False))(lkeys[:k])
        params["layers"] = jax.vmap(
            lambda kk: _init_mla_layer(kk, cfg, dtype, moe=cfg.is_moe))(lkeys[k:])
    else:
        lkeys = jax.random.split(kl, cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: _init_uniform_layer(k, cfg, dtype))(lkeys)

    if cfg.n_patches:  # VLM: projector from (stubbed) vision embeddings
        params["patch_proj"] = dense_init(kp, cfg.d_model, cfg.d_model, dtype)
    return params


# ======================================================================
# forward (train / prefill)
# ======================================================================
def _uniform_block(x, lp, cfg, positions, window, collect_cache=False):
    aux = jnp.float32(0.0)
    kv = None
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    if cfg.is_ssm_only:
        if collect_cache:
            o, kv = ssm_mod.ssm_forward(lp["ssm"], h, cfg, return_state=True)
        else:
            o = ssm_mod.ssm_forward(lp["ssm"], h, cfg)
        return x + o, aux, kv
    if collect_cache:
        o, kv = attn.attn_forward(lp["attn"], h, positions, cfg, causal=True,
                                  window=window, return_kv=True)
    else:
        o = attn.attn_forward(lp["attn"], h, positions, cfg, causal=True,
                              window=window)
    x = x + o
    if cfg.is_moe:
        y, aux = moe_mod.moe_apply(lp["moe"], rmsnorm(lp["norm2"], x, cfg.norm_eps), cfg)
        x = x + y
    elif cfg.d_ff > 0:
        x = x + mlp(lp["ffn"], rmsnorm(lp["norm2"], x, cfg.norm_eps))
    return x, aux, kv


def _hybrid_group_block(x, gp, cfg, positions, window, collect_cache=False):
    ae = cfg.attn_every
    aux = jnp.float32(0.0)
    take = lambda t, i: jax.tree.map(lambda a: a[i], t)
    attn_kv, ssm_states = None, []
    for pos in range(ae):
        n1, n2 = take(gp["norm1"], pos), take(gp["norm2"], pos)
        h = rmsnorm(n1, x, cfg.norm_eps)
        if pos == ae - 1:
            if collect_cache:
                o, attn_kv = attn.attn_forward(gp["attn"], h, positions, cfg,
                                               causal=True, window=window,
                                               return_kv=True)
            else:
                o = attn.attn_forward(gp["attn"], h, positions, cfg,
                                      causal=True, window=window)
            x = x + o
        else:
            if collect_cache:
                o, st = ssm_mod.ssm_forward(take(gp["ssm"], pos), h, cfg,
                                            return_state=True)
                ssm_states.append(st)
            else:
                o = ssm_mod.ssm_forward(take(gp["ssm"], pos), h, cfg)
            x = x + o
        hf = rmsnorm(n2, x, cfg.norm_eps)
        if pos % cfg.moe_every == cfg.moe_every - 1:
            y, lb = moe_mod.moe_apply(take(gp["moe"], pos // cfg.moe_every), hf, cfg)
            x, aux = x + y, aux + lb
        else:
            x = x + mlp(take(gp["ffn"], pos // cfg.moe_every), hf)
    kv = None
    if collect_cache:
        kv = {"attn": attn_kv,
              "ssm": jax.tree.map(lambda *a: jnp.stack(a), *ssm_states)}
    return x, aux, kv


def _mla_ffn(x, lp, cfg):
    """The FFN half of an MLA-stack layer on the residual stream x: the
    held-expert MoE, or layer 0's dense SwiGLU.  -> (x + FFN, the pairs its
    held experts computed per row (B,))."""
    h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
    if "moe" in lp:
        y, pairs = moe_mod.held_moe_apply(lp["moe"], h, cfg)
        return x + y, pairs.sum(-1)
    with jax.named_scope("dense_ffn"):
        return x + mlp(lp["ffn"], h), jnp.zeros(x.shape[:1], jnp.int32)


def _mla_block(x, lp, cfg, positions, collect_cache=False):
    kv = None
    h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
    o = attn.mla_forward(lp["attn"], h, positions, cfg,
                         return_cache=collect_cache)
    if collect_cache:
        o, kv = o
    x, pairs = _mla_ffn(x + o, lp, cfg)
    return x, pairs, kv


# the MLA stack's parameter groups, each under its own layer loop
_MLA_GROUPS = ("dense_layers", "layers")


def _run_mla_stack(params, x, cfg, positions, collect_cache=False,
                   remat=False):
    """The leading dense layers under one scan, then the MoE layers under
    another.  Returns (x, token-expert pairs the held experts computed per
    row (B,), the stacked cache rows of all layers or None)."""
    pairs, caches = jnp.zeros(x.shape[:1], jnp.int32), []
    for name in _MLA_GROUPS:
        if name not in params:
            continue
        block = partial(_mla_block, cfg=cfg, positions=positions,
                        collect_cache=collect_cache)
        if remat:
            block = jax.checkpoint(block)

        def body(carry, lp, block=block):
            x, n = carry
            x, m, kv = block(x, lp)
            return (x, n + m), kv

        (x, pairs), kv = jax.lax.scan(body, (x, pairs), params[name])
        caches.append(kv)
    cache = None
    if collect_cache:
        cache = jax.tree.map(lambda *a: jnp.concatenate(a), *caches)
    return x, pairs, cache


def _run_stack(params, x, cfg, positions, window=0, collect_cache=False,
               remat=False):
    if cfg.is_mla:
        x, _, caches = _run_mla_stack(params, x, cfg, positions,
                                      collect_cache, remat)
        return x, jnp.float32(0.0), caches
    if cfg.is_hybrid:
        block = partial(_hybrid_group_block, cfg=cfg, positions=positions,
                        window=window, collect_cache=collect_cache)
    else:
        block = partial(_uniform_block, cfg=cfg, positions=positions,
                        window=window, collect_cache=collect_cache)
    if remat:
        # per-layer activation checkpointing: backward recomputes the block
        # (essential for flash attention, whose score blocks must not be saved)
        block = jax.checkpoint(block)

    def body(carry, lp):
        x, aux = carry
        x, lb, kv = block(x, lp)
        return (x, aux + lb), kv

    (x, aux), caches = jax.lax.scan(body, (x, jnp.float32(0.0)),
                                    params["layers"])
    return x, aux, caches


def _encoder(params, frames, cfg):
    """frames: (B, S_enc, D) stubbed audio embeddings."""
    x = frames
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1]), frames.shape[:2])

    def body(x, lp):
        h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
        x = x + attn.attn_forward(lp["attn"], h, pos, cfg, causal=False)
        x = x + mlp(lp["ffn"], rmsnorm(lp["norm_ffn"], x, cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _decoder_encdec(params, tokens, enc_out, cfg):
    x = embed_lookup(params["embed"], tokens)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)

    def body(x, lp):
        x = x + attn.attn_forward(lp["attn"], rmsnorm(lp["norm1"], x, cfg.norm_eps),
                                  pos, cfg, causal=True)
        x = x + attn.attn_forward(lp["xattn"], rmsnorm(lp["norm_x"], x, cfg.norm_eps),
                                  pos, cfg, enc_out=enc_out)
        x = x + mlp(lp["ffn"], rmsnorm(lp["norm_ffn"], x, cfg.norm_eps))
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def forward(params, batch: Dict[str, jax.Array], cfg,
            window: int = 0, remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    """batch: {tokens, [patches|frames]} -> (logits over token positions, aux)."""
    if cfg.is_encoder_decoder:
        enc_out = _encoder(params, batch["frames"], cfg)
        x = _decoder_encdec(params, batch["tokens"], enc_out, cfg)
        aux = jnp.float32(0.0)
    else:
        tokens = batch["tokens"]
        x = embed_lookup(params["embed"], tokens)
        x = shard(x, "batch", "seq", "d_model")
        n_text = tokens.shape[1]
        if cfg.n_patches:
            pe = batch["patches"] @ params["patch_proj"]
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        x, aux, _ = _run_stack(params, x, cfg, positions, window, remat=remat)
        if cfg.n_patches:
            x = x[:, -n_text:, :]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(x, params["embed"] if cfg.tie_embeddings else None,
                     params.get("lm_head"))
    return logits, aux


def prefill(params, batch: Dict[str, jax.Array], cfg, window: int = 0
            ) -> Tuple[jax.Array, Any]:
    """Serve-side prefill: process the full prompt, return (last-position
    logits, layer-stacked KV/SSM cache) ready for ``decode_step``."""
    logits, cache, _ = prefill_counted(params, batch, cfg, window)
    return logits, cache


def prefill_counted(params, batch: Dict[str, jax.Array], cfg, window: int = 0
                    ) -> Tuple[jax.Array, Any, jax.Array]:
    """``prefill``, and the token-expert pairs its held experts computed
    per row ((B,) int32; zeros for a model without a held-expert layer)."""
    if cfg.is_encoder_decoder:
        raise NotImplementedError("use encdec_prefill for encoder-decoder")
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", "seq", "d_model")
    if cfg.n_patches:
        pe = batch["patches"] @ params["patch_proj"]
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    if cfg.is_mla:
        x, pairs, cache = _run_mla_stack(params, x, cfg, positions,
                                         collect_cache=True)
    else:
        x, _, cache = _run_stack(params, x, cfg, positions, window,
                                 collect_cache=True)
        pairs = jnp.zeros(x.shape[:1], jnp.int32)
    x = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    logits = lm_head(x, params["embed"] if cfg.tie_embeddings else None,
                     params.get("lm_head"))
    return logits, cache, pairs


def encdec_prefill(params, batch: Dict[str, jax.Array], cfg,
                   cache_len: int) -> Tuple[jax.Array, Any]:
    """Whisper-style prefill: run the encoder, fill cross KV caches, then
    teacher-force the prompt tokens through the decoder collecting self KV."""
    enc_out = _encoder(params, batch["frames"], cfg)
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, lp):
        o, kv = attn.attn_forward(lp["attn"], rmsnorm(lp["norm1"], x, cfg.norm_eps),
                                  pos, cfg, causal=True, return_kv=True)
        x = x + o
        o, xkv = attn.attn_forward(lp["xattn"], rmsnorm(lp["norm_x"], x, cfg.norm_eps),
                                   pos, cfg, enc_out=enc_out, return_kv=True)
        x = x + o
        x = x + mlp(lp["ffn"], rmsnorm(lp["norm_ffn"], x, cfg.norm_eps))
        return x, {"k": kv["k"], "v": kv["v"], "xk": xkv["k"], "xv": xkv["v"]}

    x, cache = jax.lax.scan(body, x, params["layers"])
    x = rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    logits = lm_head(x, params["embed"] if cfg.tie_embeddings else None,
                     params.get("lm_head"))
    return logits, cache


def lm_loss(params, batch, cfg, window: int = 0,
            lb_weight: float = 0.01, remat: bool = False,
            loss_chunk: int = 0) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy.

    ``loss_chunk > 0`` computes the loss in sequence chunks WITHOUT ever
    materializing the full (B, S, vocab) f32 logits — each chunk's lm_head +
    softmax is rematerialized in the backward pass (memory-roofline lever for
    large-vocab archs; see EXPERIMENTS.md §Perf).
    """
    if loss_chunk <= 0:
        logits, aux = forward(params, batch, cfg, window, remat=remat)
        targets = batch["tokens"][:, 1:]
        logits = logits[:, :-1, :]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        loss = nll.mean()
        return loss + lb_weight * aux, {"nll": loss, "lb": aux}

    # trunk without the head
    tokens = batch["tokens"]
    if cfg.is_encoder_decoder:
        enc_out = _encoder(params, batch["frames"], cfg)
        x = _decoder_encdec(params, tokens, enc_out, cfg)
        aux = jnp.float32(0.0)
    else:
        x = embed_lookup(params["embed"], tokens)
        x = shard(x, "batch", "seq", "d_model")
        if cfg.n_patches:
            pe = batch["patches"] @ params["patch_proj"]
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        x, aux, _ = _run_stack(params, x, cfg, positions, window, remat=remat)
        if cfg.n_patches:
            x = x[:, -tokens.shape[1]:, :]
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)

    table = params["embed"] if cfg.tie_embeddings else None
    head = params.get("lm_head")
    B, S = tokens.shape
    Sm1 = S - 1
    C = min(loss_chunk, Sm1)
    n_chunks = -(-Sm1 // C)
    pad = n_chunks * C - Sm1

    xs = jnp.pad(x[:, :-1, :], ((0, 0), (0, pad), (0, 0)))
    tg = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    valid = jnp.pad(jnp.ones((B, Sm1), jnp.float32), ((0, 0), (0, pad)))
    xs = xs.reshape(B, n_chunks, C, -1)
    tg = tg.reshape(B, n_chunks, C)
    valid = valid.reshape(B, n_chunks, C)

    @jax.checkpoint
    def chunk_nll(xc, tc, vc):
        logits = lm_head(xc, table, head)              # (B, C, V) f32
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * vc)

    def body(acc, inp):
        xc, tc, vc = inp
        return acc + chunk_nll(xc, tc, vc), None

    total, _ = jax.lax.scan(
        body, jnp.float32(0.0),
        (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(tg, 1, 0),
         jnp.moveaxis(valid, 1, 0)))
    loss = total / (B * Sm1)
    return loss + lb_weight * aux, {"nll": loss, "lb": aux}


# stacked cache leaves whose axis 2 is the sequence: name -> ndim
_SEQ_LEAVES = {"k": 5, "v": 5, "c_kv": 4, "k_pe": 4}


def extend_cache(cache, target_len: int):
    """Pad the sequence axis of attention caches (stacked layouts
    (L, B, S, Hkv, hd) for K/V, (L, B, S, width) for MLA's latent and rope
    key) out to ``target_len`` slots for continued decode."""

    def pad(path, a):
        name = None
        for p in path:
            if hasattr(p, "key"):
                name = str(p.key)
        if _SEQ_LEAVES.get(name) == a.ndim and a.shape[2] < target_len:
            padw = [(0, 0)] * a.ndim
            padw[2] = (0, target_len - a.shape[2])
            return jnp.pad(a, padw)
        return a

    return jax.tree_util.tree_map_with_path(pad, cache)


# ======================================================================
# decode (one token with caches)
# ======================================================================
def init_decode_state(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16,
                      rolling: bool = False, quantized: bool = False):
    """Stacked (over layers / groups) cache pytree."""
    if cfg.is_mla:
        if rolling or quantized:
            raise NotImplementedError("MLA keeps a plain latent cache")
        L = cfg.n_layers
        return {"c_kv": jnp.zeros((L, batch, cache_len, cfg.kv_lora_rank), dtype),
                "k_pe": jnp.zeros((L, batch, cache_len, cfg.qk_rope_dim), dtype)}
    if cfg.is_encoder_decoder:
        one = attn.init_cache(cfg, batch, cache_len, dtype,
                              cross_len=cfg.enc_seq, quantized=quantized)
        return _stack_tree(one, cfg.n_layers)
    if cfg.is_hybrid:
        g = {
            "attn": attn.init_cache(cfg, batch, cache_len, dtype,
                                    quantized=quantized),
            "ssm": _stack_tree(ssm_mod.init_ssm_cache(cfg, batch, dtype),
                               cfg.attn_every - 1),
        }
        return _stack_tree(g, cfg.n_layers // cfg.attn_every)
    if cfg.is_ssm_only:
        return _stack_tree(ssm_mod.init_ssm_cache(cfg, batch, dtype), cfg.n_layers)
    return _stack_tree(attn.init_cache(cfg, batch, cache_len, dtype,
                                       quantized=quantized), cfg.n_layers)


def _stack_tree(tree, n: int):
    return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)


def decode_step(params, tokens, pos, cfg, cache, *, rolling: bool = False,
                seq_shard_kv: bool = False) -> Tuple[jax.Array, Any]:
    """tokens: (B, 1) int32; pos: scalar int32 absolute position."""
    if cfg.is_mla:
        if rolling or seq_shard_kv:
            raise NotImplementedError("MLA decodes a plain latent cache")
        rows = jnp.full((tokens.shape[0],), pos, jnp.int32)
        logits, cache, _ = decode_step_rows(params, tokens, rows, cfg, cache)
        return logits, cache
    x = embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", "seq", "d_model")
    aux = jnp.float32(0.0)

    if cfg.is_encoder_decoder:
        def body(x, xs):
            lp, lc = xs
            h, lc2 = attn.attn_decode(lp["attn"], rmsnorm(lp["norm1"], x, cfg.norm_eps),
                                      pos, cfg, lc, rolling=rolling)
            x = x + h
            h, _ = attn.attn_decode(lp["xattn"], rmsnorm(lp["norm_x"], x, cfg.norm_eps),
                                    pos, cfg, lc, cross=True)
            x = x + h
            x = x + mlp(lp["ffn"], rmsnorm(lp["norm_ffn"], x, cfg.norm_eps))
            return x, lc2

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    elif cfg.is_hybrid:
        def body(x, xs):
            gp, gc = xs
            take = lambda t, i: jax.tree.map(lambda a: a[i], t)
            new_ssm = []
            ae = cfg.attn_every
            for p_ in range(ae):
                h = rmsnorm(take(gp["norm1"], p_), x, cfg.norm_eps)
                if p_ == ae - 1:
                    o, ac = attn.attn_decode(gp["attn"], h, pos, cfg, gc["attn"],
                                             rolling=rolling)
                    x = x + o
                else:
                    o, sc = ssm_mod.ssm_decode(take(gp["ssm"], p_), h, cfg,
                                               take(gc["ssm"], p_))
                    new_ssm.append(sc)
                    x = x + o
                hf = rmsnorm(take(gp["norm2"], p_), x, cfg.norm_eps)
                if p_ % cfg.moe_every == cfg.moe_every - 1:
                    y, _ = moe_mod.moe_apply(take(gp["moe"], p_ // cfg.moe_every), hf, cfg)
                    x = x + y
                else:
                    x = x + mlp(take(gp["ffn"], p_ // cfg.moe_every), hf)
            stacked_ssm = jax.tree.map(lambda *a: jnp.stack(a), *new_ssm)
            return x, {"attn": ac, "ssm": stacked_ssm}

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    elif cfg.is_ssm_only:
        def body(x, xs):
            lp, lc = xs
            h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
            o, lc2 = ssm_mod.ssm_decode(lp["ssm"], h, cfg, lc)
            return x + o, lc2

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    else:
        def body(carry, xs):
            x, aux = carry
            lp, lc = xs
            h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
            if seq_shard_kv:
                o, lc2 = attn.attn_decode_seqshard(lp["attn"], h, pos, cfg, lc)
            else:
                o, lc2 = attn.attn_decode(lp["attn"], h, pos, cfg, lc,
                                          rolling=rolling)
            x = x + o
            if cfg.is_moe:
                y, lb = moe_mod.moe_apply(lp["moe"], rmsnorm(lp["norm2"], x, cfg.norm_eps), cfg)
                x, aux = x + y, aux + lb
            elif cfg.d_ff > 0:
                x = x + mlp(lp["ffn"], rmsnorm(lp["norm2"], x, cfg.norm_eps))
            return (x, aux), lc2

        (x, aux), new_cache = jax.lax.scan(body, (x, aux), (params["layers"], cache))

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(x, params["embed"] if cfg.tie_embeddings else None,
                     params.get("lm_head"))
    return logits, new_cache


def decode_step_rows(params, tokens, pos, cfg, cache
                     ) -> Tuple[jax.Array, Any, jax.Array]:
    """tokens: (B, 1) int32; pos: (B,) int32, each row at its own absolute
    position.  For a plain attention stack (dense or MoE FFN; not
    encoder-decoder, hybrid or SSM) with an unquantized {k, v} cache, or
    the MLA stack with its {c_kv, k_pe} cache.

    The stacked (L, B, S, ...) cache leaves ride the layer loop's carry, so
    each layer writes only its B new rows; under a donating jit the cache
    is updated in place.  Per row, the same math as ``decode_step``.
    Returns (logits, cache, the token-expert pairs the held experts
    computed per row: (B,) int32, zeros for a model without a held-expert
    layer)."""
    if cfg.is_mla:
        return _decode_rows_mla(params, tokens, pos, cfg, cache)
    x = embed_lookup(params["embed"], tokens)
    x = shard(x, "batch", "seq", "d_model")

    def body(carry, xs):
        x, k, v = carry
        lp, l = xs
        h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
        o, k, v = attn.attn_decode_rows(lp["attn"], h, pos, cfg, k, v, l)
        x = x + o
        if cfg.is_moe:
            y, _ = moe_mod.moe_apply(lp["moe"], rmsnorm(lp["norm2"], x, cfg.norm_eps), cfg)
            x = x + y
        elif cfg.d_ff > 0:
            x = x + mlp(lp["ffn"], rmsnorm(lp["norm2"], x, cfg.norm_eps))
        return (x, k, v), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cache["k"].shape[0])))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(x, params["embed"] if cfg.tie_embeddings else None,
                     params.get("lm_head"))
    return logits, dict(cache, k=k, v=v), jnp.zeros(tokens.shape[:1],
                                                    jnp.int32)


def _decode_rows_mla(params, tokens, pos, cfg, cache):
    """``decode_step_rows`` of the MLA stack: the leading dense layers' loop,
    then the MoE layers', both carrying the whole stacked latent cache and
    writing their layers' rows in place (absorbed MLA)."""
    x = embed_lookup(params["embed"], tokens)
    carry = (x, cache["c_kv"], cache["k_pe"],
             jnp.zeros(tokens.shape[:1], jnp.int32))

    def body(carry, xs):
        x, c, pe, pairs = carry
        lp, l = xs
        h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
        o, c, pe = attn.mla_decode_rows(lp["attn"], h, pos, cfg, c, pe, l)
        x, n = _mla_ffn(x + o, lp, cfg)
        return (x, c, pe, pairs + n), None

    first = 0
    for name in _MLA_GROUPS:
        if name not in params:
            continue
        n_layers = jax.tree.leaves(params[name])[0].shape[0]
        carry, _ = jax.lax.scan(body, carry,
                                (params[name], first + jnp.arange(n_layers)))
        first += n_layers
    x, c, pe, pairs = carry
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = lm_head(x, params["embed"] if cfg.tie_embeddings else None,
                     params.get("lm_head"))
    return logits, dict(cache, c_kv=c, k_pe=pe), pairs
