"""GQA attention: train/prefill (flash-chunked), encoder (full), cross, decode;
and multi-head latent attention (MLA): plain prefill, absorbed decode.

Pure JAX. Query-chunked + kv-chunked online-softmax attention keeps live
memory bounded at 32k sequence lengths; causal chunk skipping is structural
(python loop over query chunks, inner ``lax.scan`` only over needed kv chunks)
so the compiled FLOPs match causal attention, not dense.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, head_rmsnorm, rotary
from repro.sharding.rules import shard, shard_map

NEG_INF = -1e30


def attn_init(key, cfg, dtype=jnp.float32, cross: bool = False):
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_q(p, x, positions, cfg, rope: bool):
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    if "q_norm" in p:
        q = head_rmsnorm(q, p["q_norm"], cfg.norm_eps)
    if rope:
        q = rotary(q, positions, cfg.rope_theta)
    return shard(q, "batch", "seq", "heads", "head_dim")


def _project_kv(p, x, positions, cfg, rope: bool):
    B, S, _ = x.shape
    hd = cfg.head_dim
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if "k_norm" in p:
        k = head_rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        k = rotary(k, positions, cfg.rope_theta)
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return k, v


def _grouped_scores(q, k):
    """q: (B,Sq,H,hd), k: (B,Sk,G,hd) with H = G*rep -> (B,G,rep,Sq,Sk) f32.

    Operands keep their storage dtype (bf16 on TPU) with f32 MXU
    accumulation — converting the KV cache to f32 before the dot would
    double its HBM read traffic (§Perf pair-B iteration 2).
    """
    B, Sq, H, hd = q.shape
    G = k.shape[2]
    q = q.reshape(B, Sq, G, H // G, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                   preferred_element_type=jnp.float32)
    return s / math.sqrt(hd)


def _grouped_out(probs, v, out_dtype):
    """probs: (B,G,rep,Sq,Sk), v: (B,Sk,G,hd) -> (B,Sq,H,hd)."""
    B, G, rep, Sq, _ = probs.shape
    o = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, G * rep, -1).astype(out_dtype)


def _plain_attention(q, k, v, mask) -> jax.Array:
    s = _grouped_scores(q, k)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)  # mask broadcasts over (B,G,rep)
    probs = jax.nn.softmax(s, axis=-1)
    return _grouped_out(probs, v, q.dtype)


def _flash_attention(q, k, v, *, causal: bool, window: int = 0,
                     q_chunk: int = 1024, kv_chunk: int = 1024) -> jax.Array:
    """Online-softmax attention, memory O(q_chunk * kv_chunk) scores."""
    B, Sq, H, hd = q.shape
    Sk, G = k.shape[1], k.shape[2]
    rep = H // G
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    # pad ragged sequence lengths (e.g. VLM: patches + tokens) up to chunks
    Sq_pad = -(-Sq // q_chunk) * q_chunk
    Sk_pad = -(-Sk // kv_chunk) * kv_chunk
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    if Sk_pad != Sk:
        k = jnp.pad(k, ((0, 0), (0, Sk_pad - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sk_pad - Sk), (0, 0), (0, 0)))
    Sk_real, Sq_orig = Sk, Sq
    Sq, Sk = Sq_pad, Sk_pad
    n_q = Sq // q_chunk

    def one_q_chunk(qi: int, qc):
        # kv chunks needed for this q chunk (structural causal skip)
        q_end = (qi + 1) * q_chunk if causal else Sk
        n_kv = -(-q_end // kv_chunk)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def body(carry, ki):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, ki * kv_chunk, kv_chunk, 1)
            vc = jax.lax.dynamic_slice_in_dim(v, ki * kv_chunk, kv_chunk, 1)
            s = _grouped_scores(qc, kc)                   # (B,G,rep,qc,kc)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            msk = jnp.broadcast_to((k_pos < Sk_real)[None, :],
                                   (q_chunk, kv_chunk))
            if causal:
                msk = msk & (q_pos[:, None] >= k_pos[None, :])
            if window:
                msk = msk & ((q_pos[:, None] - k_pos[None, :]) < window)
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(vc.dtype), vc,
                            preferred_element_type=jnp.float32)
            acc_new = acc * corr[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, G, rep, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, G, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, G, rep, q_chunk, v.shape[-1]), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      jnp.arange(n_kv))
        o = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,G,rep,qc,hd_v)
        return jnp.moveaxis(o, 3, 1).reshape(B, q_chunk, H, -1).astype(q.dtype)

    outs = []
    for qi in range(n_q):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, 1)
        outs.append(one_q_chunk(qi, qc))
    out = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return out[:, :Sq_orig] if Sq_orig != Sq else out


# ----------------------------------------------------------------------
def attn_forward(p, x, positions, cfg, *, causal: bool = True,
                 enc_out=None, window: int = 0,
                 flash_threshold: int = 2048, return_kv: bool = False):
    """Full-sequence attention (train / prefill / encoder / cross)."""
    B, S, _ = x.shape
    rope = enc_out is None
    q = _project_q(p, x, positions, cfg, rope)
    if enc_out is None:
        k, v = _project_kv(p, x, positions, cfg, rope)
    else:
        Se = enc_out.shape[1]
        k, v = _project_kv(p, enc_out, jnp.zeros((B, Se), jnp.int32), cfg, False)

    Sk = k.shape[1]
    if max(S, Sk) > flash_threshold:
        o = _flash_attention(q, k, v, causal=causal and enc_out is None,
                             window=window)
    else:
        mask = None
        if causal and enc_out is None:
            mask = jnp.tril(jnp.ones((S, Sk), bool))
            if window:
                mask &= (jnp.arange(S)[:, None] - jnp.arange(Sk)[None, :]) < window
        o = _plain_attention(q, k, v, mask)
    o = shard(o, "batch", "seq", "heads", "head_dim")
    o = o.reshape(B, S, -1) @ p["wo"]
    o = shard(o, "batch", "seq", "d_model")
    if return_kv:
        return o, {"k": k, "v": v}
    return o


# -- decode (one token, KV cache) ---------------------------------------
def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16,
               cross_len: int = 0, quantized: bool = False):
    """KV cache. ``quantized=True`` stores int8 levels + per-(slot, head)
    f32 scales — the paper's quantization insight applied to serving memory
    (2x HBM traffic cut at decode; see EXPERIMENTS.md §Perf)."""
    hd = cfg.head_dim
    G = cfg.n_kv_heads
    if quantized:
        c = {
            "k": jnp.zeros((batch, cache_len, G, hd), jnp.int8),
            "v": jnp.zeros((batch, cache_len, G, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, cache_len, G), jnp.float32),
            "v_scale": jnp.zeros((batch, cache_len, G), jnp.float32),
        }
    else:
        c = {
            "k": jnp.zeros((batch, cache_len, G, hd), dtype),
            "v": jnp.zeros((batch, cache_len, G, hd), dtype),
        }
    if cross_len:
        c["xk"] = jnp.zeros((batch, cross_len, G, hd), dtype)
        c["xv"] = jnp.zeros((batch, cross_len, G, hd), dtype)
    return c


def _quant_kv(x):
    """x: (B,1,G,hd) -> (int8 levels, (B,1,G) scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                        1e-12)
    lv = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None] * 127),
                  -127, 127).astype(jnp.int8)
    return lv, scale


def _dequant_kv(lv, scale, dtype):
    return (lv.astype(jnp.float32) * (scale[..., None] / 127.0)).astype(dtype)


def attn_decode(p, x, pos, cfg, cache, *, rolling: bool = False,
                cross: bool = False) -> Tuple[jax.Array, dict]:
    """One-token decode. x: (B,1,D); pos: scalar absolute position.

    ``rolling=True`` treats the cache as a circular window buffer (slot =
    pos % cache_len, all slots valid) for sub-quadratic long-context decode.
    """
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = _project_q(p, x, positions, cfg, rope=not cross)

    if cross:  # enc-dec cross attention: cache is pre-filled, never written
        k, v = cache["xk"], cache["xv"]
        mask = None
        new_cache = cache
    else:
        k_new, v_new = _project_kv(p, x, positions, cfg, rope=True)
        L = cache["k"].shape[1]
        slot = jnp.mod(pos, L) if rolling else pos
        quantized = "k_scale" in cache
        if quantized:
            k_lv, k_sc = _quant_kv(k_new)
            v_lv, v_sc = _quant_kv(v_new)
            upd = jax.lax.dynamic_update_slice_in_dim
            kq = upd(cache["k"], k_lv, slot, 1)
            vq = upd(cache["v"], v_lv, slot, 1)
            ks = upd(cache["k_scale"], k_sc, slot, 1)
            vs = upd(cache["v_scale"], v_sc, slot, 1)
            new_cache = dict(cache, k=kq, v=vq, k_scale=ks, v_scale=vs)
            k = _dequant_kv(kq, ks, x.dtype)
            v = _dequant_kv(vq, vs, x.dtype)
        else:
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), slot, 1)
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), slot, 1)
            new_cache = dict(cache, k=k, v=v)
        if rolling:
            valid = jnp.minimum(pos + 1, L)  # warmup: only first pos+1 slots
            mask = (jnp.arange(L) < valid)[None, :]
        else:
            mask = (jnp.arange(L) <= pos)[None, :]

    s = _grouped_scores(q, k)                       # (B,G,rep,1,L)
    if mask is not None:
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    o = _grouped_out(probs, v, x.dtype)             # (B,1,H,hd)
    o = o.reshape(B, 1, -1) @ p["wo"]
    return shard(o, "batch", "seq", "d_model"), new_cache


def attn_decode_rows(p, x, pos, cfg, k_all, v_all, layer
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode with each row at its own position, against layer
    ``layer`` of a stacked (L, B, S, G, hd) cache.  x: (B,1,D); pos: (B,)
    int32.  Writes only the B new K/V rows into ``k_all``/``v_all`` (a row
    whose position is past the cache writes nothing) and reads the layer's
    slab where it lies.  Same math as ``attn_decode`` per row."""
    B = x.shape[0]
    positions = pos[:, None]
    q = _project_q(p, x, positions, cfg, rope=True)
    k_new, v_new = _project_kv(p, x, positions, cfg, rope=True)
    rows = jnp.arange(B)
    k_all = k_all.at[layer, rows, pos].set(k_new[:, 0].astype(k_all.dtype),
                                           mode="drop")
    v_all = v_all.at[layer, rows, pos].set(v_new[:, 0].astype(v_all.dtype),
                                           mode="drop")
    mask = jnp.arange(k_all.shape[2])[None, :] <= positions      # (B,S)
    s = _grouped_scores(q, k_all[layer])                          # (B,G,rep,1,S)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    o = _grouped_out(probs, v_all[layer], x.dtype)                # (B,1,H,hd)
    o = o.reshape(B, 1, -1) @ p["wo"]
    return shard(o, "batch", "seq", "d_model"), k_all, v_all


# -- sequence-sharded decode (beyond-paper: MQA/GQA KV too small to TP) ---
def attn_decode_seqshard(p, x, pos, cfg, cache) -> Tuple[jax.Array, dict]:
    """One-token decode with the KV cache sharded along SEQUENCE over the
    'model' axis, merged with a log-sum-exp flash-merge psum.

    For MQA (granite: kv=1) the KV cache cannot shard over heads, so every
    TP rank otherwise reads the full 32k cache.  Sharding the cache on the
    sequence axis cuts per-chip KV HBM traffic by the TP degree at the cost
    of one tiny (B,H) psum triple.  See EXPERIMENTS.md §Perf.
    """
    from repro.sharding.rules import active_rules
    from jax.sharding import PartitionSpec as P
    rules = active_rules()
    mesh = rules.mesh
    n_model = mesh.shape["model"]
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = _project_q(p, x, positions, cfg, rope=True)        # (B,1,H,hd)
    k_new, v_new = _project_kv(p, x, positions, cfg, rope=True)

    L = cache["k"].shape[1]
    L_loc = L // n_model
    ba = rules.mapping.get("batch")
    batch_axes = (ba,) if isinstance(ba, str) else tuple(ba or ())
    bspec = batch_axes if (batch_axes and B % (
        math.prod(mesh.shape[a] for a in batch_axes)) == 0) else None

    cache_spec = P(bspec, "model", None, None)

    def body(q_r, kn, vn, kc, vc):
        r = jax.lax.axis_index("model")
        # write the new kv into the owner rank's slice
        slot_loc = pos - r * L_loc
        owned = (slot_loc >= 0) & (slot_loc < L_loc)
        slot_c = jnp.clip(slot_loc, 0, L_loc - 1)
        kc2 = jax.lax.dynamic_update_slice_in_dim(
            kc, kn.astype(kc.dtype), slot_c, 1)
        vc2 = jax.lax.dynamic_update_slice_in_dim(
            vc, vn.astype(vc.dtype), slot_c, 1)
        kc2 = jnp.where(owned, kc2, kc)
        vc2 = jnp.where(owned, vc2, vc)

        s = _grouped_scores(q_r, kc2)                      # (B,G,rep,1,L_loc)
        gidx = r * L_loc + jnp.arange(L_loc)
        s = jnp.where((gidx <= pos)[None, None, None, None, :], s, NEG_INF)
        m_loc = s.max(axis=-1)                             # (B,G,rep,1)
        m_glob = jax.lax.pmax(m_loc, "model")
        e = jnp.exp(s - m_glob[..., None])
        l_loc = e.sum(axis=-1)
        o_loc = jnp.einsum("bgrqk,bkgd->bgrqd", e.astype(vc2.dtype), vc2,
                           preferred_element_type=jnp.float32)
        l = jax.lax.psum(l_loc, "model")
        o = jax.lax.psum(o_loc, "model")
        o = (o / jnp.maximum(l, 1e-30)[..., None])
        Bq, G, rep, _, hd = o.shape
        o = jnp.moveaxis(o, 3, 1).reshape(Bq, 1, G * rep, hd)
        return o.astype(q_r.dtype), kc2, vc2

    o, k2, v2 = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None), cache_spec, cache_spec),
        out_specs=(P(bspec, None, None, None), cache_spec, cache_spec),
        check_vma=False)(q, k_new, v_new, cache["k"], cache["v"])
    new_cache = dict(cache, k=k2, v=v2)
    o = o.reshape(B, 1, -1) @ p["wo"]
    return shard(o, "batch", "seq", "d_model"), new_cache


# -- multi-head latent attention (MLA, DeepSeek-V2/V3) ---------------------
# Queries: x @ wq -> per head a part without rope (qk_nope_dim) and a rope
# part (qk_rope_dim).  Keys and values come from a latent: x @ wkv_a gives
# the latent (kv_lora_rank, RMS-normed by kv_norm) and one rope key shared
# by every head; the latent @ wkv_b gives each head's key part without rope
# and its value.  Softmax scale 1 / sqrt(qk_nope_dim + qk_rope_dim).  The
# cache holds the normed latent and the roped shared key of each position.
def mla_init(key, cfg, dtype=jnp.float32):
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, H * (dn + dr), dtype),
        "wkv_a": dense_init(k2, cfg.d_model, r + dr, dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": dense_init(k3, r, H * (dn + dv), dtype),
        "wo": dense_init(k4, H * dv, cfg.d_model, dtype),
    }


def rope_pairs(x, positions, theta: float):
    """Rotary embedding of interleaved pairs (x[2i], x[2i+1]), as the
    deepseek_v3 modeling code applies it: the pairs are gathered into
    halves (evens, then odds) before the rotate-half rotation, and the
    result stays in that order.  Queries and keys take the same order, so
    their products are those of the interleaved rotation."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return rotary(x, positions, theta)


def _mla_q(p, x, positions, cfg):
    """-> (q without rope (B,S,H,dn), roped q (B,S,H,dr))."""
    B, S, _ = x.shape
    dn = cfg.qk_nope_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, dn + cfg.qk_rope_dim)
    return q[..., :dn], rope_pairs(q[..., dn:], positions, cfg.rope_theta)


def _mla_latent(p, x, positions, cfg):
    """The rows the cache holds: normed latent (B,S,r), roped key (B,S,dr)."""
    r = cfg.kv_lora_rank
    kv = x @ p["wkv_a"]
    c = head_rmsnorm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope_pairs(kv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return c, k_pe


def mla_forward(p, x, positions, cfg, *, flash_threshold: int = 2048,
                return_cache: bool = False):
    """Full-sequence causal MLA (train / prefill): the latent is expanded
    into each head's keys and values.  With ``return_cache`` also returns
    the rows the cache holds, ``{c_kv: (B,S,r), k_pe: (B,S,dr)}``."""
    with jax.named_scope("mla_attn"):
        B, S, _ = x.shape
        H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
        q_nope, q_pe = _mla_q(p, x, positions, cfg)
        c, k_pe = _mla_latent(p, x, positions, cfg)
        kv = (c @ p["wkv_b"]).reshape(B, S, H, dn + cfg.v_head_dim)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], (B, S, H, dr))],
            axis=-1)
        v = kv[..., dn:]
        if S > flash_threshold:
            o = _flash_attention(q, k, v, causal=True)
        else:
            o = _plain_attention(q, k, v, jnp.tril(jnp.ones((S, S), bool)))
        o = o.reshape(B, S, -1) @ p["wo"]
    if return_cache:
        return o, {"c_kv": c, "k_pe": k_pe}
    return o


def mla_decode_rows(p, x, pos, cfg, c_all, pe_all, layer
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token MLA with each row at its own position, against layer
    ``layer`` of the stacked latent cache ``c_all`` (L, B, S, r) and rope
    keys ``pe_all`` (L, B, S, dr).  x: (B,1,D); pos: (B,) int32.  Writes
    only the B new rows (a row whose position is past the cache writes
    nothing).  Absorbed: wkv_b's key half is folded into the query and its
    value half into the output, so attention runs over the cached rows as
    they are.  Same math as ``mla_forward`` per row."""
    with jax.named_scope("mla_attn"):
        B = x.shape[0]
        H, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
        positions = pos[:, None]
        q_nope, q_pe = _mla_q(p, x, positions, cfg)
        c, k_pe = _mla_latent(p, x, positions, cfg)
        rows = jnp.arange(B)
        c_all = c_all.at[layer, rows, pos].set(c[:, 0].astype(c_all.dtype),
                                               mode="drop")
        pe_all = pe_all.at[layer, rows, pos].set(
            k_pe[:, 0].astype(pe_all.dtype), mode="drop")
        c_l, pe_l = c_all[layer], pe_all[layer]                  # (B,S,.)
        w = p["wkv_b"].reshape(r, H, dn + cfg.v_head_dim)
        f32 = jnp.float32
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w[..., :dn],
                           preferred_element_type=f32).astype(c_l.dtype)
        s = (jnp.einsum("bhr,bsr->bhs", q_lat, c_l, preferred_element_type=f32)
             + jnp.einsum("bhd,bsd->bhs", q_pe[:, 0].astype(pe_l.dtype), pe_l,
                          preferred_element_type=f32)) / math.sqrt(dn + dr)
        mask = jnp.arange(c_l.shape[1])[None, :] <= positions       # (B,S)
        s = jnp.where(mask[:, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs.astype(c_l.dtype), c_l,
                           preferred_element_type=f32).astype(x.dtype)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w[..., dn:],
                       preferred_element_type=f32).astype(x.dtype)
        o = o.reshape(B, 1, -1) @ p["wo"]
    return o, c_all, pe_all
