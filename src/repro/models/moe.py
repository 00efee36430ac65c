"""Mixture-of-Experts FFN with expert parallelism.

Two execution paths with identical routing semantics:

* ``_moe_dense_ref`` — reference: every expert computed on every token, masked
  combine. Exact (no capacity drops). Used on CPU/no-mesh and as the oracle.
* ``_moe_ep_sharded`` — production: ``shard_map`` over the mesh; each model
  rank owns ``E/model`` experts, selects up to capacity C tokens per expert
  from its (data-sharded, model-replicated) token slice via a sort-free
  cumsum-rank dispatch, runs the expert FFN locally, scatter-adds weighted
  outputs and ``psum``s over the model axis.  The only collective is that
  psum — token->expert transport is free because activations enter the block
  model-replicated (Megatron-TP style).

Top-k routing: softmax over the top-k router logits (Mixtral convention).
Aux output is the Switch-style load-balance loss.

``held_moe_apply`` is the DeepSeek-V3 layer (``moe_router="sigmoid"``):
sigmoid scores with a per-expert bias for selection only, shared experts
on every token, and only the routed experts ``cfg.held_experts`` computed
here — a chip's share under expert parallelism, run without the exchange.
It routes over every expert, drops no token, and its expert work grows
with the pairs routed to the experts it holds (grouped matmuls over the
pairs sorted by expert), not with experts x tokens.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, mlp, mlp_init
from repro.sharding.rules import active_rules, shard, shard_map


def moe_init(key, cfg, dtype=jnp.float32):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = 1.0 / math.sqrt(d)
    return {
        "router": dense_init(k1, d, E, jnp.float32),
        "e_gate": jax.random.uniform(k2, (E, d, f), dtype, -scale, scale),
        "e_up": jax.random.uniform(k3, (E, d, f), dtype, -scale, scale),
        "e_down": jax.random.uniform(k4, (E, f, d), dtype,
                                     -1.0 / math.sqrt(f), 1.0 / math.sqrt(f)),
    }


def _route(router, x, k: int):
    """x: (T, D) -> (weights (T,k) f32, experts (T,k) i32, probs (T,E) f32)."""
    logits = (x.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_logits, top_e = jax.lax.top_k(logits, k)
    top_w = jax.nn.softmax(top_logits, axis=-1)
    return top_w, top_e, probs


def _load_balance_loss(probs, top_e, n_experts: int) -> jax.Array:
    """Switch-transformer aux loss: E * sum_e f_e * p_e."""
    onehot = jax.nn.one_hot(top_e, n_experts, dtype=jnp.float32)  # (T,k,E)
    frac = onehot.sum(axis=(0, 1)) / (top_e.shape[0] * top_e.shape[1])
    mean_p = probs.mean(axis=0)
    return n_experts * jnp.sum(frac * mean_p)


def _expert_ffn(gate, up, down, xb):
    """xb: (E?, C, D) with per-expert weights (E?, D, F)/(E?, F, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, gate))
    h = h * jnp.einsum("ecd,edf->ecf", xb, up)
    return jnp.einsum("ecf,efd->ecd", h, down)


def _moe_dense_ref(params, x, cfg) -> Tuple[jax.Array, jax.Array]:
    T, D = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    w, e, probs = _route(params["router"], x, k)
    # compute every expert on every token (reference only)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, params["e_gate"]))
    h = h * jnp.einsum("td,edf->etf", x, params["e_up"])
    y_e = jnp.einsum("etf,efd->etd", h, params["e_down"])      # (E,T,D)
    onehot = jax.nn.one_hot(e, E, dtype=y_e.dtype)             # (T,k,E)
    comb = jnp.einsum("tke,tk->et", onehot, w.astype(y_e.dtype))
    y = jnp.einsum("etd,et->td", y_e, comb)
    return y, _load_balance_loss(probs, e, E)


def _dispatch_ranks(top_e, E: int):
    """Sort-free rank-within-expert for each (token, slot). Returns (S,) i32
    rank and (S,) i32 flat expert id, S = T*k."""
    fe = top_e.reshape(-1)                                     # (S,)
    onehot = (fe[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - 1                     # (S,E)
    rank = jnp.take_along_axis(ranks, fe[:, None], axis=1)[:, 0]
    return rank, fe


def _moe_ep_local(params_loc, x_loc, cfg, capacity: int, e_loc: int,
                  model_axis: str):
    """shard_map body: x_loc (T_loc, D) model-replicated; expert weights local."""
    T, D = x_loc.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    r = jax.lax.axis_index(model_axis)
    w, e, probs = _route(params_loc["router"], x_loc, k)
    rank, fe = _dispatch_ranks(e, E)
    fw = w.reshape(-1)
    tok = jnp.arange(T * k) // k

    le = fe - r * e_loc
    owned = (le >= 0) & (le < e_loc) & (rank < capacity)
    dest = jnp.where(owned, le * capacity + rank, e_loc * capacity)  # OOB slot

    nbuf = e_loc * capacity
    buf = jnp.zeros((nbuf + 1, D), x_loc.dtype).at[dest].set(x_loc[tok])
    tok_idx = jnp.full((nbuf + 1,), T, jnp.int32).at[dest].set(tok.astype(jnp.int32))
    w_buf = jnp.zeros((nbuf + 1,), jnp.float32).at[dest].set(fw)

    xb = buf[:nbuf].reshape(e_loc, capacity, D)
    yb = _expert_ffn(params_loc["e_gate"], params_loc["e_up"],
                     params_loc["e_down"], xb).reshape(nbuf, D)
    contrib = yb * w_buf[:nbuf, None].astype(yb.dtype)
    y = jnp.zeros((T, D), x_loc.dtype).at[tok_idx[:nbuf]].add(
        contrib.astype(x_loc.dtype), mode="drop")
    y = jax.lax.psum(y, model_axis)
    aux = _load_balance_loss(probs, e, E)
    return y, aux


def moe_apply(params, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y (B,S,D), load_balance_loss scalar)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    rules = active_rules()
    if rules is None or "model" not in rules.mesh.axis_names \
            or cfg.n_experts % rules.mesh.shape["model"] != 0:
        y, aux = _moe_dense_ref(params, xt, cfg)
        return shard(y.reshape(B, S, D), "batch", "seq", "d_model"), aux

    mesh = rules.mesh
    n_model = mesh.shape["model"]
    e_loc = cfg.n_experts // n_model
    # token sharding follows the *current* logical 'batch' mapping (inside
    # the fed group-local region this is None: the fed axes hold the groups)
    ba = rules.mapping.get("batch")
    batch_axes = (ba,) if isinstance(ba, str) else tuple(ba or ())
    n_batch = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    T = B * S
    tok_spec = batch_axes if batch_axes and T % n_batch == 0 else None
    T_loc = T // n_batch if tok_spec else T
    capacity = max(8, int(math.ceil(T_loc * cfg.moe_top_k / cfg.n_experts
                                    * cfg.capacity_factor)))

    from jax.sharding import PartitionSpec as P
    in_specs = (
        {"router": P(), "e_gate": P("model"), "e_up": P("model"),
         "e_down": P("model")},
        P(tok_spec, None),
    )
    out_specs = (P(tok_spec, None), P())

    def body(p_loc, x_loc):
        y, aux = _moe_ep_local(p_loc, x_loc, cfg, capacity, e_loc, "model")
        # aux differs per data shard; average to a replicated scalar
        axes = batch_axes if tok_spec else ()
        if axes:
            aux = jax.lax.pmean(aux, axes)
        return y, aux

    y, aux = shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)(params, xt)
    return shard(y.reshape(B, S, D), "batch", "seq", "d_model"), aux


# -- the held-expert layer (DeepSeek-V3 routing, shared experts) ----------
def held_moe_init(key, cfg, dtype=jnp.float32):
    d, f, E = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    lo, hi = cfg.held_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(k1, d, E, jnp.float32),
        "router_bias": jnp.zeros((E,), jnp.float32),
        "e_gate": jax.random.uniform(k2, (hi - lo, d, f), dtype, -scale, scale),
        "e_up": jax.random.uniform(k3, (hi - lo, d, f), dtype, -scale, scale),
        "e_down": jax.random.uniform(k4, (hi - lo, f, d), dtype,
                                     -1.0 / math.sqrt(f), 1.0 / math.sqrt(f)),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(k5, d, cfg.n_shared_experts * f, dtype)
    return p


def route_sigmoid(router, bias, x, k: int, scaling: float):
    """DeepSeek-V3 ``noaux_tc`` routing with one group.  x: (T, D) ->
    (weights (T,k) f32, experts (T,k) i32): the k experts of the largest
    sigmoid score + ``bias``, weighted by their scores without the bias,
    normalised to sum 1 and times ``scaling``.  The logits are float32 at
    full precision, as the published model computes them."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, top_e, axis=-1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * scaling, top_e


def held_moe_apply(params, x, cfg) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y (B,S,D), the token-expert pairs the held experts
    computed for each token (B,S) int32).  y is the held experts' weighted
    part of the routed result plus the shared experts."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    k = cfg.moe_top_k
    lo, hi = cfg.held_experts
    f32 = jnp.float32
    with jax.named_scope("moe_route"):
        w, e = route_sigmoid(params["router"], params["router_bias"], xt, k,
                             cfg.routed_scaling)
        local = e.reshape(-1) - lo                               # (T*k,)
        held = (local >= 0) & (local < hi - lo)
        group = jnp.where(held, local, hi - lo)   # the pairs not held last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.sum(group[:, None] == jnp.arange(hi - lo)[None, :],
                        axis=0).astype(jnp.int32)
        tok = order // k
        keep = held[order]
        wk = jnp.where(keep, w.reshape(-1)[order], 0.0)
        xs = xt[tok]
    with jax.named_scope("moe_experts"):
        g = jax.lax.ragged_dot(xs, params["e_gate"], sizes,
                               preferred_element_type=f32)
        u = jax.lax.ragged_dot(xs, params["e_up"], sizes,
                               preferred_element_type=f32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        out = jax.lax.ragged_dot(h, params["e_down"], sizes,
                                 preferred_element_type=f32)
        # rows past the held pairs belong to no group: not read
        out = jnp.where(keep[:, None], out, 0.0) * wk[:, None]
        y = jnp.zeros((B * S, D), f32).at[tok].add(out)
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            y = y + mlp(params["shared"], x).reshape(B * S, D).astype(f32)
    pairs = held.reshape(B, S, k).sum(-1, dtype=jnp.int32)
    return y.astype(x.dtype).reshape(B, S, D), pairs
