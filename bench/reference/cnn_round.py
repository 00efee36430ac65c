"""Plain reference of one TEASQ-Fed cohort round on the paper's CNN.

For every device of the round: the global model version it was sent
passes the down channel; E epochs of prox-SGD (paper Eq. 5) run on its
minibatches, each step ``w <- w - lr * (grad of the mean cross-entropy
of the batch + mu * (w - w_recv))``; the result passes the up channel.
The channel is the configuration's in-graph codec: a magnitude threshold
from a fixed-iteration binary search keeps about ``p_s`` of the entries,
and the kept ones are quantized to ``p_q`` bits, symmetric, rounding to
nearest, scaled by the largest kept magnitude.

Written from those equations in plain ``jax.numpy``; it imports nothing of
the program.  It runs in float32 at ``highest`` matmul precision;
``precision="high"`` (three bfloat16 passes) is the control of the
benchmark's comparison.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH}


def channel(x: jax.Array, p_s: float, p_q: int, iters: int) -> jax.Array:
    ax = jnp.abs(x)
    lo = jnp.zeros((), x.dtype)
    hi = jnp.max(ax) + jnp.asarray(1e-12, x.dtype)
    for _ in range(iters):
        mid = 0.5 * (lo + hi)
        keep_more = jnp.mean((ax >= mid).astype(jnp.float32)) > p_s
        lo, hi = jnp.where(keep_more, mid, lo), jnp.where(keep_more, hi, mid)
    mask = ax >= 0.5 * (lo + hi)
    kept = jnp.where(mask, x, 0)
    levels = 2 ** (p_q - 1) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(kept)), jnp.asarray(1e-12, x.dtype))
    q = jnp.clip(jnp.round(kept / scale * levels), -levels, levels)
    return jnp.where(mask, q * scale / levels, 0).astype(x.dtype)


def _conv2x2(x, w, b, prec):
    """2x2 convolution, stride 1, padded by one row and column at the high
    end (SAME for an even kernel)."""
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), ((0, 1), (0, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec)
    return y + b


def _pool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def forward(p: Dict, x: jax.Array, prec) -> jax.Array:
    h = _pool2(jax.nn.relu(_conv2x2(x, p["conv1"], p["b1"], prec)))
    h = _pool2(jax.nn.relu(_conv2x2(h, p["conv2"], p["b2"], prec)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, p["fc1"], precision=prec) + p["bf1"])
    return jnp.dot(h, p["fc2"], precision=prec) + p["bf2"]


def loss(p: Dict, x: jax.Array, y: jax.Array, prec) -> jax.Array:
    logits = forward(p, x, prec).astype(jnp.float32)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=(
    "lr", "mu", "p_s", "p_q", "iters", "dtype", "precision"))
def _device_round(w, x_dev, y_dev, bidx, valid, *, lr, mu, p_s, p_q, iters,
                  dtype, precision):
    """One device: down channel, ``len(bidx)`` masked prox-SGD steps, up
    channel.  ``x_dev``/``y_dev`` are the device's samples; ``bidx`` (T, B)
    indexes them."""
    w = jax.tree.map(lambda a: channel(a.astype(dtype), p_s, p_q, iters), w)
    anchor = w

    def step(p, sv):
        idx, v = sv
        g = jax.grad(functools.partial(loss, prec=PRECISIONS[precision]))(
            p, x_dev[idx].astype(dtype), y_dev[idx])
        v = v.astype(dtype)
        return jax.tree.map(
            lambda a, ga, an: (a - v * lr * (ga + mu * (a - an))).astype(dtype),
            p, g, anchor), None

    out, _ = jax.lax.scan(step, w, (bidx, valid))
    return w, jax.tree.map(lambda a: channel(a, p_s, p_q, iters), out)


def cohort_round(w_versions: Dict, vidx, didx, bidx, valid, data: Dict,
                 parts, *, lr: float, mu: float, p_s: float, p_q: int,
                 iters: int, dtype=jnp.float32, precision: str = "highest"):
    """The round for every cohort member ``c`` (``vidx[c]`` its version,
    ``didx[c]`` its device, ``bidx[:, c]``/``valid[:, c]`` its steps).
    Returns ``(received, result)``: per member, the model after the down
    channel and after the round, each as a dict of float32 numpy arrays
    with a leading member axis."""
    recv, outs = [], []
    with jax.default_matmul_precision(precision):
        for c in range(len(didx)):
            part = parts[int(didx[c])]
            x_dev = jnp.asarray(data["x_train"][part])
            y_dev = jnp.asarray(data["y_train"][part])
            w = {k: jnp.asarray(v[int(vidx[c])]) for k, v in w_versions.items()}
            r, o = _device_round(w, x_dev, y_dev, jnp.asarray(bidx[:, c]),
                                 jnp.asarray(valid[:, c]), lr=lr, mu=mu,
                                 p_s=p_s, p_q=p_q, iters=iters, dtype=dtype,
                                 precision=precision)
            recv.append(r)
            outs.append(o)
    stack = lambda ts: {k: np.stack([np.asarray(t[k], np.float32) for t in ts])
                        for k in ts[0]}
    return stack(recv), stack(outs)


def change_gaps(got: Dict, ref: Dict, recv: Dict) -> Dict[str, float]:
    """Per leaf, the gap between the norms of the round's change in the
    program (``got - recv``) and in the reference (``ref - recv``), over the
    reference's change of that leaf or of the median leaf, whichever is
    larger.  Leaves whose reference change is under a thousandth of the
    median leaf's are left out: they move by round-off alone."""
    norms = {k: float(np.linalg.norm((ref[k] - recv[k]).astype(np.float64)))
             for k in ref}
    med = float(np.median(list(norms.values())))
    out = {}
    for k in ref:
        if norms[k] < 1e-3 * med:
            continue
        g = float(np.linalg.norm((got[k] - recv[k]).astype(np.float64)))
        out[k] = abs(g - norms[k]) / max(norms[k], med)
    return out


def mismatch_share(got: Dict, ref: Dict, p_q: int) -> float:
    """Share of the weights (all leaves of one device) that the round puts
    on another quantization level than the reference, or on the other side
    of the sparsity threshold: ``|got - ref|`` over half a level of that
    leaf, the level being the reference's largest magnitude over
    ``2**(p_q - 1) - 1``.  Values on the same level agree to rounding of
    the scale and do not count."""
    levels = 2 ** (p_q - 1) - 1
    bad = total = 0
    for k in ref:
        r = np.asarray(ref[k], np.float64)
        half = 0.5 * np.max(np.abs(r)) / levels
        bad += int(np.sum(np.abs(np.asarray(got[k], np.float64) - r) > half))
        total += r.size
    return bad / total
