"""Runnable trainer (single host): TEASQ-Fed rounds or plain SGD on any
assigned architecture at reduced (smoke) or full scale.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --mode fed --groups 4 --local-steps 2 --steps 30
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.configs.base import get_config, get_smoke_config
from repro.core.fed_step import FedConfig, make_fed_train_step
from repro.data import make_token_batch
from repro.launch.cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import adamw, apply_updates, clip_by_global_norm


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--mode", default="plain", choices=["plain", "fed"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--fed-schedule", default="gather_q")
    ap.add_argument("--mu", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[train] {cfg.name}: {cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab} family={cfg.family}")
    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {n_params/1e6:.2f}M params")

    rng = np.random.RandomState(args.seed)

    def make_batch():
        b = make_token_batch(rng, args.batch, args.seq, cfg.vocab)
        batch = {"tokens": jnp.asarray(b["tokens"])}
        if cfg.is_encoder_decoder:
            batch["frames"] = jnp.asarray(
                rng.randn(args.batch, cfg.enc_seq, cfg.d_model), jnp.float32)
        if cfg.n_patches:
            batch["patches"] = jnp.asarray(
                rng.randn(args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
        return batch

    if args.mode == "fed":
        fed = FedConfig(n_groups=args.groups, local_steps=args.local_steps,
                        lr=args.lr, mu=args.mu, schedule=args.fed_schedule)
        step = jax.jit(make_fed_train_step(
            lambda p, b: T.lm_loss(p, b, cfg)[0], fed))
        stale = jnp.zeros((args.groups,), jnp.int32)
        for i in range(args.steps):
            t0 = time.time()
            params, m = step(params, make_batch(), stale)
            print(f"[fed round {i:3d}] loss={float(m['local_loss']):.4f} "
                  f"alpha_t={float(m['alpha_t']):.3f} "
                  f"({time.time()-t0:.2f}s)", flush=True)
    else:
        opt = adamw(args.lr)
        opt_state = opt.init(params)

        @jax.jit
        def step(p, s, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda q: T.lm_loss(q, batch, cfg), has_aux=True)(p)
            grads, gn = clip_by_global_norm(grads, 1.0)
            upd, s = opt.update(grads, s, p)
            return apply_updates(p, upd), s, loss, gn

        for i in range(args.steps):
            t0 = time.time()
            params, opt_state, loss, gn = step(params, opt_state, make_batch())
            print(f"[step {i:3d}] loss={float(loss):.4f} "
                  f"gnorm={float(gn):.2f} ({time.time()-t0:.2f}s)", flush=True)

    if args.ckpt:
        save_pytree(args.ckpt, params)
        print(f"[train] checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
