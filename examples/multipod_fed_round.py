"""The paper's technique on a (simulated) pod: TEASQ-Fed rounds as a single
jit-compiled step over a device mesh, with compressed delta exchange.

Uses 8 virtual host devices (set before jax import) to build a 4x2
(data=fed groups x model) mesh — the same code path the 512-chip dry-run
lowers, executable on CPU.

  PYTHONPATH=src python examples/multipod_fed_round.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.core.fed_step import FedConfig, fed_wire_bytes, make_fed_train_step
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.sharding.rules import Rules, use_rules

cfg = get_smoke_config("smollm-135m")
mesh = make_host_mesh(data=4, model=2)
rules = Rules(mesh)

params = T.init_model(jax.random.PRNGKey(0), cfg)
fed = FedConfig(n_groups=4, local_steps=2, lr=1e-2, schedule="gather_q",
                p_s=0.25, p_q=8)
step = jax.jit(make_fed_train_step(lambda p, b: T.lm_loss(p, b, cfg)[0], fed))

rng = np.random.RandomState(0)
batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (16, 64)), jnp.int32)}
# groups at different staleness, as the async cache would present them
stale = jnp.asarray([0, 1, 0, 3], jnp.int32)

wire = fed_wire_bytes(params, fed, 4)
print(f"[wire] per-round exchange: dense f32 {wire['dense_f32']/1e6:.1f}MB "
      f"-> int8 {wire['dense_quant']/1e6:.1f}MB "
      f"-> packed sparse {wire['packed_sparse_quant']/1e6:.1f}MB "
      f"({wire['compression_x']:.1f}x)")

with use_rules(rules), mesh:
    for i in range(5):
        t0 = time.time()
        params, m = step(params, batch, stale)
        jax.block_until_ready(m["local_loss"])
        print(f"[round {i}] loss={float(m['local_loss']):.4f} "
              f"alpha_t={float(m['alpha_t']):.3f} "
              f"|delta|={float(m['delta_norm']):.3f} "
              f"({time.time()-t0:.2f}s on {mesh.devices.size} devices)")
