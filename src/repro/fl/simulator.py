"""Event-driven asynchronous FL simulator (virtual wall-clock).

Faithfully executes the TEASQ-Fed protocol of Fig. 1 over N devices with the
paper's wireless + shifted-exponential latency model, running *real* JAX
local training (prox-SGD on the model selected by ``SimConfig.task`` — the
Fashion-MNIST-like CNN by default; see ``repro.fl.tasks.TASKS``).  Also
drives the baselines: FedAvg (synchronous), FedAsync (immediate update),
TEA-Fed (no compression), TEAS/TEAQ/TEAStatic/TEASQ (compression variants).
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import local_update
from repro.core.codecs import IdentityCodec
from repro.core.dynamic import CompressionSchedule
from repro.core.latency import ComputeConfig, WirelessConfig
from repro.core.server import ServerConfig, TeasqServer
from repro.core.staleness import staleness_weight
from repro.fl.tasks import get_task


@functools.partial(jax.jit, static_argnames=("lr", "mu_con", "tau",
                                             "forward_fn", "features_fn"))
def _moon_sgd_step(params, batch, lr: float, mu_con: float, tau: float,
                   forward_fn, features_fn):
    """MOON (Li et al., CVPR'21) local step: CE + model-contrastive loss
    pulling representations toward the global model and away from the
    device's previous local model.  ``forward_fn``/``features_fn`` come from
    the bound :class:`repro.fl.tasks.FLTask` (static: stable function
    attributes, so re-resolving a task reuses the jit cache)."""

    def loss_fn(p):
        logits = forward_fn(p, batch["images"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        ce = -jnp.take_along_axis(logp, batch["labels"][:, None], 1).mean()
        z = features_fn(p, batch["images"])
        zg = features_fn(batch["glob"], batch["images"])
        zp = features_fn(batch["prev"], batch["images"])

        def cos(a, b):
            return (a * b).sum(-1) / (jnp.linalg.norm(a, axis=-1)
                                      * jnp.linalg.norm(b, axis=-1) + 1e-8)

        sim_g = cos(z, zg) / tau
        sim_p = cos(z, zp) / tau
        lcon = -(sim_g - jnp.logaddexp(sim_g, sim_p)).mean()
        return ce + mu_con * lcon

    loss, grads = jax.value_and_grad(loss_fn)(params)
    return jax.tree.map(lambda p, g: p - lr * g, params, grads), loss


def moon_local_train(w_glob: Any, prev: Any, x, y, *, epochs: int,
                     batch_size: int, lr: float, rng: np.random.RandomState,
                     forward_fn: Callable, features_fn: Callable) -> Any:
    """MOON device-side update: E epochs of `_moon_sgd_step` minibatches.
    Shared by the legacy simulator and the engine's MoonStrategy so the two
    backends cannot drift apart.  Callers pass the bound task's
    ``forward``/``features`` (MOON needs a representation head; tasks
    without one cannot run this baseline)."""
    if forward_fn is None or features_fn is None:
        raise ValueError(
            "MOON's model-contrastive term needs the task's forward and "
            "features heads (FLTask.forward / FLTask.features)")
    params = w_glob
    for _ in range(epochs):
        order = rng.permutation(len(y))
        for s in range(0, len(y) - batch_size + 1, batch_size):
            sel = order[s:s + batch_size]
            batch = {"images": jnp.asarray(x[sel]),
                     "labels": jnp.asarray(y[sel]),
                     "glob": w_glob, "prev": prev}
            params, _ = _moon_sgd_step(params, batch, lr,
                                       mu_con=1.0, tau=0.5,
                                       forward_fn=forward_fn,
                                       features_fn=features_fn)
    return params


@dataclasses.dataclass
class TierSpec:
    """One heterogeneity tier: a fraction of the fleet with scaled compute
    speed (multiplies the shifted-exponential coefficient a_k; >1 = slower)
    and scaled link bandwidth (multiplies both directions' rates;
    <1 = slower links)."""
    fraction: float
    compute_scale: float = 1.0
    bandwidth_scale: float = 1.0
    name: str = ""


def tier_assignment(n_devices: int,
                    tiers: Optional[List[TierSpec]]) -> np.ndarray:
    """Contiguous deterministic tier indices by device id: tier ``i`` covers
    the next ``round(fraction_i * n)`` devices and the last tier absorbs the
    remainder.  Shared by ``DeviceRegistry.apply_tiers`` (latency scaling)
    and the codec policies (``repro.fl.policies``), so the latency model and
    per-device codec choice always agree on who sits in which tier."""
    tier = np.zeros(n_devices, np.int64)
    if not tiers:
        return tier
    start = 0
    for i, t in enumerate(tiers):
        stop = n_devices if i == len(tiers) - 1 else min(
            n_devices, start + int(round(t.fraction * n_devices)))
        tier[start:stop] = i
        start = stop
    return tier


@dataclasses.dataclass
class ScenarioConfig:
    """Scenario-injection knobs.  ``FLEngine`` consumes all of them; the
    legacy ``FLSimulator`` applies only ``tiers`` (latency scaling + the
    tier-aware codec policies) and ignores the failure knobs.  All
    randomness is drawn from a dedicated scenario RNG so that an all-zero
    ScenarioConfig leaves the engine's event stream bit-identical to the
    no-scenario run.

    * ``dropout_prob``: per-task probability the device leaves the fleet
      mid-round (permanent); its slot is freed and re-dispatched.
      Engine-only.
    * ``failure_prob``: per-task probability of a transient mid-round crash;
      the device retries after ``retry_backoff`` simulated seconds.
      Engine-only.
    * ``retry_backoff``: simulated seconds before a transiently-failed
      device re-requests work.
    * ``tiers``: heterogeneous compute/bandwidth ``TierSpec`` tiers assigned
      contiguously by device index according to each tier's ``fraction``
      (see ``tier_assignment``); also the tier structure the ``tier_aware``
      codec policy adapts to.
    """
    dropout_prob: float = 0.0
    failure_prob: float = 0.0
    retry_backoff: float = 1.0
    tiers: Optional[List[TierSpec]] = None

    @property
    def active(self) -> bool:
        return (self.dropout_prob > 0.0 or self.failure_prob > 0.0
                or bool(self.tiers))


@dataclasses.dataclass
class SimConfig:
    """One config object for both simulator backends — every knob, in one
    place (the README's configuration table is generated from this list):

    **Protocol & model**

    * ``method`` — protocol name from ``repro.fl.protocols.STRATEGIES``:
      the TEA-Fed family (``tea`` uncompressed, ``teas`` sparsify-only,
      ``teaq`` quantize-only, ``teastatic`` both static, ``teasq`` the full
      Alg. 5 schedule), async baselines (``fedasync``, ``port``,
      ``asofed``), and synchronous baselines (``fedavg``, ``moon``).
    * ``task`` — model family under training, from ``repro.fl.tasks.TASKS``
      (``fmnist_cnn`` = the paper's §5.1 CNN; ``transformer_lm``,
      ``fmnist_mlp`` — any registered FLTask trains under any protocol).
    * ``n_devices`` — fleet size N.

    **Server (Algs. 1-2)**

    * ``c_fraction`` — admission gate: at most ``ceil(N * C)`` devices train
      concurrently (Alg. 1).
    * ``gamma`` — aggregation cache fraction: a round completes after
      ``ceil(N * gamma)`` uploads (Alg. 2, Eq. 6).
    * ``alpha`` — server mixing rate of the cached aggregate (Eq. 10); also
      the async baselines' base mixing weight.
    * ``a`` — staleness-decay exponent (Eq. 9).
    * ``max_staleness`` — FedAsync staleness cap in its poly decay.

    **Device-side local training (Alg. 1, Eq. 5)**

    * ``mu`` — proximal term weight; ``epochs``/``batch_size``/``lr`` — the
      local prox-SGD loop.
    * ``devices_per_round`` — synchronous (FedAvg/MOON) cohort size.

    **Wire compression (Algs. 3-5)**

    * ``p_s`` — kept fraction under Top-K sparsification (1.0 = keep all).
    * ``p_q`` — quantization bit width (32 = no quantization).
    * ``schedule`` — optional Alg. 5 decay ``CompressionSchedule``;
      overrides the static point for ``teasq``.
    * ``codec`` — wire codec family (``repro.core.codecs.CODECS``):
      ``dense`` = the Algs. 3-4 reference codec, ``packed`` = the real
      bit-packed stream (docs/WIRE_FORMAT.md), ``threshold`` = the in-graph
      approximate channel, ``identity`` = compression off.  The
      uncompressed (p_s>=1, p_q>=32) point short-circuits to identity for
      every family.
    * ``codec_policy`` — per-device codec policy
      (``repro.fl.policies.POLICIES``): ``static`` (default — the
      protocol's own global operating point, byte-identical to the
      pre-policy behavior), ``tier_aware`` (slower-bandwidth tiers get more
      aggressive points, from ``tier_points`` or log2-derived notches), or
      ``staleness_aware`` (chronically stale devices get extra compression
      notches).
    * ``tier_points`` — optional explicit per-tier ``(p_s, p_q)`` list for
      the ``tier_aware`` policy, e.g. the output of the per-tier Alg. 5
      search ``profile_compression(..., tiers=...)``; index i maps to
      ``scenario.tiers[i]``.

    **Latency model (§3.1)**

    * ``wireless`` — cell geometry/power (``WirelessConfig``).
    * ``compute`` — shifted-exponential compute latency (``ComputeConfig``).

    **Infrastructure**

    * ``seed`` — the single RNG seed behind data, latency draws, and
      protocol randomness (fixed seed = bit-reproducible history).
    * ``scheduler`` — engine-only event-loop implementation: ``"heap"``
      (the reference one-event-at-a-time ``heapq`` loop) or ``"batched"``
      (``repro.fl.engine.BatchedEngine`` — resident per-device next-event
      arrays with vectorized batch selection; bit-identical histories, an
      order of magnitude cheaper per task at 10^4-10^5 devices).  The
      legacy ``FLSimulator`` ignores it.
    * ``cohort_size`` — engine-only: > 0 switches ``FLEngine`` to the
      vectorized cohort trainer (deferred training, one jitted call per
      padded cohort); the legacy ``FLSimulator`` ignores it.
    * ``cohort_channel_iters`` — threshold binary-search iterations of the
      in-graph channel the cohort path fuses.
    * ``handler_mode`` — batched-scheduler-only event *processing* mode:
      ``"serial"`` (default) falls each selected event through the scalar
      ``FLEngine`` handlers — bit-identical to the heap scheduler and
      pinned against ``tests/data/pinned_histories.json``.  ``"wave"``
      processes maximal same-kind event runs as arrays (vectorized Alg. 1
      admission gate, one ``DeviceRegistry.round_latency_batch`` draw per
      grant wave, fused Eqs. 6-10 arrival aggregation) under a documented
      *relaxed* parity contract: the same protocol decisions in the same
      event order, but RNG draws batched per wave and assigned in
      device-index order rather than heap-pop order, aggregation reduced
      via a stacked kernel, and same-``now`` drains applied once per wave.
      See the ``repro.fl.engine`` module docstring for the exact contract.
      Requires ``scheduler="batched"``; the heap scheduler rejects it.
    * ``server`` — engine-only server backend from
      ``repro.core.server.SERVERS``: ``"single"`` (default —
      ``TeasqServer``, the bit-pinned single-host reference) or
      ``"sharded"`` (``ShardedTeasqServer`` — the Eqs. 6-10 cache
      reduction runs as a ``shard_map`` over a 1-D mesh of local devices,
      e.g. host devices under
      ``XLA_FLAGS=--xla_force_host_platform_device_count=N``; on a
      single-device process it degenerates to the exact ``"single"``
      path).  The legacy ``FLSimulator`` ignores it.
    * ``server_shards`` — mesh width for ``server="sharded"`` (0 = use
      every local device; more than the process has raises).
    * ``scenario`` — ``ScenarioConfig`` injection (dropout / transient
      failure / heterogeneity tiers); see its docstring for which backend
      consumes what.
    """

    method: str = "teasq"
    task: str = "fmnist_cnn"
    n_devices: int = 100
    c_fraction: float = 0.1
    gamma: float = 0.1
    alpha: float = 0.6
    a: float = 0.5
    mu: float = 0.01
    epochs: int = 2
    batch_size: int = 40
    lr: float = 0.08
    # compression (used by teas/teaq/teastatic/teasq)
    p_s: float = 1.0
    p_q: int = 32
    schedule: Optional[CompressionSchedule] = None
    codec: str = "dense"
    # per-device adaptive codec policy (repro.fl.policies.POLICIES)
    codec_policy: str = "static"
    tier_points: Optional[List[Tuple[float, int]]] = None
    # latency model
    wireless: WirelessConfig = dataclasses.field(default_factory=WirelessConfig)
    compute: ComputeConfig = dataclasses.field(default_factory=ComputeConfig)
    # fedavg / fedasync
    devices_per_round: int = 10
    max_staleness: int = 4
    seed: int = 0
    # engine-only knobs; see class docstring
    scheduler: str = "heap"
    cohort_size: int = 0
    cohort_channel_iters: int = 12   # threshold binary-search iterations
    handler_mode: str = "serial"     # "serial" | "wave" (batched only)
    server: str = "single"           # repro.core.server.SERVERS backend
    server_shards: int = 0           # sharded-server mesh width (0 = all)
    scenario: Optional[ScenarioConfig] = None


@dataclasses.dataclass
class LogEntry:
    time: float
    round: int
    accuracy: float
    bytes_up: int
    bytes_down: int
    max_model_bytes_up: int
    max_model_bytes_down: int


class FLSimulator:
    def __init__(self, data: Dict[str, np.ndarray],
                 partitions: List[np.ndarray], w_init: Any, cfg: SimConfig):
        self.cfg = cfg
        self.data = data
        self.partitions = partitions
        self.rng = np.random.RandomState(cfg.seed)
        n = cfg.n_devices
        assert len(partitions) == n
        # the engine's DeviceRegistry draws rates then a_k in exactly this
        # simulator's historical order, so sharing it keeps bit-parity while
        # giving the legacy backend the same tier scaling (lazy import:
        # engine imports us)
        from repro.fl.engine import DeviceRegistry
        self.devices = DeviceRegistry(cfg, self.rng)
        if cfg.scenario is not None and cfg.scenario.tiers:
            self.devices.apply_tiers(cfg.scenario.tiers)
        self.server = TeasqServer(w_init, ServerConfig(
            n, cfg.c_fraction, cfg.gamma, cfg.alpha, cfg.a))
        self.bytes_up = 0
        self.bytes_down = 0
        self.max_up = 0
        self.max_down = 0
        self.prev_local: Dict[int, Any] = {}   # MOON: per-device prev model
        self.task = get_task(cfg.task)
        self._eval = jax.jit(self.task.eval_metric)
        self.history: List[LogEntry] = []
        # the codec seam is shared with the engine: the bound strategy's
        # channel_for(t, device_id) answers "which wire codec does a round-t
        # dispatch to device k use" for both simulators (lazy import:
        # protocols imports us)
        from repro.fl.protocols import make_strategy
        self.strategy = make_strategy(cfg.method, cfg)

    # ------------------------------------------------------------------
    def _train_device(self, k: int, w: Any) -> Tuple[Any, int]:
        idx = self.partitions[k]
        x, y = self.data["x_train"][idx], self.data["y_train"][idx]
        if self.cfg.method == "moon":
            return self._train_device_moon(k, w, x, y), len(idx)
        w_new, _, steps = local_update(
            w, x, y, self.task.loss, epochs=self.cfg.epochs,
            batch_size=self.cfg.batch_size, lr=self.cfg.lr, mu=self.cfg.mu,
            rng=self.rng)
        return w_new, len(idx)

    def _train_device_moon(self, k: int, w_glob: Any, x, y) -> Any:
        prev = self.prev_local.get(k, w_glob)
        params = moon_local_train(w_glob, prev, x, y, epochs=self.cfg.epochs,
                                  batch_size=self.cfg.batch_size,
                                  lr=self.cfg.lr, rng=self.rng,
                                  forward_fn=self.task.forward,
                                  features_fn=self.task.features)
        self.prev_local[k] = params
        return params

    def _round_latency(self, k: int, bits_down: float, bits_up: float,
                       n_batches: int) -> Tuple[float, float, float]:
        return self.devices.round_latency(k, bits_down, bits_up, n_batches,
                                          self.rng)

    def evaluate(self) -> float:
        xs, ys = self.data["x_test"], self.data["y_test"]
        accs = []
        for s in range(0, len(ys), 2000):
            accs.append(float(self._eval(self.server.w,
                                         jnp.asarray(xs[s:s + 2000]),
                                         jnp.asarray(ys[s:s + 2000]))))
        return float(np.mean(accs))

    def _log(self, time: float):
        self.history.append(LogEntry(
            time, self.server.t, self.evaluate(), self.bytes_up,
            self.bytes_down, self.max_up, self.max_down))

    # ------------------------------------------------------------------
    def run(self, time_budget: float = 300.0, max_rounds: int = 10 ** 9,
            eval_every: int = 1) -> List[LogEntry]:
        if self.cfg.method in ("fedavg", "moon"):
            return self._run_fedavg(time_budget, max_rounds, eval_every)
        return self._run_async(time_budget, max_rounds, eval_every)

    def _async_alpha(self, staleness: int) -> float:
        """Per-method immediate-update mixing weight (async baselines)."""
        cfg = self.cfg
        if cfg.method == "port":       # unbounded staleness, harder decay
            return cfg.alpha * (staleness + 1.0) ** -1.0
        if cfg.method == "asofed":     # linear decay
            return cfg.alpha / (1.0 + staleness)
        stale = min(staleness, cfg.max_staleness)   # fedasync: capped poly
        return cfg.alpha * float(staleness_weight(stale, cfg.a))

    # -- asynchronous protocols (teasq family + fedasync) ----------------
    def _run_async(self, time_budget: float, max_rounds: int,
                   eval_every: int) -> List[LogEntry]:
        cfg = self.cfg
        events: List[Tuple[float, int, str, int, Any, int]] = []
        seq = 0

        def push(t, kind, k, payload=None, h=0):
            nonlocal seq
            heapq.heappush(events, (t, seq, kind, k, payload, h))
            seq += 1

        waiting: List[int] = []
        for k in range(cfg.n_devices):
            push(self.rng.uniform(0, 0.05), "request", k)

        self._log(0.0)
        fedasync = cfg.method in ("fedasync", "port", "asofed")

        now = 0.0   # the heap can be empty (n_devices=0) or the first pop
        while events:  # can exceed time_budget; the final log still needs now
            now, _, kind, k, payload, h = heapq.heappop(events)
            if now > time_budget or self.server.t >= max_rounds:
                break
            if kind == "request":
                grant = self.server.try_dispatch()
                if grant is None:
                    waiting.append(k)
                    continue
                w_t, t0 = grant
                codec = self.strategy.channel_for(t0, device_id=k)
                w_recv, nbytes_down = codec.roundtrip(w_t, rng=self.rng)
                self.bytes_down += nbytes_down
                self.max_down = max(self.max_down, nbytes_down)
                w_local, n_k = self._train_device(k, w_recv)
                w_up, nbytes_up = codec.roundtrip(w_local, rng=self.rng)
                self.bytes_up += nbytes_up
                self.max_up = max(self.max_up, nbytes_up)
                n_batches = max(1, n_k // cfg.batch_size)
                dl, cp, ul = self._round_latency(
                    k, nbytes_down * 8, nbytes_up * 8, n_batches)
                push(now + dl + cp + ul, "arrival", k, (w_up, n_k), t0)
            else:  # arrival
                w_local, n_k = payload
                # feed the codec policy's per-device staleness estimator
                # (no-op for the static policy; draws no RNG)
                self.strategy.policy.observe_arrival(
                    k, max(0, self.server.t - h))
                if fedasync:
                    self.server.active = max(0, self.server.active - 1)
                    a_t = self._async_alpha(self.server.t - h)
                    self.server.w = jax.tree.map(
                        lambda wl, wg: a_t * wl + (1 - a_t) * wg,
                        w_local, self.server.w)
                    self.server.t += 1
                    done_round = True
                else:
                    done_round = self.server.receive(w_local, h, n_k)
                if done_round and self.server.t % eval_every == 0:
                    self._log(now)
                push(now, "request", k)
                # FIFO-equivalent to re-pushing the whole queue, without the
                # O(waiting) event churn per freed slot
                free = self.server.cfg.max_parallel - self.server.active
                for _ in range(min(free, len(waiting))):
                    push(now, "request", waiting.pop(0))
        self._log(min(now, time_budget))
        return self.history

    # -- synchronous FedAvg ----------------------------------------------
    def _run_fedavg(self, time_budget: float, max_rounds: int,
                    eval_every: int) -> List[LogEntry]:
        cfg = self.cfg
        now = 0.0
        self._log(now)
        per_round = min(cfg.devices_per_round, cfg.n_devices)
        identity = IdentityCodec()       # FedAvg/MOON ship dense f32
        while now < time_budget and self.server.t < max_rounds:
            sel = self.rng.choice(cfg.n_devices, per_round, replace=False)
            updates, weights, latencies = [], [], []
            for k in sel:
                nbytes = identity.wire_bytes(self.server.w)
                self.bytes_down += nbytes
                self.max_down = max(self.max_down, nbytes)
                w_local, n_k = self._train_device(k, self.server.w)
                self.bytes_up += nbytes
                self.max_up = max(self.max_up, nbytes)
                n_batches = max(1, n_k // cfg.batch_size)
                dl, cp, ul = self._round_latency(k, nbytes * 8, nbytes * 8,
                                                 n_batches)
                latencies.append(dl + cp + ul)
                updates.append(w_local)
                weights.append(n_k)
            wts = np.asarray(weights, np.float32)
            wts /= wts.sum()
            self.server.w = jax.tree.map(
                lambda *ls: sum(w * l for w, l in zip(wts, ls)), *updates)
            self.server.t += 1
            now += max(latencies)        # straggler-bound synchronous round
            if self.server.t % eval_every == 0:
                self._log(now)
        return self.history
