"""Layered event-driven FL engine: scheduler, device registry, channel
accounting, pluggable protocol strategies, vectorized cohort execution.

Mapping to the paper (TEASQ-Fed, Algs. 1-2):

* **Alg. 1, server side (Distributor)** — ``FLEngine._handle_request``:
  pops a device task request off the virtual-clock event heap and admission-
  controls it through ``TeasqServer.try_dispatch`` (the C-fraction gate,
  P < ceil(N*C)); rejected requests park in the ``waiting`` queue.
* **Alg. 1, device side (local prox-SGD, Eq. 5)** — the trainer layer:
  ``SerialTrainer`` runs ``repro.core.client.local_update`` per device
  (bit-identical to the legacy ``FLSimulator``); ``CohortTrainer`` defers
  training and executes whole cohorts of concurrently-training devices in a
  single jitted scan over the bound task's vectorized ``cohort_loss``
  (``repro.fl.tasks.FLTask`` — the einsum-formulated CNN for the default
  ``fmnist_cnn`` task), one compiled program per padded cohort bucket.
  Which model family trains is ``SimConfig.task``; the engine never touches
  model internals beyond the task object.
* **Algs. 3-4 (wire compression)** — the codec layer
  (``repro.core.codecs``): every dispatch asks the bound strategy for a
  :class:`~repro.core.codecs.Codec` via ``channel_for(t, device_id=k)``,
  which routes the protocol's global (p_s, p_q) point through the bound
  :class:`~repro.fl.policies.CodecPolicy` (``SimConfig.codec_policy`` —
  ``static`` is device-blind, ``tier_aware``/``staleness_aware`` compress
  per device, with per-tier byte totals in ``ChannelMeter``); the serial path
  runs ``codec.roundtrip`` (the faithful reference codec by default, the
  real bit-packed stream with ``SimConfig.codec="packed"``) while the
  cohort path fuses ``ThresholdGraphCodec.apply_tree`` into its jitted scan
  and meters bytes shape-only with ``codec.wire_bytes`` (the packed
  format's size is value-independent, so arrivals can be scheduled before
  training runs).
* **Alg. 2 (Receiver/Updater, Eqs. 6-10)** — ``FLEngine._handle_arrival``
  delegates to the bound :class:`~repro.fl.protocols.ProtocolStrategy`:
  the TEA/TEASQ family feeds ``TeasqServer.receive`` (cached
  staleness-weighted aggregation); FedAsync/PORT/ASO-Fed mix immediately;
  FedAvg/MOON run the synchronous straggler-bound loop instead.

On top sits the scenario-injection layer (``ScenarioConfig``): per-device
dropout, transient mid-round failure with task re-dispatch to the waiting
queue, and heterogeneous compute/bandwidth tiers.  Scenario randomness comes
from a dedicated RNG stream, so an inactive scenario leaves the event stream
bit-identical to the legacy simulator — which is what the fixed-seed parity
suite (tests/test_engine_parity.py) pins down.

Two interchangeable schedulers drive the Alg. 1-2 event loop
(``SimConfig.scheduler``, registry :data:`SCHEDULERS`):

* ``"heap"`` — :class:`FLEngine`: the reference one-``heappop``-at-a-time
  loop, kept untouched as the parity oracle.
* ``"batched"`` — :class:`BatchedEngine`: per-device next-event state lives
  in resident arrays (:class:`EventTable` on :class:`DeviceRegistry`) and
  the next K events are selected in one fused numpy call, preserving the
  heap's exact ``(time, seq)`` order — bit-identical histories at an
  order-of-magnitude lower per-task dispatch cost on 10^4-10^5-device
  fleets (tests/test_batched_engine.py pins the parity,
  ``python -m benchmarks.engine_scale --scheduler batched`` the scale).
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.client import local_update
from repro.core.codecs import Codec, IdentityCodec, ThresholdGraphCodec
from repro.core.latency import (comm_latency, comm_latency_batch,
                                device_rates, sample_compute_latency,
                                sample_compute_latency_batch)
from repro.core.server import ServerConfig, TeasqServer, make_server
from repro.fl.simulator import (LogEntry, ScenarioConfig, SimConfig,
                                tier_assignment)
from repro.fl.tasks import get_task


# ----------------------------------------------------------------------
# Device registry + channel accounting
# ----------------------------------------------------------------------
class DeviceRegistry:
    """Per-device simulation state: link rates, compute coefficients, tier
    assignment, and liveness.  Draws from the engine RNG in exactly the
    legacy ``FLSimulator.__init__`` order (rates, then a_k)."""

    def __init__(self, cfg: SimConfig, rng: np.random.RandomState):
        n = cfg.n_devices
        self.cfg = cfg
        self.down_rates, self.up_rates = device_rates(n, cfg.wireless, rng)
        self.a_k = rng.uniform(cfg.compute.a_min, cfg.compute.a_max, n)
        self.phi_k = np.full(n, cfg.compute.phi)
        self.alive = np.ones(n, bool)
        self.tier = np.zeros(n, np.int64)
        self.events: Optional[EventTable] = None   # batched scheduler only

    def event_table(self) -> "EventTable":
        """The resident per-device next-event arrays (allocated on first
        use — only the batched scheduler needs them)."""
        if self.events is None:
            self.events = EventTable(len(self.alive))
        return self.events

    def apply_tiers(self, tiers) -> None:
        """Scale latency per tier under the shared contiguous assignment
        (``repro.fl.simulator.tier_assignment`` — the same map the codec
        policies use, so latency and codec choice agree per device)."""
        self.tier = tier_assignment(len(self.alive), tiers)
        for i, t in enumerate(tiers):
            sel = self.tier == i
            self.a_k[sel] *= t.compute_scale
            self.down_rates[sel] *= t.bandwidth_scale
            self.up_rates[sel] *= t.bandwidth_scale

    def round_latency(self, k: int, bits_down: float, bits_up: float,
                      n_batches: int, rng: np.random.RandomState
                      ) -> Tuple[float, float, float]:
        cfg = self.cfg
        dl = comm_latency(bits_down, self.down_rates[k])
        ul = comm_latency(bits_up, self.up_rates[k])
        cp = sample_compute_latency(self.a_k[k], self.phi_k[k],
                                    tau_b=n_batches * cfg.epochs
                                    * 0.002 * cfg.batch_size, rng=rng)
        return dl, cp, ul

    def round_latency_batch(self, ks: np.ndarray, bits_down, bits_up,
                            n_batches: np.ndarray,
                            rng: np.random.RandomState
                            ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``round_latency`` over a whole grant wave: same elementwise
        float64 arithmetic, ONE ``rng.exponential(size=G)`` draw for the
        compute latencies.  Wave callers pass ``ks`` sorted ascending, so
        draw i belongs to the i-th lowest device id of the wave — the
        documented ``handler_mode="wave"`` draw order (heap-pop order is
        what the serial path consumes)."""
        cfg = self.cfg
        dl = comm_latency_batch(bits_down, self.down_rates[ks])
        ul = comm_latency_batch(bits_up, self.up_rates[ks])
        tau_b = (np.asarray(n_batches, np.float64) * cfg.epochs
                 * 0.002 * cfg.batch_size)
        cp = sample_compute_latency_batch(self.a_k[ks], self.phi_k[ks],
                                          tau_b, rng)
        return dl, cp, ul


# Event kinds, shared by both schedulers: the heap path stores the name in
# its event tuples, the batched path stores the id in its resident arrays.
KIND_NAMES = ("request", "arrival", "failure")
KIND_IDS = {name: i for i, name in enumerate(KIND_NAMES)}


class EventTable:
    """Resident next-event state for the batched scheduler, one slot per
    device.  The engine's event loop maintains an invariant the heap never
    exploits: every device has AT MOST ONE outstanding event at any time
    (its pending request, its in-flight arrival, or a scheduled
    failure/retry) and events are never cancelled — a device parked in the
    waiting queue or dead simply has no event.  The device id is therefore
    a perfect slot key, and the entire event queue collapses into aligned
    per-device arrays (``time`` is +inf while a slot is empty).

    ``select_batch`` is the scheduler's fused step: one ``np.partition``
    over the times plus one ``np.lexsort`` picks the next <= ``k_max``
    events in exact ``(time, seq)`` heap order.  Ties at the k-th smallest
    time are all included, so a batch boundary can never split — and hence
    never reorder — a group of same-time events."""

    def __init__(self, n: int):
        self.time = np.full(n, np.inf)
        self.seq = np.zeros(n, np.int64)
        self.kind = np.zeros(n, np.int8)
        self.h = np.zeros(n, np.int64)
        # which FL job an event belongs to: 0 for the single-task engines,
        # the task index (or -1 = assign-on-handling) under a multi-task
        # fleet (repro.fl.fleet) — carried through select_batch gathers
        # exactly like ``h``
        self.task = np.zeros(n, np.int32)
        self.payload: List[Any] = [None] * n

    def put(self, k: int, t: float, seq: int, kind: str, payload: Any,
            h: int, task: int = 0) -> None:
        assert self.time[k] == np.inf, \
            f"device {k} already has a scheduled event"
        self.time[k] = t
        self.seq[k] = seq
        self.kind[k] = KIND_IDS[kind]
        self.h[k] = h
        self.task[k] = task
        self.payload[k] = payload

    def clear(self, k: int) -> None:
        self.time[k] = np.inf
        self.payload[k] = None

    def put_wave(self, ks: np.ndarray, ts: np.ndarray, seqs: np.ndarray,
                 kind: str, payloads, h, task: int = 0) -> None:
        """Vectorized ``put`` for a whole wave of same-kind events — one
        scatter per array instead of G scalar slot writes.  ``h``/``task``
        are scalars (a wave shares its dispatch round and job id)."""
        assert np.all(self.time[ks] == np.inf), \
            "a wave member already has a scheduled event"
        self.time[ks] = ts
        self.seq[ks] = seqs
        self.kind[ks] = KIND_IDS[kind]
        self.h[ks] = h
        self.task[ks] = task
        if payloads is None:
            return
        pl = self.payload
        for k, p in zip(ks.tolist(), payloads):
            pl[k] = p

    def clear_wave(self, ks: np.ndarray) -> None:
        self.time[ks] = np.inf
        pl = self.payload
        for k in ks.tolist():
            pl[k] = None

    def select_batch(self, k_max: int) -> np.ndarray:
        """Device ids of the next <= ``k_max`` scheduled events (plus any
        events tied with the k-th time), in global ``(time, seq)`` order."""
        times = self.time
        finite = times < np.inf
        n_live = int(finite.sum())
        if n_live == 0:
            return np.empty(0, np.int64)
        if n_live > k_max:
            kth = np.partition(times, k_max - 1)[k_max - 1]
            cand = np.flatnonzero(times <= kth)
        else:
            cand = np.flatnonzero(finite)
        return cand[np.lexsort((self.seq[cand], times[cand]))]


class _FifoWaiting:
    """FIFO waiting queue with O(1) pops — call-compatible with the heap
    path's plain ``waiting`` list (``append`` / ``pop(0)`` / ``len``), but
    ``pop(0)`` advances a head cursor instead of shifting the buffer, which
    matters when 90% of a 10^5-device fleet parks behind the admission gate
    after the initial request burst."""

    __slots__ = ("_items", "_head")

    def __init__(self):
        self._items: List[int] = []
        self._head = 0

    def __len__(self) -> int:
        return len(self._items) - self._head

    def append(self, k: int) -> None:
        self._items.append(k)

    def pop(self, i: int = 0) -> int:
        assert i == 0, "the waiting queue is FIFO-only"
        k = self._items[self._head]
        self._head += 1
        self._maybe_compact()
        return k

    def extend(self, ks) -> None:
        """Park a whole wave behind the admission gate in one call."""
        self._items.extend(ks)

    def pop_many(self, g: int) -> List[int]:
        """Pop up to ``g`` waiters as ONE slice — the wave-grant drain.
        G scalar ``pop(0)`` calls advance the head cursor G times and can
        trigger G compaction checks; this is a single slice + one check."""
        h = self._head
        out = self._items[h:h + g]
        self._head = h + len(out)
        self._maybe_compact()
        return out

    def _maybe_compact(self) -> None:
        if self._head > 1024 and self._head * 2 >= len(self._items):
            del self._items[:self._head]
            self._head = 0


class ChannelMeter:
    """Cumulative and per-transfer-max byte accounting for both directions.

    Transfers are priced by the wire codec (``codec.wire_bytes`` — shape-only
    and value-independent for every registered codec) via the ``*_tree``
    helpers; the scalar ``down``/``up`` record an already-priced transfer
    (e.g. the serial path, which meters the actual encoded size).  When the
    caller knows the target device's heterogeneity tier it passes ``tier=``
    and the meter additionally keeps per-tier totals (``tier_up`` /
    ``tier_down``) — the accounting behind the tier-aware codec-policy
    acceptance numbers in results/engine_scale.json."""

    def __init__(self):
        self.bytes_up = 0
        self.bytes_down = 0
        self.max_up = 0
        self.max_down = 0
        self.tier_up: Dict[int, int] = {}
        self.tier_down: Dict[int, int] = {}

    def down(self, nbytes: int, tier: Optional[int] = None) -> None:
        self.bytes_down += nbytes
        self.max_down = max(self.max_down, nbytes)
        if tier is not None:
            self.tier_down[tier] = self.tier_down.get(tier, 0) + nbytes

    def up(self, nbytes: int, tier: Optional[int] = None) -> None:
        self.bytes_up += nbytes
        self.max_up = max(self.max_up, nbytes)
        if tier is not None:
            self.tier_up[tier] = self.tier_up.get(tier, 0) + nbytes

    def down_tree(self, codec: Codec, tree: Any,
                  tier: Optional[int] = None) -> int:
        nbytes = codec.wire_bytes(tree)
        self.down(nbytes, tier)
        return nbytes

    def up_tree(self, codec: Codec, tree: Any,
                tier: Optional[int] = None) -> int:
        nbytes = codec.wire_bytes(tree)
        self.up(nbytes, tier)
        return nbytes

    # -- wave accounting: one call per grant wave instead of G scalar
    # calls.  Integer-exact: the bincount accumulates int64 byte counts as
    # float64 (exact below 2^53, far above any simulated transfer volume)
    # and converts back per tier, so per-tier totals match G serial calls.
    def _wave(self, nbytes: np.ndarray, tiers: np.ndarray,
              tier_tot: Dict[int, int]) -> Tuple[int, int]:
        sums = np.bincount(tiers, weights=nbytes)
        for t in np.flatnonzero(sums).tolist():
            tier_tot[t] = tier_tot.get(t, 0) + int(sums[t])
        return int(nbytes.sum()), int(nbytes.max())

    def down_wave(self, nbytes: np.ndarray, tiers: np.ndarray) -> None:
        if not len(nbytes):
            return
        tot, mx = self._wave(nbytes, tiers, self.tier_down)
        self.bytes_down += tot
        self.max_down = max(self.max_down, mx)

    def up_wave(self, nbytes: np.ndarray, tiers: np.ndarray) -> None:
        if not len(nbytes):
            return
        tot, mx = self._wave(nbytes, tiers, self.tier_up)
        self.bytes_up += tot
        self.max_up = max(self.max_up, mx)


@dataclasses.dataclass
class EngineStats:
    dispatches: int = 0
    completions: int = 0
    dropouts: int = 0
    transient_failures: int = 0
    redispatched: int = 0
    flushes: int = 0
    flushed_tasks: int = 0
    completed_per_device: Optional[np.ndarray] = None


# ----------------------------------------------------------------------
# Trainers: serial (legacy-parity) and vectorized cohort
# ----------------------------------------------------------------------
class SerialTrainer:
    """Trains one device at grant time — the rng-order-exact legacy path."""

    deferred = False

    def __init__(self, engine: "FLEngine"):
        self.engine = engine

    def train(self, k: int, w: Any) -> Tuple[Any, int]:
        eng = self.engine
        idx = eng.partitions[k]
        x, y = eng.data["x_train"][idx], eng.data["y_train"][idx]
        w_new, _, _ = local_update(
            w, x, y, eng.task.loss, epochs=eng.cfg.epochs,
            batch_size=eng.cfg.batch_size, lr=eng.cfg.lr, mu=eng.cfg.mu,
            rng=eng.rng)
        return w_new, len(idx)


@dataclasses.dataclass
class PendingTask:
    """A granted-but-not-yet-trained task in the deferred cohort buffer."""
    k: int
    version: int          # index into the flush's global-model version list
    t0: int
    p_s: float
    p_q: int
    n_k: int
    bidx: np.ndarray      # (T, bs) minibatch sample indices
    result: Optional[Tuple[Any, int]] = None


@functools.partial(jax.jit,
                   static_argnames=("cohort_loss", "lr", "mu", "p_s", "p_q",
                                    "iters"))
def _cohort_round(w_versions, vidx, xs, ys, didx, bidx, valid, *,
                  cohort_loss, lr: float, mu: float, p_s: float, p_q: int,
                  iters: int):
    """One fused cohort round: down-channel (per model version), E epochs of
    prox-SGD for every device in the cohort (scan over steps, the task's
    vectorized ``cohort_loss``), up-channel.  Shapes: w_versions leaves
    (V, ...); vidx/didx (C,); xs/ys (N, n_max, ...); bidx (T, C, bs);
    valid (T, C).  ``cohort_loss`` is static (a stable FLTask attribute, so
    each task compiles once per bucket shape)."""

    channel = ThresholdGraphCodec(p_s, p_q, iters).apply_tree

    def step(params, sv):
        idx, v = sv                                   # (C, bs), (C,)
        # broadcast the (C, bs) gather over the sample feature axes, whatever
        # their rank (images (C, n, 28, 28, 1), token matrices (C, n, S), ...)
        inputs = jnp.take_along_axis(
            xd, idx.reshape(idx.shape + (1,) * (xd.ndim - 2)), axis=1)
        labs = jnp.take_along_axis(yd, idx, axis=1)
        grads = jax.grad(cohort_loss)(params, inputs, labs)

        def upd(p, g, a):
            vv = v.reshape((v.shape[0],) + (1,) * (p.ndim - 1))
            return p - vv * lr * (g + mu * (p - a))

        return jax.tree.map(upd, params, grads, w_recv), None

    # the named scopes mark the codec's and the scan's ops in the trace
    with jax.named_scope("codec_down"):
        w_recv_v = jax.vmap(channel)(w_versions)
        w_recv = jax.tree.map(lambda a: a[vidx], w_recv_v)
    with jax.named_scope("prox_sgd"):
        xd = xs[didx]
        yd = ys[didx]
        out, _ = jax.lax.scan(step, w_recv, (bidx, valid))
    with jax.named_scope("codec_up"):
        return jax.vmap(channel)(out)


@functools.partial(jax.jit, static_argnames=("p_s", "p_q", "iters"))
def _zero_step_round(w_versions, *, p_s: float, p_q: int, iters: int):
    """Wave-mode cohort fast path for groups with ZERO local steps (every
    member has n_k < batch_size, the dispatch-benchmark regime): with no
    SGD steps the up-channel input is exactly the down-channel output, so
    the cohort result depends only on the model VERSION — encode the V
    distinct versions twice (down then up) instead of running the C-wide
    ``_cohort_round`` (V ~= C / cache_size under the admission gate, a
    ~K-fold cut in channel work).  Per-task results are gathers of the
    (V, ...) output on the host side."""
    channel = ThresholdGraphCodec(p_s, p_q, iters).apply_tree

    def down_up(w):
        with jax.named_scope("codec_down"):
            w = channel(w)
        with jax.named_scope("codec_up"):
            return channel(w)

    return jax.vmap(down_up)(w_versions)


class CohortTrainer:
    """Deferred vectorized execution: granted tasks buffer up and whole
    cohorts train in one jitted call (padded to power-of-two buckets so jit's
    shape cache stays small).  Device data is pre-stacked once; minibatch
    permutations come from a dedicated RNG (the deferred path makes no
    bit-parity promise, only distributional equivalence)."""

    deferred = True

    def __init__(self, engine: "FLEngine", cohort_size: int,
                 channel_iters: int = 12):
        self.engine = engine
        self.cohort_size = max(1, cohort_size)
        self.channel_iters = channel_iters
        self.perm_rng = np.random.RandomState(engine.cfg.seed + 0x9E3779)
        self._serial = SerialTrainer(engine)   # sync-loop fallback
        self.pending: List[PendingTask] = []
        self._versions: List[Any] = []
        self._version_ids: Dict[int, int] = {}
        parts = engine.partitions
        n_max = max(len(idx) for idx in parts)
        x = engine.data["x_train"]
        xs = np.zeros((len(parts), n_max) + x.shape[1:], x.dtype)
        ys = np.zeros((len(parts), n_max), np.int32)
        for k, idx in enumerate(parts):
            xs[k, :len(idx)] = x[idx]
            ys[k, :len(idx)] = engine.data["y_train"][idx]
        self.xs = jnp.asarray(xs)
        self.ys = jnp.asarray(ys)
        # two padded-shape buckets: full cohorts and a small one for tail
        # flushes — each bucket costs one XLA compile of _cohort_round
        self.buckets = sorted({max(1, self.cohort_size // 4),
                               self.cohort_size})

    # -- sync-loop fallback -------------------------------------------------
    def train(self, k: int, w: Any) -> Tuple[Any, int]:
        return self._serial.train(k, w)

    # -- deferred protocol --------------------------------------------------
    def _version_of(self, w: Any) -> int:
        vid = self._version_ids.get(id(w))
        if vid is None:
            vid = len(self._versions)
            self._versions.append(w)       # keeps the ref alive => id stable
            self._version_ids[id(w)] = vid
        return vid

    def submit(self, k: int, w_t: Any, t0: int, p_s: float,
               p_q: int) -> PendingTask:
        cfg = self.engine.cfg
        n_k = len(self.engine.partitions[k])
        bs = cfg.batch_size
        steps = (n_k - bs) // bs + 1 if n_k >= bs else 0
        rows = []
        for _ in range(cfg.epochs):
            order = self.perm_rng.permutation(n_k)
            for s in range(steps):
                rows.append(order[s * bs:(s + 1) * bs])
        bidx = (np.asarray(rows, np.int32) if rows
                else np.zeros((0, bs), np.int32))
        task = PendingTask(k, self._version_of(w_t), t0, p_s, p_q, n_k, bidx)
        self.pending.append(task)
        if len(self.pending) >= self.cohort_size:
            self.flush()
        return task

    def result(self, task: PendingTask) -> Tuple[Any, int]:
        if task.result is None:
            self.flush()
        assert task.result is not None
        return task.result

    @staticmethod
    def _pad_pow2(n: int) -> int:
        p = 1
        while p < n:
            p *= 2
        return p

    def flush(self) -> None:
        tasks, self.pending = self.pending, []
        versions, self._versions = self._versions, []
        self._version_ids = {}
        if not tasks:
            return
        with spans.span("fl.flush", tasks=len(tasks)):
            groups: Dict[Tuple[float, int], List[PendingTask]] = {}
            for t in tasks:
                groups.setdefault((t.p_s, t.p_q), []).append(t)
            # pad the version axis to a power of two (repeat the first
            # version) so the jitted program's V dimension comes from a
            # small bucket set
            versions = versions + [versions[0]] * (
                self._pad_pow2(len(versions)) - len(versions))
            with spans.span("fl.flush.stage", nbytes=spans.nbytes(
                    versions, host_only=True) if spans.enabled() else 0):
                w_versions = jax.tree.map(lambda *ls: jnp.stack(ls),
                                          *versions)
            for (p_s, p_q), group in groups.items():
                self._flush_group(group, w_versions, p_s, p_q)
        self.engine.stats.flushes += 1
        self.engine.stats.flushed_tasks += len(tasks)

    def _flush_group(self, group: List[PendingTask], w_versions, p_s: float,
                     p_q: int) -> None:
        cfg = self.engine.cfg
        c = len(group)
        c_pad = next(b for b in self.buckets if b >= c) if \
            c <= self.buckets[-1] else c
        # pad the scan length to a power of two too (ragged partitions give
        # per-device step counts; valid=0 masks the padding) — otherwise
        # every distinct t_max recompiles the fused round
        t_max = max(t.bidx.shape[0] for t in group)
        t_max = self._pad_pow2(t_max) if t_max else 0
        if t_max == 0 and cfg.handler_mode == "wave":
            # zero local steps => the result is a pure function of the
            # version; gated to wave mode so the serial path keeps running
            # the exact pinned _cohort_round program
            program = "jit__zero_step_round"
            with spans.span("fl.flush.launch", program=program):
                w_up_v = _zero_step_round(w_versions, p_s=p_s, p_q=p_q,
                                          iters=self.channel_iters)
            w_np = self._to_host(w_up_v, program)
            for t in group:
                t.result = (jax.tree.map(lambda a, v=t.version: a[v], w_np),
                            t.n_k)
            return
        bs = cfg.batch_size
        bidx = np.zeros((c_pad, t_max, bs), np.int32)
        valid = np.zeros((c_pad, t_max), np.float32)
        vidx = np.zeros(c_pad, np.int32)
        didx = np.zeros(c_pad, np.int32)
        staged = (vidx, didx, bidx, valid)
        with spans.span("fl.flush.stage",
                        nbytes=sum(a.nbytes for a in staged)):
            for i, t in enumerate(group):
                ti = t.bidx.shape[0]
                bidx[i, :ti] = t.bidx
                valid[i, :ti] = 1.0
                vidx[i] = t.version
                didx[i] = t.k
            vidx_d, didx_d, bidx_d, valid_d = (
                jnp.asarray(vidx), jnp.asarray(didx),
                jnp.asarray(np.swapaxes(bidx, 0, 1)),
                jnp.asarray(np.swapaxes(valid, 0, 1)))
        program = "jit__cohort_round"
        with spans.span("fl.flush.launch", program=program):
            w_up = _cohort_round(
                w_versions, vidx_d, self.xs, self.ys, didx_d, bidx_d,
                valid_d, cohort_loss=self.engine.task.cohort_loss,
                lr=cfg.lr, mu=cfg.mu, p_s=p_s, p_q=p_q,
                iters=self.channel_iters)
        w_up_np = self._to_host(w_up, program)
        for i, t in enumerate(group):
            t.result = (jax.tree.map(lambda a, i=i: a[i], w_up_np), t.n_k)

    @staticmethod
    def _to_host(tree: Any, program: str) -> Any:
        """``tree`` (the result of ``program``) as numpy: one bulk
        device->host transfer per leaf, so that per-task results are free
        numpy views (a per-task jnp slice costs an eager dispatch, which
        dominated the flush at large N).  The wait for the device and the
        copy are spans of their own."""
        with spans.span("fl.flush.wait", program=program):
            jax.block_until_ready(tree)
        with spans.span("fl.flush.copy", nbytes=spans.nbytes(tree)
                        if spans.enabled() else 0):
            return jax.tree.map(np.asarray, tree)


# ----------------------------------------------------------------------
# Checkpoint helpers (engine + fleet state_dict/load_state)
# ----------------------------------------------------------------------
def _pack_rng(rng: np.random.RandomState) -> List[Any]:
    name, keys, pos, has_gauss, cached = rng.get_state()
    return [name, np.asarray(keys), int(pos), int(has_gauss), float(cached)]


def _load_rng(rng: np.random.RandomState, packed) -> None:
    rng.set_state((packed[0], np.asarray(packed[1], np.uint32),
                   int(packed[2]), int(packed[3]), float(packed[4])))


def _trees_equal(a: Any, b: Any) -> bool:
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ----------------------------------------------------------------------
# The engine
# ----------------------------------------------------------------------
class FLEngine:
    """Event-driven virtual-clock FL engine with pluggable protocol
    strategies.  Drop-in for the legacy ``FLSimulator``: with default knobs
    (no scenario, ``cohort_size=0``) it consumes the seeded RNG in the exact
    legacy order and reproduces its ``LogEntry`` history bit-for-bit."""

    supports_wave = False   # handler_mode="wave" needs the batched arrays

    def __init__(self, data: Dict[str, np.ndarray],
                 partitions: List[np.ndarray], w_init: Any, cfg: SimConfig,
                 strategy: Optional[Any] = None, *,
                 rng: Optional[np.random.RandomState] = None,
                 devices: Optional[DeviceRegistry] = None,
                 scenario_rng: Optional[np.random.RandomState] = None):
        """``rng`` / ``devices`` / ``scenario_rng`` let a multi-task fleet
        (``repro.fl.fleet.MultiTaskEngine``) share one seeded RNG stream and
        one :class:`DeviceRegistry` across several per-task engines; when a
        registry is injected the fleet owns tier application and the event
        loop, and this engine acts as a per-task runtime (its handlers are
        driven by the fleet's scheduler).  Standalone construction (the
        default) is unchanged and draws the RNG in the legacy order."""
        self.cfg = cfg
        self.data = data
        self.partitions = partitions
        self.shared_fleet = devices is not None
        self.rng = np.random.RandomState(cfg.seed) if rng is None else rng
        n = cfg.n_devices
        assert len(partitions) == n
        if cfg.handler_mode not in ("serial", "wave"):
            raise ValueError(
                f"unknown handler_mode {cfg.handler_mode!r}; "
                "expected 'serial' or 'wave'")
        if cfg.handler_mode == "wave" and not self.supports_wave:
            raise ValueError(
                "handler_mode='wave' needs the batched scheduler "
                "(SimConfig.scheduler='batched')")
        # per-device partition sizes, resident for vectorized n_batches
        self.part_sizes = np.asarray([len(p) for p in partitions], np.int64)
        self.devices = (DeviceRegistry(cfg, self.rng) if devices is None
                        else devices)
        self.server = make_server(cfg.server, w_init, ServerConfig(
            n, cfg.c_fraction, cfg.gamma, cfg.alpha, cfg.a),
            shards=cfg.server_shards)
        self.channel = ChannelMeter()
        self.prev_local: Dict[int, Any] = {}      # MOON per-device state
        self.task = get_task(cfg.task)
        self._eval = jax.jit(self.task.eval_metric)
        self.history: List[LogEntry] = []
        self.stats = EngineStats(completed_per_device=np.zeros(n, np.int64))
        self._treedef = jax.tree_util.tree_structure(w_init)

        if strategy is None:
            from repro.fl.protocols import make_strategy
            strategy = make_strategy(cfg.method, cfg)
        self.strategy = strategy

        self.scenario: Optional[ScenarioConfig] = cfg.scenario
        self.scenario_rng = (np.random.RandomState(
            (cfg.seed + 0x5CE7A710) % (2 ** 31))
            if scenario_rng is None else scenario_rng)
        if (not self.shared_fleet and self.scenario is not None
                and self.scenario.tiers):
            self.devices.apply_tiers(self.scenario.tiers)

        self.trainer = (CohortTrainer(self, cfg.cohort_size,
                                      cfg.cohort_channel_iters)
                        if cfg.cohort_size > 0 else SerialTrainer(self))

        # resumable-loop state (checkpoint/resume lives here: ``run`` picks
        # up exactly where a previous call stopped, and ``state_dict`` /
        # ``load_state`` serialize it — see the checkpoint section below)
        self._started = False
        self._now = 0.0
        self._seq = 0
        self._events: Optional[List[Tuple]] = None     # heap scheduler
        self._waiting: Optional[Any] = None
        self._tail_logged = False
        self._sync_now = 0.0

    # -- shared helpers ----------------------------------------------------
    def resolve_payload(self, payload: Any) -> Tuple[Any, int]:
        """(w_local, n_k) from either an eager tuple or a PendingTask."""
        if isinstance(payload, PendingTask):
            return self.trainer.result(payload)
        return payload

    def evaluate(self) -> float:
        xs, ys = self.data["x_test"], self.data["y_test"]
        accs = []
        with spans.span("fl.evaluate", nbytes=spans.nbytes(
                (xs, ys), host_only=True) if spans.enabled() else 0):
            for s in range(0, len(ys), 2000):
                accs.append(float(self._eval(self.server.w,
                                             jnp.asarray(xs[s:s + 2000]),
                                             jnp.asarray(ys[s:s + 2000]))))
        return float(np.mean(accs))

    def _log(self, time: float) -> None:
        self.history.append(LogEntry(
            time, self.server.t, self.evaluate(), self.channel.bytes_up,
            self.channel.bytes_down, self.channel.max_up,
            self.channel.max_down))

    # -- entry point -------------------------------------------------------
    def run(self, time_budget: float = 300.0, max_rounds: int = 10 ** 9,
            eval_every: int = 1) -> List[LogEntry]:
        with spans.span("fl.run"):
            if not self.strategy.event_driven:
                return self._run_sync(time_budget, max_rounds, eval_every)
            return self._run_async(time_budget, max_rounds, eval_every)

    # -- asynchronous event loop (Algs. 1-2) -------------------------------
    def _resume(self) -> None:
        """Drop the previous ``run`` call's trailing budget log so that
        ``run(t)`` + ``run(T)`` produces exactly ``run(T)``'s history — the
        invariant the checkpoint/resume bit-parity tests pin."""
        if self._tail_logged:
            self.history.pop()
            self._tail_logged = False

    def _push(self, t, kind, k, payload=None, h=0):
        heapq.heappush(self._events, (t, self._seq, kind, k, payload, h))
        self._seq += 1

    def _run_async(self, time_budget: float, max_rounds: int,
                   eval_every: int) -> List[LogEntry]:
        cfg = self.cfg
        self._resume()
        if not self._started:
            self._events = []
            self._waiting = []
            for k in range(cfg.n_devices):
                self._push(self.rng.uniform(0, 0.05), "request", k)
            self._log(0.0)
            self._started = True

        events, waiting, push = self._events, self._waiting, self._push
        now = self._now
        while events:
            # peek: a stop leaves the boundary event queued, so a later
            # ``run`` call (or a restored checkpoint) resumes exactly here;
            # ``now`` still advances to the boundary time, which is what the
            # pre-resume loop logged (it popped the event it then dropped)
            t_next = events[0][0]
            if t_next > time_budget or self.server.t >= max_rounds:
                now = t_next
                break
            now, _, kind, k, payload, h = heapq.heappop(events)
            if kind == "request":
                self._handle_request(now, k, push, waiting)
            elif kind == "failure":
                self._handle_failure(now, k, payload, push, waiting)
            else:
                self._handle_arrival(now, k, payload, h, eval_every, push,
                                     waiting)
        self._now = now
        self._log(min(now, time_budget))
        self._tail_logged = True
        return self.history

    def _drain_waiting(self, now, push, waiting) -> None:
        # re-issue at most free-slot many waiting requests: re-pushing the
        # whole queue is FIFO-equivalent (ungranted requests re-queue in
        # order) but costs O(waiting) events per freed slot — quadratic at
        # large N
        free = self.server.cfg.max_parallel - self.server.active
        for _ in range(min(free, len(waiting))):
            push(now, "request", waiting.pop(0))

    def _handle_request(self, now, k, push, waiting) -> None:
        cfg = self.cfg
        if not self.devices.alive[k]:
            return
        grant = self.server.try_dispatch()
        if grant is None:
            waiting.append(k)
            return
        self.stats.dispatches += 1
        w_t, t0 = grant
        codec = self.strategy.channel_for(t0, device_id=k)
        tier = int(self.devices.tier[k])

        if self.scenario is not None and self.scenario.active:
            scen = self.scenario
            u = self.scenario_rng.random_sample()
            if u < scen.dropout_prob + scen.failure_prob:
                mode = "dropout" if u < scen.dropout_prob else "transient"
                nbytes_down = self.channel.down_tree(codec, w_t, tier)
                n_k = len(self.partitions[k])
                n_batches = max(1, n_k // cfg.batch_size)
                dl, cp, _ = self.devices.round_latency(
                    k, nbytes_down * 8, 0.0, n_batches, self.scenario_rng)
                fail_at = now + self.scenario_rng.uniform(0.0, dl + cp)
                push(fail_at, "failure", k, mode)
                return

        if self.trainer.deferred:
            nbytes_down = self.channel.down_tree(codec, w_t, tier)
            task = self.trainer.submit(k, w_t, t0, codec.p_s, codec.p_q)
            # same tree shapes and (p_s, p_q) => nbytes_up == nbytes_down
            nbytes_up = self.channel.up_tree(codec, w_t, tier)
            n_batches = max(1, task.n_k // cfg.batch_size)
            dl, cp, ul = self.devices.round_latency(
                k, nbytes_down * 8, nbytes_up * 8, n_batches, self.rng)
            push(now + dl + cp + ul, "arrival", k, task, t0)
            return

        w_recv, nbytes_down = codec.roundtrip(w_t, rng=self.rng)
        self.channel.down(nbytes_down, tier)
        w_local, n_k = self.strategy.local_train(self, k, w_recv)
        w_up, nbytes_up = codec.roundtrip(w_local, rng=self.rng)
        self.channel.up(nbytes_up, tier)
        n_batches = max(1, n_k // cfg.batch_size)
        dl, cp, ul = self.devices.round_latency(
            k, nbytes_down * 8, nbytes_up * 8, n_batches, self.rng)
        push(now + dl + cp + ul, "arrival", k, (w_up, n_k), t0)

    def _handle_failure(self, now, k, mode, push, waiting) -> None:
        """Mid-round device loss: free the slot, re-dispatch the capacity to
        the waiting queue; transient failures retry after a backoff."""
        self.server.active = max(0, self.server.active - 1)
        if mode == "dropout":
            self.devices.alive[k] = False
            self.stats.dropouts += 1
        else:
            self.stats.transient_failures += 1
            push(now + self.scenario.retry_backoff, "request", k)
        if waiting:
            self.stats.redispatched += 1
        self._drain_waiting(now, push, waiting)

    def _handle_arrival(self, now, k, payload, h, eval_every, push,
                        waiting) -> None:
        # feed the codec policy's per-device staleness estimator (no-op for
        # the static policy; draws no RNG, so parity runs are untouched)
        self.strategy.policy.observe_arrival(k, max(0, self.server.t - h))
        done_round = self.strategy.on_arrival(self, now, k, payload, h)
        self.stats.completions += 1
        self.stats.completed_per_device[k] += 1
        if done_round and self.server.t % eval_every == 0:
            self._log(now)
        if self.devices.alive[k]:
            push(now, "request", k)
        self._drain_waiting(now, push, waiting)

    # -- synchronous loop (FedAvg / MOON) ----------------------------------
    def _run_sync(self, time_budget: float, max_rounds: int,
                  eval_every: int) -> List[LogEntry]:
        cfg = self.cfg
        now = self._sync_now
        if not self._started:
            self._log(now)
            self._started = True
        per_round = min(cfg.devices_per_round, cfg.n_devices)
        identity = IdentityCodec()       # FedAvg/MOON ship dense f32
        while now < time_budget and self.server.t < max_rounds:
            sel = self.rng.choice(cfg.n_devices, per_round, replace=False)
            updates, weights, latencies = [], [], []
            for k in sel:
                tier = int(self.devices.tier[k])
                nbytes = self.channel.down_tree(identity, self.server.w,
                                                tier)
                w_local, n_k = self.strategy.local_train(self, k,
                                                         self.server.w)
                self.channel.up(nbytes, tier)
                n_batches = max(1, n_k // cfg.batch_size)
                dl, cp, ul = self.devices.round_latency(
                    k, nbytes * 8, nbytes * 8, n_batches, self.rng)
                latencies.append(dl + cp + ul)
                updates.append(w_local)
                weights.append(n_k)
            self.server.w = self.strategy.aggregate(self, updates, weights)
            self.server.t += 1
            now += max(latencies)        # straggler-bound synchronous round
            if self.server.t % eval_every == 0:
                self._log(now)
        self._sync_now = now
        return self.history

    # -- checkpoint/resume -------------------------------------------------
    # Full-sim-state serialization.  Everything below produces / consumes a
    # plain nested structure of dicts, lists, scalars and numpy arrays —
    # exactly what ``repro.checkpoint.io.save_blob`` msgpacks.  Model
    # pytrees are stored as flat leaf lists and rebuilt against the engine's
    # own treedef (captured from ``w_init`` at construction), so a restored
    # engine must be built with the same (data, partitions, w_init, cfg).
    # ``PendingTask`` objects can be referenced both from the deferred
    # cohort buffer and from in-flight arrival events; a shared registry
    # (``reg = (id->index, list)``) preserves that object identity across
    # the roundtrip, which is what keeps resumed runs bit-identical.

    def _pack_tree(self, tree: Any) -> List[np.ndarray]:
        return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]

    def _unpack_tree(self, leaves) -> Any:
        return jax.tree_util.tree_unflatten(
            self._treedef, [np.asarray(l) for l in leaves])

    def _pack_payload(self, payload: Any, reg) -> List[Any]:
        idx, pts = reg
        if payload is None:
            return ["none"]
        if isinstance(payload, str):         # failure mode tag
            return ["str", payload]
        if isinstance(payload, PendingTask):
            i = idx.get(id(payload))
            if i is None:
                i = len(pts)
                idx[id(payload)] = i
                pts.append(payload)
            return ["pending", i]
        w_up, n_k = payload                  # eager (w_local, n_k) tuple
        return ["tree", self._pack_tree(w_up), int(n_k)]

    def _unpack_payload(self, packed, pts: List[PendingTask]) -> Any:
        tag = packed[0]
        if tag == "none":
            return None
        if tag == "str":
            return packed[1]
        if tag == "pending":
            return pts[int(packed[1])]
        return self._unpack_tree(packed[1]), int(packed[2])

    def _pack_pending(self, reg) -> List[Any]:
        return [[int(p.k), int(p.version), int(p.t0), float(p.p_s),
                 int(p.p_q), int(p.n_k), np.asarray(p.bidx),
                 None if p.result is None
                 else [self._pack_tree(p.result[0]), int(p.result[1])]]
                for p in reg[1]]

    def _unpack_pending(self, packed) -> List[PendingTask]:
        pts = []
        for k, version, t0, p_s, p_q, n_k, bidx, result in packed:
            p = PendingTask(int(k), int(version), int(t0), float(p_s),
                            int(p_q), int(n_k), np.asarray(bidx, np.int32))
            if result is not None:
                p.result = (self._unpack_tree(result[0]), int(result[1]))
            pts.append(p)
        return pts

    def _core_state(self, reg) -> Dict[str, Any]:
        """Per-task state: everything except the shared fleet pieces (RNG
        streams, DeviceRegistry, event queue) — a fleet saves those once."""
        srv, ch, st = self.server, self.channel, self.stats
        core = {
            "server": {"w": self._pack_tree(srv.w), "t": int(srv.t),
                       "active": int(srv.active),
                       "cache": [[self._pack_tree(w), int(h), int(n)]
                                 for w, h, n in srv.cache]},
            "strategy": self.strategy.state_dict(),
            "prev_local": [[int(k), self._pack_tree(w)]
                           for k, w in self.prev_local.items()],
            "channel": {"bytes_up": int(ch.bytes_up),
                        "bytes_down": int(ch.bytes_down),
                        "max_up": int(ch.max_up),
                        "max_down": int(ch.max_down),
                        "tier_up": [[int(t), int(b)]
                                    for t, b in ch.tier_up.items()],
                        "tier_down": [[int(t), int(b)]
                                      for t, b in ch.tier_down.items()]},
            "history": [[float(e.time), int(e.round), float(e.accuracy),
                         int(e.bytes_up), int(e.bytes_down),
                         int(e.max_model_bytes_up),
                         int(e.max_model_bytes_down)]
                        for e in self.history],
            "stats": {"dispatches": int(st.dispatches),
                      "completions": int(st.completions),
                      "dropouts": int(st.dropouts),
                      "transient_failures": int(st.transient_failures),
                      "redispatched": int(st.redispatched),
                      "flushes": int(st.flushes),
                      "flushed_tasks": int(st.flushed_tasks),
                      "completed_per_device":
                      np.asarray(st.completed_per_device)},
            "tail_logged": bool(self._tail_logged),
            "sync_now": float(self._sync_now),
            "trainer": None,
        }
        tr = self.trainer
        if isinstance(tr, CohortTrainer):
            idx, pts = reg
            refs = []
            for p in tr.pending:
                i = idx.get(id(p))
                if i is None:
                    i = len(pts)
                    idx[id(p)] = i
                    pts.append(p)
                refs.append(i)
            core["trainer"] = {
                "perm_rng": _pack_rng(tr.perm_rng),
                "pending": refs,
                "versions": [self._pack_tree(v) for v in tr._versions],
            }
        return core

    def _load_core(self, core, pts: List[PendingTask]) -> None:
        srv = self.server
        srv.w = self._unpack_tree(core["server"]["w"])
        srv.t = int(core["server"]["t"])
        srv.active = int(core["server"]["active"])
        srv.cache = [(self._unpack_tree(w), int(h), int(n))
                     for w, h, n in core["server"]["cache"]]
        self.strategy.load_state(core["strategy"])
        self.prev_local = {int(k): self._unpack_tree(w)
                           for k, w in core["prev_local"]}
        ch, c = self.channel, core["channel"]
        ch.bytes_up = int(c["bytes_up"])
        ch.bytes_down = int(c["bytes_down"])
        ch.max_up = int(c["max_up"])
        ch.max_down = int(c["max_down"])
        ch.tier_up = {int(t): int(b) for t, b in c["tier_up"]}
        ch.tier_down = {int(t): int(b) for t, b in c["tier_down"]}
        self.history = [LogEntry(float(t), int(r), float(a), int(bu),
                                 int(bd), int(mu), int(md))
                        for t, r, a, bu, bd, mu, md in core["history"]]
        s = core["stats"]
        self.stats = EngineStats(
            int(s["dispatches"]), int(s["completions"]), int(s["dropouts"]),
            int(s["transient_failures"]), int(s["redispatched"]),
            int(s["flushes"]), int(s["flushed_tasks"]),
            completed_per_device=np.asarray(s["completed_per_device"],
                                            np.int64))
        self._tail_logged = bool(core["tail_logged"])
        self._sync_now = float(core["sync_now"])
        if core["trainer"] is not None:
            tr = self.trainer
            assert isinstance(tr, CohortTrainer), \
                "checkpoint holds a deferred cohort buffer but this engine " \
                "was built with cohort_size=0"
            _load_rng(tr.perm_rng, core["trainer"]["perm_rng"])
            tr.pending = [pts[int(i)] for i in core["trainer"]["pending"]]
            tr._versions = [self._unpack_tree(v)
                            for v in core["trainer"]["versions"]]
            tr._version_ids = {id(v): i for i, v in enumerate(tr._versions)}
            # the restored global model is a fresh object; re-intern it if
            # it was one of the buffered versions so post-resume submits
            # reuse the slot an uninterrupted run would
            for i, v in enumerate(tr._versions):
                if _trees_equal(v, srv.w):
                    tr._version_ids[id(srv.w)] = i
                    break

    def _sched_state(self, reg) -> Dict[str, Any]:
        events = None
        if self._events is not None:
            events = [[float(t), int(s), kind, int(k),
                       self._pack_payload(p, reg), int(h)]
                      for t, s, kind, k, p, h in self._events]
        waiting = (None if self._waiting is None
                   else [int(x) for x in list(self._waiting)])
        return {"events": events, "waiting": waiting}

    def _load_sched(self, st, pts: List[PendingTask]) -> None:
        ev = st["events"]
        self._events = None if ev is None else [
            (float(t), int(s), str(kind), int(k),
             self._unpack_payload(p, pts), int(h))
            for t, s, kind, k, p, h in ev]
        w = st["waiting"]
        self._waiting = None if w is None else [int(x) for x in w]

    def state_dict(self) -> Dict[str, Any]:
        """Serializable full simulation state — server cache, codec-policy
        EWMAs, the DeviceRegistry, the event queue / EventTable, every RNG
        stream, history/stats/byte meters, and any deferred cohort buffer.
        Plain dicts/lists/scalars/ndarrays throughout: feed it to
        ``repro.checkpoint.io.save_blob``.  Restore with :meth:`load_state`
        on a freshly constructed engine over the same (data, partitions,
        w_init, cfg); a resumed ``run`` is bit-identical to an
        uninterrupted one (tests/test_fleet.py pins this)."""
        reg = ({}, [])
        dv = self.devices
        state = {
            "version": 1,
            "rng": _pack_rng(self.rng),
            "scenario_rng": _pack_rng(self.scenario_rng),
            "devices": {"down_rates": np.asarray(dv.down_rates),
                        "up_rates": np.asarray(dv.up_rates),
                        "a_k": np.asarray(dv.a_k),
                        "phi_k": np.asarray(dv.phi_k),
                        "alive": np.asarray(dv.alive),
                        "tier": np.asarray(dv.tier)},
            "started": bool(self._started),
            "now": float(self._now),
            "seq": int(self._seq),
            "sched": self._sched_state(reg),
            "core": self._core_state(reg),
        }
        state["pending"] = self._pack_pending(reg)
        return state

    def load_state(self, state: Dict[str, Any]) -> None:
        if int(state["version"]) != 1:
            raise ValueError(
                f"unknown engine checkpoint version {state['version']!r}")
        _load_rng(self.rng, state["rng"])
        _load_rng(self.scenario_rng, state["scenario_rng"])
        dv, d = self.devices, state["devices"]
        dv.down_rates[:] = np.asarray(d["down_rates"])
        dv.up_rates[:] = np.asarray(d["up_rates"])
        dv.a_k[:] = np.asarray(d["a_k"])
        dv.phi_k[:] = np.asarray(d["phi_k"])
        dv.alive[:] = np.asarray(d["alive"], bool)
        dv.tier[:] = np.asarray(d["tier"])
        self._started = bool(state["started"])
        self._now = float(state["now"])
        self._seq = int(state["seq"])
        pts = self._unpack_pending(state["pending"])
        self._load_core(state["core"], pts)
        self._load_sched(state["sched"], pts)


# ----------------------------------------------------------------------
# Batched scheduler (SimConfig.scheduler = "batched")
# ----------------------------------------------------------------------
class BatchedEngine(FLEngine):
    """The same event machine as ``FLEngine``, with the heap replaced by
    the resident per-device arrays of :class:`EventTable` — the scheduler
    the 10^5-device runs in results/engine_scale.json use.

    Mapping back to the paper: nothing protocol-visible changes.  Alg. 1's
    Distributor still admission-controls requests through
    ``TeasqServer.try_dispatch`` and Alg. 2's Receiver/Updater still runs
    per arrival — the batched loop only changes *how the next event is
    found*, not what any event does.  What is batched:

    * **Selection** — instead of one ``heappop`` + ``heappush`` pair per
      event, the next ``SELECT_K`` events are picked in one fused numpy
      call over the ``EventTable`` arrays (``np.partition`` + ``lexsort``),
      reproducing the exact global ``(time, seq)`` order the heap would
      produce.  Events pushed *during* a batch land back in the arrays;
      those falling inside the current batch's horizon also enter a small
      overflow heap that the merged loop interleaves, so handlers observe
      the identical event order — and therefore consume the shared RNG
      streams in the identical order.  Bit-parity holds by construction
      and is pinned by tests/test_batched_engine.py.
    * **The initial request burst** — one vectorized ``uniform`` draw,
      stream-identical to ``n`` scalar draws from the same RandomState.
    * **Arrival hooks** — arrivals route through the strategies' batched
      hooks (``ProtocolStrategy.on_arrivals`` /
      ``CodecPolicy.observe_arrivals``); the default implementations fall
      back to the serial hooks, and the engine keeps groups singleton
      because each arrival's eval log and re-request must interleave
      before the next arrival.  Protocols that can tolerate coarser
      interleaving override the batched hooks to fuse Eqs. 6-10 across a
      group.
    * **The waiting queue** — an O(1)-pop FIFO (the heap path's
      ``list.pop(0)`` shifts the whole buffer, quadratic when most of a
      large fleet parks behind the C-fraction admission gate).

    The request/failure handlers are inherited unchanged; the heap path
    stays untouched as the parity oracle.

    **Wave mode** (``SimConfig.handler_mode="wave"``) replaces the scalar
    fall-through with vectorized *wave* handlers: each selected batch is
    split into maximal same-kind event runs and every run is processed as
    arrays —

    * **grant waves** (Alg. 1 Distributor): one liveness mask, one
      admission-gate slice (the first ``free`` run members dispatch, the
      rest park via a single ``_FifoWaiting.extend``), codecs for the whole
      wave via ``channels_for`` with per-unique-codec wire pricing, and ONE
      ``DeviceRegistry.round_latency_batch`` call whose RNG draws are
      assigned in ascending device-index order; the resulting arrivals
      scatter into the ``EventTable`` in one ``put_wave``.
    * **arrival waves** (Alg. 2 Receiver/Updater, Eqs. 6-10): one
      ``CodecPolicy.observe_arrivals`` scatter, then
      ``ProtocolStrategy.on_arrivals`` — the TEA family fuses the cache
      insert + staleness-weighted aggregation through the *stacked*
      Eqs. 6-10 kernel (``aggregate_cache_stacked``), one segment per
      cache fill so eval logs observe the exact per-round server state.
      Re-requests and the waiting-queue drain (one ``pop_many`` slice)
      follow as a single request scatter.

    The relaxed-parity contract vs. ``"serial"``: protocol decisions still
    happen in global ``(time, seq)`` event order, but (1) RNG draws are
    batched per wave — grant latencies in device-index order, scenario
    draws in wave order — instead of interleaved per heap pop; (2) events
    spawned by a wave member are processed after the wave, never between
    members, so a re-dispatch within a wave observes the post-wave server
    state — in particular an arrival spawned *inside* an arrival wave's
    time span lands after it, which can regroup cache fills and shift
    round-completion instants relative to the heap order (the effect
    shrinks as fleets grow and waves become time-dense); (3) one
    aggregation reduces via tensordot instead of a
    sequential sum; (4) the deferred cohort path may use the
    ``_zero_step_round`` version-deduplicated channel.  The wave/heap
    property suite (tests/test_wave_handlers.py) pins what survives:
    identical event multisets, per-device completion counts and per-tier
    byte totals on deterministic-latency fleets, and the liveness/byte
    invariants at scale."""

    SELECT_K = 1024   # selection width; correctness is width-independent

    supports_wave = True

    def _run_async(self, time_budget: float, max_rounds: int,
                   eval_every: int) -> List[LogEntry]:
        if self.cfg.handler_mode == "wave":
            return self._run_wave(time_budget, max_rounds, eval_every)
        table = self.devices.event_table()
        n = self.cfg.n_devices
        self._resume()
        if not self._started:
            if n:
                # one vectorized draw == the heap path's n scalar draws
                table.time[:] = self.rng.uniform(0.0, 0.05, n)
                table.seq[:] = np.arange(n)
                table.kind[:] = KIND_IDS["request"]
            self._seq = n
            self._waiting = _FifoWaiting()
            self._log(0.0)
            self._started = True
        waiting = self._waiting
        spawned: List[Tuple[float, int, str, int, Any, int]] = []
        horizon = (np.inf, np.inf)   # (time, seq) of the batch's last event

        def push(t, kind, k, payload=None, h=0):
            table.put(k, t, self._seq, kind, payload, h)
            if (t, self._seq) < horizon:
                heapq.heappush(spawned, (t, self._seq, kind, k, payload, h))
            self._seq += 1

        now = self._now
        stop = False
        while not stop:
            sel = table.select_batch(self.SELECT_K)
            if not len(sel):
                break
            ts = table.time[sel].tolist()
            ss = table.seq[sel].tolist()
            kinds = table.kind[sel].tolist()
            hs = table.h[sel].tolist()
            batch = [(ts[i], ss[i], KIND_NAMES[kinds[i]], k,
                      table.payload[k], hs[i])
                     for i, k in enumerate(sel.tolist())]
            horizon = (batch[-1][0], batch[-1][1])
            i, m = 0, len(batch)
            while i < m or spawned:
                if spawned and (i >= m or spawned[0][:2] < batch[i][:2]):
                    ev = heapq.heappop(spawned)
                else:
                    ev = batch[i]
                    i += 1
                now, _, kind, k, payload, h = ev
                if now > time_budget or self.server.t >= max_rounds:
                    # stop BEFORE clearing: the boundary event stays in the
                    # table, so a later ``run`` call / restored checkpoint
                    # resumes exactly here (the heap path peeks instead)
                    stop = True
                    break
                table.clear(k)
                if kind == "request":
                    self._handle_request(now, k, push, waiting)
                elif kind == "failure":
                    self._handle_failure(now, k, payload, push, waiting)
                else:
                    self._handle_arrival(now, k, payload, h, eval_every,
                                         push, waiting)
            spawned.clear()   # leftovers (on stop) still live in `table`
            horizon = (np.inf, np.inf)
        self._now = now
        self._log(min(now, time_budget))
        self._tail_logged = True
        return self.history

    def _handle_arrival(self, now, k, payload, h, eval_every, push,
                        waiting) -> None:
        # identical semantics to FLEngine._handle_arrival, routed through
        # the batched strategy/policy hooks (whose defaults fall back to
        # the serial hooks, keeping bit-parity)
        self.strategy.policy.observe_arrivals(
            [k], [max(0, self.server.t - h)])
        done_round, = self.strategy.on_arrivals(self, [(now, k, payload, h)])
        self.stats.completions += 1
        self.stats.completed_per_device[k] += 1
        if done_round and self.server.t % eval_every == 0:
            self._log(now)
        if self.devices.alive[k]:
            push(now, "request", k)
        self._drain_waiting(now, push, waiting)

    # -- wave mode (handler_mode="wave") -----------------------------------
    def _run_wave(self, time_budget: float, max_rounds: int,
                  eval_every: int) -> List[LogEntry]:
        """Wave event loop: same selection as the serial batched loop, but
        each maximal same-kind run of the selected batch dispatches as one
        vectorized wave (see the class docstring for the relaxed-parity
        contract).  Events spawned by a wave join the table immediately and
        interleave at the next wave *boundary*; checkpoint state is
        identical to the serial batched loop (table + waiting queue), so a
        wave run can be resumed serially and vice versa."""
        table = self.devices.event_table()
        n = self.cfg.n_devices
        self._resume()
        if not self._started:
            if n:
                table.time[:] = self.rng.uniform(0.0, 0.05, n)
                table.seq[:] = np.arange(n)
                table.kind[:] = KIND_IDS["request"]
            self._seq = n
            self._waiting = _FifoWaiting()
            self._log(0.0)
            self._started = True
        waiting = self._waiting
        # overflow heap of events spawned inside the current batch horizon:
        # (time, seq, kind_id, device, payload, h) — kind as int id so runs
        # merge against the batch's int8 kind array
        spawned: List[Tuple[float, int, int, int, Any, int]] = []
        horizon = (np.inf, np.inf)

        def push(t, kind, k, payload=None, h=0):
            table.put(k, t, self._seq, kind, payload, h)
            if (t, self._seq) < horizon:
                heapq.heappush(spawned,
                               (t, self._seq, KIND_IDS[kind], k, payload, h))
            self._seq += 1

        def push_wave(ts_w, ks_w, kind, payloads, h):
            g = len(ks_w)
            if not g:
                return
            seqs = self._seq + np.arange(g)
            self._seq += g
            table.put_wave(ks_w, ts_w, seqs, kind, payloads, h)
            # fresh seqs always exceed the horizon seq, so only a strictly
            # earlier time puts a new event inside the current batch
            kid = KIND_IDS[kind]
            for j in np.flatnonzero(ts_w < horizon[0]).tolist():
                heapq.heappush(spawned, (
                    float(ts_w[j]), int(seqs[j]), kid, int(ks_w[j]),
                    None if payloads is None else payloads[j], int(h)))

        req_id = KIND_IDS["request"]
        arr_id = KIND_IDS["arrival"]
        now = self._now
        stop = False
        while not stop:
            sel = table.select_batch(self.SELECT_K)
            if not len(sel):
                break
            ts = table.time[sel]
            ss = table.seq[sel]
            kinds = table.kind[sel]
            hs = table.h[sel]
            payloads = [table.payload[k] for k in sel.tolist()]
            horizon = (float(ts[-1]), int(ss[-1]))
            bounds = np.flatnonzero(np.diff(kinds) != 0) + 1
            i, m, b = 0, len(sel), 0
            while i < m or spawned:
                if not spawned:
                    # fast path: the next run is a contiguous batch slice
                    while b < len(bounds) and bounds[b] <= i:
                        b += 1
                    j = int(bounds[b]) if b < len(bounds) else m
                    wts, wks = ts[i:j], sel[i:j]
                    wps, whs = payloads[i:j], hs[i:j]
                    kid = int(kinds[i])
                    i = j
                else:
                    # merge the overflow heap with the batch cursor event by
                    # event until the kind changes — spawned events are the
                    # wave's own re-requests/drains, i.e. the next wave
                    rt: List[float] = []
                    rk: List[int] = []
                    rp: List[Any] = []
                    rh: List[int] = []
                    kid = -1
                    while True:
                        if spawned and (i >= m or
                                        (spawned[0][0], spawned[0][1])
                                        < (ts[i], ss[i])):
                            e = spawned[0]
                            if kid < 0:
                                kid = e[2]
                            elif e[2] != kid:
                                break
                            heapq.heappop(spawned)
                            rt.append(e[0])
                            rk.append(e[3])
                            rp.append(e[4])
                            rh.append(e[5])
                        elif i < m:
                            if kid < 0:
                                kid = int(kinds[i])
                            elif int(kinds[i]) != kid:
                                break
                            rt.append(float(ts[i]))
                            rk.append(int(sel[i]))
                            rp.append(payloads[i])
                            rh.append(int(hs[i]))
                            i += 1
                        else:
                            break
                    wts = np.asarray(rt, np.float64)
                    wks = np.asarray(rk, np.int64)
                    wps, whs = rp, np.asarray(rh, np.int64)
                if self.server.t >= max_rounds:
                    stop = True
                    break
                # budget / round-cap prefix cut: unprocessed members keep
                # their table slots, so a later ``run`` resumes exactly
                # here.  A *partial* budget cut does not end the loop —
                # the processed prefix spawns re-requests at times still
                # inside the budget, which serial order grants before
                # stopping; the drain terminates because every wave after
                # the cut point is itself cut (to zero once no spawned
                # event precedes it).  The round cap, by contrast, stops
                # the stream at the capping event exactly like the serial
                # loop's per-event ``server.t >= max_rounds`` check.
                cut = int(np.searchsorted(wts, time_budget, side="right"))
                capped = False
                if kid == arr_id:
                    srv = self.server
                    if getattr(self.strategy, "arrival_wave", False):
                        allowed = ((max_rounds - srv.t)
                                   * srv.cfg.cache_size - len(srv.cache))
                    else:
                        allowed = max_rounds - srv.t
                    if max(0, allowed) < cut:
                        cut = max(0, allowed)
                        capped = True
                if cut < len(wts):
                    stop = True
                    if not cut:
                        break
                    wts, wks = wts[:cut], wks[:cut]
                    wps, whs = wps[:cut], whs[:cut]
                table.clear_wave(wks)
                if kid == req_id:
                    self._wave_requests(wts, wks, push, push_wave, waiting)
                elif kid == arr_id:
                    self._wave_arrivals(wts, wks, wps, whs, eval_every,
                                        push, push_wave, waiting)
                else:
                    for t_f, k_f, p_f in zip(wts.tolist(), wks.tolist(),
                                             wps):
                        self._handle_failure(t_f, int(k_f), p_f, push,
                                             waiting)
                if not stop:
                    now = float(wts[-1])
                if capped:
                    break
            spawned.clear()   # leftovers (on stop) still live in `table`
            horizon = (np.inf, np.inf)
        if stop:
            # resume cursor = earliest unprocessed event, exactly where
            # the serial loops stop (they break ON that event); empty
            # slots hold +inf, so min() scans the whole table once
            rem = float(table.time.min()) if n else np.inf
            if np.isfinite(rem):
                now = rem
        self._now = now
        self._log(min(now, time_budget))
        self._tail_logged = True
        return self.history

    def _wave_requests(self, wts, wks, push, push_wave, waiting) -> None:
        """Alg. 1 Distributor over a same-kind request run: one liveness
        mask, one admission-gate slice (run members are already in event
        order, so granting the first ``free`` and parking the rest matches
        serial per-event gating), one wire-pricing pass over the wave's
        codecs, one scenario draw vector, one ``round_latency_batch`` call
        (device-index draw order) and one arrival scatter."""
        dv = self.devices
        mask = dv.alive[wks]
        if not mask.all():
            wks, wts = wks[mask], wts[mask]
        srv = self.server
        free = max(srv.cfg.max_parallel - srv.active, 0)
        if free < len(wks):
            waiting.extend(wks[free:].tolist())
            wks, wts = wks[:free], wts[:free]
        g = len(wks)
        if not g:
            return
        if not self.trainer.deferred:
            # the serial trainer's codec roundtrips interleave RNG draws
            # with the latency draws per grant — keep the scalar handler
            # (slots were already granted-gated above, but the inherited
            # handler re-checks the gate, which is a no-op here)
            for t_s, k_s in zip(wts.tolist(), wks.tolist()):
                self._handle_request(t_s, int(k_s), push, waiting)
            return
        self.stats.dispatches += g
        srv.active += g
        w_t, t0 = srv.w, srv.t
        codecs = self.strategy.channels_for(t0, wks)
        tiers = dv.tier[wks]
        # wire price once per unique codec instance: wire_bytes is
        # shape-only / value-independent, and resolve_codec caches
        # instances, so a wave usually prices one or a handful of codecs
        nbytes = np.empty(g, np.int64)
        seen: Dict[int, int] = {}
        for idx, c in enumerate(codecs):
            v = seen.get(id(c))
            if v is None:
                v = seen[id(c)] = c.wire_bytes(w_t)
            nbytes[idx] = v

        scen = self.scenario
        if scen is not None and scen.active and (
                scen.dropout_prob + scen.failure_prob > 0):
            u = self.scenario_rng.random_sample(g)
            fail = u < scen.dropout_prob + scen.failure_prob
            if fail.any():
                f = np.flatnonzero(fail)
                # failing members: down metered, failure event mid-round;
                # latency + fail-point draws in device-index order
                f = f[np.argsort(wks[f], kind="stable")]
                fks = wks[f]
                self.channel.down_wave(nbytes[f], tiers[f])
                nb = np.maximum(1, self.part_sizes[fks]
                                // self.cfg.batch_size)
                dl, cp, _ = dv.round_latency_batch(
                    fks, nbytes[f] * 8.0, np.zeros(len(f)), nb,
                    self.scenario_rng)
                fail_at = wts[f] + self.scenario_rng.uniform(
                    0.0, dl + cp, len(f))
                for j, fi in enumerate(f.tolist()):
                    push(float(fail_at[j]), "failure", int(wks[fi]),
                         "dropout" if u[fi] < scen.dropout_prob
                         else "transient")
                keep = ~fail
                wks, wts = wks[keep], wts[keep]
                nbytes, tiers = nbytes[keep], tiers[keep]
                codecs = [c for c, kp in zip(codecs, keep.tolist()) if kp]
                g = len(wks)
                if not g:
                    return

        self.channel.down_wave(nbytes, tiers)
        tasks = [self.trainer.submit(int(k), w_t, t0, c.p_s, c.p_q)
                 for k, c in zip(wks.tolist(), codecs)]
        self.channel.up_wave(nbytes, tiers)
        order = np.argsort(wks, kind="stable")   # device-index draw order
        ko = wks[order]
        bits = nbytes[order] * 8.0
        nb = np.maximum(1, self.part_sizes[ko] // self.cfg.batch_size)
        dl, cp, ul = dv.round_latency_batch(ko, bits, bits, nb, self.rng)
        push_wave(wts[order] + dl + cp + ul, ko, "arrival",
                  [tasks[idx] for idx in order.tolist()], t0)

    def _wave_arrivals(self, wts, wks, wps, whs, eval_every, push,
                       push_wave, waiting, push_wave_free=None,
                       max_rounds=None) -> None:
        """Alg. 2 Receiver/Updater over a same-kind arrival run.  TEA-family
        strategies (``arrival_wave=True``) fuse the cache inserts and the
        Eqs. 6-10 aggregation via ``on_arrivals``/``receive_many``,
        processed in segments that end exactly at cache-fill boundaries so
        each eval log observes the same server round/state as the serial
        path.  Other strategies keep the bit-faithful scalar handler.

        ``push_wave_free`` routes the re-request scatter (a multi-task
        fleet hands requests back unassigned, task=-1); ``max_rounds``,
        when given, truncates the run at the round cap and *drops* the
        excess arrivals — the fleet semantics, where a finished job's
        in-flight events are consumed and ignored while other jobs keep
        running (the single-task loop instead cuts at the cap and leaves
        the excess scheduled)."""
        srv = self.server
        strategy = self.strategy
        fused = getattr(strategy, "arrival_wave", False)
        if max_rounds is not None:
            allowed = ((max_rounds - srv.t) * srv.cfg.cache_size
                       - len(srv.cache)) if fused else max_rounds - srv.t
            allowed = max(0, allowed)
            if allowed < len(wks):
                wts, wks = wts[:allowed], wks[:allowed]
                wps, whs = wps[:allowed], whs[:allowed]
        g = len(wks)
        if not g:
            return
        if not fused or (g == 1 and push_wave_free is None):
            for idx in range(g):
                self._handle_arrival(float(wts[idx]), int(wks[idx]),
                                     wps[idx], int(whs[idx]), eval_every,
                                     push, waiting)
            return
        K = srv.cfg.cache_size
        t0, c0 = srv.t, len(srv.cache)
        # staleness of arrival idx as the serial loop would observe it:
        # t has advanced by one per preceding cache fill
        stal = np.maximum(0, t0 + (c0 + np.arange(g)) // K - whs)
        strategy.policy.observe_arrivals(wks.tolist(), stal.tolist())
        ks_l, hs_l = wks.tolist(), whs.tolist()
        arrivals = [(float(wts[idx]), ks_l[idx], wps[idx], hs_l[idx])
                    for idx in range(g)]
        start = 0
        while start < g:
            seg_end = min(g, start + (K - len(srv.cache)))
            dones = strategy.on_arrivals(self, arrivals[start:seg_end])
            if dones[-1] and srv.t % eval_every == 0:
                self._log(float(wts[seg_end - 1]))
            start = seg_end
        self.stats.completions += g
        np.add.at(self.stats.completed_per_device, wks, 1)
        alive = self.devices.alive[wks]
        # a fleet hands freed devices back to its assigner (task=-1)
        (push_wave_free or push_wave)(wts[alive], wks[alive],
                                      "request", None, 0)
        # one-slice drain vs. the serial loop's per-arrival pops; drained
        # request j fires at arrival j's own timestamp, matching the slot
        # release order a serial drain would produce
        n_drain = min(len(waiting), max(0, srv.cfg.max_parallel
                                        - srv.active))
        if n_drain:
            drained = np.asarray(waiting.pop_many(n_drain), np.int64)
            push_wave(wts[:n_drain], drained, "request", None, 0)

    # -- checkpoint/resume: EventTable instead of the heap -----------------
    def _sched_state(self, reg) -> Dict[str, Any]:
        tab = self.devices.events
        table = None
        if tab is not None:
            live = np.flatnonzero(tab.time < np.inf).tolist()
            table = {"slots": [[int(k), float(tab.time[k]), int(tab.seq[k]),
                                int(tab.kind[k]), int(tab.h[k]),
                                int(tab.task[k]),
                                self._pack_payload(tab.payload[k], reg)]
                               for k in live]}
        waiting = (None if self._waiting is None
                   else [int(x) for x in
                         self._waiting._items[self._waiting._head:]])
        return {"table": table, "waiting": waiting}

    def _load_sched(self, st, pts: List[PendingTask]) -> None:
        if st["table"] is not None:
            tab = self.devices.event_table()
            tab.time[:] = np.inf
            tab.payload = [None] * len(tab.time)
            for k, t, seq, kind, h, task, p in st["table"]["slots"]:
                k = int(k)
                tab.time[k] = float(t)
                tab.seq[k] = int(seq)
                tab.kind[k] = int(kind)
                tab.h[k] = int(h)
                tab.task[k] = int(task)
                tab.payload[k] = self._unpack_payload(p, pts)
        if st["waiting"] is None:
            self._waiting = None
        else:
            w = _FifoWaiting()
            w._items = [int(x) for x in st["waiting"]]
            self._waiting = w


# scheduler registry: SimConfig.scheduler -> engine class (the same
# one-subclass-plus-one-entry idiom as STRATEGIES / CODECS / POLICIES)
SCHEDULERS: Dict[str, type] = {"heap": FLEngine, "batched": BatchedEngine}
