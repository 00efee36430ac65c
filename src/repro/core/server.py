"""Server-side TEASQ-Fed state machine (paper Algs. 1-2, server process).

Distributor: admission-controls task requests with the C-fraction gate.
Receiver/Updater: caches K = ceil(N*gamma) updates, then performs the
staleness-weighted aggregation of Eqs. 6-10.

``SERVERS`` registers the available server backends (the same
one-subclass-plus-one-entry idiom as STRATEGIES / CODECS / SCHEDULERS):

* ``"single"`` — :class:`TeasqServer`, the bit-pinned single-host
  reference every history fixture was recorded against.
* ``"sharded"`` — :class:`ShardedTeasqServer`, which partitions the
  flattened weight vector across a 1-D device mesh (chips, or host
  devices under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)
  and runs the stacked Eqs. 6-10 reduction as a ``shard_map``; with one
  device it degenerates to the parent's exact path.

``SimConfig.server`` selects the backend; ``make_server`` resolves it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

from repro import spans
from repro.core.staleness import (aggregate_cache, aggregate_cache_stacked,
                                  make_sharded_aggregator)


@dataclasses.dataclass
class ServerConfig:
    n_devices: int
    c_fraction: float = 0.1     # C: max fraction of devices training in parallel
    gamma: float = 0.1          # cache fraction: K = ceil(N * gamma)
    alpha: float = 0.6          # mixing hyper-parameter (Eq. 9)
    a: float = 0.5              # staleness exponent (Eq. 6)

    # cached: the admission gate reads these on every event-loop iteration
    @functools.cached_property
    def max_parallel(self) -> int:
        return max(1, math.ceil(self.n_devices * self.c_fraction))

    @functools.cached_property
    def cache_size(self) -> int:
        return max(1, math.ceil(self.n_devices * self.gamma))


class TeasqServer:
    """Holds the global model, round counter t, active count P and cache Q."""

    def __init__(self, w_init: Any, cfg: ServerConfig):
        self.cfg = cfg
        self.w = w_init
        self.t = 0
        self.active = 0                      # P
        self.cache: List[Tuple[Any, int, int]] = []   # (w_local, h_c, n_c)

    # -- Distributor (Alg. 1 server) ------------------------------------
    def try_dispatch(self) -> Optional[Tuple[Any, int]]:
        """Admit a task request: returns (w^t, t) or None if P >= ceil(N*C)."""
        if self.active >= self.cfg.max_parallel:
            return None
        self.active += 1
        return self.w, self.t

    # -- Receiver + Updater (Alg. 2) ------------------------------------
    def _aggregate(self) -> Any:
        """Eqs. 6-10 over the full cache via the serial K-tuple kernel —
        the bit-pinned reference path; subclasses may re-route."""
        return aggregate_cache(self.w, self.cache, self.t,
                               self.cfg.alpha, self.cfg.a)

    def _aggregate_stacked(self) -> Any:
        """Eqs. 6-10 via the stacked leading-axis kernel (wave mode's
        relaxed-parity path); subclasses may re-route."""
        return aggregate_cache_stacked(self.w, self.cache, self.t,
                                       self.cfg.alpha, self.cfg.a)

    def _fold_span(self):
        """The span around one fold; ``nbytes`` counts the cached updates
        that are on the host, which the fold moves to the device."""
        return spans.span("fl.aggregate", nbytes=spans.nbytes(
            [c[0] for c in self.cache], host_only=True)
            if spans.enabled() else 0)

    def receive(self, w_local: Any, h: int, n_samples: int) -> bool:
        """Push an update; aggregate when the cache reaches K.
        Returns True if an aggregation round completed."""
        self.active = max(0, self.active - 1)
        self.cache.append((w_local, h, n_samples))
        if len(self.cache) < self.cfg.cache_size:
            return False
        with self._fold_span():
            self.w = self._aggregate()
        self.cache.clear()
        self.t += 1
        return True

    def receive_many(self, entries: List[Tuple[Any, int, int]]) -> List[bool]:
        """Wave-mode Receiver (Alg. 2 over a whole arrival group): push the
        group's ``(w_local, h_c, n_c)`` entries in event order, aggregating
        at every cache-fill boundary with the *stacked* Eqs. 6-10 kernel
        (``aggregate_cache_stacked`` — one leading-axis stack per leaf
        instead of K separate tree arguments).  Same cache/round semantics
        as K calls to :meth:`receive`; the reduction order inside one
        aggregation differs (tensordot vs. sequential sum), which is part of
        ``handler_mode="wave"``'s relaxed-parity contract."""
        done = []
        for w_local, h, n_samples in entries:
            self.active = max(0, self.active - 1)
            self.cache.append((w_local, h, n_samples))
            if len(self.cache) < self.cfg.cache_size:
                done.append(False)
                continue
            with self._fold_span():
                self.w = self._aggregate_stacked()
            self.cache.clear()
            self.t += 1
            done.append(True)
        return done


class ShardedTeasqServer(TeasqServer):
    """`TeasqServer` with the Eqs. 6-10 reduction sharded over a device
    mesh (the "Sharded aggregation" ROADMAP tentpole).

    The flattened weight vector is partitioned into equal column blocks
    across a 1-D mesh of the first ``n_shards`` local jax devices (chips,
    or host devices when the process runs under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``), and both the
    serial and the wave receive paths reduce through ONE
    ``shard_map``-compiled flat kernel (``make_sharded_aggregator``).
    Every shard computes the identical per-element program as the
    single-host stacked kernel, so the sharded weights match
    ``aggregate_cache_stacked`` to <= 1 ulp (tests/test_sharded_server.py
    pins this across mesh sizes).

    Asking for more shards than the process has devices raises.  With
    ``n_shards`` resolving to 1 (the default single-device process) no
    mesh is built and BOTH paths delegate to the parent's kernels
    unchanged — the degenerate server is bit-identical to
    :class:`TeasqServer`, so the pinned history fixtures stay valid under
    ``SimConfig.server="sharded"`` on one device."""

    def __init__(self, w_init: Any, cfg: ServerConfig, n_shards: int = 0):
        super().__init__(w_init, cfg)
        import jax
        import numpy as np
        from jax.sharding import Mesh
        devs = jax.devices()
        self.n_shards = int(n_shards) if n_shards > 0 else len(devs)
        if self.n_shards > len(devs):
            raise ValueError(f"server_shards={self.n_shards} needs as many "
                             f"devices, this process has {len(devs)}")
        self.mesh = None
        self._agg = None
        if self.n_shards > 1:
            self.mesh = Mesh(np.asarray(devs[:self.n_shards]), ("agg",))
            self._agg = make_sharded_aggregator(self.mesh)

    def _aggregate(self) -> Any:
        if self._agg is None:      # degenerate mesh: exact parent path
            return super()._aggregate()
        return self._agg(self.w, self.cache, self.t,
                         self.cfg.alpha, self.cfg.a)

    # one flat sharded kernel serves both receive paths: the stacked and
    # the serial single-host kernels only differ in reduction order, and
    # the sharded reduction already follows the stacked one
    _aggregate_stacked = _aggregate


# server registry: SimConfig.server -> class (the same
# one-subclass-plus-one-entry idiom as STRATEGIES / CODECS / SCHEDULERS)
SERVERS: Dict[str, type] = {
    "single": TeasqServer,
    "sharded": ShardedTeasqServer,
}


def make_server(name: str, w_init: Any, cfg: ServerConfig, *,
                shards: int = 0) -> TeasqServer:
    """Resolve ``SimConfig.server`` to a constructed server backend.
    ``shards`` (``SimConfig.server_shards``) is the mesh width for
    sharded backends: 0 means "all local devices"; more than the process
    has raises."""
    try:
        cls = SERVERS[name]
    except KeyError:
        raise ValueError(f"unknown server {name!r}; "
                         f"expected one of {sorted(SERVERS)}") from None
    if issubclass(cls, ShardedTeasqServer):
        return cls(w_init, cfg, n_shards=shards)
    return cls(w_init, cfg)
