"""Sharded bucket drains: split a batched solve over a device mesh.

The paper's pipeline keeps one device's cores busy; ``ShardedDPEngine``
keeps a *mesh* of devices busy (DESIGN.md §7). A bucket drain is
embarrassingly parallel across instances — every lane of the vmapped solve
is independent — so the batch axis is the natural partition axis: each
device solves its shard of the bucket locally (the same per-lane program
the single-device engine traces), and the results concatenate back
bit-identically. Ding/Gu/Sun scale DP *within* one instance by processors;
Helal et al. partition an alignment workload across a processor grid; here
the partition is at the serving tier, across instances.

Mechanics:

  * :class:`ShardContext` carries the ``jax.sharding.Mesh`` plus the three
    hooks the batch runners in ``repro.dp.backends`` consume: ``place``
    (device_put the stacked batch with a :class:`NamedSharding` built from
    the rule-based helpers in ``repro.runtime.sharding``), ``wrap``
    (``shard_map`` the vmapped callable over the batch axis), and
    ``cache_suffix`` (the mesh size becomes part of the batch-jit cache
    key — a sharded program is a different program).
  * Ragged buckets pad up to a multiple of the mesh size by replicating
    the last spec; the pad lanes are masked out of the responses (their
    outputs are sliced away before fan-out) and counted in
    ``stats["padded_lanes"]``.
  * :class:`ShardedDPEngine` routes each drain through the normal
    ``routing``/``autotune`` stack, but ranks batchable routes on — and
    feeds realized drain latencies back under — the distinct
    ``("shard", ndev)`` measurement regime, so multi-device amortization
    never pollutes single-device calibration entries (the device count is
    also part of ``autotune._jax_backend`` for the same reason).
    Loop-fallback routes (no ``batch_run``) execute unsharded and keep
    their single-device regimes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.dp import reconstruct as _reconstruct
from repro.dp import routing as _routing
from repro.dp import telemetry as _telemetry
from repro.dp.engine import DPEngine

#: mesh axis name of the bucket's batch dimension
BATCH_AXIS = "shard"


def device_count() -> int:
    import jax

    return jax.device_count()


def default_mesh(axis: str = BATCH_AXIS, devices=None):
    """1-D mesh over all visible devices (the continuous-batching serving
    tier shards buckets, not tables, so one axis is the whole story)."""
    import jax
    from jax.sharding import Mesh

    devices = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.array(devices), (axis,))


@dataclasses.dataclass(frozen=True)
class ShardContext:
    """Everything a batch runner needs to execute one bucket drain sharded
    over ``mesh`` along ``axis``. Frozen — one context per engine, reused
    across drains so the batch-jit cache keys stay stable."""

    mesh: object
    axis: str = BATCH_AXIS

    def __post_init__(self):
        if self.axis not in self.mesh.axis_names:
            raise ValueError(f"axis {self.axis!r} not in mesh axes "
                             f"{self.mesh.axis_names}")

    @property
    def ndev(self) -> int:
        return int(self.mesh.shape[self.axis])

    def cache_suffix(self) -> tuple:
        """Batch-jit cache-key contribution: a shard_mapped program is a
        different traced program per mesh size."""
        return (("shard", self.ndev),)

    def regime(self, reconstruct: bool = False) -> tuple:
        """Calibration-key suffix of a drain executed under this context —
        the ``("shard", ndev)`` measurement regime (``backends.
        is_regime_marker``), with the arg-emitting variant marked so
        sharded reconstruct drains stay separate too."""
        marker = ("shard", self.ndev)
        if reconstruct:
            marker += ("reconstruct",)
        return (marker,)

    def pad(self, specs: list) -> tuple:
        """Pad a ragged bucket to a multiple of the mesh size by
        replicating the last spec (a real instance, so every lane runs the
        ordinary program — no NaN/garbage hazards). Returns
        ``(padded_specs, n_pad)``; callers slice the pad lanes away."""
        n_pad = self.pad_lanes(len(specs))
        return list(specs) + [specs[-1]] * n_pad, n_pad

    def pad_lanes(self, lanes: int) -> int:
        """How many pad lanes :meth:`pad` adds to a bucket of ``lanes``."""
        return -lanes % self.ndev

    def place(self, arr):
        """device_put a stacked bucket with its batch dim sharded over the
        mesh — built via the rule-based helpers in
        ``repro.runtime.sharding`` (the "bucket" logical axis). Runs under
        the profiler span ``dp.place``, inside the drain's ``dp.stack``."""
        import jax

        from repro.runtime import sharding as _rt

        with _telemetry.trace_span("dp.place"):
            axes = ("bucket",) + (None,) * (arr.ndim - 1)
            rules = {"bucket": [self.axis], None: [None]}
            ns = _rt.named_sharding(self.mesh, arr.shape, axes, rules)
            return jax.device_put(arr, ns)

    def wrap(self, call):
        """``shard_map`` a vmapped batch callable over the batch axis: each
        device vmaps its own shard with the identical per-lane program, so
        the gathered result is bit-identical to the unsharded call."""
        import jax
        from jax.sharding import PartitionSpec as P

        p = P(self.axis)
        return jax.jit(jax.shard_map(call, mesh=self.mesh, in_specs=p,
                                     out_specs=p, check_vma=False))


class ShardedDPEngine(DPEngine):
    """DPEngine whose bucket drains run sharded over a device mesh.

    Batchable routes pad the bucket to the mesh size and execute through
    ``backends``' shard_mapped batch runners; loop-fallback routes (and
    1-device meshes) fall back to the plain drain path. Observations and
    route ranking use the ``("shard", ndev)`` regime for sharded drains and
    the ordinary single-device regimes for unsharded ones."""

    def __init__(self, mesh=None, axis: Optional[str] = None, **kw):
        super().__init__(**kw)
        if mesh is None:
            mesh = default_mesh(axis or BATCH_AXIS)
        self.ctx = ShardContext(mesh=mesh, axis=axis or mesh.axis_names[0])
        self.stats.update({"sharded_drains": 0, "padded_lanes": 0})

    # -- regime / shardability hooks (DPEngine drain internals) -----------
    def _will_shard(self, backend, spec0, reconstruct: bool) -> bool:
        if self.ctx.ndev <= 1:
            return False
        if reconstruct:
            return (backend.batch_run_with_args is not None
                    and _reconstruct.supports_args(spec0))
        return backend.batch_run is not None

    def _batch_regime(self, reconstruct: bool) -> tuple:
        if self.ctx.ndev <= 1:
            return super()._batch_regime(reconstruct)
        return self.ctx.regime(reconstruct)

    def _loop_regime(self, reconstruct: bool) -> tuple:
        return super()._batch_regime(reconstruct)

    def _layout(self, backend, spec0, reconstruct: bool, lanes: int) -> tuple:
        if not self._will_shard(backend, spec0, reconstruct):
            return super()._layout(backend, spec0, reconstruct, lanes)
        return self.ctx.ndev, self.ctx.pad_lanes(lanes)

    def _obs_suffix(self, backend, spec0, reconstruct: bool) -> tuple:
        """The regime this drain will actually execute under: sharded for
        batchable routes, the single-device regime for loop fallbacks."""
        if self._will_shard(backend, spec0, reconstruct):
            return self.ctx.regime(reconstruct)
        return self._loop_regime(reconstruct)

    # -- one sharded device call ------------------------------------------
    def _run_bucket(self, backend, specs, reconstruct: bool):
        if not self._will_shard(backend, specs[0], reconstruct):
            return super()._run_bucket(backend, specs, reconstruct)
        b = len(specs)
        padded, n_pad = self.ctx.pad(specs)
        if reconstruct:
            tables, argss, source, paths = _routing.run_batch_with_args(
                backend, padded, sharding=self.ctx)
            tables, argss = tables[:b], argss[:b]
            if paths is not None:
                paths = paths[:b]
        else:
            tables = _routing.run_batch(backend, padded,
                                        sharding=self.ctx)[:b]
            argss, source, paths = None, None, None
        self.stats["sharded_drains"] += 1
        self.stats["padded_lanes"] += n_pad
        rep = _telemetry.current_drain()
        if rep is not None:
            rep.sharded = True
        _telemetry.count("dp_engine_sharded_drains_total")
        _telemetry.count("dp_engine_padded_lanes_total", n_pad)
        return tables, argss, source, paths
