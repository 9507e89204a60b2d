"""Solver-backend registry + the vmapped batch machinery.

Solvers do not get imported ad hoc any more: ``repro.core.sdp``,
``repro.core.mcm``, ``repro.core.blocked_mcm`` and ``repro.kernels`` register
themselves here at import time (bottom-of-module registration), and
``ensure_registered()`` pulls them all in lazily so this module itself stays
import-cycle-free. The dispatcher (``repro.dp.routing``) picks the
cheapest supporting backend per spec via each backend's ``cost`` model.

Batching: backends built through :func:`linear_backend` /
:func:`triangular_tab_backend` get a ``batch_run`` that stacks B same-shape
instances and executes ONE jitted ``vmap`` call. The jitted callables are
cached per (backend, shape_key); a Python-side :data:`TRACE_LOG` entry is
appended at *trace* time only, which is how tests verify the
one-device-call property without timing heuristics.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from repro.dp import telemetry as _telemetry
from repro.dp.problem import (LinearSpec, Spec, TriangularSpec,
                              family_class, plane_builder)

#: (backend_name, shape_key) appended every time a batched callable is traced.
#: Bounded at :data:`TRACE_LOG_MAX` (oldest entries dropped) so a long-running
#: engine over endless fresh shapes doesn't grow it forever.
TRACE_LOG: list = []
TRACE_LOG_MAX = 4096
#: total traces ever logged — unlike ``len(TRACE_LOG)`` this keeps moving
#: after the cap trims the list, so delta-based cold-call detection
#: (``DPEngine``) stays sound in arbitrarily long sessions.
TRACE_COUNT = 0
#: append/drain interleave once drains run off more than one thread (the
#: service's slot-recycling loop + concurrent drains) — writers and the
#: snapshot-and-clear must not race
_TRACE_LOCK = threading.Lock()

_BACKENDS: dict = {}
#: jit-callable cache, LRU-bounded (the blocked_mcm guard-cache pattern).
_BATCH_CACHE: "OrderedDict[tuple, Callable]" = OrderedDict()
_BATCH_CACHE_MAX = 128
_LOADED = False


def log_trace(key) -> None:
    """Record a trace event, keeping the log bounded. Thread-safe: traced
    callables may compile from concurrent drain threads."""
    global TRACE_COUNT
    with _TRACE_LOCK:
        TRACE_COUNT += 1
        TRACE_LOG.append(key)
        if len(TRACE_LOG) > TRACE_LOG_MAX:
            del TRACE_LOG[: len(TRACE_LOG) - TRACE_LOG_MAX]
    _telemetry.count("dp_backend_traces_total")


def drain_trace_log() -> list:
    """Snapshot and clear the trace log (tests; bounds long sessions).
    Atomic with respect to concurrent :func:`log_trace` appends."""
    with _TRACE_LOCK:
        out = list(TRACE_LOG)
        TRACE_LOG.clear()
    return out


def stack_bucket(arrays, sharding=None):
    """Stack a bucket's per-instance arrays along a new batch axis. A
    single-device drain stacks on the device. A sharded drain stacks on the
    host and lets ``ShardContext.place`` send each device its lanes:
    stacking on the device first would land the whole bucket on the
    default device before the split."""
    import jax.numpy as jnp

    if sharding is None:
        return jnp.stack([jnp.asarray(a) for a in arrays])
    return sharding.place(np.stack([np.asarray(a) for a in arrays]))


def _shard_bytes(arrays) -> dict:
    """``shards`` (the devices that hold part of ``arrays``) and
    ``h2d_bytes_per_shard`` (the most bytes of them one device holds), read
    from the placed arrays themselves."""
    held = {}
    for a in arrays:
        for shard in a.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    return {"shards": len(held), "h2d_bytes_per_shard": max(held.values())}


def stack_slots(specs, lane_arrays: Callable, sharding=None) -> tuple:
    """The drain's host→device copy: ``lane_arrays(spec)`` gives each
    lane's host arrays, one per program argument, and each argument's
    lanes are stacked with :func:`stack_bucket`. Runs under the profiler
    span ``dp.stack``, with the bytes and the number of host arrays it
    sends, and how they lie over the devices (:func:`_shard_bytes`)."""
    with _telemetry.trace_span("dp.stack") as span:
        lanes = [lane_arrays(s) for s in specs]
        stacked = tuple(stack_bucket(slot, sharding) for slot in zip(*lanes))
        if _telemetry.tracing():
            span.set_metadata(
                h2d_bytes=sum(a.nbytes for arrs in lanes for a in arrs),
                arrays=sum(len(arrs) for arrs in lanes),
                **_shard_bytes(stacked))
        return stacked


def stack_sources(specs, sharding=None) -> tuple:
    """The drain's host→device copy when the program builds the lanes'
    planes itself (:func:`common_source`): each source slot's lanes are
    stacked on the host and sent as one array, or placed over the mesh.
    Runs under ``dp.stack`` like :func:`stack_slots`, which also counts
    the lanes whose planes the program builds (``source_lanes``)."""
    import jax.numpy as jnp

    with _telemetry.trace_span("dp.stack") as span:
        slots = [np.stack(slot)
                 for slot in zip(*(s.source.arrays for s in specs))]
        if sharding is None:
            stacked = tuple(jnp.asarray(a) for a in slots)
        else:
            stacked = tuple(sharding.place(a) for a in slots)
        if _telemetry.tracing():
            span.set_metadata(h2d_bytes=sum(a.nbytes for a in slots),
                              arrays=len(slots), source_lanes=len(specs),
                              **_shard_bytes(stacked))
        return stacked


def common_source(specs) -> Optional[str]:
    """The plane builder every spec of a bucket names in its ``source``
    (``GridSpec.source``), or ``None`` when any lane has none or another."""
    names = {None if s.source is None else s.source.builder for s in specs}
    return names.pop() if len(names) == 1 else None


def launch(program: Callable, *stacked):
    """Call a drain's jitted ``program`` under the profiler span
    ``dp.launch``. Dispatch is asynchronous, so the span ends before the
    device does; a trace and compile lands inside it, counted as the
    span's ``compiles`` (the :data:`TRACE_COUNT` delta)."""
    before = TRACE_COUNT
    with _telemetry.trace_span("dp.launch") as span:
        out = program(*stacked)
        span.set_metadata(compiles=TRACE_COUNT - before)
    return out


def fetch(*outs) -> list:
    """Copy a drain's device outputs to the host — the drain's sync point,
    so it waits for the device — under the profiler span ``dp.fetch`` with
    the bytes it copies and the number of devices it gathers from."""
    with _telemetry.trace_span("dp.fetch") as span:
        host = [np.asarray(o) for o in outs]
        if _telemetry.tracing():
            span.set_metadata(
                d2h_bytes=sum(h.nbytes for h in host),
                shards=max(len(o.sharding.device_set) for o in outs))
    return host


def lru_put(cache: "OrderedDict", key, value, max_entries: int):
    """Insert-or-refresh on an OrderedDict used as an LRU, evicting the
    stalest entries past ``max_entries``."""
    cache[key] = value
    cache.move_to_end(key)
    while len(cache) > max_entries:
        cache.popitem(last=False)
    return value


def lru_cached(cache: "OrderedDict", key, build: Callable, max_entries: int):
    """Fetch-or-build on an OrderedDict used as an LRU: hits refresh recency,
    inserts evict the stalest entry past ``max_entries``. Evicted jit
    callables recompile on next use — bounded memory beats a cache that keeps
    one compiled program per shape ever seen."""
    fn = cache.get(key)
    if fn is None:
        fn = lru_put(cache, key, build(), max_entries)
    else:
        cache.move_to_end(key)
    return fn


@dataclasses.dataclass(frozen=True)
class Backend:
    """A solver route. ``run`` returns the full linearized table as numpy;
    ``batch_run`` (optional) solves a homogeneous list of specs in one
    device call — builder-made batch runners additionally accept a
    ``sharding=`` context (``repro.dp.sharding.ShardContext``) that splits
    the batch axis over a device mesh via ``shard_map`` (batch size must be
    a multiple of the mesh size; callers pad). Arg-capable routes
    additionally expose ``run_with_args`` / ``batch_run_with_args``
    returning ``(table, args)`` pairs — the winning lane (linear) or best
    split (triangular) per cell — which the reconstruction layer
    (``repro.dp.reconstruct``) prefers over its numpy from-the-cost-table
    fallback. Fused routes (``run_fused`` / ``batch_run_fused``) go one
    further: solve + args + traceback in ONE dispatch, returning
    ``(table, args, path)`` — the routing layer prefers them whenever a
    reconstruction was requested, which is what makes ``reconstruct=True``
    a single launch on the tiled kernel tier (DESIGN.md §5).

    Streaming contract (DESIGN.md §11): ``run_extend(spec, old_len, state)``
    (optional) warm-starts the solver from a solved prefix — ``spec`` is the
    EXTENDED spec, ``old_len`` the prefix length along the family's growth
    axis, ``state`` the prefix's ``extension_state()`` payload — and returns
    the family-shaped extension output (new cells / full re-laid-out table)
    that ``spec.stitch_extension`` assembles into the full table,
    bit-identical to a cold solve. Extend callables trace and cache under
    their own ``("extend", old_len)``-suffixed keys so calibration and the
    trace log never conflate extends with cold solves.

    Static-analysis contract (DESIGN.md §10): ``schedule`` is the route's
    schedule descriptor — ``schedule(spec) -> repro.dp.schedule
    .ScheduleModel`` declaring the symbolic consume/finalize steps the
    hazard verifier checks against the family's ``schedule_model()``;
    every registered route must provide one (the conformance suite and
    the ``repro.analysis`` CI gate enforce it). ``cache_tag`` is the
    normalized no-arg ambient-state tagger folded into batch-jit cache
    keys, exposed so the linter can observe it; ``env_sensitive`` names
    the REPRO_* knobs that tag must react to."""

    name: str
    geometry: str
    run: Callable[[Spec], np.ndarray]
    cost: Callable[[Spec], float]
    supports: Callable[[Spec], bool]
    batch_run: Optional[Callable] = None
    run_with_args: Optional[Callable] = None
    batch_run_with_args: Optional[Callable] = None
    run_fused: Optional[Callable] = None
    batch_run_fused: Optional[Callable] = None
    run_extend: Optional[Callable] = None
    schedule: Optional[Callable] = None
    cache_tag: Optional[Callable] = None
    env_sensitive: tuple = ()
    doc: str = ""


def register(backend: Backend) -> Backend:
    if backend.name in _BACKENDS:
        raise ValueError(f"duplicate backend name {backend.name!r}")
    _BACKENDS[backend.name] = backend
    return backend


def get(name: str) -> Backend:
    ensure_registered()
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; registered: {names()}") from None


def names(geometry: Optional[str] = None) -> list:
    ensure_registered()
    return sorted(n for n, b in _BACKENDS.items()
                  if geometry is None or b.geometry == geometry)


def candidates(spec: Spec) -> list:
    """Backends able to solve ``spec``, cheapest first (name tiebreak)."""
    ensure_registered()
    cands = [b for b in _BACKENDS.values()
             if b.geometry == spec.geometry and b.supports(spec)]
    return sorted(cands, key=lambda b: (b.cost(spec), b.name))


def ensure_registered() -> None:
    """Idempotently import every module that registers backends."""
    global _LOADED
    if _LOADED:
        return
    import repro.core.sdp  # noqa: F401  (registers linear solvers)
    import repro.core.mcm  # noqa: F401  (registers triangular solvers)
    import repro.core.blocked_mcm  # noqa: F401  (tropical-GEMM tiling)
    import repro.core.grid  # noqa: F401  (registers grid wavefront solvers)
    import repro.kernels  # noqa: F401  (Pallas-backed blocked route)
    # only after every registering import succeeded — a failure above must
    # surface again on the next call, not leave a silently partial registry
    _LOADED = True


# ---------------------------------------------------------------------------
# Builders used by the registering modules
# ---------------------------------------------------------------------------
def _cache_tagger(cache_tag: Optional[Callable]) -> Callable[[], tuple]:
    """Normalize a builder's ``cache_tag`` hook. The tag is appended to every
    batch-jit cache key (and hence TRACE_LOG entry): solver wrappers whose
    traced program depends on ambient state — the kernel tier reads
    ``REPRO_KERNELS`` at trace time — must fold that state into the key, or a
    mode flip mid-process would keep serving programs traced under the old
    mode."""
    if cache_tag is None:
        return lambda: ()
    return lambda: tuple(cache_tag())


def linear_backend(name: str, jax_fn: Callable, cost: Callable,
                   supports: Optional[Callable] = None,
                   jax_arg_fn: Optional[Callable] = None,
                   cache_tag: Optional[Callable] = None,
                   schedule: Optional[Callable] = None,
                   env_sensitive: tuple = (),
                   run_extend: Optional[Callable] = None,
                   doc: str = "") -> Backend:
    """Wrap a JAX S-DP solver ``fn(init, offsets, op, n, weights=None)``
    into a Backend with a single-call vmapped batch path. ``jax_arg_fn`` (same
    signature, returns ``(st, args)``) additionally equips the backend with
    the ``*_with_args`` capability pair. ``cache_tag`` (no-arg callable)
    contributes trace-time ambient state to the batch-jit cache keys (see
    :func:`_cache_tagger`); ``schedule``/``env_sensitive`` are the
    static-analysis descriptors (see :class:`Backend`)."""
    import jax
    import jax.numpy as jnp

    tag = _cache_tagger(cache_tag)

    def _run(fn, spec: LinearSpec):
        w = None if spec.weights is None else jnp.asarray(spec.weights)
        return fn(jnp.asarray(spec.init), spec.offsets, spec.op, spec.n,
                  weights=w)

    def run(spec: LinearSpec) -> np.ndarray:
        return np.asarray(_run(jax_fn, spec))

    def _batch(fn, specs, key, sharding=None):
        spec0 = specs[0]

        def build():
            offsets, op, n = spec0.offsets, spec0.op, spec0.n
            if spec0.weights is None:
                def call(inits):
                    log_trace(key)
                    return jax.vmap(
                        lambda i: fn(i, offsets, op, n))(inits)
            else:
                def call(inits, weights):
                    log_trace(key)
                    return jax.vmap(
                        lambda i, w: fn(i, offsets, op, n, weights=w)
                    )(inits, weights)
            if sharding is None:
                return jax.jit(call)
            return sharding.wrap(call)

        cached = lru_cached(_BATCH_CACHE, key, build, _BATCH_CACHE_MAX)
        if spec0.weights is None:
            stacked = stack_slots(specs, lambda s: (s.init,), sharding)
        else:
            stacked = stack_slots(specs, lambda s: (s.init, s.weights),
                                  sharding)
        return launch(cached, *stacked)

    def _batch_key(specs, sharding) -> tuple:
        shard_tag = sharding.cache_suffix() if sharding is not None else ()
        return (name, specs[0].shape_key()) + tag() + shard_tag

    def batch_run(specs, sharding=None) -> list:
        table, = fetch(_batch(jax_fn, specs, _batch_key(specs, sharding),
                              sharding))
        return list(table)

    run_with_args = batch_run_with_args = None
    if jax_arg_fn is not None:
        def run_with_args(spec: LinearSpec):
            st, args = _run(jax_arg_fn, spec)
            return np.asarray(st), np.asarray(args)

        def batch_run_with_args(specs, sharding=None):
            sts, argss = fetch(*_batch(
                jax_arg_fn, specs, _batch_key(specs, sharding) + ("args",),
                sharding))
            return list(sts), list(argss)

    return Backend(name=name, geometry="linear", run=run, cost=cost,
                   supports=supports or (lambda s: True),
                   batch_run=batch_run, run_with_args=run_with_args,
                   batch_run_with_args=batch_run_with_args,
                   run_extend=run_extend, schedule=schedule, cache_tag=tag,
                   env_sensitive=tuple(env_sensitive), doc=doc)


def triangular_tab_backend(name: str, jax_fn: Callable, cost: Callable,
                           supports: Optional[Callable] = None,
                           jax_arg_fn: Optional[Callable] = None,
                           jax_fused_fn: Optional[Callable] = None,
                           cache_tag: Optional[Callable] = None,
                           schedule: Optional[Callable] = None,
                           env_sensitive: tuple = (),
                           run_extend: Optional[Callable] = None,
                           doc: str = "") -> Backend:
    """Wrap a weight-table triangular solver ``fn(wtab, n)`` (e.g.
    ``core.mcm.solve_wavefront_tab``) with a vmapped batch path.
    ``jax_arg_fn`` (returns ``(st, args)``) adds the arg-capability pair;
    ``jax_fused_fn`` (returns ``(st, args, (ii, dd, ee))`` with the node
    arrays in ``triangular_traceback``'s preorder contract) adds the fused
    solve+traceback pair; ``supports`` gates eligibility (e.g. the Pallas
    route's VMEM budget); ``cache_tag`` as in :func:`linear_backend`."""
    import jax
    import jax.numpy as jnp

    tag = _cache_tagger(cache_tag)

    def run(spec: TriangularSpec) -> np.ndarray:
        return np.asarray(jax_fn(jnp.asarray(spec.weights), spec.n))

    def _batch(fn, specs, key, sharding=None):
        def build():
            n = specs[0].n

            def call(wtabs):
                log_trace(key)
                return jax.vmap(lambda w: fn(w, n))(wtabs)

            if sharding is None:
                return jax.jit(call)
            return sharding.wrap(call)

        cached = lru_cached(_BATCH_CACHE, key, build, _BATCH_CACHE_MAX)
        return launch(cached, *stack_slots(specs, lambda s: (s.weights,),
                                           sharding))

    def _batch_key(specs, sharding) -> tuple:
        shard_tag = sharding.cache_suffix() if sharding is not None else ()
        return (name, specs[0].shape_key()) + tag() + shard_tag

    def batch_run(specs, sharding=None) -> list:
        table, = fetch(_batch(jax_fn, specs, _batch_key(specs, sharding),
                              sharding))
        return list(table)

    run_with_args = batch_run_with_args = None
    if jax_arg_fn is not None:
        def run_with_args(spec: TriangularSpec):
            st, args = jax_arg_fn(jnp.asarray(spec.weights), spec.n)
            return np.asarray(st), np.asarray(args)

        def batch_run_with_args(specs, sharding=None):
            sts, argss = fetch(*_batch(
                jax_arg_fn, specs, _batch_key(specs, sharding) + ("args",),
                sharding))
            return list(sts), list(argss)

    run_fused = batch_run_fused = None
    if jax_fused_fn is not None:
        from repro.dp.problem import TriangularPath

        def run_fused(spec: TriangularSpec):
            st, args, (ii, dd, ee) = jax_fused_fn(
                jnp.asarray(spec.weights), spec.n)
            path = TriangularPath(nodes=np.stack(
                [np.asarray(ii), np.asarray(dd), np.asarray(ee)],
                axis=1).astype(np.int64))
            return np.asarray(st), np.asarray(args), path

        def batch_run_fused(specs, sharding=None):
            sts, argss, (ii, dd, ee) = _batch(
                jax_fused_fn, specs,
                _batch_key(specs, sharding) + ("fused",), sharding)
            sts, argss, ii, dd, ee = fetch(sts, argss, ii, dd, ee)
            nodes = np.stack([ii, dd, ee], axis=2)
            return (list(sts), list(argss),
                    [TriangularPath(nodes=nodes[b].astype(np.int64))
                     for b in range(len(specs))])

    return Backend(name=name, geometry="triangular", run=run, cost=cost,
                   supports=supports or (lambda s: True), batch_run=batch_run,
                   run_with_args=run_with_args,
                   batch_run_with_args=batch_run_with_args,
                   run_fused=run_fused, batch_run_fused=batch_run_fused,
                   run_extend=run_extend, schedule=schedule, cache_tag=tag,
                   env_sensitive=tuple(env_sensitive), doc=doc)


def grid_backend(name: str, jax_fn: Callable, cost: Callable,
                 supports: Optional[Callable] = None,
                 jax_arg_fn: Optional[Callable] = None,
                 cache_tag: Optional[Callable] = None,
                 schedule: Optional[Callable] = None,
                 env_sensitive: tuple = (),
                 run_extend: Optional[Callable] = None,
                 doc: str = "") -> Backend:
    """Wrap a grid wavefront solver ``fn(arrs, meta)`` — ``arrs`` the
    spec's ``device_arrays()`` slot tuple, ``meta`` its hashable
    ``static_meta()`` — with a vmapped batch path. Instances sharing a
    shape_key share ``meta`` and array shapes, so the batch runner stacks
    each slot and vmaps over all of them in one jitted call (slot count is
    schedule-dependent; the single leading ``in_specs`` prefix of a sharded
    context's ``wrap`` covers any arity). ``jax_arg_fn`` (same signature,
    returns ``(st, args)``) adds the arg-capability pair; ``supports`` and
    ``cache_tag`` as in :func:`linear_backend`."""
    import jax
    import jax.numpy as jnp

    tag = _cache_tagger(cache_tag)

    def run(spec) -> np.ndarray:
        arrs = tuple(jnp.asarray(a) for a in spec.device_arrays())
        return np.asarray(jax_fn(arrs, spec.static_meta()))

    def _batch(fn, specs, key, sharding=None):
        spec0 = specs[0]
        meta = spec0.static_meta()
        source = common_source(specs)
        planes = (plane_builder(source) if source is not None
                  else lambda a, meta: a)

        def build():
            def call(*stacked):
                log_trace(key)
                return jax.vmap(lambda *a: fn(planes(a, meta), meta))(
                    *stacked)

            if sharding is None:
                return jax.jit(call)
            return sharding.wrap(call)

        cached = lru_cached(_BATCH_CACHE, key, build, _BATCH_CACHE_MAX)
        if source is not None:
            return launch(cached, *stack_sources(specs, sharding))
        return launch(cached, *stack_slots(specs, lambda s: s.device_arrays(),
                                           sharding))

    def _batch_key(specs, sharding) -> tuple:
        """The program's cache key; a bucket whose planes the program
        builds (:func:`common_source`) names the builder in it."""
        shard_tag = sharding.cache_suffix() if sharding is not None else ()
        source = common_source(specs)
        source_tag = () if source is None else (("source", source),)
        return (name, specs[0].shape_key()) + tag() + shard_tag + source_tag

    def batch_run(specs, sharding=None) -> list:
        table, = fetch(_batch(jax_fn, specs, _batch_key(specs, sharding),
                              sharding))
        return list(table)

    run_with_args = batch_run_with_args = None
    if jax_arg_fn is not None:
        def run_with_args(spec):
            arrs = tuple(jnp.asarray(a) for a in spec.device_arrays())
            st, args = jax_arg_fn(arrs, spec.static_meta())
            return np.asarray(st), np.asarray(args)

        def batch_run_with_args(specs, sharding=None):
            sts, argss = fetch(*_batch(
                jax_arg_fn, specs, _batch_key(specs, sharding) + ("args",),
                sharding))
            return list(sts), list(argss)

    return Backend(name=name, geometry="grid", run=run, cost=cost,
                   supports=supports or (lambda s: True),
                   batch_run=batch_run, run_with_args=run_with_args,
                   batch_run_with_args=batch_run_with_args,
                   run_extend=run_extend, schedule=schedule, cache_tag=tag,
                   env_sensitive=tuple(env_sensitive), doc=doc)


# shared cost vocabulary -----------------------------------------------------
# The per-family step-count tables live on the spec classes
# (``Spec.route_costs()``, repro.dp.problem) — one hook per family instead
# of one function per family here. The named wrappers below are the stable
# entry points the registering solver modules and the docs reference.
def route_costs(spec: Spec) -> dict:
    """Analytical step-count costs of every named route of ``spec``'s
    family (the family's ``route_costs`` hook). Units are 'vectorized
    device steps'; calibration overwrites them with measured timings."""
    return spec.route_costs()


def linear_costs(spec: LinearSpec) -> dict:
    """Linear-family route costs (``LinearSpec.route_costs``)."""
    return spec.route_costs()


def triangular_costs(spec: TriangularSpec) -> dict:
    """Triangular-family route costs (``TriangularSpec.route_costs``)."""
    return spec.route_costs()


def grid_costs(spec) -> dict:
    """Grid-family route costs (``GridSpec.route_costs``)."""
    return spec.route_costs()


# shape-key plumbing for the calibration layer (repro.dp.autotune) ----------
#: measurement-regime markers a calibration key may be suffixed with:
#: ``batch`` = amortized per-instance ms observed from a vmapped bucket
#: drain, ``reconstruct`` = the arg-emitting solve. Sharded drains
#: (repro.dp.sharding) append a tuple marker ``("shard", ndev)`` — or
#: ``("shard", ndev, "reconstruct")`` for sharded arg-emitting drains — so
#: multi-device amortization never shares entries with any single-device
#: regime. Plain keys hold single-instance offline timings. ``extend`` marks
#: warm-start extension solves (DESIGN.md §11): an extend pays O(extension)
#: steps, so its timings must never transfer onto cold-solve keys (or vice
#: versa). The regimes never cross-match.
SHAPE_KEY_REGIMES = ("batch", "reconstruct", "extend")


def is_regime_marker(x) -> bool:
    """Whether ``x`` is a measurement-regime marker (string or the sharded
    tuple form)."""
    if x in SHAPE_KEY_REGIMES:
        return True
    return isinstance(x, tuple) and len(x) >= 2 and x[0] == "shard"


def split_shape_key(key: tuple) -> tuple:
    """``(geometric_key, regime_marker_or_None)`` of a calibration key."""
    if key and is_regime_marker(key[-1]):
        return key[:-1], key[-1]
    return key, None


def shape_key_size(key: tuple) -> int:
    """The table size encoded in a ``Spec.shape_key()`` (the family's
    ``shape_key_size`` hook — table length n for the 1-D families,
    rows·cols for grids)."""
    key, _ = split_shape_key(key)
    return family_class(key[0]).shape_key_size(key)


def shape_key_distance(a: tuple, b: tuple) -> Optional[float]:
    """How far apart two shape_keys are for nearest-shape calibration
    transfer: ``None`` when a measurement cannot transfer at all —
    different family (never scale a linear timing onto a grid route),
    different measurement regimes (amortized batch, reconstruct, and
    single-instance timings are incomparable), or structure the family's
    ``shape_key_compatible`` hook rejects (op, offsets, weightedness,
    schedule, planes, moves — anything that changes the traced program,
    not just its size) — else the table-size gap."""
    a, regime_a = split_shape_key(a)
    b, regime_b = split_shape_key(b)
    if regime_a != regime_b or a[0] != b[0]:
        return None
    cls = family_class(a[0])
    if not cls.shape_key_compatible(a, b):
        return None
    return float(abs(cls.shape_key_size(a) - cls.shape_key_size(b)))


def spec_from_shape_key(key: tuple) -> Spec:
    """Phantom spec carrying exactly the structure the cost models read —
    lets the analytical model price a calibration entry's shape without the
    original instance, which is what autotune's nearest-shape interpolation
    uses as its scaling prior. Regime suffixes are stripped — the cost
    models only read the geometric part. Per-family construction is the
    ``from_shape_key`` hook."""
    key, _ = split_shape_key(key)
    return family_class(key[0]).from_shape_key(key)
