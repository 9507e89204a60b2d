"""Shape-aware routing of specs to solver backends, plus the single-call
batched solve path.

``dispatch(spec)`` ranks the registered backends that support the spec with
a two-tier cost resolution (DESIGN.md §6): *measured* latencies from the
calibration table (``repro.dp.autotune`` — exact entries or nearest-shape
interpolations) come first, and the step-count cost model
(``backends.linear_costs`` vocabulary) is the prior for unmeasured routes
and the tiebreak. With no calibration data the ranking is exactly the
analytical one. ``solve`` / ``solve_spec`` execute the choice;
``batch_solve`` stacks B same-shape instances and issues ONE jitted
vmapped device call (falling back to a loop only when the chosen backend
has no batch path — e.g. the host-side table-building MCM pipeline).

Reconstruction (``reconstruct=True``) threads the arg-tracking contract
through the same routes: dispatch prefers arg-capable backends (those with
``run_with_args``), and ``solve``/``batch_solve`` return :class:`Answer`
objects carrying the decoded solution next to the cost optimum. Backends
without arg output still reconstruct via the numpy from-the-cost-table
fallback in ``repro.dp.reconstruct``. The Pallas kernel tier
(``kernel_blocked``/``kernel_wavefront``, DESIGN.md §4) registers through
the same capability flags, so weighted and arg-emitting solves dispatch
onto the VMEM kernels with no special casing here — kernel eligibility
(VMEM budget, kernel mode) lives entirely in each backend's
``supports``/``cost``.

Validation happens once per call: an explicit ``backend=`` override is
checked against the spec here, while a dispatched backend is trusted —
``backends.candidates`` already ran ``supports()`` on it.
"""
from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

from repro.dp import autotune as _autotune
from repro.dp import backends as _backends
from repro.dp import reconstruct as _reconstruct
from repro.dp import registry as _registry
from repro.dp.problem import DPProblem, Spec


def _resolve(problem: Union[str, DPProblem]) -> DPProblem:
    return _registry.get(problem) if isinstance(problem, str) else problem


#: calibration-key regime markers (see autotune / backends.SHAPE_KEY_REGIMES):
#: arg-emitting solves and amortized bucket drains cost differently from
#: plain single-instance solves and must not share entries
RECONSTRUCT_SUFFIX = ("reconstruct",)
BATCH_SUFFIX = ("batch",)
EXTEND_SUFFIX = ("extend",)


def dispatch(spec_or_problem, reconstruct: bool = False,
             **instance) -> _backends.Backend:
    """Cheapest supporting backend for a spec (or a problem + instance).
    With ``reconstruct`` the cheapest *arg-capable* route wins when one
    exists (host-fallback reconstruction costs an extra table re-rank).
    Both paths rank on plain (single-instance) entries: reconstruct-regime
    entries are batch-amortized engine observations, the wrong figure for a
    single-call caller."""
    if isinstance(spec_or_problem, (str, DPProblem)) or instance:
        spec = _resolve(spec_or_problem).encode(**instance)
    else:
        spec = spec_or_problem
    cands = _backends.candidates(spec)
    if not cands:
        raise RuntimeError(f"no backend supports spec {spec.shape_key()}")
    if reconstruct and _reconstruct.supports_args(spec):
        arg_capable = [b for b in cands if b.run_with_args is not None]
        if arg_capable:
            return _autotune.rank(spec, arg_capable)[0]
    return _autotune.rank(spec, cands)[0]


def batch_candidates(spec: Spec, reconstruct: bool = False,
                     batch_suffix: Optional[tuple] = None,
                     loop_suffix: Optional[tuple] = None) -> list:
    """Ordered route pool for a homogeneous batch. Structural preferences
    come first — arg-capable backends under ``reconstruct``, and
    batchable-before-loop-fallback otherwise — then the measured ranking is
    applied on top (``autotune.rank_batch``: a loop-fallback route can only
    overrule the batching prior on an online-amortized drain measurement,
    never on an offline single-instance timing); with no measurements the
    order is exactly the pre-calibration one. The engine explores
    alternates from exactly this pool.

    ``batch_suffix`` / ``loop_suffix`` select the measurement regimes the
    batchable and loop-fallback pools rank on (defaults: the single-device
    batch/reconstruct regimes). The sharded engine passes its
    ``("shard", ndev)`` regime as ``batch_suffix`` — loop-fallback routes
    execute unsharded there, so they keep ranking on their own regime."""
    cands = _backends.candidates(spec)
    if not cands:
        raise RuntimeError(f"no backend supports spec {spec.shape_key()}")
    if reconstruct and _reconstruct.supports_args(spec):
        for pool, sfx in (
                ([c for c in cands if c.batch_run_with_args is not None],
                 batch_suffix or RECONSTRUCT_SUFFIX),
                ([c for c in cands if c.run_with_args is not None],
                 loop_suffix or RECONSTRUCT_SUFFIX)):
            if pool:
                return _autotune.rank(spec, pool, suffix=sfx)
    batchable = [c for c in cands if c.batch_run is not None]
    loop_only = [c for c in cands if c.batch_run is None]
    return _autotune.rank_batch(spec, batchable, loop_only,
                                batch_suffix=batch_suffix or BATCH_SUFFIX,
                                loop_suffix=loop_suffix or BATCH_SUFFIX)


def select_batch_backend(spec: Spec,
                         reconstruct: bool = False) -> _backends.Backend:
    """Cheapest supporting backend, preferring ones that can batch the
    whole group in one device call (and, under ``reconstruct``, ones that
    emit arg tables device-side)."""
    return batch_candidates(spec, reconstruct=reconstruct)[0]


def resolve_backend(spec: Spec, backend=None, batch: bool = False,
                    reconstruct: bool = False) -> _backends.Backend:
    """Resolve a route exactly once: dispatch (already validated by
    ``candidates()``) or an explicit override (validated here)."""
    if backend is None:
        return (select_batch_backend(spec, reconstruct=reconstruct) if batch
                else dispatch(spec, reconstruct=reconstruct))
    b = backend if isinstance(backend, _backends.Backend) else _backends.get(backend)
    if not (b.geometry == spec.geometry and b.supports(spec)):
        raise ValueError(f"backend {b.name!r} does not support this spec")
    return b


def solve_spec(spec: Spec, backend: Optional[str] = None) -> np.ndarray:
    """Solve one canonical spec; returns the full linearized table."""
    return resolve_backend(spec, backend).run(spec)


def run_with_args(b: _backends.Backend, spec: Spec):
    """Execute a resolved route with arg tracking. Returns
    ``(table, args, source)`` — device-emitted args when the backend can,
    numpy fallback from the cost table otherwise."""
    if b.run_with_args is not None and _reconstruct.supports_args(spec):
        table, args = b.run_with_args(spec)
        return table, args, "device"
    table = b.run(spec)
    return table, _reconstruct.args_from_table(table, spec), "host"


def solve_spec_with_args(spec: Spec, backend: Optional[str] = None):
    """Solve one spec with arg tracking; returns ``(table, args, source)``."""
    return run_with_args(resolve_backend(spec, backend, reconstruct=True), spec)


def solve(problem: Union[str, DPProblem], backend: Optional[str] = None,
          reconstruct: bool = False, **instance):
    """Encode an instance, route it, and return the problem-level answer —
    a plain ``extract`` value, or a full :class:`Answer` under
    ``reconstruct=True``."""
    prob = _resolve(problem)
    spec = prob.encode(**instance)
    if not reconstruct:
        return prob.extract(solve_spec(spec, backend=backend), spec)
    b = resolve_backend(spec, backend, reconstruct=True)
    if b.run_fused is not None and _reconstruct.supports_args(spec):
        # fused route: solve + args + traceback walked in ONE dispatch
        table, args, path = b.run_fused(spec)
        return _reconstruct.reconstruct_one(prob, spec, table, args,
                                            "device", path=path)
    table, args, source = run_with_args(b, spec)
    return _reconstruct.reconstruct_one(prob, spec, table, args, source)


def extend_candidates(spec: Spec) -> list:
    """Extend-capable route pool for an extended spec (DESIGN.md §11):
    backends that both support the spec and declare ``run_extend``, ranked
    on the ``extend`` calibration regime. Warm-start drains recompute only
    the extension region, so their latencies never share entries with cold
    solves (``backends.SHAPE_KEY_REGIMES`` keeps the keys disjoint)."""
    cands = [b for b in _backends.candidates(spec)
             if b.run_extend is not None]
    if not cands:
        return []
    return _autotune.rank(spec, cands, suffix=EXTEND_SUFFIX)


def run_extend(spec: Spec, old_len: int, state, backend=None):
    """Execute a warm-start extension solve on the cheapest extend-capable
    route (or an explicit override, validated here). ``state`` is the
    resume payload from ``prefix.extension_state(...)``; the return is the
    family-shaped extension output (see :class:`backends.Backend`)."""
    if backend is not None:
        b = (backend if isinstance(backend, _backends.Backend)
             else _backends.get(backend))
        if b.run_extend is None or not (b.geometry == spec.geometry
                                        and b.supports(spec)):
            raise ValueError(
                f"backend {b.name!r} cannot extend this spec")
    else:
        cands = extend_candidates(spec)
        if not cands:
            raise RuntimeError(
                f"no extend-capable backend for spec {spec.shape_key()}")
        b = cands[0]
    return b.run_extend(spec, old_len, state)


def run_batch(b: _backends.Backend, specs: Sequence[Spec],
              sharding=None) -> list:
    """Execute a resolved route over a homogeneous batch. ``sharding``
    (a ``repro.dp.sharding.ShardContext``) splits the batch axis over a
    device mesh — only meaningful on batchable routes whose batch size the
    caller already padded to the mesh size."""
    if b.batch_run is not None:
        if sharding is not None:
            return b.batch_run(list(specs), sharding=sharding)
        return b.batch_run(list(specs))
    # loop fallback: the route has no vmapped batch path, so the "batch"
    # executes as B singleton device calls — the pipeline the engine's
    # batching exists to avoid
    return [b.run(s) for s in specs]


def run_batch_with_args(b: _backends.Backend, specs: Sequence[Spec],
                        sharding=None):
    """Batched :func:`run_with_args`; returns
    ``(tables, argss, source, paths)``. Fused routes
    (``batch_run_fused``) walk the traceback inside the solve launch and
    return the paths alongside; everywhere else ``paths`` is ``None`` and
    the reconstruction layer issues its own (second) traceback dispatch."""
    specs = list(specs)
    if _reconstruct.supports_args(specs[0]):
        if b.batch_run_fused is not None:
            if sharding is not None:
                tables, argss, paths = b.batch_run_fused(specs,
                                                         sharding=sharding)
            else:
                tables, argss, paths = b.batch_run_fused(specs)
            return tables, argss, "device", paths
        if b.batch_run_with_args is not None:
            if sharding is not None:
                tables, argss = b.batch_run_with_args(specs, sharding=sharding)
            else:
                tables, argss = b.batch_run_with_args(specs)
            return tables, argss, "device", None
        if b.run_with_args is not None:
            pairs = [b.run_with_args(s) for s in specs]
            return [t for t, _ in pairs], [a for _, a in pairs], "device", None
    tables = run_batch(b, specs)
    argss = [_reconstruct.args_from_table(t, s)
             for t, s in zip(tables, specs)]
    return tables, argss, "host", None


def batch_solve_specs(specs: Sequence[Spec],
                      backend: Optional[str] = None) -> list:
    """Batched solve over homogeneous specs; returns linearized tables."""
    specs = list(specs)
    if not specs:
        return []
    return run_batch(resolve_backend(specs[0], backend, batch=True), specs)


def batch_solve_specs_with_args(specs: Sequence[Spec],
                                backend: Optional[str] = None):
    """Batched arg-tracking solve; returns
    ``(tables, argss, source, paths)`` (``paths`` non-None only on fused
    routes)."""
    specs = list(specs)
    if not specs:
        return [], [], "device", None
    b = resolve_backend(specs[0], backend, batch=True, reconstruct=True)
    return run_batch_with_args(b, specs)


def batch_solve(problem: Union[str, DPProblem],
                instances: Sequence[dict],
                backend: Optional[str] = None,
                reconstruct: bool = False) -> list:
    """Solve B instances of one problem. All instances must share a
    shape_key (the engine's bucketing guarantees this); the whole batch is
    one vmapped device call on the selected backend. Under ``reconstruct``
    the return is a list of :class:`Answer` and the traceback of the whole
    bucket is one additional vmapped device call."""
    prob = _resolve(problem)
    specs = [prob.encode(**kw) for kw in instances]
    if not specs:
        return []
    keys = {s.shape_key() for s in specs}
    if len(keys) > 1:
        raise ValueError(f"heterogeneous batch: {sorted(keys)}; "
                         "bucket by shape_key first (see DPEngine)")
    if not reconstruct:
        tables = batch_solve_specs(specs, backend=backend)
        return [prob.extract(t, s) for t, s in zip(tables, specs)]
    tables, argss, source, paths = batch_solve_specs_with_args(
        specs, backend=backend)
    return _reconstruct.reconstruct_batch(prob, specs, tables, argss, source,
                                          paths=paths)
