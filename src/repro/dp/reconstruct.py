"""Solution reconstruction: arg tables → tracebacks → decoded answers.

The solve contract (DESIGN.md §5) has three stages:

  1. *args* — the per-cell winning argument (lane index for linear specs,
     split offset for triangular ones, move/packed-rule index for grids).
     Arg-capable backends emit it device-side alongside the cost table
     (``Backend.run_with_args``) — including the Pallas kernel tier, whose
     arg stores are bit-identical to the jnp solvers' (DESIGN.md §4/§5);
     for routes that only return costs, :func:`args_from_table` recovers it
     on the host by re-ranking each cell's candidates against the finished
     table.
  2. *path* — the argument structure actually used by the optimum: a lane
     walk (:class:`LinearPath`), a split tree in preorder
     (:class:`TriangularPath`), or a move walk / rule tree
     (:class:`GridPath`). :func:`traceback_batch` walks a whole same-shape
     batch in ONE jitted vmapped ``lax.scan`` when the args came from the
     device, and falls back to per-instance host walks otherwise.
  3. *decode* — ``DPProblem.decode(table, args, spec, path)`` turns the path
     into the problem-level answer (parenthesization tree, alignment ops,
     state path, item multiset, parse tree, …); :func:`reconstruct_one`
     wraps it all in an :class:`Answer`.

Every family-specific step is a hook on the spec class (DESIGN.md §3):
``supports_args``/``args_unsupported_reason`` (admission),
``args_from_table`` (host fallback), ``uses_start``/``default_start``
(traceback entry points), ``traceback_host`` (per-instance walk), and
``traceback_program`` (the batched device walk). This module owns only the
family-agnostic plumbing: admission, caching, start-cell resolution,
batching, and the profiler spans of the walk and the decode.

Traceback programs are cached per shape and append a
``("traceback", …)`` entry to ``backends.TRACE_LOG`` at trace time, so
tests can assert the one-program-per-bucket property for reconstruction
exactly as they do for solves.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from collections import OrderedDict

from repro.dp import backends as _backends
from repro.dp.problem import Answer, DPProblem, Path, Spec

#: jit-callable cache for batched tracebacks, LRU-bounded like
#: ``backends._BATCH_CACHE`` so long-running engines stay bounded.
_TRACEBACK_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_TRACEBACK_CACHE_MAX = 64


def supports_args(spec: Spec) -> bool:
    """Whether argument tracking is defined for this spec (the family's
    ``supports_args`` hook — e.g. linear specs need a selective semigroup)."""
    return spec.supports_args()


def check_reconstructable(prob: DPProblem, spec: Spec) -> None:
    """Raise ValueError unless ``reconstruct=True`` is admissible for this
    (problem, instance) — THE admission check both the engine and the
    service run, so a request rejected at either door is rejected for the
    same reasons with the same message."""
    if prob.decode is None:
        raise ValueError(f"problem {prob.name!r} does not define decode()")
    if not spec.supports_args():
        raise ValueError(
            f"problem {prob.name!r} instance has no argument structure "
            f"to reconstruct ({spec.args_unsupported_reason()})")


def args_from_table(table: np.ndarray, spec: Spec) -> np.ndarray:
    """Numpy fallback: winning-argument table recomputed from a finished cost
    table (backends that only return costs)."""
    return spec.args_from_table(table)


def start_cell(prob: DPProblem, table: np.ndarray, spec: Spec) -> int:
    """Traceback entry point: the problem's ``start`` hook (e.g. Viterbi's
    argmax over the last trellis row, Gotoh's argmax over planes) or the
    family default (last cell / far corner / root span)."""
    if prob.start is not None:
        return int(prob.start(table, spec))
    return int(spec.default_start(table))


def traceback_host(args: np.ndarray, spec: Spec, start: int = -1) -> Path:
    """Per-instance host walk (numpy; the family's ``traceback_host``)."""
    return spec.traceback_host(args, start)


def traceback_batch(argss: Sequence[np.ndarray], spec0: Spec,
                    starts: Optional[Sequence[int]] = None) -> list:
    """Device-side batched traceback: one jitted vmapped scan walks every arg
    table of a same-shape batch. The family's ``traceback_program`` hook
    supplies ``(key, build, post)``; the callable is cached here per key and
    tracing appends the key to ``backends.TRACE_LOG``."""
    key, build, post = spec0.traceback_program()
    walk = _backends.lru_cached(_TRACEBACK_CACHE, key, build,
                                _TRACEBACK_CACHE_MAX)
    return post(walk, argss, starts)


def reconstruct_one(prob: DPProblem, spec: Spec, table: np.ndarray,
                    args: np.ndarray, source: str,
                    path: Optional[Path] = None) -> Answer:
    """Assemble an :class:`Answer`; runs a host traceback when no path is
    supplied (the batched engine path passes device-walked paths in)."""
    if prob.decode is None:
        raise NotImplementedError(
            f"problem {prob.name!r} does not define decode()")
    if path is None:
        start = start_cell(prob, table, spec) if spec.uses_start else -1
        path = traceback_host(args, spec, start)
    solution = prob.decode(table, args, spec, path)
    return Answer(value=prob.extract(table, spec), solution=solution,
                  table=table, args=args, source=source)


def reconstruct_batch(prob: DPProblem, specs: Sequence[Spec],
                      tables: Sequence[np.ndarray],
                      argss: Sequence[np.ndarray], source: str,
                      paths: Optional[Sequence[Path]] = None) -> list:
    """Batch assembly. Device-sourced args are walked by ONE vmapped
    traceback program; host-sourced args fall back to host walks; fused
    routes pass the in-launch-walked ``paths`` in and skip the traceback
    dispatch entirely. The walk and the decode loop run inside the
    profiler spans ``dp.traceback`` / ``dp.decode``."""
    from repro.dp import telemetry as _telemetry

    spec0 = specs[0]
    with _telemetry.trace_span("dp.traceback"):
        if paths is not None:
            paths = list(paths)
        elif source == "device":
            starts = None
            if spec0.uses_start:
                starts = [start_cell(prob, t, s)
                          for t, s in zip(tables, specs)]
            paths = traceback_batch(argss, spec0, starts)
        else:
            paths = [traceback_host(a, s, start_cell(prob, t, s)
                                    if s.uses_start else -1)
                     for a, s, t in zip(argss, specs, tables)]
    with _telemetry.trace_span("dp.decode"):
        return [reconstruct_one(prob, s, t, a, source, path=p)
                for s, t, a, p in zip(specs, tables, argss, paths)]
