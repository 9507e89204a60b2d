"""repro.dp — the declarative DP problem zoo and multi-solver engine.

Layers (DESIGN.md §3, §5):

  problem     — the spec-family protocol (FAMILIES registry, hook table,
                DESIGN.md §3) + LinearSpec / TriangularSpec / GridSpec
                canonical forms, DPProblem, Answer / LinearPath /
                TriangularPath / GridPath reconstruction types
  registry    — name -> DPProblem (the zoo populates it at import)
  backends    — solver routes registered by core/sdp, core/mcm,
                core/blocked_mcm, core/grid and kernels at their import
  zoo         — edit_distance, lcs, viterbi, unbounded_knapsack, mcm,
                optimal_bst, polygon_triangulation, sdp, and the grid
                family: needleman_wunsch, gotoh, cky, edit_distance_grid,
                lcs_grid (all decodable)
  autotune    — measured-latency calibration tables; calibrate() /
                routing_report(); the engine's online feedback sink
  routing     — two-tier (measured > analytical) dispatch + single-call
                vmapped batch_solve
  reconstruct — arg tables → batched tracebacks → decoded Answers
  engine      — DPEngine: bucketed request/response serving front end,
                folding realized drain latencies back into autotune
  sharding    — ShardContext / ShardedDPEngine: bucket drains shard_mapped
                over a device mesh, observed under the ("shard", ndev)
                regime
  streaming   — ResumeToken / resume_solve warm starts + the chain-digest
                longest-prefix answer cache (PrefixIndex); extends a
                solved prefix bit-identically to the cold solve
                (DESIGN.md §11)
  service     — DPService: submit/poll handles, admission control with
                deadlines/priorities, content-digest answer cache,
                streaming sessions (open_session/append/close_session),
                the continuous scheduling loop (DESIGN.md §7, §11)
  telemetry   — the dp.* profiler spans (the one timing layer) and the
                repro.dp logger (DESIGN.md §8); the counts live in the
                engine's and the service's ``stats``

Quickstart::

    from repro import dp
    d = dp.solve("edit_distance", x=[1, 2, 3], y=[1, 3])
    ans = dp.solve("mcm", dims=[30, 35, 15, 5], reconstruct=True)
    ans.value, ans.solution["string"]   # 'cost', '((A0·A1)·A2)'
    eng = dp.DPEngine(max_batch=32)
    rids = [eng.submit("mcm", reconstruct=True, dims=d) for d in batches]
    answers = eng.run()
    svc = dp.DPService(max_batch=32)        # shards when >1 device visible
    tid = svc.submit("mcm", dims=[30, 35, 15, 5], priority=1)
    res = svc.run()[tid]                    # res.answer, res.backend
"""
from repro.dp import autotune, backends, reconstruct, registry, routing, zoo  # noqa: F401
from repro.dp.autotune import calibrate, routing_report  # noqa: F401
from repro.dp.routing import batch_solve, batch_solve_specs, dispatch, solve, solve_spec  # noqa: F401
route = dispatch
from repro.dp.engine import DPEngine, DPRequest, DPResponse  # noqa: F401
from repro.dp.problem import (  # noqa: F401
    Answer, DPProblem, GridPath, GridSpec, LinearPath, LinearSpec, Spec,
    TriangularPath, TriangularSpec, spec_digest)
from repro.dp.registry import get as get_problem  # noqa: F401
from repro.dp.registry import names as problem_names  # noqa: F401
from repro.dp.registry import problems  # noqa: F401
from repro.dp.service import AdmissionError, DPService, ServiceResult, Session  # noqa: F401
from repro.dp.sharding import ShardContext, ShardedDPEngine  # noqa: F401
from repro.dp.streaming import PrefixIndex, ResumeToken, resume_solve  # noqa: F401
from repro.dp import service, sharding, streaming, telemetry  # noqa: F401

__all__ = [
    "AdmissionError", "Answer", "DPEngine", "DPProblem", "DPRequest",
    "DPResponse", "DPService", "GridPath", "GridSpec", "LinearPath",
    "LinearSpec", "PrefixIndex", "ResumeToken", "ServiceResult", "Session",
    "ShardContext", "ShardedDPEngine", "Spec", "TriangularPath",
    "TriangularSpec", "autotune", "backends", "batch_solve",
    "batch_solve_specs", "calibrate", "dispatch", "route", "get_problem",
    "problem_names", "problems", "reconstruct", "registry", "resume_solve",
    "routing", "routing_report", "service", "sharding", "solve",
    "solve_spec", "spec_digest", "streaming", "telemetry", "zoo",
]
