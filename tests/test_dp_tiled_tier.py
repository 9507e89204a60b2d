"""The HBM-streaming tiled kernel tier (DESIGN.md §4/§5, PR 7).

Property sweeps assert BIT-equality — tables AND args AND decoded
solutions — of the tiled routes against the plain jnp solvers across
ragged n/tile combos, including instances far beyond an (overridden-small)
VMEM budget; the fused-traceback tests assert via TRACE_LOG that
``reconstruct=True`` on a tiled route traces ONE launch, not a solve plus
a separate traceback program.
"""
import zlib

import numpy as np
import pytest

import repro.dp as dp
from repro.core.mcm import (solve_wavefront_tab_with_args,
                            triangular_traceback_np)
from repro.core.sdp import solve_blocked, solve_blocked_with_args
from repro.dp import backends as _backends
from repro.dp import routing as _routing
from repro.kernels import ops
from repro.kernels.mcm_tiled import (mcm_tiled_pallas_fused,
                                     mcm_tiled_pallas_with_args)
from repro.kernels.sdp_pipeline import (sdp_chunked_pallas,
                                        sdp_chunked_pallas_with_args)


def _rng(tag: str) -> np.random.Generator:
    return np.random.default_rng(zlib.crc32(tag.encode()))


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setenv("REPRO_KERNELS", "interpret")


@pytest.fixture
def tiny_budget(monkeypatch):
    """Force a VMEM budget far below any real table so the tiled windows
    shrink to a handful of cells — every instance is 'beyond-VMEM'."""
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "2048")


# ---------------------------------------------------------------------------
# the REPRO_VMEM_BUDGET knob (satellite: env-configurable budget)
# ---------------------------------------------------------------------------
def test_vmem_budget_env_knob(monkeypatch):
    monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
    assert ops.vmem_budget_bytes() == ops.DEFAULT_VMEM_BUDGET_BYTES
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "65536")
    assert ops.vmem_budget_bytes() == 65536
    for bad in ("8MiB", "", "-1", "0"):
        monkeypatch.setenv("REPRO_VMEM_BUDGET", bad)
        with pytest.raises(ValueError, match="REPRO_VMEM_BUDGET"):
            ops.vmem_budget_bytes()


def test_vmem_budget_folds_into_cache_tag_and_platform_key(monkeypatch):
    from repro.dp.autotune import _jax_backend

    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
    base_platform = _jax_backend()
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "4096")
    assert "vmem4096" in _jax_backend()
    assert _jax_backend() != base_platform

    # the batch-jit trace key must carry the override too
    prob = dp.get_problem("mcm")
    spec = prob.encode(**prob.sample(_rng("tag"), 6))
    _backends.drain_trace_log()
    _backends.get("kernel_tiled_wavefront").batch_run_with_args([spec, spec])
    log = _backends.drain_trace_log()
    assert log and all(("vmem", 4096) in key for key in log), log


def test_vmem_budget_resizes_kernel_eligibility(monkeypatch):
    """The resident kernels' supports() gate reads the knob: a tiny budget
    rejects shapes the default accepts; the tiled routes never reject."""
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    spec = dp.LinearSpec(
        offsets=(4, 2, 1), op="min", n=2048,
        init=np.zeros(4, np.float32),
        weights=np.zeros((2048, 3), np.float32))
    monkeypatch.delenv("REPRO_VMEM_BUDGET", raising=False)
    assert _backends.get("kernel_blocked").supports(spec)
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "2048")
    assert not _backends.get("kernel_blocked").supports(spec)
    assert _backends.get("kernel_tiled").supports(spec)
    tri = dp.TriangularSpec(
        n=64, weights=np.zeros((64 * 65 // 2, 63), np.float32))
    assert not _backends.get("kernel_wavefront").supports(tri)
    assert _backends.get("kernel_tiled_wavefront").supports(tri)


# ---------------------------------------------------------------------------
# triangular tiled kernel: bit-equality property sweep
# ---------------------------------------------------------------------------
TRI_CASES = [(2, 1), (3, 3), (5, 2), (8, None), (13, 5), (21, 2), (21, 20)]


def test_tiled_triangular_bit_equal_sweep(interpret_mode):
    """Tables AND args of the tiled kernel equal the jnp wavefront solver
    bit-for-bit across ragged n × split-tile combos (tiles that divide a
    diagonal's splits, tiles that straddle them, single-split tiles)."""
    for n, tile_e in TRI_CASES:
        rng = _rng(f"tri/{n}/{tile_e}")
        cells = n * (n + 1) // 2
        wtab = rng.standard_normal((cells, max(n - 1, 1))).astype(np.float32)
        ref_st, ref_ar = solve_wavefront_tab_with_args(wtab, n)
        st, ar = mcm_tiled_pallas_with_args(wtab, n, interpret=True,
                                            tile_e=tile_e)
        assert np.array_equal(np.asarray(ref_st), np.asarray(st)), (n, tile_e)
        assert np.array_equal(np.asarray(ref_ar), np.asarray(ar)), (n, tile_e)


def test_tiled_triangular_beyond_budget(interpret_mode, tiny_budget):
    """n whose dense weight slab is far past the (tiny) budget still solves
    bit-identically — the whole point of the HBM-resident tier."""
    n = 24
    tri = dp.TriangularSpec(
        n=n, weights=_rng("beyond").standard_normal(
            (n * (n + 1) // 2, n - 1)).astype(np.float32))
    assert not _backends.get("kernel_wavefront").supports(tri)
    b = _backends.get("kernel_tiled_wavefront")
    assert b.supports(tri)
    ref_st, ref_ar = solve_wavefront_tab_with_args(tri.weights, n)
    st, ar = b.run_with_args(tri)
    assert np.array_equal(np.asarray(ref_st), st)
    assert np.array_equal(np.asarray(ref_ar), ar)


def test_tiled_fused_traceback_matches_host_walk(interpret_mode):
    """The in-kernel preorder walk reproduces triangular_traceback_np
    node-for-node, ties included (integer weights force them)."""
    for n in (2, 5, 9, 14):
        rng = _rng(f"fused/{n}")
        cells = n * (n + 1) // 2
        wtab = rng.integers(0, 3, (cells, max(n - 1, 1))).astype(np.float32)
        st, ar, (ii, dd, ee) = mcm_tiled_pallas_fused(wtab, n, interpret=True)
        ref_st, ref_ar = solve_wavefront_tab_with_args(wtab, n)
        assert np.array_equal(np.asarray(ref_st), np.asarray(st))
        assert np.array_equal(np.asarray(ref_ar), np.asarray(ar))
        nodes = np.stack([np.asarray(ii), np.asarray(dd), np.asarray(ee)],
                         axis=1)
        ref_nodes = triangular_traceback_np(np.asarray(ref_ar), n)
        assert np.array_equal(ref_nodes, nodes), n


def test_tiled_decoded_solutions_match(interpret_mode):
    """Problem-level decode through the tiled route equals the plain
    wavefront route's: same trees, same optimum."""
    for name in ("mcm", "optimal_bst", "polygon_triangulation"):
        prob = dp.get_problem(name)
        inst = prob.sample(_rng(f"decode/{name}"), 9)
        a_ref = _routing.solve(prob, backend="wavefront",
                               reconstruct=True, **inst)
        a_til = _routing.solve(prob, backend="kernel_tiled_wavefront",
                               reconstruct=True, **inst)
        assert np.array_equal(a_ref.table, a_til.table), name
        assert np.array_equal(a_ref.args, a_til.args), name
        assert a_ref.solution == a_til.solution, name
        assert a_ref.value == a_til.value, name


# ---------------------------------------------------------------------------
# linear chunked kernel: bit-equality property sweep
# ---------------------------------------------------------------------------
LIN_CASES = [((3, 1), 5, 512, 1), ((3, 1), 64, 2, 7), ((5, 3, 2), 129, 1, 3),
             ((5, 3, 2), 300, 512, 64), ((7, 4, 1), 17, 512, 1),
             ((4, 3, 2, 1), 64, 512, 3), ((16, 8, 3), 129, 512, 7)]


def test_chunked_linear_bit_equal_sweep(interpret_mode):
    for offsets, n, block, chunk in LIN_CASES:
        rng = _rng(f"lin/{offsets}/{n}/{block}/{chunk}")
        init = rng.standard_normal(offsets[0]).astype(np.float32)
        w = rng.standard_normal((n, len(offsets))).astype(np.float32)
        for weights in (None, w):
            ref = solve_blocked(init, offsets, "min", n, block=block,
                                weights=weights)
            got = sdp_chunked_pallas(init, offsets, "min", n, block=block,
                                     chunk=chunk, weights=weights,
                                     interpret=True)
            assert np.array_equal(np.asarray(ref), np.asarray(got)), \
                (offsets, n, block, chunk, weights is not None)
            ref_st, ref_ar = solve_blocked_with_args(
                init, offsets, "min", n, block=block, weights=weights)
            st, ar = sdp_chunked_pallas_with_args(
                init, offsets, "min", n, block=block, chunk=chunk,
                weights=weights, interpret=True)
            assert np.array_equal(np.asarray(ref_st), np.asarray(st))
            assert np.array_equal(np.asarray(ref_ar), np.asarray(ar))


def test_chunked_linear_beyond_budget_route(interpret_mode, tiny_budget):
    """A linear instance past the (tiny) budget routes through kernel_tiled
    bit-identically to solve_blocked, decoded solution included."""
    prob = dp.get_problem("edit_distance")
    inst = prob.sample(_rng("lin-beyond"), 300)
    spec = prob.encode(**inst)
    assert not _backends.get("kernel_blocked").supports(spec)
    assert _backends.get("kernel_tiled").supports(spec)
    a_ref = _routing.solve(prob, backend="blocked", reconstruct=True, **inst)
    a_til = _routing.solve(prob, backend="kernel_tiled",
                           reconstruct=True, **inst)
    assert np.array_equal(a_ref.table, a_til.table)
    assert np.array_equal(a_ref.args, a_til.args)
    assert a_ref.solution == a_til.solution


# ---------------------------------------------------------------------------
# fused = ONE launch (satellite: TRACE_LOG single-dispatch assertion)
# ---------------------------------------------------------------------------
def test_reconstruct_on_tiled_route_is_one_fused_launch(interpret_mode):
    """reconstruct=True on the tiled triangular route traces exactly one
    fused program — no separate ("traceback", ...) program ever compiles,
    unlike the non-fused kernel_wavefront route."""
    prob = dp.get_problem("mcm")
    insts = [prob.sample(_rng(f"one-launch/{i}"), 7) for i in range(3)]

    _backends.drain_trace_log()
    answers = _routing.batch_solve(prob, insts,
                                   backend="kernel_tiled_wavefront",
                                   reconstruct=True)
    log = _backends.drain_trace_log()
    solve_keys = [k for k in log if isinstance(k, tuple)
                  and k and k[0] == "kernel_tiled_wavefront"]
    assert len(solve_keys) == 1 and "fused" in solve_keys[0], log
    assert not any(isinstance(k, tuple) and k and k[0] == "traceback"
                   for k in log), log

    # contrast: the non-fused kernel route pays the second (traceback) trace
    _routing.batch_solve(prob, insts, backend="kernel_wavefront",
                         reconstruct=True)
    log2 = _backends.drain_trace_log()
    assert any(isinstance(k, tuple) and k and k[0] == "traceback"
               for k in log2), log2

    # and the fused answers are the real ones
    ref = _routing.batch_solve(prob, insts, backend="wavefront",
                               reconstruct=True)
    for x, y in zip(ref, answers):
        assert np.array_equal(x.table, y.table)
        assert np.array_equal(x.args, y.args)
        assert x.solution == y.solution


def test_fused_single_solve_uses_run_fused(interpret_mode, monkeypatch):
    """Single-instance reconstruct=True on the tiled route also stays one
    dispatch (Backend.run_fused): the reconstruction layer never gets to
    issue its own traceback — poison both walkers to prove it."""
    from repro.dp import reconstruct as _reconstruct

    prob = dp.get_problem("mcm")
    inst = prob.sample(_rng("single-fused"), 6)
    ref = _routing.solve(prob, backend="wavefront", reconstruct=True, **inst)

    def _boom(*a, **kw):
        raise AssertionError("fused route must not issue a traceback dispatch")

    monkeypatch.setattr(_reconstruct, "traceback_host", _boom)
    monkeypatch.setattr(_reconstruct, "traceback_batch", _boom)
    ans = _routing.solve(prob, backend="kernel_tiled_wavefront",
                         reconstruct=True, **inst)
    assert np.array_equal(ref.table, ans.table)
    assert np.array_equal(ref.args, ans.args)
    assert ref.solution == ans.solution


# ---------------------------------------------------------------------------
# engine integration: fused paths thread through bucket drains
# ---------------------------------------------------------------------------
def test_engine_drain_through_fused_route(interpret_mode):
    eng = dp.DPEngine(max_batch=8)
    prob = dp.get_problem("mcm")
    insts = [prob.sample(_rng(f"eng/{i}"), 6) for i in range(4)]
    rids = [eng.submit("mcm", reconstruct=True, **inst) for inst in insts]
    _backends.drain_trace_log()
    resp = eng.step(backend="kernel_tiled_wavefront")
    log = _backends.drain_trace_log()
    assert not any(isinstance(k, tuple) and k and k[0] == "traceback"
                   for k in log), log
    assert len(resp) == 4
    ref = _routing.batch_solve(prob, insts, backend="wavefront",
                               reconstruct=True)
    by_rid = {r.rid: r for r in resp}
    for rid, x in zip(rids, ref):
        y = by_rid[rid].solution
        assert np.array_equal(x.table, y.table)
        assert x.solution == y.solution
