"""MCM tests — Fig. 8 pipeline, Lemmas 1-2 / Theorem 1, the schedule-hazard
finding, and the beyond-paper blocked solver."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import blocked_mcm, mcm

rng = np.random.default_rng(0)


def random_dims(n, lo=1, hi=30, seed=None):
    r = np.random.default_rng(seed)
    return r.integers(lo, hi, size=n + 1).astype(np.float64)


def _mcm_reference_loop(dims):
    """The triple loop the vectorized oracle replaced (same float64 ops)."""
    p = np.asarray(dims, dtype=np.float64)
    n = len(p) - 1
    m = np.zeros((n, n))
    split = np.full((n, n), -1, dtype=np.int64)
    for d in range(1, n):
        for i in range(n - d):
            j = i + d
            best, bs = np.inf, -1
            for s in range(i, j):
                c = m[i, s] + m[s + 1, j] + p[i] * p[s + 1] * p[j + 1]
                if c < best:
                    best, bs = c, s
            m[i, j] = best
            split[i, j] = bs
    return m, split


@pytest.mark.parametrize("n,hi", [(1, 30), (2, 30), (17, 30), (40, 3)])
def test_mcm_reference_matches_loop(n, hi):
    """Vectorized oracle == the loop, tables and first-occurrence splits
    (hi=3 forces ties)."""
    dims = random_dims(n, hi=hi, seed=n)
    m, split = mcm.mcm_reference(dims)
    m_loop, split_loop = _mcm_reference_loop(dims)
    assert np.array_equal(m, m_loop)
    assert np.array_equal(split, split_loop)


# ---------------------------------------------------------------------------
# Linearization
# ---------------------------------------------------------------------------
def test_linearization_bijective():
    n = 9
    seen = set()
    for d in range(n):
        for i in range(n - d):
            c = mcm.lin_index(i, d, n)
            assert 0 <= c < mcm.num_cells(n)
            assert c not in seen
            seen.add(c)
            assert mcm.diag_of(c, n) == d
    assert len(seen) == mcm.num_cells(n)


def test_paper_fig5_cell13():
    """Paper: ST[13] (1-based) = f(ST[1],ST[11]) ↓ f(ST[6],ST[8]) ↓ f(ST[10],ST[4]).
    0-based: cell 12 reads (0,10), (5,7), (9,3)."""
    n = 5
    t = mcm.build_pipeline_tables(np.ones(n + 1), order="paper")
    c = mcm.lin_index(0, 3, n)  # (1,4) 1-based == cell 13 1-based == 12 0-based
    assert c == 12
    pairs = {(int(t.left[c, j]), int(t.right[c, j])) for j in range(int(t.k[c]))}
    assert pairs == {(0, 10), (5, 7), (9, 3)}


# ---------------------------------------------------------------------------
# The schedule-hazard finding (see mcm.py docstring / DESIGN.md)
# ---------------------------------------------------------------------------
def test_paper_order_hazard():
    """The literal Fig.-8 candidate order violates operand finalization for
    n ≥ 5 and produces inflated costs on random instances."""
    t = mcm.build_pipeline_tables(random_dims(8, seed=1), order="paper")
    assert not t.feasible
    mismatch = 0
    for s in range(25):
        dims = random_dims(6, seed=100 + s)
        st, stats = mcm.solve_pipeline_np(dims, order="paper", check_conflicts=True)
        assert stats["max_write_dup"] == 1  # Theorem 1 holds regardless
        ref = mcm.reference_linear(dims)
        if not np.allclose(st, ref):
            mismatch += 1
            assert np.all(st >= ref - 1e-9)  # partial reads only inflate
    assert mismatch > 0


def test_safe_order_is_feasible_and_exact():
    for n in (2, 3, 5, 8, 13, 21):
        dims = random_dims(n, seed=n)
        t = mcm.build_pipeline_tables(dims, order="safe")
        assert t.feasible, n
        st, stats = mcm.solve_pipeline_np(dims, order="safe", check_conflicts=True)
        assert stats["dependency_violations"] == 0
        assert stats["max_write_dup"] == 1  # write distinctness survives
        np.testing.assert_allclose(st, mcm.reference_linear(dims))


def test_theorem1_paper_order_distinct_reads():
    """Lemmas 1-2: under the paper's candidate order, reads are also distinct."""
    dims = random_dims(10, seed=3)
    _, stats = mcm.solve_pipeline_np(dims, order="paper", check_conflicts=True)
    assert stats["max_read_dup"] == 1
    assert stats["max_write_dup"] == 1


# ---------------------------------------------------------------------------
# JAX solvers vs oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 3, 4, 7, 12, 20, 33])
def test_wavefront_matches_oracle(n):
    dims = random_dims(n, seed=n)
    got = np.asarray(mcm.solve_wavefront(jnp.asarray(dims), n))
    np.testing.assert_allclose(got, mcm.reference_linear(dims), rtol=1e-6)


@pytest.mark.parametrize("n", [2, 3, 5, 9, 16, 24])
def test_jax_pipeline_matches_oracle(n):
    dims = random_dims(n, seed=n + 50)
    got = mcm.solve_mcm_pipeline(dims, order="safe")
    np.testing.assert_allclose(got, mcm.reference_linear(dims), rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(n=st.integers(2, 16), seed=st.integers(0, 2**31 - 1))
def test_property_pipeline_equals_wavefront(n, seed):
    dims = random_dims(n, seed=seed)
    pipe = mcm.solve_mcm_pipeline(dims, order="safe")
    wave = np.asarray(mcm.solve_wavefront(jnp.asarray(dims), n))
    np.testing.assert_allclose(pipe, wave, rtol=1e-6)


def test_pipeline_step_count_claim():
    """§IV: O(n²) steps — exactly cells + (n-1) - 1 - n head positions."""
    for n in (5, 8, 13):
        assert mcm.pipeline_num_steps(n) == mcm.num_cells(n) + n - 2 - n


# ---------------------------------------------------------------------------
# Blocked (tropical GEMM) solver
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n,tile", [(4, 2), (8, 2), (8, 4), (16, 4), (24, 8), (32, 8)])
def test_blocked_matches_oracle(n, tile):
    dims = random_dims(n, seed=7 * n + tile)
    m_ref, _ = mcm.mcm_reference(dims)
    got = np.asarray(blocked_mcm.solve_blocked(jnp.asarray(dims), n, tile))
    iu = np.triu_indices(n)
    np.testing.assert_allclose(got[iu], m_ref[iu], rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(nt=st.integers(2, 5), tile=st.sampled_from([2, 4]), seed=st.integers(0, 10**6))
def test_property_blocked_equals_oracle(nt, tile, seed):
    n = nt * tile
    dims = random_dims(n, seed=seed)
    m_ref, _ = mcm.mcm_reference(dims)
    got = np.asarray(blocked_mcm.solve_blocked(jnp.asarray(dims), n, tile))
    iu = np.triu_indices(n)
    np.testing.assert_allclose(got[iu], m_ref[iu], rtol=1e-6)


def test_gemm_fraction_grows():
    f8 = blocked_mcm.gemm_fraction(64, 8)
    f4 = blocked_mcm.gemm_fraction(64, 16)
    assert 0 < f4 < f8 < 1
