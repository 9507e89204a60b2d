"""The plain references on instances worked by hand, and against an
independent cell-by-cell recurrence."""
import ml_dtypes
import numpy as np
import pytest

from reference import gotoh, solutions

MM2 = {"match": 2.0, "mismatch": -4.0, "gap_open": -6.0, "gap_extend": -2.0}


def cellwise_gotoh(x, y, match, mismatch, gap_open, gap_extend):
    m, n = len(x), len(y)
    M = np.full((m + 1, n + 1), -np.inf)
    X = np.full((m + 1, n + 1), -np.inf)
    Y = np.full((m + 1, n + 1), -np.inf)
    M[0, 0] = 0.0
    for i in range(m + 1):
        for j in range(n + 1):
            if i and j:
                s = match if x[i - 1] == y[j - 1] else mismatch
                M[i, j] = s + max(M[i - 1, j - 1], X[i - 1, j - 1],
                                  Y[i - 1, j - 1])
            if i:
                X[i, j] = max(M[i - 1, j] + gap_open, X[i - 1, j] + gap_extend)
            if j:
                Y[i, j] = max(M[i, j - 1] + gap_open, Y[i, j - 1] + gap_extend)
    return max(M[m, n], X[m, n], Y[m, n])


@pytest.mark.parametrize("x, y, want", [
    ([0], [0], 2.0),                       # one match
    ([0], [1], -4.0),                      # one mismatch beats two gaps (-12)
    ([0, 1], [0], 2.0 - 6.0),              # match + a gap of one
    ([0, 1, 2, 3], [0, 3], 2 + 2 - 6 - 2),  # match, gap of two, match
])
def test_gotoh_by_hand(x, y, want):
    got = gotoh.scores(np.array([x]), np.array([y]), **MM2)
    assert got[0] == want


def test_gotoh_matches_cellwise_recurrence():
    rng = np.random.default_rng(7)
    for m, n in [(1, 5), (6, 6), (13, 29)]:
        xs, ys = rng.integers(0, 4, (6, m)), rng.integers(0, 4, (6, n))
        got = gotoh.scores(xs, ys, **MM2)
        want = [cellwise_gotoh(x, y, **MM2) for x, y in zip(xs, ys)]
        np.testing.assert_array_equal(got, want)


def test_lower_precision_rounds():
    rng = np.random.default_rng(1)
    xs, ys = rng.integers(0, 4, (8, 250)), rng.integers(0, 4, (8, 282))
    low = gotoh.scores(xs, ys, **MM2, dtype=ml_dtypes.bfloat16)
    assert np.any(low.astype(float) != gotoh.scores(xs, ys, **MM2))


def test_solution_checks():
    x, y = np.array([0, 1, 2, 3]), np.array([0, 3])
    ops = [("align", 0, 0), ("del", 1), ("del", 2), ("align", 3, 1)]
    assert solutions.alignment_score(ops, x, y, **MM2) == 2 + 2 - 6 - 2
    with pytest.raises(solutions.InvalidSolution):
        solutions.alignment_score(ops[:-1], x, y, **MM2)
    with pytest.raises(solutions.InvalidSolution):
        solutions.alignment_score([("ins", 0)] + ops, x, y, **MM2)
