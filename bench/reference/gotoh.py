"""Affine-gap global alignment (Gotoh), row by row, many pairs at once.

Planes: ``M`` ends in an aligned pair, ``X`` in a gap in ``y`` (a run of
``x`` symbols), ``Y`` in a gap in ``x``. A gap of length ``L`` scores
``gap_open + (L - 1) * gap_extend``; ``X`` and ``Y`` open only from ``M``::

    M[i,j] = s(x_i, y_j) + max(M, X, Y)[i-1, j-1]
    X[i,j] = max(M[i-1,j] + gap_open, X[i-1,j] + gap_extend)
    Y[i,j] = max(M[i,j-1] + gap_open, Y[i,j-1] + gap_extend)

with ``M[0,0] = 0`` and every other border cell unreachable except the gap
runs the recurrence itself gives. The optimum is the best of the three
planes at ``(m, n)``. Within a row ``Y`` is a running maximum::

    Y[i,j] = gap_open + (j-1) * gap_extend + max_{k<j} (M[i,k] - k * gap_extend)

so a row costs a few whole-array operations over every pair of the batch.
Every operation runs in ``dtype`` (float64 for the comparison, bfloat16 for
the control), rounding after each one.
"""
from __future__ import annotations

import numpy as np


def scores(xs, ys, match, mismatch, gap_open, gap_extend,
           dtype=np.float64) -> np.ndarray:
    """Optimal scores of pairs ``xs[b]`` vs ``ys[b]``: ``xs`` is ``(B, m)``
    and ``ys`` is ``(B, n)`` symbol codes. Returns ``(B,)`` in ``dtype``."""
    xs, ys = np.asarray(xs), np.asarray(ys)
    B, m = xs.shape
    n = ys.shape[1]
    t = np.dtype(dtype).type
    neg = t(-np.inf)
    go, ge = t(gap_open), t(gap_extend)
    match, mismatch = t(match), t(mismatch)
    kge = (np.arange(n + 1) * ge).astype(dtype)            # k * gap_extend
    run = (go + (np.arange(n + 1) - 1) * ge).astype(dtype)  # open + (j-1)*e

    def gap_row(M):
        best = np.maximum.accumulate((M - kge).astype(dtype), axis=1)
        Y = np.full_like(M, neg)
        Y[:, 1:] = (run[1:] + best[:, :-1]).astype(dtype)
        return Y

    M = np.full((B, n + 1), neg, dtype=dtype)
    M[:, 0] = 0
    X = np.full((B, n + 1), neg, dtype=dtype)
    Y = gap_row(M)
    for i in range(1, m + 1):
        H = np.maximum(np.maximum(M, X), Y)
        s = np.where(xs[:, i - 1, None] == ys, match, mismatch).astype(dtype)
        Xn = np.maximum((M + go).astype(dtype), (X + ge).astype(dtype))
        M = np.full((B, n + 1), neg, dtype=dtype)
        M[:, 1:] = (s + H[:, :-1]).astype(dtype)
        X = Xn
        Y = gap_row(M)
    return np.maximum(np.maximum(M[:, n], X[:, n]), Y[:, n])


def answers(payloads, dtype=np.float64) -> np.ndarray:
    """Optimal scores of request payloads that share one shape."""
    p0 = payloads[0]
    return scores(np.stack([p["x"] for p in payloads]),
                  np.stack([p["y"] for p in payloads]),
                  p0["match"], p0["mismatch"], p0["gap_open"],
                  p0["gap_extend"], dtype=dtype)


def solution_cost(payload, solution) -> float:
    """Score of a decoded alignment, recomputed from the instance."""
    from reference.solutions import alignment_score

    return alignment_score(solution["ops"], np.asarray(payload["x"]),
                           np.asarray(payload["y"]), payload["match"],
                           payload["mismatch"], payload["gap_open"],
                           payload["gap_extend"])
