"""Recompute a decoded solution's cost from the instance alone.

A solution that is not a solution of the instance (operations out of
order, or not covering both sequences) raises :class:`InvalidSolution`.
"""
from __future__ import annotations


class InvalidSolution(ValueError):
    pass


def alignment_score(ops, x, y, match, mismatch, gap_open, gap_extend) -> float:
    """Score of an affine-gap alignment script: ``('align', i, j)`` pairs
    ``x[i]`` with ``y[j]``, ``('del', i)`` gaps ``x[i]``, ``('ins', j)``
    gaps ``y[j]``; the first symbol of a gap run scores ``gap_open``, each
    further one ``gap_extend``."""
    i = j = 0
    score, run_kind = 0.0, None
    for op in ops:
        if op[0] == "align":
            if (op[1], op[2]) != (i, j) or i >= len(x) or j >= len(y):
                raise InvalidSolution(f"alignment op {op} out of order at {(i, j)}")
            score += match if x[i] == y[j] else mismatch
            i, j, run_kind = i + 1, j + 1, None
            continue
        if op[0] not in ("del", "ins") or op[1] != (i if op[0] == "del" else j):
            raise InvalidSolution(f"alignment op {op} out of order at {(i, j)}")
        score += gap_extend if run_kind == op[0] else gap_open
        run_kind = op[0]
        i, j = (i + 1, j) if op[0] == "del" else (i, j + 1)
    if (i, j) != (len(x), len(y)):
        raise InvalidSolution("alignment does not cover both sequences")
    return score
