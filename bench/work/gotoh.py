"""Gotoh work: ``shape = (m, n)``, the lengths of ``x`` and ``y``.

Operations: each of the ``m * n`` matrix cells fills three planes with
three operations apiece -- ``M``: a 3-way max (2) plus the substitution
score (1); ``X`` and ``Y``: two candidate additions and one max -- so 9 per
cell. Bytes: one per symbol of ``x`` and ``y``, a 4-byte score, and for a
decoded alignment one byte per step of the path, which has at most
``m + n`` steps.
"""
OPS_PER_CELL = 9


def count(shape, reconstruct: bool) -> tuple:
    m, n = shape
    nbytes = m + n + 4 + (m + n if reconstruct else 0)
    return OPS_PER_CELL * m * n, nbytes
