"""Operation and byte counts against instances counted by hand."""
import pytest

from work import gotoh


@pytest.mark.parametrize("shape, recon, ops, nbytes", [
    ((1, 1), False, 9, 1 + 1 + 4),          # one cell: M 3, X 3, Y 3
    ((2, 3), False, 54, 2 + 3 + 4),         # 6 cells
    ((2, 3), True, 54, 2 + 3 + 4 + 5),      # + a path of at most 5 steps
    ((100, 132), False, 9 * 13200, 236),
])
def test_gotoh_counts(shape, recon, ops, nbytes):
    assert gotoh.count(shape, recon) == (ops, nbytes)
