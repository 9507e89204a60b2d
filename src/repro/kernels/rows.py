"""Lane-row layout shared by the DP kernels (DESIGN.md §4).

Every DP kernel keeps its flat cell order (S-DP cells, diagonal-major
triangles, anti-diagonal-major grids) but stores the flat buffer as
``(rows, 128)``: cell ``s`` sits at row ``s // 128``, lane ``s % 128``. Mosaic
refuses a dynamic start along the lane axis unless it can prove it aligned
(a 1-D f32 slice must start at a multiple of 1024 cells), but it compiles a
dynamic *row* start and a lane rotation by a traced amount. So a window of
``nv·128`` cells starting anywhere is ``nv + 1`` whole rows plus one
``pltpu.roll`` and a select, and an unaligned store is the same rotation in
reverse, blended into the rows it touches under a lane mask.

Windows come back as ``(nv, 128)`` values: window cell ``w`` at row
``w // 128``, lane ``w % 128``, so an elementwise fold over windows fills
whole vregs. A buffer read through :func:`load_window` or written through
:func:`store_window` needs one row of tail past the last window it serves
(:func:`num_rows` adds it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: ceiling for a kernel's scoped VMEM request; v5e has 128 MiB of VMEM per
#: core and Mosaic keeps some for itself
VMEM_LIMIT_CAP = 100 << 20
_VMEM_LIMIT_FLOOR = 16 << 20        # Mosaic's default scoped limit on v5e


def nvec(cells: int) -> int:
    """Whole 128-lane rows that hold ``cells`` window cells (at least 1)."""
    return max(1, -(-int(cells) // LANES))


def num_rows(cells: int) -> int:
    """Rows of a buffer holding ``cells`` flat cells, plus the one tail row
    a window read or write past the last cell touches."""
    return nvec(cells) + 1


def to_rows(flat, rows: int, fill=0):
    """``(..., m)`` → ``(..., rows, 128)``, padded with ``fill`` (XLA side)."""
    flat = jnp.asarray(flat)
    pad = rows * LANES - flat.shape[-1]
    widths = [(0, 0)] * (flat.ndim - 1) + [(0, pad)]
    flat = jnp.pad(flat, widths, constant_values=fill)
    return flat.reshape(flat.shape[:-1] + (rows, LANES))


def from_rows(x, cells: int):
    """Inverse of :func:`to_rows`: the first ``cells`` flat cells."""
    return x.reshape(x.shape[:-2] + (-1,))[..., :cells]


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def load_window(ref, s, nv: int):
    """Cells ``[s, s + nv·128)`` of a ``(rows, 128)`` ref as ``(nv, 128)``."""
    r, c = s // LANES, s % LANES
    x = ref[pl.ds(r, nv + 1), :]
    y = pltpu.roll(x, (LANES - c) % LANES, 1)     # y[k, l] = x[k, (l + c) % 128]
    return jnp.where(_lane((nv, LANES)) < LANES - c, y[:nv], y[1:])


def store_window(ref, s, vals, length):
    """Write the first ``length`` cells of the ``(nv, 128)`` window ``vals``
    to cells ``[s, s + length)`` of a ``(rows, 128)`` ref; the other cells
    of the rows touched keep their values."""
    nv = vals.shape[0]
    r, c = s // LANES, s % LANES
    y = pltpu.roll(vals, c, 1)                    # y[k, l] = vals[k, (l - c) % 128]
    hi = jnp.concatenate([y, y[-1:]], 0)          # row k, lanes >= c: y[k]
    lo = jnp.concatenate([y[:1], y], 0)           # row k, lanes < c: y[k - 1]
    lane = _lane((nv + 1, LANES))
    w = jax.lax.broadcasted_iota(jnp.int32, (nv + 1, LANES), 0) * LANES + lane - c
    old = ref[pl.ds(r, nv + 1), :]
    ref[pl.ds(r, nv + 1), :] = jnp.where((w >= 0) & (w < length),
                                         jnp.where(lane >= c, hi, lo), old)


def load_lanes(ref, s):
    """Cells ``[s, s + 128 - s % 128)`` — the rest of ``s``'s row — rotated
    to lanes 0…; for windows known not to cross a row (no tail row read)."""
    row = ref[pl.ds(s // LANES, 1), :]
    return pltpu.roll(row, (LANES - s % LANES) % LANES, 1)


def store_lanes(ref, s, vals, length):
    """Write lanes ``[0, length)`` of the ``(1, 128)`` value ``vals`` to cells
    ``[s, s + length)``, which must lie in one row."""
    r, c = s // LANES, s % LANES
    lane = _lane((1, LANES))
    old = ref[pl.ds(r, 1), :]
    ref[pl.ds(r, 1), :] = jnp.where((lane >= c) & (lane < c + length),
                                    pltpu.roll(vals, c, 1), old)


def read_cell(ref, s):
    """One cell of an int32 ``(rows, 128)`` ref as a scalar (row load, then
    a lane pick by masked reduce)."""
    row = ref[pl.ds(s // LANES, 1), :]
    pick = _lane((1, LANES)) == s % LANES
    return jnp.max(jnp.where(pick, row, jnp.iinfo(jnp.int32).min))


def vmem_params(nbytes: int) -> pltpu.CompilerParams:
    """Scoped-VMEM request for a kernel whose buffers take ``nbytes``
    (pipelined blocks counted twice by the caller), with headroom for
    Mosaic's own scratch."""
    limit = min(max(int(nbytes) + (4 << 20), _VMEM_LIMIT_FLOOR),
                VMEM_LIMIT_CAP)
    return pltpu.CompilerParams(vmem_limit_bytes=limit)
