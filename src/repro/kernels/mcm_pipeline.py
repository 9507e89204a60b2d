"""Pallas TPU kernel: blocked triangular pipeline for the MCM family.

The canonical triangular recurrence (DESIGN.md §3) on the diagonal-major
linearized table,

    m[i, i+d] = min_{0≤e<d} ( m[i, i+e] + m[i+e+1, i+d] + W[lin(i,d), e] ),

finalizes one whole diagonal per outer step — the TPU-blocked reading of the
paper's Fig.-8 pipeline, with the diagonal playing the role the ``B``-element
block plays for S-DP: every operand of diagonal ``d`` lives on a strictly
earlier diagonal, so the step's reads touch only finalized cells and its
writes are address-distinct (Theorem 1's argument at diagonal granularity).

The key VMEM property mirroring ``sdp_pipeline``: both the cost table and the
dense ``(cells, n-1)`` split-major ``weight_table`` stay VMEM-resident for the
whole solve, so HBM traffic is one load of the weights plus one store of the
table — the split-candidate loop never touches HBM. Because diagonal-major
order makes each diagonal *contiguous*, candidate ``e`` of the whole diagonal
is three dynamic-start constant-length VMEM slices (left operands start at
``off(e)``, right operands at ``off(d-e-1) + e + 1``, weights at column ``e``
of rows ``off(d)``…), i.e. no gather at all — the same no-gather discipline
the S-DP kernel gets from its static offsets.

Layout (``kernels/rows.py``): the table is a ``(rows, 128)`` VMEM buffer in
the same diagonal-major cell order, and the weights arrive transposed —
``(n-1, rows, 128)``, one flat cell-ordered plane per split ``e`` — so the
weight operand of candidate ``e`` is a window of plane ``e`` at ``off(d)``
exactly like the two table operands. Each operand is an ``L``-lane window
(``L = n-1``) at a traced unaligned start: ``nvec(L) + 1`` rows plus a lane
rotation, never a lane-dynamic slice (which Mosaic refuses).

Windows are padded to the longest diagonal (``n-1`` lanes); lanes past the
diagonal's true length compute garbage that lands in cells of *later*
diagonals, each of which is fully rewritten by its own step before anything
reads it — so no masking is needed on the write side, only the semiring-zero
mask on the (exact-count) candidate loop. The arg variant stores the winning
split offset per cell with the same address vector as the cost store
(DESIGN.md §5). VMEM budget: the weight planes dominate at
``≈ 2 n³ bytes`` f32, which bounds the kernel to n ≈ 158 under the 8 MiB
budget enforced by the backend's ``supports`` (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.mcm import lin_index, num_cells
from repro.kernels.rows import (LANES, from_rows, load_window, num_rows,
                                nvec, store_window, to_rows, vmem_params)

INF = jnp.inf


def _off(d, n):
    """Linear index of the first cell of diagonal ``d``; ``lin_index`` is
    pure int arithmetic, so it traces fine on kernel scalars."""
    return lin_index(0, d, n)


def _geometry(n: int):
    """(L, rows): lane count of a diagonal window and rows of every buffer.
    A window of ``nvec(L)`` rows starting at any operand offset (≤ the cell
    count) stays inside ``rows``."""
    L = max(n - 1, 1)
    return L, num_rows(num_cells(n) + nvec(L) * LANES)


def vmem_bytes(n: int) -> int:
    """Resident working set: the ``L`` weight planes plus the cost and arg
    tables, 4 bytes a cell."""
    L, rows = _geometry(n)
    return 4 * rows * LANES * (L + 2)


def _make_kernel(n, with_args):
    L, _ = _geometry(n)
    nv = nvec(L)

    def kernel(*refs):
        refs = list(refs)
        w_ref = refs.pop(0)
        st_ref = refs.pop(0)
        arg_ref = refs.pop(0) if with_args else None

        # diagonal 0 is preset to 0; the rest is rewritten diagonal-by-diagonal
        st_ref[...] = jnp.zeros_like(st_ref)
        if with_args:
            arg_ref[...] = jnp.full_like(arg_ref, -1)

        def diag(d, _):
            off_d = _off(d, n)

            def cand(e, carry):
                acc, arg = carry
                left = load_window(st_ref, _off(e, n), nv)
                right = load_window(st_ref, _off(d - e - 1, n) + e + 1, nv)
                w = load_window(w_ref.at[e], off_d, nv)
                val = (left + right) + w          # association of the jnp path
                if with_args:
                    arg = jnp.where(val < acc, e.astype(jnp.int32), arg)
                return jnp.minimum(acc, val), arg

            acc, arg = jax.lax.fori_loop(
                0, d, cand,
                (jnp.full((nv, LANES), INF, dtype=st_ref.dtype),
                 jnp.zeros((nv, LANES), dtype=jnp.int32)))
            store_window(st_ref, off_d, acc, L)
            if with_args:
                store_window(arg_ref, off_d, arg, L)
            return 0

        jax.lax.fori_loop(1, n, diag, 0)

    return kernel


def _call(wtab, n, with_args, interpret):
    L, rows = _geometry(n)
    w = jnp.asarray(wtab)
    planes = to_rows(w.T, rows)                   # (L, rows, 128)
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), w.dtype)]
    if with_args:
        out_shape.append(jax.ShapeDtypeStruct((rows, LANES), jnp.int32))
    out = pl.pallas_call(
        _make_kernel(n, with_args),
        name="mcm_pipeline_pallas" + ("_args" if with_args else ""),
        out_shape=out_shape,
        compiler_params=vmem_params(2 * vmem_bytes(n)),
        interpret=interpret,
    )(planes)
    cells = num_cells(n)
    if with_args:
        return from_rows(out[0], cells), from_rows(out[1], cells)
    return from_rows(out[0], cells)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def mcm_pipeline_pallas(wtab, n: int, interpret: bool = False):
    """wtab: (num_cells(n), n-1) split-major weights (``core.mcm.weight_table``).
    Returns the linearized cost table, bit-equal to ``solve_wavefront_tab``."""
    return _call(wtab, n, with_args=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def mcm_pipeline_pallas_with_args(wtab, n: int, interpret: bool = False):
    """``mcm_pipeline_pallas`` + the best-split table (−1 on diagonal 0),
    matching ``solve_wavefront_tab_with_args``: splits scanned in ascending
    ``e`` with a strict improve predicate = argmin's first-occurrence rule.
    Returns ``(st, args)``."""
    return _call(wtab, n, with_args=True, interpret=interpret)
