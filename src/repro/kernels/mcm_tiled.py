"""Pallas TPU kernel: tiled triangular solver with HBM-streamed weights and
a fused traceback.

``mcm_pipeline`` keeps the dense ``(cells, n-1)`` weight slab VMEM-resident,
which caps the route at n ≈ 158 under the 8 MiB budget. That slab is the
only part that grows as n³ (≈ 2n³ bytes; the cost and arg tables are
8 bytes a cell, ≈ 4n² bytes). This module breaks the wall (DESIGN.md §4) by
keeping the two tables VMEM-resident in the ``kernels/rows.py`` layout and
streaming the weights from HBM: the grid walks ``(diagonal d, split tile
j)`` sequentially, and BlockSpec pipelining fetches the ``(E, nvec(n-1),
128)`` weight tile of step ``(d, j+1)`` while step ``(d, j)`` folds — the
paper's pipeline idea applied at the memory hierarchy instead of the core
array. Tile ``j`` of diagonal ``d`` holds splits ``e ∈ [jE, jE + E)``, each as
the diagonal's ``n-1`` weights along lanes (the wrapper lays the slab out
as ``(n-1, J, E, lanes)`` once per call; steps past the diagonal's last tile
re-use that tile's block and fetch nothing).

Each step folds ``(left + right) + w`` for its splits into a running
(min, strict-improve arg) pair kept in VMEM scratch across the tile steps —
left and right are ``L``-lane windows of the resident table at traced
unaligned starts, exactly as in ``mcm_pipeline`` — and the diagonal's last
tile stores its ``n - d`` true cells. Splits are scanned in ascending ``e``
with a strict improve predicate (= ``argmin`` first occurrence), so tables
and args match the jnp wavefront bit-for-bit.

The fused variant walks the finished arg table *in the same launch*: at the
last step, an in-kernel DFS (SMEM stack, one-cell reads of ``args[c]``)
mirrors ``core.mcm.triangular_traceback`` exactly and emits the preorder
``(i, d, e)`` node arrays as an extra output, so ``reconstruct=True`` costs
one launch instead of solve + traceback dispatch.

``mcm_tiled_ref`` is the banded-tile jnp body — the kernel's oracle under
interpret mode and the CPU/GPU fallback route, ~6× less padded work than
``solve_wavefront_tab``'s dense masked combine at large n because both the
row extent and the candidate extent track the true band instead of the
padded (n, n-1) rectangle. Its tiles fold in the same ascending-``e``
first-occurrence order, so it too is bit-equal to the wavefront.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mcm import lin_index, num_cells, triangular_traceback
from repro.kernels.rows import (LANES, from_rows, load_window, num_rows,
                                nvec, read_cell, store_lanes, store_window,
                                vmem_params)

INF = jnp.inf

#: default tile geometry for the jnp fallback (no VMEM constraint — small
#: tiles track the true band closely, which beats the dense masked wavefront
#: combine by ~1.5× at n ≥ 512 on CPU despite the dynamic tile loops)
REF_TILE = 32
#: BlockSpec pipelining discipline: the kernel folds weight tile ``j`` out
#: of one buffer while Pallas prefetches tile ``j + 1`` into the other, so
#: the buffer pool must cover the folding tile plus every in-flight
#: prefetch — ``DMA_SLOTS >= PREFETCH_DEPTH + 1``, checked statically by the
#: schedule-hazard verifier (repro.analysis)
DMA_SLOTS = 2
PREFETCH_DEPTH = 1


def _off(d, n):
    """Linear index of the first cell of diagonal ``d`` (traced-safe)."""
    return lin_index(0, d, n)


def kernel_plan(n: int, budget=None, tile_e=None) -> dict:
    """The kernel's geometry: ``L`` lanes per diagonal window (``nv`` rows),
    ``E`` splits per weight tile — sized so the ``DMA_SLOTS`` tile buffers
    fit ``budget`` unless ``tile_e`` pins it — ``J`` tiles per diagonal,
    and ``rows`` of the resident cost/arg tables."""
    L = max(n - 1, 1)
    nv = nvec(L)
    tile_bytes = 4 * nv * LANES                    # one split's weights
    if tile_e is None:
        cap = (budget or (8 << 20)) // (DMA_SLOTS * tile_bytes)
        tile_e = max(1, cap)
    E = max(1, min(int(tile_e), L))
    return {"L": L, "nv": nv, "E": E, "J": -(-L // E),
            "rows": num_rows(num_cells(n) + nv * LANES),
            "tile_bytes": DMA_SLOTS * E * tile_bytes}


def _weight_tiles(wtab, n, plan):
    """``(n-1, J, E, nv, 128)``: tile ``j`` of diagonal ``d`` (row ``d-1``)
    holds, for each split ``e = jE + l``, the diagonal's weights
    ``wtab[off(d) + i, e]`` along lanes ``i``. Lanes past the diagonal and
    splits past ``n-2`` are padding the kernel never folds."""
    L, nv, E, J = plan["L"], plan["nv"], plan["E"], plan["J"]
    w = jnp.asarray(wtab)
    d = jnp.arange(1, n)
    src = jnp.clip(_off(d, n)[:, None] + jnp.arange(nv * LANES)[None, :],
                   0, num_cells(n) - 1)
    g = jnp.swapaxes(w[src], 1, 2)                 # (n-1, L, lanes)
    g = jnp.pad(g, ((0, 0), (0, J * E - L), (0, 0)))
    return g.reshape(n - 1, J, E, nv, LANES)


def _make_tiled_kernel(n, plan, fused):
    cells = num_cells(n)
    nv, E = plan["nv"], plan["E"]

    def kernel(*refs):
        refs = list(refs)
        w_ref = refs.pop(0)
        st_ref = refs.pop(0)
        arg_ref = refs.pop(0)
        nodes_ref = refs.pop(0) if fused else None
        acc_ref, accarg_ref = refs.pop(0), refs.pop(0)
        if fused:
            si, sd = refs.pop(0), refs.pop(0)

        d = pl.program_id(0) + 1
        j = pl.program_id(1)
        last = (d - 1) // E                       # the diagonal's last tile

        @pl.when((d == 1) & (j == 0))
        def _preset():  # diagonal 0 preset to 0 (arg -1); the rest rewritten
            st_ref[...] = jnp.zeros_like(st_ref)
            arg_ref[...] = jnp.full_like(arg_ref, -1)

        @pl.when(j == 0)
        def _reset():
            acc_ref[...] = jnp.full_like(acc_ref, INF)
            accarg_ref[...] = jnp.zeros_like(accarg_ref)

        @pl.when(j <= last)
        def _fold():
            e0 = j * E

            def cand(e, carry):
                acc, arg = carry
                left = load_window(st_ref, _off(e, n), nv)
                right = load_window(st_ref, _off(d - e - 1, n) + e + 1, nv)
                val = (left + right) + w_ref[e - e0]
                arg = jnp.where(val < acc, e.astype(jnp.int32), arg)
                return jnp.minimum(acc, val), arg

            acc, arg = jax.lax.fori_loop(
                e0, jnp.minimum(e0 + E, d), cand,
                (acc_ref[...], accarg_ref[...]))
            acc_ref[...] = acc
            accarg_ref[...] = arg

            @pl.when(j == last)
            def _store():
                store_window(st_ref, _off(d, n), acc, n - d)
                store_window(arg_ref, _off(d, n), arg, n - d)

        # -- fused traceback: DFS over the finished arg table ----------------
        if fused:
            @pl.when((d == n - 1) & (j == last))
            def _walk():
                si[0] = 0
                sd[0] = n - 1

                def emit(k, t, v):
                    store_lanes(nodes_ref.at[k], t,
                                jnp.full((1, LANES), v, jnp.int32), 1)

                def step(t, sp):
                    top = sp - 1
                    i = si[top]
                    dd = sd[top]
                    c = jnp.clip(lin_index(i, dd, n), 0, cells - 1)
                    e = jnp.clip(read_cell(arg_ref, c), 0,
                                 jnp.maximum(dd - 1, 0))
                    sp = sp - 1
                    # push right child first so the left pops next (preorder)
                    rd = dd - e - 1
                    idx = jnp.where(rd >= 1, sp, n + 1)
                    si[idx] = i + e + 1
                    sd[idx] = rd
                    sp = sp + (rd >= 1).astype(jnp.int32)
                    idx = jnp.where(e >= 1, sp, n + 1)
                    si[idx] = i
                    sd[idx] = e
                    sp = sp + (e >= 1).astype(jnp.int32)
                    emit(0, t, i)
                    emit(1, t, dd)
                    emit(2, t, e)
                    return sp

                jax.lax.fori_loop(0, n - 1, step, jnp.int32(1))

    return kernel


def _tiled_call(wtab, n, plan, with_args, fused, interpret):
    rows, L, nv, E, J = plan["rows"], plan["L"], plan["nv"], plan["E"], \
        plan["J"]
    w = jnp.asarray(wtab)
    table = pl.BlockSpec((rows, LANES), lambda d, j: (0, 0))
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), w.dtype),
                 jax.ShapeDtypeStruct((rows, LANES), jnp.int32)]
    out_specs = [table, table]
    scratch = [pltpu.VMEM((nv, LANES), w.dtype),      # running min
               pltpu.VMEM((nv, LANES), jnp.int32)]    # running arg
    if fused:
        out_shape.append(jax.ShapeDtypeStruct((3, nvec(L), LANES), jnp.int32))
        out_specs.append(pl.BlockSpec((3, nvec(L), LANES),
                                      lambda d, j: (0, 0, 0)))
        scratch += [pltpu.SMEM((n + 2,), jnp.int32),   # si (slot n+1 = trash)
                    pltpu.SMEM((n + 2,), jnp.int32)]   # sd
    nbytes = (2 * 2 * 4 * rows * LANES + plan["tile_bytes"]
              + (2 * 4 * 3 * nvec(L) * LANES if fused else 0))
    outs = pl.pallas_call(
        _make_tiled_kernel(n, plan, fused),
        name="mcm_tiled_pallas" + ("_fused" if fused else ""),
        grid=(n - 1, J),
        in_specs=[pl.BlockSpec(
            (None, None, E, nv, LANES),
            lambda d, j: (d, jnp.minimum(j, d // E), 0, 0, 0))],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=vmem_params(nbytes),
        interpret=interpret,
    )(_weight_tiles(w, n, plan))
    cells = num_cells(n)
    st = from_rows(outs[0], cells)
    if not with_args:
        return st
    args = from_rows(outs[1], cells)
    if not fused:
        return st, args
    nodes = from_rows(outs[2], L)
    return st, args, (nodes[0], nodes[1], nodes[2])


def _degenerate(wtab, n, with_args, fused):
    """n ≤ 1: a preset-only table (grid would be empty)."""
    st = jnp.zeros((num_cells(n),), dtype=jnp.asarray(wtab).dtype)
    if not with_args:
        return st
    args = jnp.full((num_cells(n),), -1, dtype=jnp.int32)
    if not fused:
        return st, args
    empty = jnp.zeros((0,), jnp.int32)
    return st, args, (empty, empty, empty)


def _solve(wtab, n, tile_e, budget, with_args, fused, interpret):
    if n <= 1:
        return _degenerate(wtab, n, with_args, fused)
    return _tiled_call(wtab, n, kernel_plan(n, budget, tile_e), with_args,
                       fused, interpret)


@functools.partial(jax.jit,
                   static_argnames=("n", "tile_e", "budget", "interpret"))
def mcm_tiled_pallas(wtab, n: int, tile_e=None, budget=None,
                     interpret: bool = False):
    """wtab: (num_cells(n), n-1) split-major weights. HBM-streamed weights;
    returns the linearized cost table, bit-equal to ``solve_wavefront_tab``."""
    return _solve(wtab, n, tile_e, budget, False, False, interpret)


@functools.partial(jax.jit,
                   static_argnames=("n", "tile_e", "budget", "interpret"))
def mcm_tiled_pallas_with_args(wtab, n: int, tile_e=None, budget=None,
                               interpret: bool = False):
    """``mcm_tiled_pallas`` + the best-split table; returns ``(st, args)``
    bit-equal to ``solve_wavefront_tab_with_args``."""
    return _solve(wtab, n, tile_e, budget, True, False, interpret)


@functools.partial(jax.jit,
                   static_argnames=("n", "tile_e", "budget", "interpret"))
def mcm_tiled_pallas_fused(wtab, n: int, tile_e=None, budget=None,
                           interpret: bool = False):
    """Solve + args + in-kernel preorder traceback in ONE launch; returns
    ``(st, args, (ii, dd, ee))`` with the node arrays matching
    ``core.mcm.triangular_traceback`` exactly."""
    return _solve(wtab, n, tile_e, budget, True, True, interpret)


# ---------------------------------------------------------------------------
# jnp fallback: banded tiles with gathers. Same ascending-split
# first-occurrence fold as the kernel — bit-equal to it by construction, and
# the route the CPU/GPU fallback lowers.
# ---------------------------------------------------------------------------
def _tile_plan(n: int, tile_t=None, tile_e=None):
    """(T, E) of the jnp fallback: rows per band tile and split lanes per
    candidate tile, both REF_TILE unless pinned."""
    L = max(n - 1, 1)
    T = tile_t or min(L, REF_TILE)
    E = tile_e or min(L, REF_TILE)
    return max(1, min(int(T), L)), max(1, min(int(E), L))


def _geometry(n: int, T: int, E: int):
    """(L, L_pad, size): true lane count, lane count padded to whole
    candidate tiles (weight columns), and padded table length — the last
    diagonal's band tile spills at most T cells past ``num_cells``."""
    L = max(n - 1, 1)
    L_pad = -(-L // E) * E
    return L, L_pad, num_cells(n) + T + 8


def _pad_weights(wtab, n, T, E):
    L, L_pad, size = _geometry(n, T, E)
    w = jnp.asarray(wtab)
    return jnp.zeros((size, L_pad), dtype=w.dtype).at[: num_cells(n), :L].set(w)


def _ref_body(wtab, n, T, E, with_args):
    L, L_pad, size = _geometry(n, T, E)
    cells = num_cells(n)
    w = _pad_weights(wtab, n, T, E)
    st = jnp.zeros((size,), dtype=w.dtype)
    ar = jnp.full((size,), -1, dtype=jnp.int32)
    tt = jnp.arange(T)

    def diag(d, carry):
        st, ar = carry
        off_d = _off(d, n)
        rows = n - d
        nrt = (rows + T - 1) // T
        net = (d + E - 1) // E

        def rowtile(rt, carry):
            st, ar = carry
            i0 = rt * T

            def etile(j, c2):
                acc, arg = c2
                e0 = j * E
                e_glob = e0 + jnp.arange(E)
                ec = jnp.minimum(e_glob, d - 1)
                lidx = _off(ec, n)[:, None] + i0 + tt[None, :]
                ridx = (_off(d - ec - 1, n) + ec + 1)[:, None] + i0 + tt[None, :]
                wt = jax.lax.dynamic_slice(w, (off_d + i0, e0), (T, E))
                vals = (st[lidx] + st[ridx]) + wt.T              # (E, T)
                vals = jnp.where((e_glob < d)[:, None], vals, INF)
                tmin = jnp.min(vals, axis=0)
                if with_args:
                    targ = (e0 + jnp.argmin(vals, axis=0)).astype(jnp.int32)
                    arg = jnp.where(tmin < acc, targ, arg)
                return jnp.minimum(acc, tmin), arg

            acc, arg = jax.lax.fori_loop(
                0, net, etile,
                (jnp.full((T,), INF, dtype=w.dtype),
                 jnp.zeros((T,), dtype=jnp.int32)))
            st = jax.lax.dynamic_update_slice(st, acc, (off_d + i0,))
            if with_args:
                ar = jax.lax.dynamic_update_slice(ar, arg, (off_d + i0,))
            return st, ar

        return jax.lax.fori_loop(0, nrt, rowtile, (st, ar))

    st, ar = jax.lax.fori_loop(1, n, diag, (st, ar))
    return (st[:cells], ar[:cells]) if with_args else st[:cells]


@functools.partial(jax.jit, static_argnames=("n", "tile_t", "tile_e"))
def mcm_tiled_ref(wtab, n: int, tile_t=None, tile_e=None):
    """Chunked jnp triangular solve, bit-equal to ``solve_wavefront_tab``."""
    if n <= 1:
        return _degenerate(wtab, n, with_args=False, fused=False)
    T, E = _tile_plan(n, tile_t=tile_t, tile_e=tile_e)
    return _ref_body(wtab, n, T, E, with_args=False)


@functools.partial(jax.jit, static_argnames=("n", "tile_t", "tile_e"))
def mcm_tiled_ref_with_args(wtab, n: int, tile_t=None, tile_e=None):
    """Chunked jnp solve + args; bit-equal to
    ``solve_wavefront_tab_with_args``."""
    if n <= 1:
        return _degenerate(wtab, n, with_args=True, fused=False)
    T, E = _tile_plan(n, tile_t=tile_t, tile_e=tile_e)
    return _ref_body(wtab, n, T, E, with_args=True)


@functools.partial(jax.jit, static_argnames=("n", "tile_t", "tile_e"))
def mcm_tiled_ref_fused(wtab, n: int, tile_t=None, tile_e=None):
    """Chunked jnp solve + args + traceback as ONE jitted program — the
    fallback fusion: no second dispatch for ``reconstruct=True``."""
    if n <= 1:
        return _degenerate(wtab, n, with_args=True, fused=True)
    T, E = _tile_plan(n, tile_t=tile_t, tile_e=tile_e)
    st, ar = _ref_body(wtab, n, T, E, with_args=True)
    ii, dd, ee = triangular_traceback(ar, n)
    return st, ar, (ii, dd, ee)
