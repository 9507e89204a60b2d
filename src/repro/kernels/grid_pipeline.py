"""Pallas TPU kernel: wavefront pipeline for the grid family.

Reuses ``mcm_pipeline``'s contiguous-diagonal addressing trick on 2-D
multi-plane grids (DESIGN.md §9): store the table in *frontier-major*
order so every wavefront is a contiguous run, and every per-frontier
operand becomes a traced-start constant-length window — no gathers. Tables
use the ``kernels/rows.py`` layout: ``(planes, rows, 128)`` VMEM buffers,
windows of ``nv·128`` cells read as ``nv + 1`` rows plus a lane rotation.

``antidiag`` — the table is kept in anti-diagonal-major order with a
``PAD`` prefix. Cell ``(i, j)`` of front ``t = i + j`` sits at
``PAD + base(t) + (j - c0(t))`` where ``c0(t) = max(0, t - rows + 1)`` and
``base(t)`` (the sum of earlier front lengths) has a closed three-piece
form evaluated with traced integer arithmetic. The source operand of
shift move ``(di, dj)`` then lives at the *constant* lane shift
``base(ts) + c0(t) - dj - c0(ts)`` of front ``ts = t - di - dj`` — one
window per (plane, move) per front. Only the two tables (cost and arg)
stay VMEM-resident: the grid walks the fronts, and BlockSpec pipelining
streams front ``t + 1``'s weights, preset values and preset mask — laid
out front-major by the wrapper, one row block per front — while front
``t`` folds. Each front stores exactly its own cells (a lane-masked
window write), so no cell is written before its front; the buffers start
at the semiring zero, so out-of-grid sources read a zero that their
semiring-zero weight keeps out of the fold (never mixing +inf with -inf,
so no NaNs). Preset cells take their preset value per front.

``spandiag`` — the mcm kernel with a plane axis: per span diagonal, per
target plane (static loop), the inner ``fori_loop`` over split offsets
folds every rule into that plane as left/right diagonal windows plus a
rule weight. Args store the packed ``e·len(rules) + r``.

Both variants scan candidates in the jnp solvers' declaration order with
strict-improve folds (= argmin/argmax first-occurrence), so tables AND
args are bit-identical to ``core.grid.solve_grid_with_args`` —
reconstruction through this kernel decodes the same solutions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.mcm import lin_index, num_cells
from repro.kernels.rows import (LANES, from_rows, load_window, num_rows,
                                nvec, store_window, to_rows, vmem_params)


def _zero(op: str) -> float:
    return float("inf") if op == "min" else float("-inf")


# ---------------------------------------------------------------------------
# antidiag geometry
# ---------------------------------------------------------------------------
def _ad_geometry(meta):
    """(PAD, rows, Lf): pad prefix, rows of each plane's buffer, and the
    front window width (the longest front)."""
    _, _, _, R, C, moves, _ = meta
    Lf = min(R, C)
    span = max(int(m[2]) + int(m[3]) for m in moves)
    PAD = span + 1
    return PAD, num_rows(PAD + R * C + nvec(Lf) * LANES), Lf


def _ad_base(t, R: int, C: int):
    """Traced closed form of ``base(t)`` (three regimes: growing fronts,
    the constant-width band, shrinking fronts)."""
    m, M = min(R, C), max(R, C)
    u = t - M
    b_grow = t * (t + 1) // 2
    b_band = m * (m + 1) // 2 + (t - m) * m
    b_shrink = m * (m + 1) // 2 + (M - m) * m + u * m - u * (u + 1) // 2
    return jnp.where(t <= m, b_grow, jnp.where(t <= M, b_band, b_shrink))


def _ad_front_cells(R: int, C: int, width: int):
    """Row-major cell of each (front, lane) slot and whether the slot is a
    real cell: front ``t``'s lane ``q`` is cell ``(t - j, j)``,
    ``j = c0(t) + q``."""
    t = jnp.arange(R + C - 1)[:, None]
    j = jnp.maximum(0, t - (R - 1)) + jnp.arange(width)[None, :]
    i = t - j
    valid = (j <= jnp.minimum(t, C - 1)) & (i >= 0)
    return jnp.clip(i, 0, R - 1) * C + jnp.clip(j, 0, C - 1), valid


def _make_antidiag_kernel(meta, with_args):
    _, op, P, R, C, moves, _ = meta
    PAD, _, Lf = _ad_geometry(meta)
    nv = nvec(Lf)
    Lm = len(moves)
    zero = _zero(op)
    is_min = op == "min"
    by_plane = [[(l, m) for l, m in enumerate(moves) if int(m[0]) == p]
                for p in range(P)]

    def kernel(*refs):
        refs = list(refs)
        f_ref = refs.pop(0)          # this front: Lm weights, P presets, P masks
        st_ref = refs.pop(0)
        arg_ref = refs.pop(0) if with_args else None
        t = pl.program_id(0)

        @pl.when(t == 0)
        def _init():
            st_ref[...] = jnp.full_like(st_ref, zero)
            if with_args:
                arg_ref[...] = jnp.full_like(arg_ref, -1)

        base_t = PAD + _ad_base(t, R, C)
        c0_t = jnp.maximum(0, t - (R - 1))
        len_t = jnp.minimum(t, C - 1) - c0_t + 1
        for p in range(P):                           # static plane loop
            mlist = by_plane[p]
            acc = jnp.full((nv, LANES), zero, dtype=st_ref.dtype)
            arg = jnp.full((nv, LANES), mlist[0][0] if mlist else -1,
                           dtype=jnp.int32)
            for l, (_, p_from, di, dj) in mlist:     # static move loop
                ts = jnp.maximum(t - int(di) - int(dj), 0)
                src = jnp.maximum(
                    PAD + _ad_base(ts, R, C) + c0_t - int(dj)
                    - jnp.maximum(0, ts - (R - 1)), 0)
                val = load_window(st_ref.at[int(p_from)], src, nv) + f_ref[l]
                improve = val < acc if is_min else val > acc
                if with_args:
                    arg = jnp.where(improve, jnp.int32(l), arg)
                acc = jnp.where(improve, val, acc)
            preset = f_ref[Lm + P + p] > 0
            store_window(st_ref.at[p], base_t,
                         jnp.where(preset, f_ref[Lm + p], acc), len_t)
            if with_args:
                store_window(arg_ref.at[p], base_t,
                             jnp.where(preset | (t == 0), -1, arg), len_t)

    return kernel


def _antidiag_call(arrs, meta, with_args, interpret):
    _, op, P, R, C, moves, _ = meta
    w, init, pmask = arrs
    PAD, rows, Lf = _ad_geometry(meta)
    nv = nvec(Lf)
    zero = _zero(op)
    RC, Lm = R * C, len(moves)
    fronts = R + C - 1
    pmf = pmask.reshape(P, RC) > 0
    per_cell = jnp.concatenate([
        w.reshape(Lm, RC),
        jnp.where(pmf, init.reshape(P, RC), jnp.asarray(zero, w.dtype)),
        pmf.astype(w.dtype)])                                   # (Lm+2P, RC)
    cell, valid = _ad_front_cells(R, C, nv * LANES)
    per_front = jnp.where(valid[None], per_cell[:, cell], 0)    # (Lm+2P, T, lanes)
    per_front = jnp.swapaxes(per_front, 0, 1).reshape(
        fronts, Lm + 2 * P, nv, LANES)
    table = pl.BlockSpec((P, rows, LANES), lambda t: (0, 0, 0))
    out_shape = [jax.ShapeDtypeStruct((P, rows, LANES), w.dtype)]
    if with_args:
        out_shape.append(jax.ShapeDtypeStruct((P, rows, LANES), jnp.int32))
    out = pl.pallas_call(
        _make_antidiag_kernel(meta, with_args),
        name="grid_pipeline_pallas_antidiag" + ("_args" if with_args else ""),
        grid=(fronts,),
        in_specs=[pl.BlockSpec((None, Lm + 2 * P, nv, LANES),
                               lambda t: (t, 0, 0, 0))],
        out_specs=[table] * len(out_shape),
        out_shape=out_shape,
        compiler_params=vmem_params(_ad_vmem_bytes(meta, len(out_shape))),
        interpret=interpret,
    )(per_front)
    # row-major cell (i, j) <- its anti-diagonal-major buffer position
    i = jnp.arange(R)[:, None]
    j = jnp.arange(C)[None, :]
    t = i + j
    pos = (PAD + _ad_base(t, R, C) + j - jnp.maximum(0, t - (R - 1))).reshape(-1)
    st = out[0].reshape(P, -1)[:, pos].reshape(-1)
    if with_args:
        return st, out[1].reshape(P, -1)[:, pos].reshape(-1)
    return st


def _ad_vmem_bytes(meta, tables: int) -> int:
    """Resident cost (+ arg) tables plus the double-buffered per-front
    block, 4 bytes a cell."""
    _, _, P, _, _, moves, _ = meta
    _, rows, Lf = _ad_geometry(meta)
    front = (len(moves) + 2 * P) * nvec(Lf) * LANES
    return 4 * (tables * P * rows * LANES + 2 * front)


# ---------------------------------------------------------------------------
# spandiag (the mcm pipeline with a plane axis)
# ---------------------------------------------------------------------------
def _off(d, n):
    return lin_index(0, d, n)


def _span_geometry(n: int):
    """(L, rows): diagonal window width and rows of each plane's buffer."""
    L = max(n - 1, 1)
    return L, num_rows(num_cells(n) + nvec(L) * LANES)


def _make_spandiag_kernel(meta, with_args):
    _, op, P, n, _, _, rules = meta
    L, _ = _span_geometry(n)
    nv = nvec(L)
    r0 = nvec(n)
    zero = _zero(op)
    is_min = op == "min"
    NR = len(rules)
    by_plane = [[(r, rule) for r, rule in enumerate(rules)
                 if int(rule[0]) == A] for A in range(P)]

    def kernel(*refs):
        refs = list(refs)
        rw_ref = refs.pop(0)         # (NR, 128): rule weight r on every lane
        init_ref = refs.pop(0)       # (P, r0, 128): diagonal 0, zero-padded
        st_ref = refs.pop(0)
        arg_ref = refs.pop(0) if with_args else None

        st_ref[...] = jnp.full_like(st_ref, zero)
        st_ref[:, 0:r0, :] = init_ref[...]
        if with_args:
            arg_ref[...] = jnp.full_like(arg_ref, -1)

        def diag(d, _):
            off_d = _off(d, n)
            for A in range(P):                       # static plane loop
                rl = by_plane[A]
                if not rl:
                    continue

                def cand(e, carry, rl=rl):
                    acc, arg = carry
                    for r, (_, B, Cc) in rl:         # static rule loop
                        left = load_window(st_ref.at[int(B)], _off(e, n), nv)
                        right = load_window(st_ref.at[int(Cc)],
                                            _off(d - e - 1, n) + e + 1, nv)
                        val = (left + right) + rw_ref[r:r + 1, :]
                        improve = val < acc if is_min else val > acc
                        if with_args:
                            arg = jnp.where(
                                improve, e.astype(jnp.int32) * NR + r, arg)
                        acc = jnp.where(improve, val, acc)
                    return acc, arg

                acc, arg = jax.lax.fori_loop(
                    0, d, cand,
                    (jnp.full((nv, LANES), zero, dtype=st_ref.dtype),
                     jnp.full((nv, LANES), rl[0][0], dtype=jnp.int32)))
                store_window(st_ref.at[A], off_d, acc, L)
                if with_args:
                    store_window(arg_ref.at[A], off_d, arg, L)
            return 0

        jax.lax.fori_loop(1, n, diag, 0)

    return kernel


def _spandiag_call(arrs, meta, with_args, interpret):
    _, op, P, n, _, _, rules = meta
    rw, init = arrs
    _, rows = _span_geometry(n)
    cells = num_cells(n)
    out_shape = [jax.ShapeDtypeStruct((P, rows, LANES), rw.dtype)]
    if with_args:
        out_shape.append(jax.ShapeDtypeStruct((P, rows, LANES), jnp.int32))
    out = pl.pallas_call(
        _make_spandiag_kernel(meta, with_args),
        name="grid_pipeline_pallas_spandiag" + ("_args" if with_args else ""),
        out_shape=out_shape,
        compiler_params=vmem_params(2 * 4 * len(out_shape) * P * rows * LANES),
        interpret=interpret,
    )(jnp.broadcast_to(rw[:, None], (len(rules), LANES)),
      to_rows(init, nvec(n), fill=_zero(op)))
    st = from_rows(out[0], cells).reshape(-1)
    if with_args:
        return st, from_rows(out[1], cells).reshape(-1)
    return st


# ---------------------------------------------------------------------------
# Public entry points + VMEM accounting
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(1, 2))
def grid_pipeline_pallas(arrs, meta: tuple, interpret: bool = False):
    """Flat grid table from the wavefront kernel — ``arrs`` / ``meta`` as in
    ``core.grid.solve_grid``; bit-equal to it."""
    if meta[0] == "antidiag":
        return _antidiag_call(arrs, meta, False, interpret)
    return _spandiag_call(arrs, meta, False, interpret)


@functools.partial(jax.jit, static_argnums=(1, 2))
def grid_pipeline_pallas_with_args(arrs, meta: tuple,
                                   interpret: bool = False):
    """``grid_pipeline_pallas`` + the winning-argument table, matching
    ``core.grid.solve_grid_with_args`` bit-for-bit (strict-improve scans in
    declaration order = first-occurrence argmin/argmax)."""
    if meta[0] == "antidiag":
        return _antidiag_call(arrs, meta, True, interpret)
    return _spandiag_call(arrs, meta, True, interpret)


def grid_vmem_bytes(spec) -> int:
    """VMEM working set of the kernel's buffers (f32 + the int32 arg
    table), for the backend's ``supports`` gate."""
    meta = spec.static_meta()
    if spec.schedule == "antidiag":
        return _ad_vmem_bytes(meta, tables=2)
    _, rows = _span_geometry(spec.rows)
    return 4 * (len(spec.rules) * LANES + 2 * spec.planes * rows * LANES)
