"""Pallas TPU kernel: blocked pipelined S-DP solver (the paper's Fig. 2 on TPU).

The GPU pipeline finalizes 1 element/step with k threads; the TPU-native
reading (DESIGN.md §2) finalizes a block of ``B`` elements per step, ``B`` a
power of two no larger than ``min(a_k, block, 128)``: all reads for block
``[t, t+B)`` use offsets ``≥ a_k ≥ B`` and hence touch only finalized
elements, so each step is k shifted window reads + a fold + one store — no
gather, no conflicts, exactly the property Theorem 1 buys on GPU.

Layout (``kernels/rows.py``): the table is a ``(rows, 128)`` VMEM window,
cell ``s`` at row ``s // 128``, lane ``s % 128``. A read at ``s - a_j`` is a
two-row load plus a lane rotation; a block never crosses a row (``B`` divides
128 and blocks start at multiples of ``B``), so its store is one masked row
write. Weights arrive transposed, ``(k, rows, 128)`` — lane ``j``'s weights
for a block are a slice of one row.

One kernel serves both routes. The grid walks C-cell chunks sequentially
(C a multiple of 128); BlockSpec pipelining streams each chunk's ``(k, C)``
weight tile HBM→VMEM double-buffered and the finished chunk back out, while a
persistent ``(A + C)``-cell VMEM window carries the inter-chunk boundary —
the last ``A ≥ a_1`` finalized cells (whole rows), the whole dependency
horizon of the recurrence. :func:`sdp_pipeline_pallas` is the one-chunk case
(the VMEM-resident table: one HBM load + one store regardless of k);
:func:`sdp_chunked_pallas` sizes C from a VMEM budget, so VMEM holds
O(a_1 + C·k) regardless of n and there is no size cap at all.

Weighted extension (DESIGN.md §3/§4): with ``(⊕, ⊙)`` the semiring whose
``add`` matches the semigroup ``op``, passing an ``(n, k)`` ``weights`` array
turns each term into one extra weight-row read plus a per-lane semiring-⊙
before the ⊕ fold — the recurrence becomes
``ST[i] = ⊕_j (ST[i-a_j] ⊙ w[i, j])``, which is the form every weighted zoo
problem (edit distance, LCS, Viterbi, knapsack) linearizes into. The
arg-emitting variants additionally store the winning lane index next to each
cost block with the same address vector as the cost store, so Theorem 1's
write-distinctness argument extends verbatim (DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.semiring import SEMIGROUP_TO_SEMIRING
from repro.kernels.rows import (LANES, load_lanes, load_window, nvec,
                                store_lanes, vmem_params)

_OPS = {"min": jnp.minimum, "max": jnp.maximum, "add": jnp.add}
#: strict "new term wins" predicates reproducing jnp.arg{min,max}'s
#: first-occurrence tie-breaking when lanes are scanned in ascending order
_BEATS = {"min": jnp.less, "max": jnp.greater}
#: default VMEM budget that sizes the chunked kernel's chunks
DEFAULT_CHUNK_BUDGET = 8 << 20


def _plan(offsets, n: int, block: int, chunk=None, budget=None,
          resident: bool = False):
    """Kernel geometry ``(B, A, C, nc)``: cells per step, carried cells (whole
    rows covering ``a_1``), cells per chunk (a multiple of 128, hence of B),
    and chunk count. ``resident`` takes the whole table as one chunk;
    otherwise C comes from ``chunk`` or from ``budget`` (≈ 4·(k+3) VMEM bytes
    per streamed cell: window + weight lanes + cost + arg)."""
    a1, ak = int(offsets[0]), int(offsets[-1])
    B = 1 << (max(1, min(ak, block, LANES)).bit_length() - 1)
    A = LANES * nvec(a1)
    M = n - a1                                   # cells to compute
    whole = LANES * nvec(M)
    if resident:
        C = whole
    elif chunk is not None:
        C = min(LANES * nvec(chunk), whole)
    else:
        cap = (budget or DEFAULT_CHUNK_BUDGET) // (4 * (len(offsets) + 3))
        C = min(max(LANES, cap // LANES * LANES), whole)
    return B, A, C, -(-M // C)


def chunk_geometry(offsets, n: int, block: int = 512, chunk=None,
                   budget=None, resident: bool = False) -> dict:
    """The kernel's window geometry as data, for the static schedule-hazard
    verifier (DESIGN.md §10): the carried window prefix is the last
    ``carry ≥ a_1`` computed cells, the window holds carry + one chunk, and
    chunks are whole step blocks. ``repro.dp.schedule
    .chunk_carry_invariants`` checks those properties; deriving them from
    ``_plan`` itself keeps the checked geometry honest against the kernel."""
    B, A, C, nc = _plan(tuple(int(a) for a in offsets), n, block, chunk,
                        budget, resident)
    return {"block": B, "chunk": C, "chunks": nc, "carry": A,
            "window": A + C}


def resident_vmem_bytes(offsets, n: int, weighted: bool,
                        block: int = 512) -> int:
    """Working set of the one-chunk (VMEM-resident) kernel: the window, the
    weight rows, and the cost and arg outputs, 4 bytes a cell."""
    _, A, C, _ = _plan(tuple(offsets), n, block, resident=True)
    k = len(offsets) if weighted else 0
    return 4 * (A + C + LANES + C * (k + 2))


def _make_kernel(offsets, op, B, A, C, weighted, with_args):
    combine = _OPS[op]
    mul = SEMIGROUP_TO_SEMIRING[op].mul
    Ar, Cr = A // LANES, C // LANES

    def kernel(*refs):
        refs = list(refs)
        init_ref = refs.pop(0)
        w_ref = refs.pop(0) if weighted else None
        out_ref = refs.pop(0)
        arg_ref = refs.pop(0) if with_args else None
        win_ref = refs.pop(0)

        @pl.when(pl.program_id(0) == 0)
        def _seed():  # window rows [0, Ar): the preset table, right-aligned
            win_ref[0:Ar, :] = init_ref[...]

        def body(b, _):
            p = b * B                          # chunk-local block start
            s = A + p                          # window position

            def term(j):
                t = load_window(win_ref, s - offsets[j], 1)
                return mul(t, load_lanes(w_ref.at[j], p)) if weighted else t

            acc = term(0)
            if with_args:
                arg = jnp.zeros((1, LANES), dtype=jnp.int32)
            for j in range(1, len(offsets)):  # k unrolled shifted reads
                val = term(j)
                if with_args:
                    arg = jnp.where(_BEATS[op](val, acc), jnp.int32(j), arg)
                acc = combine(acc, val)
            store_lanes(win_ref, s, acc, B)
            if with_args:
                store_lanes(arg_ref, p, arg, B)
            return 0

        jax.lax.fori_loop(0, C // B, body, 0)
        out_ref[...] = win_ref[Ar:Ar + Cr, :]
        # Slide the window: the next chunk's first cell depends on the last
        # A cells just finalized. Materialize before writing — when C < A
        # the source and destination rows overlap.
        carry = win_ref[Cr:Cr + Ar, :]
        win_ref[0:Ar, :] = carry

    return kernel


def _call(init, offsets, op, n, geom, weights, with_args, interpret):
    B, A, C, nc = geom
    a1, k = offsets[0], len(offsets)
    Ar, Cr = A // LANES, C // LANES
    dt = init.dtype
    operands = [jnp.zeros((A,), dt).at[A - a1:].set(init).reshape(Ar, LANES)]
    in_specs = [pl.BlockSpec((Ar, LANES), lambda c: (0, 0))]
    nbytes = 4 * (2 * A + A + C + LANES)
    if weights is not None:
        wt = jnp.zeros((k, nc * C), dt).at[:, : n - a1].set(
            weights[a1:n].astype(dt).T)
        operands.append(wt.reshape(k, nc, Cr, LANES))
        in_specs.append(pl.BlockSpec((k, None, Cr, LANES),
                                     lambda c: (0, c, 0, 0)))
        nbytes += 4 * 2 * k * C
    out_shape = [jax.ShapeDtypeStruct((nc, Cr, LANES), dt)]
    out_specs = [pl.BlockSpec((None, Cr, LANES), lambda c: (c, 0, 0))]
    if with_args:
        out_shape.append(jax.ShapeDtypeStruct((nc, Cr, LANES), jnp.int32))
        out_specs.append(pl.BlockSpec((None, Cr, LANES), lambda c: (c, 0, 0)))
    nbytes += 4 * 2 * C * len(out_shape)
    out = pl.pallas_call(
        _make_kernel(offsets, op, B, A, C, weighted=weights is not None,
                     with_args=with_args),
        name="sdp_pipeline_pallas" + ("_args" if with_args else ""),
        grid=(nc,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((Ar + Cr + 1, LANES), dt)],
        compiler_params=vmem_params(nbytes),
        interpret=interpret,
    )(*operands)
    st = jnp.concatenate([init, out[0].reshape(-1)])[:n]
    if not with_args:
        return st
    args = jnp.concatenate([jnp.full((a1,), -1, dtype=jnp.int32),
                            out[1].reshape(-1)])[:n]
    return st, args


def _check_args_op(op):
    if op not in _BEATS:
        raise ValueError(f"argument tracking is undefined for op={op!r} "
                         "(every lane contributes to the reduction)")


@functools.partial(jax.jit, static_argnames=("offsets", "op", "n", "block", "interpret"))
def sdp_pipeline_pallas(init, offsets: tuple, op: str, n: int,
                        block: int = 512, weights=None,
                        interpret: bool = False):
    """init: (a_1,) preset values; optional (n, k) semiring ``weights``.
    Returns ST[0..n-1] from the VMEM-resident (one-chunk) kernel."""
    if n <= offsets[0]:  # preset-only table: nothing to pipeline, clamp
        return init[:n]
    return _call(init, offsets, op, n,
                 _plan(offsets, n, block, resident=True), weights,
                 with_args=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("offsets", "op", "n", "block", "interpret"))
def sdp_pipeline_pallas_with_args(init, offsets: tuple, op: str, n: int,
                                  block: int = 512, weights=None,
                                  interpret: bool = False):
    """``sdp_pipeline_pallas`` + the per-cell winning-lane index (preset cells
    carry -1), matching ``core.sdp.solve_blocked_with_args`` exactly: lanes are
    scanned in ascending order with a strict improve predicate, which is
    jnp.arg{min,max}'s first-occurrence tie rule. Returns ``(st, args)``."""
    _check_args_op(op)
    if n <= offsets[0]:  # preset-only: clamped presets, every cell an init cell
        return init[:n], jnp.full((n,), -1, dtype=jnp.int32)
    return _call(init, offsets, op, n,
                 _plan(offsets, n, block, resident=True), weights,
                 with_args=True, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("offsets", "op", "n", "block",
                                             "chunk", "budget", "interpret"))
def sdp_chunked_pallas(init, offsets: tuple, op: str, n: int,
                       block: int = 512, chunk: int | None = None,
                       budget: int | None = None, weights=None,
                       interpret: bool = False):
    """HBM-streaming ``sdp_pipeline_pallas``: same recurrence, but the table
    streams through an ``(A + C)`` VMEM window instead of residing whole in
    VMEM — any n fits. Returns ST[0..n-1]."""
    if n <= offsets[0]:  # preset-only table: nothing to pipeline, clamp
        return init[:n]
    return _call(init, offsets, op, n,
                 _plan(offsets, n, block, chunk, budget), weights,
                 with_args=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("offsets", "op", "n", "block",
                                             "chunk", "budget", "interpret"))
def sdp_chunked_pallas_with_args(init, offsets: tuple, op: str, n: int,
                                 block: int = 512, chunk: int | None = None,
                                 budget: int | None = None, weights=None,
                                 interpret: bool = False):
    """``sdp_chunked_pallas`` + per-cell winning-lane indices (preset cells
    carry -1), same ascending-lane strict-improve tie rule as
    ``solve_blocked_with_args``. Returns ``(st, args)``."""
    _check_args_op(op)
    if n <= offsets[0]:  # preset-only: clamped presets, every cell an init cell
        return init[:n], jnp.full((n,), -1, dtype=jnp.int32)
    return _call(init, offsets, op, n,
                 _plan(offsets, n, block, chunk, budget), weights,
                 with_args=True, interpret=interpret)
