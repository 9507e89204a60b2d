"""Pallas TPU kernel: weighted tropical (min,+) matrix multiplication.

    C[i,j] = min_k ( A[i,k] + B[k,j] + av[i]·gv[k]·bv[j] )

This is the compute core of the beyond-paper blocked MCM solver
(``core/blocked_mcm.py``): the middle-tile split combine *is* this
contraction. The MXU cannot evaluate (min,+), so the kernel targets the VPU
with explicit VMEM tiling: (bm × bk) and (bk × bn) operand tiles are streamed
from HBM, the (bm × bk × bn) broadcast combine happens entirely in VMEM, and
a (bm × bn) accumulator scratch persists across the sequential K grid steps —
the same fill/accumulate/drain pipeline shape as the paper's Fig. 2, one
memory-hierarchy level down.

Grid: (M/bm, N/bn, K/bk); K is innermost (sequential on TPU).
VMEM working set: bm·bk + bk·bn + bm·bn + bm·bk·bn/unroll floats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 16


def _kernel(a_ref, b_ref, av_ref, gv_ref, bv_ref, o_ref, acc_ref):
    k_step = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)

    a = a_ref[...]            # (bm, bk)
    b = b_ref[...]            # (bk, bn)
    av = av_ref[...]          # (bm, 1)
    gv = gv_ref[...]          # (bk, 1)
    bv = bv_ref[...]          # (1, bn)

    # (bm, bk, bn) broadcast combine on the VPU; bk is kept small so the
    # 3-D intermediate fits VMEM (128·16·128·4B = 1 MiB by default).
    t = (a[:, :, None] + b[None, :, :]
         + (av[:, :, None] * gv[None, :, :]) * bv[None, :, :])
    acc_ref[...] = jnp.minimum(acc_ref[...], jnp.min(t, axis=1))

    @pl.when(k_step == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tropical_matmul_pallas(a, b, av=None, gv=None, bv=None, *,
                           bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                           bk: int = DEFAULT_BK, interpret: bool = False):
    """C = weighted (min,+) product. a: (M, K), b: (K, N); av/gv/bv optional
    rank-1 weights (M,), (K,), (N,) — zeros disable the weighted term."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if av is None:
        av = jnp.zeros((m,), a.dtype)
        gv = jnp.zeros((k,), a.dtype)
        bv = jnp.zeros((n,), a.dtype)

    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k})x({k},{n}) not divisible by blocks ({bm},{bn},{bk})")

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        name="tropical_matmul_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bk, 1), lambda i, j, kk: (kk, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b, av[:, None], gv[:, None], bv[None, :])
