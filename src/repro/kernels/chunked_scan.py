"""Pallas TPU kernel: chunked gated linear scan  h_t = decay_t ⊙ h_{t-1} + x_t.

This is the S-DP pipeline idea applied to the recurrences inside the assigned
SSM/RWKV architectures (DESIGN.md §3): the sequence is cut into chunks; the
inter-chunk state is carried sequentially in a VMEM scratch that persists
across the (sequential) chunk grid dimension, while each chunk's (C × D) tile
is streamed HBM→VMEM and processed with vector ops — chunk b+1's DMA overlaps
chunk b's compute, a literal two-stage pipeline.

Grid: (D/bd, T/C) — feature blocks parallel (outer), chunks sequential (inner).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128
DEFAULT_BD = 256


def _kernel(x_ref, d_ref, h0_ref, o_ref, hlast_ref, carry_ref):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = h0_ref[...]

    x = x_ref[...]        # (C, bd)
    dec = d_ref[...]      # (C, bd)
    C = x.shape[0]

    def row(t, st):
        h, out = st
        h = dec[t] * h + x[t]
        return h, jax.lax.dynamic_update_slice(out, h[None, :], (t, 0))

    h, out = jax.lax.fori_loop(
        0, C, row, (carry_ref[0, :], jnp.zeros_like(x)))
    o_ref[...] = out
    carry_ref[...] = h[None, :]

    @pl.when(c == pl.num_programs(1) - 1)
    def _done():
        hlast_ref[...] = carry_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "bd", "interpret"))
def chunked_scan_pallas(x, decay, h0, *, chunk: int = DEFAULT_CHUNK,
                        bd: int = DEFAULT_BD, interpret: bool = False):
    """x, decay: (T, D); h0: (D,). Returns (h_all (T, D), h_final (D,))."""
    t, d = x.shape
    chunk = min(chunk, t)
    bd = min(bd, d)
    if t % chunk or d % bd:
        raise ValueError(f"(T={t}, D={d}) not divisible by (chunk={chunk}, bd={bd})")
    grid = (d // bd, t // chunk)
    h_all, h_last = pl.pallas_call(
        _kernel,
        name="chunked_scan_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, bd), lambda j, c: (c, j)),
            pl.BlockSpec((chunk, bd), lambda j, c: (c, j)),
            pl.BlockSpec((1, bd), lambda j, c: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((chunk, bd), lambda j, c: (c, j)),
            pl.BlockSpec((1, bd), lambda j, c: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
    )(x, decay, h0[None, :])
    return h_all, h_last[0]
