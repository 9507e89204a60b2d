"""Pallas TPU kernel: causal flash attention (online softmax, VMEM tiles).

Used for the `prefill_32k` shape cells where materializing (Sq × Sk) logits is
impossible. Classic structure: grid (B·H, Sq/bq, Sk/bk) with the KV dimension
innermost (sequential); scratch (acc, m, l) persists across KV steps — again
the paper's fill/accumulate/drain pipeline, with the online-softmax rescale as
the ⊗-combine.

GQA is handled by the wrapper (kv heads broadcast to q heads before the call);
`decode`-shape attention uses the mesh-level flash-decode path in
``models/attention.py`` instead of this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, causal, bq, bk):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    run_block = True
    if causal:
        # skip blocks strictly above the diagonal: q_max < k_min
        run_block = (iq + 1) * bq - 1 >= ik * bk

    @pl.when(run_block)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bk)
        if causal:
            qi = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= ki, s, NEG_INF)
        m_prev = m_ref[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)               # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        o_ref[0, :, :] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False):
    """q, k, v: (BH, S, D) with kv already broadcast to q heads."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"S ({sq},{sk}) not divisible by blocks ({bq},{bk})")
    scale = 1.0 / (d ** 0.5)
    grid = (bh, sq // bq, sk // bk)
    kernel = functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        name="flash_attention_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
