"""Dispatch layer: jit'd public wrappers around the Pallas kernels.

Policy (recorded in DESIGN.md §4): the Pallas path is taken on TPU backends;
CPU (this container, incl. the 512-device dry-run) lowers the pure-jnp
reference path — Mosaic kernels cannot lower to the CPU backend. Tests force
the kernels through ``interpret=True`` to validate them against ``ref.py``.

Set env ``REPRO_KERNELS=pallas|ref|interpret`` to override.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.chunked_scan import chunked_scan_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.grid_pipeline import (grid_pipeline_pallas,
                                         grid_pipeline_pallas_with_args)
from repro.kernels.mcm_pipeline import (mcm_pipeline_pallas,
                                        mcm_pipeline_pallas_with_args)
from repro.kernels.mcm_tiled import (mcm_tiled_pallas,
                                     mcm_tiled_pallas_fused,
                                     mcm_tiled_pallas_with_args,
                                     mcm_tiled_ref, mcm_tiled_ref_fused,
                                     mcm_tiled_ref_with_args)
from repro.kernels.sdp_pipeline import (sdp_chunked_pallas,
                                        sdp_chunked_pallas_with_args,
                                        sdp_pipeline_pallas,
                                        sdp_pipeline_pallas_with_args)
from repro.kernels.semiring_matmul import tropical_matmul_pallas


from repro.dp import envknobs as _envknobs

#: aliased from the central knob catalog (dp/envknobs.py) — one source of
#: truth for modes and defaults; kept as module attributes for the existing
#: import surface
_KERNEL_MODES = _envknobs.knob("REPRO_KERNELS").choices
DEFAULT_VMEM_BUDGET_BYTES = _envknobs.DEFAULT_VMEM_BUDGET_BYTES


def vmem_budget_bytes() -> int:
    """The per-launch VMEM budget, overridable via ``REPRO_VMEM_BUDGET``
    (bytes). Gates kernel-route eligibility (``supports``) and sizes the tiled
    kernels' streaming windows; the resolved value is folded into backend
    cache tags and calibration regime keys (``autotune._jax_backend``) so an
    override never serves stale compiled programs or cross-pollutes
    calibration entries. A malformed value fails loudly naming the env var
    (``dp/envknobs.py``'s validated-on-read contract)."""
    return _envknobs.read("REPRO_VMEM_BUDGET")


#: ``(fn, mode)`` -> calls of the kernel-tier entry ``fn`` in ``mode``
#: (``kernel_mode()``); read by the chip smoke to prove each kernel ran
ENTRY_COUNTS: dict = {}


def _count_entry(fn: str, mode: str) -> None:
    ENTRY_COUNTS[fn, mode] = ENTRY_COUNTS.get((fn, mode), 0) + 1


def kernel_mode() -> str:
    # a typo like "palas" must not silently fall through to the ref path —
    # envknobs.read raises ValueError naming REPRO_KERNELS
    env = _envknobs.read("REPRO_KERNELS")
    if env != "auto":
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
def tropical_matmul(a, b, av=None, gv=None, bv=None, **blocks):
    mode = kernel_mode()
    _count_entry("tropical_matmul", mode)
    if mode == "pallas":
        return tropical_matmul_pallas(a, b, av, gv, bv, **blocks)
    if mode == "interpret":
        return tropical_matmul_pallas(a, b, av, gv, bv, interpret=True, **blocks)
    return ref.tropical_matmul_ref(a, b, av, gv, bv)


def sdp_blocked(init, offsets: tuple, op: str, n: int, block: int = 512,
                weights=None):
    from repro.core.sdp import solve_blocked

    mode = kernel_mode()
    _count_entry("sdp_blocked", mode)
    if mode in ("pallas", "interpret"):
        return sdp_pipeline_pallas(init, offsets, op, n, block=block,
                                   weights=weights,
                                   interpret=(mode == "interpret"))
    return solve_blocked(init, offsets, op, n, block=block, weights=weights)


def sdp_blocked_with_args(init, offsets: tuple, op: str, n: int,
                          block: int = 512, weights=None):
    """Arg-emitting blocked S-DP: the Pallas kernel writes the winning lane
    next to each cost block on the kernel path, the jnp blocked solver
    elsewhere — both with identical first-occurrence tie rules, so
    ``reconstruct=True`` routes through Pallas bit-identically."""
    from repro.core.sdp import solve_blocked_with_args

    mode = kernel_mode()
    _count_entry("sdp_blocked_with_args", mode)
    if mode in ("pallas", "interpret"):
        return sdp_pipeline_pallas_with_args(init, offsets, op, n, block=block,
                                             weights=weights,
                                             interpret=(mode == "interpret"))
    return solve_blocked_with_args(init, offsets, op, n, block=block,
                                   weights=weights)


def mcm_blocked(wtab, n: int):
    """Triangular (split-form) table solve: VMEM-resident diagonal-pipeline
    Pallas kernel on the kernel path, jnp wavefront solver elsewhere."""
    from repro.core.mcm import solve_wavefront_tab

    mode = kernel_mode()
    _count_entry("mcm_blocked", mode)
    if mode in ("pallas", "interpret"):
        return mcm_pipeline_pallas(wtab, n, interpret=(mode == "interpret"))
    return solve_wavefront_tab(wtab, n)


def mcm_blocked_with_args(wtab, n: int):
    """``mcm_blocked`` + best-split table (device-side args on every path)."""
    from repro.core.mcm import solve_wavefront_tab_with_args

    mode = kernel_mode()
    _count_entry("mcm_blocked_with_args", mode)
    if mode in ("pallas", "interpret"):
        return mcm_pipeline_pallas_with_args(wtab, n,
                                             interpret=(mode == "interpret"))
    return solve_wavefront_tab_with_args(wtab, n)


def sdp_chunked(init, offsets: tuple, op: str, n: int, block: int = 512,
                weights=None):
    """HBM-streaming blocked S-DP (DESIGN.md §4): the chunked Pallas kernel
    on the kernel path (VMEM window sized from the budget knob), the jnp
    blocked solver elsewhere. No table-size cap on any path."""
    from repro.core.sdp import solve_blocked

    mode = kernel_mode()
    _count_entry("sdp_chunked", mode)
    if mode in ("pallas", "interpret"):
        return sdp_chunked_pallas(init, offsets, op, n, block=block,
                                  budget=vmem_budget_bytes(), weights=weights,
                                  interpret=(mode == "interpret"))
    return solve_blocked(init, offsets, op, n, block=block, weights=weights)


def sdp_chunked_with_args(init, offsets: tuple, op: str, n: int,
                          block: int = 512, weights=None):
    """``sdp_chunked`` + per-cell winning lanes, first-occurrence tie rule on
    every path."""
    from repro.core.sdp import solve_blocked_with_args

    mode = kernel_mode()
    _count_entry("sdp_chunked_with_args", mode)
    if mode in ("pallas", "interpret"):
        return sdp_chunked_pallas_with_args(init, offsets, op, n, block=block,
                                            budget=vmem_budget_bytes(),
                                            weights=weights,
                                            interpret=(mode == "interpret"))
    return solve_blocked_with_args(init, offsets, op, n, block=block,
                                   weights=weights)


def mcm_tiled(wtab, n: int):
    """Triangular table solve with HBM-streamed weights (DESIGN.md §4): the
    double-buffered tiled Pallas kernel on the kernel path, the equivalent
    banded-tile jnp body elsewhere. No table-size cap on any path."""
    mode = kernel_mode()
    _count_entry("mcm_tiled", mode)
    if mode in ("pallas", "interpret"):
        return mcm_tiled_pallas(wtab, n, budget=vmem_budget_bytes(),
                                interpret=(mode == "interpret"))
    return mcm_tiled_ref(wtab, n)


def mcm_tiled_with_args(wtab, n: int):
    """``mcm_tiled`` + best-split table (device-side args on every path)."""
    mode = kernel_mode()
    _count_entry("mcm_tiled_with_args", mode)
    if mode in ("pallas", "interpret"):
        return mcm_tiled_pallas_with_args(wtab, n, budget=vmem_budget_bytes(),
                                          interpret=(mode == "interpret"))
    return mcm_tiled_ref_with_args(wtab, n)


def mcm_tiled_fused(wtab, n: int):
    """``mcm_tiled_with_args`` + the preorder traceback walked inside the
    same launch (DESIGN.md §5): returns ``(st, args, (node_i, node_d,
    node_e))`` from ONE dispatch, so ``reconstruct=True`` stops paying a
    second one. The ref path fuses solve + ``triangular_traceback`` into one
    jit program — still a single dispatch, same contract."""
    mode = kernel_mode()
    _count_entry("mcm_tiled_fused", mode)
    if mode in ("pallas", "interpret"):
        return mcm_tiled_pallas_fused(wtab, n, budget=vmem_budget_bytes(),
                                      interpret=(mode == "interpret"))
    return mcm_tiled_ref_fused(wtab, n)


def grid_blocked(arrs, meta: tuple):
    """Grid (antidiag/spandiag) table solve: the VMEM-resident wavefront
    Pallas kernel on the kernel path, the jnp masked-wavefront solver
    elsewhere — ``arrs``/``meta`` per ``GridSpec.device_arrays()`` /
    ``static_meta()``."""
    from repro.core.grid import solve_grid

    mode = kernel_mode()
    _count_entry("grid_blocked", mode)
    if mode in ("pallas", "interpret"):
        return grid_pipeline_pallas(arrs, meta,
                                    interpret=(mode == "interpret"))
    return solve_grid(arrs, meta)


def grid_blocked_with_args(arrs, meta: tuple):
    """``grid_blocked`` + the winning move / packed-split table, identical
    first-occurrence tie order on every path."""
    from repro.core.grid import solve_grid_with_args

    mode = kernel_mode()
    _count_entry("grid_blocked_with_args", mode)
    if mode in ("pallas", "interpret"):
        return grid_pipeline_pallas_with_args(arrs, meta,
                                              interpret=(mode == "interpret"))
    return solve_grid_with_args(arrs, meta)


def linear_scan(x, decay, h0, chunk: int = 128):
    """h_t = decay_t ⊙ h_{t-1} + x_t; returns (h_all, h_final)."""
    mode = kernel_mode()
    _count_entry("linear_scan", mode)
    if mode == "pallas":
        return chunked_scan_pallas(x, decay, h0, chunk=chunk)
    if mode == "interpret":
        return chunked_scan_pallas(x, decay, h0, chunk=chunk, interpret=True)
    return ref.chunked_scan_ref(x, decay, h0)


# ---------------------------------------------------------------------------
# Flash attention: (B, Hq, S, D) with GQA kv (B, Hkv, S, D)
# ---------------------------------------------------------------------------
def _gqa_broadcast(k, hq):
    b, hkv, s, d = k.shape
    if hq % hkv != 0:
        # floor-division repeat would silently drop heads (Hkv=3, Hq=7 -> 6)
        raise ValueError(
            f"GQA requires the query head count to be a multiple of the kv "
            f"head count; got Hq={hq} query heads, Hkv={hkv} kv heads")
    rep = hq // hkv
    return jnp.repeat(k, rep, axis=1) if rep > 1 else k


def _flash_chunk_env(default: int) -> int:
    """Resolve the KV chunk size, validating ``REPRO_FLASH_CHUNK`` — a typo
    must fail naming the env var, not as a bare int() ValueError from deep
    inside ``flash_attention`` (dp/envknobs' validated-on-read contract)."""
    return _envknobs.read("REPRO_FLASH_CHUNK", default=default)


@functools.partial(jax.jit, static_argnames=("causal", "chunk"))
def _flash_ref_chunked(q, k, v, causal: bool = True, chunk: int = 512):
    """Memory-safe jnp flash attention: lax.scan over KV chunks with online
    softmax. This is the path the CPU dry-run lowers for prefill cells."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    chunk = min(chunk, sk)
    nk = -(-sk // chunk)
    padded = nk * chunk != sk
    if padded:  # ragged tail: pad KV to whole chunks, mask below
        pad = ((0, 0), (0, 0), (0, nk * chunk - sk), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(sq) + (sk - sq)

    @jax.checkpoint
    def step(carry, kv):
        # remat'd: without this, scan-backward stacks the per-chunk (B,H,Sq,Kc)
        # probability matrices in f32 — the full quadratic attention matrix.
        acc, m, l = carry
        kc, vc, k0 = kv
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(jnp.float32))
        if causal or padded:  # aligned non-causal stays mask-free
            k_pos = k0 + jnp.arange(chunk)
            valid = k_pos[None, :] < sk        # padded tail keys drop out
            if causal:
                valid = valid & (q_pos[:, None] >= k_pos[None, :])
            s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = alpha[..., None] * acc + jnp.einsum("bhqk,bhkd->bhqd", p, vc.astype(jnp.float32))
        return (acc, m_new, l), None

    ks = k.reshape(b, h, nk, chunk, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(b, h, nk, chunk, d).transpose(2, 0, 1, 3, 4)
    k0s = jnp.arange(nk) * chunk
    init = (jnp.zeros((b, h, sq, d), jnp.float32),
            jnp.full((b, h, sq), -jnp.inf, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32))
    (acc, m, l), _ = jax.lax.scan(step, init, (ks, vs, k0s))
    return (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def flash_attention(q, k, v, causal: bool = True, chunk: int = 512):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D). Returns (B, Hq, S, D)."""
    from repro.runtime.sharding import hint

    chunk = _flash_chunk_env(chunk)

    hq = q.shape[1]
    k = _gqa_broadcast(k, hq)
    v = _gqa_broadcast(v, hq)
    # heads shard over model when divisible; else sequence takes the axis
    # (first-fit in spec_for) — e.g. arctic's 56 heads don't divide 16
    ax = ("act_batch", "act_heads", "act_seq_attn", None)
    q, k, v = hint(q, ax), hint(k, ax), hint(v, ax)
    mode = kernel_mode()
    _count_entry("flash_attention", mode)
    if mode in ("pallas", "interpret"):
        b, h, s, d = q.shape
        out = flash_attention_pallas(
            q.reshape(b * h, s, d), k.reshape(b * h, s, d), v.reshape(b * h, s, d),
            causal=causal, interpret=(mode == "interpret"))
        return out.reshape(b, h, s, d)
    return _flash_ref_chunked(q, k, v, causal=causal, chunk=chunk)
