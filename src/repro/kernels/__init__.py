"""Pallas TPU kernels for the perf-critical compute spots, each with a
pure-jnp oracle in ``ref.py`` and a dispatching wrapper in ``ops.py``.

  * ``semiring_matmul`` — weighted tropical (min,+) GEMM (blocked MCM core)
  * ``sdp_pipeline``    — VMEM-resident blocked pipelined S-DP solver
                          (weighted + arg-emitting variants, DESIGN.md §4)
                          plus the HBM-streaming chunked variant (no size cap)
  * ``mcm_pipeline``    — VMEM-resident diagonal-pipeline triangular solver
  * ``mcm_tiled``       — tiled triangular solver with HBM-streamed,
                          double-buffered weights and fused traceback (§4/§5)
  * ``grid_pipeline``   — VMEM-resident frontier-major wavefront solver for
                          the grid family (antidiag/spandiag, DESIGN.md §9)
  * ``chunked_scan``    — gated linear recurrence (SSM/RWKV layers)
  * ``flash_attention`` — causal online-softmax attention (prefill cells)
"""
from repro.kernels import ops, ref  # noqa: F401

# ---------------------------------------------------------------------------
# Backend registration (repro.dp): the Pallas kernel tier.
#
# ``kernel_blocked`` (linear) and ``kernel_wavefront`` (triangular) route
# through ``ops`` wrappers, so one registered backend covers every kernel
# mode: the Pallas VMEM kernels on TPU (or under REPRO_KERNELS=
# pallas|interpret), the equivalent jnp solver elsewhere. Costs are honest
# per mode — discounted where the VMEM-resident kernel actually lowers
# (one HBM load + store of the table), penalized on the jnp fallback (same
# program as the plain route plus indirection) and heavily penalized under
# the Python interpreter. ``supports`` enforces the VMEM budget whenever the
# kernel path would be taken, and ``cache_tag`` folds the kernel mode into
# the batch-jit cache keys so a mid-process REPRO_KERNELS flip can never
# serve a program traced under the old mode (DESIGN.md §4).
#
# ``kernel_tiled`` (linear, chunked HBM-streaming) and
# ``kernel_tiled_wavefront`` (triangular, HBM-streamed weights + fused
# traceback) have NO supports() size cap — the streaming window is sized
# from ``ops.vmem_budget_bytes()`` (env ``REPRO_VMEM_BUDGET``), which is
# folded into the cache tag alongside the mode so a budget override never
# serves stale programs.
# ---------------------------------------------------------------------------
from repro.dp import backends as _dp_backends  # noqa: E402


def _mode_factor() -> float:
    mode = ops.kernel_mode()
    if mode == "pallas":
        return 0.5      # VMEM-resident table: one HBM load + one store
    if mode == "interpret":
        return 32.0     # Python-interpreted kernel body (test mode)
    return 1.25         # jnp fallback — plain solver + wrapper indirection


def _tiled_mode_factor() -> float:
    """Per-mode factor for the HBM-streaming tiled routes. Slightly worse
    than the VMEM-resident kernels where those fit (DMA orchestration
    overhead) but the only kernel routes with no size cap; the interpreter
    penalty is harsher still — per-tile grid steps are Python loops there."""
    mode = ops.kernel_mode()
    if mode == "pallas":
        return 0.6
    if mode == "interpret":
        return 40.0
    return 1.2          # banded-tile jnp body: wins at large n (BENCH large_n)


def _on_kernel_path() -> bool:
    return ops.kernel_mode() in ("pallas", "interpret")


def _linear_vmem_bytes(spec) -> int:
    """Working set of the (weighted, arg-emitting) VMEM-resident S-DP
    kernel: window, weight rows, cost and arg outputs. Geometry comes from
    the kernel itself so the gate can't diverge from the real buffers."""
    from repro.kernels.sdp_pipeline import resident_vmem_bytes

    return resident_vmem_bytes(spec.offsets, spec.n,
                               weighted=spec.weights is not None)


def _triangular_vmem_bytes(spec) -> int:
    """Working set of the triangular kernel: the weight planes (the
    dominant ~2n³ bytes term) plus the cost and arg tables, from the
    kernel's own geometry."""
    from repro.kernels.mcm_pipeline import vmem_bytes

    return vmem_bytes(spec.n)


def _kernel_blocked_cost(spec) -> float:
    return _dp_backends.linear_costs(spec)["blocked"] * _mode_factor()


def _kernel_blocked_supports(spec) -> bool:
    return (not _on_kernel_path()
            or _linear_vmem_bytes(spec) <= ops.vmem_budget_bytes())


def _kernel_wavefront_cost(spec) -> float:
    return _dp_backends.triangular_costs(spec)["wavefront"] * _mode_factor()


def _kernel_wavefront_supports(spec) -> bool:
    return (not _on_kernel_path()
            or _triangular_vmem_bytes(spec) <= ops.vmem_budget_bytes())


def _grid_vmem_bytes(spec) -> int:
    """f32 + int32 working set of the grid wavefront kernel (frontier-major
    buffers + arg store); geometry comes from the kernel itself."""
    from repro.kernels.grid_pipeline import grid_vmem_bytes

    return grid_vmem_bytes(spec)


def _kernel_grid_cost(spec) -> float:
    return _dp_backends.grid_costs(spec)["grid_wavefront"] * _mode_factor()


def _kernel_grid_supports(spec) -> bool:
    return (not _on_kernel_path()
            or _grid_vmem_bytes(spec) <= ops.vmem_budget_bytes())


def _kernel_tiled_cost(spec) -> float:
    # the VMEM-resident blocked prior plus a flat streaming-orchestration
    # term, so where both fit the resident kernel stays preferred
    return _dp_backends.linear_costs(spec)["blocked"] * _tiled_mode_factor() + 8.0


def _kernel_tiled_wavefront_cost(spec) -> float:
    return (_dp_backends.triangular_costs(spec)["tiled_wavefront"]
            * _tiled_mode_factor())


def _mode_tag() -> tuple:
    tag = (ops.kernel_mode(),)
    budget = ops.vmem_budget_bytes()
    if budget != ops.DEFAULT_VMEM_BUDGET_BYTES:
        tag += (("vmem", budget),)
    return tag


#: the REPRO_* knobs every kernel-tier route's traced program depends on —
#: ``_mode_tag`` must react to each (the ``repro.analysis`` linter flips
#: them and asserts the tag changes)
_KERNEL_ENV = ("REPRO_KERNELS", "REPRO_VMEM_BUDGET")

from repro.dp import schedule as _sched  # noqa: E402

_dp_backends.register(_dp_backends.linear_backend(
    "kernel_blocked", ops.sdp_blocked, cost=_kernel_blocked_cost,
    supports=_kernel_blocked_supports,
    jax_arg_fn=ops.sdp_blocked_with_args, cache_tag=_mode_tag,
    schedule=_sched.linear_kernel_blocked_schedule,
    env_sensitive=_KERNEL_ENV,
    doc="ops.sdp_blocked: Pallas VMEM-resident pipeline (weighted + "
        "arg-emitting) on the kernel path, jnp blocked solver elsewhere"))

_dp_backends.register(_dp_backends.triangular_tab_backend(
    "kernel_wavefront", ops.mcm_blocked, cost=_kernel_wavefront_cost,
    supports=_kernel_wavefront_supports,
    jax_arg_fn=ops.mcm_blocked_with_args, cache_tag=_mode_tag,
    schedule=_sched.mcm_kernel_schedule,
    env_sensitive=_KERNEL_ENV,
    doc="ops.mcm_blocked: Pallas VMEM-resident diagonal pipeline over the "
        "weight table on the kernel path, jnp wavefront solver elsewhere"))

_dp_backends.register(_dp_backends.linear_backend(
    "kernel_tiled", ops.sdp_chunked, cost=_kernel_tiled_cost,
    jax_arg_fn=ops.sdp_chunked_with_args, cache_tag=_mode_tag,
    schedule=_sched.linear_kernel_tiled_schedule,
    env_sensitive=_KERNEL_ENV,
    doc="ops.sdp_chunked: HBM-streaming chunked S-DP pipeline — the table "
        "streams through a budget-sized VMEM window; no size cap"))

_dp_backends.register(_dp_backends.grid_backend(
    "kernel_grid", ops.grid_blocked, cost=_kernel_grid_cost,
    supports=_kernel_grid_supports,
    jax_arg_fn=ops.grid_blocked_with_args, cache_tag=_mode_tag,
    schedule=_sched.grid_kernel_schedule,
    env_sensitive=_KERNEL_ENV,
    doc="ops.grid_blocked: Pallas VMEM-resident frontier-major wavefront "
        "kernel (antidiag/spandiag, arg-emitting) on the kernel path, jnp "
        "masked wavefront solver elsewhere"))

_dp_backends.register(_dp_backends.triangular_tab_backend(
    "kernel_tiled_wavefront", ops.mcm_tiled,
    cost=_kernel_tiled_wavefront_cost,
    jax_arg_fn=ops.mcm_tiled_with_args, jax_fused_fn=ops.mcm_tiled_fused,
    cache_tag=_mode_tag,
    schedule=_sched.mcm_tiled_schedule,
    env_sensitive=_KERNEL_ENV,
    doc="ops.mcm_tiled: tiled triangular solver, HBM-streamed per-tile "
        "weight DMA, fused in-launch traceback; no size cap"))
