"""Pipeline parallelism — the paper's skewed schedule as a mesh runtime.

``SkewedSchedule`` (core/schedule.py) is shared verbatim with the S-DP/MCM
solvers: stage ``j`` serves microbatch ``t - j`` at step ``t``; the pipeline
fills for S-1 steps, streams one microbatch per step, and drains. Activations
move stage→stage with ``lax.ppermute`` inside ``shard_map`` over a "stage"
mesh axis; stage assignment is balanced by the DP planner
(``planner.partition_stages``).

Forward pipeline (inference / the serving path). Training PP (1F1B with
activation stashes) composes the same schedule twice and is left as the
documented extension — the production meshes in this repo train with
FSDP×TP, PP is the serving-latency feature.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.schedule import SkewedSchedule


def pipeline_apply(stage_fn: Callable, stacked_params, x_micro, mesh: Mesh,
                   axis: str = "stage"):
    """Run `stage_fn(params_s, x)` as an S-stage pipeline over microbatches.

    stacked_params: pytree with leading (S, …) axis (one slice per stage).
    x_micro: (M, mb, d) microbatched input (replicated).
    Returns (M, mb, d) outputs (replicated), equal to applying the S stages
    in sequence to every microbatch.
    """
    s = mesh.shape[axis]
    m = x_micro.shape[0]
    sched = SkewedSchedule(num_items=m, num_stages=s)

    def inner(params_local, xs):
        idx = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(t, carry):
            buf, outs = carry
            item = t - idx                                  # SkewedSchedule.items_at
            active = (item >= 0) & (item < m)
            x_in = jnp.where(idx == 0, xs[jnp.clip(t, 0, m - 1)], buf)
            y = stage_fn(p, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage emits; everyone else forwards
            write = active & (idx == s - 1)
            oi = jnp.clip(item, 0, m - 1)
            outs = outs.at[oi].set(jnp.where(write, y, outs[oi]))
            nxt = jax.lax.ppermute(y, axis, [(i, (i + 1) % s) for i in range(s)])
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, sched.num_steps, step, (buf, outs))
        return jax.lax.psum(outs, axis)                     # zeros elsewhere

    fn = jax.shard_map(inner, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    return fn(stacked_params, x_micro)


def stage_boundaries(layer_costs, num_stages: int):
    """DP-balanced contiguous layer→stage assignment (planner integration)."""
    from repro.core.planner import partition_stages

    bounds, bottleneck = partition_stages(layer_costs, num_stages)
    return bounds, bottleneck
