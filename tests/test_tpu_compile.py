"""The DP kernels compile for a TPU v5e (DESIGN.md §4).

Interpret mode runs a kernel body on the CPU but cannot show what Mosaic
refuses: lane-dynamic slices, unaligned blocks, layouts it cannot infer.
These tests compile every DP kernel for a *described* ``v5e:2x2`` topology
(the TPU compiler is installed, no chip is needed), at the sizes
``chip_smoke.py`` serves, plus one vmapped batch drain program per
streaming route and one ``shard_map`` drain program over four devices.

The topology is described inside a module-scoped fixture — never at
import time — and the persistent compilation cache is off around the
compiles (an entry written for a described device cannot be read back).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core.mcm import num_cells
from repro.kernels import grid_pipeline as gp
from repro.kernels import mcm_pipeline as mp
from repro.kernels import mcm_tiled as mt
from repro.kernels import ops
from repro.kernels import sdp_pipeline as sp

f32 = jnp.float32

#: chip_smoke.py's shapes: edit_distance 60×60 (kernel_blocked), knapsack
#: capacity 10⁵ with 64 item weights (kernel_tiled), mcm n=128 / n=512,
#: gotoh 250×300 and needleman_wunsch 512×1088 (kernel_grid)
EDIT_OFFSETS, EDIT_N = (62, 61, 1), 61 * 61
KNAP_OFFSETS = tuple(sorted({1} | set(range(15, 15 * 65, 15)), reverse=True))
KNAP_N = 100_001
GOTOH = (251, 301, 3, ((0, 0, 1, 1), (0, 1, 1, 1), (0, 2, 1, 1),
                       (1, 0, 1, 0), (1, 1, 1, 0), (2, 0, 0, 1), (2, 2, 0, 1)))
NW = (513, 1089, 1, ((0, 0, 1, 1), (0, 0, 1, 0), (0, 0, 0, 1)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def pallas_mode(monkeypatch):
    """Route the ``ops`` wrappers to the Mosaic kernels, as on a TPU."""
    monkeypatch.setenv("REPRO_KERNELS", "pallas")


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _grid_meta(rows, cols, planes, moves):
    return ("antidiag", "max", planes, rows, cols, moves, ())


def _grid_shapes(rows, cols, planes, moves):
    return (((len(moves), rows, cols), f32), ((planes, rows, cols), f32),
            ((planes, rows, cols), f32))


CKY_RULES = ((0, 1, 2), (0, 2, 1), (1, 1, 1), (2, 3, 2), (3, 0, 3))


@pytest.mark.parametrize("case", [
    "sdp_resident", "sdp_resident_weighted_args", "sdp_chunked",
    "sdp_chunked_args", "mcm_pipeline", "mcm_pipeline_args",
    "mcm_tiled", "mcm_tiled_args", "mcm_tiled_fused",
    "grid_antidiag_args", "grid_antidiag_nw", "grid_spandiag_args"])
def test_kernel_compiles_for_v5e(case, one_chip):
    """Each DP kernel of the chip path compiles with Mosaic at the smoke's
    sizes (no interpreter, no jnp stand-in)."""
    budget = ops.DEFAULT_VMEM_BUDGET_BYTES
    if case == "sdp_resident":
        _compile(lambda i: sp.sdp_pipeline_pallas(i, (3, 2, 1), "min", 4096),
                 one_chip, ((3,), f32))
    elif case == "sdp_resident_weighted_args":
        _compile(lambda i, w: sp.sdp_pipeline_pallas_with_args(
            i, EDIT_OFFSETS, "min", EDIT_N, weights=w), one_chip,
            ((EDIT_OFFSETS[0],), f32), ((EDIT_N, 3), f32))
    elif case in ("sdp_chunked", "sdp_chunked_args"):
        fn = (sp.sdp_chunked_pallas_with_args if case.endswith("args")
              else sp.sdp_chunked_pallas)
        _compile(lambda i, w: fn(i, KNAP_OFFSETS, "max", KNAP_N,
                                 budget=budget, weights=w), one_chip,
                 ((KNAP_OFFSETS[0],), f32), ((KNAP_N, len(KNAP_OFFSETS)), f32))
    elif case.startswith("mcm_pipeline"):
        fn = (mp.mcm_pipeline_pallas_with_args if case.endswith("args")
              else mp.mcm_pipeline_pallas)
        _compile(lambda w: fn(w, 128), one_chip, ((num_cells(128), 127), f32))
    elif case.startswith("mcm_tiled"):
        fn = {"mcm_tiled": mt.mcm_tiled_pallas,
              "mcm_tiled_args": mt.mcm_tiled_pallas_with_args,
              "mcm_tiled_fused": mt.mcm_tiled_pallas_fused}[case]
        _compile(lambda w: fn(w, 512, budget=budget), one_chip,
                 ((num_cells(512), 511), f32))
    elif case == "grid_antidiag_args":
        meta = _grid_meta(*GOTOH)
        _compile(lambda *a: gp.grid_pipeline_pallas_with_args(a, meta),
                 one_chip, *_grid_shapes(*GOTOH))
    elif case == "grid_antidiag_nw":
        meta = _grid_meta(*NW)
        _compile(lambda *a: gp.grid_pipeline_pallas(a, meta), one_chip,
                 *_grid_shapes(*NW))
    else:
        meta = ("spandiag", "max", 4, 32, 32, (), CKY_RULES)
        _compile(lambda rw, init: gp.grid_pipeline_pallas_with_args(
            (rw, init), meta), one_chip,
            ((len(CKY_RULES),), f32), ((4, 32), f32))


def _gotoh_source_program(meta):
    """The drain program of a gotoh bucket that carries plane sources:
    the planes are built from each lane's packed int32 vector."""
    from repro.dp.problem import plane_builder

    build = plane_builder("gotoh")
    return jax.vmap(lambda *a: ops.grid_blocked_with_args(build(a, meta),
                                                          meta))


#: a gotoh source vector at GOTOH's size: symbols, 4 scores, 2 gap edges
GOTOH_SOURCE = ((2 * (GOTOH[0] - 1 + GOTOH[1] - 1) + 4,), jnp.int32)


@pytest.mark.parametrize("route", ["kernel_grid", "kernel_grid_source",
                                   "kernel_tiled_wavefront"])
def test_vmapped_drain_program_compiles(route, one_chip, pallas_mode):
    """A batch-8 drain program as the backends build it: the route's
    ``ops`` entry point vmapped over the stacked bucket."""
    if route == "kernel_grid":
        meta = _grid_meta(*GOTOH)
        shapes = [((8,) + s, d) for s, d in _grid_shapes(*GOTOH)]
        fn = jax.vmap(lambda *a: ops.grid_blocked_with_args(a, meta))
    elif route == "kernel_grid_source":
        meta = _grid_meta(*GOTOH)
        shapes = [((8,) + GOTOH_SOURCE[0], GOTOH_SOURCE[1])]
        fn = _gotoh_source_program(meta)
    else:
        shapes = [((8, num_cells(512), 511), f32)]
        fn = jax.vmap(lambda w: ops.mcm_tiled_fused(w, 512))
    _compile(fn, one_chip, *shapes)


def test_sharded_drain_program_compiles(topo, pallas_mode):
    """The four-chip drain: ``ShardContext.wrap`` shard_maps the vmapped
    grid program over a 4-device mesh; each device runs the kernel on its
    shard of the bucket."""
    meta = _grid_meta(*GOTOH)
    _compile_sharded(topo, jax.vmap(
        lambda *a: ops.grid_blocked_with_args(a, meta)), _grid_shapes(*GOTOH))


def test_sharded_source_drain_program_compiles(topo, pallas_mode):
    """The four-chip drain of a bucket sent as plane sources: each device
    builds its shard's planes, then runs the kernel."""
    _compile_sharded(topo, _gotoh_source_program(_grid_meta(*GOTOH)),
                     [GOTOH_SOURCE])


def _compile_sharded(topo, call, shapes):
    from jax.sharding import Mesh

    from repro.dp.sharding import BATCH_AXIS, ShardContext

    mesh = Mesh(np.array(topo.devices), (BATCH_AXIS,))
    ctx = ShardContext(mesh=mesh)
    shard = NamedSharding(mesh, PartitionSpec(BATCH_AXIS))
    args = [jax.ShapeDtypeStruct((8,) + s, d, sharding=shard)
            for s, d in shapes]
    compiled = ctx.wrap(call).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert len(compiled.output_shardings[0].device_set) == 4
