"""Plane sources (DESIGN.md §9): a grid spec's compact form from which a
batch program builds the weight, preset and mask planes on the device.

The gotoh builder must reproduce ``_gotoh_encode``'s planes bit for bit;
batch routes must answer bit-equal with and without the source; a bucket
that does not share one builder falls back to sending planes; specs derived
from other planes drop the source."""
import dataclasses
import zlib

import numpy as np
import pytest

import jax

from repro import dp
from repro.dp import backends as _backends
from repro.dp import zoo as _zoo
from repro.dp.problem import PlaneSource, plane_builder
from repro.dp.sharding import ShardContext, default_mesh

#: BWA-MEM's default scoring (bwa mem -A 1 -B 4 -O 6 -E 1)
_BWA = {"match": 1.0, "mismatch": -4.0, "gap_open": -7.0, "gap_extend": -1.0}


def _rng(tag: str) -> np.random.Generator:
    return np.random.default_rng(zlib.crc32(tag.encode()))


def _gotoh(rng, m: int, c: int, scoring=None):
    if scoring is None:
        scoring = {k: v for k, v in _zoo._gotoh_sample(rng, 4).items()
                   if k not in ("x", "y")}
    return dp.get_problem("gotoh").encode(
        x=rng.integers(0, 4, m), y=rng.integers(0, 4, c), **scoring)


def _planes_only(spec):
    return dataclasses.replace(spec, source=None)


def _bucket(tag: str, lanes: int = 4, m: int = 9, c: int = 13):
    rng = _rng(tag)
    return [_gotoh(rng, m, c) for _ in range(lanes)]


@pytest.mark.parametrize("case", [
    "bwa-100", "bwa-250", "sample-0", "sample-1", "sample-2", "m1", "c1",
    "m1c1"])
def test_gotoh_builder_planes_bit_equal(case):
    rng = _rng(f"builder/{case}")
    sizes = {"bwa-100": (100, 132), "bwa-250": (250, 282), "m1": (1, 17),
             "c1": (17, 1), "m1c1": (1, 1)}
    m, c = sizes.get(case, (int(rng.integers(2, 40)),
                            int(rng.integers(2, 40))))
    spec = _gotoh(rng, m, c, _BWA if case.startswith("bwa") else None)
    assert spec.source is not None and spec.source.builder == "gotoh"
    meta = spec.static_meta()
    built = jax.jit(lambda a: plane_builder("gotoh")(a, meta))(
        spec.source.arrays)
    host = spec.device_arrays()
    assert len(built) == len(host) == 3
    for name, h, d in zip(("weights", "init", "init_mask"), host, built):
        d = np.asarray(d)
        assert d.dtype == h.dtype and d.shape == h.shape, name
        np.testing.assert_array_equal(d.view(np.int32), h.view(np.int32),
                                      err_msg=f"{case}: {name}")


def test_gotoh_source_is_one_small_int32_vector():
    spec = _gotoh(_rng("compact"), 100, 132, _BWA)
    packed, = spec.source.arrays
    assert packed.dtype == np.int32
    assert packed.shape == (2 * (100 + 132) + 4,)
    assert packed.nbytes < sum(a.nbytes for a in spec.device_arrays()) / 100


def test_gotoh_source_needs_int32_symbols():
    """Symbols the int32 cast would merge, or that are not integers, keep
    the spec on the planes."""
    prob = dp.get_problem("gotoh")
    big = np.array([0, 2 ** 40], np.int64)
    assert prob.encode(x=big, y=big + 1, **_BWA).source is None
    chars = np.array(list("ACGT"))
    assert prob.encode(x=chars, y=chars[::-1], **_BWA).source is None
    assert prob.encode(x=np.array([True, False]), y=np.array([True]),
                       **_BWA).source is not None


_ROUTES = [("kernel_grid", "ref"), ("kernel_grid", "interpret"),
           ("grid_wavefront", None)]


@pytest.mark.parametrize("with_args", [False, True],
                         ids=["batch_run", "batch_run_with_args"])
@pytest.mark.parametrize("backend,mode", _ROUTES,
                         ids=[f"{b}-{m}" for b, m in _ROUTES])
def test_batch_routes_bit_equal_with_and_without_source(
        monkeypatch, backend, mode, with_args):
    if mode is not None:
        monkeypatch.setenv("REPRO_KERNELS", mode)
    be = _backends.get(backend)
    run = be.batch_run_with_args if with_args else be.batch_run
    specs = _bucket(f"routes/{backend}/{mode}/{with_args}")
    _backends.drain_trace_log()
    got = run(specs)
    keys = _backends.drain_trace_log()
    want = run([_planes_only(s) for s in specs])
    assert keys and all(("source", "gotoh") in k for k in keys)
    if not with_args:
        got, want = (got,), (want,)
    for g, w in zip(got, want):
        assert len(g) == len(w) == len(specs)
        for a, b in zip(g, w):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)


def test_mixed_bucket_falls_back_to_planes(monkeypatch):
    specs = _bucket("mixed")
    mixed = specs[:2] + [_planes_only(s) for s in specs[2:]]
    other = PlaneSource("another", specs[0].source.arrays)
    renamed = specs[:3] + [dataclasses.replace(specs[3], source=other)]
    assert _backends.common_source(specs) == "gotoh"
    assert _backends.common_source(mixed) is None
    assert _backends.common_source(renamed) is None

    def refuse(*a, **k):
        raise AssertionError("a mixed bucket must send planes")

    be = _backends.get("grid_wavefront")
    want = be.batch_run(specs)
    monkeypatch.setattr(_backends, "stack_sources", refuse)
    for bucket in (mixed, renamed):
        for a, b in zip(be.batch_run(bucket), want):
            np.testing.assert_array_equal(a, b)


def test_sharded_context_places_sources():
    """On a mesh the stacked source goes through ``ShardContext.place``;
    answers match the single-device program (one device here, as many as
    are visible under the multi-device test leg)."""
    ctx = ShardContext(mesh=default_mesh())
    specs = _bucket("sharded", lanes=2 * ctx.ndev)
    be = _backends.get("grid_wavefront")
    got_t, got_a = be.batch_run_with_args(specs, sharding=ctx)
    want_t, want_a = be.batch_run_with_args([_planes_only(s) for s in specs])
    for g, w in zip(got_t + got_a, want_t + want_a):
        np.testing.assert_array_equal(g, w)


def test_derived_specs_drop_the_source():
    rng = _rng("derived")
    full = _gotoh(rng, 9, 14, _BWA)
    prefix = full.split_spec(10)
    assert full.source is not None and prefix.source is None
    ext = prefix.extend_spec(full.extension_delta(prefix))
    assert ext.source is None
    np.testing.assert_array_equal(ext.weights, full.weights)
    # the source takes no part in equality: the planes are the content
    assert dataclasses.replace(full, source=None).source is None
    assert dp.problem.spec_digest(_planes_only(full)) == \
        dp.problem.spec_digest(full)
