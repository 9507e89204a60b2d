"""Plane sources (DESIGN.md §9): a grid spec's compact form from which a
batch program builds the weight, preset and mask planes on the device, and
the host builds them only when a host reader asks.

The gotoh builders, device and host, must reproduce the planes the encode
used to materialize bit for bit; a sourced spec digests its source, and
every spec without one digests as before; batch routes and the service
must answer bit-equal with and without the source, and the service must
never build a sourced spec's planes on the host; a bucket that does not
share one builder falls back to sending planes; specs derived from other
planes drop the source."""
import dataclasses
import zlib

import numpy as np
import pytest

import jax

from repro import dp
from repro.dp import backends as _backends
from repro.dp import zoo as _zoo
from repro.dp import telemetry as _telemetry
from repro.dp.problem import (GridSpec, PlaneSource, plane_builder,
                              spec_digest, spec_digest_bytes)
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


def _old_gotoh_planes(x, y, match=2.0, mismatch=-1.0, gap_open=-3.0,
                      gap_extend=-1.0):
    """The planes gotoh's encode materialized before its specs became
    sourced: (weights, init, init_mask), float64 arithmetic cast to
    float32 on assignment."""
    x, y = np.asarray(x), np.asarray(y)
    m, c = len(x), len(y)
    R, C = m + 1, c + 1
    w = np.full((7, R, C), -np.inf, dtype=np.float32)
    s = np.where(x[:, None] == y[None, :], match, mismatch)
    w[0, 1:, 1:] = w[1, 1:, 1:] = w[2, 1:, 1:] = s
    w[3, 1:, :] = gap_open
    w[4, 1:, :] = gap_extend
    w[5, :, 1:] = gap_open
    w[6, :, 1:] = gap_extend
    init = np.full((3, R, C), -np.inf, dtype=np.float32)
    mask = np.zeros((3, R, C), dtype=bool)
    mask[:, 0, :] = mask[:, :, 0] = True
    init[0, 0, 0] = 0.0
    init[1, 1:, 0] = gap_open + gap_extend * np.arange(m)
    init[2, 0, 1:] = gap_open + gap_extend * np.arange(c)
    return w, init, mask


def _held(spec) -> bool:
    """Whether the spec holds its planes on the host (without building)."""
    return spec.__dict__.get("_weights") is not None


def _assert_planes_equal(spec, want, what=""):
    for name, h, w in zip(("weights", "init", "init_mask"),
                          (spec.weights, spec.init, spec.init_mask), want):
        assert h.dtype == w.dtype and h.shape == w.shape, (what, name)
        np.testing.assert_array_equal(h.view(np.uint8), w.view(np.uint8),
                                      err_msg=f"{what}: {name}")


def _bucket(tag: str, lanes: int = 4, m: int = 9, c: int = 13):
    rng = _rng(tag)
    return [_gotoh(rng, m, c) for _ in range(lanes)]


_BUILDER_CASES = ["bwa-100", "bwa-250", "sample-0", "sample-1", "sample-2",
                  "m1", "c1", "m1c1"]


def _builder_case(case):
    """``(spec, payload)`` of one builder case: a fresh sourced encode."""
    rng = _rng(f"builder/{case}")
    sizes = {"bwa-100": (100, 132), "bwa-250": (250, 282), "m1": (1, 17),
             "c1": (17, 1), "m1c1": (1, 1)}
    m, c = sizes.get(case, (int(rng.integers(2, 40)),
                            int(rng.integers(2, 40))))
    scoring = (_BWA if case.startswith("bwa") else
               {k: v for k, v in _zoo._gotoh_sample(rng, 4).items()
                if k not in ("x", "y")})
    payload = {"x": rng.integers(0, 4, m), "y": rng.integers(0, 4, c),
               **scoring}
    return dp.get_problem("gotoh").encode(**payload), payload


@pytest.mark.parametrize("case", _BUILDER_CASES)
def test_gotoh_builder_planes_bit_equal(case):
    spec, _ = _builder_case(case)
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


@pytest.mark.parametrize("case", _BUILDER_CASES)
def test_gotoh_host_planes_equal_the_old_encode(case):
    """The encode leaves a sourced spec's planes out; the first read
    builds them on the host, bit-equal to what the encode used to
    materialize, and keeps them."""
    spec, payload = _builder_case(case)
    assert not _held(spec)
    _assert_planes_equal(spec, _old_gotoh_planes(**payload), case)
    assert _held(spec)
    assert spec.weights is spec.weights


def test_gotoh_planes_are_built_once_under_a_span(monkeypatch):
    """Reading any plane of a sourced spec builds all three on the host
    once, inside the profiler span ``dp.planes``; the digest, validate and
    the batch program's source build none."""
    opened = []
    real = _telemetry.trace_span
    monkeypatch.setattr(_telemetry, "trace_span",
                        lambda name, **a: opened.append(name) or real(name))
    spec = _gotoh(_rng("span"), 20, 30, _BWA)
    spec.validate()
    spec_digest(spec)
    assert not opened and not _held(spec)
    spec.init_mask
    spec.device_arrays()
    spec.weights
    assert opened == ["dp.planes"]


def test_gotoh_source_is_one_small_int32_vector():
    spec = _gotoh(_rng("compact"), 100, 132, _BWA)
    packed, = spec.source.arrays
    assert packed.dtype == np.int32
    assert packed.shape == (2 * (100 + 132) + 4,)
    assert packed.nbytes < sum(a.nbytes for a in spec.device_arrays()) / 100


def test_sourced_spec_is_validated_by_its_source():
    """validate() checks a sourced spec's source arrays, length
    ``2 (m + c) + 4`` int32, without building its planes."""
    spec = _gotoh(_rng("validate"), 7, 11, _BWA)
    spec.validate()
    packed, = spec.source.arrays
    bad = {"short": packed[:-1], "int64": packed.astype(np.int64),
           "two": None}
    for what, arr in bad.items():
        arrays = (packed, packed) if arr is None else (arr,)
        broken = _sourced(spec, source=PlaneSource("gotoh", arrays))
        with pytest.raises(ValueError, match="source arrays"):
            broken.validate()
        assert not _held(broken), what
    unknown = _sourced(spec, source=PlaneSource("nope", (packed,)))
    with pytest.raises(ValueError, match="unknown plane builder"):
        unknown.validate()
    chart = GridSpec.probe_specs()[2]
    with pytest.raises(ValueError, match="only antidiag"):
        dataclasses.replace(chart, source=spec.source).validate()


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
    payload = {"x": rng.integers(0, 4, 9), "y": rng.integers(0, 4, 14),
               **_BWA}
    full = dp.get_problem("gotoh").encode(**payload)
    old = _old_gotoh_planes(**payload)
    prefix = full.split_spec(10)
    assert full.source is not None and prefix.source is None
    _assert_planes_equal(prefix, tuple(p[:, :, :10] for p in old), "split")
    ext = prefix.extend_spec(full.extension_delta(prefix))
    assert ext.source is None
    _assert_planes_equal(ext, old, "extend")
    np.testing.assert_array_equal(ext.weights, full.weights)
    planes_only = _planes_only(dp.get_problem("gotoh").encode(**payload))
    assert planes_only.source is None
    _assert_planes_equal(planes_only, old, "planes-only")
    # a sourced spec digests its source, a planes-only one its planes: the
    # two forms of one instance no longer share a digest
    assert spec_digest(_planes_only(full)) != spec_digest(full)


def test_equal_sources_digest_equal():
    """Two encodes of one payload digest equal, hashing only the source
    (under 5 KB for a 250 x 282 pair) and building no planes."""
    rng = _rng("equal")
    payload = {"x": rng.integers(0, 4, 250), "y": rng.integers(0, 4, 282),
               **_BWA}
    a, b = (dp.get_problem("gotoh").encode(**payload) for _ in range(2))
    assert a.source.arrays[0] is not b.source.arrays[0]
    (da, na), (db, nb) = spec_digest_bytes(a), spec_digest_bytes(b)
    assert da == db == spec_digest(a)
    assert na == nb < 5000
    assert not _held(a) and not _held(b)
    assert spec_digest_bytes(_planes_only(a))[1] > 100 * na


def _sourced(spec, **changes):
    """``spec`` with ``changes``, holding no planes: a plain
    ``dataclasses.replace`` reads, and so builds, the planes it copies."""
    return dataclasses.replace(spec, weights=None, init=None, init_mask=None,
                               **changes)


def _with_packed(spec, change):
    packed = spec.source.arrays[0].copy()
    change(packed)
    return _sourced(spec, source=PlaneSource(spec.source.builder, (packed,)))


def _flip(packed, i):
    packed[i] ^= 1


_M, _C = 9, 12
#: each changes one thing a sourced spec's digest must cover
_SOURCE_CHANGES = {
    "symbol": lambda s: _with_packed(s, lambda p: _flip(p, 3)),
    "score-bit": lambda s: _with_packed(s, lambda p: _flip(p, _M + _C)),
    "ramp-entry": lambda s: _with_packed(
        s, lambda p: _flip(p, _M + _C + 4 + 2)),
    "rows": lambda s: _sourced(s, rows=s.rows + 1),
    "cols": lambda s: _sourced(s, cols=s.cols + 1),
    "builder": lambda s: _sourced(s, source=PlaneSource(
        "gotoh2", s.source.arrays)),
}


@pytest.mark.parametrize("change", list(_SOURCE_CHANGES))
def test_source_digest_covers(change):
    spec = _gotoh(_rng("covers"), _M, _C, _BWA)
    changed = _SOURCE_CHANGES[change](spec)
    assert changed.source is not None
    assert spec_digest(changed) != spec_digest(spec)
    assert not _held(spec) and not _held(changed)


def _golden_instance(name):
    prob = dp.get_problem(name)
    rng = np.random.default_rng(zlib.crc32(f"golden/{name}".encode()))
    return prob.encode(**prob.sample(rng, 6))


def _gotoh_chars():
    chars = np.array(list("ACGTTGCA"))
    return dp.get_problem("gotoh").encode(x=chars, y=chars[2:], **_BWA)


#: hex digests of specs without a plane source, as the planes-only digest
#: computed them before sourced specs digested their source; "gotoh" is the
#: planes-only form of a sourced gotoh instance, whose host-built planes
#: must hash as the encode's materialized planes did
_GOLDEN = {
    "cky": "22c94dd2a897df924978042dc3764cbcdc9692ac1e3ba6646f17e9aef64b66a7",
    "edit_distance":
        "9c92e644123d6164029388b67c7ff09f1d9a0355cfac5680cf620c93dcbaa80d",
    "edit_distance_grid":
        "e0e1a629b3544e9f7741697449443be392a7376dba6c9fd66c0675821bc61431",
    "gotoh": "bdcd713ad63ac4c8bf44ccd4e34ddb5fa652e2b6fac60e56f365f4dfaedfba59",
    "gotoh-chars":
        "32ad7b52484864f2063f6d20579c3ea0fdbeb6efa5ecedbee26ea357e98c9d7f",
    "lcs": "4ca6fdd3f005ed6596c367f7cb53b27f375ab7bcf17ddb001e83b1640c968a97",
    "lcs_grid":
        "7325c7b608502615e82174077aae8bf722f0b8fe9d46d9eb37c3d905944aadf3",
    "mcm": "d47845b73bc7d5904191b7d332f388c37f0f227c5ab2b9e6fe9840243ab55c6b",
    "needleman_wunsch":
        "28d6ab01ac052c2b95f33f468d64b074e3e00b9a76d78a1892af2f8d346c59e3",
    "optimal_bst":
        "f25926d2d479faf0fee751effb625ec014da05815160b63d3fffdcdb57906f47",
    "polygon_triangulation":
        "9454027348390937846102ba1633e278fa235e4255083d1919eb6e22170f9685",
    "sdp": "b0cb5f04dd282f5e4555339ff09d57e4f638f5017c6e9c0cfc6f562c92428ad2",
    "unbounded_knapsack":
        "7efdaa977c9a81a1d0d964b96fd459ec0aa84f7fd74e6994d9b4590cc00090b4",
    "viterbi":
        "6e79731a33f8d58a2320d8d68c180f92076c3db29b72c2b66f0e4acbc11c963e",
}


@pytest.mark.parametrize("name", sorted(_GOLDEN))
def test_unsourced_digests_are_unchanged(name):
    if name == "gotoh-chars":
        spec = _gotoh_chars()
    elif name == "gotoh":
        spec = _planes_only(_golden_instance(name))
    else:
        spec = _golden_instance(name)
    assert getattr(spec, "source", None) is None
    assert spec_digest(spec) == _GOLDEN[name]


def test_every_problem_has_a_golden_digest():
    assert set(dp.problem_names()) <= set(_GOLDEN)


def _service_round(payloads, mesh):
    """One CPU ``DPService`` round of ``payloads``, every 4th with
    ``reconstruct``: ``(value, score, ops)`` per request."""
    svc = dp.DPService(max_batch=8, mesh=mesh, feedback=False)
    tids = [svc.submit("gotoh", reconstruct=i % 4 == 0, **kw)
            for i, kw in enumerate(payloads)]
    out = svc.run()
    got = []
    for i, t in enumerate(tids):
        r = out[t]
        assert r.status == "done" and (r.solution is not None) == (i % 4 == 0)
        sol = r.solution.solution if r.solution is not None else None
        got.append((r.answer, sol and sol["score"], sol and sol["ops"]))
    return got


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["DPEngine", "ShardedDPEngine"])
def test_service_round_is_bit_equal_and_builds_no_planes(monkeypatch,
                                                         sharded):
    """A round of sourced pairs answers bit-equal to the same round
    encoded with planes (no source), and no sourced spec's planes are
    built on the host on the way; through the single-device engine and
    through the sharded one on every visible device."""
    mesh = default_mesh() if sharded else None
    rng = _rng("service")
    payloads = [{"x": rng.integers(0, 4, 11), "y": rng.integers(0, 4, 15),
                 **_BWA} for _ in range(16)]
    built = []
    real_build = GridSpec._build_planes
    monkeypatch.setattr(GridSpec, "_build_planes",
                        lambda self: built.append(1) or real_build(self))
    sourced = _service_round(payloads, mesh)
    assert built == []
    monkeypatch.setattr(_zoo, "_gotoh_source", lambda *a: None)
    planes = _service_round(payloads, mesh)
    assert built == []
    for s, p in zip(sourced, planes):
        assert np.float32(s[0]).view(np.int32) == \
            np.float32(p[0]).view(np.int32)
        assert s[1:] == p[1:]
