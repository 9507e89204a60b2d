"""Telemetry layer (DESIGN.md §8): mode knobs, metrics registry, request
spans, drain attribution, profiler spans, thread safety, exporters, and the
service accounting invariant."""
import glob
import json
import threading

import jax
import numpy as np
import pytest

from repro import dp
from repro.dp import backends, telemetry
from repro.dp.problem import spec_digest_bytes


@pytest.fixture(autouse=True)
def _telemetry_isolated(monkeypatch):
    """Telemetry state is process-global (cached mode, registry, rings);
    every test starts and ends at a clean ``off``."""
    monkeypatch.delenv(telemetry.ENV_MODE, raising=False)
    monkeypatch.delenv(telemetry.ENV_LOG, raising=False)

    def clean():
        telemetry.reset()
        telemetry.REGISTRY.reset()
        telemetry.clear_spans()

    clean()
    yield
    clean()


def _mcm_payloads(n, rng=None, size=6):
    rng = rng or np.random.default_rng(0)
    return [dp.get_problem("mcm").sample(rng, size) for _ in range(n)]


# ---------------------------------------------------------------------------
# Mode / log knobs
# ---------------------------------------------------------------------------
def test_mode_env_validated(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_MODE, "span")   # typo, not "spans"
    telemetry.reset()
    with pytest.raises(ValueError, match="REPRO_TELEMETRY"):
        telemetry.mode()


def test_mode_env_resolves_and_caches(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_MODE, "basic")
    telemetry.reset()
    assert telemetry.mode() == "basic"
    assert telemetry.enabled("basic")
    assert not telemetry.enabled("spans")


def test_configure_validates_and_returns_previous():
    assert telemetry.configure("spans") == "off"
    assert telemetry.enabled("spans")
    assert telemetry.configure("off") == "spans"
    with pytest.raises(ValueError, match="invalid telemetry mode"):
        telemetry.configure("verbose")


def test_log_level_env_validated(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_LOG, "loud")
    with pytest.raises(ValueError, match="REPRO_LOG"):
        telemetry.log_level()


def test_get_logger_hierarchy():
    log = telemetry.get_logger("engine")
    assert log.name == "repro.dp.engine"


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------
def test_counter_is_monotonic():
    c = telemetry.REGISTRY.counter("t_total")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_metric_kind_collision_raises():
    telemetry.REGISTRY.counter("t_name")
    with pytest.raises(ValueError, match="already registered"):
        telemetry.REGISTRY.gauge("t_name")


def test_histogram_quantiles_clamped_to_observed():
    h = telemetry.REGISTRY.histogram("t_ms", buckets=(1.0, 10.0, 100.0))
    for v in (2.0, 3.0, 4.0, 5.0, 200.0):
        h.observe(v)
    assert h.count == 5
    assert 2.0 <= h.quantile(0.5) <= 10.0
    assert h.quantile(0.99) <= 200.0      # clamped to observed max
    assert h.quantile(0.0) >= 2.0         # clamped to observed min
    d = h.to_dict()
    assert d["count"] == 5 and d["buckets"][-1] == ["+inf", 1]


def test_helpers_are_noop_when_off():
    telemetry.count("t_off_total")
    telemetry.observe_ms("t_off_ms", 1.0)
    telemetry.set_gauge("t_off_gauge", 1.0)
    assert telemetry.REGISTRY.counters() == {}
    assert telemetry.new_span(0, "mcm") is None


def test_registry_source_absorbs_engine_stats():
    telemetry.configure("basic")
    eng = dp.DPEngine(max_batch=8)
    eng.submit("mcm", dims=[4, 5, 6, 7])
    eng.run()
    sources = telemetry.REGISTRY.sources()
    row = next(v for k, v in sources.items() if k.startswith("dp_engine/"))
    assert row["completed"] == 1           # the compatibility stats view


# ---------------------------------------------------------------------------
# Thread safety
# ---------------------------------------------------------------------------
def test_registry_counter_thread_safe():
    telemetry.configure("basic")
    n_threads, per = 8, 500

    def worker():
        for _ in range(per):
            telemetry.count("t_conc_total")

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert telemetry.REGISTRY.counter("t_conc_total").value == n_threads * per


def test_trace_log_concurrent_append_and_drain():
    backends.drain_trace_log()
    n_threads, per = 4, 200
    drained = []
    stop = threading.Event()

    def appender(i):
        for j in range(per):
            backends.log_trace(("t_trace", i, j))

    def drainer():
        while not stop.is_set():
            drained.extend(backends.drain_trace_log())

    dt = threading.Thread(target=drainer)
    threads = [threading.Thread(target=appender, args=(i,))
               for i in range(n_threads)]
    dt.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    dt.join()
    drained.extend(backends.drain_trace_log())
    # every append lands in exactly one drain — none lost, none doubled
    assert sorted(drained) == sorted(
        ("t_trace", i, j) for i in range(n_threads) for j in range(per))


# ---------------------------------------------------------------------------
# Engine drains: counters + drain reports
# ---------------------------------------------------------------------------
def test_counters_monotonic_across_drains():
    telemetry.configure("basic")
    eng = dp.DPEngine(max_batch=4)
    seen = []
    for kw in _mcm_payloads(6):
        eng.submit("mcm", **kw)
    while eng.pending():
        eng.step()
        c = telemetry.REGISTRY.counters()
        seen.append((c["dp_engine_drains_total"],
                     c["dp_engine_requests_total"]))
    assert seen == sorted(seen)            # never decreases
    assert seen[-1][0] == len(seen)        # one drain per step
    assert seen[-1][1] == 6


def test_drain_report_phases():
    telemetry.configure("basic")
    eng = dp.DPEngine(max_batch=8)
    eng.submit("mcm", reconstruct=True, dims=[4, 5, 6, 7, 8])
    eng.run()
    rep = eng.last_drain
    assert rep is not None and rep.backend
    assert {"solve", "traceback", "decode"} <= set(rep.phases)
    assert all(ms >= 0.0 for ms in rep.phases.values())
    hists = telemetry.REGISTRY.histograms()
    assert hists["dp_engine_solve_ms"].count == 1
    assert hists["dp_engine_traceback_ms"].count == 1


# ---------------------------------------------------------------------------
# Service spans
# ---------------------------------------------------------------------------
def test_completed_poll_returns_span_with_phase_events():
    telemetry.configure("spans")
    svc = dp.DPService(max_batch=8, mesh=None)
    tid = svc.submit("mcm", reconstruct=True, dims=[4, 5, 6, 7])
    res = svc.run()[tid]
    span = res.span
    assert span is not None and span.tid == tid
    names = set(span.event_names())
    # the ≥5-distinct-phase-events acceptance bar, comfortably cleared
    assert {"admitted", "enqueued", "dispatched", "batched", "solved",
            "traceback", "decoded", "resolved"} <= names
    phases = span.phases()
    assert {"queue", "dispatch", "solve", "traceback", "decode",
            "total"} <= set(phases)
    assert span.meta["backend"] == res.backend
    ts = [t for _, t in span.events]
    assert ts == sorted(ts)                # one monotonic timebase
    # the completed span also landed in the export ring
    assert any(s["tid"] == tid for s in telemetry.spans_snapshot())


def test_cache_hit_span():
    telemetry.configure("spans")
    svc = dp.DPService(max_batch=8, mesh=None)
    kw = {"dims": [4, 5, 6, 7]}
    first = svc.submit("mcm", **kw)
    svc.run()[first]
    hit = svc.submit("mcm", **kw)
    res = svc.poll(hit)
    assert res.cached
    assert "cache_hit" in res.span.event_names()
    assert res.span.meta["cached"] is True


def test_expired_span():
    telemetry.configure("spans")
    svc = dp.DPService(max_batch=8, mesh=None)
    tid = svc.submit("mcm", deadline_ms=0.0001, dims=[4, 5, 6, 7])
    import time
    time.sleep(0.002)
    res = svc.run()[tid]
    assert res.status == "expired"
    assert "expired" in res.span.event_names()


def test_per_phase_service_histograms():
    telemetry.configure("basic")      # histograms need no span machinery
    svc = dp.DPService(max_batch=8, mesh=None)
    for kw in _mcm_payloads(5):
        svc.submit("mcm", **kw)
    svc.run()
    hists = telemetry.REGISTRY.histograms()
    for ph in ("queue", "dispatch", "solve"):
        assert hists[f"dp_service_{ph}_ms"].count >= 5, ph
    assert hists["dp_service_latency_ms"].count == 5


# ---------------------------------------------------------------------------
# Service accounting invariant
# ---------------------------------------------------------------------------
def test_submitted_balances_under_mixed_traffic():
    telemetry.configure("spans")
    svc = dp.DPService(max_batch=4, max_pending=6, mesh=None)
    rng = np.random.default_rng(1)
    shed = 0
    for i, kw in enumerate(_mcm_payloads(24, rng)):
        try:
            svc.submit("mcm", reconstruct=(i % 5 == 0),
                       deadline_ms=0.0001 if i % 7 == 3 else None, **kw)
        except dp.AdmissionError:
            shed += 1
        if i % 9 == 8:
            svc.step()
    svc.run()
    s = svc.stats
    assert shed > 0 and s["expired"] > 0       # both paths exercised
    assert s["shed"] == s["rejected"] == shed
    assert s["submitted"] == (s["completed"] + svc.pending()
                              + s["expired"] + s["shed"])
    assert svc.pending() == 0


# ---------------------------------------------------------------------------
# Routing is observability-independent
# ---------------------------------------------------------------------------
def test_off_mode_routing_bit_identical():
    """REPRO_TELEMETRY must be observability only: same traffic, same
    routes, same answers with it off and on."""
    def leg():
        from repro.dp import autotune
        autotune.reset()
        eng = dp.DPEngine(max_batch=8, feedback=False)
        rids = [eng.submit("mcm", **kw) for kw in _mcm_payloads(4)]
        out = eng.run()
        return [(out[r].backend, out[r].answer) for r in rids]

    telemetry.configure("off")
    off = leg()
    telemetry.configure("spans")
    spans = leg()
    assert off == spans


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------
def test_snapshot_and_save(tmp_path):
    telemetry.configure("spans")
    svc = dp.DPService(max_batch=8, mesh=None)
    tid = svc.submit("mcm", dims=[4, 5, 6, 7])
    svc.run()[tid]
    snap = telemetry.snapshot()
    assert snap["mode"] == "spans"
    assert snap["counters"]["dp_service_completed_total"] == 1
    assert "dp_service_latency_ms" in snap["histograms"]
    assert any(s["tid"] == tid for s in snap["spans"])
    path = telemetry.save_snapshot(str(tmp_path / "snap.json"))
    assert json.load(open(path))["mode"] == "spans"


def test_prometheus_exposition_format():
    telemetry.configure("basic")
    telemetry.count("t_reqs_total", 3)
    telemetry.set_gauge("t_depth", 7)
    telemetry.observe_ms("t_lat_ms", 12.0)
    text = telemetry.to_prometheus()
    assert "# TYPE t_reqs_total counter\nt_reqs_total 3" in text
    assert "# TYPE t_depth gauge\nt_depth 7" in text
    assert "# TYPE t_lat_ms histogram" in text
    assert 't_lat_ms_bucket{le="+Inf"} 1' in text
    assert "t_lat_ms_count 1" in text


def test_kernel_entry_counter():
    telemetry.configure("basic")
    from repro.kernels import ops
    x = np.zeros((4, 4), np.float32)
    ops.tropical_matmul(x, x)
    mode = ops.kernel_mode()
    assert telemetry.REGISTRY.counters()[
        f"dp_kernel_tropical_matmul_{mode}_total"] == 1


# ---------------------------------------------------------------------------
# Profiler spans (dp.*)
# ---------------------------------------------------------------------------
def test_trace_span_builds_nothing_while_not_capturing(monkeypatch):
    """Outside a profiler capture every span is the one shared no-op: no
    ``TraceAnnotation`` is built, on the span helper or on a whole
    service round."""
    built = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            built.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(telemetry, "TraceAnnotation", Counting)
    assert not telemetry.tracing()
    span = telemetry.trace_span("dp.test", n=1)
    assert span is telemetry.trace_span("dp.other")
    with span as inner:
        inner.set_metadata(bytes=3)
    svc = dp.DPService(max_batch=4, mesh=None)
    tids = [svc.submit("mcm", reconstruct=True, **kw)
            for kw in _mcm_payloads(3)]
    out = svc.run()
    assert all(out[t].status == "done" for t in tids)
    assert built == []


#: a small gotoh batch: reads of 13 against windows of 19 (a shape no other
#: test drains, so its first drain here traces its programs)
_SCORING = {"match": 1.0, "mismatch": -4.0, "gap_open": -7.0,
            "gap_extend": -1.0}
_ROUNDS, _LANES = 2, 4
_SPAN_NAMES = ("dp.submit", "dp.encode", "dp.digest", "dp.step", "dp.admit",
               "dp.drain", "dp.stack", "dp.launch", "dp.fetch",
               "dp.traceback", "dp.decode", "dp.extract", "dp.resolve")
#: span -> the span it runs inside
_PARENT = {"dp.encode": "dp.submit", "dp.digest": "dp.submit",
           "dp.admit": "dp.step", "dp.drain": "dp.step",
           "dp.stack": "dp.drain", "dp.launch": "dp.drain",
           "dp.fetch": "dp.drain", "dp.traceback": "dp.drain",
           "dp.decode": "dp.drain", "dp.extract": "dp.step",
           "dp.resolve": "dp.step"}


@pytest.fixture(scope="module")
def gotoh_capture(tmp_path_factory):
    """Two rounds of four distinct 13x19 gotoh pairs with
    ``reconstruct=True`` through ``DPService`` under a CPU profiler
    capture. Returns ``(events, payloads)``: every ``dp.*`` host event as
    ``(start, end, name, args)`` in time order, and each round's
    payloads."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    rng = np.random.default_rng(7)
    rounds = [[{"x": rng.integers(0, 4, 13), "y": rng.integers(0, 4, 19),
                **_SCORING} for _ in range(_LANES)] for _ in range(_ROUNDS)]
    svc = dp.DPService(max_batch=_LANES, mesh=None, feedback=False)
    jax.profiler.start_trace(log_dir)
    try:
        for payloads in rounds:
            tids = [svc.submit("gotoh", reconstruct=True, **kw)
                    for kw in payloads]
            out = svc.run()
            assert all(out[t].solution is not None for t in tids)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("dp."):
                    events.append((e.start_ns, e.start_ns + e.duration_ns,
                                   e.name, dict(e.stats)))
    return sorted(events), rounds


def _named(events, name):
    return [ev for ev in events if ev[2] == name]


def test_capture_records_every_span(gotoh_capture):
    events, _ = gotoh_capture
    names = {name for _, _, name, _ in events}
    assert set(_SPAN_NAMES) <= names
    assert len(_named(events, "dp.submit")) == _ROUNDS * _LANES
    assert len(_named(events, "dp.drain")) == _ROUNDS
    assert [args["tid"] for *_, args in _named(events, "dp.submit")] == \
        list(range(_ROUNDS * _LANES))
    assert all(args == {"lanes": _LANES, "unique": _LANES,
                        "cold": int(i == 0), "shards": 1, "pad_lanes": 0}
               for i, (*_, args) in enumerate(_named(events, "dp.drain")))


def test_capture_spans_nest(gotoh_capture):
    events, _ = gotoh_capture
    for name, parent in _PARENT.items():
        outer = _named(events, parent)
        for a, b, _, _ in _named(events, name):
            assert any(pa <= a and b <= pb for pa, pb, _, _ in outer), name
    # the service's step answers each round whole
    assert [args["answered"] for *_, args in _named(events, "dp.step")
            if args["answered"]] == [_LANES] * _ROUNDS


def test_capture_counts_host_to_device_bytes(gotoh_capture):
    """``h2d_bytes`` of each drain's ``dp.stack`` is the bytes of the
    arrays it sends: gotoh lanes carry a plane source, so each source slot
    goes as one array of every lane's slot, counted here from the
    payloads; ``source_lanes`` counts the lanes whose planes the program
    builds. On one device the one shard receives every byte."""
    events, rounds = gotoh_capture
    gotoh = dp.get_problem("gotoh")
    stacks = _named(events, "dp.stack")
    assert len(stacks) == _ROUNDS
    for (*_, args), payloads in zip(stacks, rounds):
        slots = [np.stack(slot) for slot in zip(
            *(gotoh.encode(**kw).source.arrays for kw in payloads))]
        assert args["h2d_bytes"] == sum(a.nbytes for a in slots)
        assert args["arrays"] == len(slots) == 1
        assert args["source_lanes"] == _LANES
        assert args["shards"] == 1
        assert args["h2d_bytes_per_shard"] == args["h2d_bytes"]
    assert not _named(events, "dp.place")
    fetches = _named(events, "dp.fetch")
    assert len(fetches) == _ROUNDS
    assert all(args["d2h_bytes"] > 0 and args["shards"] == 1
               for *_, args in fetches)


def test_capture_digests_gotoh_by_its_source(gotoh_capture):
    """Every gotoh ``dp.digest`` hashes the pair's plane source
    (``sourced`` 1; ``digest_bytes`` a few hundred, not the planes' tens
    of kilobytes), and no sourced spec's planes are built on the host: the
    capture holds no ``dp.planes``."""
    events, rounds = gotoh_capture
    gotoh = dp.get_problem("gotoh")
    want = [spec_digest_bytes(gotoh.encode(**kw))[1]
            for payloads in rounds for kw in payloads]
    assert [args for *_, args in _named(events, "dp.digest")] == \
        [{"sourced": 1, "digest_bytes": n} for n in want]
    assert all(n < 1000 for n in want)
    assert not _named(events, "dp.planes")


def test_capture_counts_compiles_on_first_drain_only(gotoh_capture):
    events, _ = gotoh_capture
    compiles = [args["compiles"] for *_, args in _named(events, "dp.launch")]
    assert len(compiles) == _ROUNDS
    assert compiles[0] >= 1
    assert compiles[1:] == [0] * (_ROUNDS - 1)


def test_drain_report_phase_bounds_are_real_times():
    """A drain report keeps each phase's start and end; the request's span
    places ``solved``/``traceback``/``decoded`` at those ends."""
    telemetry.configure("spans")
    svc = dp.DPService(max_batch=8, mesh=None)
    tid = svc.submit("mcm", reconstruct=True, dims=[4, 5, 6, 7, 8])
    res = svc.run()[tid]
    rep = svc.engine.last_drain
    assert set(rep.bounds) == {"solve", "traceback", "decode"}
    solve, tb, dec = (rep.bounds[p] for p in ("solve", "traceback",
                                               "decode"))
    assert rep.t_start <= solve[0] <= solve[1] <= tb[0] <= tb[1] \
        <= dec[0] <= dec[1]
    for phase, (t0, t1) in rep.bounds.items():
        assert rep.phases[phase] == pytest.approx((t1 - t0) * 1e3)
    events = dict(res.span.events)
    assert events["solved"] == solve[1]
    assert events["traceback"] == tb[1]
    assert events["decoded"] == dec[1]
