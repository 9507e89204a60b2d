"""The timing layer (DESIGN.md §8): the log knob, the engine's and the
service's ``stats`` counts, kernel entry counts, the trace log under
concurrency, and the ``dp.*`` profiler spans of a captured service round."""
import glob
import threading

import jax
import numpy as np
import pytest

from repro import dp
from repro.dp import backends, telemetry
from repro.dp.problem import spec_digest_bytes


@pytest.fixture(autouse=True)
def _log_env_unset(monkeypatch):
    """No test here sees a ``REPRO_LOG`` from the calling environment."""
    monkeypatch.delenv(telemetry.ENV_LOG, raising=False)


def _mcm_payloads(n, rng=None, size=6):
    rng = rng or np.random.default_rng(0)
    return [dp.get_problem("mcm").sample(rng, size) for _ in range(n)]


# ---------------------------------------------------------------------------
# Log knob
# ---------------------------------------------------------------------------
def test_log_level_env_validated(monkeypatch):
    monkeypatch.setenv(telemetry.ENV_LOG, "loud")
    with pytest.raises(ValueError, match="REPRO_LOG"):
        telemetry.log_level()


def test_get_logger_hierarchy():
    log = telemetry.get_logger("engine")
    assert log.name == "repro.dp.engine"


# ---------------------------------------------------------------------------
# Thread safety
# ---------------------------------------------------------------------------
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
# Engine drains: stats counts
# ---------------------------------------------------------------------------
def test_counters_monotonic_across_drains():
    eng = dp.DPEngine(max_batch=4)
    seen = []
    for kw in _mcm_payloads(6):
        eng.submit("mcm", **kw)
    while eng.pending():
        eng.step()
        seen.append((eng.stats["device_batches"], eng.stats["completed"]))
    assert seen == sorted(seen)            # never decreases
    assert seen[-1][0] == len(seen)        # one drain per step
    assert seen[-1][1] == 6


# ---------------------------------------------------------------------------
# Service accounting invariant
# ---------------------------------------------------------------------------
def test_submitted_balances_under_mixed_traffic():
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
# Kernel entry counts
# ---------------------------------------------------------------------------
def test_kernel_entry_counter(monkeypatch):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "ENTRY_COUNTS", {})
    x = np.zeros((4, 4), np.float32)
    ops.tropical_matmul(x, x)
    mode = ops.kernel_mode()
    assert ops.ENTRY_COUNTS == {("tropical_matmul", mode): 1}


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

