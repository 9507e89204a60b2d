"""The reduction of the program's ``dp.*`` spans: innermost-span attribution
on a hand-made profile, and the recorded trace (``data/mcm.batch.xplane.pb``,
from a program that records no ``dp.*`` span), on which it reads nothing
and ``trace_reduce`` reads what it always did."""
import os
import types

import pytest

import span_reduce as sr
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "mcm.batch.xplane.pb")


def _ev(name, a, b, **stats):
    return types.SimpleNamespace(name=name, start_ns=float(a),
                                 duration_ns=float(b - a),
                                 stats=list(stats.items()))


def _profile(host_events, busy):
    """A stand-in ``ProfileData``: one host line, one device's ``XLA Ops``
    line with an op over each of ``busy``."""
    ops = [_ev(f"%fusion.{i} = f32[8] fusion(f32[8] %p)", a, b)
           for i, (a, b) in enumerate(busy)]
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            types.SimpleNamespace(name="python", events=host_events)]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            types.SimpleNamespace(name=tr.OPS_LINE, events=ops)]),
    ])


#: a window of 1000 ns: one submit (encode, digest) and one step whose
#: drain stacks, launches, fetches, walks and decodes; the device runs
#: from 450 to 780, inside the fetch
HAND_SPANS = [
    _ev("window", 0, 1000),
    _ev("submit", 0, 300),
    _ev("dp.submit", 10, 290, tid=0),
    _ev("dp.encode", 20, 100),
    _ev("dp.digest", 100, 200),
    _ev("step", 300, 1000),
    _ev("dp.step", 310, 990, answered=1),
    _ev("dp.drain", 320, 900, lanes=1, unique=1, cold=0),
    _ev("dp.stack", 330, 400, h2d_bytes=96, arrays=3),
    _ev("dp.launch", 400, 420, compiles=0),
    _ev("dp.fetch", 420, 800, d2h_bytes=48),
    _ev("dp.traceback", 800, 850),
    _ev("dp.decode", 850, 900),
    _ev("dp.resolve", 900, 980),
    # a drain of the warm-up, before the window: counted nowhere
    _ev("dp.launch", -50, -40, compiles=2),
]
HAND_IDLE = {"dp.submit": 100.0, "dp.encode": 80.0, "dp.digest": 100.0,
             "dp.step": 20.0, "dp.drain": 10.0, "dp.stack": 70.0,
             "dp.launch": 20.0, "dp.fetch": 50.0, "dp.traceback": 50.0,
             "dp.decode": 50.0, "dp.resolve": 80.0, "other": 40.0}


@pytest.fixture
def hand():
    return sr.reduce(_profile(HAND_SPANS, [(450, 780)]))


@pytest.fixture(scope="module")
def recorded():
    import jax

    return jax.profiler.ProfileData.from_file(DATA)


def test_innermost_pieces():
    pieces = sr._innermost([(0, 10, "a"), (2, 4, "b"), (4, 12, "c"),
                            (20, 30, "d")])
    # c outlives a, the span it started in: it is cut at a's end
    assert pieces == [(0, 2, "a"), (2, 4, "b"), (4, 10, "c"),
                      (20, 30, "d")]


def test_idle_charged_to_innermost_span(hand):
    assert hand.idle_ns == HAND_IDLE
    # program idle plus `other` is all the idle time of the window
    idle = hand.window_ns - 330.0
    assert sum(hand.idle_ns.values()) == idle
    harness = tr.reduce(_profile(HAND_SPANS, [(450, 780)]))
    assert sum(harness.idle_ns.values()) == idle
    # each call's span family takes the idle time of the harness span that
    # wraps that call, but for the gaps between the two
    assert hand.idle_share(*sr.FAMILIES["submit"]) == 28.0
    assert harness.idle_ns["submit"] == 300.0
    assert hand.idle_share(*sr.FAMILIES["step"]) == 35.0
    assert harness.idle_ns["step"] == 370.0


def test_idle_mean_over_devices():
    prof = _profile(HAND_SPANS, [(450, 780)])
    second = types.SimpleNamespace(name="/device:TPU:1", lines=[
        types.SimpleNamespace(name=tr.OPS_LINE, events=[
            _ev("%copy.1 = f32[8] copy(f32[8] %p)", 0, 1000)])])
    red = sr.reduce(types.SimpleNamespace(planes=prof.planes + [second]))
    # the second device never idles: every share halves
    assert red.idle_ns == {k: v / 2 for k, v in HAND_IDLE.items()}


def test_span_union_count_and_args(hand):
    assert hand.ns["dp.fetch"] == 380.0
    assert hand.ns["dp.submit"] == 280.0
    assert hand.ns["dp.launch"] == 20.0          # the warm-up's is out
    assert hand.count["dp.launch"] == 1
    assert hand.args["dp.launch"] == {"compiles": 0}
    assert hand.args["dp.stack"] == {"h2d_bytes": 96, "arrays": 3}
    assert hand.args["dp.drain"] == {"lanes": 1, "unique": 1, "cold": 0}
    assert hand.idle_share("dp.traceback", "dp.decode") == 10.0


def test_summary(hand):
    s = sr.summary(hand)
    assert s["window_s"] == 1e-6
    assert s["idle_pct"]["dp.stack"] == 7.0
    assert s["family_idle_pct"] == {"submit": 28.0, "step": 35.0}
    assert s["spans"]["dp.stack"] == {"host_s": 7e-8, "count": 1,
                                      "args": {"h2d_bytes": 96, "arrays": 3}}


def test_recorded_trace_without_program_spans(recorded):
    red = sr.reduce(recorded)
    assert (red.ns, red.count, red.args, red.idle_ns) == ({}, {}, {}, {})
    assert red.idle_share("dp.stack") is None
    assert sr.summary(red)["family_idle_pct"] == {"submit": None,
                                                  "step": None}


def test_recorded_trace_harness_numbers_pinned(recorded):
    """``trace_reduce`` reads the recorded trace as it did when the trace
    was recorded."""
    red = tr.reduce(recorded)
    assert red.window_ns == 1996994162.0
    assert red.busy_ns == {"/device:TPU:0": 115066424.0}
    assert red.idle_ns == {"submit": 1704363445.0, "step": 171262923.0,
                           "generate": 4289689.0, "poll": 73980.0,
                           "other": 1937701.0}
    assert red.span_ns == {"submit": 1704363445.0, "step": 286329347.0,
                           "generate": 4289689.0, "poll": 73980.0}


def test_cli(capsys):
    assert sr.main([]) == 2
    assert sr.main([DATA]) == 0
    out = capsys.readouterr().out
    assert '"window_s": 1.996994162' in out
