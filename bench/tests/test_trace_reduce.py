"""The trace reduction: interval arithmetic on hand-made cases, and the
whole reduction on a short trace recorded on a TPU v5e by ``bench/run.py
--trace 1 --keep-trace`` with a 1-s window of 64 closed-loop callers of
n=128 matrix chains (``data/mcm.batch.xplane.pb``): two 64-lane
``mcm_pipeline`` drains."""
import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "mcm.batch.xplane.pb")


def test_merge_and_complement():
    merged = tr._merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert tr._complement(merged, 0, 10) == [(3, 5), (8, 10)]
    assert tr._complement(merged, 1, 6) == [(3, 5)]
    assert tr._overlap(merged, 2, 6) == 1 + 1


def test_intersection():
    a = [[0, 4], [6, 10]]
    b = [[2, 7], [9, 12]]
    assert tr._intersection(a, b) == 2 + 1 + 1
    assert tr._intersection(a, []) == 0


def test_short_name():
    name = ("%vmap_jit_grid_pipeline_pallas__.2 = f32[48,3,558,128]{3,2,1,0} "
            "custom-call(f32[48,533,13,2,128] %copy.4)")
    assert tr.short_name(name) == "vmap_jit_grid_pipeline_pallas__"
    assert tr.short_name("%copy.12.3 = f32[2] copy(f32[2] %p)") == "copy"


@pytest.fixture(scope="module")
def red():
    return tr.reduce(DATA)


def test_recorded_trace_window_and_busy(red):
    assert red.devices == ["/device:TPU:0"]
    assert 0.9 < red.window_s < 5.0
    assert 0 < red.busy_s < red.window_s
    idle = sum(red.idle_ns.values())
    assert idle == pytest.approx(red.window_ns - red.busy_ns["/device:TPU:0"],
                                 rel=1e-9)
    # every device op of the window ran inside a `step` span: no idle time
    # is charged to `step` beyond the step spans themselves
    assert red.idle_ns["step"] <= red.span_ns["step"]
    assert red.span_ns["submit"] > 0


def test_recorded_trace_kernels(red):
    wavefront = red.kernel_ns([r"mcm_pipeline_pallas"])
    assert wavefront > 0
    assert red.kernel_ns([r"grid_pipeline_pallas", r"mcm_tiled_pallas"]) == 0
    assert wavefront <= red.busy_ns["/device:TPU:0"]


def test_breakdown_shape(red):
    b = tr.breakdown(red)
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and s > 0 for n, s in b[key])
    ops = [s for _, s in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
