"""``shard_imbalance``: the spread of the devices' busy time in a traced
window, read from the trace reduction's per-device ``busy_ns``."""
import os
from types import SimpleNamespace

import pytest

import run

READER = run.load_module(os.path.join(run.HERE, "metrics",
                                      "shard_imbalance.py"))


def _run(busy_ns):
    trace = SimpleNamespace(busy_ns={f"/device:TPU:{i}": ns
                                     for i, ns in enumerate(busy_ns)})
    return SimpleNamespace(trace=trace)


@pytest.mark.parametrize("busy_ns, want", [
    ([7e9, 7e9, 7e9, 7e9], 0.0),
    ([8e9, 8e9, 4e9, 8e9], 50.0),
    ([7e9], None),
])
def test_read(busy_ns, want):
    assert READER.read(_run(busy_ns)) == want


def test_untraced_run_reads_nothing():
    assert READER.read(SimpleNamespace(trace=None)) is None
