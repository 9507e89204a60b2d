"""A whole run on the CPU (the harness's look for a chip skipped), with the
timed path broken underneath once the window opens: ``correct`` must come
out false for every fault a cell can have, and true with none.

The faults are planted in ``DPEngine.step``, where answers are produced:
an answer altered, half of each drain's requests left out (never
answered), and a decoded solution altered.
"""
import dataclasses
import os

import jax
import pytest

import loadgen
import run
from repro.dp import engine

SEED = 2 ** 31 + 977
SMALL = {  # cell -> (traffic overrides, lanes per device)
    "gotoh.batch": ({"sizes": [9, 14], "round": 16}, 4),
}


def alter_answer(out):
    return [dataclasses.replace(out[0], answer=out[0].answer + 2.0)] + out[1:]


def drop_half(out):
    return out[: len(out) // 2]


def alter_solution(out):
    def bad(resp):
        if resp.solution is None:
            return resp
        sol = dict(resp.solution.solution)
        sol["ops"] = list(reversed(sol["ops"]))
        return dataclasses.replace(
            resp, solution=dataclasses.replace(resp.solution, solution=sol))
    return [bad(r) for r in out]


def measure(monkeypatch, cell_name, fault):
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, config, traffic = run.lookup(bench, cell_name)
    over, lanes = SMALL[cell_name]
    traffic = dict(traffic, **over)
    config = dict(config, service={"lanes_per_device": lanes})
    armed = []
    real_measure, real_step = loadgen.Client.measure, engine.DPEngine.step

    def measure_armed(self, seconds):
        armed.append(True)
        return real_measure(self, seconds)

    def step(self, *a, **k):
        out = real_step(self, *a, **k)
        return fault(out) if armed and out and fault else out

    monkeypatch.setattr(loadgen.Client, "measure", measure_armed)
    monkeypatch.setattr(engine.DPEngine, "step", step)
    peaks = run.load_json(os.path.join(run.HERE, "peaks.json"))["TPU v5 lite"]
    return run.measure(config, traffic, SEED, 1.0, False,
                       jax.devices()[:1], run.cell_metrics(bench, cell_name,
                                                           False),
                       grace_s=1.0, peaks=peaks)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_sound_run_is_correct(monkeypatch, cell):
    out = measure(monkeypatch, cell, None)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", sorted(SMALL))
@pytest.mark.parametrize("fault", [alter_answer, drop_half])
def test_fault_is_caught(monkeypatch, cell, fault):
    out = measure(monkeypatch, cell, fault)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_altered_solution_is_caught(monkeypatch, cell):
    out = measure(monkeypatch, cell, alter_solution)
    assert not out["correct"], out["checks"]
    assert out["checks"]["invalid_solutions"]["value"] > 0
