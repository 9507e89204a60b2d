#!/usr/bin/env python3
"""The control of a cell's comparison: the reference in the program's place.

    python bench/control.py --workload <cell> --seed <n> --requests <k>

Draws the cell's first ``k`` timed requests from the seed exactly as
``bench/run.py`` does, answers them with the plain reference computed in
bfloat16 (the next precision below the float32 the configurations state),
and compares those answers with the float64 reference through the same
``run.check`` and limits as a run. The control has to come out not correct;
it prints the compared numbers and a JSON line like a run's (``"control":
true``). It does not decode, so its requests are compared by answer only.
Runs on the host: no accelerator needed; the benchmark's own runs never
call it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

import ml_dtypes
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
import run  # noqa: E402

CONTROL_DTYPE = ml_dtypes.bfloat16


def requests(config: dict, traffic: dict, seed: int, k: int) -> list:
    """The first ``k`` timed requests of a run of this seed."""
    gen = loadgen.Generator(config, traffic, np.random.default_rng([seed, 0]))
    out = []
    while len(out) < k:
        out.extend(gen.round() if traffic["loop"] == "rounds" else [gen.one()])
    return out[:k]


def control(config: dict, traffic: dict, seed: int, k: int) -> dict:
    reqs = requests(config, traffic, seed, k)
    ref = run.load_module(os.path.join(HERE, "reference",
                                       config["problem"] + ".py"))
    groups = {}
    for r in reqs:
        r.reconstruct = False
        groups.setdefault(r.shape, []).append(r)
    for group in groups.values():
        low = ref.answers([r.payload for r in group], dtype=CONTROL_DTYPE)
        for r, a in zip(group, np.asarray(low, dtype=np.float64)):
            r.result = SimpleNamespace(status="done", answer=float(a),
                                       solution=None)
    checks, failed = run.check(reqs, config)
    return {"control": True, "correct": run.passed(checks),
            "attempted": len(reqs), "failed": failed, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, config, traffic = run.lookup(bench, args.workload)
    out = control(config, traffic, args.seed, args.requests)
    for name, c in out["checks"].items():
        print(f"control {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
