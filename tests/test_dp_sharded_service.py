"""The four-device serving path (DESIGN.md §7) end to end on the CPU.

A child process with four forced host devices drives
``DPService(mesh="auto")`` — so ``ShardedDPEngine`` — with gotoh pairs drawn
by the benchmark's own maker (``bench/instances/pair.py``) at small sizes
with the ``readmap-gotoh-4chip`` scoring, under a profiler capture. One
round fills every drain to a multiple of the mesh, one is ragged so that
pad lanes occur. The tests compare every answer, and the rescored cost of
every decoded alignment, with the float64 reference
(``bench/reference/gotoh.py``) exactly, and read the engine's counters and
the ``dp.*`` spans' args. Tier-1 runs on one CPU device, so the child is
the only way the sharded path runs there.

Run the child by hand (it prints one JSON line of what it saw)::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/test_dp_sharded_service.py
"""
import glob
import json
import os
import subprocess
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
NDEV = 4
SEED = 2 ** 31 + 15
#: read lengths, windows 8 wider; pairs of each length per round
SIZES = (12, 20)
ROUNDS = (16, 7)
RECONSTRUCT_EVERY = 4
MAX_BATCH = 16
CHILD_TIMEOUT_S = 600


# ---------------------------------------------------------------------------
# the child: runs with four host devices
# ---------------------------------------------------------------------------
def _child() -> dict:
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import numpy as np

    import jax

    import loadgen
    from reference import gotoh as ref
    from repro import dp
    from repro.dp.sharding import ShardedDPEngine

    with open(os.path.join(BENCH, "configs",
                           "readmap-gotoh-4chip.json")) as f:
        inst = dict(json.load(f)["instance"], window_extra=8)
    pair = loadgen.load_module(os.path.join(BENCH, "instances", "pair.py"))
    rng = np.random.default_rng(SEED)
    rounds = [[(pair.make(inst, size, rng)[0], k % RECONSTRUCT_EVERY == 0)
               for size in SIZES for k in range(per)] for per in ROUNDS]

    svc = dp.DPService(mesh="auto", feedback=False, max_batch=MAX_BATCH)
    answered, spans = [], []
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        try:
            for reqs in rounds:
                tids = [svc.submit("gotoh", reconstruct=recon, **payload)
                        for payload, recon in reqs]
                out = svc.run()
                answered += [(payload, recon, out[t]) for (payload, recon), t
                             in zip(reqs, tids)]
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans += [[e.start_ns, e.start_ns + e.duration_ns,
                               e.name, dict(e.stats)] for e in line.events
                              if e.name.startswith("dp.")]

    value_gap, rescore_gap, decoded, done = 0.0, 0.0, 0, 0
    for payload, recon, res in answered:
        done += res.status == "done"
        want = float(ref.answers([payload])[0])
        value_gap = max(value_gap, abs(float(res.answer) - want))
        if recon:
            decoded += 1
            cost = ref.solution_cost(payload, res.solution.solution)
            rescore_gap = max(rescore_gap, abs(cost - want))

    eng = svc.engine
    return {"devices": jax.device_count(),
            "engine": type(eng).__name__,
            "sharded_engine": isinstance(eng, ShardedDPEngine),
            "requests": len(answered), "done": done, "decoded": decoded,
            "value_gap": value_gap, "rescore_gap": rescore_gap,
            "stats": {k: eng.stats[k] for k in (
                "device_batches", "sharded_drains", "padded_lanes")},
            "routes": sorted(b for _, b in svc.routes),
            "spans": sorted(spans)}


# ---------------------------------------------------------------------------
# the tests: read what the child saw
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def seen():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={NDEV}")
    env.pop("REPRO_TELEMETRY", None)
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=CHILD_TIMEOUT_S)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _named(seen, name):
    return [s for s in seen["spans"] if s[2] == name]


def _pad(lanes: int) -> int:
    """The pad lanes ``ShardContext.pad`` adds to a drain of ``lanes``."""
    return -(-lanes // NDEV) * NDEV - lanes


def test_child_runs_the_sharded_engine_on_four_devices(seen):
    assert seen["devices"] == NDEV
    assert seen["sharded_engine"], seen["engine"]


def test_every_answer_equals_the_reference(seen):
    assert seen["requests"] == sum(ROUNDS) * len(SIZES)
    assert seen["done"] == seen["requests"]
    assert seen["value_gap"] == 0.0


def test_every_decoded_alignment_rescores_to_the_optimum(seen):
    assert seen["decoded"] == len(SIZES) * sum(
        -(-per // RECONSTRUCT_EVERY) for per in ROUNDS)
    assert seen["rescore_gap"] == 0.0


def test_every_drain_is_sharded_and_padded_as_the_context_pads(seen):
    drains = _named(seen, "dp.drain")
    stats = seen["stats"]
    assert stats["sharded_drains"] == stats["device_batches"] == len(drains)
    pads = [_pad(args["unique"]) for *_, args in drains]
    assert any(pads), "the ragged round made no pad lanes"
    assert stats["padded_lanes"] == sum(pads)
    assert [args["pad_lanes"] for *_, args in drains] == pads
    assert all(args["shards"] == NDEV for *_, args in drains)


def test_stack_and_fetch_spans_count_per_shard(seen):
    """``dp.stack`` sends each device a quarter of the padded bucket, each
    source slot through one ``dp.place`` inside it; ``dp.fetch`` gathers
    from every device."""
    stacks = _named(seen, "dp.stack")
    places = _named(seen, "dp.place")
    assert len(stacks) == len(_named(seen, "dp.drain"))
    for a, b, _, args in stacks:
        assert args["shards"] == NDEV
        assert args["h2d_bytes_per_shard"] * NDEV == args["h2d_bytes"]
        inside = [p for p in places if a <= p[0] and p[1] <= b]
        assert len(inside) == args["arrays"] >= 1
    assert len(places) == sum(args["arrays"] for *_, args in stacks)
    assert all(args["shards"] == NDEV
               for *_, args in _named(seen, "dp.fetch"))


if __name__ == "__main__":
    print(json.dumps(_child()))
