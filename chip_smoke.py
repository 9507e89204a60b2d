#!/usr/bin/env python3
"""Chip smoke: drive the DPService kernel path once on a TPU and check it.

    python chip_smoke.py             # one chip: all five kernel routes
    python chip_smoke.py --chips 4   # sharded service vs one device

One chip: a ``DPService`` serves deployment-sized traffic per family —
``gotoh`` read-vs-window pairs (reads of 100–250 against windows a band
wider, the read-mapper extension step) and one ``needleman_wunsch`` at
512×1088 with reconstruction (``kernel_grid``); ``mcm`` at n=128
(``kernel_wavefront``) and at n=512 with reconstruction
(``kernel_tiled_wavefront``, fused traceback, 268 MB of weights per
instance in HBM); ``edit_distance`` at a few thousand cells
(``kernel_blocked``) and ``unbounded_knapsack`` at capacity 10⁵ with 64
items (``kernel_tiled``), both with reconstruction — for a plain linear
value the dispatcher prefers the jnp ``pipeline``/``companion_scan``
routes, which emit no args. Routes come from the normal dispatcher. The
traffic runs twice on fresh services: the first pass compiles, the second
reuses the compiled programs. Every answer must match the family's float64
reference, every decoded solution must recompute to the optimum, each of
the five kernel routes must serve a drain, and each kernel entry counter
must be nonzero in ``pallas`` mode.

``--chips 4``: only the grid and mcm n=512 traffic, through the
auto-meshed service on every device and through ``DPService(mesh=None)``
on device 0; answers, tables, args and decoded solutions must be
bit-identical, and every device must hold a shard.

Lines before the last are for information (routes, compile seconds, warm
seconds per request, peak device memory); they are not benchmark metrics.
The last line is one JSON object, printed only when every check passed.
The script refuses to run anywhere but a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the five kernel routes of the chip path
KERNEL_ROUTES = ("kernel_blocked", "kernel_tiled", "kernel_wavefront",
                 "kernel_tiled_wavefront", "kernel_grid")
#: ``ops`` entry points the traffic below must reach on the kernel path
KERNEL_ENTRIES = ("sdp_blocked_with_args", "sdp_chunked_with_args",
                  "mcm_blocked", "mcm_tiled_fused", "grid_blocked",
                  "grid_blocked_with_args")
#: minimap2's default affine scoring (a=2, b=4, q=4, e=2: a gap of length
#: L costs q + L·e); gotoh's X/Y planes open at q + e
GOTOH_SCORING = {"match": 2.0, "mismatch": -4.0, "gap_open": -6.0,
                 "gap_extend": -2.0}
NW_SCORING = {"match": 2.0, "mismatch": -1.0, "gap": -2.0}


class SmokeFailure(RuntimeError):
    pass


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Traffic sizes; the defaults are the chip run's."""

    read_lens: tuple = (100, 150, 200, 250)
    band: int = 32
    pairs_per_len: int = 64
    nw_shape: tuple = (512, 1088)
    mcm_small: int = 128
    mcm_small_count: int = 4
    mcm_large: int = 512
    mcm_large_count: int = 4
    edit_len: int = 60
    edit_count: int = 8
    knapsack_capacity: int = 100_000
    knapsack_items: int = 64
    knapsack_count: int = 2


@dataclasses.dataclass
class Request:
    problem: str
    payload: dict
    reconstruct: bool


# ---------------------------------------------------------------------------
# traffic (seeded)
# ---------------------------------------------------------------------------
def grid_traffic(rng, sizes: Sizes) -> list:
    reqs = []
    for L in sizes.read_lens:
        for k in range(sizes.pairs_per_len):
            reqs.append(Request("gotoh", {
                "x": rng.integers(0, 4, size=L),
                "y": rng.integers(0, 4, size=L + sizes.band),
                **GOTOH_SCORING}, reconstruct=k % 4 == 0))
    r, c = sizes.nw_shape
    reqs.append(Request("needleman_wunsch", {
        "x": rng.integers(0, 4, size=r), "y": rng.integers(0, 4, size=c),
        **NW_SCORING}, reconstruct=True))
    return reqs


def mcm_large_traffic(rng, sizes: Sizes) -> list:
    return [Request("mcm", {"dims": rng.integers(
        1, 30, size=sizes.mcm_large + 1).astype(np.float64)},
        reconstruct=True) for _ in range(sizes.mcm_large_count)]


def other_traffic(rng, sizes: Sizes) -> list:
    reqs = [Request("mcm", {"dims": rng.integers(
        1, 30, size=sizes.mcm_small + 1).astype(np.float64)},
        reconstruct=False) for _ in range(sizes.mcm_small_count)]
    for _ in range(sizes.edit_count):
        reqs.append(Request("edit_distance", {
            "x": rng.integers(0, 4, size=sizes.edit_len),
            "y": rng.integers(0, 4, size=sizes.edit_len)},
            reconstruct=True))
    cap = sizes.knapsack_capacity
    for _ in range(sizes.knapsack_count):
        weights = rng.choice(np.arange(2, max(cap // 100, 3 * sizes.knapsack_items)),
                             size=sizes.knapsack_items, replace=False)
        reqs.append(Request("unbounded_knapsack", {
            "item_weights": weights,
            "item_values": np.round(rng.random(sizes.knapsack_items) * 100
                                    + weights * 0.5, 3),
            "capacity": cap}, reconstruct=True))
    return reqs


# ---------------------------------------------------------------------------
# independent checks of decoded solutions: solution + instance -> cost
# ---------------------------------------------------------------------------
def _alignment_score(ops, x, y, align, gap):
    i = j = 0
    score, run_kind, run = 0.0, None, 0
    for op in ops:
        if op[0] == "align":
            if (op[1], op[2]) != (i, j):
                raise SmokeFailure(f"alignment op {op} out of order at {(i, j)}")
            score += align(i, j)
            i, j, run_kind, run = i + 1, j + 1, None, 0
            continue
        pos = i if op[0] == "del" else j
        if op[0] not in ("del", "ins") or op[1] != pos:
            raise SmokeFailure(f"alignment op {op} out of order at {(i, j)}")
        run = run + 1 if run_kind == op[0] else 1
        run_kind = op[0]
        score += gap(run)
        i, j = (i + 1, j) if op[0] == "del" else (i, j + 1)
    if (i, j) != (len(x), len(y)):
        raise SmokeFailure("alignment does not cover both sequences")
    return score


def _mcm_cost(tree, p):
    if isinstance(tree, (int, np.integer)):
        return 0.0, (p[tree], p[tree + 1])
    cl, (r0, c0) = _mcm_cost(tree[0], p)
    cr, (r1, c1) = _mcm_cost(tree[1], p)
    if c0 != r1:
        raise SmokeFailure("mcm tree multiplies non-conforming shapes")
    return cl + cr + r0 * c0 * c1, (r0, c1)


def solution_cost(req: Request, solution) -> float:
    kw = req.payload
    if req.problem in ("gotoh", "needleman_wunsch"):
        x, y = np.asarray(kw["x"]), np.asarray(kw["y"])
        if req.problem == "gotoh":
            gap = lambda run: kw["gap_open"] if run == 1 else kw["gap_extend"]
        else:
            gap = lambda run: kw["gap"]
        return _alignment_score(
            solution["ops"], x, y,
            lambda i, j: kw["match"] if x[i] == y[j] else kw["mismatch"], gap)
    if req.problem == "mcm":
        return _mcm_cost(solution["tree"], np.asarray(kw["dims"]))[0]
    if req.problem == "edit_distance":
        x, y = np.asarray(kw["x"]), np.asarray(kw["y"])
        i = j = 0
        cost = 0.0
        for op in solution["ops"]:
            if op[0] in ("match", "sub"):
                if (x[i] == y[j]) != (op[0] == "match"):
                    raise SmokeFailure(f"edit op {op} mislabels the pair")
                cost, i, j = cost + (op[0] == "sub"), i + 1, j + 1
            elif op[0] == "del":
                cost, i = cost + 1, i + 1
            else:
                cost, j = cost + 1, j + 1
        if (i, j) != (len(x), len(y)):
            raise SmokeFailure("edit script does not cover both sequences")
        return cost
    if req.problem == "unbounded_knapsack":
        real = {int(w): float(v) for w, v in zip(kw["item_weights"],
                                                 kw["item_values"])}
        items = solution["items"]
        if any(w not in real or not np.isclose(v, real[w], rtol=1e-5)
               for w, v in items):
            raise SmokeFailure("knapsack solution takes an item not offered")
        if sum(w for w, _ in items) > int(kw["capacity"]):
            raise SmokeFailure("knapsack solution exceeds the capacity")
        return float(sum(v for _, v in items))
    raise SmokeFailure(f"no solution check for {req.problem}")


def check_results(reqs: list, results: dict, refs: list) -> None:
    """Every answer equals its float64 reference; every decoded solution
    recomputes to it (the conformance suite's tolerance)."""
    for tid, (req, ref) in enumerate(zip(reqs, refs)):
        res = results[tid]
        if res.status != "done":
            raise SmokeFailure(f"request {tid} ({req.problem}) {res.status}")
        if not np.isclose(float(res.answer), ref, rtol=1e-4, atol=1e-4):
            raise SmokeFailure(f"request {tid} ({req.problem}): answer "
                               f"{float(res.answer)} != reference {ref}")
        if req.reconstruct:
            got = solution_cost(req, res.solution.solution)
            if not np.isclose(got, ref, rtol=1e-4, atol=1e-4):
                raise SmokeFailure(
                    f"request {tid} ({req.problem}): decoded solution "
                    f"recomputes to {got}, optimum {ref}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def serve(svc, reqs: list) -> tuple:
    """Submit ``reqs`` (tids 0..) and drive the service to empty. Returns
    ``(results, drains)``: each drain's wall seconds — the drain ends in
    the engine's host copy of the batch — request count, and whether it
    traced a new program."""
    from repro.dp import backends

    tids = [svc.submit(r.problem, reconstruct=r.reconstruct, **r.payload)
            for r in reqs]
    if tids != list(range(len(reqs))):
        raise SmokeFailure("service handed out unexpected ticket ids")
    results, drains = {}, []
    while svc.pending():
        traces = backends.TRACE_COUNT
        t0 = time.perf_counter()
        resolved = svc.step()
        dt = time.perf_counter() - t0
        done = {tid: svc.poll(tid) for tid in resolved}
        results.update(done)
        if done:
            first = next(iter(done.values()))
            drains.append({"problem": first.problem, "backend": first.backend,
                           "requests": len(done), "seconds": dt,
                           "cold": backends.TRACE_COUNT != traces})
    for tid in tids:                   # answer-cache hits resolved at submit
        if tid not in results:
            results[tid] = svc.poll(tid)
    return results, drains


def references(reqs: list) -> list:
    from repro import dp

    return [float(dp.get_problem(r.problem).solve_reference(**r.payload))
            for r in reqs]


def kernel_counters(mode: str) -> dict:
    from repro.kernels import ops

    return {fn: ops.ENTRY_COUNTS.get((fn, mode), 0) for fn in KERNEL_ENTRIES}


def check_routes(routes: dict) -> None:
    served = {backend for (_, backend) in routes}
    missing = [r for r in KERNEL_ROUTES if r not in served]
    if missing:
        raise SmokeFailure(f"kernel routes served no drain: {missing}; "
                           f"routes taken: {routes}")


def check_counters(counts: dict) -> None:
    zero = [fn for fn, v in counts.items() if not v]
    if zero:
        raise SmokeFailure(f"kernel entry counters still 0: {zero}")


def one_chip(sizes: Sizes, seed: int = 0) -> dict:
    """The one-chip phase: all traffic through a fresh service twice (cold,
    then warm). Returns the information lines' data."""
    from repro import dp

    rng = np.random.default_rng(seed)
    reqs = (grid_traffic(rng, sizes) + mcm_large_traffic(rng, sizes)
            + other_traffic(rng, sizes))
    refs = references(reqs)
    info = {}
    for leg in ("cold", "warm"):
        svc = dp.DPService(feedback=False)
        results, drains = serve(svc, reqs)
        check_results(reqs, results, refs)
        check_routes(svc.routes)
        info[leg] = {"routes": {f"{p}/{b}": n
                                for (p, b), n in sorted(svc.routes.items())},
                     "drains": drains}
    return info


def _digest(res):
    """Everything a request returns, as comparable plain data."""
    out = {"answer": float(res.answer), "backend": res.backend}
    if res.solution is not None:
        out["table"] = np.asarray(res.solution.table).tobytes()
        out["args"] = np.asarray(res.solution.args).tobytes()
        out["solution"] = repr(res.solution.solution)
    return out


def four_chips(sizes: Sizes, seed: int = 0) -> dict:
    """The sharded phase: grid and mcm n=512 traffic through the auto-meshed
    service and through a single-device service; bit-identical outputs and
    a shard on every device."""
    import jax
    import jax.numpy as jnp

    from repro import dp
    from repro.dp.sharding import ShardedDPEngine

    rng = np.random.default_rng(seed)
    reqs = grid_traffic(rng, sizes) + mcm_large_traffic(rng, sizes)
    refs = references(reqs)
    devices = jax.devices()
    sharded = dp.DPService(feedback=False)
    if not isinstance(sharded.engine, ShardedDPEngine) \
            or sharded.engine.ctx.ndev != len(devices):
        raise SmokeFailure("the auto-meshed service does not span every device")
    probe = sharded.engine.ctx.place(jnp.zeros((len(devices), 8), jnp.float32))
    placed = {s.device for s in probe.addressable_shards}
    if placed != set(devices) or any(s.data.shape[0] != 1
                                     for s in probe.addressable_shards):
        raise SmokeFailure(f"ShardContext.place put the batch on {placed}")
    res_sharded, drains_sharded = serve(sharded, reqs)
    check_results(reqs, res_sharded, refs)
    if not sharded.engine.stats["sharded_drains"]:
        raise SmokeFailure("no drain ran sharded")
    # every device held at least one mcm n=512 lane's weights
    lane = 4 * (sizes.mcm_large * (sizes.mcm_large + 1) // 2) * (sizes.mcm_large - 1)
    peaks = [d.memory_stats()["peak_bytes_in_use"] for d in devices]
    if min(peaks) < lane:
        raise SmokeFailure(f"a device held no shard: peak bytes {peaks}, "
                           f"one mcm lane {lane}")
    single = dp.DPService(feedback=False, mesh=None)
    res_single, drains_single = serve(single, reqs)
    check_results(reqs, res_single, refs)
    for tid in range(len(reqs)):
        a, b = _digest(res_sharded[tid]), _digest(res_single[tid])
        if a != b:
            raise SmokeFailure(f"request {tid} ({reqs[tid].problem}): "
                               "sharded and single-device outputs differ")
    return {"routes_sharded": {f"{p}/{b}": n for (p, b), n
                               in sorted(sharded.routes.items())},
            "routes_single": {f"{p}/{b}": n for (p, b), n
                              in sorted(single.routes.items())},
            "sharded_drains": sharded.engine.stats["sharded_drains"],
            "padded_lanes": sharded.engine.stats["padded_lanes"],
            "peak_bytes_per_device": peaks,
            "drains": drains_sharded + drains_single}


# ---------------------------------------------------------------------------
# reporting + entry point
# ---------------------------------------------------------------------------
def _print_drains(leg: str, drains: list) -> None:
    cold = [d for d in drains if d["cold"]]
    warm = [d for d in drains if not d["cold"]]
    print(f"# {leg}: {len(drains)} drains, {len(cold)} traced a program; "
          f"compile+first-run seconds {sum(d['seconds'] for d in cold):.3f}")
    per = {}
    for d in warm:
        k = (d["problem"], d["backend"])
        s, n = per.get(k, (0.0, 0))
        per[k] = (s + d["seconds"], n + d["requests"])
    for (p, b), (s, n) in sorted(per.items()):
        print(f"#   warm {p}/{b}: {s / n:.6f} s per request over {n}")


def require_tpu():
    """The device the smoke runs on; refuses anything but a TPU with the
    kernels in ``pallas`` mode."""
    import jax

    from repro.kernels import ops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX runs on {dev.platform}")
    if ops.kernel_mode() != "pallas":
        raise SmokeFailure(f"kernel mode is {ops.kernel_mode()!r}, not "
                           "'pallas' (REPRO_KERNELS set?)")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-single-device phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.jax_cache import use_persistent_cache

    print(f"# compilation cache: {use_persistent_cache()}")
    import jax

    dev = require_tpu()
    if len(jax.devices()) != args.chips:
        raise SmokeFailure(f"--chips {args.chips} but JAX sees "
                           f"{len(jax.devices())} devices")
    sizes = Sizes()
    if args.chips == 1:
        info = one_chip(sizes, args.seed)
        for leg in ("cold", "warm"):
            print(f"# {leg} routes: {info[leg]['routes']}")
            _print_drains(leg, info[leg]["drains"])
        counts = kernel_counters("pallas")
        print(f"# kernel entry counters (pallas): {counts}")
        check_counters(counts)
    else:
        info = four_chips(sizes, args.seed)
        print(f"# sharded routes: {info['routes_sharded']}")
        print(f"# single-device routes: {info['routes_single']}")
        print(f"# sharded drains {info['sharded_drains']}, padded lanes "
              f"{info['padded_lanes']}")
        print(f"# peak bytes per device: {info['peak_bytes_per_device']}")
        _print_drains("sharded+single", info["drains"])
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(f"# device 0 peak bytes in use: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
