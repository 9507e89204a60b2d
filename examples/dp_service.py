"""DPService tour: the sharded, cache-fronted serving tier (DESIGN.md §7).

Mixed-problem traffic through submit/poll handles — priorities, deadlines,
the content-digest answer cache, intra-drain dedup, and (with more than one
visible device) sharded bucket drains, and the counts the service and the
engine keep in their ``stats`` (DESIGN.md §8).

The tour ends with a streaming session (DESIGN.md §11): one alignment
instance grown a few columns at a time through ``open_session/append``,
where every append after the first warm-starts off the longest solved
prefix in the chain-digest index — recomputing only the extension, sticky
to the session's affine backend — and re-sending an already-solved length
is answered at admission with no device work at all.

Run: ``PYTHONPATH=src python examples/dp_service.py``
Try: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first to watch
the same traffic drain sharded over an 8-device host mesh.
"""
import time

import numpy as np

from repro import dp


def main() -> None:
    import jax

    ndev = jax.device_count()
    svc = dp.DPService(max_batch=16)
    print(f"devices: {ndev} -> engine: {type(svc.engine).__name__}")

    rng = np.random.default_rng(0)
    # a small pool of unique instances, drawn with repeats — the shape of
    # real traffic, and what the digest cache + dedup are for
    pool = []
    for name, size in [("mcm", 9), ("mcm", 13), ("lcs", 8),
                       ("edit_distance", 8), ("unbounded_knapsack", 10)]:
        prob = dp.get_problem(name)
        pool += [(name, prob.sample(rng, size)) for _ in range(3)]

    tids = []
    t0 = time.perf_counter()
    for i in range(120):
        name, kw = pool[int(rng.integers(len(pool)))]
        tids.append(svc.submit(
            name, reconstruct=(i % 5 == 0), priority=int(rng.integers(3)),
            deadline_ms=60_000.0, **kw))
        if (i + 1) % 10 == 0:       # arrivals interleave with service steps
            svc.step()
    out = svc.run()
    wall = time.perf_counter() - t0

    done = [r for r in out.values() if r.status == "done"]
    recon = [r for r in done if r.solution is not None]
    lat = sorted(r.latency_ms for r in done)
    print(f"\n{len(done)} requests in {wall:.2f}s "
          f"({len(done) / wall:.0f} req/s), "
          f"p50 latency {lat[len(lat) // 2]:.1f} ms")
    cs = svc.cache_stats()
    print(f"cache: {cs['hits']} hits / {cs['misses']} misses "
          f"({100 * cs['hit_rate']:.0f}% hit rate, {cs['size']} entries); "
          f"intra-drain dedup: {svc.engine.stats['dedup_hits']} requests "
          f"shared a solve lane")
    eng = svc.engine.stats
    print(f"engine: {eng['device_batches']} device batches, "
          f"{eng.get('sharded_drains', 0)} sharded over the mesh "
          f"({eng.get('padded_lanes', 0)} pad lanes), "
          f"{eng['feedback_observations']} latencies fed back to routing")
    sample = next(r for r in recon if r.problem == "mcm")
    print(f"sample reconstructed {sample.problem}: "
          f"{sample.solution.solution['string']} via {sample.backend}")

    print("\nroutes served (problem, backend -> requests):")
    for (name, backend), count in sorted(svc.routes.items()):
        print(f"  {name:20s} {backend:14s} {count}")

    rep = dp.routing_report()
    print(f"\nrouting_report on {rep['jax_backend']}: observations by "
          f"measurement regime")
    by_regime = {}
    for row in rep["shapes"]:
        key = str(row["regime"])
        by_regime.setdefault(key, []).append(row)
    for regime, rows in sorted(by_regime.items()):
        picks = {r["measured_choice"] for r in rows}
        print(f"  {regime:24s} {len(rows)} shape(s), measured picks: "
              f"{', '.join(sorted(picks))}")

    # -- the counts (DESIGN.md §8) ----------------------------------------
    # the service's and the engine's stats are the run's counts; where the
    # time went is the profiler's to say (wrap the traffic in
    # jax.profiler.start_trace/stop_trace to record the dp.* spans)
    print("\nservice stats: " + ", ".join(
        f"{k}={v}" for k, v in svc.stats.items() if v))
    print("engine stats:  " + ", ".join(
        f"{k}={v}" for k, v in svc.engine.stats.items() if v))

    # -- streaming session (DESIGN.md §11) --------------------------------
    # one growing alignment: y gains 24 columns per append; the service
    # finds the longest already-solved prefix through the chain-digest
    # index and recomputes only the extension — bit-identical to a cold
    # solve of the full instance
    x = rng.integers(0, 4, size=96)
    y = rng.integers(0, 4, size=240)
    sid = svc.open_session("needleman_wunsch")
    print(f"\nstreaming session {sid}: needleman_wunsch, "
          f"{len(x)} rows, y growing 120 -> {len(y)}")
    for length in range(120, len(y) + 1, 24):
        t0 = time.perf_counter()
        tid = svc.append(sid, x=x, y=y[:length])
        res = svc.run()[tid]
        kind = "extend" if res.extended else "cold"
        print(f"  len={length:3d} {kind:6s} via {res.backend:14s} "
              f"answer={float(np.float64(res.answer)):8.1f}  "
              f"({(time.perf_counter() - t0) * 1e3:6.2f} ms)")
    # an already-solved length resolves at admission: full prefix-index hit
    rep = svc.poll(svc.append(sid, x=x, y=y))
    print(f"  len={len(y):3d} replay: cached={rep.cached} "
          f"(no backlog slot, no device work)")
    pidx = svc.session_stats()["prefix_index"]
    summary = svc.close_session(sid)
    print(f"  closed: {summary['appends']} appends, "
          f"{summary['extends']} extends, affinity {summary['affinity']}; "
          f"prefix index {pidx['hits']} hits / {pidx['misses']} misses "
          f"({100 * pidx['hit_rate']:.0f}% hit rate)")


if __name__ == "__main__":
    main()
