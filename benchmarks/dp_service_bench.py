"""DPService bench: serving-tier throughput/latency under mixed traffic.

Drives a :class:`repro.dp.DPService` with a mixed-problem request stream
(four problems × two shapes, ~3 requests per unique instance so the digest
cache and intra-drain dedup both engage, a reconstruct slice, random
priorities) and reports requests/sec, p50/p99 completion latency with the
per-phase queue/dispatch/solve/traceback/decode breakdown from the
telemetry histograms (DESIGN.md §8), cache hit rate, and the engine's
dedup/shard counters.

Prints ``service,<devices>,<requests>,<req_per_s>,<p50_ms>,<p99_ms>,
<cache_hit_rate>,<ok>`` CSV lines, writes ``BENCH_dp_service.json`` and a
compact telemetry *summary* (counters + per-histogram count/p50/p99, a few
hundred lines) to ``TELEMETRY_dp_service_summary.json`` — the file that is
committed run-over-run. The full snapshot (every span; tens of thousands
of lines) still goes to ``TELEMETRY_dp_service.json``
but is a CI artifact only, never committed.

The append-heavy *streaming* leg (DESIGN.md §11) drives one growing
needleman_wunsch session — each append extends the instance by a small
fraction — against cold submits of the identical instances on a fresh
service, and reports the extend-vs-cold latency speedup plus the
longest-prefix cache's hit rate. Warm-start serving is only worth its
machinery if extending ~5% of an instance is much cheaper than re-solving
it, so the full bench gates ``speedup_mean ≥ STREAM_SPEEDUP_GATE``; the
answers of the two paths must agree either way. Prints a
``service-streaming,...`` CSV line into the same report.

The service shards its drains over every device this process sees
(``DPService``'s auto mesh); one process drives them all, so the bench
never starts a child that would need the accelerator its parent holds.

``--telemetry-gate`` is the CI overhead gate: the same traffic with
telemetry ``off`` vs ``spans`` (routing feedback disabled and the
calibration table reset per leg, so routing is a deterministic function of
the analytical model), asserting bit-identical routing and answers between
the modes and ≤``GATE_OVERHEAD_FRACTION`` span-mode wall-time overhead
(with an absolute floor — sub-second walls on shared CI runners would
otherwise turn scheduler noise into failures).
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Optional

import numpy as np

N_REQUESTS = 256
UNIQUE_FRACTION = 3          # ~N/3 unique instances → repeats hit the cache
RECONSTRUCT_EVERY = 4        # every 4th request asks for a decoded solution
#: telemetry-gate budget: spans-mode wall ≤ off-mode wall × (1 + fraction),
#: with an absolute slack floor so short walls don't gate on timer noise
GATE_OVERHEAD_FRACTION = 0.05
GATE_ABS_FLOOR_S = 0.15
#: phases exported per leg (the service histograms feeding them)
PHASES = ("queue", "dispatch", "solve", "traceback", "decode")
#: streaming leg geometry: one session over a rows × (base + i·k) grid
#: alignment; k/final-length stays well under the ≤10% extension fraction
#: the warm-start contract targets
STREAM_ROWS = 512
STREAM_BASE_LEN = 1024
STREAM_APPEND_LEN = 64
STREAM_APPENDS = 5
#: full-bench gate: mean extend-vs-cold speedup the streaming leg must hit
STREAM_SPEEDUP_GATE = 5.0


def _traffic(rng, n_requests: int) -> list:
    """(problem, payload, reconstruct, priority) tuples with repeats."""
    from repro import dp

    problems = ["mcm", "lcs", "edit_distance", "unbounded_knapsack"]
    sizes = (8, 12)
    pool = []
    for name in problems:
        prob = dp.get_problem(name)
        for size in sizes:
            for _ in range(max(1, n_requests // (UNIQUE_FRACTION
                                                 * len(problems)
                                                 * len(sizes)))):
                pool.append((name, prob.sample(rng, size)))
    reqs = []
    for i in range(n_requests):
        name, kw = pool[int(rng.integers(len(pool)))]
        reqs.append((name, kw, i % RECONSTRUCT_EVERY == 0,
                     int(rng.integers(0, 3))))
    return reqs


def _phase_quantiles(telemetry) -> dict:
    """p50/p99 (+ sample count) per service phase from the registry
    histograms — {} for phases with no samples (e.g. telemetry off)."""
    hists = telemetry.REGISTRY.histograms()
    out = {}
    for ph in PHASES:
        h = hists.get(f"dp_service_{ph}_ms")
        if h is not None and h.count:
            out[ph] = {"p50_ms": round(h.quantile(0.5), 3),
                       "p99_ms": round(h.quantile(0.99), 3),
                       "samples": h.count}
    return out


def _measure(n_requests: int, seed: int = 0, telemetry_mode: str = "spans",
             feedback: bool = True) -> dict:
    """One leg: mixed traffic through a DPService on THIS process's
    devices under the given telemetry mode. Returns the metrics row
    (latency quantiles from the telemetry histograms when they have
    samples, the raw latency list otherwise)."""
    import jax

    from repro import dp
    from repro.dp import telemetry

    prev_mode = telemetry.configure(telemetry_mode)
    rng = np.random.default_rng(seed)
    reqs = _traffic(rng, n_requests)

    # warm the jit caches with one instance per (problem, shape, regime):
    # compile time is a one-off, not a serving-throughput signal
    warm = dp.DPService(max_batch=32, feedback=feedback)
    seen = set()
    for name, kw, reconstruct, _ in reqs:
        spec = dp.get_problem(name).encode(**kw)
        key = (name, spec.shape_key(), reconstruct)
        if key not in seen:
            seen.add(key)
            warm.submit(name, reconstruct=reconstruct, **kw)
    warm.run()
    # the warm leg's telemetry is not part of the measurement
    telemetry.REGISTRY.reset()
    telemetry.clear_spans()

    svc = dp.DPService(max_batch=32, feedback=feedback)
    submit_t = {}
    latencies = []
    checks = {}          # tid -> (name, kw): gate on SERVICE answers
    answers = {}
    t0 = time.perf_counter()
    # arrivals interleave with service steps (small waves) — the
    # continuous-batching pattern: later repeats of an already-served
    # instance hit the digest cache, same-wave repeats dedup in-drain
    wave = 8

    def collect(done):
        latencies.append((time.perf_counter() - submit_t[done]) * 1e3)
        res = svc.poll(done)
        if done in checks:
            answers[done] = res.answer

    for i, (name, kw, reconstruct, priority) in enumerate(reqs):
        tid = svc.submit(name, reconstruct=reconstruct, priority=priority,
                         **kw)
        submit_t[tid] = time.perf_counter()
        if i < 16:
            checks[tid] = (name, kw)
        if (i + 1) % wave == 0:
            for done in svc.step():
                collect(done)
    while svc.pending():
        for done in svc.step():
            collect(done)
    wall = time.perf_counter() - t0
    # cache-hit tickets resolved at submit: latency ≈ 0 by construction
    latencies.extend(0.0 for _ in range(n_requests - len(latencies)))

    # correctness gate: what the SERVICE answered (through whatever drain
    # path this leg used — sharded, deduped, cached) vs the numpy oracles;
    # re-solving through dp.solve here would bypass the very path under
    # test. Checked tids that resolved at submit (cache hits) are still
    # pollable now.
    ok = True
    for tid, (name, kw) in checks.items():
        if tid not in answers:
            answers[tid] = svc.poll(tid).answer
        ref = dp.get_problem(name).solve_reference(**kw)
        if not np.allclose(answers[tid], ref, rtol=1e-4, atol=1e-4):
            ok = False

    # end-to-end latency quantiles: service-side histogram when telemetry
    # recorded one (its sample count covers EVERY resolution — including
    # cache hits the old percentile-of-collected-list reporting undercounted
    # when a checked tid was polled late), client-side list otherwise
    lat_hist = telemetry.REGISTRY.histograms().get("dp_service_latency_ms")
    if lat_hist is not None and lat_hist.count:
        p50 = lat_hist.quantile(0.5)
        p99 = lat_hist.quantile(0.99)
        samples = lat_hist.count
    else:
        p50 = float(np.percentile(latencies, 50))
        p99 = float(np.percentile(latencies, 99))
        samples = len(latencies)

    # routing/answer fingerprint — the telemetry gate's bit-identical check
    digest = hashlib.sha256()
    for tid in sorted(answers):
        digest.update(repr((tid, answers[tid])).encode())
    fingerprint = {
        "routes": sorted(f"{p}:{b}={n}" for (p, b), n in svc.routes.items()),
        "answers_sha256": digest.hexdigest(),
    }

    eng = svc.engine.stats
    row = {
        "devices": jax.device_count(),
        "requests": n_requests,
        "telemetry_mode": telemetry_mode,
        "wall_s": round(wall, 4),
        "req_per_s": round(n_requests / max(wall, 1e-9), 1),
        "p50_ms": round(p50, 3),
        "p99_ms": round(p99, 3),
        "latency_samples": samples,
        "phases": _phase_quantiles(telemetry),
        "cache_hit_rate": round(svc.cache_stats()["hit_rate"], 3),
        "dedup_hits": eng["dedup_hits"],
        "device_batches": eng["device_batches"],
        "sharded_drains": eng.get("sharded_drains", 0),
        "expired": svc.stats["expired"],
        "fingerprint": fingerprint,
        "ok": ok,
    }
    telemetry.configure(prev_mode)
    return row


def _csv(row: dict) -> None:
    print(f"service,{row['devices']},{row['requests']},{row['req_per_s']},"
          f"{row['p50_ms']},{row['p99_ms']},{row['cache_hit_rate']},"
          f"{int(row['ok'])}")


def _measure_streaming(rows: int = STREAM_ROWS, base: int = STREAM_BASE_LEN,
                       k: int = STREAM_APPEND_LEN,
                       n_appends: int = STREAM_APPENDS,
                       seed: int = 7) -> dict:
    """Append-heavy leg: a needleman_wunsch session growing by ``k``
    columns per append vs cold submits of the identical instances.

    Four passes, each over the same length ladder with content that is
    prefix-consistent per salt: a throwaway session and a throwaway cold
    service first (compile/trace warm-up — every length is a fresh grid
    shape, and compile time is a one-off, not a serving signal), then the
    measured session and the measured cold service share one salt so the
    two paths' answers can be compared instance-for-instance."""
    from repro import dp

    name = "needleman_wunsch"
    rng = np.random.default_rng(seed)
    lens = [base + k * i for i in range(n_appends + 1)]
    xs = {s: rng.integers(0, 4, size=rows) for s in range(3)}
    ys = {s: rng.integers(0, 4, size=lens[-1]) for s in range(3)}

    def kw(length, salt):
        return dict(x=xs[salt], y=ys[salt][:length],
                    match=2.0, mismatch=-1.0, gap=-2.0)

    warm = dp.DPService(max_batch=8)
    sid = warm.open_session(name)
    for length in lens:
        warm.append(sid, **kw(length, 0))
        warm.run()
    warm.close_session(sid)
    warm_cold = dp.DPService(max_batch=8)
    for length in lens[1:]:
        warm_cold.submit(name, **kw(length, 1))
        warm_cold.run()

    ok = True
    svc = dp.DPService(max_batch=8)
    sid = svc.open_session(name)
    svc.append(sid, **kw(lens[0], 2))
    svc.run()
    extend_ms, warm_answers = [], []
    for length in lens[1:]:
        t0 = time.perf_counter()
        tid = svc.append(sid, **kw(length, 2))
        res = svc.run()[tid]
        extend_ms.append((time.perf_counter() - t0) * 1e3)
        ok = ok and res.extended and not res.cached
        warm_answers.append(res.answer)
    # re-sending the final instance: a full prefix-index hit resolves at
    # admission — no backlog slot, no device work
    rep = svc.poll(svc.append(sid, **kw(lens[-1], 2)))
    ok = ok and rep is not None and rep.cached and rep.extended
    prefix = svc.session_stats()["prefix_index"]
    summary = svc.close_session(sid)

    cold = dp.DPService(max_batch=8)
    cold_ms = []
    for length, warm_answer in zip(lens[1:], warm_answers):
        t0 = time.perf_counter()
        tid = cold.submit(name, **kw(length, 2))
        res = cold.run()[tid]
        cold_ms.append((time.perf_counter() - t0) * 1e3)
        ok = ok and bool(np.allclose(np.float64(res.answer),
                                     np.float64(warm_answer), rtol=1e-5))

    speedups = np.array(cold_ms) / np.array(extend_ms)
    return {
        "problem": name,
        "rows": rows,
        "base_len": base,
        "append_len": k,
        "appends": n_appends,
        "extension_fraction": round(k / lens[-1], 4),
        "extend_ms": [round(t, 3) for t in extend_ms],
        "cold_ms": [round(t, 3) for t in cold_ms],
        "speedup_mean": round(float(speedups.mean()), 3),
        "speedup_min": round(float(speedups.min()), 3),
        "prefix_hit_rate": round(prefix["hit_rate"], 3),
        "prefix_index": prefix,
        "session": summary,
        "ok": ok,
    }


def _csv_streaming(row: dict) -> None:
    print(f"service-streaming,{row['rows']},{row['base_len']},"
          f"{row['append_len']},{row['extension_fraction']},"
          f"{row['speedup_mean']},{row['speedup_min']},"
          f"{row['prefix_hit_rate']},{int(row['ok'])}")


def _telemetry_summary(telemetry) -> dict:
    """Compact, committable digest of the registry: counters/gauges plus
    count/p50/p99 per histogram — no span bodies (those stay in the full
    snapshot, which is a CI artifact only)."""
    snap = telemetry.snapshot(spans_limit=1)
    return {
        "mode": snap["mode"],
        "counters": snap["counters"],
        "gauges": snap["gauges"],
        "histograms": {
            name: {"count": h.get("count"), "p50": h.get("p50"),
                   "p99": h.get("p99")}
            for name, h in snap["histograms"].items()},
    }


def run(out_path: str = "BENCH_dp_service.json",
        telemetry_out_path: str = "TELEMETRY_dp_service.json",
        telemetry_summary_path: str = "TELEMETRY_dp_service_summary.json",
        n_requests: int = N_REQUESTS, check_perf: bool = True,
        streaming: bool = True,
        streaming_cfg: Optional[dict] = None) -> dict:
    from repro.dp import telemetry

    legs = [_measure(n_requests)]
    _csv(legs[0])
    if telemetry_out_path:
        # the CI artifact: full spans/metrics state of the leg
        print(f"# wrote {telemetry.save_snapshot(telemetry_out_path)}")
    if telemetry_summary_path:
        # the committed file: small enough to diff run-over-run
        with open(telemetry_summary_path, "w") as f:
            json.dump(_telemetry_summary(telemetry), f, indent=1,
                      default=str)
        print(f"# wrote {os.path.abspath(telemetry_summary_path)}")
    report = {"legs": legs, "n_requests": n_requests}
    if streaming:
        report["streaming"] = _measure_streaming(**(streaming_cfg or {}))
        _csv_streaming(report["streaming"])
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {os.path.abspath(out_path)}")
    bad = [l for l in legs + [report.get("streaming")]
           if l is not None and not l.get("ok")]
    if bad:
        raise SystemExit(f"correctness failures in service bench: {bad}")
    if check_perf and legs[0]["req_per_s"] <= 0:
        raise SystemExit("service bench measured zero throughput")
    if check_perf and streaming and (
            report["streaming"]["speedup_mean"] < STREAM_SPEEDUP_GATE):
        raise SystemExit(
            "streaming leg: extend-vs-cold speedup "
            f"{report['streaming']['speedup_mean']}x below the "
            f"{STREAM_SPEEDUP_GATE}x gate at extension fraction "
            f"{report['streaming']['extension_fraction']}")
    return report


def telemetry_gate(n_requests: int = N_REQUESTS,
                   out_path: str = "TELEMETRY_gate.json") -> dict:
    """CI gate: spans-mode overhead and off-mode transparency.

    Runs the identical traffic under telemetry ``off`` and ``spans`` with
    routing feedback disabled and the calibration table reset before every
    leg — routing then depends only on the analytical cost model, so any
    fingerprint divergence is caused by telemetry, not by timing-dependent
    EMA feedback. Each mode runs twice interleaved and keeps its best wall
    (min-of-2 rejects one-off scheduler hiccups); the spans wall must stay
    within ``GATE_OVERHEAD_FRACTION`` of the off wall plus an absolute
    floor, and routing + answers must be bit-identical across modes."""
    from repro.dp import autotune

    def leg(mode_name: str) -> dict:
        autotune.reset()
        return _measure(n_requests, telemetry_mode=mode_name,
                        feedback=False)

    runs = {"off": [], "spans": []}
    for _ in range(2):
        for mode_name in ("off", "spans"):
            runs[mode_name].append(leg(mode_name))

    best = {m: min(rs, key=lambda r: r["wall_s"]) for m, rs in runs.items()}
    fp_off = [r["fingerprint"] for r in runs["off"]]
    fp_spans = [r["fingerprint"] for r in runs["spans"]]
    identical = all(fp == fp_off[0] for fp in fp_off + fp_spans)
    wall_off, wall_spans = best["off"]["wall_s"], best["spans"]["wall_s"]
    budget = wall_off * (1.0 + GATE_OVERHEAD_FRACTION) + GATE_ABS_FLOOR_S
    overhead = (wall_spans - wall_off) / max(wall_off, 1e-9)
    report = {
        "n_requests": n_requests,
        "wall_off_s": wall_off,
        "wall_spans_s": wall_spans,
        "overhead_fraction": round(overhead, 4),
        "budget_s": round(budget, 4),
        "fingerprints_identical": identical,
        "legs": {m: rs for m, rs in runs.items()},
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
        print(f"# wrote {os.path.abspath(out_path)}")
    print(f"telemetry-gate,off={wall_off}s,spans={wall_spans}s,"
          f"overhead={overhead:+.1%},identical={int(identical)}")
    if not identical:
        raise SystemExit(
            "telemetry gate: routing/answers differ between "
            f"REPRO_TELEMETRY=off and spans:\noff:   {fp_off}\n"
            f"spans: {fp_spans}")
    if wall_spans > budget:
        raise SystemExit(
            f"telemetry gate: spans-mode wall {wall_spans:.3f}s exceeds "
            f"budget {budget:.3f}s (off {wall_off:.3f}s + "
            f"{GATE_OVERHEAD_FRACTION:.0%} + {GATE_ABS_FLOOR_S}s floor)")
    return report


if __name__ == "__main__":
    import argparse

    from repro.jax_cache import use_persistent_cache

    use_persistent_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=N_REQUESTS)
    ap.add_argument("--no-streaming", action="store_true",
                    help="skip the append-heavy streaming-session leg")
    ap.add_argument("--telemetry-gate", action="store_true",
                    help="run the off-vs-spans overhead/transparency gate "
                         "instead of the throughput legs")
    args = ap.parse_args()
    if args.telemetry_gate:
        telemetry_gate(args.requests)
    else:
        run(n_requests=args.requests, streaming=not args.no_streaming)
