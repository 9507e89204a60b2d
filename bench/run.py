#!/usr/bin/env python3
"""Benchmark of the DP-solving service on the chip: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's root;
its configuration, traffic mix and metrics are files under ``bench/`` found
by their names (see ``PERF.md``). One process:

1. refuses to run on anything but a TPU with the DP kernels in ``pallas``
   mode and at least the cell's chips (exit 3, no result);
2. builds ``DPService(feedback=False)`` (routes from the analytical cost
   model, so two runs of one tree take the same routes) and warms up every
   shape the mix uses through that same service, with instances of their
   own from the seed; this and everything before it is ``setup_s``;
3. measures: the mix's closed-loop client drives ``submit``/``step``/``poll``
   for ``--seconds``, closing the window at the end of the round (or the
   callers' step) that crosses it; with ``--trace 1`` under the profiler;
4. reads the peak device memory, drains what is still open, and compares
   every answer of the window with the float64 reference under
   ``bench/reference`` (and every decoded solution with its recomputed
   cost);
5. prints information lines, then the compared numbers with their limits
   on standard error, then one JSON line on standard output.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import loadgen  # noqa: E402
import trace_reduce  # noqa: E402
from loadgen import load_module  # noqa: E402

#: seconds past the window's close that a late answer is waited for
GRACE_S = 60.0
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoAccelerator(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def lookup(bench: dict, name: str) -> tuple:
    """``(cell, config, traffic)`` of workload ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def cell_metrics(bench: dict, cell: str, traced: bool) -> list:
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(run, name: str):
    """A metric's value from its reader ``bench/metrics/<base>.py``, where
    ``<base>`` is the name up to its first dot; ``None`` when the reader
    finds nothing to read."""
    base = name.split(".", 1)[0]
    return load_module(os.path.join(HERE, "metrics", base + ".py")).read(run)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def require_tpu(chips: int) -> list:
    """The devices the cell runs on; anything but a TPU in ``pallas`` mode
    with at least ``chips`` devices raises :class:`NoAccelerator`."""
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX runs on {devices[0].platform}")
    if ops.kernel_mode() != "pallas":
        raise NoAccelerator(f"kernel mode is {ops.kernel_mode()!r}, not "
                            "'pallas'")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devices)}")
    return devices[:chips]


def make_service(config: dict, devices: list):
    import jax

    from repro import dp

    lanes = config["service"]["lanes_per_device"]
    # the default mesh="auto" spans every visible device; a one-chip cell
    # on a larger host stays on its own device
    mesh = "auto" if len(devices) == len(jax.devices()) else None
    return dp.DPService(max_batch=lanes * len(devices), feedback=False,
                        mesh=mesh)


class CompileWatch:
    """JAX compile events (``jax.monitoring``): count and seconds by name."""

    def __init__(self):
        import jax

        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if "compil" in event or "trace" in event:
            self.events.append((loadgen.clock(), event, duration))

    def between(self, t0: float, t1: float) -> dict:
        out = {}
        for t, ev, secs in self.events:
            if t0 <= t <= t1:
                n, total = out.get(ev, (0, 0.0))
                out[ev] = (n + 1, round(total + secs, 6))
        return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def check(reqs: list, config: dict) -> tuple:
    """Compare every request with the float64 reference. Returns ``(checks,
    failed)``: each compared number with its limit, and how many requests
    failed any of them.

    - ``unanswered``: requests whose result never came back ``done``;
    - ``invalid_solutions``: decoded solutions that are not solutions of
      their instance (``reference.solutions``);
    - ``value_gap``: the widest gap between a served answer, or the
      recomputed cost of a decoded solution, and the reference's optimum.
    """
    ref_mod = load_module(os.path.join(HERE, "reference",
                                       config["problem"] + ".py"))
    from reference.solutions import InvalidSolution

    limit = config["limits"]["value_gap"]
    groups = {}
    for r in reqs:
        groups.setdefault(r.shape, []).append(r)
    unanswered = invalid = failed = 0
    value_gap = 0.0
    for group in groups.values():
        ref = ref_mod.answers([r.payload for r in group])
        for r, want in zip(group, np.asarray(ref, dtype=np.float64).tolist()):
            res = r.result
            if res is None or res.status != "done":
                unanswered += 1
                failed += 1
                continue
            gap = abs(float(res.answer) - want)
            if r.reconstruct:
                try:
                    cost = ref_mod.solution_cost(r.payload,
                                                 res.solution.solution)
                    gap = max(gap, abs(cost - want))
                except (InvalidSolution, AttributeError, KeyError,
                        TypeError, IndexError) as e:
                    print(f"# request {r.tid}: invalid solution ({e!r})",
                          file=sys.stderr)
                    invalid += 1
                    failed += 1
                    continue
            value_gap = max(value_gap, gap)
            failed += not gap <= limit
    checks = {"unanswered": {"value": unanswered, "limit": 0},
              "invalid_solutions": {"value": invalid, "limit": 0},
              "value_gap": {"value": value_gap, "limit": limit}}
    return checks, failed


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
class RunData:
    """What the metric readers read."""

    def __init__(self, window, setup_s, trace, peaks, work):
        self.window = window
        self.setup_s = setup_s
        self.trace = trace
        self.peaks = peaks
        self._work = work

    @property
    def window_s(self) -> float:
        return self.window.seconds

    def completed(self) -> list:
        return self.window.completed()

    def work(self, reqs) -> tuple:
        """Total ``(ops, bytes)`` of ``reqs``."""
        ops = nbytes = 0
        for r in reqs:
            o, b = self._work.count(r.shape, r.reconstruct)
            ops, nbytes = ops + o, nbytes + b
        return ops, nbytes

    def least_s(self, reqs) -> float:
        """The least time the chip could take for ``reqs``: the larger of
        their operations at the VPU's peak and their bytes at HBM's."""
        ops, nbytes = self.work(reqs)
        return max(ops / self.peaks["vpu_ops_per_s"]["value"],
                   nbytes / self.peaks["hbm_bytes_per_s"]["value"])


def measure(config: dict, traffic: dict, seed: int,
            seconds: float, traced: bool, devices: list, metrics: list,
            t_process: float = T_PROCESS, grace_s: float = GRACE_S,
            trace_dir: str = TRACE_DIR, keep_trace: bool = False,
            peaks: dict = None) -> dict:
    """Set up, warm up, measure and check one cell; returns the result
    line's object. Needs no particular platform: ``require_tpu`` is the
    caller's business."""
    import jax

    from repro.dp import backends

    kind = devices[0].device_kind
    if peaks is None:
        table = load_json(os.path.join(HERE, "peaks.json"))
        if kind not in table:
            raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
        peaks = table[kind]
    work = load_module(os.path.join(HERE, "work", config["problem"] + ".py"))
    watch = CompileWatch()
    svc = make_service(config, devices)
    warm = loadgen.Client(svc, loadgen.Generator(
        config, traffic, np.random.default_rng([seed, 1])))
    warm.run(math.inf, units=traffic["warmup_units"])
    warm.drain(grace_s)
    annotate = jax.profiler.TraceAnnotation if traced else None
    client = loadgen.Client(svc, loadgen.Generator(
        config, traffic, np.random.default_rng([seed, 0])), annotate)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    traces_before = backends.TRACE_COUNT
    win = client.measure(seconds)
    traces_in = backends.TRACE_COUNT - traces_before
    if traced:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    client.drain(grace_s)
    setup_s = win.t0 - t_process

    # -- information lines -------------------------------------------------
    lanes = [n for _, _, n in win.steps if n]
    print(f"# routes: {dict(sorted(((p + '/' + b), n) for (p, b), n in svc.routes.items()))}")
    print(f"# window {win.seconds:.6f} s: {len(win.reqs)} submitted, "
          f"{len(win.completed())} answered inside it, {len(win.steps)} "
          f"steps, {len(lanes)} drains, lanes per drain "
          f"{sorted(set(lanes))}")
    print(f"# compile events in set-up: {watch.between(0.0, win.t0)}")
    print(f"# compiles inside the window: TRACE_COUNT delta {traces_in}, "
          f"jax events {watch.between(win.t0, win.t_end)}")
    client_s = sum(b - a for name in ("generate", "poll")
                   for a, b in win.spans.get(name, []))
    print(f"# client (closed loop, no schedule to run late against): "
          f"{client_s:.6f} s generating and polling in the window")

    reduction = None
    if traced:
        t = loadgen.clock()
        reduction = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"# trace reduced in {loadgen.clock() - t:.3f} s: window "
              f"{reduction.window_s:.6f} s, device busy "
              f"{reduction.busy_s:.6f} s on {reduction.devices}")

    t = loadgen.clock()
    checks, failed = check(win.reqs, config)
    print(f"# reference check of {len(win.reqs)} requests took "
          f"{loadgen.clock() - t:.3f} s")

    run = RunData(win, setup_s, reduction, peaks, work)
    values = {}
    for m in metrics:
        v = read_metric(run, m["name"])
        if v is not None and math.isfinite(v):
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": passed(checks), "attempted": len(win.reqs),
           "failed": int(failed), "metrics": values, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        out["breakdown"] = trace_reduce.breakdown(reduction)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help=f"leave the profile under {TRACE_DIR}")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = lookup(bench, args.workload)
    # the compilation cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.jax_cache import use_persistent_cache

    print(f"# compilation cache: {use_persistent_cache()}")
    try:
        devices = require_tpu(cell["chips"])
    except NoAccelerator as e:
        print(f"refusing to run: {e}", file=sys.stderr)
        return 3
    print(f"# device: {devices[0].platform} {devices[0].device_kind!r} x "
          f"{len(devices)}")
    out = measure(config, traffic, args.seed, args.seconds,
                  bool(args.trace), devices,
                  cell_metrics(bench, cell["name"], bool(args.trace)),
                  keep_trace=args.keep_trace)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
