"""Reduce the program's own host spans in one profiler trace.

``repro.dp.telemetry.trace_span`` writes spans named ``dp.*`` (``dp.submit``,
``dp.encode``, ``dp.digest``, ``dp.step``, ``dp.drain``, ``dp.stack``,
``dp.launch``, ``dp.fetch``, ``dp.traceback``, ``dp.decode``, ...) while the
profiler captures. This reduction reads them beside ``trace_reduce``, which
it leaves as it is, inside the harness's ``window`` span:

- per span name: the union of its intervals, the number of its spans that
  start inside the window, and the sum of each numeric arg of those
  (``h2d_bytes`` of ``dp.stack``, ``compiles`` of ``dp.launch``, ...);
- device idle time charged to the innermost ``dp.*`` span open on the host
  at that instant, or to ``other`` where none is (ns, mean over devices).

A program without such spans gives empty fields. Keep a traced run's trace
and reduce it:

    python3 bench/run.py --workload gotoh.batch --seed 1 --seconds 51 \\
        --trace 1 --keep-trace
    python3 bench/span_reduce.py .bench_trace
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import trace_reduce as tr

#: name prefix of the program's own spans
PREFIX = "dp."
#: the spans that nest in one call into the service, by that call
FAMILIES = {
    "submit": ("dp.submit", "dp.encode", "dp.digest"),
    "step": ("dp.step", "dp.admit", "dp.drain", "dp.stack", "dp.launch",
             "dp.fetch", "dp.traceback", "dp.decode", "dp.extract",
             "dp.resolve"),
}


@dataclasses.dataclass
class SpanReduction:
    window_ns: float
    #: span name -> host ns of the union of its spans inside the window
    ns: dict
    #: span name -> number of its spans that start inside the window
    count: dict
    #: span name -> {arg: sum over its spans that start inside the
    #: window}, numeric args only
    args: dict
    #: innermost open span name (or "other") -> idle device ns, mean over
    #: devices; empty when the trace has no ``dp.*`` span
    idle_ns: dict

    def idle_share(self, *names):
        """Percent of the window the device sat idle while one of ``names``
        was the innermost ``dp.*`` span open on the host; ``None`` when
        the trace has no ``dp.*`` span."""
        if not self.idle_ns:
            return None
        return 100.0 * sum(self.idle_ns.get(n, 0.0)
                           for n in names) / self.window_ns


def _innermost(events):
    """Disjoint ``(a, b, name)`` pieces of the time covered by ``events``
    (``(start, end, name)``), each named after the innermost event open in
    it: the one that started last. Spans on one thread nest; an event that
    outlives the one it started in is cut at that one's end."""
    pieces, stack, t = [], [], None
    for a, b, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= a:
            end, inner = stack.pop()
            if end > t:
                pieces.append((t, end, inner))
                t = end
        if stack and a > t:
            pieces.append((t, a, stack[-1][1]))
        t = a
        stack.append((min(b, stack[-1][0]) if stack else b, name))
    while stack:
        end, inner = stack.pop()
        if end > t:
            pieces.append((t, end, inner))
            t = end
    return pieces


def reduce(profile) -> SpanReduction:
    """``profile``: a ``jax.profiler.ProfileData`` or a path to an
    ``.xplane.pb``."""
    if isinstance(profile, str):
        import jax

        profile = jax.profiler.ProfileData.from_file(profile)
    windows, events, busy = [], [], {}
    for plane in profile.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == tr.OPS_LINE:
                    busy[plane.name] = tr._merge(
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tr.WINDOW:
                        windows.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(PREFIX):
                        events.append((e.start_ns, e.start_ns + e.duration_ns,
                                       e.name, list(e.stats)))
    if not windows:
        raise ValueError(f"the trace has no {tr.WINDOW!r} span")
    if not busy:
        raise ValueError("the trace has no device plane with XLA Ops")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    pieces = {}
    for a, b, name in _innermost([ev[:3] for ev in events]):
        pieces.setdefault(name, []).append([a, b])
    idle_ns = {name: 0.0 for name in (*pieces, "other")} if events else {}
    for merged in busy.values() if events else ():
        gaps = tr._complement(merged, lo, hi)
        charged = 0.0
        for name, iv in pieces.items():
            ns = tr._intersection(gaps, iv)
            idle_ns[name] += ns / len(busy)
            charged += ns
        idle_ns["other"] += (sum(b - a for a, b in gaps) - charged) / len(busy)
    by_name, count, args = {}, {}, {}
    for a, b, name, stats in events:
        by_name.setdefault(name, []).append((a, b))
        if not lo <= a < hi:
            continue
        count[name] = count.get(name, 0) + 1
        sums = args.setdefault(name, {})
        for key, value in stats:
            if isinstance(value, (int, float)):
                sums[key] = sums.get(key, 0) + value
    ns = {name: tr._overlap(tr._merge(iv), lo, hi)
          for name, iv in by_name.items()}
    return SpanReduction(window_ns=hi - lo, ns=ns, count=count, args=args,
                         idle_ns=idle_ns)


def summary(red: SpanReduction) -> dict:
    """Device idle by innermost span and by family, in percent of the
    window, beside each span's host seconds, count and arg sums."""
    return {
        "window_s": red.window_ns / 1e9,
        "idle_pct": {k: red.idle_share(k) for k in sorted(red.idle_ns)},
        "family_idle_pct": {f: red.idle_share(*names)
                            for f, names in FAMILIES.items()},
        "spans": {k: {"host_s": red.ns[k] / 1e9, "count": red.count.get(k, 0),
                      "args": red.args.get(k, {})} for k in sorted(red.ns)},
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    print(json.dumps(summary(reduce(path)), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
