"""Reduce one profiler trace (``.xplane.pb``) to the benchmark's numbers.

The traced run wraps its measured window in a host span named ``window``
and the calls into each layer in spans of their own (``submit``, ``step``,
``generate``, ``poll``), written with ``jax.profiler.TraceAnnotation``. On a
TPU the profiler puts host and device events on one clock, so:

- device busy time is the union of the intervals of the device's
  ``XLA Ops`` events inside the window (per device; the reduction reports
  the mean over devices);
- a kernel's device time is the sum of the durations of its custom-call
  events, found by a pattern on the op's name (the text before `` = `` in
  the event name, e.g. ``%vmap_jit_grid_pipeline_pallas__.2``);
- each idle stretch of a device is charged to the innermost harness span
  open at that time on the host, or to ``other`` where none is.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "window"
SPANS = ("submit", "step", "generate", "poll")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduction:
    window_ns: float
    devices: list
    #: device -> busy ns inside the window
    busy_ns: dict
    #: short op name -> device ns inside the window, summed over devices
    op_ns: dict
    #: (short op name, ns) of every custom call inside the window
    custom_calls: list
    #: span name -> host ns inside the window
    span_ns: dict
    #: span name (or "other") -> idle device ns, mean over devices
    idle_ns: dict

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def kernel_ns(self, patterns) -> float:
        """Device ns of the custom calls whose short name matches any of
        ``patterns`` (regular expressions), summed over devices."""
        rx = [re.compile(p) for p in patterns]
        return sum(ns for name, ns in self.custom_calls
                   if any(r.search(name) for r in rx))


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def short_name(event_name: str) -> str:
    """``%vmap_jit_grid_pipeline_pallas__.2 = f32[...] custom-call(...)`` ->
    ``vmap_jit_grid_pipeline_pallas__``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _overlap(intervals, lo, hi) -> float:
    """Length of the merged ``intervals`` inside ``[lo, hi]``."""
    total = 0.0
    for a, b in intervals:
        a, b = _clip(a, b, lo, hi)
        if b > a:
            total += b - a
    return total


def _intersection(a, b) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _complement(merged, lo, hi):
    gaps, cur = [], lo
    for a, b in merged:
        a, b = _clip(a, b, lo, hi)
        if b <= a:
            continue
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    return gaps


def reduce(profile) -> Reduction:
    """``profile``: a ``jax.profiler.ProfileData`` or a path to an
    ``.xplane.pb``."""
    if isinstance(profile, str):
        import jax

        profile = jax.profiler.ProfileData.from_file(profile)
    spans = {name: [] for name in SPANS}
    windows = []
    device_events = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events[plane.name] = [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        windows.append((e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name in spans:
                        spans[e.name].append((e.start_ns,
                                              e.start_ns + e.duration_ns))
    if not windows:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    if not device_events:
        raise ValueError("the trace has no device plane with XLA Ops")
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    span_ns = {name: _overlap(_merge(iv), lo, hi) for name, iv in spans.items()}
    busy_ns, op_ns, custom_calls = {}, {}, []
    idle_ns = {name: 0.0 for name in (*SPANS, "other")}
    ndev = len(device_events)
    for dev, events in sorted(device_events.items()):
        busy = _merge((a, b) for a, b, _ in events)
        busy_ns[dev] = _overlap(busy, lo, hi)
        for a, b, name in events:
            a, b = _clip(a, b, lo, hi)
            if b <= a:
                continue
            short = short_name(name)
            op_ns[short] = op_ns.get(short, 0.0) + (b - a)
            if "custom-call(" in name:
                custom_calls.append((short, b - a))
        gaps = _complement(busy, lo, hi)
        charged = 0.0
        for sname in SPANS:
            ns = _intersection(gaps, _merge(spans[sname]))
            idle_ns[sname] += ns / ndev
            charged += ns
        idle_ns["other"] += (sum(b - a for a, b in gaps) - charged) / ndev
    return Reduction(window_ns=hi - lo, devices=sorted(device_events),
                     busy_ns=busy_ns, op_ns=op_ns, custom_calls=custom_calls,
                     span_ns=span_ns, idle_ns=idle_ns)


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The trace's ``breakdown`` entry: the device ops that took most time,
    and device idle time by the harness span open on the host, in seconds."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(((k, v) for k, v in red.idle_ns.items() if v > 0),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[k, v / 1e9] for k, v in idle]}
