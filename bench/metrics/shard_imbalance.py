"""Balance of the mesh: the spread of the devices' busy time inside the
traced window, ``100 * (max - min) / max`` over the per-device union of
``XLA Ops`` intervals, in percent. 0 when every device is as busy as the
busiest; ``None`` on fewer than two devices."""


def read(run):
    if run.trace is None or len(run.trace.busy_ns) < 2:
        return None
    busy = run.trace.busy_ns.values()
    most = max(busy)
    if most <= 0:
        return None
    return 100.0 * (most - min(busy)) / most
