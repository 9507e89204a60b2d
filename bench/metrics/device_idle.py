"""Device idle share of the traced window: 1 - the union of the device's
``XLA Ops`` intervals over the window, in percent; the mean over devices."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
