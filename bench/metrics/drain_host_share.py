"""Engine and batch programs on the host: share of the traced window spent
inside the harness's ``step`` span while no operation ran on the device
(admission, stacking, dispatch, host copies, traceback decode), in percent;
the mean over devices."""


def read(run):
    if run.trace is None or run.trace.span_ns.get("step", 0) == 0:
        return None
    return 100.0 * run.trace.idle_ns["step"] / run.trace.window_ns
