"""A kernel's share of its roofline from the trace and ``bench/work``.

The least time for the requests a route answered inside the window (their
operations at the VPU's peak or their bytes at HBM's, whichever is longer)
over the device time of the kernel's custom calls in the trace, summed over
devices. ``None`` when the route answered nothing or the trace holds no
such call.
"""


def share(run, routes, events):
    if run.trace is None:
        return None
    reqs = [r for r in run.completed() if r.result.backend in routes]
    kernel_ns = run.trace.kernel_ns(events)
    if not reqs or kernel_ns <= 0:
        return None
    return 100.0 * run.least_s(reqs) / (kernel_ns / 1e9)
