"""Alignment matrix cells (len(x) * len(y)) of the requests answered inside
the window, per second of the window, in billions."""


def read(run):
    done = [r for r in run.completed() if len(r.shape) == 2]
    if not done:
        return None
    return sum(r.shape[0] * r.shape[1] for r in done) / run.window_s / 1e9
