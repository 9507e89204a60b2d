"""Service admission: share of the window the client spent inside
``DPService.submit`` (encode, digest, answer cache, backlog), in percent.
The harness's own span around each call, on the host clock."""


def read(run):
    spans = run.window.spans.get("submit", [])
    if not spans:
        return None
    return 100.0 * sum(b - a for a, b in spans) / run.window_s
