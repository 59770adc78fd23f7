"""Time a request waits on its pull (``PullHandle.block``: the modeled
wire time still outstanding, then the buffer's transfer): the mean
``blocked_s`` over the traced window's requests, in milliseconds.  Moves
``serve_p95_ms``."""


def read(run):
    reqs = run.get("requests")
    if not reqs:
        return None
    return 1e3 * sum(r["blocked_s"] for r in reqs) / len(reqs)
