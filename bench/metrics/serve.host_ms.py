"""The PS host path per request (pull planning and issue, the request's
batch, push metering and commit): the window's time per request less the
mean serve step and the mean pull wait, in milliseconds.  Moves
``serve_rate``."""


def read(run):
    reqs = run.get("requests")
    if not reqs or run.get("window_s", 0) <= 0:
        return None
    n = len(reqs)
    return 1e3 * (run["window_s"] / n
                  - sum(r["compute_s"] for r in reqs) / n
                  - sum(r["blocked_s"] for r in reqs) / n)
