"""The serve step (``_serve_step``) per request on the host clock,
fenced by ``block_until_ready``: the mean ``compute_s`` over the traced
window's requests, in milliseconds.  Moves ``serve_rate``."""


def read(run):
    reqs = run.get("requests")
    if not reqs:
        return None
    return 1e3 * sum(r["compute_s"] for r in reqs) / len(reqs)
