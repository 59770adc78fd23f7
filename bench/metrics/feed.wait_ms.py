"""Host blocked on the partition scan per feed, from the feed's own
``StreamUpdate.timings["wait"]`` (the program's ``parsa.feed.wait``
phase, the read-back of the scan's parts): the mean over the traced
window's feeds, in milliseconds.  It holds both the host-to-device copy
of the packed blocks, which runs after ``jnp.asarray`` returns, and the
device scan.  Silent where the program does not time that phase.  Moves
``partition_rate``."""


def read(run):
    feeds = run.get("feeds")
    if not feeds or any("wait" not in f["timings"] for f in feeds):
        return None
    return 1e3 * sum(f["timings"]["wait"] for f in feeds) / len(feeds)
