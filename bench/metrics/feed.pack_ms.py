"""Host packing per feed (``pack_graph_blocks``), from the feed's own
``StreamUpdate.timings["pack"]``: the mean over the traced window's
feeds, in milliseconds.  Moves ``partition_rate``."""


def read(run):
    feeds = run.get("feeds")
    if not feeds:
        return None
    return 1e3 * sum(f["timings"]["pack"] for f in feeds) / len(feeds)
