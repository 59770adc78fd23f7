"""Device time of Alg 4's cross-chip merge per feed, from the profiler's
trace: the seconds of the collective operations of
``_parallel_partition_scan`` inside the traced window (the all-gather of
the server sets and the ``psum`` all-reduces; ``bench.peaks``), averaged
over the chips and over the window's feeds, in milliseconds.  Silent
where no collective ran, as on one chip.  Moves ``partition_rate``."""
from bench import peaks


def read(run):
    feeds, trace = run.get("feeds"), run.get("trace")
    if not feeds or trace is None or trace.devices <= 0:
        return None
    secs = peaks.merge_seconds(trace)
    if secs <= 0:
        return None
    return 1e3 * secs / trace.devices / len(feeds)
