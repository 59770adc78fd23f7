"""Device time of the partition scan per feed, from the profiler's
trace: the seconds of every launch of the ``_partition_scan`` program
inside the traced window, over the window's feeds, in milliseconds.
Moves ``partition_rate``."""


def read(run):
    feeds, trace = run.get("feeds"), run.get("trace")
    if not feeds or trace is None:
        return None
    secs = sum(v for name, v in trace.programs.items()
               if "_partition_scan" in name)
    if secs <= 0:
        return None
    return 1e3 * secs / len(feeds)
