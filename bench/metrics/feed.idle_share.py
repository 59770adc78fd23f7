"""Share of the traced window in which no operation ran on the device
(averaged over the chips used), for the stream cells, in percent.
Moves ``partition_rate``."""


def read(run):
    trace = run.get("trace")
    if run.get("kind") != "stream" or trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
