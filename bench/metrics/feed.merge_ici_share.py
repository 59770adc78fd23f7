"""Alg 4's merge against its roofline, the chip-to-chip interconnect, in
percent: the bytes each chip receives in the window's all-gathers
(``bench.peaks.merge_bytes`` of the feeds' ``merge_rounds`` counters)
over the merge's device seconds per chip (``bench.peaks.merge_seconds``
over the chips) times the device's published ICI bytes/s.  Silent where
the feeds count no merge rounds or the trace holds no collective.
Moves ``partition_rate``."""
from bench import peaks


def read(run):
    feeds, trace, mesh = run.get("feeds"), run.get("trace"), run.get("mesh")
    if (not feeds or trace is None or mesh is None or trace.devices <= 0
            or any("merge_rounds" not in f.get("counters", {})
                   for f in feeds)):
        return None
    secs = peaks.merge_seconds(trace) / trace.devices
    if secs <= 0:
        return None
    rounds = sum(f["counters"]["merge_rounds"] for f in feeds)
    nbytes = peaks.merge_bytes(rounds, mesh["workers"], mesh["k"],
                               mesh["words"])
    return 100.0 * nbytes / (
        secs * peaks.peak(mesh["device_kind"], "ici_bytes_per_s"))
