"""Share of the traced window in which no operation ran on the device,
for the serving cells, in percent.  Moves ``serve_rate``."""


def read(run):
    trace = run.get("trace")
    if run.get("kind") != "serve" or trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
