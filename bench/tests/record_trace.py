"""Record the small device trace that ``test_trace.py`` reads.

    python bench/tests/record_trace.py <out_dir>

Runs the tiny stream and serve cells once each with the profiler on, on
the chip this process holds, and copies each raw ``.xplane.pb`` to
``<out_dir>/tiny.<driver>.xplane.pb``.
"""
import json
import pathlib
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parents[1]))

from bench import harness  # noqa: E402
from bench.tests import tiny  # noqa: E402


def main(out: str) -> None:
    out = pathlib.Path(out)
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=out) as tmp:
        root = tiny.make_root(pathlib.Path(tmp) / "root")
        for cell, driver in (("tiny.ctr_stream", "stream"),
                             ("tiny.ctr_serve", "serve")):
            line, _ = harness.run_cell(
                cell, 7, 1.0, True, root=root,
                keep_trace=str(out / f"tiny.{driver}.xplane.pb"))
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])


def shrink(xplane: str, out: str, keep_ms: float) -> None:
    """Write the first ``keep_ms`` of a recorded trace's window as a text
    XSpace (``.pbtxt``): the device planes' module and op lines and the
    host's ``bench.`` spans, with the window span cut to that length and
    each op named by its HLO instruction alone."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane)
    spans = [(ev.start_ns, ev.duration_ns) for pl in pd.planes
             for ln in pl.lines for ev in ln.events
             if ev.name == "bench.window"]
    lo = spans[0][0]
    hi = lo + keep_ms * 1e6
    planes = []
    for pid, pl in enumerate(pd.planes, 1):
        device = pl.name.startswith("/device:TPU:")
        names: dict = {}
        lines = []
        for lid, ln in enumerate(pl.lines, 1):
            if device and ln.name not in ("XLA Modules", "XLA Ops"):
                continue
            evs = []
            for ev in ln.events:
                name = ev.name
                if not device and not name.startswith("bench."):
                    continue
                s, d = ev.start_ns, ev.duration_ns
                if name == "bench.window":
                    s, d = lo, hi - lo
                elif s < lo or s + d > hi:
                    continue
                name = name.split(" = ")[0] if device else name
                mid = names.setdefault(name, len(names) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round((s - lo) * 1000))} duration_ps: "
                           f"{int(round(d * 1000))} }}")
            if evs:
                lines.append(f'lines {{ id: {lid} name: "{ln.name}" '
                             f"timestamp_ns: {int(lo)}\n    "
                             + "\n    ".join(evs) + " }")
        if not lines:
            continue
        meta = [f'event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"{n}" }} }}' for n, i in names.items()]
        planes.append(f'planes {{\n  id: {pid}\n  name: "{pl.name}"\n  '
                      + "\n  ".join(lines + meta) + "\n}")
    pathlib.Path(out).write_text("\n".join(planes) + "\n")
