"""The stream feed's phase metric ``feed.wait_ms`` and the trace
reduction beside the program's ``parsa.feed.*`` host spans."""
import pathlib

import pytest

from bench import harness, trace
from bench.tests import tiny
from bench.tests.test_trace import _TEXT, DATA, _load

METRICS = pathlib.Path(__file__).resolve().parents[1] / "metrics"
def _reader(name):
    return harness._module(METRICS / f"{name}.py", "bench_metric").read


def _feeds(*timings):
    return {"kind": "stream", "feeds": [{"rows": 8, "timings": t, "end": 0}
                                        for t in timings]}


def test_wait_metric_reads_the_mean_phase():
    run = _feeds({"upload": 0.002, "wait": 0.010},
                 {"upload": 0.004, "wait": 0.030})
    assert _reader("feed.wait_ms")(run) == pytest.approx(20.0)


@pytest.mark.parametrize("timings", [
    # a program that times pack and partition_u alone
    {"pack": 0.1, "partition_u": 0.5, "metrics": 0.01, "total": 0.7},
    # an Alg 4 feed: upload, launch and wait in one scan phase
    {"pack": 0.1, "scan": 0.4, "append": 0.1, "partition_u": 0.5,
     "metrics": 0.01, "total": 0.7}])
def test_wait_metric_silent_without_the_phase(timings):
    read = _reader("feed.wait_ms")
    assert read(_feeds(timings)) is None
    assert read({"kind": "stream", "feeds": []}) is None


def _summary(s):
    return (s.window_s, s.busy_s, s.devices, s.programs, s.ops, s.launches,
            sum(s.gaps.values()))


def test_program_spans_leave_the_device_numbers_alone():
    """A ``parsa.feed.upload`` span inside ``bench.feed`` moves none of
    the device numbers, nor the idle total the gaps add up to."""
    text = _TEXT.replace(
        "duration_ps: 5000000 } }",
        "duration_ps: 5000000 }\n"
        "    events { metadata_id: 3 offset_ps: 2000000 "
        "duration_ps: 2000000 } }").replace(
        'value { id: 2 name: "bench.feed" } }',
        'value { id: 2 name: "bench.feed" } }\n'
        '  event_metadata { key: 3 value { id: 3 name: '
        '"parsa.feed.upload" } }')
    profile = _load(text)
    assert "parsa.feed.upload" in {ev.name for pl in profile.planes
                                   for ln in pl.lines for ev in ln.events}
    with_span = trace.reduce_trace(profile)
    without = trace.reduce_trace(_load(_TEXT))
    assert _summary(with_span) == _summary(without)


# what each recorded trace reduced to when it was recorded
RECORDED = {
    "tiny.stream": (0.012, 0.004617601, 2, 282,
                    {"jit__partition_scan(4842753950295018539)": 1,
                     "jit_body(9305428830533125184)": 1}),
    "tiny.serve": (0.2, 0.003435961, 2, 24,
                   {"jit__serve_step(2670373756716732419)": 14,
                    "jit__serve_step(8931403989767255348)": 20}),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_traces_reduce_as_recorded(name):
    window_s, busy_s, programs, ops, launches = RECORDED[name]
    s = trace.reduce_trace(_load((DATA / f"{name}.pbtxt").read_text()))
    assert s.window_s == pytest.approx(window_s, rel=1e-9)
    assert s.busy_s == pytest.approx(busy_s, rel=1e-9)
    assert (len(s.programs), len(s.ops)) == (programs, ops)
    assert s.launches == launches


def test_traced_tiny_stream_reads_the_wait_metric(tmp_path):
    root = tiny.make_root(tmp_path / "root")
    line, _ = harness.run_cell("tiny.ctr_stream", 2**31 + 29, 0.5, True,
                               root=root, accelerator=False)
    assert line["correct"]
    assert line["metrics"]["feed.wait_ms"]["value"] > 0
    assert line["metrics"]["feed.wait_ms"]["unit"] == "ms"
    assert "feed.upload_ms" not in line["metrics"]
