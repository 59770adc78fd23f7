"""The trace reduction on a hand-made trace with known answers, and on
small traces recorded on a TPU v5e by ``record_trace.py`` (the first
milliseconds of each window, kept as text by its ``shrink``)."""
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

# One chip: a 4 us program of two 1 us operations at 1-2 us and 4-5 us,
# inside a 10 us window whose host is in ``bench.feed`` from 1 to 6 us.
_TEXT = '''
planes {
  id: 1
  name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 10 value { id: 10 name: "jit__partition_scan" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.feed" } }
}
'''


def _load(text: str):
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_known_trace():
    s = trace.reduce_trace(_load(_TEXT))
    assert s.window_s == pytest.approx(10e-6)
    assert s.busy_s == pytest.approx(2e-6)
    assert s.devices == 1
    assert s.programs == {"jit__partition_scan": pytest.approx(4e-6)}
    assert s.ops == {"fusion.1": pytest.approx(1e-6),
                     "copy.2": pytest.approx(1e-6)}
    # idle: 0-1 us (window only), 2-4 us (in feed), 5-10 us (window only)
    assert s.gaps == {"host:other": pytest.approx(6e-6),
                      "bench.feed": pytest.approx(2e-6)}
    assert s.spans == {"bench.feed": pytest.approx(5e-6)}
    assert s.top(s.gaps, 1) == [["host:other", pytest.approx(6e-6)]]


def test_union_merges_overlaps():
    import numpy as np

    s, e = trace._union(np.array([0.0, 1.0, 5.0, 2.0]),
                        np.array([2.0, 3.0, 6.0, 2.5]))
    assert s.tolist() == [0.0, 5.0] and e.tolist() == [3.0, 6.0]


@pytest.mark.parametrize("name", ["tiny.stream", "tiny.serve"])
def test_recorded_tpu_trace(name):
    s = trace.reduce_trace(_load((DATA / f"{name}.pbtxt").read_text()))
    assert s.devices == 1
    assert 0 < s.busy_s <= s.window_s
    assert s.programs and s.ops
    idle = s.window_s - s.busy_s
    assert sum(s.gaps.values()) == pytest.approx(idle, rel=1e-6)
    spans = {"bench.feed"} if name == "tiny.stream" else {
        "bench.produce", "bench.block", "bench.compute", "bench.commit"}
    assert spans <= set(s.spans)
    if name == "tiny.stream":
        assert any("_partition_scan" in p for p in s.programs)
