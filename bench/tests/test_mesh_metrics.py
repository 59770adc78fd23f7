"""The Alg 4 cell's driver and its merge metrics: the tiny four-device
cell through ``stream_mesh``, and ``feed.merge_ms`` and
``feed.merge_ici_share`` on a hand-made four-chip trace with known
answers and on one-chip records."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import harness, peaks, trace
from bench.tests.test_trace import _TEXT, DATA, _load

HERE = pathlib.Path(__file__).resolve().parent
METRICS = HERE.parent / "metrics"


def _reader(name):
    return harness._module(METRICS / f"{name}.py", "bench_metric").read


def _plane(pid: int) -> str:
    """One chip: a merge round's all-gather (2 us) and psum (1 us) and a
    4 us fusion, inside the 10 us window of ``_TEXT``'s host plane."""
    return f'''
planes {{
  id: {pid}
  name: "/device:TPU:{pid - 10}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 10 offset_ps: 0 duration_ps: 7000000 }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }}
    events {{ metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 }}
    events {{ metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.3" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%all-gather.7" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%psum.17" }} }}
  event_metadata {{ key: 10 value {{ id: 10 name:
    "jit__parallel_partition_scan" }} }}
}}'''


_HOST = _TEXT[_TEXT.index("planes {\n  id: 2"):]
FOUR_CHIPS = "".join(_plane(10 + c) for c in range(4)) + "\n" + _HOST


def _mesh_run(summary, rounds=(3, 3)):
    return {"kind": "stream", "window_s": summary.window_s, "trace": summary,
            "feeds": [{"rows": 8, "end": 0, "timings": {"wait": 0.01},
                       "counters": {"merge_rounds": r}} for r in rounds],
            "mesh": {"device_kind": "TPU v5 lite", "workers": 4, "k": 2,
                     "words": 1000}}


def test_merge_metrics_read_a_four_chip_trace():
    s = trace.reduce_trace(_load(FOUR_CHIPS))
    assert s.devices == 4
    run = _mesh_run(s)
    # 3 us of collectives on each of 4 chips, over 2 feeds
    assert _reader("feed.merge_ms")(run) == pytest.approx(1.5e-3)
    # 6 rounds × 3 peers × (2, 1000) int32 = 144,000 bytes a chip in 3 us
    assert peaks.merge_bytes(6, 4, 2, 1000) == 144_000
    assert _reader("feed.merge_ici_share")(run) == pytest.approx(
        100 * 144_000 / (3e-6 * 200e9))


@pytest.mark.parametrize("text", ["hand-made", "tiny.stream"])
def test_merge_metrics_silent_on_one_chip_records(text):
    s = trace.reduce_trace(_load(
        _TEXT if text == "hand-made"
        else (DATA / f"{text}.pbtxt").read_text()))
    one_chip = {"kind": "stream", "window_s": s.window_s, "trace": s,
                "feeds": [{"rows": 8, "end": 0, "timings": {"wait": 0.01},
                           "counters": {"upload_bytes": 64}}]}
    assert _reader("feed.merge_ms")(one_chip) is None
    assert _reader("feed.merge_ici_share")(one_chip) is None


def test_merge_share_silent_without_merge_counters():
    """A program whose Alg 4 feeds count no merges still has a merge time
    in its trace, but no bytes to set against it."""
    run = _mesh_run(trace.reduce_trace(_load(FOUR_CHIPS)))
    for f in run["feeds"]:
        f["counters"] = {}
    assert _reader("feed.merge_ms")(run) > 0
    assert _reader("feed.merge_ici_share")(run) is None


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu", "ici_bytes_per_s")


def test_tiny_mesh_cell_through_the_driver(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "mesh_cell.py"),
                        str(tmp_path)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = {r["trace"]: r["line"] for r in
             map(json.loads, p.stdout.strip().splitlines())}
    for line in lines.values():
        assert line["correct"], line["checks"]
        assert line["device"]["count"] == 4
    assert lines[False]["metrics"]["partition_rate"]["value"] > 0
    traced = lines[True]["metrics"]
    # the Alg 4 feed times its wait phase; a CPU trace holds no chip, so
    # the merge metrics stay silent
    assert traced["feed.wait_ms"]["value"] > 0
    assert traced["feed.pack_ms"]["value"] > 0
    assert "feed.merge_ms" not in traced
    assert "feed.merge_ici_share" not in traced
