"""Each driver end to end at a tiny size on the CPU: a run is correct,
reports its cell's metrics, and a cell made of new files alone runs."""
import hashlib
import json
import pathlib

import pytest

from bench import harness
from bench.tests import tiny

REPO = tiny.REPO


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench") / "root")


def _run(root, cell, trace=False, seed=2**31 + 11, seconds=0.5):
    return harness.run_cell(cell, seed, seconds, trace, root=root,
                            accelerator=False)


@pytest.mark.parametrize("cell", sorted(tiny.TINY_CELLS))
def test_untraced_run_is_correct(root, monkeypatch, cell):
    if "serve" in cell:
        tiny.fix_commit(monkeypatch)
    line, checks = _run(root, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    spec = json.loads((root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["tiny.ctr_stream", "tiny.ctr_serve"])
def test_traced_run_reads_host_metrics(root, monkeypatch, cell):
    """On the CPU no device plane exists, so the device-trace metrics stay
    silent and the host-side ones are read."""
    if "serve" in cell:
        tiny.fix_commit(monkeypatch)
    line, _ = _run(root, cell, trace=True)
    assert line["correct"]
    assert "window_s" in line["device"] and "busy_s" in line["device"]
    host = ({"feed.pack_ms"} if "stream" in cell else
            {"serve.host_ms", "serve.compute_ms", "serve.blocked_ms"})
    assert host <= set(line["metrics"])
    assert "feed.scan_device_ms" not in line["metrics"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _digest(paths):
    return {str(p): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in paths}


def test_new_cell_from_new_files_only(tmp_path):
    """Adding a configuration, a traffic mix and a ``workloads`` entry is
    enough: every file the benchmark already has stays byte for byte."""
    existing = [p for p in (REPO / "bench").rglob("*")
                if p.is_file() and "__pycache__" not in p.parts
                and "tests" not in p.parts]
    root = tiny.make_root(tmp_path / "root")
    before = _digest(root / p.relative_to(REPO) for p in existing)
    line, _ = _run(root, "tiny.social_stream")
    assert line["correct"]
    after = _digest(root / p.relative_to(REPO) for p in existing)
    assert before == after
    assert before == {str(root / p.relative_to(REPO)): d for p, d in
                      ((p, hashlib.sha256(p.read_bytes()).hexdigest())
                       for p in existing)}
