"""Reduce a profiler trace (``.xplane.pb``) to what the benchmark reports.

Device planes are those named ``/device:TPU:<n>`` (a SparseCore or other
sub-plane of a chip is skipped).  On each, the ``XLA Ops`` line holds one
event per operation the chip ran and the ``XLA Modules`` line one per
program launch.  Host spans come from the benchmark's own
``jax.profiler.TraceAnnotation`` calls, whose names start with
``bench.``; the span ``bench.window`` bounds the traced window.

``reduce_trace`` returns a ``TraceSummary``:

* ``window_s`` — the length of ``bench.window``;
* ``busy_s`` — the union of the operations' intervals inside the window,
  averaged over the device planes that ran anything;
* ``programs`` — device seconds per program (module) name, all planes
  summed, inside the window;
* ``ops`` — device seconds per operation (its HLO instruction name, the
  text before `` = ``), likewise;
* ``gaps`` — idle seconds of the first device plane, each stretch
  labelled by the innermost ``bench.`` span that covers its midpoint
  (``host:other`` where none does);
* ``launches`` — program launches per name inside the window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

__all__ = ["TraceSummary", "reduce_trace", "reduce_file", "find_xplane"]

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    programs: dict
    ops: dict
    gaps: dict
    launches: dict
    spans: dict          # host seconds per bench span name

    def top(self, table: dict, n: int = 10) -> list:
        return [[k, v] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(root: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    return hits[-1] if hits else None


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merge intervals; returns (merged starts, merged ends)."""
    if starts.size == 0:
        return starts, ends
    o = np.argsort(starts, kind="stable")
    s, e = starts[o], ends[o]
    run_end = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    m_s = s[idx]
    m_e = np.append(run_end[idx[1:] - 1], run_end[-1])
    return m_s, m_e


def _clip_sum(s, e, lo, hi) -> float:
    return float(np.clip(np.minimum(e, hi) - np.maximum(s, lo), 0, None).sum())


def reduce_trace(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans = []   # (start_ns, end_ns, name)
    devices = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    win = [s for s in spans if s[2] == _PREFIX + "window"]
    if not win:
        raise ValueError("trace holds no bench.window span")
    lo, hi = win[0][0], win[0][1]
    window_s = (hi - lo) * 1e-9
    span_s: dict = {}
    for s, e, name in spans:
        if name != _PREFIX + "window":
            span_s[name] = span_s.get(name, 0.0) + max(
                0.0, min(e, hi) - max(s, lo)) * 1e-9

    programs: dict = {}
    ops: dict = {}
    launches: dict = {}
    busy = []
    gaps: dict = {}
    first = True
    for plane in devices:
        op_s, op_e = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                table = programs
            elif line.name == "XLA Ops":
                table = ops
            else:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                inside = max(0.0, min(e, hi) - max(s, lo)) * 1e-9
                if inside <= 0:
                    continue
                name = ev.name.split(" = ")[0]
                table[name] = table.get(name, 0.0) + inside
                if table is programs:
                    launches[name] = launches.get(name, 0) + 1
                else:
                    op_s.append(s)
                    op_e.append(e)
        if not op_s:
            continue
        m_s, m_e = _union(np.asarray(op_s, np.float64),
                          np.asarray(op_e, np.float64))
        busy.append(_clip_sum(m_s, m_e, lo, hi) * 1e-9)
        if first:
            first = False
            gap_s = np.concatenate([[lo], np.minimum(m_e, hi)])
            gap_e = np.concatenate([np.maximum(m_s, lo), [hi]])
            keep = gap_e > gap_s
            gap_s, gap_e = gap_s[keep], gap_e[keep]
            labels = _labels((gap_s + gap_e) / 2, spans)
            for label, dur in zip(labels, (gap_e - gap_s) * 1e-9):
                gaps[label] = gaps.get(label, 0.0) + float(dur)
    busy_s = float(np.mean(busy)) if busy else 0.0
    return TraceSummary(window_s=window_s, busy_s=busy_s,
                        devices=len(busy), programs=programs, ops=ops,
                        gaps=gaps, launches=launches, spans=span_s)


def _labels(mid: np.ndarray, spans: list) -> list:
    """For each (sorted) time in ``mid``, the innermost (shortest) bench
    span other than the window that covers it."""
    out = np.full(mid.shape[0], -1, np.int64)
    inner = [sp for sp in spans if sp[2] != _PREFIX + "window"]
    inner.sort(key=lambda sp: -(sp[1] - sp[0]))   # longest first
    for j, (s, e, _) in enumerate(inner):
        out[np.searchsorted(mid, s, "left"):np.searchsorted(mid, e, "right")] = j
    return [inner[j][2] if j >= 0 else "host:other" for j in out]


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_trace(ProfileData.from_file(path))
