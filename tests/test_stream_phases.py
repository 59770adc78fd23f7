"""Host phases of ``StreamSession.feed``: each timed into
``StreamUpdate.timings``, marked as a ``parsa.feed.<phase>`` span in the
profiler's trace, and the per-feed counters of what the packed blocks
hold."""
import gc
import glob
import statistics

import numpy as np
import pytest

from repro.api import ParsaConfig, ParsaStreamConfig, StreamSession
from repro.core.bipartite import BipartiteGraph
from repro.core.jax_partition import pack_graph_blocks
from repro.graphs import ctr_like_stream, text_like

PHASES = ("prepare", "pack", "upload", "launch", "wait", "append",
          "metrics", "release")
SCAN_PHASES = ("upload", "launch", "wait", "append")
CAP = 8


def _session(num_v, **kw):
    base = ParsaConfig(k=4, backend="device_scan", block_size=64, cap=CAP,
                       use_kernel=False, refine_v=False, seed=3)
    return StreamSession(ParsaStreamConfig(base=base, **kw), num_v=num_v)


def _phase_sum(timings):
    return sum(v for name, v in timings.items()
               if name not in ("partition_u", "total"))


def test_feed_times_every_phase():
    g = text_like(4000, 1 << 14, mean_len=40, seed=1)
    sess = _session(g.num_v, repartition="never")
    shares = []
    gc.disable()    # a collection between two phases is not the feed's
    try:
        for i in range(4):
            upd = sess.feed(g.slice_u(1000 * i, 1000 * (i + 1)))
            t = upd.timings
            assert list(t) == [*PHASES[:6], "partition_u", *PHASES[6:],
                               "total"]
            assert all(v >= 0 for v in t.values())
            assert t["partition_u"] == pytest.approx(
                sum(t[name] for name in SCAN_PHASES), rel=1e-12)
            assert _phase_sum(t) <= t["total"]
            if i:          # the first feed compiles the scan
                shares.append(_phase_sum(t) / t["total"])
    finally:
        gc.enable()
    assert statistics.median(shares) >= 0.99, shares


def test_drift_feed_times_repartition():
    chunks = ctr_like_stream(900, 2000, chunks=4, nnz_per_row=12, churn=0.7,
                             seed=1)
    sess = _session(2000, drift_threshold=1.0, drift_min_feeds=1,
                    repartition_frac=0.0)
    updates = [sess.feed(ch) for ch in chunks]
    repaired = [u for u in updates if u.repartitioned]
    assert repaired, "drift repair never triggered"
    for u in updates:
        assert ("repartition" in u.timings) == u.repartitioned
        assert _phase_sum(u.timings) <= u.timings["total"]


def test_feed_counters_count_the_packed_blocks():
    g = text_like(600, 3000, mean_len=20, seed=2)
    sess = _session(g.num_v, repartition="never", tb_pad=4)
    upd = sess.feed(g)
    order = np.random.default_rng(3).permutation(g.num_u)
    packed = pack_graph_blocks(sess.arena.capacity_graph(g), 64,
                               order=order, cap=CAP, tb_pad=4)
    arrays = (packed.valid, packed.widx, packed.vals, packed.trunc,
              packed.overflow_spans, packed.overflow_words)
    assert upd.counters["upload_bytes"] == sum(a.nbytes for a in arrays)
    words = [np.unique(g.u_indices[g.u_indptr[u]:g.u_indptr[u + 1]] // 32)
             for u in range(g.num_u)]
    over_cap = sum(len(w) > CAP for w in words)
    assert over_cap > 0
    assert upd.counters["channel_rows"] == over_cap
    assert upd.counters["channel_words"] == sum(
        max(0, len(w) - CAP) for w in words)
    assert upd.counters["channel_slots"] == packed.overflow_words.shape[1]
    assert upd.counters["channel_words"] <= upd.counters["channel_slots"]
    assert upd.counters["channel_grew"] == 1     # the first feed sets it
    assert sess.feed(g).counters["channel_grew"] == 0
    # every block holds a row past cap: none runs in compact space
    assert packed.trunc.any(axis=1).all()
    assert upd.counters["compact_blocks"] == 0


def test_feed_counters_count_compact_blocks():
    """Click-log rows of no more words than cap: every block of the feed
    is one the scan runs in compact space."""
    chunks = ctr_like_stream(1000, 1 << 14, chunks=2, nnz_per_row=CAP,
                             seed=4)
    sess = _session(1 << 14, repartition="never")
    for ch in chunks:
        upd = sess.feed(ch)
        blocks = -(-ch.num_u // 64)
        assert upd.counters["channel_rows"] == 0
        assert upd.counters["compact_blocks"] == blocks


@pytest.mark.parametrize("reverse", [False, True])
def test_feed_counters_count_pack_sorted(reverse):
    """``pack_sorted`` reads 1 on a chunk whose rows hold their columns in
    ascending order (the pack runs without its sort) and 0 on the same
    chunk with every row's columns reversed (it sorts), and the feed's
    other counters are the same either way."""
    g = text_like(600, 3000, mean_len=20, seed=2)
    if reverse:
        lens = np.diff(g.u_indptr)
        start = np.repeat(g.u_indptr[:-1], lens)
        stop = np.repeat(g.u_indptr[1:], lens)
        e = np.arange(g.u_indices.size)
        g = BipartiteGraph(g.num_u, g.num_v, g.u_indptr,
                           g.u_indices[start + stop - 1 - e])
    upd = _session(g.num_v, repartition="never", tb_pad=4).feed(g)
    assert upd.counters["pack_sorted"] == int(not reverse)
    ref = _session(g.num_v, repartition="never", tb_pad=4).feed(
        text_like(600, 3000, mean_len=20, seed=2))
    assert {k: v for k, v in upd.counters.items() if k != "pack_sorted"} \
        == {k: v for k, v in ref.counters.items() if k != "pack_sorted"}


def test_feed_spans_land_in_the_profiler_trace(tmp_path):
    import jax
    from jax.profiler import ProfileData

    g = text_like(400, 2000, mean_len=20, seed=4)
    sess = _session(g.num_v, repartition="never")
    sess.feed(g.slice_u(0, 200))              # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        upd = sess.feed(g.slice_u(200, 400))
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert path
    found = {}
    for plane in ProfileData.from_file(path[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("parsa.feed."):
                    found[ev.name] = dict(ev.stats)
    for name in PHASES:
        assert found[f"parsa.feed.{name}"]["feed"] == upd.chunk == 1
    stats = found["parsa.feed.pack"]
    assert {k: stats[k] for k in upd.counters} == upd.counters
    # the counts are attached once, to the phase that computes them
    assert not set(upd.counters) & set(found["parsa.feed.upload"])
