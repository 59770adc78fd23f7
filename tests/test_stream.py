"""repro.stream: online incremental Parsa — arena growth, one-chunk
degenerate parity vs device_scan, O(1) dispatches per feed, the
padding-bit invariant of the ragged last packed word, drift-triggered
repartition + migration metering, snapshot round trips, and the PSCluster
mid-run placement update."""
import numpy as np
import pytest

from repro.api import ParsaConfig, ParsaStreamConfig, StreamSession, partition
from repro.api_backends import TrafficCounters
from repro.core.bipartite import BipartiteGraph, from_edges, load_npz
from repro.core.costs import evaluate, need_matrix
from repro.core.jax_partition import dispatch_counter
from repro.graphs import (
    ctr_like_stream,
    social_like_stream,
    text_like,
    text_like_stream,
)
from repro.kernels.parsa_cost import (
    pack_bitmask,
    packed_delta,
    packed_intersect_counts,
    packed_union,
    packed_union_delta,
    unpack_bitmask,
)
from repro.stream import StreamArena, stream_partition


def _stream_cfg(k=4, **kw):
    base = ParsaConfig(k=k, backend="device_scan", block_size=64,
                       use_kernel=False, refine_v=False)
    return ParsaStreamConfig(base=base, **kw)


# ----------------------------------------------------------- satellite: io
def test_save_npz_round_trip(tmp_path):
    g = text_like(300, 777, mean_len=12, seed=5)   # 777 % 32 != 0
    path = tmp_path / "graph.npz"
    g.save_npz(path)
    g2 = load_npz(path)
    assert (g2.num_u, g2.num_v) == (g.num_u, g.num_v)
    assert np.array_equal(g2.u_indptr, g.u_indptr)
    assert np.array_equal(g2.u_indices, g.u_indices)
    g2.validate()


def test_arena_snapshot_round_trip(tmp_path):
    cfg = _stream_cfg()
    sess = StreamSession(cfg, num_v=500)
    for ch in text_like_stream(300, 500, chunks=3, mean_len=10, seed=2):
        sess.feed(ch)
    path = tmp_path / "arena.npz"
    sess.arena.save(path)
    arena2 = StreamArena.load(path)
    assert arena2.num_u == sess.arena.num_u
    assert arena2.num_v == sess.arena.num_v
    g1, g2 = sess.arena.graph(), arena2.graph()
    assert np.array_equal(g1.u_indptr, g2.u_indptr)
    assert np.array_equal(g1.u_indices, g2.u_indices)
    assert np.array_equal(np.asarray(sess.arena.s_masks),
                          np.asarray(arena2.s_masks))
    assert np.array_equal(np.asarray(sess.arena.sizes),
                          np.asarray(arena2.sizes))


def test_session_snapshot_resumes_bit_identically(tmp_path):
    """StreamSession.save/load restores the FULL stream state: resuming
    the same chunk sequence produces bit-identical parts and sets."""
    chunks = text_like_stream(450, 700, chunks=3, mean_len=10, seed=8)
    cfg = _stream_cfg(repartition="never")
    sess = StreamSession(cfg, num_v=700)
    sess.feed(chunks[0])
    sess.feed(chunks[1])
    path = tmp_path / "session.npz"
    sess.save(path)
    restored = StreamSession.load(path, cfg)
    assert np.array_equal(restored.parts, sess.parts)
    u1 = sess.feed(chunks[2])
    u2 = restored.feed(chunks[2])
    assert np.array_equal(u2.parts, u1.parts)
    assert np.array_equal(restored.parts, sess.parts)
    assert np.array_equal(restored.arena.masks_np(), sess.arena.masks_np())
    assert restored.n_feeds == sess.n_feeds
    with pytest.raises(ValueError, match="k="):
        StreamSession.load(path, _stream_cfg(k=8))


def test_feed_failure_leaves_session_consistent():
    """A chunk that fails validation must not mutate the appended graph or
    the parts — feed is retry-safe (append happens after the scan)."""
    g = text_like(200, 400, mean_len=8, seed=0)
    sess = StreamSession(_stream_cfg(), num_v=400)
    sess.feed(g.slice_u(0, 100))
    bad = BipartiteGraph(5, 10, np.array([0, 1, 2, 3, 4, 5], np.int64),
                         np.array([1, 2, 3, 99, 4], np.int32))  # 99 >= 10
    before_u, before_parts = sess.arena.num_u, sess.parts.copy()
    with pytest.raises(ValueError, match="exceeds"):
        sess.feed(bad)
    assert sess.arena.num_u == before_u
    assert np.array_equal(sess.parts, before_parts)
    sess.feed(g.slice_u(100, 200))  # stream continues fine
    assert sess.parts.shape == (200,)


def test_slice_u_matches_subgraph_u():
    g = text_like(200, 300, mean_len=8, seed=1)
    sl = g.slice_u(37, 151)
    ref = g.subgraph_u(np.arange(37, 151))
    assert sl.num_u == ref.num_u and sl.num_v == ref.num_v
    assert np.array_equal(sl.u_indptr, ref.u_indptr)
    assert np.array_equal(sl.u_indices, ref.u_indices)
    with pytest.raises(ValueError, match="out of range"):
        g.slice_u(10, 500)


# ---------------------------------------- satellite: padding-bit invariant
def _padding_bits_zero(masks: np.ndarray, num_v: int) -> bool:
    """True iff every bit at a column ≥ num_v is zero."""
    W = masks.shape[1]
    assert W * 32 >= num_v
    dense = unpack_bitmask(masks, W * 32)
    return not dense[:, num_v:].any()


@pytest.mark.parametrize("seed", range(5))
def test_padding_bits_stay_zero_through_packed_ops(seed):
    """Property: with num_v % 32 != 0, the ragged last word's padding bits
    are zero after packing and remain zero through union / delta / fused
    union+delta — the invariant the stream arena's appends lean on."""
    rng = np.random.default_rng(seed)
    num_v = int(rng.integers(33, 400))
    if num_v % 32 == 0:
        num_v += 1
    k = int(rng.integers(2, 8))
    a = pack_bitmask([rng.integers(0, num_v, rng.integers(1, 50))
                      for _ in range(k)], num_v)
    b = pack_bitmask([rng.integers(0, num_v, rng.integers(1, 50))
                      for _ in range(k)], num_v)
    assert _padding_bits_zero(a, num_v) and _padding_bits_zero(b, num_v)
    assert _padding_bits_zero(packed_union(a, b), num_v)
    assert _padding_bits_zero(packed_delta(a, b), num_v)
    u, d = packed_union_delta(np.asarray(a), np.asarray(b), use_kernel=False)
    assert _padding_bits_zero(np.asarray(u), num_v)
    assert _padding_bits_zero(np.asarray(d), num_v)
    u, d = packed_union_delta(np.asarray(a), np.asarray(b), interpret=True)
    assert _padding_bits_zero(np.asarray(u), num_v)
    assert _padding_bits_zero(np.asarray(d), num_v)


@pytest.mark.parametrize("num_v", [97, 510, 1001])
def test_padding_bits_stay_zero_through_stream_and_need(num_v):
    """The arena's live sets and the device need path keep capacity bits
    beyond num_v zero across appends (ragged last word included)."""
    from repro.core.jax_refine import need_masks

    chunks = text_like_stream(240, num_v, chunks=3, mean_len=9, seed=3)
    sess = StreamSession(_stream_cfg(), num_v=num_v)
    for ch in chunks:
        sess.feed(ch)
        masks = np.asarray(sess.arena.s_masks)
        assert _padding_bits_zero(masks, sess.arena.num_v)
    g = sess.arena.graph()
    nw = np.asarray(need_masks(g, sess.parts, 4))
    assert _padding_bits_zero(nw, num_v)
    # popcount metrics over the live sets == exact host evaluate (cold
    # stream ⇒ S_i == N(U_i)), so padding bits never inflate objectives
    want = evaluate(g, sess.parts, None, 4)
    got = sess._popcount_metrics()
    assert got.as_dict() == want.as_dict()


def test_padding_bits_stay_zero_through_sketched_stream():
    """PR 9 extension of the invariant, both sketch regimes.  Compressing:
    the arena runs at the word-aligned sketched width and every set bit
    stays inside it.  Exact-collapse (hot >= |V|): the arena runs at the
    ragged TRUE width and the PR 5 padding invariant must survive the
    sketch-mode plumbing bit for bit.  (Truly ragged sketched widths need
    a hand-built SketchSpec — covered in test_sketch.py.)"""
    num_v = 1001                                  # ragged true width
    chunks = text_like_stream(240, num_v, chunks=3, mean_len=9, seed=3)

    base = ParsaConfig(k=4, backend="device_scan", block_size=64,
                       use_kernel=False, refine_v=False, set_repr="sketch",
                       sketch_hot_bits=96, sketch_bucket_bits=64)
    sess = StreamSession(ParsaStreamConfig(base=base), num_v=num_v)
    assert sess.sketch is not None
    width = sess.sketch.width_bits
    assert sess.arena.num_v == width == 160
    for ch in chunks:
        sess.feed(ch)
        assert _padding_bits_zero(np.asarray(sess.arena.s_masks), width)

    base_x = base.replace(sketch_hot_bits=1024)   # >= num_v: exact collapse
    sx = StreamSession(ParsaStreamConfig(base=base_x), num_v=num_v)
    assert sx.sketch is None and sx.arena.num_v == num_v
    for ch in chunks:
        sx.feed(ch)
        assert _padding_bits_zero(np.asarray(sx.arena.s_masks), num_v)
    # exact collapse is bit-identical to the plain stream (PR 9 regression)
    plain = StreamSession(_stream_cfg(), num_v=num_v)
    for ch in chunks:
        plain.feed(ch)
    assert np.array_equal(sx.parts, plain.parts)
    assert np.array_equal(sx.arena.masks_np(), plain.arena.masks_np())


# ------------------------------------------- satellite: degenerate parity
def test_one_chunk_feed_bit_identical_to_device_scan():
    """Feeding the entire graph as ONE chunk is the device_scan backend:
    same permutation, same scan, same parts and s_masks bit for bit."""
    g = text_like(900, 1100, mean_len=18, seed=11)
    cfg = _stream_cfg(k=8)
    sess = StreamSession(cfg, num_v=g.num_v)
    upd = sess.feed(g)
    ref = partition(g, ParsaConfig(k=8, backend="device_scan", block_size=64,
                                   use_kernel=False, refine_v=False))
    assert np.array_equal(sess.parts, ref.parts_u)
    assert np.array_equal(upd.parts, ref.parts_u)
    assert np.array_equal(sess.arena.masks_np(), ref.s_masks)
    res = sess.result(refine_v=True)
    want = partition(g, ParsaConfig(k=8, backend="device_scan",
                                    block_size=64, use_kernel=False,
                                    refine_backend="device"))
    assert np.array_equal(res.parts_v, want.parts_v)
    assert res.metrics.as_dict() == want.metrics.as_dict()


# --------------------------------------------------- feeding fundamentals
def test_multi_chunk_feed_o1_dispatches_and_balance():
    g = text_like(800, 1000, mean_len=15, seed=7)
    sess = StreamSession(_stream_cfg(repartition="never"), num_v=g.num_v)
    for i in range(4):
        with dispatch_counter() as counts:
            upd = sess.feed(g.slice_u(i * 200, (i + 1) * 200))
        # O(1) device dispatches per feed: the scan + the metrics popcount
        # (labeled records: the scan record carries the live-arena bytes)
        phases = [r.phase for r in counts.records]
        assert phases.count("stream_feed_scan") == 1, phases
        assert phases.count("stream_metrics") == 1, phases
        scan = next(r for r in counts.records
                    if r.phase == "stream_feed_scan")
        assert scan.nbytes > 0 and scan.meta.get("k") == 4
        assert upd.u_stop - upd.u_start == 200
        assert (upd.parts >= 0).all() and (upd.parts < 4).all()
    assert sess.parts.shape == (800,)
    sizes = np.bincount(sess.parts, minlength=4)
    # carried (S, sizes) keep §4.1 perfect balance across chunk boundaries
    assert sizes.max() - sizes.min() <= 1
    # the live sets cover exactly the assigned neighborhoods
    need = need_matrix(g, sess.parts, 4)
    assert np.array_equal(
        pack_bitmask(need, g.num_v), sess.arena.masks_np())


def test_growing_v_capacity_doubling():
    chunks = social_like_stream(600, chunks=4, m=5, seed=2)
    sess = StreamSession(_stream_cfg(repartition="never"),
                         num_v=chunks[0].num_v)
    w0 = sess.arena.W_cap
    for ch in chunks:
        sess.feed(ch)
    assert sess.arena.num_v == 600
    assert sess.arena.W_cap >= (600 + 31) // 32 > w0
    assert _padding_bits_zero(np.asarray(sess.arena.s_masks),
                              sess.arena.num_v)
    res = sess.result(refine_v=False)
    assert res.num_v == 600
    assert (res.parts_u >= 0).all()
    want = evaluate(sess.arena.graph(), sess.parts, None, 4)
    assert res.metrics.as_dict() == want.as_dict()


def test_arena_zero_edge_snapshot_restores_and_grows(tmp_path):
    """A snapshot taken before any edges arrived restores with zero-length
    buffers; the next append must re-grow them (capacity floor)."""
    arena = StreamArena(4, 100)
    path = tmp_path / "empty.npz"
    arena.save(path)
    arena2 = StreamArena.load(path)
    g = text_like(50, 100, mean_len=5, seed=0)
    start, stop = arena2.append(g)
    assert (start, stop) == (0, 50)
    g2 = arena2.graph()
    assert np.array_equal(g2.u_indices, g.u_indices)


def test_session_rejects_unreachable_worker_count_at_construction():
    """The device-count check runs at __init__ — a mid-feed failure would
    leave the arena appended but the parts unassigned."""
    import jax

    workers = len(jax.devices()) + 1
    base = ParsaConfig(k=4, backend="parallel_device", workers=workers,
                       block_size=64, use_kernel=False, refine_v=False)
    with pytest.raises(ValueError, match="devices"):
        StreamSession(ParsaStreamConfig(base=base), num_v=100)


def test_update_dispatches_reports_repartition_launches():
    """StreamUpdate.dispatches comes from a real dispatch counter: a
    drift-repair feed reports the repartition's own scan too."""
    chunks = ctr_like_stream(900, 2000, chunks=4, nnz_per_row=12, churn=0.7,
                             seed=1)
    cfg = _stream_cfg(drift_threshold=1.0, drift_min_feeds=1,
                      repartition_frac=0.0)
    sess = StreamSession(cfg, num_v=2000)
    updates = [sess.feed(ch) for ch in chunks]
    plain = [u for u in updates if not u.repartitioned]
    repaired = [u for u in updates if u.repartitioned]
    assert repaired, "drift repair never triggered"
    for u in plain:
        assert u.dispatches == {"stream_feed_scan": 1, "stream_metrics": 1}
    for u in repaired:
        assert u.dispatches["stream_feed_scan"] == 1
        assert u.dispatches["stream_metrics"] == 2
        assert u.dispatches["partition_scan"] == 1  # the repair's full scan


def _overflow_chunk(n, words_per_row, seed):
    """``n`` rows; row i holds ``words_per_row[i]`` distinct packed words
    (a few bits each) of a 256-word (8,192-column) space."""
    rng = np.random.default_rng(seed)
    us, vs = [], []
    for u, m in enumerate(words_per_row):
        cols = (rng.choice(256, m, replace=False)[:, None] * 32
                + rng.integers(0, 32, (m, 3))).ravel()
        us.append(np.full(cols.size, u))
        vs.append(cols)
    return from_edges(n, 8192, np.concatenate(us), np.concatenate(vs))


def _overflow_session():
    base = ParsaConfig(k=4, backend="device_scan", block_size=64, cap=4,
                       use_kernel=False, refine_v=False, seed=7)
    return StreamSession(ParsaStreamConfig(base=base, tb_pad=1,
                                           repartition="never"), num_v=8192)


def _sets_from_parts(sess):
    """The packed server sets the fed rows and their parts imply."""
    g = sess.arena.graph()
    want = np.zeros((sess.k, (g.num_v + 31) // 32), np.uint32)
    for u in range(g.num_u):
        want[sess.parts[u]] |= pack_bitmask(
            [g.neighbors(u)], g.num_v).view(np.uint32)[0]
    return want


def test_overflow_capacity_holds_across_feeds():
    """Chunks whose overflow-word counts vary under the list's capacity
    run on the scan compiled for the first feed: no further compile."""
    from repro.core.jax_partition import _partition_scan

    rng = np.random.default_rng(11)
    sess = _overflow_session()
    ups = []
    # 128 rows (two blocks); (rows past cap, words past cap each): the
    # first sets the capacity at 2·30 → 64, the rest fit under it
    for c, (hubs, extra) in enumerate([(6, 5), (0, 0), (3, 7), (8, 7),
                                       (5, 2)]):
        lens = np.full(128, 3)
        lens[rng.choice(128, hubs, replace=False)] = 4 + extra
        ups.append(sess.feed(_overflow_chunk(128, lens, seed=c)))
        if c == 0:
            compiled = _partition_scan._cache_size()
    assert _partition_scan._cache_size() == compiled
    words = [u.counters["channel_words"] for u in ups]
    assert words == [30, 0, 21, 56, 10]
    assert {u.counters["channel_slots"] for u in ups} == {64}
    assert [u.counters["channel_grew"] for u in ups] == [1, 0, 0, 0, 0]
    assert np.array_equal(sess.arena.masks_np().view(np.uint32),
                          _sets_from_parts(sess))


def test_overflow_capacity_grows_once_and_never_shrinks():
    """A chunk whose overflow words do not fit raises the capacity once
    (``channel_grew`` on that feed alone); a smaller chunk after it keeps
    the larger capacity, on the same compiled scan."""
    from repro.core.jax_partition import _partition_scan

    sess = _overflow_session()
    small = np.full(128, 3)
    small[:4] = 10                             # 4 rows × 6 words past cap
    big = np.full(128, 3)
    big[:40] = 12                              # 40 rows × 8 words past cap
    chunks = [small, small, big, small, big]
    ups = [sess.feed(_overflow_chunk(128, lens, seed=c))
           for c, lens in enumerate(chunks)]
    assert [u.counters["channel_words"] for u in ups] == [24, 24, 320, 24, 320]
    # floor tb_pad·cap·n_blocks = 8, so 2·24 → 64; 2·320 → 1024
    assert [u.counters["channel_slots"] for u in ups] == [64, 64, 1024,
                                                          1024, 1024]
    assert [u.counters["channel_grew"] for u in ups] == [1, 0, 1, 0, 0]
    before = _partition_scan._cache_size()
    sess.feed(_overflow_chunk(128, small, seed=9))
    assert _partition_scan._cache_size() == before
    assert np.array_equal(sess.arena.masks_np().view(np.uint32),
                          _sets_from_parts(sess))


def test_stream_config_validation():
    with pytest.raises(ValueError, match="device backend"):
        ParsaStreamConfig(base=ParsaConfig(k=4, backend="host"))
    with pytest.raises(ValueError, match="repartition must be"):
        _stream_cfg(repartition="sometimes")
    with pytest.raises(ValueError, match="repartition_frac"):
        _stream_cfg(repartition_frac=1.5)
    with pytest.raises(ValueError, match="tb_pad"):
        _stream_cfg(tb_pad=0)
    with pytest.raises(ValueError, match="window"):
        _stream_cfg(drift_window=0)
    with pytest.raises(ValueError, match="threshold"):
        _stream_cfg(drift_threshold=0.5)


def test_stream_partition_convenience():
    chunks = text_like_stream(400, 600, chunks=3, mean_len=10, seed=4)
    res, updates = stream_partition(chunks, _stream_cfg(repartition="never"))
    assert len(updates) == 3
    assert res.parts_u.shape == (400,)
    assert [u.chunk for u in updates] == [0, 1, 2]
    with pytest.raises(ValueError, match="at least one chunk"):
        stream_partition([], _stream_cfg())


# ------------------------------------------------ drift repair + migration
def test_drift_triggered_repartition_and_migration_metering():
    chunks = ctr_like_stream(900, 2000, chunks=5, nnz_per_row=12, churn=0.6,
                             seed=1)
    cfg = _stream_cfg(drift_threshold=1.0, drift_min_feeds=1,
                      repartition_frac=0.0)
    sess = StreamSession(cfg, num_v=2000)
    updates = [sess.feed(ch) for ch in chunks]
    assert sess.repartitions >= 1
    reparted = [u for u in updates if u.repartitioned]
    assert reparted, "drift threshold 1.0 should have tripped"
    mig = reparted[0].migration
    assert mig is not None
    assert mig.traffic.migration_bytes > 0
    assert mig.traffic.migration_bytes == mig.acquired_bytes + mig.retired_bytes
    # recovery traffic never pollutes the steady-state push/pull counters
    assert mig.traffic.pushed_bytes == 0 and mig.traffic.pulled_bytes == 0
    assert 0 <= mig.moved_u <= sess.parts.shape[0]
    assert np.array_equal(np.sort(mig.assign), np.arange(4))
    # session accumulates migration traffic in TrafficCounters units
    assert sess.traffic.migration_bytes >= mig.traffic.migration_bytes
    # cold repartition keeps the need invariant: popcounts stay exact
    g = sess.arena.graph()
    want = evaluate(g, sess.parts, None, 4)
    assert sess._popcount_metrics().as_dict() == want.as_dict()


def test_repartition_improves_or_matches_drifted_quality():
    """After heavy churn, one repartition should not be worse than the
    decayed online assignment it replaces (same graph, fresh greedy)."""
    chunks = ctr_like_stream(800, 1600, chunks=4, nnz_per_row=12, churn=0.8,
                             seed=9)
    sess = StreamSession(_stream_cfg(repartition="never"), num_v=1600)
    for ch in chunks:
        sess.feed(ch)
    g = sess.arena.graph()
    before = evaluate(g, sess.parts, None, 4).traffic_max
    plan = sess.repartition()
    after = evaluate(g, sess.parts, None, 4).traffic_max
    assert after <= before * 1.02  # fresh greedy ≥ decayed online (±noise)
    assert np.array_equal(plan.parts_u, sess.parts)


def test_migration_relabel_maximizes_overlap():
    from repro.stream import plan_migration

    rng = np.random.default_rng(0)
    num_v, k = 200, 4
    old = pack_bitmask([rng.integers(0, num_v, 60) for _ in range(k)], num_v)
    # the "new" partition is the old one with labels rotated by 1
    rot = np.roll(np.arange(k), -1)
    new = old[rot]
    old_parts = rng.integers(0, k, 100).astype(np.int32)
    new_parts = np.empty_like(old_parts)
    for i in range(k):
        new_parts[old_parts == rot[i]] = i
    plan = plan_migration(new_parts, new, old_parts, old)
    # perfect overlap exists: the matcher must find the rotation and
    # reconstruct the identical labeling with zero migration
    assert np.array_equal(plan.parts_u, old_parts)
    assert np.array_equal(plan.s_masks, old)
    assert plan.moved_u == 0
    assert plan.traffic.migration_bytes == 0
    assert plan.acquired_bytes == 0 and plan.retired_bytes == 0
    M = packed_intersect_counts(new, old)
    assert plan.kept_overlap == int(M.max(axis=1).sum())


def test_traffic_counters_add():
    a = TrafficCounters(1, 2, 3, 4)
    b = TrafficCounters(10, 20, 30, 40)
    # positional construction stays backward compatible: migration_bytes
    # defaults to 0 and sums component-wise like the original four fields
    assert a + b == TrafficCounters(11, 22, 33, 44)
    assert (a + b).migration_bytes == 0
    c = TrafficCounters(migration_bytes=7)
    assert (a + c).migration_bytes == 7
    assert (a + c).pushed_bytes == 1


# ------------------------------------------------------- PSCluster updates
def test_ps_cluster_apply_placement_mid_run():
    from repro.ml.dbpg import DBPGConfig
    from repro.ml.ps import PSCluster

    g = text_like(120, 300, mean_len=10, seed=6)
    rng = np.random.default_rng(0)
    labels = rng.choice([-1.0, 1.0], g.num_u)
    k = 4
    r1 = partition(g, ParsaConfig(k=k, backend="host"))
    cluster = PSCluster(g, labels, r1.parts_u, r1.parts_v, k,
                        DBPGConfig(lam=1e-4, lr=0.1))
    cluster.step(0)
    total_before = cluster.meter.total
    # a genuinely different placement: rotate every assignment
    new_u = ((r1.parts_u + 1) % k).astype(np.int32)
    new_v = np.where(r1.parts_v >= 0, (r1.parts_v + 1) % k, -1).astype(
        np.int32)
    info = cluster.apply_placement(new_u, new_v)
    assert info["moved_rows"] == g.num_u
    assert info["moved_weights"] == int((r1.parts_v >= 0).sum())
    assert info["reshard_bytes"] > 0
    assert cluster.meter.total == total_before + info["reshard_bytes"]
    assert np.array_equal(cluster.need, need_matrix(g, new_u, k))
    assert not cluster._keys_sent.any()
    cluster.step(1)  # training continues on the new placement
    with pytest.raises(ValueError, match="fixed graph"):
        cluster.apply_placement(new_u[:-1], new_v)


def test_stream_generators_shapes():
    for chunks in (text_like_stream(200, 500, chunks=4, mean_len=8, seed=0),
                   ctr_like_stream(200, 800, chunks=4, nnz_per_row=10,
                                   seed=0)):
        assert len(chunks) == 4
        assert sum(c.num_u for c in chunks) == 200
        for c in chunks:
            c.validate()
    soc = social_like_stream(300, chunks=3, m=4, seed=0)
    assert sum(c.num_u for c in soc) == 300
    assert soc[-1].num_v == 300
    nv = 0
    for c in soc:
        c.validate()
        assert c.num_v >= nv
        nv = c.num_v
