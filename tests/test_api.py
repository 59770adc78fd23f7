"""The unified repro.api facade: config validation, backend registry,
result uniformity, warm-start refine, and bit-exact parity between each
registered strategy and the implementation it wraps."""
import numpy as np
import pytest

from repro.api import (
    ParsaConfig,
    PartitionResult,
    available_backends,
    partition,
)
from repro.graphs import text_like


@pytest.fixture(scope="module")
def parity_graph():
    """Fixed-seed 2k-vertex graph for the registry parity test."""
    return text_like(2000, 3000, mean_len=20, seed=42)


@pytest.fixture(scope="module")
def small_graph():
    return text_like(300, 600, mean_len=15, seed=0)


# ------------------------------------------------------------- validation
def test_registry_has_all_backends():
    assert {"host", "device_scan", "host_blocked_oracle",
            "parallel_sim", "parallel_device"} <= set(available_backends())


@pytest.mark.parametrize("kwargs,match", [
    (dict(k=4, backend="nope"), "unknown Parsa backend"),
    (dict(k=0), "k must be"),
    (dict(k=-3), "k must be"),
    (dict(k=4, block_size=100), "multiple of 8"),
    (dict(k=4, block_size=0), "multiple of 8"),
    (dict(k=4, blocks=0), "blocks must be"),
    (dict(k=4, init_iters=-1), "init_iters"),
    (dict(k=4, select="weird"), "select must be"),
    (dict(k=4, workers=0), "workers"),
    (dict(k=4, tau=-1), "tau"),
    (dict(k=4, global_init_frac=1.5), "global_init_frac"),
    (dict(k=4, merge_every=0), "merge_every"),
    (dict(k=4, devices=0), "devices"),
    (dict(k=4, sweeps=0), "sweeps"),
    (dict(k=4, placement=True, refine_v=False), "placement"),
])
def test_config_validation_errors(kwargs, match):
    with pytest.raises(ValueError, match=match):
        ParsaConfig(**kwargs)


def test_config_is_frozen_and_replaceable():
    import dataclasses

    cfg = ParsaConfig(k=8)
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.k = 4
    cfg2 = cfg.replace(backend="device_scan", block_size=64)
    assert cfg2.k == 8 and cfg2.backend == "device_scan"
    assert cfg.backend == "host"  # original untouched


# ------------------------------------------------- backend equivalence smoke
@pytest.mark.parametrize("backend,extra", [
    ("host", {}),
    ("device_scan", dict(block_size=64)),
    ("host_blocked_oracle", dict(block_size=64)),
    ("parallel_sim", dict(workers=4, tau=0)),
    ("parallel_device", dict(workers=1, block_size=64, merge_every=2)),
])
def test_backend_smoke_valid_partition_and_schema(small_graph, backend, extra):
    """Every backend yields a valid partition and the identical metrics /
    result schema through the one partition() entry point."""
    g, k = small_graph, 4
    res = partition(g, ParsaConfig(k=k, backend=backend, blocks=4, **extra))
    assert isinstance(res, PartitionResult)
    assert res.parts_u.shape == (g.num_u,)
    assert (res.parts_u >= 0).all() and (res.parts_u < k).all()
    assert res.parts_v is not None and res.parts_v.shape == (g.num_v,)
    assert res.s_masks.shape == (k, (g.num_v + 31) // 32)
    assert res.neighbor_sets.shape == (k, g.num_v)
    assert res.neighbor_sets.dtype == bool
    # identical metrics schema across backends
    assert set(res.metrics.as_dict()) == {
        "k", "size_max", "mem_max", "traffic_max", "traffic_sum"}
    assert {"partition_u", "partition_v", "metrics", "total"} <= set(res.timings)
    if backend == "parallel_sim":
        assert res.traffic is not None and res.traffic.tasks == 4
    elif backend == "parallel_device":
        assert res.traffic is not None and res.traffic.pulled_bytes > 0
    else:
        assert res.traffic is None


def test_neighbor_sets_cover_assigned_vertices(small_graph):
    """S_i ⊇ N(U_i) for every backend output (dense view of s_masks)."""
    from repro.core.costs import need_matrix

    g, k = small_graph, 4
    for backend in ("host", "device_scan", "parallel_sim", "parallel_device"):
        res = partition(g, ParsaConfig(k=k, backend=backend, blocks=2,
                                       block_size=64, workers=1,
                                       refine_v=False))
        need = need_matrix(g, res.parts_u, k)
        assert not (need & ~res.neighbor_sets).any(), backend


def test_placement_composition(small_graph):
    g, k = small_graph, 4
    res = partition(g, ParsaConfig(k=k, blocks=4, init_iters=2,
                                   placement=True))
    pl = res.placement
    assert pl is not None and pl.k == k
    assert np.array_equal(pl.doc_to_shard, res.parts_u)
    assert np.array_equal(np.sort(pl.vocab_perm), np.arange(g.num_v))
    assert "placement" in res.timings


def test_refine_warm_start_matches_hand_threaded(small_graph):
    from repro.core.partition_u import partition_u_impl

    g1 = small_graph
    g2 = text_like(200, 600, mean_len=15, seed=1)
    cfg = ParsaConfig(k=4, backend="host")
    r1 = partition(g1, cfg)
    r2 = r1.refine(g2)
    want = partition_u_impl(g2, 4, init_sets=r1.neighbor_sets)
    assert np.array_equal(r2.parts_u, want.parts_u)
    assert np.array_equal(r2.neighbor_sets, want.neighbor_sets)


def test_refine_rejects_mismatched_parameter_side(small_graph):
    res = partition(small_graph, ParsaConfig(k=4, refine_v=False))
    g_other = text_like(100, small_graph.num_v + 17, mean_len=10, seed=2)
    with pytest.raises(ValueError, match="num_v"):
        res.refine(g_other)


def test_sets_views_round_trip_both_directions(small_graph):
    """host produces dense sets (packed view lazy), device_scan produces
    packed sets (dense view lazy) — both views must agree bit-for-bit."""
    from repro.kernels.parsa_cost import pack_bitmask, unpack_bitmask

    for backend in ("host", "device_scan"):
        res = partition(small_graph, ParsaConfig(
            k=4, backend=backend, block_size=64, refine_v=False))
        dense, packed = res.neighbor_sets, res.s_masks
        assert np.array_equal(pack_bitmask(dense, res.num_v), packed)
        assert np.array_equal(unpack_bitmask(packed, res.num_v), dense)


def test_unknown_backend_at_partition_time(small_graph):
    """Construction is validated; replace() re-validates too."""
    with pytest.raises(ValueError, match="unknown Parsa backend"):
        ParsaConfig(k=4).replace(backend="also-nope")


# ------------------------------------------- registry → _impl exact parity
# Each registered strategy, reached through ``partition``, returns results
# bit-identical to its implementation called directly on a fixed-seed
# 2k-vertex graph: the config → implementation plumbing adds nothing.
def _host_impl(g):
    from repro.core.partition_u import partition_u_impl

    ref = partition_u_impl(g, 8, seed=3)
    return ref.parts_u, ref.neighbor_sets, None


def _host_blocks_impl(g):
    from repro.core.subgraphs import sequential_parsa_impl

    parts, sets = sequential_parsa_impl(g, 8, b=8, a=4, seed=1)
    return parts, sets, None


def _parallel_sim_impl(g):
    from repro.core.parallel import parallel_parsa_impl

    rep, s_masks = parallel_parsa_impl(g, 8, b=8, a=2, workers=4, tau=2,
                                       seed=5)
    return rep.parts_u, s_masks, (rep.pushed_bytes, rep.pulled_bytes,
                                  rep.tasks, rep.stale_pushes_missed)


def _device_scan_impl(g):
    from repro.core.jax_partition import blocked_partition_u_impl

    parts, s_masks = blocked_partition_u_impl(g, 8, block=256,
                                              use_kernel=False, seed=7)
    return parts, s_masks, None


def _host_blocked_oracle_impl(g):
    from repro.core.jax_partition import blocked_partition_u_hostloop_impl

    parts, s_masks = blocked_partition_u_hostloop_impl(
        g, 8, block=256, use_kernel=False, seed=7)
    return parts, s_masks, None


@pytest.mark.parametrize("cfg,impl", [
    (dict(backend="host", seed=3), _host_impl),
    (dict(backend="host", blocks=8, init_iters=4, seed=1), _host_blocks_impl),
    (dict(backend="parallel_sim", workers=4, tau=2, blocks=8, init_iters=2,
          seed=5), _parallel_sim_impl),
    (dict(backend="device_scan", block_size=256, use_kernel=False, seed=7),
     _device_scan_impl),
    (dict(backend="host_blocked_oracle", block_size=256, use_kernel=False,
          seed=7), _host_blocked_oracle_impl),
], ids=["host", "host_blocks", "parallel_sim", "device_scan",
        "host_blocked_oracle"])
def test_registry_matches_impl(parity_graph, cfg, impl):
    res = partition(parity_graph, ParsaConfig(k=8, refine_v=False, **cfg))
    parts, sets, traffic = impl(parity_graph)
    assert np.array_equal(res.parts_u, parts)
    got_sets = res.neighbor_sets if sets.dtype == bool else res.s_masks
    assert np.array_equal(got_sets, sets)
    if traffic is not None:
        t = res.traffic
        assert (t.pushed_bytes, t.pulled_bytes, t.tasks,
                t.stale_pushes_missed) == traffic


def test_parity_build_placement_matches_pre_refactor_recipe(parity_graph):
    """build_placement now routes through the facade; its output must match
    the pre-refactor recipe (sequential_parsa_impl + partition_v) exactly."""
    from repro.core.partition_v import partition_v
    from repro.core.placement import build_placement, placement_from_parts
    from repro.core.subgraphs import sequential_parsa_impl

    g, k = parity_graph, 8
    pl = build_placement(g, k, b=4, a=2, seed=0)
    pu, _ = sequential_parsa_impl(g, k, b=4, a=2, seed=0)
    pv = partition_v(g, pu, k, sweeps=2)
    ref = placement_from_parts(pu, pv, g.num_v, k)
    assert np.array_equal(pl.doc_to_shard, ref.doc_to_shard)
    assert np.array_equal(pl.vocab_to_shard, ref.vocab_to_shard)
    assert np.array_equal(pl.vocab_perm, ref.vocab_perm)
    assert np.array_equal(pl.shard_row_counts, ref.shard_row_counts)
