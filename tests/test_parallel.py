"""Alg 4 parallel Parsa: staleness robustness (§5.4) + the TPU-native
blocked/bitmask reformulation (DESIGN §2)."""
import numpy as np
import pytest

from repro.api import ParsaConfig, partition
from repro.core import evaluate, global_initialization, partition_v, random_parts
from repro.graphs import text_like


def _quality(g, parts_u, k):
    return evaluate(g, parts_u, partition_v(g, parts_u, k), k).traffic_max


def _parallel_sim(g, k, b, init_sets=None, **kw):
    """The Alg 4 parameter-server simulation over ``b`` subgraphs, through
    the facade with refine off."""
    return partition(g, ParsaConfig(k=k, backend="parallel_sim", blocks=b,
                                    refine_v=False, **kw),
                     init_sets=init_sets)


def test_parallel_matches_sequential_quality(small_text_graph):
    g, k = small_text_graph, 8
    seq = _parallel_sim(g, k, 8, workers=1, tau=0)
    par = _parallel_sim(g, k, 8, workers=4, tau=2)
    q_seq, q_par = _quality(g, seq.parts_u, k), _quality(g, par.parts_u, k)
    # §5.4: staleness costs at most a few percent (allow 25% on tiny graphs)
    assert q_par <= q_seq * 1.25
    assert par.traffic.stale_pushes_missed > 0  # staleness actually exercised


def test_eventual_consistency_still_beats_random(small_text_graph):
    g, k = small_text_graph, 8
    par = _parallel_sim(g, k, 16, workers=8, tau=None)
    rand = _quality(g, random_parts(g.num_u, k, 0), k)
    assert _quality(g, par.parts_u, k) < rand


def test_global_initialization_helps(small_ctr_graph):
    g, k = small_ctr_graph, 8
    cold = _parallel_sim(g, k, 8, workers=4, tau=1, seed=1)
    S0 = global_initialization(g, k, sample_frac=0.1, seed=1)
    warm = _parallel_sim(g, k, 8, init_sets=S0, workers=4, tau=1, seed=1)
    assert _quality(g, warm.parts_u, k) <= _quality(g, cold.parts_u, k) * 1.1


def test_parallel_sim_w1_tau0_equals_host_backend(small_text_graph):
    """Degenerate parity: one worker with no delay is the §4.2 sequential
    subgraph stream — bit-identical parts and (packed) sets vs the host
    backend at the same block count."""
    from repro.core.parallel import parallel_parsa_impl
    from repro.kernels.parsa_cost import pack_bitmask

    g, k, b = small_text_graph, 8, 8
    host = partition(g, ParsaConfig(k=k, backend="host", blocks=b, seed=3,
                                    refine_v=False))
    rep, s_packed = parallel_parsa_impl(g, k, b=b, workers=1, tau=0, seed=3)
    assert np.array_equal(rep.parts_u, host.parts_u)
    assert np.array_equal(s_packed, pack_bitmask(host.neighbor_sets, g.num_v))
    assert rep.stale_pushes_missed == 0


def test_parallel_sim_server_stays_packed_no_dense_snapshot(small_text_graph):
    """The satellite guarantee: the server state is packed words end to end
    and the worker pull is handed to Alg 3 without a per-task dense copy —
    the scratch partition_u_impl mutates IS the array it was given."""
    import repro.core.parallel as par

    g, k = small_text_graph, 8
    adopted = []
    real = par.partition_u_impl

    def spy(sg, kk, init_sets=None, copy_init=True, **kw):
        res = real(sg, kk, init_sets=init_sets, copy_init=copy_init, **kw)
        adopted.append(res.neighbor_sets is init_sets)
        return res

    par.partition_u_impl = spy
    try:
        rep, s_packed = par.parallel_parsa_impl(g, k, b=4, a=2, workers=2,
                                                tau=1, seed=0)
    finally:
        par.partition_u_impl = real
    assert adopted and all(adopted)  # no dense snapshot between pull and run
    assert s_packed.dtype == np.int32
    assert s_packed.shape == (k, (g.num_v + 31) // 32)
    assert (rep.parts_u >= 0).all()


def test_parallel_sim_peak_memory_bounded():
    """Allocation assertion: with the packed server state, peak incremental
    memory stays near ONE dense (k, |V|) worker scratch — the old dense
    server + per-task snapshot + Alg-3 copy (3×dense concurrent, plus dense
    pending pushes) would blow this bound."""
    import tracemalloc

    from repro.core.parallel import parallel_parsa_impl
    from repro.graphs import text_like

    # k large enough that the dense (k, |V|) term dominates the
    # k-independent per-subgraph CSC transients
    g = text_like(300, 200_000, mean_len=10, seed=1)
    k, b = 64, 4
    dense_bytes = k * g.num_v  # one (k, |V|) bool scratch
    parallel_parsa_impl(g, k, b=b, workers=2, tau=1, seed=0)  # warm imports
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    parallel_parsa_impl(g, k, b=b, workers=2, tau=1, seed=0)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    # one pull scratch + pack transients ≈ 2×dense; the old layout held
    # ≥ 3×dense concurrently (server + snapshot + Alg-3 copy) plus up to
    # W+τ dense pending pushes in flight
    assert peak - base < 2.5 * dense_bytes, (peak - base, dense_bytes)


def test_blocked_jax_partitioner(small_text_graph):
    """TPU-native blocked greedy: balanced, complete, beats random."""
    g, k = small_text_graph, 8
    parts = partition(g, ParsaConfig(
        k=k, backend="device_scan", block_size=128, use_kernel=True,
        refine_v=False)).parts_u
    assert np.all(parts >= 0)
    sizes = np.bincount(parts, minlength=k)
    assert sizes.max() - sizes.min() <= 1
    assert _quality(g, parts, k) < _quality(
        g, random_parts(g.num_u, k, 0), k)
