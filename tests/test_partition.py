"""Partitioner invariants (Algorithms 2 & 3) — property-based."""
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.api import ParsaConfig, partition
from repro.core import (
    evaluate, from_edges, need_matrix, partition_v, random_parts,
)
from repro.core.partition_u import partition_u_impl
from repro.graphs import text_like


@st.composite
def bipartite_graphs(draw):
    nu = draw(st.integers(5, 60))
    nv = draw(st.integers(5, 60))
    ne = draw(st.integers(1, 300))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    eu = rng.integers(0, nu, size=ne)
    ev = rng.integers(0, nv, size=ne)
    return from_edges(nu, nv, eu, ev)


@given(g=bipartite_graphs(), k=st.integers(2, 8))
@settings(max_examples=30, deadline=None)
def test_partition_u_invariants(g, k):
    res = partition_u_impl(g, k)
    # disjoint cover
    assert res.parts_u.shape == (g.num_u,)
    assert np.all(res.parts_u >= 0) and np.all(res.parts_u < k)
    # perfect balance (select="size", one vertex at a time — §4.1)
    sizes = np.bincount(res.parts_u, minlength=k)
    assert sizes.max() - sizes.min() <= 1
    # returned neighbor sets == N(U_i)
    assert np.array_equal(res.neighbor_sets, need_matrix(g, res.parts_u, k))


@given(g=bipartite_graphs(), k=st.integers(2, 8))
@settings(max_examples=30, deadline=None)
def test_partition_v_invariants(g, k):
    parts_u = partition_u_impl(g, k).parts_u
    need = need_matrix(g, parts_u, k)
    parts_v = partition_v(g, parts_u, k)
    for j in range(g.num_v):
        if need[:, j].any():
            assert parts_v[j] >= 0
            assert need[parts_v[j], j]  # v_ij ≤ u_ij (8b)
        else:
            assert parts_v[j] == -1     # isolated → unassigned


@given(g=bipartite_graphs(), k=st.integers(2, 6))
@settings(max_examples=20, deadline=None)
def test_repeated_sweeps_never_worse(g, k):
    """§3.2: repeated sweeps improve until convergence (convex ⇒ global)."""
    parts_u = partition_u_impl(g, k).parts_u
    m1 = evaluate(g, parts_u, partition_v(g, parts_u, k, sweeps=1), k)
    m3 = evaluate(g, parts_u, partition_v(g, parts_u, k, sweeps=3), k)
    assert m3.traffic_max <= m1.traffic_max


def test_cost_definition_matches_bruteforce():
    g = text_like(60, 150, mean_len=10, seed=3)
    k = 4
    parts_u = partition_u_impl(g, k).parts_u
    parts_v = partition_v(g, parts_u, k)
    m = evaluate(g, parts_u, parts_v, k)
    # brute force with python sets
    N = [set() for _ in range(k)]
    for u in range(g.num_u):
        N[parts_u[u]].update(g.neighbors(u).tolist())
    for i in range(k):
        Vi = set(np.flatnonzero(parts_v == i).tolist())
        worker = len(N[i] - Vi)
        server = sum(len(Vi & N[j]) for j in range(k) if j != i)
        assert m.footprint[i] == len(N[i])
        assert m.traffic[i] == worker + server


def test_parsa_beats_random_on_traffic(small_text_graph, small_ctr_graph):
    k = 8
    for g in (small_text_graph, small_ctr_graph):
        pu = partition(g, ParsaConfig(k=k, backend="host", blocks=4,
                                      init_iters=2, refine_v=False)).parts_u
        pv = partition_v(g, pu, k)
        m = evaluate(g, pu, pv, k)
        mr = evaluate(g, random_parts(g.num_u, k, 0), random_parts(g.num_v, k, 1), k)
        assert m.traffic_max < mr.traffic_max
        assert m.traffic_sum < mr.traffic_sum


def test_init_sets_carry_over():
    """Incremental partitioning: warm S_i must change (and not hurt) results."""
    g = text_like(200, 500, mean_len=15, seed=5)
    k = 4
    r1 = partition_u_impl(g, k)
    r2 = partition_u_impl(g, k, init_sets=r1.neighbor_sets)
    assert np.array_equal(
        r2.neighbor_sets & ~r1.neighbor_sets,
        need_matrix(g, r2.parts_u, k) & ~r1.neighbor_sets)
