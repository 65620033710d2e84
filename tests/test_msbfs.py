"""MS-BFS index vs host BFS oracle (+ packed kernel parity)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-shim

from repro.core.graph import Graph, DeviceGraph
from repro.core.msbfs import msbfs_dist, INF_FOR
from repro.core.oracle import bfs_dist_from
from repro.core import generators


def _check(g: Graph, sources, k_max):
    dg = DeviceGraph.build(g)
    dist = np.asarray(msbfs_dist(dg.esrc, dg.edst, jnp.asarray(sources),
                                 n=g.n, k_max=k_max))
    INF = INF_FOR(k_max)
    for i, s in enumerate(sources):
        truth = bfs_dist_from(g, int(s), k_max)
        got = dist[:-1, i].astype(np.int32)
        got = np.where(got >= INF, k_max + 1, got)
        assert np.array_equal(got, truth), f"source {s}"
    assert np.all(dist[-1] == INF)  # sentinel row


@given(st.integers(5, 80), st.integers(0, 300), st.integers(1, 6),
       st.integers(0, 10))
@settings(max_examples=20, deadline=None)
def test_msbfs_matches_oracle(n, m, k_max, seed):
    r = np.random.default_rng(seed)
    g = Graph.from_edges(n, r.integers(0, n, m), r.integers(0, n, m))
    sources = r.integers(0, n, size=min(8, n)).astype(np.int32)
    _check(g, sources, k_max)


def test_msbfs_reverse_direction():
    g = generators.erdos(60, 3.0, seed=7)
    dg = DeviceGraph.build(g)
    tgts = np.array([3, 11], np.int32)
    dist = np.asarray(msbfs_dist(dg.r_esrc, dg.r_edst, jnp.asarray(tgts),
                                 n=g.n, k_max=4))
    for i, t in enumerate(tgts):
        truth = bfs_dist_from(g, int(t), 4, reverse=True)
        got = np.where(dist[:-1, i] >= 5, 5, dist[:-1, i])
        assert np.array_equal(got.astype(np.int32), truth)


def test_msbfs_edge_chunking_invariant():
    g = generators.erdos(50, 4.0, seed=8)
    dg = DeviceGraph.build(g)
    srcs = jnp.asarray(np.array([0, 1, 2], np.int32))
    a = msbfs_dist(dg.esrc, dg.edst, srcs, n=g.n, k_max=4)
    b = msbfs_dist(dg.esrc, dg.edst, srcs, n=g.n, k_max=4, edge_chunk=17)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_packed_msbfs_hop_matches_dense():
    """kernels/msbfs_expand (packed ELL) == one unpacked msbfs hop."""
    from repro.kernels.msbfs_expand import ops as mops
    from repro.kernels.msbfs_expand.ref import pack_bits, unpack_bits
    from repro.core.msbfs import msbfs_hop
    g = generators.powerlaw(80, 4.0, seed=9)
    dg = DeviceGraph.build(g)
    r = np.random.default_rng(0)
    S = 37
    frontier = r.random((g.n + 1, S)) < 0.2
    frontier[-1] = False
    dense_next = np.asarray(msbfs_hop(jnp.asarray(frontier, jnp.int8),
                                      dg.esrc, dg.edst, g.n))
    # packed path uses the reverse-ELL (in-neighbors OR)
    words = pack_bits(jnp.asarray(frontier))
    nxt = mops.msbfs_hop_packed(dg.r_ell_idx, words, backend="jnp")
    unpacked = np.asarray(unpack_bits(nxt, S))
    assert np.array_equal(unpacked[:-1], dense_next[:-1].astype(bool))


@pytest.mark.parametrize("reverse", [False, True])
def test_oracle_bfs_matches_plain_bfs(reverse):
    """The oracle's level-synchronous BFS against a one-vertex-at-a-time
    deque BFS, on a seeded community graph with a hub (reverse: in-edges)."""
    from collections import deque
    g = generators.community(3000, n_comm=6, avg_deg=5, seed=11)
    hub = np.arange(1, 3000, 7)
    g = Graph.from_edges(
        g.n, np.concatenate([np.repeat(np.arange(g.n), np.diff(g.indptr)),
                             np.zeros(hub.size, np.int64)]),
        np.concatenate([g.indices, hub]))
    k = 5
    for s in (0, 17, 2999):
        want = np.full(g.n, k + 1, np.int32)
        want[s] = 0
        todo = deque([s])
        while todo:
            u = todo.popleft()
            if want[u] == k:
                continue
            for v in g.neighbors(u, reverse=reverse):
                if want[v] > want[u] + 1:
                    want[v] = want[u] + 1
                    todo.append(int(v))
        got = bfs_dist_from(g, s, k, reverse=reverse)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n_cols,seed", [(1, 0), (5, 1), (32, 2), (33, 3)])
def test_slack_vector_matches_per_consumer_max(n_cols, seed):
    """slack[v] = max over consumers of k - off - dist[v, col], with INF
    distances and the sentinel row giving -1, several consumers per column."""
    from repro.core.index import column_reach, slack_vector
    r = np.random.default_rng(seed)
    n, inf = 200, INF_FOR(6)
    dist = r.integers(0, inf + 1, (n + 1, n_cols)).astype(np.int8)
    dist[-1] = inf
    n_cons = int(r.integers(1, 2 * n_cols + 1))
    cols = r.integers(0, n_cols, n_cons)
    reach = r.integers(0, 7, n_cons)
    want = np.full(n + 1, -1, np.int32)
    for c, k in zip(cols, reach):
        val = np.where(dist[:, c] >= inf, -1, k - dist[:, c].astype(np.int32))
        want = np.maximum(want, val)
    want[-1] = -1
    got = slack_vector(jnp.asarray(dist),
                       jnp.asarray(column_reach(n_cols, cols, reach)), inf)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("reverse,budget", [(False, 1), (False, 4),
                                            (True, 3), (True, 5)])
def test_walk_counts_matches_dense_dp(reverse, budget):
    """The host frontier DP equals the dense per-level DP over all n."""
    from repro.core.index import walk_counts
    g = generators.community(400, n_comm=4, avg_deg=4, seed=budget)
    indptr, indices = ((g.r_indptr, g.r_indices) if reverse
                       else (g.indptr, g.indices))
    r = np.random.default_rng(budget)
    slack = r.integers(-1, budget + 1, g.n + 1).astype(np.int8)
    src = int(r.integers(0, g.n))
    c = np.zeros(g.n)
    c[src] = 1.0
    want = [1.0]
    rows = np.repeat(np.arange(g.n), np.diff(indptr))
    for lvl in range(1, budget + 1):
        nxt = np.zeros(g.n)
        np.add.at(nxt, indices, c[rows])
        c = nxt * (slack[:-1] >= lvl)
        want.append(c.sum())
    got = walk_counts(indptr, indices, src, slack, budget)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# packed sweeps: the sliced ELL against the padded ELL and the edge lists
# ---------------------------------------------------------------------------

def _star_hub(n=70):
    """Vertex 0 points at every other vertex and back: one row of degree
    n-1 beside n-1 rows of degree 1."""
    leaves = np.arange(1, n)
    return Graph.from_edges(n, np.r_[np.zeros(n - 1, np.int64), leaves],
                            np.r_[leaves, np.zeros(n - 1, np.int64)])


def _isolated(n=60):
    """A path over the even vertices; every odd vertex has no arc."""
    ev = np.arange(0, n - 2, 2)
    return Graph.from_edges(n, ev, ev + 2)


def _regular(n=48, d=3):
    """Every vertex of out- and in-degree d (a circulant)."""
    src = np.repeat(np.arange(n), d)
    return Graph.from_edges(n, src, (src + np.tile([1, 5, 11], n)) % n)


def _poisson(n=90, avg=4.0, seed=3):
    r = np.random.default_rng(seed)
    src = np.repeat(np.arange(n), r.poisson(avg, n))
    return Graph.from_edges(n, src, r.integers(0, n, src.size))


SWEEP_GRAPHS = {"star_hub": _star_hub, "isolated": _isolated,
                "regular": _regular, "poisson": _poisson}


# each source count takes two hop budgets, one per direction, so the four
# counts cover k_max 1..8 on every graph
SWEEP_CASES = {1: (1, 5), 31: (2, 6), 33: (3, 7), 64: (4, 8)}


@pytest.mark.parametrize("S", sorted(SWEEP_CASES))
@pytest.mark.parametrize("graph", sorted(SWEEP_GRAPHS))
def test_sliced_sweep_bit_equal(graph, S):
    """msbfs_dist_ell over the sliced ELL == over the padded ELL == the
    edge-list msbfs_dist, in both directions, with repeated sources
    (W = 1 and 2 words)."""
    from repro.core.graph import SlicedEll
    from repro.core.msbfs import msbfs_dist_ell
    g = SWEEP_GRAPHS[graph]()
    dg = DeviceGraph.build(g)
    assert isinstance(dg.sweep_table(True), SlicedEll)
    r = np.random.default_rng(S)
    srcs = r.integers(0, g.n, S).astype(np.int32)
    srcs[S // 2:] = srcs[:S - S // 2][::-1]        # repeats
    srcs = jnp.asarray(srcs)
    for k, reverse in zip(SWEEP_CASES[S], (True, False)):
        es, ed = (dg.esrc, dg.edst) if reverse else (dg.r_esrc, dg.r_edst)
        padded = dg.r_ell_idx if reverse else dg.ell_idx
        want = np.asarray(msbfs_dist(es, ed, srcs, n=g.n, k_max=k))
        for table in (dg.sweep_table(reverse), padded):
            got = msbfs_dist_ell(table, srcs, n=g.n, k_max=k)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"{reverse} k={k}")


@pytest.mark.parametrize("backend,k_max", [("jnp", 1), ("interpret", 3),
                                           ("jnp", 8)])
@pytest.mark.parametrize("graph", sorted(SWEEP_GRAPHS))
def test_sliced_set_sweep_bit_equal(graph, backend, k_max):
    """msbfs_set_dist_ell over the sliced ELL == over the padded ELL ==
    the edge-list msbfs_set_dist, in both directions."""
    from repro.core.msbfs import msbfs_set_dist, msbfs_set_dist_ell
    g = SWEEP_GRAPHS[graph]()
    dg = DeviceGraph.build(g)
    mask = np.zeros(g.n + 1, np.int8)
    mask[[0, 3, g.n - 1]] = 1
    mask = jnp.asarray(mask)
    for reverse, (es, ed) in ((True, (dg.esrc, dg.edst)),
                              (False, (dg.r_esrc, dg.r_edst))):
        padded = dg.r_ell_idx if reverse else dg.ell_idx
        want = np.asarray(msbfs_set_dist(es, ed, mask, n=g.n, k_max=k_max))
        for table in (dg.sweep_table(reverse), padded):
            got = msbfs_set_dist_ell(table, mask, n=g.n, k_max=k_max,
                                     backend=backend)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=f"{reverse}")


def test_index_counts_gathered_rows_per_layout():
    """build_index on a kernel backend counts each sweep's gathered rows
    under its layout and the valid arcs x hops it relaxes."""
    from repro.core.index import build_index
    from repro.core.msbfs import swept
    from repro.obs import metrics as obsmetrics
    g = _poisson()
    dg = DeviceGraph.build(g)
    rows = obsmetrics.registry().counter("engine_index_rows_total",
                                         layout="sliced")
    rows0, (all0, arcs0) = rows.value, swept()
    build_index(dg, [(0, 5, 4), (1, 7, 3)], backend="interpret")
    per_level = (dg.ell_sliced.rows_per_level
                 + dg.r_ell_sliced.rows_per_level)
    assert rows.value - rows0 == 4 * per_level
    all1, arcs1 = swept()
    assert all1 - all0 == 4 * per_level and arcs1 - arcs0 == 2 * 4 * g.m
