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
