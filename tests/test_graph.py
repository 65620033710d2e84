"""Graph container + generator invariants."""
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-shim

from repro.core.graph import Graph, DeviceGraph
from repro.core import generators


def random_graph(n, m, seed):
    r = np.random.default_rng(seed)
    return Graph.from_edges(n, r.integers(0, n, m), r.integers(0, n, m))


class TestGraph:
    def test_csr_roundtrip(self):
        g = Graph.from_edges(4, [0, 0, 1, 2], [1, 2, 2, 3])
        assert g.n == 4 and g.m == 4
        assert list(g.neighbors(0)) == [1, 2]
        assert list(g.neighbors(2, reverse=True)) == [0, 1]

    def test_dedup_and_self_loops(self):
        g = Graph.from_edges(3, [0, 0, 1, 1], [1, 1, 1, 2])
        assert g.m == 2  # dup (0,1) removed, self loop (1,1) removed

    @given(st.integers(5, 60), st.integers(0, 200), st.integers(0, 5))
    @settings(max_examples=25, deadline=None)
    def test_reverse_is_involution(self, n, m, seed):
        g = random_graph(n, m, seed)
        gr = g.reverse()
        assert np.array_equal(gr.indptr, g.r_indptr)
        for v in range(n):
            assert sorted(gr.neighbors(v)) == sorted(g.neighbors(v, reverse=True))

    @given(st.integers(5, 60), st.integers(1, 200), st.integers(0, 5))
    @settings(max_examples=25, deadline=None)
    def test_ell_covers_all_edges(self, n, m, seed):
        g = random_graph(n, m, seed)
        ell = g.ell()
        edges = set()
        for v in range(n):
            for d in range(ell.cap):
                if ell.mask[v, d]:
                    edges.add((v, int(ell.idx[v, d])))
        truth = {(int(s), int(t)) for s in range(n)
                 for t in g.neighbors(s)}
        assert edges == truth
        assert ell.spill_src.size == 0

    def test_ell_spill(self):
        g = Graph.from_edges(5, [0, 0, 0, 0], [1, 2, 3, 4])
        ell = g.ell(cap=2)
        assert ell.spill_src.size == 2
        assert set(ell.spill_dst) | {int(x) for x in ell.idx[0] if x != 5} \
            == {1, 2, 3, 4}

    def test_edges_by_dst_sorted(self):
        g = random_graph(30, 100, 1)
        src, dst = g.edges_by_dst
        assert np.all(np.diff(dst) >= 0)
        assert src.shape == dst.shape == (g.m,)

    def test_device_graph(self):
        g = random_graph(20, 60, 2)
        dg = DeviceGraph.build(g)
        assert dg.n == g.n and dg.m == g.m
        assert dg.ell_idx.shape[0] == g.n

    def test_device_graph_pow2_buckets(self):
        from repro.core.graph import pow2_ceil
        g = random_graph(20, 60, 2)
        dg = DeviceGraph.build(g)
        # edge lists sentinel-padded to the pow2 bucket, ELL caps bucketed
        assert dg.m_cap == pow2_ceil(g.m) and dg.m_valid == g.m
        for esrc, edst in ((dg.esrc, dg.edst), (dg.r_esrc, dg.r_edst)):
            assert esrc.shape == edst.shape == (dg.m_cap,)
            assert np.all(np.asarray(esrc)[g.m:] == g.n)
            assert np.all(np.asarray(edst)[g.m:] == g.n)
            assert np.all(np.diff(np.asarray(edst)) >= 0)  # stays dst-sorted
        assert dg.ell_cap == pow2_ceil(int(g.out_degree().max()))
        assert dg.r_ell_cap == pow2_ceil(int(g.in_degree().max()))
        # pad=False restores the exact legacy shapes
        dgx = DeviceGraph.build(g, pad=False)
        assert dgx.m_cap == g.m
        assert dgx.ell_cap == int(g.out_degree().max())

    def test_device_graph_empty_graph_pads_to_one_sentinel(self):
        g = Graph.from_edges(4, [], [])
        dg = DeviceGraph.build(g)
        assert dg.m == 0 and dg.m_cap == 1
        assert int(dg.esrc[0]) == g.n and int(dg.edst[0]) == g.n


class TestGenerators:
    @pytest.mark.parametrize("gen,kw", [
        (generators.powerlaw, {}), (generators.erdos, {}),
        (generators.community, {"n_comm": 3})])
    def test_generators_basic(self, gen, kw):
        g = gen(200, avg_deg=4.0, seed=3, **kw)
        assert g.n == 200
        assert 0 < g.m <= 200 * 4.5
        assert g.indices.max() < 200

    def test_grid_degree(self):
        g = generators.grid(5)
        assert g.n == 25
        assert np.all(g.out_degree() == 4)

    def test_random_queries_reachable(self):
        from repro.core.oracle import bfs_dist_from
        g = generators.erdos(100, 4.0, seed=4)
        qs = generators.random_queries(g, 10, (2, 5), seed=5)
        for s, t, k in qs:
            assert bfs_dist_from(g, s, k)[t] <= k


def _rgg(n, seed, radius_c=0.55):
    """A DIMACS10-style random geometric graph, every edge both ways."""
    from scipy.spatial import cKDTree
    p = np.random.default_rng(seed).random((n, 2))
    pairs = cKDTree(p).query_pairs(radius_c * np.sqrt(np.log(n) / n),
                                   output_type="ndarray")
    a, b = pairs[:, 0], pairs[:, 1]
    return Graph.from_edges(n, np.r_[a, b], np.r_[b, a])


def _delaunay(n, seed):
    """A DIMACS10-style Delaunay triangulation, every edge both ways."""
    from scipy.spatial import Delaunay
    tri = Delaunay(np.random.default_rng(seed).random((n, 2))).simplices
    e = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    return Graph.from_edges(n, np.r_[e[:, 0], e[:, 1]],
                            np.r_[e[:, 1], e[:, 0]])


LAYOUT_GRAPHS = {
    "random": lambda: random_graph(300, 1500, 4),
    "powerlaw": lambda: generators.powerlaw(400, 4.0, seed=5),
    "isolated": lambda: Graph.from_edges(50, [0, 1, 2], [1, 2, 3]),
    "empty": lambda: Graph.from_edges(6, [], []),
    "rgg": lambda: _rgg(4096, 1),
    "delaunay": lambda: _delaunay(4096, 2),
}


class TestSlicedEll:
    @pytest.mark.parametrize("name", sorted(LAYOUT_GRAPHS))
    def test_layout_holds_every_arc_once(self, name):
        """Each direction's sliced ELL: perm and inv_perm are inverse
        permutations, rows sorted by degree, at most MAX_SLICES tables
        as wide as their widest row, and every valid arc of the row's
        vertex exactly once (as a renumbered position), pad = n."""
        from repro.core.graph import MAX_SLICES
        g = LAYOUT_GRAPHS[name]()
        dg = DeviceGraph.build(g)
        for sl, ip, ix in ((dg.ell_sliced, g.indptr, g.indices),
                           (dg.r_ell_sliced, g.r_indptr, g.r_indices)):
            perm, inv = np.asarray(sl.perm), np.asarray(sl.inv_perm)
            np.testing.assert_array_equal(perm[inv], np.arange(g.n))
            np.testing.assert_array_equal(inv[perm], np.arange(g.n))
            assert 1 <= len(sl.tables) <= MAX_SLICES
            deg = np.diff(ip)[perm]
            assert np.all(np.diff(deg) <= 0)
            rows = np.concatenate([
                np.pad(np.asarray(t), ((0, 0), (0, max(sl.widths) - w)),
                       constant_values=g.n)
                for t, w in zip(sl.tables, sl.widths)])
            assert rows.shape[0] == g.n
            for t, w in zip(sl.tables, sl.widths):
                assert w == (np.asarray(t) != g.n).sum(axis=1).max(
                    initial=0)
            for p in range(g.n):
                got = rows[p][rows[p] != g.n]
                want = inv[ix[ip[perm[p]]:ip[perm[p] + 1]]]
                np.testing.assert_array_equal(got, want)
            assert sl.rows_per_level == (rows != g.n).sum() + sum(
                (np.asarray(t) == g.n).sum() for t in sl.tables)

    @pytest.mark.parametrize("name", ["rgg", "delaunay"])
    def test_sliced_rows_per_level_near_arcs(self, name):
        """On DIMACS10-style rgg and Delaunay draws a sweep level gathers
        at most 1.10 rows per valid arc (the padded ELL: n * pow2(max
        degree))."""
        g = LAYOUT_GRAPHS[name]()
        dg = DeviceGraph.build(g)
        for sl in (dg.ell_sliced, dg.r_ell_sliced):
            assert g.m <= sl.rows_per_level <= 1.10 * g.m

    def test_slice_bounds_minimise_entries(self):
        """The DP's cuts against every way to cut a small degree list."""
        from itertools import combinations
        from repro.core.graph import slice_bounds
        deg = np.array([9, 7, 7, 5, 4, 4, 4, 2, 1, 1, 0, 0])

        def entries(b):
            return sum((hi - lo) * deg[lo] for lo, hi in zip(b[:-1], b[1:]))
        for k in (1, 2, 3, 8):
            best = min(entries([0, *c, deg.size])
                       for r in range(k)
                       for c in combinations(range(1, deg.size), r))
            got = slice_bounds(deg, k)
            assert len(got) - 1 <= k and got[0] == 0 and got[-1] == deg.size
            assert entries(got) == best
