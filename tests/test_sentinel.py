"""Sentinel-padding semantics: pow2-bucketed edge lists padded with
sentinel edges ``(n, n)`` must be *bit-equivalent* to exact-shape
execution for every edge kernel (``msbfs_dist`` / ``msbfs_set_dist`` /
``build_index``), across random graphs, random
valid-edge prefixes, the empty graph, and the all-sentinel edge case."""
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st  # hypothesis or skip-shim

from repro.core import build_index, generators
from repro.core.graph import DeviceGraph, Graph, pad_edge_list, pow2_ceil
from repro.core.index import walk_counts
from repro.core.msbfs import INF_FOR, edge_span, msbfs_dist, msbfs_set_dist
from repro.core.oracle import enumerate_paths_bruteforce, path_set


def _random_graph(n, m, seed):
    r = np.random.default_rng(seed)
    return Graph.from_edges(n, r.integers(0, n, m), r.integers(0, n, m))


def _padded(g: Graph, cap: int, reverse: bool = False):
    esrc, edst = g.r_edges_by_dst if reverse else g.edges_by_dst
    ps, pd = pad_edge_list(esrc, edst, g.n, cap)
    return jnp.asarray(ps), jnp.asarray(pd)


def _exact(g: Graph, reverse: bool = False):
    esrc, edst = g.r_edges_by_dst if reverse else g.edges_by_dst
    return jnp.asarray(esrc), jnp.asarray(edst)


class TestEdgeSpan:
    def test_rounds_up_to_chunk_and_clamps_to_cap(self):
        assert edge_span(0, 16, 64) == 0
        assert edge_span(1, 16, 64) == 16
        assert edge_span(16, 16, 64) == 16
        assert edge_span(17, 16, 64) == 32
        assert edge_span(63, 16, 64) == 64
        assert edge_span(64, 16, 64) == 64
        assert edge_span(100, 16, 64) == 64       # clamped
        assert edge_span(5, 1 << 22, 8) == 8      # chunk larger than cap

    def test_in_bucket_churn_is_one_static_value(self):
        # every valid count inside one chunk granule maps to the same
        # span: the invariant that makes m_valid safe as a static jit arg
        spans = {edge_span(m, 16, 256) for m in range(17, 33)}
        assert spans == {32}


class TestMsbfsSentinelParity:
    @given(st.integers(4, 60), st.integers(0, 200), st.integers(1, 5),
           st.integers(0, 31))
    @settings(max_examples=25, deadline=None)
    def test_msbfs_dist_bit_equal(self, n, m, k_max, seed):
        g = _random_graph(n, m, seed)
        r = np.random.default_rng(seed)
        srcs = jnp.asarray(r.integers(0, n, 4).astype(np.int32))
        cap = pow2_ceil(g.m) * int(r.integers(1, 3))   # this or next bucket
        want = np.asarray(msbfs_dist(*_exact(g), srcs, n=n, k_max=k_max))
        got = np.asarray(msbfs_dist(*_padded(g, cap), srcs, n=n, k_max=k_max))
        np.testing.assert_array_equal(got, want)
        # the chunk-rounded m_valid span must not change the answer either
        mv = edge_span(g.m, 16, cap)
        got_mv = np.asarray(msbfs_dist(*_padded(g, cap), srcs, n=n,
                                       k_max=k_max, edge_chunk=16,
                                       m_valid=mv))
        np.testing.assert_array_equal(got_mv, want)

    @given(st.integers(4, 60), st.integers(0, 200), st.integers(1, 5),
           st.integers(0, 31))
    @settings(max_examples=25, deadline=None)
    def test_msbfs_set_dist_bit_equal(self, n, m, k_max, seed):
        g = _random_graph(n, m, seed)
        r = np.random.default_rng(seed + 1)
        mask = np.zeros(n + 1, np.int8)
        mask[r.integers(0, n, 3)] = 1
        mask = jnp.asarray(mask)
        cap = pow2_ceil(max(g.m, 2))
        for reverse in (False, True):
            want = np.asarray(msbfs_set_dist(*_exact(g, reverse), mask,
                                             n=n, k_max=k_max))
            got = np.asarray(msbfs_set_dist(*_padded(g, cap, reverse), mask,
                                            n=n, k_max=k_max,
                                            m_valid=edge_span(g.m, 1 << 22,
                                                              cap)))
            np.testing.assert_array_equal(got, want)

    def test_empty_graph(self):
        g = Graph.from_edges(5, [], [])
        dg = DeviceGraph.build(g)            # pads to one sentinel edge
        dist = np.asarray(msbfs_dist(dg.esrc, dg.edst,
                                     jnp.asarray(np.array([2], np.int32)),
                                     n=g.n, k_max=3))
        INF = INF_FOR(3)
        want = np.full((g.n + 1, 1), INF, np.int8)
        want[2, 0] = 0
        np.testing.assert_array_equal(dist, want)
        tot = walk_counts(g.indptr, g.indices, 2,
                          np.full(g.n + 1, 3, np.int8), 2)
        np.testing.assert_array_equal(tot, [1.0, 0.0, 0.0])

    def test_all_sentinel_prefix(self):
        """m_valid = 0 over a non-empty padded buffer: every edge is
        sentinel, the sweep must behave exactly like the empty graph."""
        g = _random_graph(12, 40, 3)
        esrc, edst = _padded(g, pow2_ceil(g.m))
        srcs = jnp.asarray(np.array([0, 5], np.int32))
        got = np.asarray(msbfs_dist(esrc, edst, srcs, n=g.n, k_max=3,
                                    m_valid=0))
        empty = Graph.from_edges(g.n, [], [])
        want = np.asarray(msbfs_dist(*_exact(empty), srcs, n=g.n, k_max=3))
        np.testing.assert_array_equal(got, want)


class TestIndexAndEngineParity:
    def test_build_index_padded_vs_exact(self):
        g = generators.community(150, n_comm=3, avg_deg=4.0, seed=5)
        qs = generators.similar_queries(g, 6, similarity=0.7,
                                        k_range=(3, 4), seed=6)
        keys = [tuple(q) for q in qs]
        ix_pad = build_index(DeviceGraph.build(g), keys)
        ix_exact = build_index(DeviceGraph.build(g, pad=False), keys)
        np.testing.assert_array_equal(np.asarray(ix_pad.dist_s),
                                      np.asarray(ix_exact.dist_s))
        np.testing.assert_array_equal(np.asarray(ix_pad.dist_t),
                                      np.asarray(ix_exact.dist_t))

    def test_engine_results_padded_vs_unpadded(self):
        """End-to-end parity: the default (sentinel-padded) engine and one
        forced onto exact-shape device views enumerate identical path
        sets, both oracle-exact."""
        from repro.core import BatchPathEngine, EngineConfig
        g = generators.community(150, n_comm=3, avg_deg=4.0, seed=7)
        qs = generators.similar_queries(g, 5, similarity=0.7,
                                        k_range=(3, 3), seed=8)
        eng_pad = BatchPathEngine(g, EngineConfig(min_cap=64))
        assert eng_pad.dg.m_cap == pow2_ceil(g.m)
        eng_exact = BatchPathEngine(g, EngineConfig(min_cap=64))
        eng_exact.dg = DeviceGraph.build(g, pad=False)
        r_pad = eng_pad.run(qs)
        r_exact = eng_exact.run(qs)
        for qi, (s, t, k) in enumerate(qs):
            truth = path_set(enumerate_paths_bruteforce(g, s, t, k))
            assert path_set(r_pad[qi].paths) == truth, f"padded q{qi}"
            assert path_set(r_exact[qi].paths) == truth, f"exact q{qi}"
