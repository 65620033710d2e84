"""Unified kernel-backend API: registry semantics, the MS-BFS step and the
packed sweeps against references, and engine-level oracle exactness under
interpret-mode dispatch."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-shim

from repro.core.graph import DeviceGraph, Graph
from repro.kernels.registry import (ENV_VAR, KernelBackend, dispatch,
                                    registered_ops, resolve_backend)


def _random_graph(n, avg_deg, seed):
    r = np.random.default_rng(seed)
    m = int(n * avg_deg)
    e = r.integers(0, n, size=(m, 2))
    e = e[e[:, 0] != e[:, 1]]
    return Graph.from_edges(n, e[:, 0], e[:, 1])


class TestRegistry:
    def test_coerce_accepts_enum_and_strings(self):
        assert KernelBackend.coerce("pallas") is KernelBackend.PALLAS
        assert KernelBackend.coerce("INTERPRET") is KernelBackend.INTERPRET
        assert KernelBackend.coerce(KernelBackend.JNP) is KernelBackend.JNP

    def test_unknown_backend_raises_listing_valid(self):
        with pytest.raises(ValueError, match="pallas | interpret | jnp"):
            resolve_backend("palas")   # typo must not silently fall back

    def test_str_enum_compares_to_value(self):
        # call sites use plain string comparison on the static jit arg
        assert KernelBackend.INTERPRET == "interpret"
        assert str(KernelBackend.JNP) == "jnp"
        assert KernelBackend.PALLAS.uses_kernel
        assert KernelBackend.INTERPRET.uses_kernel
        assert not KernelBackend.JNP.uses_kernel

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv(ENV_VAR, "interpret")
        assert resolve_backend() is KernelBackend.INTERPRET
        # explicit beats env
        assert resolve_backend("jnp") is KernelBackend.JNP
        monkeypatch.setenv(ENV_VAR, "bogus")
        with pytest.raises(ValueError):
            resolve_backend()

    def test_auto_resolution_off_tpu(self, monkeypatch):
        monkeypatch.delenv(ENV_VAR, raising=False)
        import jax
        expect = (KernelBackend.PALLAS if jax.default_backend() == "tpu"
                  else KernelBackend.JNP)
        assert resolve_backend() is expect

    def test_dispatch_unknown_op(self):
        with pytest.raises(KeyError):
            dispatch("not_an_op", "jnp")

    def test_every_registered_op_dispatches(self):
        for name in registered_ops():
            for kb in KernelBackend:
                assert callable(dispatch(name, kb))

    def test_jnp_only_ops_dispatch_their_jnp_arm(self):
        from repro.kernels.registry import JNP_ONLY_OPS
        assert set(JNP_ONLY_OPS) == {"msbfs_expand", "msbfs_step"}
        for name in JNP_ONLY_OPS:
            jnp_arm = dispatch(name, "jnp")
            for kb in KernelBackend:
                assert dispatch(name, kb) is jnp_arm

    def test_register_op_arms_match_the_static_table(self):
        from repro.kernels.registry import register_op
        with pytest.raises(ValueError, match="JNP_ONLY_OPS"):
            register_op("not_listed", jnp=len)          # missing pallas arm
        with pytest.raises(ValueError, match="JNP_ONLY_OPS"):
            register_op("msbfs_step", jnp=len, pallas=len, interpret=len)


class TestEngineBackendConfig:
    def test_bogus_backend_raises_at_init(self):
        from repro.core.engine import BatchPathEngine, EngineConfig
        g = _random_graph(20, 3, 0)
        with pytest.raises(ValueError, match="valid backends"):
            BatchPathEngine(g, EngineConfig(kernel_backend="bogus"))

    def test_deprecated_backend_field_warns(self):
        from repro.core.engine import BatchPathEngine, EngineConfig
        g = _random_graph(20, 3, 0)
        with pytest.warns(DeprecationWarning, match="kernel_backend"):
            eng = BatchPathEngine(g, EngineConfig(backend="jnp"))
        assert eng.kernel_backend is KernelBackend.JNP

    def test_default_config_does_not_warn(self):
        from repro.core.engine import BatchPathEngine, EngineConfig
        g = _random_graph(20, 3, 0)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            BatchPathEngine(g, EngineConfig())

    def test_stats_record_backend(self):
        from repro.core.engine import BatchPathEngine, EngineConfig
        g = _random_graph(30, 3, 1)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              kernel_backend="interpret"))
        r = eng.run([(0, 5, 3)])
        assert r.stats["kernel_backend"] == "interpret"

    def test_session_kwarg_and_batch_log(self):
        from repro.core.engine import EngineConfig
        from repro.core.session import PathSession
        g = _random_graph(30, 3, 1)
        ses = PathSession(g, EngineConfig(min_cap=64),
                          kernel_backend="interpret")
        assert ses.kernel_backend == "interpret"
        ses.submit((0, 5, 3))
        ses.results()
        assert all(b["kernel_backend"] == "interpret"
                   for b in ses.batch_log)


class TestFusedStepParity:
    """msbfs_step: expand + dedup against the visited set vs plain numpy."""

    @given(st.integers(4, 90), st.integers(1, 6), st.integers(1, 3),
           st.integers(0, 6))
    @settings(max_examples=10, deadline=None)
    def test_property(self, V, D, W, seed):
        from repro.kernels.msbfs_expand.ops import msbfs_step
        r = np.random.default_rng(seed)
        ell = jnp.asarray(r.integers(0, V + 1, (V, D)).astype(np.int32))
        fr = jnp.asarray(r.integers(0, 2**32, (V + 1, W), dtype=np.uint64)
                         .astype(np.uint32)).at[-1].set(0)
        vis = jnp.asarray(r.integers(0, 2**32, (V, W), dtype=np.uint64)
                          .astype(np.uint32))
        got = msbfs_step(ell, fr, vis, backend="jnp")
        acc = np.bitwise_or.reduce(np.asarray(fr)[np.asarray(ell)], axis=1)
        new = acc & ~np.asarray(vis)
        for x, y in zip(got, (new, np.asarray(vis) | new)):
            assert np.array_equal(np.asarray(x), y)

    def test_all_sentinel_ell(self):
        # a fully padded ELL table (empty graph row bucket) expands nothing
        from repro.kernels.msbfs_expand.ops import msbfs_step
        V, W = 17, 2
        ell = jnp.full((V, 4), V, jnp.int32)
        fr = jnp.ones((V + 1, W), jnp.uint32).at[-1].set(0)
        vis = jnp.zeros((V, W), jnp.uint32)
        nf, nv = msbfs_step(ell, fr, vis, backend="jnp")
        assert not np.asarray(nf).any()
        assert not np.asarray(nv).any()


class TestSweepParity:
    """Whole packed sweeps vs the segment-op reference on DeviceGraphs."""

    @given(st.integers(2, 120), st.floats(0.0, 5.0), st.integers(1, 40),
           st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_msbfs_dist_ell(self, n, avg_deg, S, seed):
        from repro.core.msbfs import edge_span, msbfs_dist, msbfs_dist_ell
        g = _random_graph(n, avg_deg, seed)
        dg = DeviceGraph.build(g)
        r = np.random.default_rng(seed)
        srcs = jnp.asarray(r.integers(0, n, S).astype(np.int32))
        mv = edge_span(dg.m, 1 << 22, dg.m_cap)
        for ell, es, ed in ((dg.r_ell_idx, dg.esrc, dg.edst),
                            (dg.ell_idx, dg.r_esrc, dg.r_edst)):
            ref = msbfs_dist(es, ed, srcs, n=dg.n, k_max=4, m_valid=mv)
            got = msbfs_dist_ell(ell, srcs, n=dg.n, k_max=4,
                                 backend="interpret")
            assert np.array_equal(np.asarray(ref), np.asarray(got))

    def test_set_dist_ell(self):
        from repro.core.msbfs import (edge_span, msbfs_set_dist,
                                      msbfs_set_dist_ell)
        g = _random_graph(64, 4, 7)
        dg = DeviceGraph.build(g)
        seed = np.zeros(dg.n + 1, np.int8)
        seed[[3, 9, 40]] = 1
        seed = jnp.asarray(seed)
        mv = edge_span(dg.m, 1 << 22, dg.m_cap)
        ref = msbfs_set_dist(dg.esrc, dg.edst, seed, n=dg.n, k_max=5,
                             m_valid=mv)
        got = msbfs_set_dist_ell(dg.r_ell_idx, seed, n=dg.n, k_max=5,
                                 backend="interpret")
        assert np.array_equal(np.asarray(ref), np.asarray(got))

    def test_empty_graph(self):
        from repro.core.msbfs import msbfs_dist, msbfs_dist_ell
        g = Graph.from_edges(5, np.empty(0, np.int32), np.empty(0, np.int32))
        dg = DeviceGraph.build(g)
        srcs = jnp.asarray(np.array([0, 3], np.int32))
        ref = msbfs_dist(dg.esrc, dg.edst, srcs, n=dg.n, k_max=3)
        got = msbfs_dist_ell(dg.r_ell_idx, srcs, n=dg.n, k_max=3,
                             backend="interpret")
        assert np.array_equal(np.asarray(ref), np.asarray(got))

class TestJoinParity:
    """Row-aligned overlap join validity vs the dense _dup_mask route, on
    engine-realistic rows (each half individually simple)."""

    @staticmethod
    def _simple_rows(r, N, L, hi):
        rows = np.full((N, L), -1, np.int32)
        for i in range(N):
            rows[i] = r.choice(hi, size=L, replace=False)
        return rows

    @given(st.integers(1, 40), st.integers(1, 40), st.integers(1, 4),
           st.integers(1, 4), st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_keyed_join(self, NA, NB, a_col, b_col, seed):
        from repro.core.join import keyed_join, sort_by_last
        r = np.random.default_rng(seed)
        A = self._simple_rows(r, NA, a_col + 1, 30)
        B = self._simple_rows(r, NB, b_col + 1, 30)
        a = sort_by_last(jnp.asarray(A), jnp.int32(NA), col=a_col)
        width = a_col + b_col + 1
        cap = 256
        pj = keyed_join(a, jnp.asarray(B), jnp.int32(NB), a_col=a_col,
                        b_col=b_col, out_cap=cap, out_width=width,
                        backend="jnp")
        pk = keyed_join(a, jnp.asarray(B), jnp.int32(NB), a_col=a_col,
                        b_col=b_col, out_cap=cap, out_width=width,
                        backend="interpret")
        assert int(pj.count) == int(pk.count)
        assert np.array_equal(np.asarray(pj.verts), np.asarray(pk.verts))

    @given(st.integers(1, 30), st.integers(1, 30), st.integers(0, 3),
           st.integers(0, 3), st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_cross_join(self, NP, NC, p_col, c_col, seed):
        from repro.core.join import cross_join
        r = np.random.default_rng(seed)
        P = self._simple_rows(r, NP, p_col + 1, 25)
        C = self._simple_rows(r, NC, c_col + 1, 25)
        width = p_col + c_col + 2
        pj = cross_join(jnp.asarray(P), jnp.int32(NP), jnp.asarray(C),
                        jnp.int32(NC), p_col=p_col, c_col=c_col,
                        out_cap=256, out_width=width, backend="jnp")
        pk = cross_join(jnp.asarray(P), jnp.int32(NP), jnp.asarray(C),
                        jnp.int32(NC), p_col=p_col, c_col=c_col,
                        out_cap=256, out_width=width, backend="interpret")
        assert int(pj.count) == int(pk.count)
        assert np.array_equal(np.asarray(pj.verts), np.asarray(pk.verts))

    @given(st.integers(1, 50), st.integers(1, 5), st.integers(1, 5),
           st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_rowwise_overlap_property(self, N, LA, LB, seed):
        from repro.kernels.path_join.ops import rowwise_overlap
        r = np.random.default_rng(seed)
        A = jnp.asarray(r.integers(-1, 12, (N, LA)).astype(np.int32))
        B = jnp.asarray(r.integers(-1, 12, (N, LB)).astype(np.int32))
        a = rowwise_overlap(A, B, backend="interpret")
        b = rowwise_overlap(A, B, backend="jnp")
        assert np.array_equal(np.asarray(a), np.asarray(b))

    @given(st.integers(1, 40), st.integers(1, 5), st.integers(1, 6),
           st.integers(0, 5))
    @settings(max_examples=10, deadline=None)
    def test_path_member_property(self, N, L, D, seed):
        from repro.kernels.path_join.ops import path_member
        r = np.random.default_rng(seed)
        verts = jnp.asarray(r.integers(-1, 15, (N, L)).astype(np.int32))
        cand = jnp.asarray(r.integers(0, 16, (N, D)).astype(np.int32))
        a = path_member(verts, cand, backend="interpret")
        b = path_member(verts, cand, backend="jnp")
        assert np.array_equal(np.asarray(a), np.asarray(b))


class TestEngineOracle:
    """End-to-end: interpret dispatch must be oracle-exact and identical
    to the jnp engine on every planner."""

    @pytest.mark.parametrize("planner", ["basic", "basic+", "batch",
                                         "batch+", "pathenum"])
    def test_all_planners(self, planner):
        from repro.core.engine import BatchPathEngine, EngineConfig
        from repro.core.oracle import enumerate_paths_bruteforce, path_set
        g = _random_graph(48, 4, 13)
        qs = [(0, 7, 5), (1, 7, 4), (2, 9, 5)]
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              kernel_backend="interpret"))
        ref = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              kernel_backend="jnp"))
        ri = eng.run(qs, planner=planner)
        rj = ref.run(qs, planner=planner)
        for i, (s, t, k) in enumerate(qs):
            got = path_set(np.asarray(ri.results[i].paths))
            assert got == path_set(np.asarray(rj.results[i].paths))
            assert got == path_set(enumerate_paths_bruteforce(g, s, t, k))

    def test_similarity_backends_agree(self):
        from repro.core.engine import BatchPathEngine, EngineConfig
        from repro.core.index import build_index
        from repro.core.similarity import similarity_matrix
        g = _random_graph(60, 4, 17)
        eng = BatchPathEngine(g, EngineConfig())
        index = build_index(eng.dg, [(0, 7, 4), (1, 7, 4), (2, 9, 3)])
        a = similarity_matrix(index, backend="jnp")
        b = similarity_matrix(index, backend="interpret")
        np.testing.assert_allclose(a, b)
