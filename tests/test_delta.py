"""Dynamic-graph subsystem: ``GraphDelta`` normalization, ``apply_delta``
equivalence with full ``from_edges`` rebuilds (CSR both directions, ELL
views, self-loop/duplicate handling — property-tested on random edge-churn
sequences), device-view patching, hop-scoped cache invalidation semantics,
and delta-at-micro-batch-boundary streaming behavior."""
import numpy as np
import pytest
from _hyp import given, settings, st  # hypothesis or skip-shim

from repro.core import (BatchPathEngine, EngineConfig, GraphDelta,
                        PathSession, generators)
from repro.core.cache import SharedPathCache, dedicated_keys
from repro.core.delta import apply_delta, update_device_graph
from repro.core.graph import DeviceGraph, Graph
from repro.core.oracle import (bfs_dist_from, enumerate_paths_bruteforce,
                               path_set)
from repro.core.pathset import PathSet, offload, pathset_nbytes
from repro.core.query import midpoint_split
from repro.launch.serve import AdmissionPolicy, StreamingServer

import jax.numpy as jnp


def _edge_list(g: Graph):
    src = np.repeat(np.arange(g.n), np.diff(g.indptr))
    return src, g.indices.astype(np.int64)


def _rebuild_after(g: Graph, delta: GraphDelta) -> Graph:
    """Reference successor: edit the edge set, full from_edges rebuild."""
    src, dst = _edge_list(g)
    old = set(zip(src.tolist(), dst.tolist()))
    new = ((old - set(zip(delta.del_src.tolist(), delta.del_dst.tolist())))
           | set(zip(delta.add_src.tolist(), delta.add_dst.tolist())))
    ns = np.array([u for u, _ in new], np.int64)
    nd = np.array([v for _, v in new], np.int64)
    return Graph.from_edges(g.n, ns, nd)


def _assert_graph_equal(a: Graph, b: Graph):
    np.testing.assert_array_equal(a.indptr, b.indptr)
    np.testing.assert_array_equal(a.indices, b.indices)
    np.testing.assert_array_equal(a.r_indptr, b.r_indptr)
    np.testing.assert_array_equal(a.r_indices, b.r_indices)


def _random_delta(g: Graph, rng, n_add=6, n_del=6) -> GraphDelta:
    """Messy delta: self-loops, duplicates, absent deletes, present adds."""
    n = g.n
    a_s = rng.integers(0, n, n_add)
    a_d = rng.integers(0, n, n_add)
    src, dst = _edge_list(g)
    if g.m:
        pick = rng.integers(0, g.m, max(n_del // 2, 1))
        d_s = np.concatenate([src[pick], rng.integers(0, n, n_del)])
        d_d = np.concatenate([dst[pick], rng.integers(0, n, n_del)])
    else:
        d_s, d_d = rng.integers(0, n, n_del), rng.integers(0, n, n_del)
    return GraphDelta(a_s, a_d, d_s, d_d)


class TestGraphDelta:
    def test_normalization_drops_self_loops_and_dups(self):
        d = GraphDelta([1, 1, 2, 3], [2, 2, 4, 3], [5, 5], [6, 6])
        assert d.n_add == 2            # (1,2) deduped, (3,3) loop dropped
        assert d.n_del == 1
        assert bool(d)
        assert not bool(GraphDelta.empty())

    def test_from_pairs_and_max_vertex(self):
        d = GraphDelta.from_pairs(add=[(0, 9)], remove=[(4, 2)])
        assert d.max_vertex() == 9
        assert GraphDelta.empty().max_vertex() == -1

    def test_negative_ids_rejected(self):
        with pytest.raises(ValueError):
            GraphDelta([-1], [0], [], [])

    def test_out_of_bounds_rejected_at_apply(self):
        g = generators.erdos(10, 2.0, seed=0)
        with pytest.raises(ValueError):
            apply_delta(g, GraphDelta.from_pairs(add=[(0, 10)]))


class TestApplyDelta:
    def test_matches_full_rebuild_deterministic_churn(self):
        rng = np.random.default_rng(3)
        g = generators.community(120, n_comm=3, avg_deg=4.0, seed=1)
        for _ in range(12):   # a churn *sequence*: deltas compound
            delta = _random_delta(g, rng)
            ref = _rebuild_after(g, delta)
            applied = apply_delta(g, delta)
            _assert_graph_equal(applied.graph, ref)
            # touched == endpoints of effective changes, no-ops excluded
            old = set(zip(*(x.tolist() for x in _edge_list(g))))
            new = set(zip(*(x.tolist() for x in _edge_list(ref))))
            want = sorted({v for e in (old ^ new) for v in e})
            assert applied.touched.tolist() == want
            g = applied.graph

    def test_noop_delta_returns_same_graph(self):
        g = generators.erdos(30, 3.0, seed=2)
        src, dst = _edge_list(g)
        delta = GraphDelta.from_pairs(
            add=[(int(src[0]), int(dst[0]))],      # already present
            remove=[(int(src[1]), int(dst[1] + 1) % g.n)]
            if (int(src[1]), (int(dst[1]) + 1) % g.n) not in
            set(zip(src.tolist(), dst.tolist())) else [])
        applied = apply_delta(g, delta)
        assert applied.n_changed == 0 and applied.graph is g
        g2, touched = g.apply_delta(delta)
        assert g2 is g and touched.size == 0

    def test_delete_then_add_same_edge_is_noop(self):
        g = generators.erdos(30, 3.0, seed=4)
        src, dst = _edge_list(g)
        e = (int(src[0]), int(dst[0]))
        applied = apply_delta(g, GraphDelta.from_pairs(add=[e], remove=[e]))
        assert applied.n_changed == 0     # new = (old - e) | e == old

    def test_ell_views_match_rebuild(self):
        rng = np.random.default_rng(5)
        g = generators.community(80, n_comm=2, avg_deg=4.0, seed=3)
        delta = _random_delta(g, rng)
        g2 = apply_delta(g, delta).graph
        ref = _rebuild_after(g, delta)
        for reverse in (False, True):
            cap = max(int(np.diff(ref.r_indptr if reverse else
                                  ref.indptr).max()), 1)
            e1, e2 = g2.ell(cap, reverse), ref.ell(cap, reverse)
            np.testing.assert_array_equal(e1.idx, e2.idx)
            np.testing.assert_array_equal(e1.mask, e2.mask)

    @settings(max_examples=40, deadline=None)
    @given(st.data())
    def test_property_churn_equivalence(self, data):
        n = data.draw(st.integers(3, 50), label="n")
        m = data.draw(st.integers(0, 150), label="m")
        seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
        rng = np.random.default_rng(seed)
        g = Graph.from_edges(n, rng.integers(0, n, m), rng.integers(0, n, m))
        for _ in range(data.draw(st.integers(1, 3), label="rounds")):
            delta = _random_delta(g, rng,
                                  n_add=data.draw(st.integers(0, 12)),
                                  n_del=data.draw(st.integers(0, 12)))
            ref = _rebuild_after(g, delta)
            applied = apply_delta(g, delta)
            _assert_graph_equal(applied.graph, ref)
            g = applied.graph


class TestDeviceGraphUpdate:
    def test_incremental_patch_matches_build(self):
        rng = np.random.default_rng(6)
        g = generators.community(70, n_comm=2, avg_deg=4.0, seed=7)
        dg = DeviceGraph.build(g)
        # keep per-row degree within the existing caps: rewire existing
        # edges (delete one, add one from the same source)
        src, dst = _edge_list(g)
        i = int(rng.integers(0, g.m))
        u, v = int(src[i]), int(dst[i])
        w = next(int(x) for x in rng.permutation(g.n)
                 if x != u and x not in g.neighbors(u))
        applied = apply_delta(g, GraphDelta.from_pairs(add=[(u, w)],
                                                       remove=[(u, v)]))
        dg2, incremental = update_device_graph(dg, applied)
        assert incremental
        g2 = applied.graph
        assert dg2.ell_cap == dg.ell_cap and dg2.r_ell_cap == dg.r_ell_cap
        # a rewire keeps m constant: the edge bucket (and with it every
        # kernel shape) must be preserved, valid prefix exact, sentinel
        # (n, n) edges beyond it
        assert dg2.m_cap == dg.m_cap and dg2.m == g2.m
        esrc, edst = g2.edges_by_dst
        r_esrc, r_edst = g2.r_edges_by_dst
        for got, want in ((dg2.esrc, esrc), (dg2.edst, edst),
                          (dg2.r_esrc, r_esrc), (dg2.r_edst, r_edst)):
            got = np.asarray(got)
            np.testing.assert_array_equal(got[:g2.m], want)
            assert np.all(got[g2.m:] == g2.n)
        ell = g2.ell(cap=dg2.ell_cap)
        rell = g2.reverse().ell(cap=dg2.r_ell_cap)
        np.testing.assert_array_equal(np.asarray(dg2.ell_idx), ell.idx)
        np.testing.assert_array_equal(np.asarray(dg2.ell_mask), ell.mask)
        np.testing.assert_array_equal(np.asarray(dg2.r_ell_idx), rell.idx)
        np.testing.assert_array_equal(np.asarray(dg2.r_ell_mask), rell.mask)
        assert dg2.m == g2.m

    def test_incremental_patch_drops_sliced_layout(self):
        """An incremental patch drops the sliced ELL: the index sweep runs
        over the padded ELL (counted under layout="padded") and the
        engine's answers still match the oracle; a full rebuild restores
        the sliced layout."""
        from repro.obs import metrics as obsmetrics
        g = generators.community(120, n_comm=2, avg_deg=4.0, seed=31)
        qs = generators.similar_queries(g, 4, similarity=0.8,
                                        k_range=(3, 4), seed=32)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              kernel_backend="interpret"))
        assert eng.dg.ell_sliced is not None
        src, dst = _edge_list(g)
        u, v = int(src[0]), int(dst[0])
        w = next(int(x) for x in range(g.n)
                 if x != u and x not in g.neighbors(u))
        rep = eng.apply_delta(GraphDelta.from_pairs(add=[(u, w)],
                                                    remove=[(u, v)]))
        assert rep["device_update"] == "incremental"
        assert eng.dg.ell_sliced is None and eng.dg.r_ell_sliced is None
        assert eng.dg.sweep_table(True) is eng.dg.r_ell_idx
        padded = obsmetrics.registry().counter("engine_index_rows_total",
                                               layout="padded")
        before = padded.value
        r = eng.run(qs)
        assert padded.value > before
        for qi, (s, t, k) in enumerate(qs):
            assert path_set(r[qi].paths) == path_set(
                enumerate_paths_bruteforce(eng.g, s, t, k))
        assert DeviceGraph.build(eng.g).ell_sliced is not None

    def test_cap_overflow_falls_back_to_rebuild(self):
        g = Graph.from_edges(5, [0, 1], [1, 2])   # max out-degree 1
        dg = DeviceGraph.build(g)
        applied = apply_delta(g, GraphDelta.from_pairs(add=[(0, 2), (0, 3)]))
        dg2, incremental = update_device_graph(dg, applied)
        assert not incremental and dg2.ell_cap >= 3
        ref = DeviceGraph.build(applied.graph)
        np.testing.assert_array_equal(np.asarray(dg2.ell_idx),
                                      np.asarray(ref.ell_idx))

    def test_cap_overflow_rebuild_never_shrinks_buckets(self):
        """The ELL-overflow fallback must keep every shape bucket monotone
        (edge cap and both ELL caps): an overflow after deletion-heavy
        churn re-bucketing smaller would re-thrash the next insert wave."""
        g = Graph.from_edges(6, [0, 1, 2], [1, 2, 3])
        # simulate previously grown buckets: a larger edge pad + ELL caps
        dg = DeviceGraph.build(g, edge_cap=16)
        dg = DeviceGraph.build(g, edge_cap=16,
                               min_ell_caps=(dg.ell_cap * 4, dg.r_ell_cap))
        applied = apply_delta(g, GraphDelta.from_pairs(
            add=[(5, v) for v in range(5)]))      # out-row 5: deg 5 > cap
        dg2, incremental = update_device_graph(dg, applied)
        assert not incremental
        assert dg2.m_cap >= dg.m_cap              # edge bucket kept
        assert dg2.ell_cap >= dg.ell_cap          # fwd ELL bucket kept
        assert dg2.r_ell_cap >= dg.r_ell_cap
        # and the rebuilt views are still a correct padded graph
        g2 = applied.graph
        got = np.asarray(dg2.esrc)
        np.testing.assert_array_equal(got[:g2.m], g2.edges_by_dst[0])
        assert np.all(got[g2.m:] == g2.n)

    def test_frontier_dists_agree_on_old_and_new_graph(self):
        """The invalidation invariant: both endpoints of every changed edge
        are seeds, so set distances from the touched frontier are the same
        whether walked on the old or the new graph."""
        from repro.core.delta import host_set_dist
        rng = np.random.default_rng(23)
        for seed in range(4):
            g = generators.erdos(40, 3.0, seed=seed)
            applied = apply_delta(g, _random_delta(g, rng))
            if applied.touched.size == 0:
                continue
            for k_max in (2, 4):
                for reverse in (False, True):
                    d_old = host_set_dist(g, applied, k_max, reverse)
                    d_new = host_set_dist(applied.graph, applied, k_max,
                                          reverse)
                    np.testing.assert_array_equal(d_old, d_new)


def _levels(width=4, rows=4):
    verts = jnp.full((rows, width), -1, jnp.int32).at[:, 0].set(1)
    return [PathSet(verts, jnp.int32(rows), jnp.bool_(False))]


class TestHopScopedInvalidation:
    """invalidate_delta against hand-built distance fields: eviction iff the
    damage intersects the enumeration ball or a consumer prune radius."""

    def _dists(self, n, to=(), frm=()):
        INF = 99
        d_to = np.full(n + 1, INF, np.int32)
        d_from = np.full(n + 1, INF, np.int32)
        for v, d in to:
            d_to[v] = d
        for v, d in frm:
            d_from[v] = d
        return {"to": d_to, "from": d_from}

    def test_far_entries_survive_with_epoch_bump(self):
        c = SharedPathCache()
        c.put(("f", 3, 2, ((9, 4),), -2), _levels())
        info = c.invalidate_delta([5], self._dists(20, to=[(3, 3)],
                                                   frm=[(9, 5)]))
        assert info == {"evicted": 0, "kept": 1, "epoch": 1}
        assert c.contains(("f", 3, 2, ((9, 4),), -2))
        assert c.stats.delta_kept == 1 and c.stats.delta_evictions == 0

    def test_enumeration_ball_eviction(self):
        c = SharedPathCache()
        c.put(("f", 3, 2, ((9, 4),), -2), _levels())   # source can reach
        c.put(("b", 7, 2, ((1, 4),), -2), _levels())   # damage reaches root
        info = c.invalidate_delta([5], self._dists(
            20, to=[(3, 2), (1, 99)], frm=[(7, 1), (9, 99)]))
        assert info["evicted"] == 2 and info["kept"] == 0
        assert not c.has_root("f", 3) and c.nbytes == 0

    def test_consumer_prune_radius_eviction(self):
        c = SharedPathCache()
        # enumeration balls untouched, but an insert lands within a
        # consumer endpoint's prune radius -> the slack mask could loosen
        c.put(("f", 3, 2, ((9, 4),), -2), _levels())
        info = c.invalidate_delta([5], self._dists(20, frm=[(9, 4)]))
        assert info["evicted"] == 1
        c.put(("b", 7, 2, ((1, 4),), -2), _levels())
        info = c.invalidate_delta([5], self._dists(20, to=[(1, 3)]))
        assert info["evicted"] == 1
        assert c.stats.delta_invalidations == 2

    def test_boundary_is_inclusive(self):
        c = SharedPathCache()
        c.put(("f", 3, 2, ((9, 4),), -2), _levels())
        # exactly budget hops away -> a path could end on a changed edge
        assert c.invalidate_delta([5], self._dists(
            20, to=[(3, 2)]))["evicted"] == 1
        c.put(("f", 3, 2, ((9, 4),), -2), _levels())
        assert c.invalidate_delta([5], self._dists(
            20, to=[(3, 3)]))["evicted"] == 0

    def test_empty_touched_keeps_everything(self):
        c = SharedPathCache()
        c.put(("f", 3, 2, ((9, 4),), -2), _levels())
        info = c.invalidate_delta([], {"to": np.empty(0), "from": np.empty(0)})
        assert info["evicted"] == 0 and c.epoch == 1

    def test_epoch_guard_drops_desynced_entries(self):
        """Defensive contract: a resident entry must carry the current
        epoch (invalidate_delta re-stamps survivors); one that somehow
        missed an invalidation pass serves as a miss, never as stale."""
        c = SharedPathCache()
        key = ("f", 3, 2, ((9, 4),), -2)
        c.put(key, _levels())
        assert c.get(key) is not None
        c.epoch += 1                      # simulate a missed invalidation
        assert c.get(key) is None and not c.contains(key)
        assert c.nbytes == 0 and not c.has_root("f", 3)

    def test_max_radius(self):
        c = SharedPathCache()
        assert c.max_radius() == 0
        c.put(("f", 3, 2, ((9, 6),), -2), _levels())
        c.put(("b", 7, 4, ((1, 3),), -2), _levels())
        assert c.max_radius() == 6


class TestSetDist:
    def test_host_backend_matches_device_backend(self):
        """host_set_dist (CSR ball walk) ≡ msbfs_set_dist (device sweep)
        from the touched frontier, both directions, across random deltas."""
        from repro.core.delta import host_set_dist
        from repro.core.msbfs import msbfs_set_dist
        rng = np.random.default_rng(19)
        for seed in range(4):
            g = generators.erdos(50, 3.0, seed=seed)
            dg = DeviceGraph.build(g)
            applied = apply_delta(g, _random_delta(g, rng))
            if applied.touched.size == 0:
                continue
            mask = np.zeros(g.n + 1, np.int8)
            mask[applied.touched] = 1
            for k_max in (1, 3, 5):
                for reverse in (False, True):
                    esrc, edst = ((dg.r_esrc, dg.r_edst) if reverse
                                  else (dg.esrc, dg.edst))
                    want = np.asarray(msbfs_set_dist(
                        esrc, edst, jnp.asarray(mask), n=g.n, k_max=k_max))
                    got = host_set_dist(g, applied, k_max, reverse=reverse)
                    np.testing.assert_array_equal(got, want,
                                                  err_msg=f"{seed} {k_max}")

    def test_msbfs_engine_backend_stays_exact(self):
        g = generators.community(200, n_comm=3, avg_deg=4.0, seed=14)
        qs = generators.similar_queries(g, 5, similarity=0.8,
                                        k_range=(3, 3), seed=15)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=32 << 20,
                                              delta_backend="msbfs"))
        eng.run(qs)
        rng = np.random.default_rng(16)
        rep = eng.apply_delta(_random_delta(g, rng, 3, 3))
        assert rep["cache_mode"] == "delta"
        r = eng.run(qs)
        fresh = BatchPathEngine(eng.g, EngineConfig(min_cap=64))
        rf = fresh.run(qs)
        for qi in range(len(qs)):
            assert path_set(r[qi].paths) == path_set(rf[qi].paths)

    def test_set_dist_is_min_over_sources(self):
        g = generators.erdos(60, 3.0, seed=20)
        dg = DeviceGraph.build(g)
        from repro.core.msbfs import msbfs_dist, msbfs_set_dist
        rng = np.random.default_rng(21)
        seeds = np.unique(rng.integers(0, g.n, 5)).astype(np.int32)
        per_src = np.asarray(msbfs_dist(dg.esrc, dg.edst, jnp.asarray(seeds),
                                        n=g.n, k_max=4))
        mask = np.zeros(g.n + 1, np.int8)
        mask[seeds] = 1
        got = np.asarray(msbfs_set_dist(dg.esrc, dg.edst, jnp.asarray(mask),
                                        n=g.n, k_max=4))
        np.testing.assert_array_equal(got, per_src.min(axis=1))
        assert got[g.n] == 5   # sentinel row stays INF


class TestEngineDelta:
    def _workload(self, n=900, nq=8, seed=0):
        g = generators.community(n, n_comm=max(3, n // 250), avg_deg=4.0,
                                 seed=seed)
        qs = generators.similar_queries(g, nq, similarity=0.85,
                                        k_range=(3, 4), seed=seed + 1)
        return g, qs

    def _cold_edges(self, g, qs, count):
        """Existing edges with both endpoints beyond every query's hop
        radius (the pool the hop-scoped invalidation must keep warm)."""
        hot = np.zeros(g.n, bool)
        for s, t, k in qs:
            hot |= bfs_dist_from(g, s, k) <= k
            hot |= bfs_dist_from(g, t, k, reverse=True) <= k
        cold = ~hot
        src, dst = _edge_list(g)
        idx = np.flatnonzero(cold[src] & cold[dst])
        if idx.size < count + 4:
            pytest.skip("graph too small for a cold edge pool")
        cold_v = np.flatnonzero(cold)
        adds, have = [], set(zip(src.tolist(), dst.tolist()))
        rng = np.random.default_rng(9)
        while len(adds) < count:
            u, v = (int(x) for x in rng.choice(cold_v, 2, replace=False))
            if u != v and (u, v) not in have:
                adds.append((u, v))
        dels = [(int(src[i]), int(dst[i])) for i in idx[:count]]
        return adds, dels

    def test_far_delta_keeps_cache_warm_and_exact(self):
        g, qs = self._workload()
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=64 << 20))
        eng.run(qs)
        n_entries = len(eng.cache)
        assert n_entries > 0
        adds, dels = self._cold_edges(g, qs, 2)
        rep = eng.apply_delta(GraphDelta.from_pairs(add=adds, remove=dels))
        assert rep["cache_mode"] == "delta"
        assert rep["cache_kept"] == n_entries and rep["cache_evicted"] == 0
        assert rep["device_update"] in ("incremental", "rebuild")
        r2 = eng.run(qs)
        assert r2.stats["n_materialized"] == 0        # fully warm
        fresh = BatchPathEngine(eng.g, EngineConfig(min_cap=64))
        rf = fresh.run(qs)
        for qi, (s, t, k) in enumerate(qs):
            truth = path_set(enumerate_paths_bruteforce(eng.g, s, t, k))
            assert path_set(r2[qi].paths) == truth, f"warm q{qi}"
            assert path_set(rf[qi].paths) == truth, f"fresh q{qi}"

    def test_near_delta_evicts_and_stays_exact(self):
        g, qs = self._workload(seed=2)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=64 << 20))
        eng.run(qs)
        s0 = qs[0][0]
        nb = g.neighbors(s0)
        assert nb.size > 0
        rep = eng.apply_delta(GraphDelta.from_pairs(remove=[(s0, int(nb[0]))]))
        assert rep["cache_evicted"] > 0
        r2 = eng.run(qs)
        for qi, (s, t, k) in enumerate(qs):
            truth = path_set(enumerate_paths_bruteforce(eng.g, s, t, k))
            assert path_set(r2[qi].paths) == truth, f"q{qi}"

    def test_random_churn_stays_exact(self):
        """No cold-edge engineering: arbitrary deltas, exactness only."""
        g, qs = self._workload(n=200, nq=5, seed=5)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=64 << 20))
        rng = np.random.default_rng(11)
        for round_ in range(3):
            eng.run(qs)
            rep = eng.apply_delta(_random_delta(g, rng, n_add=4, n_del=4))
            g = eng.g
            r = eng.run(qs)
            fresh = BatchPathEngine(g, EngineConfig(min_cap=64))
            rf = fresh.run(qs)
            for qi in range(len(qs)):
                assert path_set(r[qi].paths) == path_set(rf[qi].paths), \
                    (round_, qi, rep)

    def test_noop_delta_keeps_all_state(self):
        g, qs = self._workload(n=200, nq=4, seed=6)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=64 << 20))
        eng.run(qs)
        src, dst = _edge_list(g)
        epoch = eng.cache.epoch
        dg = eng.dg
        rep = eng.apply_delta(GraphDelta.from_pairs(
            add=[(int(src[0]), int(dst[0]))]))    # already present
        assert rep["n_added"] == rep["n_removed"] == 0
        assert eng.g is g and eng.dg is dg and eng.cache.epoch == epoch

    def test_wide_delta_falls_back_to_full_invalidate(self):
        g, qs = self._workload(n=200, nq=4, seed=7)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=64 << 20,
                                              delta_max_sources=4))
        eng.run(qs)
        rng = np.random.default_rng(13)
        rep = eng.apply_delta(_random_delta(g, rng, n_add=16, n_del=16))
        assert rep["cache_mode"] == "full" and len(eng.cache) == 0
        assert rep["cache_evicted"] > 0 and rep["cache_kept"] == 0
        r = eng.run(qs)
        fresh = BatchPathEngine(eng.g, EngineConfig(min_cap=64))
        rf = fresh.run(qs)
        for qi in range(len(qs)):
            assert path_set(r[qi].paths) == path_set(rf[qi].paths)


class TestSessionAndStreaming:
    def test_session_apply_delta_batch_mode(self):
        g = generators.community(200, n_comm=3, avg_deg=4.0, seed=8)
        qs = generators.similar_queries(g, 5, similarity=0.8,
                                        k_range=(3, 3), seed=9)
        session = PathSession(g, EngineConfig(min_cap=64,
                                              cache_bytes=32 << 20))
        session.run(qs)
        rng = np.random.default_rng(15)
        rep = session.apply_delta(_random_delta(g, rng, 3, 3))
        assert rep is not None and "cache_mode" in rep
        r = session.run(qs)
        fresh = BatchPathEngine(session.engine.g, EngineConfig(min_cap=64))
        rf = fresh.run(qs)
        for qi in range(len(qs)):
            assert path_set(r[qi].paths) == path_set(rf[qi].paths)

    def test_streaming_delta_applies_at_batch_boundary(self):
        g = generators.community(200, n_comm=3, avg_deg=4.0, seed=10)
        qs = generators.similar_queries(g, 6, similarity=0.8,
                                        k_range=(3, 3), seed=11)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=32 << 20))
        srv = StreamingServer(eng, n_groups=1,
                              policy=AdmissionPolicy(max_batch=6,
                                                     max_delay_s=0.0))
        ids1 = [srv.submit(q) for q in qs]
        srv.drain()
        rng = np.random.default_rng(17)
        delta = _random_delta(g, rng, 3, 3)
        srv.apply_delta(delta)
        assert eng.g is g                 # queued, not yet applied
        ids2 = [srv.submit(q) for q in qs]
        srv.drain()                       # boundary: delta applies first
        assert eng.g is not g or not delta
        assert len(srv.delta_log) == 1
        b2 = srv.batch_log[-1]
        assert b2["n_deltas"] == 1
        assert b2["delta_edges"] == srv.delta_log[0]["n_added"] + \
            srv.delta_log[0]["n_removed"]
        g2 = eng.g
        for qid, (s, t, k) in zip(ids2, qs):
            truth = path_set(enumerate_paths_bruteforce(g2, s, t, k))
            assert path_set(srv.take(qid).paths) == truth
        for qid, (s, t, k) in zip(ids1, qs):   # pre-delta answers: old graph
            truth = path_set(enumerate_paths_bruteforce(g, s, t, k))
            assert path_set(srv.take(qid).paths) == truth

    def test_session_run_flushes_queued_deltas(self):
        """A one-shot batch is a boundary: run() must not execute on the
        pre-delta graph while a delta sits queued behind the server."""
        g = generators.community(150, n_comm=2, avg_deg=4.0, seed=18)
        qs = generators.similar_queries(g, 4, similarity=0.8,
                                        k_range=(3, 3), seed=19)
        session = PathSession(g, EngineConfig(min_cap=64))
        session.submit(qs[0])
        session.results()
        src, dst = _edge_list(g)
        session.apply_delta(GraphDelta.from_pairs(
            remove=[(int(src[0]), int(dst[0]))]))     # queued
        assert session.engine.g is g
        r = session.run(qs)                           # boundary: flush first
        g2 = session.engine.g
        assert g2 is not g and len(session.server.delta_log) == 1
        for qi, (s, t, k) in enumerate(qs):
            truth = path_set(enumerate_paths_bruteforce(g2, s, t, k))
            assert path_set(r[qi].paths) == truth

    def test_update_graph_discards_queued_deltas(self):
        """A full swap supersedes deltas queued against the old graph —
        they must never be applied to the unrelated new one."""
        g = generators.community(150, n_comm=2, avg_deg=4.0, seed=20)
        (q,) = generators.random_queries(g, 1, (3, 3), seed=21)
        session = PathSession(g, EngineConfig(min_cap=64))
        session.submit(q)
        session.results()
        src, dst = _edge_list(g)
        session.apply_delta(GraphDelta.from_pairs(
            remove=[(int(src[0]), int(dst[0]))]))
        g2 = generators.community(150, n_comm=2, avg_deg=4.0, seed=22)
        session.update_graph(g2)
        session.submit(q)
        session.results()                             # would apply the queue
        assert session.server.delta_log == []         # delta was discarded
        assert session.engine.g is g2

    def test_session_routes_delta_to_server_when_streaming(self):
        g = generators.community(150, n_comm=2, avg_deg=4.0, seed=12)
        (q,) = generators.random_queries(g, 1, (3, 3), seed=13)
        session = PathSession(g, EngineConfig(min_cap=64))
        session.submit(q)
        src, dst = _edge_list(g)
        assert session.apply_delta(GraphDelta.from_pairs(
            remove=[(int(src[0]), int(dst[0]))])) is None   # queued
        session.results()
        assert len(session.server.delta_log) == 1

    def test_streaming_delta_validated_at_queue_time(self):
        """Out-of-range deltas are rejected when queued (like submit),
        never lost mid-flush with later deltas still applying."""
        g = generators.erdos(50, 3.0, seed=24)
        (q,) = generators.random_queries(g, 1, (3, 3), seed=25)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64))
        srv = StreamingServer(eng, n_groups=1)
        srv.submit(q)
        with pytest.raises(ValueError, match="outside the graph"):
            srv.apply_delta(GraphDelta.from_pairs(add=[(0, g.n)]))
        srv.drain()
        assert srv.delta_log == []                # nothing was queued


class TestSatellites:
    @pytest.mark.parametrize("k", [1, 2, 3, 4, 5, 8])
    def test_midpoint_split_is_single_source_of_truth(self, k):
        a, b = midpoint_split(k)
        assert a + b == k and a == (k + 1) // 2
        fkey, bkey = dedicated_keys(0, 1, k)
        assert fkey[2] == a and bkey[2] == b

    @pytest.mark.parametrize("k", [2, 3, 4])
    def test_engine_keys_match_dedicated_keys(self, k):
        g = generators.erdos(60, 3.0, seed=k)
        (q,) = generators.random_queries(g, 1, (k, k), seed=k + 1)
        eng = BatchPathEngine(g, EngineConfig(min_cap=64,
                                              cache_bytes=1 << 20))
        eng.run([q])
        fkey, bkey = dedicated_keys(*q)
        assert eng.cache.contains(fkey) and eng.cache.contains(bkey)

    def test_put_estimate_equals_host_accounting(self):
        """The pre-transfer oversize estimate and the LRU accounting use
        the same byte math (pathset_nbytes) — bit-equal, not just close."""
        levels = _levels(width=5, rows=7)
        est = sum(pathset_nbytes(ps.cap, ps.width, ps.verts.dtype.itemsize)
                  for ps in levels)
        assert est == sum(offload(ps).nbytes for ps in levels)
        c = SharedPathCache(budget_bytes=est)     # fits exactly
        c.put(("f", 0, 1, ((1, 1),), -2), levels)
        assert len(c) == 1 and c.nbytes == est
        c2 = SharedPathCache(budget_bytes=est - 1)  # off by one byte: skip
        c2.put(("f", 0, 1, ((1, 1),), -2), levels)
        assert len(c2) == 0 and c2.stats.oversize_skips == 1
