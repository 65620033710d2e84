"""Unit + property tests for the static-shape substrate: PathSet compaction,
concat packing, the ⊕ bucket join vs a brute-force join, and the DP
capacity planner's upper-bound property."""
import jax.numpy as jnp
import numpy as np
from _hyp import given, settings, st  # hypothesis or skip-shim

from repro.core.pathset import PathSet, compact_rows, concat, empty, singleton
from repro.core.join import keyed_join, cross_join, sort_by_last


class TestCompact:
    @given(st.integers(1, 64), st.integers(1, 64), st.integers(0, 6))
    @settings(max_examples=25, deadline=None)
    def test_compact_keeps_masked_rows_in_order(self, n, cap, seed):
        r = np.random.default_rng(seed)
        mask = jnp.asarray(r.random(n) < 0.5)
        payload = jnp.asarray(r.integers(0, 100, (n, 3)).astype(np.int32))
        out, count, ovf = compact_rows(mask, payload, cap)
        kept = np.asarray(payload)[np.asarray(mask)]
        expect = kept[:cap]
        assert int(count) == min(kept.shape[0], cap)
        assert bool(ovf) == (kept.shape[0] > cap)
        assert np.array_equal(np.asarray(out)[:int(count)], expect)

    def test_concat_packs(self):
        a = singleton(5, 3)
        b = PathSet(jnp.asarray([[1, 2, -1], [3, 4, -1]], jnp.int32),
                    jnp.int32(2), jnp.bool_(False))
        c = concat([a, b])
        assert int(c.count) == 3
        rows = np.asarray(c.verts)[:3]
        assert rows[0][0] == 5 and rows[1][0] == 1 and rows[2][0] == 3

    def test_empty(self):
        e = empty(4, 2)
        assert int(e.count) == 0 and e.verts.shape == (4, 2)


def _brute_join(A, a_col, B, b_col, width):
    out = set()
    for pa in A:
        if pa[0] < 0:
            continue
        for pb in B:
            if pb[0] < 0:
                continue
            if pa[a_col] != pb[b_col] or pa[a_col] < 0:
                continue
            path = list(pa[:a_col + 1]) + list(pb[:b_col][::-1])
            if len(set(path)) != len(path):
                continue
            out.add(tuple(path + [-1] * (width - len(path))))
    return out


class TestKeyedJoin:
    @given(st.integers(1, 20), st.integers(1, 20), st.integers(0, 8))
    @settings(max_examples=25, deadline=None)
    def test_matches_bruteforce(self, na, nb, seed):
        r = np.random.default_rng(seed)
        a_col, b_col = 2, 2
        width = a_col + b_col + 1
        A = r.integers(0, 8, (na, a_col + 1)).astype(np.int32)
        B = r.integers(0, 8, (nb, b_col + 1)).astype(np.int32)
        # make rows simple internally (join machinery assumes halves simple)
        keep_a = np.array([len(set(row)) == len(row) for row in A])
        keep_b = np.array([len(set(row)) == len(row) for row in B])
        A, B = A[keep_a], B[keep_b]
        if len(A) == 0 or len(B) == 0:
            return
        sa = sort_by_last(jnp.asarray(A), jnp.int32(len(A)), col=a_col)
        res = keyed_join(sa, jnp.asarray(B), jnp.int32(len(B)),
                         a_col=a_col, b_col=b_col, out_cap=256,
                         out_width=width)
        got = {tuple(int(x) for x in row)
               for row in np.asarray(res.verts)[:int(res.count)]}
        assert got == _brute_join(A, a_col, B, b_col, width)

    def test_overflow_flag(self):
        A = np.zeros((8, 2), np.int32)       # all join on vertex 0
        A[:, 1] = 0
        A[:, 0] = np.arange(1, 9)
        B = np.zeros((8, 2), np.int32)
        B[:, 0] = 9
        B[:, 1] = 0
        sa = sort_by_last(jnp.asarray(A), jnp.int32(8), col=1)
        res = keyed_join(sa, jnp.asarray(B), jnp.int32(8), a_col=1, b_col=1,
                         out_cap=4, out_width=3)
        assert bool(res.overflow)


class TestCrossJoin:
    def test_splice_semantics(self):
        P = jnp.asarray([[0, 1, -1], [2, 3, -1]], jnp.int32)
        C = jnp.asarray([[4, 5], [1, 6]], jnp.int32)
        res = cross_join(P, jnp.int32(2), C, jnp.int32(2),
                         p_col=1, c_col=1, out_cap=16, out_width=4)
        got = {tuple(int(x) for x in row)
               for row in np.asarray(res.verts)[:int(res.count)]}
        # (0,1)+(1,6) shares vertex 1 -> dropped; other three valid
        assert got == {(0, 1, 4, 5), (2, 3, 4, 5), (2, 3, 1, 6)}


class TestWalkCountsUpperBound:
    @given(st.integers(10, 40), st.integers(10, 80), st.integers(0, 5),
           st.integers(2, 4))
    @settings(max_examples=15, deadline=None)
    def test_dp_bounds_simple_path_counts(self, n, m, seed, k):
        """The DP plan is an upper bound on true per-level simple-path
        counts (so planned capacities never overflow)."""
        from repro.core.graph import Graph
        from repro.core.index import walk_counts
        r = np.random.default_rng(seed)
        g = Graph.from_edges(n, r.integers(0, n, m), r.integers(0, n, m))
        s = int(r.integers(0, n))
        slack = np.full(n + 1, 127, np.int8)  # no pruning
        tot = walk_counts(g.indptr, g.indices, s, slack, k)
        # count true simple paths from s per level by DFS
        counts = np.zeros(k + 1, np.int64)
        counts[0] = 1
        stack = [(s, (s,))]
        while stack:
            u, path = stack.pop()
            d = len(path) - 1
            if d == k:
                continue
            for v in g.neighbors(u):
                v = int(v)
                if v in path:
                    continue
                counts[d + 1] += 1
                stack.append((v, path + (v,)))
        assert np.all(tot + 1e-6 >= counts)
