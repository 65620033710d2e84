"""Engine correctness: every mode vs the brute-force DFS oracle.

Deliberately kept on the legacy ``process(queries, mode=...)`` API: these
pre-existing tests double as coverage that the deprecation shim stays a
faithful front for ``run()`` (the warning itself is asserted in
test_query_api.py). Property-based invariants live in
test_engine_properties.py (they need hypothesis, an optional [test]
dependency, and degrade to skips there).
"""
import pytest

from repro.core import BatchPathEngine, EngineConfig
from repro.core import generators
from repro.core.oracle import enumerate_paths_bruteforce, path_set

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

MODES = ["basic", "basic+", "batch", "batch+", "pathenum", "auto"]


def _run_and_compare(g, qs, mode, cfg=None):
    eng = BatchPathEngine(g, cfg or EngineConfig(min_cap=64))
    res = eng.process(qs, mode=mode)
    for qi, (s, t, k) in enumerate(qs):
        got_list = [tuple(int(x) for x in row if x >= 0)
                    for row in res.paths[qi]]
        got = set(got_list)
        truth = path_set(enumerate_paths_bruteforce(g, s, t, k))
        assert len(got_list) == len(got), f"{mode} q{qi}: duplicate paths"
        assert got == truth, (f"{mode} q{qi}: {len(got)} vs {len(truth)}; "
                              f"missing {sorted(truth - got)[:3]} "
                              f"extra {sorted(got - truth)[:3]}")
    return res


@pytest.mark.parametrize("mode", MODES)
def test_modes_match_oracle_erdos(mode):
    g = generators.erdos(70, 3.0, seed=1)
    qs = generators.random_queries(g, 6, (2, 5), seed=2)
    _run_and_compare(g, qs, mode)


@pytest.mark.parametrize("mode", ["basic", "batch", "batch+"])
def test_modes_match_oracle_powerlaw(mode):
    g = generators.powerlaw(120, 3.0, seed=3)
    qs = generators.random_queries(g, 6, (3, 5), seed=4)
    _run_and_compare(g, qs, mode)


def test_batch_community_high_similarity():
    """Community graphs: heavy sharing; paper-faithful shared-node setting."""
    g = generators.community(90, n_comm=3, avg_deg=4.0, seed=5)
    qs = generators.similar_queries(g, 8, similarity=0.9, k_range=(3, 4),
                                    seed=6)
    res = _run_and_compare(g, qs, "batch",
                           EngineConfig(min_cap=64,
                                        paper_faithful_shares=True))
    assert res.stats["n_clusters"] >= 1


def test_k_edge_cases():
    g = generators.erdos(40, 3.0, seed=7)
    qs = generators.random_queries(g, 5, (1, 2), seed=8)
    for mode in ["basic", "batch"]:
        _run_and_compare(g, qs, mode)


def test_duplicate_and_nested_queries():
    g = generators.erdos(50, 3.0, seed=9)
    base = generators.random_queries(g, 3, (3, 4), seed=10)
    qs = base + [base[0], (base[1][0], base[1][1], 2)]
    _run_and_compare(g, qs, "batch")


def test_rejects_degenerate_queries():
    g = generators.erdos(20, 2.0, seed=11)
    eng = BatchPathEngine(g)
    with pytest.raises(ValueError):
        eng.process([(3, 3, 4)])
    with pytest.raises(ValueError):
        eng.process([(0, 1, 0)])


def test_repeated_process_calls_use_fresh_index():
    """Regression: the engine memoized host distance matrices by id(index);
    a freed index's id can be reused by the next batch's index, silently
    pruning with the PREVIOUS batch's distances. Back-to-back batches with
    different query sets on one engine must both be oracle-exact."""
    g = generators.community(100, n_comm=3, avg_deg=4.0, seed=7)
    eng = BatchPathEngine(g, EngineConfig(min_cap=64))
    qs1 = generators.similar_queries(g, 6, similarity=0.8, k_range=(3, 4),
                                     seed=8)
    qs2 = qs1[:3] + generators.similar_queries(g, 3, similarity=0.8,
                                               k_range=(3, 4), seed=9)
    for qs in (qs1, qs2, qs1):
        res = eng.process(qs, mode="batch")
        for qi, (s, t, k) in enumerate(qs):
            assert path_set(res.paths[qi]) == \
                path_set(enumerate_paths_bruteforce(g, s, t, k)), (qs, qi)


def test_n_dedup_counts_per_direction():
    """n_dedup = halves that mapped onto an existing plan node, summed over
    both directions (the seed version short-circuited on an empty dict and
    double-counted otherwise)."""
    g = generators.erdos(60, 3.0, seed=12)
    qs = generators.random_queries(g, 3, (3, 4), seed=13)
    eng = BatchPathEngine(g, EngineConfig(min_cap=64))

    # 3 identical queries: each direction collapses 3 halves onto 1 node
    res = eng.process([qs[0]] * 3, mode="batch")
    assert res.stats["n_dedup"] == 4  # (3-1) forward + (3-1) backward

    # queries with pairwise-distinct sources and targets share no halves
    seen_s, seen_t, distinct = set(), set(), []
    for s, t, k in qs:
        if s not in seen_s and t not in seen_t:
            distinct.append((s, t, k))
            seen_s.add(s)
            seen_t.add(t)
    res = eng.process(distinct, mode="batch")
    assert res.stats["n_dedup"] == 0


@pytest.mark.parametrize("seed", range(6))
def test_splice_dag_drops_exactly_the_cycle_closing_edges(seed):
    """Incremental topological order vs a full reachability test on every
    insert: the same edges are kept, in the same per-node order."""
    import numpy as np
    from repro.core.detect import PlanNode, _SpliceDAG

    r = np.random.default_rng(seed)
    nodes = [PlanNode(nid=i, src=i, budget=0, query=None) for i in range(5)]
    ref = {i: [] for i in range(5)}            # parent -> spliced children
    dag = _SpliceDAG(nodes)

    def reaches(a, b):                          # path a ->* b in ref?
        seen, stack = set(), [a]
        while stack:
            x = stack.pop()
            if x == b:
                return True
            for y in ref[x]:
                if y not in seen:
                    seen.add(y)
                    stack.append(y)
        return False

    for _ in range(400):
        if r.random() < 0.05:
            nid = len(nodes)
            nodes.append(PlanNode(nid=nid, src=nid, budget=0, query=None))
            dag.append()
            ref[nid] = []
        child, parent = (int(x) for x in r.integers(0, len(nodes), 2))
        dag.add_edge(child, parent)
        if child != parent and child not in ref[parent] \
                and not reaches(child, parent):
            ref[parent].append(child)
    assert {n.nid: n.in_edges for n in nodes} == ref
    order = dag.ord
    assert all(order[p] < order[c] for p in ref for c in ref[p])


def test_splice_hits_marks_roots_with_a_trigger():
    import jax.numpy as jnp
    import numpy as np
    from repro.core.enumerate import splice_hits

    n = 10
    nbrs = np.array([[1, 2, n], [3, 1, n], [4, 5, 6]], np.int32)
    hit = np.array([[True, False, False], [False, False, False],
                    [False, True, True]])
    roots = np.array([1, 2, 5, 6, 7, n, n, n], np.int32)
    got = splice_hits(jnp.asarray(nbrs), jnp.asarray(hit), jnp.asarray(roots),
                      n=n)
    np.testing.assert_array_equal(
        np.asarray(got), [True, False, True, True, False, False, False,
                          False])


def test_run_with_a_given_index_matches_its_own():
    """engine.run over a prebuilt index (and a subset of a bigger one)
    answers exactly as with the index it would build itself."""
    from repro.core.index import build_index
    from repro.core.query import PathQuery

    g = generators.community(300, n_comm=3, avg_deg=4, seed=5)
    qs = [PathQuery(s, t, 4) for s, t, _ in
          generators.random_queries(g, 6, k_range=(4, 4), seed=2)]
    eng = BatchPathEngine(g, EngineConfig(min_cap=64))
    full = build_index(eng.dg, [q.key for q in qs])
    want = eng.run(qs[2:5])
    got = eng.run(qs[2:5], index=full.subset([2, 3, 4]))
    assert [path_set(r.paths) for r in got] == \
        [path_set(r.paths) for r in want]
    with pytest.raises(ValueError):
        eng.run(qs[:2], index=full.subset([2, 3]))
