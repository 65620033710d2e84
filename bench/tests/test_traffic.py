"""The query stream gives the mix its traffic file states."""
import json
from pathlib import Path

import numpy as np
import pytest

from generators import graphs, queries as qgen
from reference import Adjacency, answer

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def adj():
    n, src, dst = graphs.rgg(5, 1 << 13, 0.55)
    return Adjacency.build(n, src, dst)


@pytest.mark.parametrize("name,k", [("shared64-k5", 5), ("random64-k8", 8)])
def test_batch_size_k_and_output_cycle(adj, name, k):
    mix = _mix(name)
    kinds = []
    for i in range(3):
        b = qgen.batch(adj, mix, 2**40 + 3, qgen.WINDOW, i)
        assert len(b) == mix["batch"] == 64
        assert all(q[2] == k and q[0] != q[1] for q in b)
        kinds += [q[3] for q in b]
    assert kinds == [("paths", "count", "exists")[j % 3]
                     for j in range(len(kinds))]


def test_batches_repeat_per_seed(adj):
    mix = _mix("shared64-k5")
    a = qgen.batch(adj, mix, 99, qgen.WINDOW, 4)
    assert a == qgen.batch(adj, mix, 99, qgen.WINDOW, 4)
    assert a != qgen.batch(adj, mix, 99, qgen.WINDOW, 3)
    assert a != qgen.batch(adj, mix, 100, qgen.WINDOW, 4)


def test_window_batches_are_fresh(adj):
    """The window never sends a batch that set-up sent."""
    mix = _mix("shared64-k5")
    warm = {tuple(q[:2] for q in qgen.batch(adj, mix, 5, qgen.WARM, i))
            for i in range(mix["warm_batches"])}
    for i in range(8):
        b = qgen.batch(adj, mix, 5, qgen.WINDOW, i)
        assert tuple(q[:2] for q in b) not in warm


@pytest.mark.parametrize("name", ["shared64-k5", "random64-k8"])
def test_every_seed_serves_the_same_batches(adj, name):
    """Every seed's window holds the same fresh batches in the same turn,
    each with its queries in the seed's own order, none of them a warm-up
    batch."""
    mix = _mix(name)
    first = qgen.window_batches(adj, mix, 2**40 + 3)
    assert len(first) == mix["window_batches"]
    orders = {tuple(map(tuple, first))}
    for seed in (1, 2**33 + 17, 987654321):
        got = qgen.window_batches(adj, mix, seed)
        assert [sorted(b) for b in got] == [sorted(b) for b in first]
        orders.add(tuple(map(tuple, got)))
    assert len(orders) == 4
    key = lambda b: frozenset(q[:2] for q in b)  # noqa: E731
    assert len({key(b) for b in first}) == len(first)
    assert not {key(b) for b in first} & {
        key(b) for b in qgen.warm_batches(adj, mix)}


def test_shared_share(adj):
    """Without perturbation the shared part is copies of the seed pairs:
    51 of 64 queries (80%) fall on at most 4 distinct pairs."""
    mix = dict(_mix("shared64-k5"), shared={"share": 0.8,
                                            "seeds_per_batch": 4,
                                            "perturb_p": 0.0})
    for i in range(5):
        b = qgen.batch(adj, mix, 17, qgen.WINDOW, i)
        pairs = {}
        for s, t, _, _ in b:
            pairs[(s, t)] = pairs.get((s, t), 0) + 1
        top = sorted(pairs.values(), reverse=True)[:4]
        assert sum(top) >= 51


def _seed_of(adj, b):
    """For each query, the seed pairs (s0, t0) it can come from."""
    out = []
    for s, t, _, _ in b:
        out.append({(s0, t0)
                    for s0 in {s, *adj.out_neighbors(s).tolist()}
                    for t0 in {t, *adj.in_neighbors(t).tolist()}})
    return out


def test_shared_queries_are_one_hop_perturbations(adj):
    """With perturbation, a shared query's s is a seed's s or one of its
    in-neighbours, and its t the seed's t or one of its out-neighbours:
    most queries of a batch sit within one hop of a few seed pairs."""
    mix = _mix("shared64-k5")
    for i in range(3):
        b = qgen.batch(adj, mix, 23, qgen.WINDOW, i)
        hits = {}
        for seeds in _seed_of(adj, b):
            for pair in seeds:
                hits[pair] = hits.get(pair, 0) + 1
        assert sum(sorted(hits.values(), reverse=True)[:4]) >= 51


def test_perturbation_rate(adj):
    """Each endpoint of a shared query moves with probability
    ``perturb_p``: over many batches, about half of the shared queries
    keep their seed's s, and about a quarter keep the whole pair."""
    mix = dict(_mix("shared64-k5"), shared={"share": 1.0,
                                            "seeds_per_batch": 1,
                                            "perturb_p": 0.5})
    keep_s = keep_both = total = 0
    for i in range(40):
        b = qgen.batch(adj, mix, 31, qgen.WINDOW, i)
        pairs = {}
        for s, t, _, _ in b:
            pairs[(s, t)] = pairs.get((s, t), 0) + 1
        s_count = np.bincount([q[0] for q in b]).max()
        keep_s += s_count
        keep_both += max(pairs.values())
        total += len(b)
    assert 0.4 < keep_s / total < 0.65
    assert 0.18 < keep_both / total < 0.35


def test_random_walk_queries_have_a_path():
    """t ends a walk of at most k steps from s, so a path of at most k
    arcs exists: the reference must say so for every query."""
    n, src, dst = graphs.delaunay(5, 1 << 12)
    tri = Adjacency.build(n, src, dst)
    mix = _mix("random64-k8")
    b = qgen.batch(tri, mix, 8, qgen.WINDOW, 0)
    assert all(answer(tri, s, t, k, "exists") for s, t, k, _ in b)


def test_k_range(adj):
    mix = dict(_mix("random64-k8"), k=[4, 7])
    ks = {q[2] for i in range(4)
          for q in qgen.batch(adj, mix, 1, qgen.WINDOW, i)}
    assert ks == {4, 5, 6, 7}
