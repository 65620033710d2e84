"""Each configuration's graph generator: deterministic per seed, at its
published size, and the published model at a size a brute force can
check."""
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from generators import graphs
from reference import Adjacency

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
GRAPHS = Path(__file__).resolve().parents[1] / "graphs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", ["rgg-n21", "delaunay-n20"])
def test_deterministic_per_seed(name):
    cfg = dict(_config(name), params=dict(_config(name)["params"], n=4096))
    n, src, dst = graphs.make(cfg, GRAPHS)
    n2, src2, dst2 = graphs.make(cfg, GRAPHS)
    assert n == n2 and np.array_equal(src, src2) and np.array_equal(dst, dst2)
    other = dict(cfg, params=dict(cfg["params"], seed=2**31 + 12345))
    _, src3, _ = graphs.make(other, GRAPHS)
    assert not np.array_equal(src, src3)


@pytest.mark.parametrize("name,log_n,rel,ell", [("rgg-n21", 21, 0.002, 64),
                                                ("delaunay-n20", 20, 1e-4, 32)])
def test_published_size(name, log_n, rel, ell):
    """The published vertex count, the edge count within a draw's
    spread of the published instance's, every edge both ways, and the
    maximum degree inside the ELL width the configuration states (the
    program sizes the width to the power of two at or above it)."""
    cfg = _config(name)
    n, src, dst = graphs.make(cfg, GRAPHS)
    assert n == cfg["published"]["n"] == cfg["params"]["n"] == 1 << log_n
    adj = Adjacency.build(n, src, dst)
    assert adj.m == src.size             # no self-loop, no repeated arc
    assert abs(adj.m / 2 / cfg["published"]["edges"] - 1) < rel
    assert np.array_equal(np.sort(src * n + dst), np.sort(dst * n + src))
    assert ell // 2 < np.diff(adj.out_ptr).max() <= ell


@pytest.mark.parametrize("name,digest", [
    ("rgg-n21",
     "62e77ff285f410c6a74c229c6546a1443d9bafe4b22ee13d93c4e81e11d34d7b"),
    ("delaunay-n20",
     "0ccb3fd7511e46ed089ca256de3b767df22637a1274adb7e679dc00519421fc3"),
])
def test_committed_instance_is_pinned(name, digest):
    """The committed configurations' generator and params, at n = 4096,
    draw the same arcs as when the digests were taken: sha256 of n, src
    and dst as int64 bytes."""
    cfg = _config(name)
    n, src, dst = graphs.make(dict(cfg, params=dict(cfg["params"], n=4096)),
                              GRAPHS)
    h = hashlib.sha256(np.array([n], np.int64).tobytes())
    h.update(np.ascontiguousarray(src, np.int64).tobytes())
    h.update(np.ascontiguousarray(dst, np.int64).tobytes())
    assert h.hexdigest() == digest


def _points(seed, n):
    return graphs._points(seed, n)


def test_rgg_is_the_distance_threshold():
    """At 600 points: an edge joins exactly the pairs closer than
    0.55 * sqrt(ln n / n), found by brute force."""
    n, seed = 600, 3
    _, src, dst = graphs.rgg(seed, n, 0.55)
    p = _points(seed, n)
    d = np.linalg.norm(p[:, None, :] - p[None, :, :], axis=2)
    want = np.argwhere((d < 0.55 * np.sqrt(np.log(n) / n))
                       & ~np.eye(n, dtype=bool))
    got = np.stack([src, dst], axis=1)
    assert np.array_equal(want[np.lexsort(want.T[::-1])],
                          got[np.lexsort(got.T[::-1])])


def test_delaunay_is_a_triangulation():
    """A triangulation of n points with h on the hull has 3n - 3 - h
    edges, and every point is on at least two."""
    from scipy.spatial import ConvexHull

    n, seed = 5000, 4
    _, src, _ = graphs.delaunay(seed, n)
    h = len(ConvexHull(_points(seed, n)).vertices)
    assert src.size == 2 * (3 * n - 3 - h)
    assert np.bincount(src, minlength=n).min() >= 2


def test_unknown_generator_is_refused():
    with pytest.raises(ValueError, match="unknown graph generator"):
        graphs.make({"generator": "nope", "params": {}}, GRAPHS)
