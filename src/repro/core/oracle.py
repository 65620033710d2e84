"""Brute-force references for tests: pure-Python DFS enumeration + host BFS.

These are the ground truth every engine variant (BasicEnum, BasicEnum+,
BatchEnum, BatchEnum+) is validated against. Deliberately simple: a DFS
over the host CSR, pruned by a level-synchronous numpy BFS.
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from .graph import Graph

__all__ = ["enumerate_paths_bruteforce", "bfs_dist_from", "path_set"]


def bfs_dist_from(g: Graph, s: int, k_max: int, reverse: bool = False) -> np.ndarray:
    """Host BFS distances from s, capped at k_max (unreached = k_max+1).

    Level-synchronous over the CSR arrays: each level gathers every
    out-edge of the frontier at once, so a ball of millions of vertices
    costs a few numpy passes rather than a Python loop per edge.
    """
    indptr, indices = (g.r_indptr, g.r_indices) if reverse \
        else (g.indptr, g.indices)
    INF = k_max + 1
    dist = np.full(g.n, INF, dtype=np.int32)
    dist[s] = 0
    frontier = np.array([s], dtype=np.int64)
    for d in range(1, k_max + 1):
        lo, hi = indptr[frontier], indptr[frontier + 1]
        deg = (hi - lo).astype(np.int64)
        if not deg.sum():
            break
        starts = np.repeat(lo - np.cumsum(deg) + deg, deg)
        nbrs = indices[starts + np.arange(int(deg.sum()))]
        nbrs = np.unique(nbrs[dist[nbrs] == INF])
        if not nbrs.size:
            break
        dist[nbrs] = d
        frontier = nbrs.astype(np.int64)
    return dist


def enumerate_paths_bruteforce(g: Graph, s: int, t: int, k: int) -> list[tuple[int, ...]]:
    """All simple paths s->t with <= k hops, via recursive DFS."""
    out: list[tuple[int, ...]] = []
    if s == t or k <= 0:
        return out
    # prune with reverse BFS to keep the oracle usable on medium graphs
    dist_t = bfs_dist_from(g, t, k, reverse=True)
    path = [s]
    on_path = {s}

    def dfs(u: int):
        depth = len(path) - 1
        if u == t and depth >= 1:
            out.append(tuple(path))
            return  # extensions of a path through t would revisit t
        if depth == k:
            return
        for v in g.neighbors(u):
            v = int(v)
            if v in on_path:
                continue
            if depth + 1 + dist_t[v] > k:
                continue
            path.append(v)
            on_path.add(v)
            dfs(v)
            path.pop()
            on_path.remove(v)

    dfs(s)
    return out


def path_set(paths: Iterable) -> set[tuple[int, ...]]:
    """Normalize any iterable of paths (lists/arrays) to a set of tuples."""
    out = set()
    for p in paths:
        p = tuple(int(x) for x in np.asarray(p) if int(x) >= 0)
        out.add(p)
    return out
