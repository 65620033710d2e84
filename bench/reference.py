"""Plain reference for hop-constrained s-t simple path queries.

Independent of the program: it builds its own adjacency from the arc list
the benchmark generated and imports nothing of ``repro``. Semantics, as
every configuration states them: the graph is a simple digraph (self-loops
and repeated arcs dropped); the answer to (s, t, k) is every simple path
from s to t with at most k arcs, each exactly once; ``count`` is their
number and ``exists`` whether there is one.

Enumeration meets in the middle. With a = ceil(k / 2) and b = k - a, a
path of L arcs is split after min(L, a) arcs: paths of at most a arcs are
found by the forward search alone (which stops at t); longer ones are a
forward prefix of exactly a arcs that avoids t, joined on its last vertex
with a backward path of 1..b arcs from t, the two sharing only that
vertex. Every path has exactly one such split, so none is counted twice.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Adjacency", "answer", "simple_paths"]


@dataclasses.dataclass(frozen=True)
class Adjacency:
    """Out- and in-neighbour lists (CSR) of a simple digraph."""

    n: int
    out_ptr: np.ndarray
    out_idx: np.ndarray
    in_ptr: np.ndarray
    in_idx: np.ndarray

    @staticmethod
    def build(n: int, src: np.ndarray, dst: np.ndarray) -> "Adjacency":
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)
        keep = src != dst
        key = np.sort(src[keep] * n + dst[keep])       # sorted by (src, dst)
        key = key[np.concatenate([[True], key[1:] != key[:-1]])]
        s, d = np.divmod(key, n)
        rkey = np.sort(d * n + s)                      # sorted by (dst, src)
        rd, rs = np.divmod(rkey, n)
        out_ptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(s, minlength=n), out=out_ptr[1:])
        in_ptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(rd, minlength=n), out=in_ptr[1:])
        return Adjacency(n, out_ptr, d.astype(np.int32), in_ptr,
                         rs.astype(np.int32))

    @property
    def m(self) -> int:
        return int(self.out_idx.size)

    def out_neighbors(self, v: int) -> np.ndarray:
        return self.out_idx[self.out_ptr[v]:self.out_ptr[v + 1]]

    def in_neighbors(self, v: int) -> np.ndarray:
        return self.in_idx[self.in_ptr[v]:self.in_ptr[v + 1]]


def _extend(paths: np.ndarray, ptr: np.ndarray, idx: np.ndarray
            ) -> np.ndarray:
    """Every simple one-arc extension of each row of ``paths`` (P, l)."""
    last = paths[:, -1]
    lo = ptr[last]
    deg = ptr[last + 1] - lo
    rows = np.repeat(np.arange(paths.shape[0]), deg)
    offs = np.arange(int(deg.sum())) - np.repeat(np.cumsum(deg) - deg, deg)
    nbr = idx[np.repeat(lo, deg) + offs].astype(np.int64)
    prev = paths[rows]
    simple = ~(prev == nbr[:, None]).any(axis=1)
    return np.concatenate([prev, nbr[:, None]], axis=1)[simple]


def simple_paths(adj: Adjacency, s: int, t: int, k: int) -> list[tuple]:
    """All simple s-t paths of at most ``k`` arcs, sorted."""
    if s == t or k < 1:
        return []
    a = (k + 1) // 2
    b = k - a
    found: list[np.ndarray] = []
    fwd = np.array([[s]], np.int64)
    for _ in range(a):
        fwd = _extend(fwd, adj.out_ptr, adj.out_idx)
        at_t = fwd[:, -1] == t
        found.append(fwd[at_t])
        fwd = fwd[~at_t]
    out = [tuple(int(x) for x in p) for f in found for p in f]
    if b and fwd.shape[0]:
        order = np.argsort(fwd[:, -1], kind="stable")
        fwd = fwd[order]
        mids = fwd[:, -1]
        bwd = np.array([[t]], np.int64)       # t, then its predecessors
        for _ in range(b):
            bwd = _extend(bwd, adj.in_ptr, adj.in_idx)
            lo = np.searchsorted(mids, bwd[:, -1], side="left")
            hi = np.searchsorted(mids, bwd[:, -1], side="right")
            cnt = hi - lo
            if not cnt.sum():
                continue
            bi = np.repeat(np.arange(bwd.shape[0]), cnt)
            fi = np.repeat(lo, cnt) + (np.arange(int(cnt.sum()))
                                       - np.repeat(np.cumsum(cnt) - cnt, cnt))
            pre = fwd[fi]                      # s .. mid
            suf = bwd[bi][:, ::-1]             # mid .. t
            clash = (pre[:, :-1, None] == suf[:, None, 1:]).any(axis=(1, 2))
            joined = np.concatenate([pre, suf[:, 1:]], axis=1)[~clash]
            out.extend(tuple(int(x) for x in p) for p in joined)
    out.sort()
    return out


def answer(adj: Adjacency, s: int, t: int, k: int, output: str):
    """The reference answer in the form the comparison reads: the sorted
    path list, the count, or the existence flag."""
    paths = simple_paths(adj, s, t, k)
    if output == "paths":
        return paths
    if output == "count":
        return len(paths)
    return bool(paths)
