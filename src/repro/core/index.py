"""Query index: per-source/target distance matrices, slack vectors, DP planner.

This is PathEnum's light-weight index (Lemma 3.1) built for the whole batch
with one multi-source BFS per direction (Alg 1/4 lines 1-2), plus two
engine-internal derived products:

  * slack vectors -- per-query / per-shared-node prune thresholds
      slack[v] = max over consumers (k_q - offset_q - dist(v, endpoint_q))
    A frontier vertex v at depth d survives iff d <= slack[v]
    (equivalently Lemma 3.1's  |p| + dist(v, t) <= k). One device pass
    over a distance table (``slack_vector``).

  * walk-count DP -- c_{l+1}[v] = sum_{(u,v)} c_l[u] * [slack[v] >= l+1]
    an upper bound on per-level path counts, used to plan static buffer
    capacities and to pick the forward/backward split (the "+" variants'
    cost-based search order, after PathEnum [15]). Host-side over the
    CSR, touching only the vertices the walks reach (``walk_counts``).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .graph import DeviceGraph, ragged_arange
from .msbfs import (count_sweep, edge_span, msbfs_dist, msbfs_dist_ell,
                    INF_FOR)

__all__ = ["QueryIndex", "build_index", "walk_counts", "slack_vector",
           "column_reach"]

Query = tuple[int, int, int]  # (s, t, k)


@dataclasses.dataclass(frozen=True)
class QueryIndex:
    queries: tuple[Query, ...]
    k_max: int
    sources: np.ndarray       # (Su,) unique source vertices
    targets: np.ndarray       # (Tu,) unique target vertices
    src_col: np.ndarray       # (Q,) column of q.s in dist_s
    tgt_col: np.ndarray       # (Q,) column of q.t in dist_t
    dist_s: jax.Array         # (n+1, Su) int8 -- dist_G(s, v); row n = INF
    dist_t: jax.Array         # (n+1, Tu) int8 -- dist_{G_r}(t, v) = dist_G(v, t)
    INF: int

    def subset(self, qis: Sequence[int]) -> "QueryIndex":
        """The index of queries ``qis`` (positions in this one), sharing
        its distance tables: a column serves every query with that
        endpoint, and distances up to each query's k are the same."""
        qis = np.asarray(qis, np.int64)
        return dataclasses.replace(
            self, queries=tuple(self.queries[i] for i in qis),
            src_col=self.src_col[qis], tgt_col=self.tgt_col[qis])

    def on_device(self, device) -> "QueryIndex":
        """This index with its distance tables copied to ``device``."""
        return dataclasses.replace(
            self, dist_s=jax.device_put(self.dist_s, device),
            dist_t=jax.device_put(self.dist_t, device))

    def table(self, forward: bool) -> tuple[jax.Array, np.ndarray]:
        """The distance table a search in that direction prunes with and
        each query's column in it: dist_t / tgt_col for forward searches
        (distance to the target), dist_s / src_col for backward ones."""
        return ((self.dist_t, self.tgt_col) if forward
                else (self.dist_s, self.src_col))


@jax.jit
def slack_vector(dist: jax.Array, reach: jax.Array, inf) -> jax.Array:
    """(n+1,) int8 prune thresholds from one (n+1, C) int8 distance table.

    slack[v] = max over columns c of reach[c] - dist[v, c], floored at -1,
    with a distance >= ``inf`` contributing -1; row n is -1. ``reach[c]``
    is the largest k_q - offset_q over the consumers whose endpoint is
    column c, and -1 for a column no consumer uses (which then cannot
    raise any entry above -1). One dense pass over the table on the
    device: no column gather and no host copy.
    """
    d = dist.astype(jnp.int32)
    val = jnp.where(d >= inf, -1, reach[None, :] - d)
    out = jnp.maximum(jnp.max(val, axis=1), -1).astype(jnp.int8)
    return out.at[-1].set(-1)


def column_reach(n_cols: int, cols: np.ndarray, reach: np.ndarray) -> np.ndarray:
    """The ``reach`` argument of :func:`slack_vector`: the largest of
    ``reach`` per column in ``cols`` (several consumers may share an
    endpoint), -1 elsewhere."""
    out = np.full(n_cols, -1, np.int32)
    np.maximum.at(out, np.asarray(cols, np.int64), np.asarray(reach, np.int32))
    return out


def build_index(dg: DeviceGraph, queries: Sequence[Query],
                edge_chunk: int = 1 << 22,
                backend: Optional[str] = None) -> QueryIndex:
    """Multi-source BFS from all sources on G and all targets on G_r.

    ``dg``'s edge lists may be sentinel-padded to a pow2 bucket; the
    chunk-rounded valid-edge span (``edge_span``) is threaded into the
    MS-BFS so the sweep skips all-sentinel chunks without the raw edge
    count ever becoming a trace-shaping value.

    ``backend``: a resolved kernel backend. ``None``/``"jnp"`` runs the
    segment-op sweeps over the edge lists; ``"pallas"``/``"interpret"``
    runs the fused bit-packed ELL sweeps (``msbfs_dist_ell``) — one
    dispatch per level, bit-equal distances — over ``dg.sweep_table``
    (the sliced ELL, or the padded ELL of a delta-patched graph), each
    counted by :func:`~repro.core.msbfs.count_sweep`. Forward distances
    gather the reverse table (in-neighbors of G) and vice versa; the ELL
    tables are replicated even on a sharded engine, so the kernel route
    never depends on the GSPMD edge partition.
    """
    queries = tuple((int(s), int(t), int(k)) for s, t, k in queries)
    k_max = max(k for _, _, k in queries)
    srcs = np.unique(np.array([q[0] for q in queries], np.int32))
    tgts = np.unique(np.array([q[1] for q in queries], np.int32))
    src_col = np.searchsorted(srcs, [q[0] for q in queries]).astype(np.int32)
    tgt_col = np.searchsorted(tgts, [q[1] for q in queries]).astype(np.int32)
    if backend is not None and backend != "jnp":
        r_table, table = dg.sweep_table(True), dg.sweep_table(False)
        dist_s = msbfs_dist_ell(r_table, jnp.asarray(srcs),
                                n=dg.n, k_max=k_max, backend=backend)
        dist_t = msbfs_dist_ell(table, jnp.asarray(tgts),
                                n=dg.n, k_max=k_max, backend=backend)
        for t in (r_table, table):
            count_sweep(t, dg.n, dg.m, k_max)
    else:
        m_valid = edge_span(dg.m, edge_chunk, dg.m_cap)
        dist_s = msbfs_dist(dg.esrc, dg.edst, jnp.asarray(srcs),
                            n=dg.n, k_max=k_max, edge_chunk=edge_chunk,
                            m_valid=m_valid)
        dist_t = msbfs_dist(dg.r_esrc, dg.r_edst, jnp.asarray(tgts),
                            n=dg.n, k_max=k_max, edge_chunk=edge_chunk,
                            m_valid=m_valid)
    return QueryIndex(queries=queries, k_max=k_max, sources=srcs, targets=tgts,
                      src_col=src_col, tgt_col=tgt_col,
                      dist_s=dist_s, dist_t=dist_t, INF=INF_FOR(k_max))


def walk_counts(indptr: np.ndarray, indices: np.ndarray, source: int,
                slack: np.ndarray, budget: int) -> np.ndarray:
    """Per-level pruned-walk counts from ``source`` over a host CSR: an
    upper bound on the enumeration's frontier sizes.

    A walk may step onto v at level l iff ``slack[v] >= l`` (``slack`` is
    the host copy of the search's (n+1,) int8 prune vector). Only the
    vertices the pruned walks reach are touched, a few thousand for a
    half-query at k=6, never all n per level. Returns (budget+1,) float64
    totals, level 0 == 1.
    """
    verts = np.array([source], np.int64)
    cnt = np.ones(1, np.float64)
    totals = [1.0]
    for lvl in range(1, budget + 1):
        starts = indptr[verts]
        deg = (indptr[verts + 1] - starts).astype(np.int64)
        nbr = indices[np.repeat(starts, deg) + ragged_arange(deg)]
        w = np.repeat(cnt, deg)
        keep = slack[nbr] >= lvl
        verts, inv = np.unique(nbr[keep], return_inverse=True)
        cnt = np.bincount(inv, weights=w[keep], minlength=verts.size)
        totals.append(float(cnt.sum()))
        if verts.size == 0:
            totals += [0.0] * (budget - lvl)
            break
    return np.array(totals)

