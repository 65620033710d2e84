"""Dynamic-graph subsystem: batched edge deltas with incremental CSR merge.

Streaming workloads (fraud detection, real-time social graphs) interleave
queries with continuous edge arrivals. Rebuilding the graph from scratch
(``Graph.from_edges``) for every mutation re-sorts the whole edge list and
forces the serving stack to cold-start; this module makes a mutation
proportional to its *size* instead:

  * ``GraphDelta``    -- a normalized batch of edge insertions/deletions
                         (self-loops dropped, duplicates collapsed, vertex
                         set fixed — matching ``from_edges`` semantics).
  * ``apply_delta``   -- successor graph by sorted-key CSR merge in both
                         directions: kept edges are copied in bulk, the
                         few changed rows absorb the inserts, nothing is
                         re-sorted. Returns the *effective* change set
                         (edges actually inserted/removed after no-op
                         elimination) and the touched vertices — the
                         locality radius everything downstream (ELL row
                         refresh, hop-scoped cache invalidation) keys off.
  * ``update_device_graph`` -- patches a :class:`DeviceGraph` in place of a
                         full rebuild: edge lists re-uploaded sentinel-
                         padded inside their pow2 shape bucket (no traced
                         shape changes while churn stays in-bucket), only
                         touched ELL rows recomputed and scattered; falls
                         back to ``DeviceGraph.build`` when a row outgrows
                         the current ELL capacity.
  * ``host_set_dist``   -- BFS from the touched frontier for hop-scoped
                         cache invalidation. Both endpoints of every
                         changed edge are seeds, so frontier distances
                         agree on the old, new, and union graphs — one
                         sweep over the *old* CSR certifies cached state
                         and its fresh recomputation alike.

Delta semantics: deletions apply first, then insertions —
``new = (old − remove) ∪ add``. Deleting an absent edge and inserting a
present one are no-ops and do not mark vertices as touched.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence

import numpy as np

from .graph import (DeviceGraph, Graph, ragged_arange, pad_edge_list,
                    pow2_ceil)

__all__ = ["GraphDelta", "AppliedDelta", "apply_delta",
           "update_device_graph", "host_set_dist", "pow2_ceil"]


def _normalize_pairs(src, dst, drop_self_loops: bool) -> tuple[np.ndarray, np.ndarray]:
    src = np.asarray(src, dtype=np.int64).ravel()
    dst = np.asarray(dst, dtype=np.int64).ravel()
    if src.shape != dst.shape:
        raise ValueError("src/dst arrays must have equal length")
    if src.size and (src.min() < 0 or dst.min() < 0):
        raise ValueError("vertex ids must be >= 0")
    if drop_self_loops and src.size:
        keep = src != dst
        src, dst = src[keep], dst[keep]
    if src.size:  # dedupe pairs without knowing n (delta is graph-agnostic)
        pairs = np.unique(np.stack([src, dst], axis=1), axis=0)
        src, dst = pairs[:, 0], pairs[:, 1]
    return src, dst


@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """A normalized batch of edge mutations against a fixed vertex set.

    Insertions drop self-loops (never on a simple path, mirroring
    ``Graph.from_edges``) and both lists are deduplicated at construction,
    so a delta is a pair of edge *sets*. Vertex-id bounds are checked
    against the graph at apply time.
    """

    add_src: np.ndarray
    add_dst: np.ndarray
    del_src: np.ndarray
    del_dst: np.ndarray

    def __post_init__(self):
        a_s, a_d = _normalize_pairs(self.add_src, self.add_dst,
                                    drop_self_loops=True)
        d_s, d_d = _normalize_pairs(self.del_src, self.del_dst,
                                    drop_self_loops=False)
        object.__setattr__(self, "add_src", a_s)
        object.__setattr__(self, "add_dst", a_d)
        object.__setattr__(self, "del_src", d_s)
        object.__setattr__(self, "del_dst", d_d)

    @classmethod
    def from_pairs(cls, add: Sequence = (), remove: Sequence = ()) -> "GraphDelta":
        """Build from iterables of ``(u, v)`` pairs."""
        add = np.asarray(list(add), dtype=np.int64).reshape(-1, 2)
        rem = np.asarray(list(remove), dtype=np.int64).reshape(-1, 2)
        return cls(add[:, 0], add[:, 1], rem[:, 0], rem[:, 1])

    @classmethod
    def empty(cls) -> "GraphDelta":
        z = np.zeros(0, np.int64)
        return cls(z, z, z, z)

    @property
    def n_add(self) -> int:
        return int(self.add_src.size)

    @property
    def n_del(self) -> int:
        return int(self.del_src.size)

    def __bool__(self) -> bool:
        return self.n_add > 0 or self.n_del > 0

    def max_vertex(self) -> int:
        """Largest vertex id referenced (-1 for an empty delta)."""
        parts = [a for a in (self.add_src, self.add_dst,
                             self.del_src, self.del_dst) if a.size]
        return int(max(int(a.max()) for a in parts)) if parts else -1


class AppliedDelta(NamedTuple):
    """Result of merging one delta: the successor graph plus the effective
    change set (after no-op elimination) in both decoded and key form."""

    graph: Graph
    added_src: np.ndarray     # (na,) int64 — edges actually inserted
    added_dst: np.ndarray
    removed_src: np.ndarray   # (nr,) int64 — edges actually removed
    removed_dst: np.ndarray
    touched: np.ndarray       # (nt,) int64 — unique endpoints of all changes

    @property
    def n_changed(self) -> int:
        return int(self.added_src.size + self.removed_src.size)


def _member(a: np.ndarray, b_sorted: np.ndarray) -> np.ndarray:
    """Mask over ``a``: which elements occur in sorted array ``b_sorted``."""
    if b_sorted.size == 0 or a.size == 0:
        return np.zeros(a.size, dtype=bool)
    pos = np.searchsorted(b_sorted, a)
    hit = pos < b_sorted.size
    out = np.zeros(a.size, dtype=bool)
    out[hit] = b_sorted[pos[hit]] == a[hit]
    return out


def _merge_disjoint_sorted(kept: np.ndarray, added: np.ndarray) -> np.ndarray:
    """Merge two sorted, disjoint key arrays in O(len) — no re-sort."""
    if added.size == 0:
        return kept
    if kept.size == 0:
        return added
    out = np.empty(kept.size + added.size, dtype=kept.dtype)
    # final index of each element = own rank + #smaller elements of the other
    out[np.arange(kept.size) + np.searchsorted(added, kept)] = kept
    out[np.arange(added.size) + np.searchsorted(kept, added)] = added
    return out


def _csr_keys(indptr: np.ndarray, indices: np.ndarray, n: int) -> np.ndarray:
    """``row * n + col`` keys of a CSR, ascending (rows sorted, cols sorted
    within each row)."""
    rows = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr))
    return rows * n + indices


def _merged_csr(indptr: np.ndarray, indices: np.ndarray, n: int,
                removed_keys: np.ndarray, added_keys: np.ndarray,
                key_old: Optional[np.ndarray] = None,
                ) -> tuple[np.ndarray, np.ndarray]:
    """One direction of the CSR merge; all key arrays ``row * n + col``
    ascending."""
    if key_old is None:
        key_old = _csr_keys(indptr, indices, n)
    kept = key_old[~_member(key_old, removed_keys)]
    new_key = _merge_disjoint_sorted(kept, added_keys)
    # indptr shifts by the cumulative per-row degree change — O(n + d),
    # no O(m) bincount over the whole edge list
    delta_deg = (np.bincount(added_keys // n, minlength=n)
                 - np.bincount(removed_keys // n, minlength=n)).astype(np.int64)
    new_indptr = indptr + np.concatenate([[0], np.cumsum(delta_deg)])
    return new_indptr, (new_key % n).astype(np.int32)


def apply_delta(g: Graph, delta: GraphDelta) -> AppliedDelta:
    """Merge a delta into ``g``: ``new = (old − remove) ∪ add``.

    Equivalent to ``Graph.from_edges`` on the edited edge list (the
    property tests assert this bit-for-bit, both CSR directions), but kept
    edges are copied without re-sorting. Requires a deduplicated graph
    (``from_edges`` default).
    """
    n = g.n
    if delta.max_vertex() >= n:
        raise ValueError(f"delta references vertices outside the graph "
                         f"(n={n}, max id {delta.max_vertex()})")
    key_old = _csr_keys(g.indptr, g.indices, n)
    add_key = delta.add_src * n + delta.add_dst          # unique by construction
    del_key = delta.del_src * n + delta.del_dst
    # effective change set: deleting an absent edge / inserting a present
    # one is a no-op; delete-then-insert of a present edge cancels out
    removed = del_key[_member(del_key, key_old) & ~_member(del_key, add_key)]
    added = add_key[~_member(add_key, key_old)]
    if removed.size == 0 and added.size == 0:
        z = np.zeros(0, np.int64)
        return AppliedDelta(graph=g, added_src=z, added_dst=z,
                            removed_src=z, removed_dst=z, touched=z)

    indptr, indices = _merged_csr(g.indptr, g.indices, n, removed, added,
                                  key_old=key_old)
    # reverse direction: rekey (u, v) -> v * n + u
    removed_r = np.sort((removed % n) * n + removed // n)
    added_r = np.sort((added % n) * n + added // n)
    r_indptr, r_indices = _merged_csr(g.r_indptr, g.r_indices, n,
                                      removed_r, added_r)
    g2 = Graph(n=n, indptr=indptr, indices=indices,
               r_indptr=r_indptr, r_indices=r_indices)
    touched = np.unique(np.concatenate([added // n, added % n,
                                        removed // n, removed % n]))
    return AppliedDelta(graph=g2,
                        added_src=added // n, added_dst=added % n,
                        removed_src=removed // n, removed_dst=removed % n,
                        touched=touched)


# ----------------------------------------------------------------------
# device-view patching
# ----------------------------------------------------------------------

def _ell_rows(g: Graph, rows: np.ndarray, cap: int, reverse: bool,
              ) -> tuple[np.ndarray, np.ndarray]:
    """(len(rows), cap) padded-ELL idx/mask for a subset of vertices."""
    ip, ix = (g.r_indptr, g.r_indices) if reverse else (g.indptr, g.indices)
    deg = (ip[rows + 1] - ip[rows]).astype(np.int64)
    idx = np.full((rows.size, cap), g.n, dtype=np.int32)
    r = np.repeat(np.arange(rows.size), deg)
    c = ragged_arange(deg)
    idx[r, c] = ix[np.repeat(ip[rows], deg) + c]
    return idx, idx != g.n


def _scatter_rows(g: Graph, ell_idx, ell_mask, rows: np.ndarray, cap: int,
                  reverse: bool):
    """Scatter recomputed ELL rows into the device matrices. Rows are
    padded to a power of two by repeating the first row (duplicate indices
    write identical content), so repeated small deltas reuse one scatter
    shape instead of compiling per row count."""
    import jax.numpy as jnp

    pad = pow2_ceil(rows.size)
    rows = np.concatenate([rows, np.full(pad - rows.size, rows[0],
                                         rows.dtype)])
    idx, mask = _ell_rows(g, rows, cap, reverse=reverse)
    rows = jnp.asarray(rows.astype(np.int32))
    return (ell_idx.at[rows].set(jnp.asarray(idx)),
            ell_mask.at[rows].set(jnp.asarray(mask)))


def update_device_graph(dg: DeviceGraph, applied: AppliedDelta,
                        ) -> tuple[DeviceGraph, bool]:
    """Patch device views for a merged delta; ``(new_dg, incremental)``.

    Every updated view keeps its shape bucket: edge lists are re-uploaded
    sentinel-padded to the *current* ``m_cap`` (growing to the next pow2
    bucket only when the valid count outgrows it — shrinking never
    reclaims, so repeated grow/shrink around a boundary cannot thrash),
    and the padded ELL matrices — the big (n, cap) buffers the kernels
    read — are updated by scattering only the touched rows. In-bucket
    churn therefore changes no traced shape and re-uses every warm
    compile. The sliced ELL of the index sweep is dropped (the sweep
    takes the padded ELL from then on; the first sweep after the drop
    compiles that table's program once). Falls back to a full
    ``DeviceGraph.build`` when a touched row outgrows the current ELL
    capacity (the ELL must stay spill-free for enumeration); the rebuild
    re-buckets, restores the sliced ELL and is the one mutation that may
    retrace — at most once per bucket crossing.
    """
    import jax.numpy as jnp

    g2 = applied.graph
    fwd_rows = np.unique(np.concatenate([applied.added_src,
                                         applied.removed_src]))
    rev_rows = np.unique(np.concatenate([applied.added_dst,
                                         applied.removed_dst]))
    fwd_deg = g2.indptr[fwd_rows + 1] - g2.indptr[fwd_rows]
    rev_deg = g2.r_indptr[rev_rows + 1] - g2.r_indptr[rev_rows]
    if ((fwd_deg.size and int(fwd_deg.max()) > dg.ell_cap)
            or (rev_deg.size and int(rev_deg.max()) > dg.r_ell_cap)):
        # the rebuild keeps every bucket monotone too: edge cap and ELL
        # caps only grow, so an overflow after deletion-heavy churn cannot
        # shrink a bucket and re-thrash the next insert wave
        return DeviceGraph.build(
            g2, edge_cap=max(dg.m_cap, pow2_ceil(g2.m)),
            min_ell_caps=(dg.ell_cap, dg.r_ell_cap)), False

    ell_idx, ell_mask = dg.ell_idx, dg.ell_mask
    if fwd_rows.size:
        ell_idx, ell_mask = _scatter_rows(g2, ell_idx, ell_mask, fwd_rows,
                                          dg.ell_cap, reverse=False)
    r_ell_idx, r_ell_mask = dg.r_ell_idx, dg.r_ell_mask
    if rev_rows.size:
        r_ell_idx, r_ell_mask = _scatter_rows(g2, r_ell_idx, r_ell_mask,
                                              rev_rows, dg.r_ell_cap,
                                              reverse=True)

    cap = dg.m_cap if g2.m <= dg.m_cap else pow2_ceil(g2.m)
    esrc, edst = pad_edge_list(*g2.edges_by_dst, g2.n, cap)
    r_esrc, r_edst = pad_edge_list(*g2.r_edges_by_dst, g2.n, cap)
    # the sliced ELL's shapes follow every row's degree, so it is dropped
    # rather than patched: the index sweep falls back to the padded ELL
    # (fixed shapes under in-bucket churn) until the next full build
    return dataclasses.replace(
        dg, m=g2.m,
        esrc=jnp.asarray(esrc), edst=jnp.asarray(edst),
        ell_idx=ell_idx, ell_mask=ell_mask,
        r_esrc=jnp.asarray(r_esrc), r_edst=jnp.asarray(r_edst),
        r_ell_idx=r_ell_idx, r_ell_mask=r_ell_mask,
        ell_sliced=None, r_ell_sliced=None), True


def host_set_dist(g_old: Graph, applied: AppliedDelta, k_max: int,
                  reverse: bool) -> np.ndarray:
    """BFS distances from the touched frontier, host-side over the old CSR.

    ``dist[v] = min over touched x of hops(x -> v)``; ``reverse=True``
    walks G_r (i.e. prices ``hops(v -> x)``). Only the touched balls'
    edges are visited, not ``m``. Returns ``(n+1,) int32`` capped at
    ``k_max`` (unreached = k_max + 1, row n INF), matching
    :func:`~repro.core.msbfs.msbfs_set_dist` — the device backend for
    accelerator-resident graphs — exactly.

    Walking the *old* graph alone suffices for old, new, and union alike:
    both endpoints of every changed edge are seeds, so any path using a
    changed edge has a suffix from a distance-0 vertex over unchanged
    edges only — distances from the touched set agree on all three
    graphs, and one sweep certifies cached state and its fresh
    recomputation.
    """
    ip, ix = (g_old.r_indptr, g_old.r_indices) if reverse \
        else (g_old.indptr, g_old.indices)
    INF = k_max + 1
    dist = np.full(g_old.n + 1, INF, np.int32)
    frontier = applied.touched
    dist[frontier] = 0
    for hop in range(1, k_max + 1):
        if frontier.size == 0:
            break
        deg = (ip[frontier + 1] - ip[frontier]).astype(np.int64)
        nbrs = np.unique(ix[np.repeat(ip[frontier], deg) +
                            ragged_arange(deg)].astype(np.int64))
        frontier = nbrs[dist[nbrs] == INF]
        dist[frontier] = hop
    return dist


