"""Graph containers for the batch HC-s-t path engine.

The host-side ``Graph`` is built with numpy (CSR both directions, padded-ELL
views, destination-sorted edge lists). Device views are materialized lazily
as jnp arrays. All layouts are static-shape so every downstream stage is
jit-compilable:

  * CSR            -- indptr/indices, canonical storage.
  * edge list      -- (src, dst) sorted by dst; drives segment-reduce hops.
  * padded ELL     -- (V, max_deg_cap) neighbor matrix padded with the
                      sentinel row ``V`` (frontier tables carry one extra
                      zero row); drives the enumeration gather and the
                      index sweep of a mutated graph. Vertices with
                      deg > cap spill to a COO remainder (power-law safety
                      valve).
  * sliced ELL     -- rows sorted by degree and cut into at most
                      ``MAX_SLICES`` tables, each as wide as its own rows
                      need (:class:`SlicedEll`); drives the packed index
                      sweep, which then gathers about one row per arc.

Shape stability under mutation: every *device* view is quantized to a
power-of-two bucket so incremental edge churn (``delta.apply_delta``)
re-uses warm XLA compiles instead of retracing on each new ``(m,)``.
Edge lists are padded with **sentinel edges** ``(n, n)``: ``edst = n`` is
out of segment range, so ``segment_max`` / ``segment_sum`` drop the
message, and ``esrc = n`` gathers the all-zero sentinel row that every
frontier table carries — a sentinel edge is inert in the boolean BFS
semiring. ELL capacities are bucketed the same way,
so a touched row growing within its bucket never changes the ``(n, cap)``
kernel shapes.
"""
from __future__ import annotations

import dataclasses
from functools import cached_property
from typing import NamedTuple, Optional, TYPE_CHECKING

import numpy as np

if TYPE_CHECKING:   # the "jax.Array" annotations below; jax itself is
    import jax      # imported lazily so host-only use never inits a device

__all__ = ["Graph", "DeviceGraph", "EllView", "SlicedEll", "sliced_ell",
           "slice_bounds", "pow2_ceil", "pad_edge_list", "MAX_SLICES"]

SENTINEL = -1
# most tables a sliced ELL is cut into (one gather loop each per level)
MAX_SLICES = 8


def pow2_ceil(x: int) -> int:
    """Smallest power of two >= x (1 for x <= 1) — the shared shape-bucket
    rounding for every device view (edge-list pads, ELL capacities, the
    delta path's scatter widths and MS-BFS hop budgets)."""
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


def pad_edge_list(esrc: np.ndarray, edst: np.ndarray, n: int,
                  cap: int) -> tuple[np.ndarray, np.ndarray]:
    """Sentinel-pad a dst-sorted edge list to ``cap`` entries.

    Sentinel edges are ``(n, n)``: dropped by segment reductions over
    ``num_segments = n`` and reading the zero sentinel row on gathers, so
    the padded list is semantically identical to the exact one. ``n``
    sorts after every real destination, so the dst-sorted invariant (and
    ``indices_are_sorted=True`` segment ops) survives the pad.
    """
    m = int(esrc.shape[0])
    if cap < m:
        raise ValueError(f"edge bucket {cap} smaller than edge count {m}")
    if cap == m:
        return esrc.astype(np.int32, copy=False), \
            edst.astype(np.int32, copy=False)
    pad = np.full(cap - m, n, dtype=np.int32)
    return (np.concatenate([esrc.astype(np.int32, copy=False), pad]),
            np.concatenate([edst.astype(np.int32, copy=False), pad]))


@dataclasses.dataclass(frozen=True)
class EllView:
    """Padded ELL adjacency: idx[v, d] = d-th out-neighbor or n (sentinel)."""

    idx: np.ndarray          # (n, cap) int32, padded with n
    mask: np.ndarray         # (n, cap) bool
    spill_src: np.ndarray    # (n_spill,) int32 COO remainder
    spill_dst: np.ndarray    # (n_spill,) int32
    cap: int


class SlicedEll(NamedTuple):
    """Degree-sorted sliced ELL of one direction's rows (after SELL-C-σ),
    the table the packed MS-BFS sweep gathers over.

    Vertices are renumbered by descending row degree (a stable sort):
    position ``p`` holds vertex ``perm[p]``, and ``inv_perm[v] = p``.
    ``tables[i]`` holds a contiguous run of positions, as wide as the
    largest degree in it; its entries are neighbour *positions*, pad =
    n. A run of degree-0 rows is a width-0 table that gathers nothing.
    Bounds and widths are the tables' static shapes, so a jit over this
    pytree sees fixed shapes per graph. About one entry per arc, against
    ``n * pow2_ceil(max degree)`` for the padded ELL.
    """

    perm: "jax.Array"        # (n,) int32
    inv_perm: "jax.Array"    # (n,) int32
    tables: tuple            # ((rows_i, width_i) int32, ...) in perm order

    @property
    def widths(self) -> tuple[int, ...]:
        return tuple(int(t.shape[1]) for t in self.tables)

    @property
    def rows_per_level(self) -> int:
        """Frontier rows one sweep level gathers: the tables' entries."""
        return sum(int(t.shape[0]) * int(t.shape[1]) for t in self.tables)


def slice_bounds(deg_desc: np.ndarray,
                 max_slices: int = MAX_SLICES) -> list[int]:
    """Cut points ``[0, b_1, ..., n]`` of at most ``max_slices``
    contiguous slices of rows sorted by degree, descending, that
    minimise the entries ``sum(rows_i * width_i)`` (a slice is as wide as
    its first row). A DP over the distinct degrees: a cut inside a run of
    equal degrees never helps."""
    deg = np.asarray(deg_desc, np.int64)
    if deg.size == 0:
        return [0, 0]
    # distinct degrees d_0 > d_1 > ... and the row span of each
    starts = np.flatnonzero(np.r_[True, deg[1:] != deg[:-1]])
    ends = np.r_[starts[1:], deg.size]
    d = deg[starts]
    K = d.size
    # cost[j, l]: one slice over groups j..l = (ends[l] - starts[j]) * d[j]
    cost = (ends[None, :] - starts[:, None]) * d[:, None]
    cost = np.where(np.arange(K)[None, :] >= np.arange(K)[:, None], cost,
                    np.iinfo(np.int64).max // 4)
    best = cost[0].copy()                 # one slice over groups 0..l
    choice = [np.zeros(K, np.int64)]      # first group of the last slice
    for _ in range(1, min(max_slices, K)):
        # best_prev[j-1] + cost[j, l], over j in 1..l (j = 0: no cut)
        cand = np.full((K, K), np.iinfo(np.int64).max // 4)
        cand[1:] = best[:-1, None] + cost[1:]
        cand[0] = cost[0]
        j = np.argmin(cand, axis=0)
        best = cand[j, np.arange(K)]
        choice.append(j)
    cuts, l = [], K - 1
    for c in reversed(choice):
        j = int(c[l])
        cuts.append(int(starts[j]))
        if j == 0:
            break
        l = j - 1
    return sorted(set(cuts)) + [int(deg.size)]


def sliced_ell(indptr: np.ndarray, indices: np.ndarray, n: int) -> tuple:
    """Host arrays of a :class:`SlicedEll` over the CSR rows
    ``indptr``/``indices``: ``(perm, inv_perm, tables)``, all int32."""
    deg = np.diff(indptr).astype(np.int64)
    perm = np.argsort(-deg, kind="stable")
    inv_perm = np.empty(n, np.int64)
    inv_perm[perm] = np.arange(n)
    bounds = slice_bounds(deg[perm])
    tables = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        rows = perm[lo:hi]
        d = deg[rows]
        width = int(d[0]) if d.size else 0
        tab = np.full((hi - lo, width), n, np.int32)
        r = np.repeat(np.arange(hi - lo), d)
        c = ragged_arange(d)
        tab[r, c] = inv_perm[indices[np.repeat(indptr[rows], d) + c]]
        tables.append(tab)
    return perm.astype(np.int32), inv_perm.astype(np.int32), tuple(tables)


@dataclasses.dataclass(frozen=True)
class Graph:
    """Directed graph, CSR in both directions. Vertices are 0..n-1."""

    n: int
    indptr: np.ndarray       # (n+1,) int64 — out-edges CSR
    indices: np.ndarray      # (m,) int32, sorted within row
    r_indptr: np.ndarray     # (n+1,) int64 — in-edges CSR (reverse graph)
    r_indices: np.ndarray    # (m,) int32

    # ------------------------------------------------------------------
    @staticmethod
    def from_edges(n: int, src, dst, dedup: bool = True) -> "Graph":
        src = np.asarray(src, dtype=np.int64)
        dst = np.asarray(dst, dtype=np.int64)
        if src.size:
            keep = src != dst  # drop self loops: never on a simple path twice
            src, dst = src[keep], dst[keep]
        if dedup and src.size:
            src, dst = np.divmod(np.unique(src * n + dst), n)
        indptr, indices = _csr(n, src, dst)
        r_indptr, r_indices = _csr(n, dst, src)
        return Graph(n=n, indptr=indptr, indices=indices,
                     r_indptr=r_indptr, r_indices=r_indices)

    # ------------------------------------------------------------------
    @property
    def m(self) -> int:
        return int(self.indices.shape[0])

    def out_degree(self) -> np.ndarray:
        return np.diff(self.indptr)

    def in_degree(self) -> np.ndarray:
        return np.diff(self.r_indptr)

    def neighbors(self, v: int, reverse: bool = False) -> np.ndarray:
        ip, ix = (self.r_indptr, self.r_indices) if reverse else (self.indptr, self.indices)
        return ix[ip[v]:ip[v + 1]]

    # -- edge lists sorted by destination (segment-reduce friendly) ----
    @cached_property
    def edges_by_dst(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) of G with dst non-decreasing."""
        dst = np.repeat(np.arange(self.n, dtype=np.int32), np.diff(self.r_indptr))
        src = self.r_indices
        return src.astype(np.int32), dst

    @cached_property
    def r_edges_by_dst(self) -> tuple[np.ndarray, np.ndarray]:
        """(src, dst) of G_r with dst non-decreasing (i.e. edges of G keyed by src)."""
        dst = np.repeat(np.arange(self.n, dtype=np.int32), np.diff(self.indptr))
        src = self.indices
        return src.astype(np.int32), dst

    # -- padded ELL views ----------------------------------------------
    def ell(self, cap: Optional[int] = None, reverse: bool = False) -> EllView:
        ip, ix = (self.r_indptr, self.r_indices) if reverse else (self.indptr, self.indices)
        deg = np.diff(ip).astype(np.int64)
        if cap is None:
            cap = int(deg.max()) if self.n else 1
        cap = max(int(cap), 1)
        idx = np.full((self.n, cap), self.n, dtype=np.int32)
        # vectorized fill of the first `cap` neighbors per row
        take = np.minimum(deg, cap)
        rows = np.repeat(np.arange(self.n), take)
        cols = ragged_arange(take)
        flat = np.repeat(ip[:-1], take) + cols
        idx[rows, cols] = ix[flat]
        mask = idx != self.n
        # spill: neighbors beyond cap
        extra = deg - take
        s_rows = np.repeat(np.arange(self.n, dtype=np.int32), extra)
        s_cols = ragged_arange(extra) + np.repeat(take, extra)
        s_flat = np.repeat(ip[:-1], extra) + s_cols
        return EllView(idx=idx, mask=mask,
                       spill_src=s_rows, spill_dst=ix[s_flat].astype(np.int32),
                       cap=cap)

    def reverse(self) -> "Graph":
        return Graph(n=self.n, indptr=self.r_indptr, indices=self.r_indices,
                     r_indptr=self.indptr, r_indices=self.indices)

    # -- incremental mutation ------------------------------------------
    def apply_delta(self, delta) -> tuple["Graph", np.ndarray]:
        """Successor graph after a :class:`~repro.core.delta.GraphDelta`.

        Merges the (deduplicated, self-loop-free) edge mutations into both
        CSR directions without re-sorting the kept edges — equivalent to a
        ``from_edges`` rebuild on the edited edge list, in time
        proportional to ``m + |delta| log m``. Returns ``(new_graph,
        touched)`` where ``touched`` holds the unique endpoints of every
        *effective* change (no-op inserts/deletes excluded); an empty
        ``touched`` means ``new_graph is self``.
        """
        from .delta import apply_delta as _apply_delta
        applied = _apply_delta(self, delta)
        return applied.graph, applied.touched


def _csr(n: int, src: np.ndarray, dst: np.ndarray):
    # one sort of the (src, dst) pair packed into an int64 key; equal keys
    # are equal pairs, so the order among them cannot show
    src, dst = np.divmod(np.sort(src.astype(np.int64) * n + dst), n)
    counts = np.bincount(src, minlength=n).astype(np.int64)
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(counts, out=indptr[1:])
    return indptr, dst.astype(np.int32)


def ragged_arange(counts: np.ndarray) -> np.ndarray:
    """[0..c0), [0..c1), ... concatenated."""
    counts = counts.astype(np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    offs = np.repeat(np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    return np.arange(total, dtype=np.int64) - offs


@dataclasses.dataclass(frozen=True)
class DeviceGraph:
    """jnp views of a Graph (built once per engine instance).

    ``m`` is the *valid* edge count; the edge arrays themselves are padded
    to the ``m_cap`` pow2 bucket with sentinel ``(n, n)`` edges (see
    :func:`pad_edge_list`), and ELL capacities are pow2-bucketed, so the
    traced shapes of every downstream kernel stay constant while the graph
    mutates within its buckets.
    """

    n: int
    m: int                   # valid edge count (m_valid); arrays hold m_cap
    # forward direction
    esrc: "jax.Array"        # (m_cap,) int32 sorted by dst, sentinel = n
    edst: "jax.Array"
    ell_idx: "jax.Array"     # (n, cap) int32, pad = n
    ell_mask: "jax.Array"
    # reverse direction
    r_esrc: "jax.Array"
    r_edst: "jax.Array"
    r_ell_idx: "jax.Array"
    r_ell_mask: "jax.Array"
    ell_cap: int
    r_ell_cap: int
    # the packed index sweep's tables (sweep_table); None after an
    # incremental delta patch, which keeps only the padded ELL current
    ell_sliced: Optional[SlicedEll] = None
    r_ell_sliced: Optional[SlicedEll] = None

    @property
    def m_cap(self) -> int:
        """Padded edge-bucket capacity (== m when built with pad=False)."""
        return int(self.esrc.shape[0])

    @property
    def m_valid(self) -> int:
        """Valid (non-sentinel) edge count — alias of ``m``, named for the
        kernels it is threaded through."""
        return self.m

    @staticmethod
    def build(g: Graph, ell_cap: Optional[int] = None, *,
              pad: bool = True, edge_cap: Optional[int] = None,
              min_ell_caps: tuple[int, int] = (1, 1),
              ) -> "DeviceGraph":
        """Materialize device views.

        pad=True (default) quantizes every shape to pow2 buckets: edge
        lists sentinel-padded to ``edge_cap`` (default ``pow2_ceil(m)``)
        and, when ``ell_cap`` is not given, ELL capacities bucketed to
        ``pow2_ceil(max degree)`` per direction, floored at
        ``min_ell_caps`` (fwd, rev) — the delta path passes its current
        caps so a rebuild never shrinks a bucket and grow/shrink churn
        around a boundary cannot thrash. pad=False keeps the exact
        legacy shapes (tests use it to assert padded/unpadded parity).
        """
        import jax.numpy as jnp

        if pad and ell_cap is None:
            deg = np.diff(g.indptr)
            r_deg = np.diff(g.r_indptr)
            cap_f = max(pow2_ceil(int(deg.max()) if deg.size else 1),
                        min_ell_caps[0])
            cap_r = max(pow2_ceil(int(r_deg.max()) if r_deg.size else 1),
                        min_ell_caps[1])
        else:
            cap_f = cap_r = ell_cap
        ell = g.ell(cap=cap_f)
        rell = g.reverse().ell(cap=cap_r)
        if ell.spill_src.size or rell.spill_src.size:
            raise ValueError(
                "ell_cap too small: spill present; enumeration requires the "
                "full ELL (pass ell_cap=None or >= max degree)")
        esrc, edst = g.edges_by_dst
        r_esrc, r_edst = g.r_edges_by_dst
        if pad:
            cap = pow2_ceil(g.m) if edge_cap is None else int(edge_cap)
            esrc, edst = pad_edge_list(esrc, edst, g.n, cap)
            r_esrc, r_edst = pad_edge_list(r_esrc, r_edst, g.n, cap)

        def sliced(indptr, indices) -> SlicedEll:
            perm, inv_perm, tables = sliced_ell(indptr, indices, g.n)
            return SlicedEll(jnp.asarray(perm), jnp.asarray(inv_perm),
                             tuple(jnp.asarray(t) for t in tables))

        return DeviceGraph(
            n=g.n, m=g.m,
            esrc=jnp.asarray(esrc), edst=jnp.asarray(edst),
            ell_idx=jnp.asarray(ell.idx), ell_mask=jnp.asarray(ell.mask),
            r_esrc=jnp.asarray(r_esrc), r_edst=jnp.asarray(r_edst),
            r_ell_idx=jnp.asarray(rell.idx), r_ell_mask=jnp.asarray(rell.mask),
            ell_cap=ell.cap, r_ell_cap=rell.cap,
            ell_sliced=sliced(g.indptr, g.indices),
            r_ell_sliced=sliced(g.r_indptr, g.r_indices),
        )

    def direction(self, reverse: bool):
        """(ell_idx, ell_mask) for a search direction."""
        if reverse:
            return self.r_ell_idx, self.r_ell_mask
        return self.ell_idx, self.ell_mask

    def sweep_table(self, reverse: bool):
        """The table a packed index sweep gathers over: the rows of
        ``ell_idx`` (``reverse=False``) or of ``r_ell_idx``, sliced when
        this graph carries the sliced layout, else the padded ELL."""
        if reverse:
            return self.r_ell_idx if self.r_ell_sliced is None \
                else self.r_ell_sliced
        return self.ell_idx if self.ell_sliced is None else self.ell_sliced
