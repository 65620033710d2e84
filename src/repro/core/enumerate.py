"""Frontier path-enumeration supersteps (TPU form of Alg 1/4 ``Search``).

The recursive DFS of the paper becomes level-synchronous: the level-l
frontier is a PathSet of all simple paths of length exactly l that survive
the slack prune. One superstep expands every frontier path by every
ELL neighbor at once, masks invalid candidates (padding / duplicate vertex /
Lemma-3.1 slack prune / splice triggers), and cumsum-compacts the survivors.

Splice handling (BatchEnum, Alg 4 lines 20-23): vertices that root a
materialized dominating HC-s path query are *not* expanded when the cached
budget covers the remaining budget; the (prefix x cached-suffix) cross join
happens in join.py.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .pathset import PathSet, compact_rows

__all__ = ["ExpandOut", "expand_level", "prune_table", "splice_table",
           "splice_hits", "extract_rows", "select_ending_at",
           "count_ending_at"]


class ExpandOut(NamedTuple):
    frontier: PathSet     # level+1 frontier (spliced candidates excluded)
    nbrs: jax.Array       # (cap, D) raw neighbor matrix (for splice extraction)
    splice_hit: jax.Array  # (cap, D) bool -- candidates redirected to splice


def prune_table(slack: jax.Array, splice_budget: jax.Array) -> jax.Array:
    """Stack the two per-vertex int8 prune vectors into the (n+1, 2)
    table :func:`expand_level` consumes — column 0 = Lemma-3.1 slack,
    column 1 = splice budget (-1 = no dominating query). Built once per
    node run (both vectors are fixed for a node), so every level pays a
    single fused gather instead of one gather per vector."""
    return jnp.stack([slack, splice_budget], axis=1)


@jax.jit
def splice_table(slack: jax.Array, roots: jax.Array,
                 budgets: jax.Array) -> jax.Array:
    """:func:`prune_table` with the splice column scattered on the device:
    ``roots`` (C,) int32 splice-child root vertices, ``budgets`` (C,) int8
    their budgets; pad entries point at the sentinel row n with -1."""
    splice = jnp.full(slack.shape, -1, jnp.int8).at[roots].set(budgets)
    return prune_table(slack, splice)


@partial(jax.jit, static_argnames=("n",))
def splice_hits(nbrs: jax.Array, splice_hit: jax.Array, roots: jax.Array,
                *, n: int) -> jax.Array:
    """(C,) bool: whether any splice trigger of an :class:`ExpandOut`
    landed on each root in ``roots`` — one dispatch per level instead of
    one row mask per splice child. Pad entries of ``nbrs`` and ``roots``
    are the sentinel n, where no trigger lands."""
    landed = jnp.zeros((n + 1,), bool).at[nbrs.reshape(-1)].max(
        splice_hit.reshape(-1))
    return landed[roots]


@partial(jax.jit, static_argnames=("level", "budget", "out_cap", "backend"))
def expand_level(verts: jax.Array, count: jax.Array,
                 ell_idx: jax.Array, prune_tbl: jax.Array,
                 stop_vertex: jax.Array,
                 *, level: int, budget: int, out_cap: int,
                 backend: str = "jnp") -> ExpandOut:
    """One superstep: expand all level-`level` paths by one hop.

    verts:  (cap, L) int32 frontier paths (cols 0..level used).
    ell_idx: (n, D) or (n+1, D) int32 padded ELL table; pad entries hold
            the sentinel value ``n``. The validity mask is derived as
            ``nbrs != n`` — the EllView/delta-patch invariant
            ``mask == (idx != n)`` holds by construction, so no separate
            mask gather is dispatched.
    prune_tbl: (n+1, 2) int8 from :func:`prune_table` — one gather feeds
            both the slack prune (col 0: keep candidate v at depth d iff
            slack[v] >= d) and the splice trigger (col 1: kappa' of a
            materialized dominating query rooted at v, else -1;
            candidates with splice >= budget-(level+1) splice instead of
            expanding).
    stop_vertex: () int32 -- do not expand *from* this vertex (dedicated
            query optimization; pass -2 to disable).
    backend: static resolved kernel backend; ``pallas``/``interpret`` route
            the duplicate-vertex mask through one kernels/path_join
            membership dispatch instead of the broadcast-compare chain.

    Dispatch accounting (audited: see benchmarks/baselines/
    DISPATCH_BUDGETS.json and ``python -m repro.analysis --audit``):
    fusing the mask gather into the ``nbrs != n`` compare and the
    slack + splice gathers into the single prune-table gather cut the
    traced superstep from 85 to 80 eqns (jnp) / 83 to 78 (interpret) at
    the audit probe shape. The remainder stays unfused deliberately:
    the duplicate mask is one broadcast-compare XLA fuses on its own
    (and is already a single kernel dispatch on the kernel backends),
    and the cumsum compaction is the shared ``compact_rows`` primitive —
    fusing it here would fork the compaction path every PathSet consumer
    relies on for a ~2-eqn saving.
    """
    cap, L = verts.shape
    # the prune table always has n+1 rows (slack/splice carry a sentinel
    # entry), whereas ELL tables come in both (n, D) and (n+1, D) forms —
    # so the pad-sentinel value is derived from prune_tbl, not ell_idx
    n = prune_tbl.shape[0] - 1
    D = ell_idx.shape[1]
    row_valid = jnp.arange(cap) < count
    # rows past `count` gather row 0 (any in-bounds row works: row_valid
    # masks every candidate they produce)
    last = jnp.where(row_valid, verts[:, level], 0)
    nbrs = ell_idx[last]                             # (cap, D)
    valid = (nbrs != n) & row_valid[:, None]
    valid &= (last != stop_vertex)[:, None]
    # duplicate-vertex mask: candidate already on the path
    if backend != "jnp":
        from ..kernels.path_join.ops import path_member
        dup = path_member(verts[:, :level + 1], nbrs, backend=backend)
    else:
        dup = (nbrs[:, :, None] == verts[:, None, :level + 1]).any(-1)
    pruned = prune_tbl[nbrs]                         # (cap, D, 2) one gather
    # Lemma 3.1 prune at depth level+1
    keep = valid & ~dup & (pruned[..., 0] >= level + 1)
    # splice triggers (cached dominating query covers the remaining budget)
    remaining = budget - (level + 1)
    splice_hit = keep & (pruned[..., 1] >= remaining)
    expand_mask = keep & ~splice_hit

    # build candidate rows: prefix + new vertex at column level+1
    flat_mask = expand_mask.reshape(-1)
    rows = jnp.repeat(jnp.arange(cap), D)
    cand = verts[rows]                               # (cap*D, L)
    cand = cand.at[:, level + 1].set(nbrs.reshape(-1))
    out, n_out, ovf = compact_rows(flat_mask, cand, out_cap)
    return ExpandOut(frontier=PathSet(out, n_out, ovf),
                     nbrs=nbrs, splice_hit=splice_hit)


@partial(jax.jit, static_argnames=("out_cap",))
def extract_rows(verts: jax.Array, row_mask: jax.Array, *, out_cap: int) -> PathSet:
    """Compact the rows of `verts` where row_mask is True."""
    out, n_out, ovf = compact_rows(row_mask, verts, out_cap)
    return PathSet(out, n_out, ovf)


@partial(jax.jit, static_argnames=("col",))
def count_ending_at(verts: jax.Array, count: jax.Array, vertex,
                    *, col: int) -> jax.Array:
    """Number of rows ending (column `col`) at `vertex` — a mask reduction,
    no compaction and no output buffer (count-/exists-only fast path)."""
    cap = verts.shape[0]
    mask = (jnp.arange(cap) < count) & (verts[:, col] == vertex)
    return mask.sum(dtype=jnp.int32)


@partial(jax.jit, static_argnames=("col", "out_cap"))
def select_ending_at(verts: jax.Array, count: jax.Array, vertex,
                     *, col: int, out_cap: int) -> PathSet:
    """Rows whose path ends (column `col`) at `vertex` (forward-complete paths)."""
    cap = verts.shape[0]
    mask = (jnp.arange(cap) < count) & (verts[:, col] == vertex)
    out, n_out, ovf = compact_rows(mask, verts, out_cap)
    return PathSet(out, n_out, ovf)
