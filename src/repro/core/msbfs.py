"""Bit-parallel multi-source BFS (the paper's BuildIndex, Alg 1/4 lines 1-2).

TPU adaptation of "The More the Merrier" MS-BFS [36]: instead of per-source
queues, the frontier is a dense (n+1, S) int8/bool matrix (one column per
source; row n is a sentinel for padded ELL gathers). One hop is an
edge-gather + ``segment_max`` (max == OR on {0,1}), i.e. a sparse-matrix ×
dense-frontier product in the boolean semiring — MXU/VPU-friendly and
shardable.

Two sweeps:
  * ``msbfs_dist``     -- int8 frontier, chunked edge-list gathers (the
                          ``jnp`` kernel backend).
  * ``msbfs_dist_ell`` -- bit-packed frontier, OR-gather over the
                          degree-sorted sliced ELL (or the padded ELL of a
                          delta-patched graph; kernels/msbfs_expand; the
                          ``pallas`` and ``interpret`` backends),
                          bit-equal to the edge-list sweep.

Distances are int8 (k_max <= 120); unreached = INF = k_max + 1.

Sentinel padding: edge lists may be pow2-bucketed with sentinel edges
``(n, n)`` (``graph.pad_edge_list``). A sentinel edge gathers the all-zero
frontier row ``n`` and its ``edst = n`` falls outside ``num_segments = n``,
so segment reductions drop it — padded and exact edge lists are
bit-equivalent. Callers pass ``m_valid`` (the chunk-rounded valid-edge
span from :func:`edge_span`) so the chunk loop skips all-sentinel chunks;
it is a static jit argument, which is why it must be pre-rounded — raw
per-delta edge counts would retrace on every mutation.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .graph import SlicedEll
from ..obs import metrics as obsmetrics

__all__ = ["msbfs_dist", "msbfs_set_dist", "msbfs_hop", "msbfs_dist_ell",
           "msbfs_set_dist_ell", "INF_FOR", "edge_span", "K_MAX_INT8",
           "count_sweep", "swept"]

# Largest hop budget the int8 distance representation supports. INF_FOR
# (k_max + 1) must stay representable AND keep headroom below int8 max
# for downstream +1/-offset hop arithmetic (prune tables, splice
# budgets); 120 leaves 127 - 121 = 6 values of slack above the sentinel.
K_MAX_INT8 = 120
_INT8_MAX = 127


def INF_FOR(k_max: int) -> int:
    return k_max + 1


def _check_k_max(k_max: int) -> None:
    """Static int8-range guard for the sweep entry points.

    ``k_max`` is a static jit argument, so this raises at trace time —
    before any device work — instead of silently clamping (the historical
    behaviour) and computing wrong-radius distances.
    """
    if not 0 <= int(k_max) <= K_MAX_INT8:
        raise ValueError(
            f"k_max={k_max} out of range for int8 MS-BFS distances: "
            f"requires 0 <= k_max <= K_MAX_INT8={K_MAX_INT8} so the "
            f"sentinel INF_FOR(k_max)={int(k_max) + 1} fits int8 "
            f"(max {_INT8_MAX}) with {_INT8_MAX - K_MAX_INT8 - 1} values "
            f"of headroom above INF for downstream hop arithmetic; "
            f"reduce the hop budget (or bucket it) before the sweep")


def edge_span(m_valid: int, edge_chunk: int, m_cap: int) -> int:
    """Chunk-rounded prefix of a sentinel-padded edge list that the chunked
    sweeps must visit: ``m_valid`` rounded *up* to an ``edge_chunk``
    multiple, clamped to ``m_cap``. Rounding up means every edge count
    inside one chunk-granule maps to the same static value — in-bucket
    churn cannot retrace a kernel, only crossing a chunk (or bucket)
    boundary can."""
    if m_valid >= m_cap:
        return int(m_cap)
    return int(min(-(-int(m_valid) // int(edge_chunk)) * int(edge_chunk),
                   m_cap))


def msbfs_hop(frontier: jax.Array, esrc: jax.Array, edst: jax.Array,
              n: int, edge_chunk: int = 1 << 22,
              m_valid: Optional[int] = None) -> jax.Array:
    """One BFS relaxation: next[v, s] = OR over edges (u->v) frontier[u, s].

    frontier: (n+1, S) int8 in {0,1} (row n = sentinel zeros).
    m_valid: chunk-rounded valid-edge span (see :func:`edge_span`); None
    sweeps the full (possibly sentinel-padded) list — correct either way,
    the rounding only skips provably all-sentinel chunks.
    Returns (n+1, S) int8.
    """
    S = frontier.shape[1]
    m = esrc.shape[0]
    m_used = m if m_valid is None else min(int(m_valid), m)
    nxt = jnp.zeros((n, S), dtype=jnp.int8)
    # static chunking keeps the (Ec, S) gather bounded; a whole-list
    # sweep (the common case — m fits one chunk) skips the slice ops
    # entirely, so a GSPMD-sharded edge list is gathered shard-local
    # instead of being resharded at a mid-shard slice boundary
    for lo in range(0, m_used, edge_chunk):
        hi = min(lo + edge_chunk, m)
        es, ed = (esrc, edst) if lo == 0 and hi == m \
            else (esrc[lo:hi], edst[lo:hi])
        msgs = frontier[es]                               # (Ec, S) int8
        part = jax.ops.segment_max(msgs, ed, num_segments=n,
                                   indices_are_sorted=True)
        nxt = jnp.maximum(nxt, part)
    return jnp.concatenate([nxt, jnp.zeros((1, S), jnp.int8)], axis=0)


@partial(jax.jit, static_argnames=("n", "k_max", "edge_chunk", "m_valid"))
def msbfs_set_dist(esrc: jax.Array, edst: jax.Array, seed_mask: jax.Array,
                   *, n: int, k_max: int, edge_chunk: int = 1 << 22,
                   m_valid: Optional[int] = None) -> jax.Array:
    """Distance from a vertex *set*: one bit-column seeded with every
    member, so ``dist[v] = min over seeds of hops(seed -> v)`` in a single
    S=1 sweep. This is what hop-scoped cache invalidation asks ("how close
    is the nearest touched vertex?") — one compile per (n, k_max) instead
    of one per frontier size.

    seed_mask : (n+1,) int8 in {0,1} (row n must be 0).
    Returns (n+1,) int8 with unreached = INF = k_max + 1, row n = INF.
    """
    _check_k_max(k_max)
    INF = np.int8(INF_FOR(k_max))
    seed = seed_mask.astype(jnp.int8)[:, None]          # (n+1, 1)
    dist = jnp.where(seed[:, 0].astype(bool), jnp.int8(0), INF)
    frontier = seed
    for hop in range(1, k_max + 1):
        # named_scope tags this hop's HLO ops for profiler device
        # timelines (metadata only: zero jaxpr eqns, budgets unaffected)
        with jax.named_scope(f"msbfs.hop{hop}"):
            reached = (dist < INF).astype(jnp.int8)
            nxt = msbfs_hop(frontier, esrc, edst, n, edge_chunk, m_valid)
            new = nxt * (1 - reached)[:, None]
            dist = jnp.where(new[:, 0].astype(bool), jnp.int8(hop), dist)
            frontier = new.at[n].set(0)
    return dist.at[n].set(INF)


@partial(jax.jit, static_argnames=("n", "k_max", "edge_chunk", "m_valid"))
def msbfs_dist(esrc: jax.Array, edst: jax.Array, sources: jax.Array,
               *, n: int, k_max: int, edge_chunk: int = 1 << 22,
               m_valid: Optional[int] = None) -> jax.Array:
    """Distances from each source, capped at k_max.

    esrc/edst : (m,) int32 edges sorted by dst (use reverse edges for G_r).
    sources   : (S,) int32 (padded entries may repeat; they are independent).
    Returns dist (n+1, S) int8; dist[v, i] = min(hops(sources[i] -> v), INF),
    row n is INF (sentinel for padded gathers).
    """
    _check_k_max(k_max)
    S = sources.shape[0]
    INF = np.int8(INF_FOR(k_max))
    dist = jnp.full((n + 1, S), INF, dtype=jnp.int8)
    dist = dist.at[sources, jnp.arange(S)].min(jnp.int8(0))
    frontier = jnp.zeros((n + 1, S), jnp.int8).at[sources, jnp.arange(S)].set(1)
    for hop in range(1, k_max + 1):
        with jax.named_scope(f"msbfs.hop{hop}"):
            reached = (dist < INF).astype(jnp.int8)
            nxt = msbfs_hop(frontier, esrc, edst, n, edge_chunk, m_valid)
            new = nxt * (1 - reached)                      # newly reached only
            dist = jnp.where(new.astype(bool), jnp.int8(hop), dist)
            frontier = new.at[n].set(0)
        # NOTE: no early exit under jit; k_max is small (<= 8 in the paper).
    return dist.at[n].set(INF)


# ---------------------------------------------------------------------------
# packed twins: bit-packed sweeps over an ELL in-neighbour table
# (kernels/msbfs_expand msbfs_step: expand + visited dedup per level),
# 32 sources per uint32 word instead of one int8 byte each on the
# segment-op path.
#
# The table is a DeviceGraph's sweep_table: the degree-sorted sliced ELL
# (SlicedEll) of a fully built graph, or the padded ELL of a delta-patched
# one. The sliced sweep runs in permuted vertex order: sources are seeded
# at inv_perm[sources], each slice OR-gathers into its own contiguous run
# of rows, and the distances are un-permuted once at the end. A level then
# gathers one frontier row per table entry, about one per valid arc
# (rows_per_level), where the padded table gathers n * pow2(max degree)
# rows, most of them the all-zero sentinel row n. Both tables have fixed
# shapes per graph, so these sweeps need no edge chunking: m_valid has no
# analogue here because pad entries gather row n and contribute nothing.
# The padded ELL stays for the delta path (rows patched in place, no
# retrace) and for expand_level.
#
# Direction convention (matches msbfs_dist's edge-list arguments):
# relaxation is next[v] = OR over in-neighbors u of v, so forward
# distances on G take the *reverse* table dg.sweep_table(reverse=True)
# (out-neighbors in G_r == in-neighbors in G) and distances on G_r take
# dg.sweep_table(reverse=False).
# ---------------------------------------------------------------------------

_ROWS = "engine_index_rows_total"
_ARCS = "engine_index_arcs_total"


def count_sweep(table, n: int, m: int, k_max: int) -> None:
    """Count one packed sweep over ``table`` on the host, from shapes:
    the frontier rows its levels gather (``engine_index_rows_total``,
    labelled ``layout="sliced"|"padded"``) and the valid arcs they relax,
    ``m`` a level (``engine_index_arcs_total``)."""
    reg = obsmetrics.registry()
    if isinstance(table, SlicedEll):
        layout, rows = "sliced", table.rows_per_level
    else:
        layout, rows = "padded", n * int(table.shape[1])
    reg.counter(_ROWS, layout=layout).inc(rows * k_max)
    reg.counter(_ARCS).inc(m * k_max)


def swept() -> tuple[int, int]:
    """``(rows gathered, arcs relaxed)`` by every packed sweep counted so
    far (:func:`count_sweep`), both layouts together."""
    reg = obsmetrics.registry()
    rows = sum(reg.counter(_ROWS, layout=lay).value
               for lay in ("sliced", "padded"))
    return int(rows), int(reg.counter(_ARCS).value)


def _packed_sweep(idx, frontier: jax.Array, n: int, S: int, k_max: int,
                  backend: str,
                  inv_perm: Optional[jax.Array] = None) -> jax.Array:
    """Levels 1..k_max from the packed level-0 ``frontier`` (n+1, W),
    whose row n stays 0, over ``idx``: an (n, D) in-neighbour table, or a
    sliced ELL's tables, in which case every row index is a permuted
    position and ``inv_perm`` un-permutes the result. Returns (n+1, S)
    int8 distances.

    A vertex first reached at hop d has its bit clear in the visited sets
    of levels 0..d-1 and set from d on, so its distance is the number of
    levels (0..k_max) at which the bit is clear; never reached gives
    k_max + 1 = INF. The running count is kept source-major (32*W, n),
    so unpacking a word is a stack of sublane rows (kernels/msbfs_expand
    ``msbfs_count``), and transposed once at the end.
    """
    from ..kernels.msbfs_expand.ops import msbfs_step, unreached_count

    W = frontier.shape[1]
    visited = frontier[:n]
    count = unreached_count(visited, jnp.zeros((32 * W, n), jnp.int8),
                            backend)
    zero = jnp.zeros((1, W), jnp.uint32)
    for hop in range(1, k_max + 1):
        with jax.named_scope(f"msbfs.hop{hop}"):
            new, visited = msbfs_step(idx, frontier, visited,
                                      backend=backend)
            frontier = jnp.concatenate([new, zero], axis=0)
            count = unreached_count(visited, count, backend)
    dist = count[:S].T
    if inv_perm is not None:
        # vertex v's row is the sweep's row inv_perm[v]
        dist = dist.at[inv_perm].get(mode="promise_in_bounds")
    inf = jnp.full((1, S), INF_FOR(k_max), jnp.int8)
    return jnp.concatenate([dist, inf], axis=0)


def _sweep_args(table, n: int):
    """``(idx, inv_perm)`` for :func:`_packed_sweep` from a sweep table."""
    if isinstance(table, SlicedEll):
        return table.tables, table.inv_perm
    return table[:n], None


@partial(jax.jit, static_argnames=("n", "k_max", "backend"))
def msbfs_dist_ell(table, sources: jax.Array,
                   *, n: int, k_max: int, backend: str = "jnp") -> jax.Array:
    """Packed ELL twin of :func:`msbfs_dist`.

    table      : a :class:`~repro.core.graph.SlicedEll` of the
                 in-neighbour rows, or the padded (n[+1], D) int32 ELL
                 *in*-neighbor table (pad = n; a row n is never expanded)
                 — ``DeviceGraph.sweep_table``.
    sources    : (S,) int32.
    backend    : static "pallas" | "interpret" | "jnp" (resolved by the
                 caller; a registry enum's value — strings keep the jit
                 cache key plain).
    Returns (n+1, S) int8, bit-equal to :func:`msbfs_dist` on the same
    graph whichever table it sweeps (distances are set-membership facts;
    only the dispatch shape of a level differs between backends).

    Source ``i`` is bit ``i % 32`` of word ``i // 32`` of the packed
    frontier (W = ceil(S / 32) words per vertex).
    """
    _check_k_max(k_max)
    S = sources.shape[0]
    W = -(-S // 32)
    cols = jnp.arange(S)
    idx, inv_perm = _sweep_args(table, n)
    rows = sources if inv_perm is None else inv_perm[sources]
    # distinct columns set distinct bits, so add == or, even where a
    # source repeats; the sentinel row n is never a source
    frontier = jnp.zeros((n + 1, W), jnp.uint32).at[rows, cols // 32].add(
        jnp.uint32(1) << (cols % 32).astype(jnp.uint32))
    return _packed_sweep(idx, frontier, n, S, k_max, backend, inv_perm)


@partial(jax.jit, static_argnames=("n", "k_max", "backend"))
def msbfs_set_dist_ell(table, seed_mask: jax.Array,
                       *, n: int, k_max: int,
                       backend: str = "jnp") -> jax.Array:
    """Packed ELL twin of :func:`msbfs_set_dist` (one bit column seeded
    with the whole vertex set; 31 of the word's 32 bits idle). ``table``
    as for :func:`msbfs_dist_ell`.

    seed_mask : (n+1,) int8 in {0,1} (row n must be 0).
    Returns (n+1,) int8 bit-equal to :func:`msbfs_set_dist`.
    """
    _check_k_max(k_max)
    idx, inv_perm = _sweep_args(table, n)
    seed = seed_mask.astype(bool).at[n].set(False)
    if inv_perm is not None:
        seed = jnp.concatenate([seed[table.perm], seed[n:]])
    frontier = seed.astype(jnp.uint32)[:, None]            # bit 0 of word 0
    return _packed_sweep(idx, frontier, n, 1, k_max, backend,
                         inv_perm)[:, 0]
