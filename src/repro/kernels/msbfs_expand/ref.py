"""Bit-packed MS-BFS expansion over an ELL table (jnp) and the
pack/unpack helpers.

    next[v, w] = OR over d of frontier[ell_idx[v, d], w]

Frontiers are bit-packed uint32 words, 32 BFS sources per word (the MS-BFS
[36] trick): one OR handles 32 sources at once. The table is padded ELL,
or a sliced ELL: a tuple of ELL tables over contiguous row runs, each as
wide as its own rows need (``core/graph.py`` ``SlicedEll``). Either way
the expansion is a regular row gather, taken one column of a table at a
time: the live gather stays (rows, W) words and never becomes
(rows, D, W), which at 2^22 vertices would not fit the device. Each entry
costs one gathered row, so a level over the sliced ELL gathers about one
row per arc, and over the padded ELL V * D rows. The index sweep takes the
sliced ELL; the padded one remains for a delta-patched graph and for the
enumeration's ``expand_level``. The expansion has no Pallas arm (see
``JNP_ONLY_OPS`` in :mod:`repro.kernels.registry`); the per-level
distance count (:func:`unreached_count_ref`) has one (kernel.py).

Sentinel: ell row entries equal to V point at frontier row V, which the
callers pin to zero words, so padding contributes nothing to the OR.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["msbfs_expand_ref", "msbfs_step_ref", "unreached_count_ref",
           "pack_bits", "unpack_bits"]


def pack_bits(bits: jax.Array) -> jax.Array:
    """(V, S) bool -> (V, ceil(S/32)) uint32 (little-endian within a word)."""
    V, S = bits.shape
    W = -(-S // 32)
    pad = W * 32 - S
    b = jnp.pad(bits.astype(jnp.uint32), ((0, 0), (0, pad)))
    b = b.reshape(V, W, 32)
    powers = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(b * powers[None, None, :], axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, S: int) -> jax.Array:
    """(V, W) uint32 -> (V, S) bool."""
    V, W = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(V, W * 32)[:, :S].astype(bool)


def msbfs_expand_ref(ell_idx, frontier: jax.Array) -> jax.Array:
    """ell_idx: (V, D) int32 (pad = V), or a tuple of (rows_i, D_i) tables
    whose rows stack to V; frontier: (V+1, W) uint32 (row V = 0).

    Returns next[v, w] = OR_d frontier[ell_idx[v, d], w], (V, W) uint32.
    """
    if isinstance(ell_idx, tuple):
        # each table fills its own contiguous run of rows: no scatter
        return jnp.concatenate([msbfs_expand_ref(t, frontier)
                                for t in ell_idx], axis=0)

    def column(acc, rows):
        return acc | frontier.at[rows].get(mode="promise_in_bounds"), None

    acc0 = jnp.zeros((ell_idx.shape[0], frontier.shape[1]), jnp.uint32)
    return jax.lax.scan(column, acc0, ell_idx.T)[0]


def msbfs_step_ref(ell_idx: jax.Array, frontier: jax.Array,
                   visited: jax.Array):
    """One MS-BFS level: expand, then dedup against the visited set.

    ell_idx  : (V, D) int32 in-neighbour table (pad = V), or a sliced
               ELL's tuple of tables (see :func:`msbfs_expand_ref`)
    frontier : (V+1, W) uint32 packed level-(hop-1) frontier (row V = 0)
    visited  : (V, W) uint32 packed reached-set (hop-0 seeds included)

    Returns (next_frontier, visited | next_frontier), both (V, W).
    """
    new = msbfs_expand_ref(ell_idx, frontier) & ~visited
    return new, visited | new


def unreached_count_ref(words_t: jax.Array, count: jax.Array) -> jax.Array:
    """jnp twin of :func:`~repro.kernels.msbfs_expand.kernel.
    unreached_count_pallas`: ``count[32w + b, v] += 1 - bit b of
    words_t[w, v]``. words_t: (W, V) int32; count: (32*W, V) int8."""
    W = words_t.shape[0]
    shifts = jnp.arange(32, dtype=jnp.int32)[None, :, None]
    bits = jax.lax.shift_right_logical(words_t[:, None, :], shifts) & 1
    return count + (1 - bits).astype(jnp.int8).reshape(32 * W, -1)
