"""Public wrappers: packed MS-BFS hop and the per-level step."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..registry import BackendLike, dispatch, register_op
from .kernel import unreached_count_pallas
from .ref import (msbfs_expand_ref, msbfs_step_ref, pack_bits, unpack_bits,
                  unreached_count_ref)

__all__ = ["msbfs_hop_packed", "msbfs_step", "unreached_count", "pack_bits",
           "unpack_bits"]


register_op("msbfs_expand", jnp=msbfs_expand_ref)
register_op("msbfs_step", jnp=msbfs_step_ref)
register_op(
    "msbfs_count",
    pallas=unreached_count_pallas,
    interpret=lambda w, c: unreached_count_pallas(w, c, interpret=True),
    jnp=unreached_count_ref,
)


def msbfs_hop_packed(ell_idx: jax.Array, frontier_words: jax.Array,
                     backend: BackendLike = None) -> jax.Array:
    """frontier_words: (V+1, W) uint32 with sentinel row V zeroed.

    Returns (V+1, W) next frontier (sentinel row re-zeroed).
    """
    fw = frontier_words.at[-1].set(jnp.uint32(0))
    nxt = dispatch("msbfs_expand", backend)(ell_idx, fw)
    zero = jnp.zeros((1, nxt.shape[1]), jnp.uint32)
    return jnp.concatenate([nxt, zero], axis=0)


def msbfs_step(ell_idx: jax.Array, frontier: jax.Array, visited: jax.Array,
               backend: BackendLike = None):
    """One MS-BFS level (expand + dedup against ``visited``).

    See :func:`~repro.kernels.msbfs_expand.ref.msbfs_step_ref` for shapes.
    Returns (next_frontier, visited | next_frontier).
    """
    return dispatch("msbfs_step", backend)(ell_idx, frontier, visited)


def unreached_count(visited: jax.Array, count: jax.Array,
                    backend: BackendLike = None) -> jax.Array:
    """Add one to ``count[i, v]`` wherever source ``i`` has not reached
    vertex ``v``: visited (V, W) uint32 packed as :func:`pack_bits`
    (source ``i`` = bit ``i % 32`` of word ``i // 32``); count
    (32*W, V) int8, source-major."""
    words_t = jax.lax.bitcast_convert_type(visited.T, jnp.int32)
    return dispatch("msbfs_count", backend)(words_t, count)
