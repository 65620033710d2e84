"""Pallas kernel: per-level distance count from a packed reached-set.

    count[32w + b, v] += 1 - bit b of words[w, v]

The packed MS-BFS sweep (core/msbfs.py) keeps, per source, the number of
levels at which a vertex was still unreached; that number is the
distance. Vertices lie on the lane axis of both operands, so word ``w``
unpacks into the 32 sublane rows ``32w .. 32w + 31`` of the int8 count:
one (32, BV) int8 tile per word, with no relayout. Unpacking the same
bits in XLA materializes a (W, 32, V) uint32 broadcast, 8 GiB per level
at 2^22 vertices and 16 words.

Tiling: grid = (vertex blocks,); each program reads a (W, BV) int32 word
tile and rewrites its (32*W, BV) int8 count tile in place (aliased).
VMEM: (W*4 + 2*32*W) * BV bytes, double-buffered: 9 MiB at W=16,
BV=4096.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["unreached_count_pallas"]


def _count_kernel(w_ref, c_ref, o_ref):
    W, bv = w_ref.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, bv), 0)
    for w in range(W):
        row = w_ref[w:w + 1, :]                              # (1, BV)
        clear = (jax.lax.shift_right_logical(row, shifts) & 1) ^ 1
        rows = slice(32 * w, 32 * w + 32)
        o_ref[rows, :] = (c_ref[rows, :].astype(jnp.int32)
                          + clear).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_v", "interpret"))
def unreached_count_pallas(words_t: jax.Array, count: jax.Array, *,
                           block_v: int = 4096,
                           interpret: bool = False) -> jax.Array:
    """words_t: (W, V) int32 packed reached-set, vertex-minor (the bits of
    a uint32 word); count: (32*W, V) int8. Returns ``count`` plus one
    wherever the matching bit is clear."""
    W, V = words_t.shape
    bv = min(block_v, V)
    return pl.pallas_call(
        _count_kernel,
        grid=(pl.cdiv(V, bv),),
        in_specs=[pl.BlockSpec((W, bv), lambda i: (0, i)),
                  pl.BlockSpec((32 * W, bv), lambda i: (0, i))],
        out_specs=pl.BlockSpec((32 * W, bv), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(count.shape, jnp.int8),
        input_output_aliases={1: 0},
        interpret=interpret,
    )(words_t, count)
