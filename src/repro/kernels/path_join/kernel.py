"""Pallas kernel: path-pair overlap counting for the ⊕ join (Def 3.1).

    overlap[i, j] = #{ (p, q) : A[i, p] == B[j, q], A[i, p] >= 0 }

The enumeration hot spot (Fig 3c: join/scan dominates): joining forward and
backward half-paths requires, for every candidate pair, the simple-path
check "do the two halves share a vertex?". On CPU that is a hash probe per
pair; here it is an equality reduction over the tiny L dimensions
(L <= 9): a static loop over the path columns, each step one 2-D
(BA, BB) compare, so every op is a plain VPU tile op that Mosaic lowers.
The wrapper derives join validity:

  keyed join  : valid = key match (last cols) & overlap == 1 (join vertex only)
  splice join : valid = overlap == 0 (prefix vs cached suffix are disjoint)

Tiling: grid = (A blocks, B blocks); each program owns a (BA, BB) int32
tile; the A tile (BA, LA) and the transposed B tile (LB, BB) are
VMEM-resident (BA=BB=256, L=9 -> ~18 KB in, 256 KB out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["path_overlap_pallas", "rowwise_overlap_pallas",
           "path_member_pallas"]


def _kernel(a_ref, bt_ref, out_ref):
    a = a_ref[...]                            # (BA, LA) int32
    bt = bt_ref[...]                          # (LB, BB) int32
    acc = jnp.zeros(out_ref.shape, jnp.int32)
    for p in range(a.shape[1]):
        ap = a[:, p:p + 1]                    # (BA, 1)
        hit = jnp.zeros(out_ref.shape, jnp.int32)
        for q in range(bt.shape[0]):
            hit += (ap == bt[q:q + 1, :]).astype(jnp.int32)   # (BA, BB)
        acc += jnp.where(ap >= 0, hit, 0)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_a", "block_b", "interpret"))
def path_overlap_pallas(a_verts: jax.Array, b_verts: jax.Array,
                        *, block_a: int = 256, block_b: int = 256,
                        interpret: bool = False) -> jax.Array:
    """a_verts: (NA, LA), b_verts: (NB, LB) int32 (pad -1) -> (NA, NB) int32."""
    NA, LA = a_verts.shape
    NB, LB = b_verts.shape
    ba = min(block_a, NA)
    bb = min(block_b, NB)
    grid = (pl.cdiv(NA, ba), pl.cdiv(NB, bb))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ba, LA), lambda i, j: (i, 0)),
            pl.BlockSpec((LB, bb), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((ba, bb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((NA, NB), jnp.int32),
        interpret=interpret,
    )(a_verts, b_verts.T)


def _rowwise_kernel(a_ref, b_ref, out_ref):
    a = a_ref[...]                            # (BN, LA) int32
    b = b_ref[...]                            # (BN, LB) int32
    acc = jnp.zeros(b.shape, jnp.int32)
    for p in range(a.shape[1]):
        ap = a[:, p:p + 1]                    # (BN, 1)
        acc += ((ap == b) & (ap >= 0)).astype(jnp.int32)      # (BN, LB)
    out_ref[...] = jnp.sum(acc, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def rowwise_overlap_pallas(a_verts: jax.Array, b_verts: jax.Array,
                           *, block_n: int = 1024,
                           interpret: bool = False) -> jax.Array:
    """Row-aligned overlap counts for already-enumerated join pairs:

        out[i] = #{ (p, q) : A[i, p] == B[i, q], A[i, p] >= 0 }

    The join hot loop's shape: the searchsorted bucket enumeration (or the
    cross-join index split) has already paired row i of A with row i of B,
    so the dense (NA, NB) product of :func:`path_overlap_pallas` would be
    quadratic waste — this kernel fuses the per-pair simple-path check of
    one assembled join into a single dispatch over the pair buffer.

    a_verts: (N, LA), b_verts: (N, LB) int32 (pad -1) -> (N, 1) int32.
    """
    N, LA = a_verts.shape
    LB = b_verts.shape[1]
    bn = min(block_n, N)
    return pl.pallas_call(
        _rowwise_kernel,
        grid=(pl.cdiv(N, bn),),
        in_specs=[
            pl.BlockSpec((bn, LA), lambda i: (i, 0)),
            pl.BlockSpec((bn, LB), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1), jnp.int32),
        interpret=interpret,
    )(a_verts, b_verts)


def _member_kernel(v_ref, c_ref, out_ref):
    v = v_ref[...]                            # (BN, L)  path prefixes
    c = c_ref[...]                            # (BN, D)  candidate vertices
    acc = jnp.zeros(c.shape, jnp.int32)
    for p in range(v.shape[1]):
        acc += (c == v[:, p:p + 1]).astype(jnp.int32)         # (BN, D)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def path_member_pallas(verts: jax.Array, cand: jax.Array,
                       *, block_n: int = 512,
                       interpret: bool = False) -> jax.Array:
    """Per-candidate membership counts against the owning path prefix:

        out[i, d] = #{ p : cand[i, d] == verts[i, p] }

    The expand superstep's duplicate-vertex mask — every frontier path's D
    ELL neighbor candidates checked against its own L-vertex prefix in one
    dispatch. verts: (N, L), cand: (N, D) int32 -> (N, D) int32.
    """
    N, L = verts.shape
    D = cand.shape[1]
    bn = min(block_n, N)
    return pl.pallas_call(
        _member_kernel,
        grid=(pl.cdiv(N, bn),),
        in_specs=[
            pl.BlockSpec((bn, L), lambda i: (i, 0)),
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.int32),
        interpret=interpret,
    )(verts, cand)
