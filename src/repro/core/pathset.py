"""Static-shape path buffers (``PathSet``) and compaction utilities.

A PathSet stores up to ``cap`` paths as a dense int32 matrix. The first
``count`` rows are valid and packed at the front; unused cells are -1. All
sizes are static so every consumer is jit-compilable; data-dependent sizes
surface as (count, overflow) pairs that the host driver inspects.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PathSet", "HostPathSet", "empty", "singleton", "compact_rows",
           "concat", "to_host", "offload", "upload", "pathset_nbytes"]

# per-PathSet bookkeeping charged on top of the vertex matrix (count +
# overflow scalars); shared by HostPathSet.nbytes and the cache's
# pre-transfer size estimate so the two can never diverge
PATHSET_BOOKKEEPING_BYTES = 16


def pathset_nbytes(cap: int, width: int, itemsize: int = 4) -> int:
    """Bytes one (cap, width) path buffer accounts for — the *single*
    byte-math used both for ``HostPathSet.nbytes`` (LRU budget accounting)
    and for size estimates taken from device shapes before any transfer."""
    return int(cap) * int(width) * int(itemsize) + PATHSET_BOOKKEEPING_BYTES


class PathSet(NamedTuple):
    verts: jax.Array    # (cap, L) int32, row i cols 0..length_i are vertices
    count: jax.Array    # () int32 -- number of valid (packed) rows
    overflow: jax.Array  # () bool -- True if rows were dropped to fit cap

    @property
    def cap(self) -> int:
        return self.verts.shape[0]

    @property
    def width(self) -> int:
        return self.verts.shape[1]


def empty(cap: int, width: int) -> PathSet:
    return PathSet(verts=jnp.full((cap, width), -1, jnp.int32),
                   count=jnp.int32(0), overflow=jnp.bool_(False))


def singleton(vertex, width: int) -> PathSet:
    """PathSet holding the single length-0 path [vertex]."""
    verts = jnp.full((1, width), -1, jnp.int32).at[0, 0].set(vertex)
    return PathSet(verts=verts, count=jnp.int32(1), overflow=jnp.bool_(False))


def compact_rows(mask: jax.Array, payload: jax.Array, out_cap: int,
                 fill: int = -1) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Scatter payload rows where mask is True into a packed (out_cap, ...) buffer.

    mask: (N,) bool; payload: (N, ...) -- returns (out, count, overflow).
    Rows beyond out_cap are dropped (overflow=True).
    """
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    total = jnp.where(mask.shape[0] > 0, pos[-1] + 1, 0).astype(jnp.int32)
    dest = jnp.where(mask & (pos < out_cap), pos, out_cap)
    out = jnp.full((out_cap + 1,) + payload.shape[1:], fill, payload.dtype)
    out = out.at[dest].set(payload)
    return out[:out_cap], jnp.minimum(total, out_cap), total > out_cap


@jax.jit
def _place(out_verts, out_count, b_verts, b_count):
    """Write b's valid rows at row ``out_count`` of ``out_verts``. The
    caller sizes ``out_verts`` to hold every input's full capacity, so
    the write never runs past the end."""
    bmask = jnp.arange(b_verts.shape[0])[:, None] < b_count
    b = jnp.where(bmask, b_verts, -1)
    out = jax.lax.dynamic_update_slice(out_verts, b, (out_count, 0))
    return out, out_count + b_count


def concat(sets: list[PathSet]) -> PathSet:
    """Concatenate packed PathSets (same width) into one packed PathSet.

    The output capacity is the pow2 bucket of the summed input
    capacities, so the per-input placement compiles once per (bucket,
    input bucket, width) however many sets are joined.
    """
    sets = [s for s in sets if s is not None]
    if not sets:
        raise ValueError("concat of no PathSets")
    if len(sets) == 1:
        return sets[0]
    from .graph import pow2_ceil

    width = sets[0].verts.shape[1]
    cap = pow2_ceil(sum(s.verts.shape[0] for s in sets))
    verts = jnp.full((cap, width), -1, jnp.int32)
    count = jnp.int32(0)
    ov = sets[0].overflow
    for s in sets:
        verts, count = _place(verts, count, s.verts, s.count)
        ov = ov | s.overflow
    return PathSet(verts=verts, count=count, overflow=ov)


def to_host(ps: PathSet) -> np.ndarray:
    """Valid rows as a host numpy array (n, L)."""
    # slicing on the host: a device slice would compile once per count
    return np.asarray(ps.verts)[:int(ps.count)]


class HostPathSet(NamedTuple):
    """Host-pinned copy of a PathSet (the cross-batch cache's storage form).

    The full padded buffer is kept (not just the valid rows) so a device
    re-upload restores the exact capacity bucket and stays within the same
    jit shape cache as the original materialization.
    """

    verts: np.ndarray   # (cap, L) int32
    count: int
    overflow: bool

    @property
    def nbytes(self) -> int:
        return pathset_nbytes(self.verts.shape[0], self.verts.shape[1],
                              self.verts.itemsize)

    @property
    def cap(self) -> int:
        return self.verts.shape[0]


def offload(ps: PathSet) -> HostPathSet:
    """Device -> host copy preserving capacity, count and overflow."""
    return HostPathSet(verts=np.asarray(ps.verts), count=int(ps.count),
                       overflow=bool(ps.overflow))


def upload(hps: HostPathSet) -> PathSet:
    """Host -> device round-trip inverse of :func:`offload`."""
    return PathSet(verts=jnp.asarray(hps.verts), count=jnp.int32(hps.count),
                   overflow=jnp.bool_(hps.overflow))
