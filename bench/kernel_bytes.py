"""Least bytes a kernel must move, computed from shapes alone.

A roofline share divides these bytes by the kernel's device time and by
the chip's HBM bandwidth (``peaks.py``). The counts are floors: they count
the work a layout has to do whatever its padding, so a layout that drops
padding reads as the same work done faster.
"""
from __future__ import annotations

__all__ = ["msbfs_sweep_bytes", "index_bytes"]


def msbfs_sweep_bytes(n: int, m: int, n_sources: int, k_max: int) -> int:
    """Bytes of one packed multi-source BFS sweep (``msbfs_dist_ell``):
    ``n`` vertices, ``m`` valid arcs, ``n_sources`` sources packed 32 to a
    uint32 word (W words per vertex), ``k_max`` hops. Per hop:

    - the ELL index entries of the valid arcs, read: m * 4;
    - the frontier words those arcs gather: m * W * 4;
    - the visited words read and the new frontier and visited words
      written: 3 * n * W * 4;
    - the int8 per-source count table read and written: 2 * 32W * n.
    """
    w = -(-int(n_sources) // 32)
    per_hop = m * 4 + m * w * 4 + 3 * n * w * 4 + 2 * 32 * w * n
    return int(k_max) * per_hop


def index_bytes(n: int, m: int, n_src: int, n_tgt: int, k_max: int) -> int:
    """Both sweeps of one batch's index: from the distinct sources over the
    in-arcs, and from the distinct targets over the out-arcs."""
    return (msbfs_sweep_bytes(n, m, n_src, k_max)
            + msbfs_sweep_bytes(n, m, n_tgt, k_max))
