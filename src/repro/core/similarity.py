"""Query similarity (Def 4.4/4.5) from hop-constrained neighborhoods.

Γ(q) / Γ_r(q) are reusable by-products of the index BFS (Def 4.4 note): a
vertex is in Γ(q) iff dist(q.s, v) <= q.k. We materialize them as boolean
rows and compute all-pairs intersection sizes either as a chunked MXU
matmul (jnp reference) or with the packed AND+popcount Pallas kernel.

Def 4.5's printed formula has a stray ^{-1}; properties (1)-(3) and the
zero-intersection footnote pin the intended quantity to a mean of the two
directional *overlap coefficients*  i = |Γ_A ∩ Γ_B| / min(|Γ_A|, |Γ_B|).
We use the arithmetic mean (the only reading consistent with the footnote's
"the corresponding part ... is 0"), documented in DESIGN.md.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .index import QueryIndex
from ..kernels.registry import KernelBackend, resolve_backend

__all__ = ["gamma_matrix", "intersection_matrix", "similarity_matrix"]


def _gamma_rows(dist: jax.Array, cols, ks: jax.Array) -> jax.Array:
    """(Q, n) bool: row q marks the vertices within ks[q] hops of the
    endpoint in column cols[q] of ``dist`` (sentinel row dropped)."""
    return (dist[:-1, cols] <= ks[None, :]).T


def _hop_budgets(index: QueryIndex) -> jax.Array:
    return jnp.asarray(np.array([q[2] for q in index.queries], np.int8))


def gamma_matrix(index: QueryIndex, reverse: bool = False) -> jax.Array:
    """(Q, n) bool — Γ_r if reverse else Γ."""
    if reverse:
        return _gamma_rows(index.dist_t, index.tgt_col, _hop_budgets(index))
    return _gamma_rows(index.dist_s, index.src_col, _hop_budgets(index))


@partial(jax.jit, static_argnames=("chunk",))
def intersection_matrix(gam: jax.Array, chunk: int = 1 << 16) -> jax.Array:
    """All-pairs |Γ_A ∩ Γ_B| via chunked f32 matmul on the MXU (ref path)."""
    Q, n = gam.shape
    out = jnp.zeros((Q, Q), jnp.float32)
    for lo in range(0, n, chunk):
        g = gam[:, lo:lo + chunk].astype(jnp.float32)
        out = out + g @ g.T
    return out.astype(jnp.int32)


@partial(jax.jit, static_argnames=("backend",))
def _gamma_stats(dist: jax.Array, cols: jax.Array, ks: jax.Array, *,
                 backend: str) -> tuple[jax.Array, jax.Array]:
    """(Q, Q) |Γ_A ∩ Γ_B| and (Q,) |Γ| for one direction, in one program:
    the (Q, n) membership rows and their packed words exist only inside
    it (run op by op they cost several (Q, n) buffers at once)."""
    gam = _gamma_rows(dist, cols, ks)                       # (Q, n) bool
    if KernelBackend(backend).uses_kernel:
        from ..kernels.pairwise_popcount.ops import pairwise_intersections
        inter = pairwise_intersections(gam, backend=backend)
    else:
        inter = intersection_matrix(gam)
    return inter, jnp.sum(gam, axis=1, dtype=jnp.int32)


def similarity_matrix(index: QueryIndex,
                      backend: Optional[str] = None) -> np.ndarray:
    """(Q, Q) float64 μ matrix on host (diagonal = 1).

    ``backend`` resolves through the kernel registry (None -> env/auto;
    unknown names raise ValueError): kernel backends run the packed
    AND+popcount kernel, ``jnp`` the chunked MXU matmul reference.
    """
    kb = resolve_backend(backend).value
    ks = _hop_budgets(index)
    inter_f, size_f = _gamma_stats(index.dist_s, jnp.asarray(index.src_col),
                                   ks, backend=kb)
    inter_r, size_r = _gamma_stats(index.dist_t, jnp.asarray(index.tgt_col),
                                   ks, backend=kb)
    inter_f, inter_r = np.asarray(inter_f), np.asarray(inter_r)
    size_f = np.asarray(size_f).astype(np.int64)
    size_r = np.asarray(size_r).astype(np.int64)

    def overlap(inter, size):
        mins = np.minimum(size[:, None], size[None, :]).astype(np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            i = np.where(mins > 0, inter / np.maximum(mins, 1), 0.0)
        return np.where(inter > 0, i, 0.0)

    mu = 0.5 * (overlap(inter_f, size_f) + overlap(inter_r, size_r))
    np.fill_diagonal(mu, 1.0)
    return mu
