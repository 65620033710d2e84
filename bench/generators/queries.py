"""The one query-stream generator; a traffic file only sets its numbers.

A traffic mix (``bench/traffic/<name>.json``) holds:

- ``batch``: queries per batch (one batch is outstanding at a time);
- ``k``: the hop budget, an int, or ``[lo, hi]`` drawn uniformly per query;
- ``outputs``: the output kinds, cycled by the query's position in the
  stream (``paths``, ``count``, ``exists``);
- ``walk``: ``[lo, hi]`` steps of the random walk that picks t from s;
  ``hi`` may be ``"k"`` for the query's own hop budget;
- ``shared``: the share of each batch that perturbs a few seed pairs
  (``share``, ``seeds_per_batch``, ``perturb_p``), or null for none;
- ``warm_batches``: how many batches set-up serves before the window.

A random-walk query takes s uniform over all vertices and t the end of a
walk of ``walk`` steps from s, so a path of at most that many arcs exists
when the walk is no longer than k; a walk that meets a vertex with no
out-arc stops there, and one that ends where it began is drawn again.

A shared query belongs to one of the batch's seed pairs (s0, t0), which
are random-walk queries; the shared queries are dealt to the seeds in
turn. Each has s0 replaced by a random in-neighbour of s0 with
probability ``perturb_p``, and t0 by a random out-neighbour of t0 with the
same probability, each drawn on its own; a perturbation that would make
s == t keeps the seed pair. A batch is shuffled before it is sent, so
shared and random queries share the output cycle.

Batch ``i`` of stream ``stream`` depends only on (seed, stream, i) and the
graph. Every run draws its batches from one fixed pool, seed ``POOL``:
set-up serves the first ``warm_batches`` of the ``WARM`` stream, and the
window the first ``window_batches`` of the ``WINDOW`` stream, fresh
batches that set-up never sent, in turn and again from the start when the
window outlasts them. The run's seed orders the queries inside each batch.
So every seed serves the same batches, each in another order, and its
window ends after the same batches.
"""
from __future__ import annotations

import numpy as np

__all__ = ["POOL", "WARM", "WINDOW", "SAMPLE", "batch", "seeded",
           "warm_batches", "window_batches"]

# streams drawn from one seed: the warm-up batches, the window's batches,
# and the sample of answers the reference checks when a window holds many
WARM = 1
WINDOW = 2
SAMPLE = 3
ORDER = 4
# the seed of the pool that every run's batches come from
POOL = 0
MAX_DRAWS = 1000


def seeded(seed: int, *tags: int) -> np.random.Generator:
    """A generator that depends on ``seed`` (any integer >= 0, of any
    size) and the ``tags`` alone."""
    return np.random.default_rng([int(seed), *tags])


def _hop_budget(mix: dict, rng) -> int:
    k = mix["k"]
    if isinstance(k, int):
        return k
    lo, hi = k
    return int(rng.integers(lo, hi + 1))


def _walk_query(adj, mix: dict, rng) -> tuple[int, int, int]:
    """A random-walk query with s != t."""
    lo, hi = mix["walk"]
    for _ in range(MAX_DRAWS):
        k = _hop_budget(mix, rng)
        steps = int(rng.integers(lo, (k if hi == "k" else hi) + 1))
        s = v = int(rng.integers(0, adj.n))
        for _ in range(steps):
            nbrs = adj.out_neighbors(v)
            if nbrs.size == 0:
                break
            v = int(nbrs[rng.integers(0, nbrs.size)])
        if v != s:
            return s, v, k
    raise ValueError(f"no random-walk query with s != t in {MAX_DRAWS} "
                     "draws")


def _near(nbrs: np.ndarray, v: int, p: float, rng) -> int:
    """One of ``nbrs`` with probability ``p`` (when there is one), else
    ``v``."""
    if rng.random() < p and nbrs.size:
        return int(nbrs[rng.integers(0, nbrs.size)])
    return v


def _seed_group(adj, mix: dict, c: int, p: float, rng
                ) -> list[tuple[int, int, int]]:
    """The ``c`` shared queries of one seed pair."""
    s0, t0, k = _walk_query(adj, mix, rng)
    ins, outs = adj.in_neighbors(s0), adj.out_neighbors(t0)
    group = []
    for _ in range(c):
        s, t = _near(ins, s0, p, rng), _near(outs, t0, p, rng)
        group.append((s, t, k) if s != t else (s0, t0, k))
    return group


def batch(adj, mix: dict, seed: int, stream: int, index: int
          ) -> list[tuple[int, int, int, str]]:
    """Batch ``index`` of ``stream``: ``mix["batch"]`` queries as
    ``(s, t, k, output)``."""
    rng = seeded(seed, stream, index)
    size = int(mix["batch"])
    shared = mix.get("shared") or {}
    n_shared = int(round(float(shared.get("share", 0.0)) * size))
    qs = []
    if n_shared:
        n_seeds = int(shared["seeds_per_batch"])
        for j in range(n_seeds):
            qs += _seed_group(adj, mix, len(range(j, n_shared, n_seeds)),
                              float(shared["perturb_p"]), rng)
    qs += [_walk_query(adj, mix, rng) for _ in range(size - n_shared)]
    qs = [qs[i] for i in rng.permutation(size)]
    outputs = mix["outputs"]
    first = index * size
    return [(s, t, k, outputs[(first + j) % len(outputs)])
            for j, (s, t, k) in enumerate(qs)]


def warm_batches(adj, mix: dict) -> list:
    """The batches set-up serves."""
    return [batch(adj, mix, POOL, WARM, i)
            for i in range(int(mix["warm_batches"]))]


def window_batches(adj, mix: dict, seed: int) -> list:
    """The window's batches, each with its queries in the order of
    ``seed``; the window serves them in turn, from the start again when it
    outlasts them."""
    out = []
    for i in range(int(mix["window_batches"])):
        b = batch(adj, mix, POOL, WINDOW, i)
        out.append([b[j] for j in seeded(seed, ORDER, i).permutation(len(b))])
    return out
