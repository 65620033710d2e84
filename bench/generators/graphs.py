"""Seeded graph generators, one per deployment kind.

Each returns ``(n, src, dst)``: the vertex count and the arc list as int64
arrays, before any normalisation. The program and the reference are both
handed these arrays and each builds its own adjacency from them; both drop
self-loops and repeated arcs, which is the graph model (a simple digraph)
that every configuration states.

Both generators draw the random models of the 10th DIMACS Implementation
Challenge's synthetic instances: ``n`` points uniform in the unit square,
joined by a distance threshold (``rgg``) or by their Delaunay
triangulation (``delaunay``). Every edge is an arc in both directions.
Vertex ids follow the points' cell in a square grid of about one point
per cell, row by row, so that ids carry spatial locality.

A configuration whose ``generator`` is none of these names brings its own
generator as a file: ``bench/graphs/<generator>.py``, which defines
``make(**params) -> (n, src, dst)`` under the same contract, with
``params["seed"]`` fixing the instance. A new deployment then adds files
and leaves this module as it is.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

__all__ = ["GENERATORS", "rgg", "delaunay", "make", "load_file"]


def _points(seed: int, n: int) -> np.ndarray:
    """``n`` points uniform in the unit square, ordered by grid cell."""
    p = np.random.default_rng(seed).random((n, 2))
    side = max(int(np.sqrt(n)), 1)
    cell = (np.minimum((p[:, 1] * side).astype(np.int64), side - 1) * side
            + np.minimum((p[:, 0] * side).astype(np.int64), side - 1))
    return p[np.argsort(cell, kind="stable")]


def _both_ways(a: np.ndarray, b: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    a = np.asarray(a, np.int64)
    b = np.asarray(b, np.int64)
    return np.concatenate([a, b]), np.concatenate([b, a])


def rgg(seed: int, n: int, radius_c: float) -> tuple[int, np.ndarray,
                                                     np.ndarray]:
    """Random geometric graph: an edge joins two points closer than
    ``radius_c * sqrt(ln n / n)``."""
    from scipy.spatial import cKDTree

    r = radius_c * np.sqrt(np.log(n) / n)
    pairs = cKDTree(_points(seed, n)).query_pairs(r, output_type="ndarray")
    return (n, *_both_ways(pairs[:, 0], pairs[:, 1]))


def delaunay(seed: int, n: int) -> tuple[int, np.ndarray, np.ndarray]:
    """Delaunay triangulation: an edge joins two points that share a
    triangle."""
    from scipy.spatial import Delaunay

    tri = Delaunay(_points(seed, n)).simplices.astype(np.int64)
    e = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    e.sort(axis=1)
    a, b = np.divmod(np.unique(e[:, 0] * n + e[:, 1]), n)
    return (n, *_both_ways(a, b))


GENERATORS = {"rgg": rgg, "delaunay": delaunay}


def load_file(path: Path, attr: str):
    """The attribute ``attr`` of the Python file ``path``, loaded as a
    module of its own: how the benchmark finds a configuration's generator
    and a per-layer metric's reader by name."""
    module = f"bench_{path.parent.name}_{path.stem}"
    spec = importlib.util.spec_from_file_location(
        module.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr)


def make(config: dict, graph_dir: Path
         ) -> tuple[int, np.ndarray, np.ndarray]:
    """The arc list of ``config``'s graph: the generator named by
    ``config["generator"]`` called with ``config["params"]``, whose
    ``seed`` fixes the instance. Every run of a configuration serves the
    same instance, as every user of a published instance does.

    The name is a built-in of :data:`GENERATORS` or the file
    ``<graph_dir>/<name>.py``; a name that is both, or neither, raises
    ``ValueError``."""
    name = config["generator"]
    path = Path(graph_dir) / f"{name}.py"
    files = sorted(p.stem for p in Path(graph_dir).glob("*.py"))
    if name in GENERATORS and name in files:
        raise ValueError(f"graph generator {name!r} is both a built-in and "
                         f"the file {path}")
    if name in GENERATORS:
        return GENERATORS[name](**config["params"])
    if name not in files:
        raise ValueError(f"unknown graph generator {name!r}; built-in: "
                         f"{sorted(GENERATORS)}, files in {graph_dir}: "
                         f"{files}")
    return load_file(path, "make")(**config["params"])
