"""Sharded multi-device batch execution: plan -> place -> gather.

The engine's heavy stages distribute along two orthogonal axes, both
provided here and both degrading to the identity on a single device (a
mesh of size 1 — or no mesh at all — runs exactly the single-device code):

  * **mesh-parallel index** -- the edge kernels (MS-BFS ``msbfs_dist`` /
    ``msbfs_set_dist``) are pure pjit programs over the
    dst-sorted edge lists, so sharding the edge axis over a named 1-D mesh
    ("cells") and letting GSPMD partition the gather + segment-reduce is a
    placement decision: :func:`shard_graph_edges` re-pads the PR-4
    sentinel-pow2 buckets to a device-count-aligned capacity (a pow2
    bucket is already divisible by any pow2 device count) and
    ``device_put``\\ s them under a ``NamedSharding``. Results are
    bit-equal to single-device: the boolean-semiring ``segment_max`` is
    order-free.

  * **cluster-parallel enumeration** -- sharing clusters are the natural
    data-parallel work unit (sharing graphs never cross clusters, per the
    paper's Ψ construction), so detected clusters are placed on
    per-device *engine replicas* by a greedy cost-balanced assignment
    (:func:`plan_clusters`; cluster cost ≈ Σ per-query hop budget ×
    frontier estimate from the already-built index) and executed
    concurrently, one worker thread per replica pinned with
    ``jax.default_device``. Per-device ``PathSet`` results and stats are
    gathered back into one ``BatchReport`` (``stats["per_device"]``).

A replica is a shallow engine clone owning device-local copies of the
``DeviceGraph`` views and its *own* ``SharedPathCache`` (the cache is not
thread-safe by design); ``BatchPathEngine.apply_delta`` fans every edge
delta out through :meth:`ShardedExecutor.propagate_delta`, so all replica
graphs patch in lockstep and all replica caches see the same hop-scoped
invalidation — and therefore the same epochs — as the primary.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import numpy as np

from .graph import DeviceGraph, Graph, pad_edge_list, pow2_ceil
from .query import midpoint_split
from ..obs import metrics as obsmetrics

__all__ = ["shard_edges", "distributed_graph", "shard_graph_edges",
           "resolve_mesh", "edge_bucket_for", "replicate_graph",
           "query_ball_cost", "cluster_costs", "plan_clusters",
           "ShardedExecutor"]

# every device-resident array field of a DeviceGraph (the placement unit)
_DG_ARRAYS = ("esrc", "edst", "ell_idx", "ell_mask",
              "r_esrc", "r_edst", "r_ell_idx", "r_ell_mask",
              "ell_sliced", "r_ell_sliced")   # pytrees of arrays, or None


# ----------------------------------------------------------------------
# mesh resolution
# ----------------------------------------------------------------------
def resolve_mesh(mesh=None, n_devices: Optional[int] = None):
    """The mesh an engine executes on, or None for plain single-device.

    ``mesh`` wins when given (any ``jax.sharding.Mesh``; all axes are
    used). Otherwise ``n_devices >= 1`` builds a 1-D mesh named "cells"
    over the first N local devices — ``n_devices=1`` is a real (identity)
    mesh, so the sharded code path can be exercised on one device.
    ``None``/``0`` means no mesh.
    """
    if mesh is not None:
        return mesh
    if not n_devices:
        return None
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if n_devices > len(devs):
        raise ValueError(f"n_devices={n_devices} but only {len(devs)} "
                         f"local devices are visible")
    return Mesh(np.array(devs[:int(n_devices)]), ("cells",))


def edge_bucket_for(m: int, n_dev: int) -> int:
    """Device-count-aligned edge capacity: the pow2 bucket of ``m``,
    grown to the next multiple of ``n_dev`` when the device count is not
    a power of two (for pow2 device counts the pow2 bucket is already
    divisible, so sharded and single-device shapes share warm compiles).
    """
    cap = max(pow2_ceil(max(int(m), 1)), int(n_dev))
    if cap % n_dev:
        cap = -(-cap // n_dev) * n_dev
    return cap


# ----------------------------------------------------------------------
# edge-list sharding (the GSPMD index layer)
# ----------------------------------------------------------------------
def shard_edges(esrc, edst, mesh, axes=None, *, n: int):
    """Place a dst-sorted edge list sharded over the mesh.

    Padding to a device multiple reuses the sentinel ``(n, n)`` pad from
    :func:`~repro.core.graph.pad_edge_list`: sentinel edges are dropped by
    every segment op and gather the zero sentinel row, so they are inert
    in the boolean BFS semiring and in any counting ``segment_sum``.
    ``n`` is the vertex count the sentinel encodes. Sentinel ``n`` sorts
    after every real destination, so the dst-sorted invariant survives.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    axes = tuple(mesh.axis_names) if axes is None else tuple(axes)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))
    m_cap = int(esrc.shape[0])
    cap = -(-m_cap // n_dev) * n_dev
    if cap > m_cap:
        esrc, edst = pad_edge_list(np.asarray(esrc), np.asarray(edst),
                                   n, cap)
    sh = NamedSharding(mesh, PartitionSpec(axes))
    return jax.device_put(esrc, sh), jax.device_put(edst, sh)


def shard_graph_edges(dg: DeviceGraph, mesh, axes=None) -> DeviceGraph:
    """A DeviceGraph whose edge lists are GSPMD-sharded over ``mesh``.

    Only the edge lists move — the ELL matrices (enumeration gathers) are
    untouched, because enumeration parallelism is cluster-level replica
    placement, not GSPMD. ``m`` stays the valid edge count: any pad added
    here is capacity, not edges.
    """
    esrc, edst = shard_edges(dg.esrc, dg.edst, mesh, axes, n=dg.n)
    r_esrc, r_edst = shard_edges(dg.r_esrc, dg.r_edst, mesh, axes, n=dg.n)
    return dataclasses.replace(dg, esrc=esrc, edst=edst,
                               r_esrc=r_esrc, r_edst=r_edst)


def distributed_graph(g: Graph, mesh, axes=None) -> DeviceGraph:
    """DeviceGraph built straight into the sharded-edge layout (ELL
    replicated on the default device; suitable for graphs whose
    index-pruned ELL fits per device)."""
    n_dev = int(np.prod(list(mesh.shape.values())))
    dg = DeviceGraph.build(g, edge_cap=edge_bucket_for(g.m, n_dev))
    return shard_graph_edges(dg, mesh, axes)


def replicate_graph(dg: DeviceGraph, device) -> DeviceGraph:
    """Device-local copy of every DeviceGraph array (committed to
    ``device``), for a cluster-enumeration replica."""
    import jax

    return dataclasses.replace(dg, **{f: jax.device_put(getattr(dg, f),
                                                        device)
                                      for f in _DG_ARRAYS})


# ----------------------------------------------------------------------
# cluster placement (the data-parallel enumeration layer)
# ----------------------------------------------------------------------
def query_ball_cost(index, qi: int, dists: tuple) -> float:
    """Estimated enumeration cost of one query:
    ``k × (|ball_a(s)| + |ball_b(t)|)``, where the balls count vertices
    within the midpoint-split hop budgets of each endpoint — a
    frontier-size estimate read straight from the index distance
    matrices (``dists`` = host ``(dist_s, dist_t)``, sentinel row
    included; sliced off here). The shared per-query term of both LPT
    placement (:func:`cluster_costs`) and GREEN/YELLOW/RED routing
    (:class:`repro.core.planner.CostRouter`). Deliberately cheap:
    callers need relative weight, not the exact DP bound.
    """
    ds, dt = dists
    _, _, k = index.queries[qi]
    a, b = midpoint_split(k)
    ball = int((ds[:-1, index.src_col[qi]] <= a).sum()) \
        + int((dt[:-1, index.tgt_col[qi]] <= b).sum())
    return float(k) * float(ball)


def cluster_costs(index, clusters: Sequence[Sequence[int]],
                  dists: Optional[tuple] = None) -> list[float]:
    """Estimated enumeration cost per cluster:
    ``cost(C) = Σ_{q ∈ C} query_ball_cost(q)``.

    ``dists`` is the engine's host memo ``(dist_s, dist_t)``; pass it on
    every hot-path call — the ``dists is None`` fallback transfers both
    matrices device→host each time, which the
    ``host_dist_transfers_total`` counter makes visible (the streaming
    loop gates on it staying flat).
    """
    if dists is None:
        obsmetrics.registry().counter("host_dist_transfers_total",
                                      site="cluster_costs").inc()
        dists = (np.asarray(index.dist_s), np.asarray(index.dist_t))
    return [sum(query_ball_cost(index, qi, dists) for qi in cl)
            for cl in clusters]


def plan_clusters(costs: Sequence[float],
                  n_replicas: int) -> tuple[list[list[int]], list[float]]:
    """Greedy cost-balanced (LPT) assignment of clusters to replicas.

    Heaviest cluster first onto the least-loaded replica — the classic
    4/3-approximate makespan heuristic, matching the work-stealing
    scheduler's submit order. Returns ``(assignment, loads)`` where
    ``assignment[r]`` lists cluster indices (ascending, so execution
    order within a replica is deterministic) and ``loads[r]`` the summed
    cost. Handles every uneven shape: more clusters than replicas (some
    replicas take several), fewer (trailing replicas stay empty), zero
    clusters (all empty). Load ties break on assignment *count* (then
    replica id) rather than always replica 0, so zero-cost clusters
    spread round-robin instead of serializing on one replica.
    """
    n_replicas = max(int(n_replicas), 1)
    order = sorted(range(len(costs)), key=lambda i: (-costs[i], i))
    assign: list[list[int]] = [[] for _ in range(n_replicas)]
    loads = [0.0] * n_replicas
    for ci in order:
        r = min(range(n_replicas),
                key=lambda i: (loads[i], len(assign[i]), i))
        assign[r].append(ci)
        loads[r] += costs[ci]
    for a in assign:
        a.sort()
    return assign, loads


# ----------------------------------------------------------------------
# the executor: one code path for 1..D devices
# ----------------------------------------------------------------------
class ShardedExecutor:
    """Plan → place → gather for one engine.

    Owns (a) the GSPMD-sharded edge view the index kernels sweep
    (``index_dg``) and (b) the per-device engine replicas that enumerate
    clusters. Built by ``BatchPathEngine.__init__`` for *every* engine:
    with no mesh (or a 1-device mesh) ``index_dg is engine.dg``, the only
    replica is the engine itself, and :meth:`run_clusters` is the plain
    sequential loop — sharded and single-device execution share this one
    code path.
    """

    def __init__(self, engine, mesh=None, axes=None):
        self.engine = engine
        self.mesh = mesh
        self.axes = None if mesh is None else \
            (tuple(axes) if axes is not None else tuple(mesh.axis_names))
        if mesh is None:
            self.devices = [None]        # None = the default device
        else:
            self.devices = list(np.asarray(mesh.devices).ravel())
        self._replicas: Optional[list] = None
        self.in_fanout = False       # True while replica threads run —
        # replica 0 (the engine) must then plan on local, not mesh, views
        self.index_dg: DeviceGraph = engine.dg
        self.refresh_index_graph()

    # -- topology ------------------------------------------------------
    @property
    def n_replicas(self) -> int:
        return len(self.devices)

    @property
    def sharded(self) -> bool:
        return self.n_replicas > 1

    # -- graph lifecycle ----------------------------------------------
    def refresh_index_graph(self) -> None:
        """(Re)shard the engine's edge lists for the GSPMD index kernels.
        Identity without a mesh. Called after every graph mutation; the
        sharded copy keeps the engine's (monotone) edge bucket, so
        in-bucket churn re-lands in the same traced shapes."""
        if self.mesh is None:
            self.index_dg = self.engine.dg
        else:
            self.index_dg = shard_graph_edges(self.engine.dg, self.mesh,
                                              self.axes)

    def reset(self) -> None:
        """Wholesale graph swap: drop replicas (they rebuild lazily from
        the new graph) and reshard the index view."""
        self._replicas = None
        self.refresh_index_graph()

    def propagate_delta(self, applied) -> None:
        """Patch every existing replica's device views for one merged
        delta (same ``update_device_graph`` semantics as the primary) and
        reshard the index view. Replica caches are NOT touched here —
        ``BatchPathEngine._invalidate_for`` invalidates all caches with
        one shared distance sweep *before* any device view changes, which
        is what keeps the epochs identical across replicas."""
        import jax
        from .delta import update_device_graph

        if self._replicas is not None:
            for rep, dev in zip(self._replicas[1:], self.devices[1:]):
                with jax.default_device(dev):
                    new_dg, _ = update_device_graph(rep.dg, applied)
                rep.dg = replicate_graph(new_dg, dev)
                rep.g = applied.graph
                rep._host_dists = None
        self.refresh_index_graph()

    # -- replicas ------------------------------------------------------
    def replica_caches(self) -> list:
        """The caches of every *materialized* secondary replica (lazily
        created replicas sync their epoch at birth instead)."""
        if self._replicas is None:
            return []
        return [r.cache for r in self._replicas[1:] if r.cache is not None]

    def replicas(self) -> list:
        """All replicas, replica 0 being the engine itself; secondaries
        are created on first use (one device-local DeviceGraph copy and a
        fresh, epoch-synced SharedPathCache each)."""
        if self._replicas is None:
            self._replicas = [self.engine]
            for dev in self.devices[1:]:
                self._replicas.append(self._clone(dev))
        return self._replicas

    def _clone(self, device):
        import copy
        from .cache import SharedPathCache

        eng = self.engine
        rep = copy.copy(eng)
        rep.executor = None          # replicas are leaves: never re-fan-out
        rep.dg = replicate_graph(eng.dg, device)
        rep._host_dists = None
        rep.cache = None
        if eng.cache is not None:
            rep.cache = SharedPathCache(eng.cache.budget_bytes)
            rep.cache.epoch = eng.cache.epoch   # lockstep from birth
        return rep

    # -- execution -----------------------------------------------------
    def run_clusters(self, queries, index, plus: bool, min_sb: int,
                     clusters: list[list[int]], stats: dict,
                     planners: Optional[Sequence[str]] = None) -> dict:
        """Execute every sharing cluster, gathering ``{qi: QueryResult}``.

        One replica (or a single cluster): the inline sequential loop —
        byte-for-byte the single-device engine. Several: clusters are
        cost-balanced onto replicas and executed by one pinned worker
        thread per replica; per-replica stats land in
        ``stats["per_device"]``. ``planners`` (one ``"batch"``/``"basic"``
        entry per cluster, from the cost router) picks the per-cluster
        plan — ``"basic"`` runs the direct per-query path with no Ψ
        detection; ``None`` means batch everywhere. Results are exact
        either way, so the gather is a plain dict merge.
        """
        eng = self.engine

        def cluster_fn(engine, ci: int):
            if planners is not None and planners[ci] == "basic":
                return engine._cluster_basic
            return engine._cluster_work

        if not self.sharded or len(clusters) <= 1:
            results: dict = {}
            for ci, cluster in enumerate(clusters):
                out, cstats = cluster_fn(eng, ci)(queries, index, plus,
                                                  min_sb, cluster)
                results.update(out)
                _merge_stats(stats, cstats)
            return results

        reps = self.replicas()
        dists = eng._dists_host(index)
        costs = cluster_costs(index, clusters, dists=dists)
        assign, loads = plan_clusters(costs, len(reps))

        outs: list[dict] = [{} for _ in reps]
        cstats_all: list[list[dict]] = [[] for _ in reps]
        walls = [0.0] * len(reps)
        errs: list = [None] * len(reps)

        def work(ri: int) -> None:
            import jax

            rep, dev = reps[ri], self.devices[ri]
            try:
                # replica spans are roots of their worker thread's stack
                # (thread-local nesting); the recorded trace shows each
                # replica's clusters on its own timeline row
                with eng.obs.span("replica.run", replica=ri,
                                  device=str(dev),
                                  n_clusters=len(assign[ri])) as sr:
                    # the fan-out path implies a real mesh, so dev is
                    # always a concrete device (the no-mesh executor
                    # never fans out)
                    with jax.default_device(dev):
                        # the replica's slack vectors come from its own
                        # copy of the distance tables
                        local = index.on_device(dev)
                        for ci in assign[ri]:
                            out, cst = cluster_fn(rep, ci)(
                                queries, local, plus, min_sb, clusters[ci])
                            outs[ri].update(out)
                            cstats_all[ri].append(cst)
                walls[ri] = sr.duration
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errs[ri] = e

        # one worker per replica, but never more RUNNING than the host
        # has cores: on real accelerators each replica owns its compute,
        # while on virtual (forced host) devices every replica shares the
        # same cores and oversubscription only adds contention — a
        # core-capped pool drains the replica queue at full tilt either
        # way (device pinning is per work item, not per pool thread)
        workers = max(1, min(len(reps), os.cpu_count() or 1))
        self.in_fanout = True
        try:
            with ThreadPoolExecutor(max_workers=workers,
                                    thread_name_prefix="hcsp-replica") as px:
                list(px.map(work, range(len(reps))))
        finally:
            self.in_fanout = False
        for e in errs:
            if e is not None:
                raise e

        results = {}
        for ri in range(len(reps)):
            results.update(outs[ri])
            for cst in cstats_all[ri]:
                _merge_stats(stats, cst)
        stats["n_devices"] = len(reps)
        stats["per_device"] = [
            {"device": str(self.devices[ri]),
             "n_clusters": len(assign[ri]),
             "n_queries": sum(len(clusters[ci]) for ci in assign[ri]),
             "cost": loads[ri],
             "t_wall_s": walls[ri],
             "cache_hits": sum(c.get("n_cache_hits", 0)
                               for c in cstats_all[ri])}
            for ri in range(len(reps))]
        return results


def _merge_stats(stats: dict, cstats: dict) -> None:
    """Accumulate one cluster's counters/timings into the run stats."""
    for key, val in cstats.items():
        stats[key] = stats.get(key, 0) + val
