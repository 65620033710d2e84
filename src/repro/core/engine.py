"""BatchPathEngine: BasicEnum (Alg 1), BatchEnum (Alg 4) and the "+" variants.

The engine is the device-side executor: the host planner (clustering +
detection) emits per-cluster DirectionPlans; this module materializes HC-s
path queries level by level (expand supersteps + splice joins), caches them
(the paper's R), and assembles per-query HC-s-t results with the exact-split
⊕ join. Every stage is static-shape jit with overflow-retry doubling.

Entry point is :meth:`BatchPathEngine.run`, which takes typed
:class:`~repro.core.query.PathQuery` objects (legacy ``(s, t, k)`` tuples
are coerced) and returns a :class:`~repro.core.query.BatchReport` of
:class:`~repro.core.query.QueryResult` objects. Per-query ``output`` kinds
are threaded all the way down: count-only and exists-only queries never
assemble path rows (counting ⊕ joins, mask reductions) and early-terminate,
as do ``limit``-capped queries. The legacy ``process(queries, mode=...)``
API survives as a thin deprecation shim.
"""
from __future__ import annotations

import dataclasses
import warnings
import weakref
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compilelog, distributed
from ..obs import metrics as obsmetrics
from ..obs import trace as obstrace
from .cache import SharedPathCache
from .delta import (AppliedDelta, GraphDelta, apply_delta as _merge_delta,
                    host_set_dist, pow2_ceil as _pow2, update_device_graph)
from .graph import DeviceGraph, Graph
from .index import (QueryIndex, build_index, column_reach, slack_vector,
                    walk_counts)
from .msbfs import (K_MAX_INT8, count_sweep, edge_span, msbfs_set_dist,
                    msbfs_set_dist_ell)
from ..kernels.registry import resolve_backend
from .pathset import PathSet, concat, empty, singleton
from .enumerate import (count_ending_at, expand_level, extract_rows,
                        select_ending_at, splice_hits, splice_table)
from .join import cross_join, keyed_join, keyed_join_count, sort_by_last
from .planner import CostRouter, Route, RouterConfig
from .query import (BatchReport, Output, PathQuery, PathsStore, Planner,
                    QueryLike, QueryResult, midpoint_split)
from .similarity import similarity_matrix
from .clustering import cluster_queries
from .detect import DirectionPlan, PlanNode, detect_common_queries

__all__ = ["EngineConfig", "BatchPathEngine", "EngineOverflow", "BatchResult"]

Query = tuple[int, int, int]

# backward levels are produced lazily: basic planners skip the whole
# backward enumeration when a forward level already answers exists-only
Levels = Callable[[], list]


class EngineOverflow(RuntimeError):
    """A query exceeded hard capacity limits (the paper's OT analogue)."""


@dataclasses.dataclass
class EngineConfig:
    gamma: float = 0.5              # clustering threshold (paper default)
    backend: Optional[str] = None   # DEPRECATED alias of kernel_backend
    # (kept one release for old callers; setting it warns at engine init)
    kernel_backend: Optional[str] = None  # "pallas" | "interpret" | "jnp";
    # None resolves via kernels.registry (REPRO_KERNEL_BACKEND env, else
    # auto: pallas on TPU, jnp elsewhere). Unknown names raise ValueError
    # at engine construction.
    min_cap: int = 256
    max_cap: int = 1 << 20          # planned per-level frontier cap clamp
    hard_cap: int = 1 << 22         # absolute limit before EngineOverflow
    join_cap: int = 1 << 21
    min_shared_budget: int = 2      # don't materialize trivially small shares
    plus: bool = False              # cost-based fwd/bwd split (the "+" variants)
    edge_chunk: int = 1 << 22
    plan_caps: bool = True          # DP-based capacity planning
    paper_faithful_shares: bool = False  # min_shared_budget -> 0
    cache_bytes: int = 0            # >0: cross-batch SharedPathCache budget
    delta_max_sources: int = 1024   # touched-frontier cap for hop-scoped
    # invalidation; bigger deltas fall back to a full cache invalidate
    delta_backend: str = "host"     # "host": vectorized CSR BFS over the
    # touched balls (cost ~ ball edges); "msbfs": device set-seeded MS-BFS
    # (for accelerator-resident graphs where m is device-scale)
    log_compiles: bool = False      # compile telemetry: per-kernel retrace
    # counts in run()/apply_delta() stats (core.compilelog recorder)
    mesh: Optional[object] = None   # jax.sharding.Mesh to shard/place on;
    # None + n_devices -> a 1-D "cells" mesh over the first N local devices
    n_devices: Optional[int] = None  # mesh size knob (1 = identity mesh;
    # None/0 = plain single-device). See core.distributed.
    balance_clusters: bool = False  # sharded runs stop cluster merging at
    # n_replicas clusters so the mesh never idles on an over-merged batch
    # (changes the clustering, hence result row order — off by default so
    # sharded == single-device stays bit-identical)
    trace: bool = False             # record hierarchical stage spans into
    # the process-wide repro.obs tracer (Chrome-trace exportable); off =
    # spans still time the t_* stats but nothing is recorded
    trace_fence: bool = False       # block_until_ready fenced device values
    # at span exit so async device work is attributed to the launching
    # span (costs dispatch overlap; measurement mode only)
    trace_annotations: bool = False  # wrap spans in jax.profiler
    # TraceAnnotation so they appear on profiler device timelines
    router: Optional[RouterConfig] = None  # Planner.AUTO routing thresholds
    # and output-kind weights (None = planner.RouterConfig defaults)


@dataclasses.dataclass
class BatchResult:
    """Legacy aggregate (eager host matrices); produced only by the
    deprecated :meth:`BatchPathEngine.process` shim. New code gets a
    :class:`~repro.core.query.BatchReport` from :meth:`BatchPathEngine.run`.
    """

    paths: dict[int, np.ndarray]    # query idx -> (n_paths, k+1) int32 (pad -1)
    stats: dict


def _sync_device_graph(dg: DeviceGraph) -> None:
    """Block until every device view is resident. apply_delta calls this
    before stopping its timer so the reported ``t_apply_s`` charges the
    async uploads/scatters to the mutation, not to the next batch;
    set_graph deliberately does NOT sync (no report to keep honest —
    benchmarks comparing against it must block explicitly)."""
    import jax

    jax.block_until_ready((dg.esrc, dg.edst, dg.ell_idx, dg.ell_mask,
                           dg.r_esrc, dg.r_edst, dg.r_ell_idx, dg.r_ell_mask,
                           dg.ell_sliced, dg.r_ell_sliced))


def _bucket(x: int, min_cap: int = 256) -> int:
    """Quantize capacities to powers of four (fewer jit shape buckets)."""
    b = min_cap
    while b < x:
        b *= 4
    return b


class BatchPathEngine:
    def __init__(self, graph: Graph, config: Optional[EngineConfig] = None,
                 cache: Optional[SharedPathCache] = None):
        self.g = graph
        self.cfg = config or EngineConfig()
        kb = self.cfg.kernel_backend
        if self.cfg.backend is not None:
            warnings.warn(
                "EngineConfig.backend is deprecated; use "
                "EngineConfig.kernel_backend", DeprecationWarning,
                stacklevel=2)
            if kb is None:
                kb = self.cfg.backend
        # resolve once at construction: explicit > REPRO_KERNEL_BACKEND env
        # > auto (pallas on TPU, jnp elsewhere); typos raise here, not as a
        # silently different code path mid-batch
        self.kernel_backend = resolve_backend(kb)
        # plain string for jit static args (clean cache keys, no enum repr)
        self._kb = self.kernel_backend.value
        mesh = distributed.resolve_mesh(self.cfg.mesh, self.cfg.n_devices)
        if mesh is None:
            self.dg = DeviceGraph.build(graph)
        else:
            # device-count-aligned edge bucket: sharded and single-device
            # shapes coincide for pow2 device counts, so both stay warm
            n_dev = int(np.prod(list(mesh.shape.values())))
            self.dg = DeviceGraph.build(
                graph, edge_cap=distributed.edge_bucket_for(graph.m, n_dev))
        self._host_dists: Optional[tuple] = None   # (weakref(dist_s), dists)
        # plan -> place -> gather layer; identity on a single device (the
        # executor IS the cluster-execution loop for every engine)
        self.executor: Optional[distributed.ShardedExecutor] = \
            distributed.ShardedExecutor(self, mesh)
        if cache is None and self.cfg.cache_bytes > 0:
            cache = SharedPathCache(self.cfg.cache_bytes)
        self.cache = cache
        # Planner.AUTO tier routing + per-cluster planner choice
        self.router = CostRouter(self.cfg.router)
        # process-wide recorder (jit caches are process-global); None when
        # telemetry is off — every run()/apply_delta() report then carries
        # n_compiles / n_retraces / compiled_kernels for its window
        self.compile_log = compilelog.enable() if self.cfg.log_compiles \
            else None
        # stage spans: like the jit cache and compile log, the recorder is
        # process-wide — any engine with cfg.trace turns recording on; the
        # handle itself is always present because every t_* stat below is
        # a derived view over a span's duration (recorded or not)
        self.obs = obstrace.enable(
            fence=self.cfg.trace_fence,
            annotate=self.cfg.trace_annotations) if self.cfg.trace \
            else obstrace.tracer()
        # search-node counters, bound once: the hot path pays one add each
        reg = obsmetrics.registry()
        self._n_nodes = reg.counter("engine_nodes_total")
        self._node_syncs = reg.counter("engine_host_syncs_total",
                                       stage="node")
        self._asm_syncs = reg.counter("engine_host_syncs_total",
                                      stage="assemble")
        self._node_retries = reg.counter("engine_retries_total", kind="node")
        self._join_retries = reg.counter("engine_retries_total", kind="join")
        self._fetched: weakref.WeakValueDictionary = \
            weakref.WeakValueDictionary()     # id -> device value read

    def set_graph(self, graph: Graph) -> None:
        """Swap the graph wholesale: rebuild device views and drop every
        piece of graph-derived state (host dists, cross-batch cache). For
        incremental edge churn prefer :meth:`apply_delta`, which keeps the
        warm state whose hop-locality a small delta cannot reach."""
        self.g = graph
        if self.executor is not None and self.executor.mesh is not None:
            n_dev = self.executor.n_replicas
            self.dg = DeviceGraph.build(
                graph, edge_cap=distributed.edge_bucket_for(graph.m, n_dev))
        else:
            self.dg = DeviceGraph.build(graph)
        self._host_dists = None
        # replica caches invalidate BEFORE the replicas are dropped so a
        # swap bumps every epoch in lockstep with the primary
        for cache in self._all_caches():
            cache.invalidate()
        if self.executor is not None:
            self.executor.reset()

    def apply_delta(self, delta: GraphDelta) -> dict:
        """Apply an incremental edge delta; returns an application report.

        The successor graph comes from a CSR merge (``Graph.apply_delta``
        semantics: ``new = (old − remove) ∪ add``), device views are
        patched rather than rebuilt (only touched ELL rows change), and
        the cross-batch cache is invalidated *hop-scoped*: a set-seeded
        BFS from the delta's touched vertices prices each
        entry's distance to the damage, and only entries whose enumeration
        ball or consumer prune radius the damage can reach are evicted
        (``SharedPathCache.invalidate_delta``). A no-op delta (every edge
        already present/absent) leaves all state — including the host
        distance memo — untouched; an effective delta drops only that
        memo, which the next batch's index rebuilds anyway.

        Device views stay in their pow2 shape buckets (sentinel-padded
        edge lists, bucketed ELL capacities), so an in-bucket delta
        triggers no retrace; with ``EngineConfig.log_compiles`` the report
        carries the window's ``n_compiles`` / ``n_retraces``.
        """
        if self.compile_log is None:
            return self._apply_delta_impl(delta)
        snap = self.compile_log.snapshot()
        report = self._apply_delta_impl(delta)
        self.compile_log.annotate(report, snap)
        return report

    def _apply_delta_impl(self, delta: GraphDelta) -> dict:
        with self.obs.span("engine.apply_delta") as sp:
            applied = _merge_delta(self.g, delta)
            sp.set(n_added=int(applied.added_src.size),
                   n_removed=int(applied.removed_src.size))
            report = {
                "n_added": int(applied.added_src.size),
                "n_removed": int(applied.removed_src.size),
                "n_touched": int(applied.touched.size),
                "cache_mode": "none", "device_update": "none",
            }
            if applied.n_changed == 0:
                report["t_apply_s"] = sp.elapsed
                return report
            if self.cache is not None:
                with self.obs.span("cache.invalidate"):
                    report.update(self._invalidate_for(applied))
            self.dg, incremental = update_device_graph(self.dg, applied)
            report["device_update"] = ("incremental" if incremental
                                       else "rebuild")
            self.g = applied.graph
            self._host_dists = None
            if self.executor is not None:
                # replica device views patch in lockstep; their caches were
                # already invalidated above with the same distance sweep
                self.executor.propagate_delta(applied)
            _sync_device_graph(self.dg)   # timer measures completed work
            report["t_apply_s"] = sp.elapsed
        return report

    def _all_caches(self) -> list[SharedPathCache]:
        """Primary cache + every materialized replica's cache. All of
        them receive each invalidation event (same dists, same order), so
        their epochs advance in lockstep; replicas created later sync the
        epoch at birth (see ``distributed.ShardedExecutor._clone``)."""
        caches = [] if self.cache is None else [self.cache]
        if self.executor is not None:
            caches += self.executor.replica_caches()
        return caches

    def _invalidate_for(self, applied: AppliedDelta) -> dict:
        """Cache invalidation for one merged delta (primary cache must
        exist; replica caches, when materialized, invalidate identically)."""
        caches = self._all_caches()
        if all(len(c) == 0 for c in caches):
            empty = {"to": np.empty(0, np.int8),
                     "from": np.empty(0, np.int8)}
            info = {}
            for c in caches:
                info = c.invalidate_delta(applied.touched, empty)
            return {"cache_mode": "delta", "cache_evicted": 0,
                    "cache_kept": 0, "cache_epoch": info["epoch"],
                    "cache_epochs": [c.epoch for c in caches]}
        if applied.touched.size > self.cfg.delta_max_sources:
            dropped = sum(len(c) for c in caches)   # primary + replicas
            for c in caches:
                c.invalidate()   # frontier too wide: hop-scoping won't pay
            return {"cache_mode": "full", "cache_evicted": dropped,
                    "cache_kept": 0, "cache_epoch": self.cache.epoch,
                    "cache_epochs": [c.epoch for c in caches]}
        # one distance sweep prices the damage for every cache: the
        # radius must cover the widest live entry anywhere in the fleet
        k_max = max(max(c.max_radius() for c in caches), 1)
        dists = self._delta_dists(applied, k_max)
        info = {}
        for c in caches:
            got = c.invalidate_delta(applied.touched, dists)
            if c is self.cache:
                info = got
        return {"cache_mode": "delta", "cache_evicted": info["evicted"],
                "cache_kept": info["kept"], "cache_epoch": info["epoch"],
                "cache_epochs": [c.epoch for c in caches]}

    def _delta_dists(self, applied: AppliedDelta, k_max: int) -> dict:
        """Min hop distances to/from the touched frontier.

        Both endpoints of every changed edge are seeds, so these distances
        agree on the old, new, and union graphs (see ``host_set_dist``) —
        the sweep runs on the *old* graph, which for the "msbfs" backend
        means the still-resident old device edge lists (``self.dg`` is
        patched only after invalidation), no transfer or merge needed.
        Backend "host" (default) walks only the touched balls' edges over
        the CSR; "msbfs" is for accelerator-resident graphs. ``k_max`` is
        the widest live radius across every cache (primary + replicas).
        """
        if self.cfg.delta_backend == "host":
            return {"from": host_set_dist(self.g, applied, k_max,
                                          reverse=False),
                    "to": host_set_dist(self.g, applied, k_max,
                                        reverse=True)}
        # distances beyond every live radius are never compared, so the
        # pow2-bucketed (larger) k_max is just slack — stable jit shapes
        # across deltas. Clamping the *bucket* to the int8 sweeps' static
        # ceiling is sound only while the live radius itself fits; a
        # radius beyond K_MAX_INT8 would silently lose distances, so it
        # raises here (the sweeps' _check_k_max guard backstops this).
        if k_max > K_MAX_INT8:
            raise ValueError(
                f"live cache radius k_max={k_max} exceeds the int8 MS-BFS "
                f"ceiling K_MAX_INT8={K_MAX_INT8}; shrink the hop budgets "
                f"or drop delta_backend='msbfs'")
        k_max = min(_pow2(k_max), K_MAX_INT8)
        seed = np.zeros(self.g.n + 1, np.int8)
        seed[applied.touched] = 1
        seed = jnp.asarray(seed)

        # the still-resident old edge lists are already sentinel-padded to
        # their pow2 bucket (DeviceGraph.build / update_device_graph), so
        # the sweep's traced shape is stable across deltas by construction.
        # _kernel_dg: on a sharded engine this sweep runs GSPMD over the
        # mesh (the index view re-shards only after the patch).
        kdg = self._kernel_dg()
        dists = {}
        if self.kernel_backend.uses_kernel:
            # fused bit-packed sweep: "from" distances relax over G's
            # in-neighbors (r_ell), "to" over G_r's (ell) — bit-equal to
            # the segment path below
            for name, reverse in (("from", True), ("to", False)):
                table = kdg.sweep_table(reverse)
                d = msbfs_set_dist_ell(table, seed, n=self.g.n, k_max=k_max,
                                       backend=self._kb)
                count_sweep(table, self.g.n, kdg.m, k_max)
                dists[name] = np.asarray(d)
            return dists
        m_valid = edge_span(kdg.m, self.cfg.edge_chunk, kdg.m_cap)
        for name, (esrc, edst) in (("from", (kdg.esrc, kdg.edst)),
                                   ("to", (kdg.r_esrc, kdg.r_edst))):
            d = msbfs_set_dist(esrc, edst, seed, n=self.g.n,
                               k_max=k_max, edge_chunk=self.cfg.edge_chunk,
                               m_valid=m_valid)
            dists[name] = np.asarray(d)
        return dists

    def _dists_host(self, index: QueryIndex):
        # memoized per distance TABLE (subsets of one index share it),
        # held by a weak reference: a freed table's id can never serve
        # stale distances, and the memo does not keep the device tables
        # alive after their batch. Transposed on the device and viewed
        # back on the host, so each (n+1, S) matrix is column-major: one
        # query's column is a contiguous read, not n+1 reads S bytes apart.
        if (self._host_dists is None
                or self._host_dists[0]() is not index.dist_s):
            self._host_dists = (weakref.ref(index.dist_s),
                                (np.asarray(index.dist_s.T).T,
                                 np.asarray(index.dist_t.T).T))
        return self._host_dists[1]

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def run(self, queries: Sequence[QueryLike],
            planner: Planner | str = Planner.BATCH,
            clusters: Optional[list[list[int]]] = None,
            index: Optional[QueryIndex] = None) -> BatchReport:
        """Execute a batch of :class:`PathQuery` (tuples are coerced).

        planner : execution strategy (:class:`Planner` or its string value).
        clusters : optional precomputed partition of query indices (batch
        planners only). The caller — e.g. the streaming server, which
        clusters with a cache-aware bias — keeps its grouping instead of
        this method re-running similarity + clustering over the same
        queries.
        index : optional prebuilt :class:`~repro.core.index.QueryIndex`
        over exactly these queries (the streaming server's, already
        built for its similarity); None builds one. Ignored by PATHENUM,
        which indexes each query alone.

        The report stats count this run's search nodes (``n_nodes``),
        their device→host reads (``n_node_syncs``), those of answer
        assembly (``n_assemble_syncs``) and the overflow re-runs of nodes
        and joins (``n_retries``).

        With ``EngineConfig.log_compiles`` the report stats carry this
        run's compile-telemetry window: ``n_compiles`` (trace-cache
        misses), ``n_retraces`` (misses on kernels that were already warm
        — zero on a shape-stable serving path) and ``compiled_kernels``.
        """
        before = self._search_counts()
        if self.compile_log is None:
            report = self._run_impl(queries, planner, clusters, index)
        else:
            snap = self.compile_log.snapshot()
            report = self._run_impl(queries, planner, clusters, index)
            self.compile_log.annotate(report.stats, snap)
        report.stats.update(
            (key, int(b - a)) for key, a, b in zip(
                ("n_nodes", "n_node_syncs", "n_assemble_syncs", "n_retries"),
                before, self._search_counts()))
        return report

    def _host(self, x, syncs: obsmetrics.Counter) -> np.ndarray:
        """``x`` on the host. The first read of a device value is a
        device→host round-trip, counted on ``syncs``; JAX keeps the host
        copy it fetched, so reading the value again costs none."""
        if isinstance(x, jax.Array) and id(x) not in self._fetched:
            self._fetched[id(x)] = x
            syncs.inc()
        return np.asarray(x)

    def _search_counts(self) -> tuple:
        return (self._n_nodes.value, self._node_syncs.value,
                self._asm_syncs.value,
                self._node_retries.value + self._join_retries.value)

    def _run_impl(self, queries: Sequence[QueryLike],
                  planner: Planner | str,
                  clusters: Optional[list[list[int]]],
                  index: Optional[QueryIndex]) -> BatchReport:
        qs = tuple(PathQuery.coerce(q).check_bounds(self.g.n)
                   for q in queries)
        planner = Planner.coerce(planner)
        plus = planner.plus or self.cfg.plus
        stats: dict = {"planner": planner.value, "mode": planner.value,
                       "kernel_backend": self._kb,
                       "n_queries": len(qs), "n_rows_assembled": 0}
        if not qs:   # degenerate but legal (e.g. a filter left nothing)
            stats["t_build_index"] = stats["t_enumerate"] = 0.0
            return BatchReport(queries=qs, results=(), stats=stats)
        with self.obs.span("engine.run", planner=planner.value,
                           n_queries=len(qs)) as root:
            if planner is Planner.PATHENUM:
                report = self._run_pathenum(qs, stats)
            else:
                keys = tuple(q.key for q in qs)
                stats["t_build_index"] = 0.0
                if index is None:
                    with self.obs.span("index.build",
                                       n_queries=len(qs)) as sidx:
                        index = build_index(self._kernel_dg(), keys,
                                            self.cfg.edge_chunk,
                                            backend=self._kb)
                        index.dist_s.block_until_ready()
                    stats["t_build_index"] = sidx.duration
                elif index.queries != keys:
                    raise ValueError("index was built for other queries")
                if planner is Planner.AUTO:
                    report = self._run_auto(qs, index, plus, stats,
                                            clusters)
                elif planner.batched:
                    report = self._run_batch(qs, index, plus, stats,
                                             clusters)
                else:
                    report = self._run_basic(qs, index, plus, stats)
        stats["t_wall_s"] = root.duration
        reg = obsmetrics.registry()
        reg.histogram("engine_batch_wall_s", planner=planner.value,
                      backend=self._kb).record(root.duration)
        lat = reg.histogram("query_latency_s", planner=planner.value,
                            backend=self._kb)
        for r in report.results:
            if r.time_s is not None:
                lat.record(r.time_s)
        return report

    def process(self, queries: Sequence[Query], mode: str = "batch",
                clusters: Optional[list[list[int]]] = None) -> BatchResult:
        """Deprecated tuple-in / dict-out API; thin shim over :meth:`run`."""
        warnings.warn(
            "BatchPathEngine.process(queries, mode=...) is deprecated; use "
            "run(queries, planner=...) or the PathSession facade",
            DeprecationWarning, stacklevel=2)
        report = self.run(queries, planner=mode, clusters=clusters)
        return BatchResult(paths=report.paths, stats=report.stats)

    # ------------------------------------------------------------------
    # BasicEnum (Alg 1): shared index, per-query bidirectional enumeration
    # ------------------------------------------------------------------
    def _direct_query(self, q: PathQuery, qi: int, index: QueryIndex,
                      plus: bool, stats: dict) -> QueryResult:
        """One query through the Alg-1 direct plan: bidirectional
        enumeration off the shared index, backward half lazy. Shared by
        the basic planners, AUTO's GREEN tier and basic-routed clusters."""
        a, b = self._split(qi, index, plus)
        fs = self._dedicated_slack(index, qi, forward=True)
        fl = self._run_node(False, q.s, a, fs, [], stop_vertex=q.t)

        def bwd(qi=qi, q=q, b=b):
            bs = self._dedicated_slack(index, qi, forward=False)
            return self._run_node(True, q.t, b, bs, [], stop_vertex=q.s)

        return self._wrap(q, self._payload(q, fl, a, bwd, b, stats))

    def _run_basic(self, queries, index: QueryIndex, plus: bool,
                   stats) -> BatchReport:
        with self.obs.span("enumerate.batch",
                           n_queries=len(queries)) as senum:
            results = []
            for qi, q in enumerate(queries):
                with self.obs.span("assemble.query", qi=qi) as sq:
                    r = self._direct_query(q, qi, index, plus, stats)
                r.time_s = sq.duration
                results.append(r)
        stats["t_enumerate"] = senum.duration
        return BatchReport(queries=tuple(queries), results=tuple(results),
                           stats=stats)

    def _cluster_basic(self, queries, index: QueryIndex, plus: bool,
                       min_sb: int, cluster: list[int]):
        """Direct per-query plan for one routed cluster — the executor's
        ``planners=["basic", ...]`` arm (see ``CostRouter.cluster_planner``).
        Same ``({qi: QueryResult}, cstats)`` contract as
        :meth:`_cluster_work`, but no Ψ detection, no sharing, no cache:
        a cluster with nothing to share skips that machinery's overhead.
        """
        del min_sb   # no shares to budget on the direct plan
        cstats = {"n_psi_nodes": 0, "n_materialized": 0,
                  "n_cache_hits": 0, "n_cache_misses": 0,
                  "n_rows_assembled": 0, "n_shared": 0, "n_dedup": 0,
                  "n_share_edges": 0, "t_detect": 0.0}
        with self.obs.span("enumerate.cluster", size=len(cluster),
                           direct=True) as se:
            results: dict[int, QueryResult] = {}
            for qi in cluster:
                q = queries[qi]
                with self.obs.span("assemble.query", qi=qi) as sq:
                    results[qi] = self._direct_query(q, qi, index, plus,
                                                     cstats)
                results[qi].time_s = sq.duration
        cstats["t_enumerate"] = se.duration
        return results, cstats

    def _run_pathenum(self, queries, stats) -> BatchReport:
        """Per-query index construction + enumeration (the PathEnum baseline)."""
        results = []
        t_idx = t_enum = 0.0
        for q in queries:
            with self.obs.span("index.build", pathenum=True) as sidx:
                index = build_index(self._kernel_dg(), [q.key],
                                    self.cfg.edge_chunk, backend=self._kb)
                index.dist_s.block_until_ready()
            t_idx += sidx.duration
            with self.obs.span("assemble.query") as sq:
                a, b = self._split(0, index, False)
                fs = self._dedicated_slack(index, 0, forward=True)
                fl = self._run_node(False, q.s, a, fs, [], stop_vertex=q.t)

                def bwd(q=q, b=b, index=index):
                    bs = self._dedicated_slack(index, 0, forward=False)
                    return self._run_node(True, q.t, b, bs, [],
                                          stop_vertex=q.s)

                r = self._wrap(q, self._payload(q, fl, a, bwd, b, stats))
            t_enum += sq.duration
            r.time_s = sidx.duration + sq.duration
            results.append(r)
        stats["t_build_index"] = t_idx
        stats["t_enumerate"] = t_enum
        return BatchReport(queries=tuple(queries), results=tuple(results),
                           stats=stats)

    # ------------------------------------------------------------------
    # BatchEnum (Alg 4): cluster -> detect -> shared enumeration
    # ------------------------------------------------------------------
    def _run_batch(self, queries, index: QueryIndex, plus: bool, stats,
                   clusters: Optional[list[list[int]]] = None) -> BatchReport:
        results = self._run_clustered(queries, index, plus, stats, clusters)
        return BatchReport(queries=tuple(queries),
                           results=tuple(results[qi]
                                         for qi in range(len(queries))),
                           stats=stats)

    def _run_clustered(self, queries, index: QueryIndex, plus: bool, stats,
                       clusters: Optional[list[list[int]]] = None, *,
                       subset: Optional[list[int]] = None,
                       ests: Optional[dict] = None,
                       routes: Optional[dict] = None) -> dict:
        """Cluster → (route) → execute; returns ``{qi: QueryResult}``.

        The shared body of the batch planners and the AUTO YELLOW/RED
        tier. ``subset`` restricts clustering to those query indices
        (AUTO runs it on the non-GREEN remainder; similarity rows are
        sliced, cluster members stay *global* indices). With ``ests``
        (qi → :class:`~repro.core.planner.CostEstimate`) the router picks
        each cluster's planner (basic vs. batch) and tier — RED clusters
        keep LPT placement priority implicitly through their summed cost;
        ``routes`` entries are upgraded in place for RED members.
        """
        qis = list(range(len(queries))) if subset is None else list(subset)
        with self.obs.span("cluster.queries",
                           precomputed=clusters is not None) as sc:
            if clusters is None:
                mu = similarity_matrix(index, backend=self._kb)
                if subset is None:
                    stats["mu_mean"] = float(
                        (mu.sum() - len(queries)) /
                        max(len(queries) * (len(queries) - 1), 1))
                else:
                    mu = mu[np.ix_(qis, qis)]
                min_clusters = 1
                if self.cfg.balance_clusters and self.executor is not None:
                    min_clusters = self.executor.n_replicas
                local = cluster_queries(mu, self.cfg.gamma,
                                        min_clusters=min_clusters)
                clusters = [[qis[i] for i in cl] for cl in local]
            else:
                seen = [qi for cl in clusters for qi in cl]
                if sorted(seen) != sorted(qis):
                    raise ValueError(
                        "clusters must partition the query indices")
            sc.set(n_clusters=len(clusters))
        stats["t_cluster"] = sc.duration
        stats["n_clusters"] = len(clusters)

        min_sb = 0 if self.cfg.paper_faithful_shares else self.cfg.min_shared_budget
        for key in ("n_psi_nodes", "n_materialized",
                    "n_cache_hits", "n_cache_misses",
                    "t_detect", "t_enumerate",
                    "n_shared", "n_dedup", "n_share_edges"):
            stats.setdefault(key, 0)

        planners = None
        if ests is not None:
            sharded = self.executor is not None and self.executor.sharded
            planners = [self.router.cluster_planner(cl, ests,
                                                    self.cache is not None)
                        for cl in clusters]
            stats["cluster_planners"] = list(planners)
            croutes = [self.router.cluster_route(cl, ests, sharded)
                       for cl in clusters]
            stats["cluster_routes"] = [r.value for r in croutes]
            if routes is not None:
                for cl, r in zip(clusters, croutes):
                    if r is Route.RED:
                        for qi in cl:
                            routes[qi] = Route.RED
        # plan -> place -> gather: the executor runs every cluster —
        # inline here on one device, fanned across per-device replicas on
        # a mesh (distributed.ShardedExecutor.run_clusters)
        return self.executor.run_clusters(queries, index, plus, min_sb,
                                          clusters, stats, planners=planners)

    # ------------------------------------------------------------------
    # AUTO: cost-routed GREEN/YELLOW/RED tiers (core.planner)
    # ------------------------------------------------------------------
    def _run_auto(self, queries, index: QueryIndex, plus: bool, stats,
                  clusters: Optional[list[list[int]]] = None) -> BatchReport:
        """Route each query by its index-derived cost estimate: GREEN
        queries take the direct sweep (no clustering/detection/cache);
        the remainder runs through :meth:`_run_clustered`, which also
        picks each cluster's planner and RED/YELLOW tier. Exactness is
        planner-independent, so routing can only move wall time."""
        with self.obs.span("route.estimate", n_queries=len(queries)) as sr:
            dists = self._dists_host(index)
            ests = self.router.estimate(index, queries, dists)
            routes = {e.qi: e.route for e in ests}
            green = [e.qi for e in ests if e.route is Route.GREEN]
            rest = [e.qi for e in ests if e.route is not Route.GREEN]
            sr.set(n_green=len(green))
        stats["t_route"] = sr.duration

        # AUTO answers may skip whole stages; pre-zero the batch counters
        # so report consumers see one stable schema across routes
        for key in ("n_psi_nodes", "n_materialized",
                    "n_cache_hits", "n_cache_misses",
                    "t_detect", "t_enumerate", "t_cluster",
                    "n_shared", "n_dedup", "n_share_edges"):
            stats[key] = 0
        stats["n_clusters"] = 0

        results: dict[int, QueryResult] = {}
        if green:
            results.update(self._run_green(queries, index, plus, green,
                                           stats))
        if rest:
            if clusters is not None:
                # the caller's grouping covered every query; keep only the
                # non-GREEN members (GREEN ones were just answered)
                keep = set(rest)
                clusters = [[qi for qi in cl if qi in keep]
                            for cl in clusters]
                clusters = [cl for cl in clusters if cl]
            results.update(self._run_clustered(
                queries, index, plus, stats, clusters,
                subset=rest, ests={e.qi: e for e in ests}, routes=routes))

        reg = obsmetrics.registry()
        for route in Route:
            n = sum(1 for r in routes.values() if r is route)
            stats[f"routed_{route.value}"] = n
            if n:
                reg.counter(f"routed_{route.value}").inc(n)
        return BatchReport(
            queries=tuple(queries),
            results=tuple(results[qi] for qi in range(len(queries))),
            stats=stats,
            routes=tuple(routes[qi].value for qi in range(len(queries))))

    def _run_green(self, queries, index: QueryIndex, plus: bool,
                   green: list[int], stats) -> dict:
        """The GREEN tier: answer routed queries straight off the shared
        index. exists-only and index-unreachable queries are decided by
        the MS-BFS distances alone (``dist_G(s,t) <= k`` iff a ≤k-hop
        simple path exists — shortest walks are simple); the rest run the
        direct per-query plan with no detection/clustering/cache."""
        ds, _ = self._dists_host(index)
        results: dict[int, QueryResult] = {}
        with self.obs.span("route.green", n_queries=len(green)) as sg:
            for qi in green:
                q = queries[qi]
                with self.obs.span("assemble.query", qi=qi,
                                   route="green") as sq:
                    if int(ds[q.t, index.src_col[qi]]) > q.k:
                        r = self._empty_result(q)
                    elif q.output is Output.EXISTS:
                        r = QueryResult(q, _exists=True)
                    else:
                        r = self._direct_query(q, qi, index, plus, stats)
                r.time_s = sq.duration
                results[qi] = r
        stats["t_green"] = sg.duration
        return results

    @staticmethod
    def _empty_result(q: PathQuery) -> QueryResult:
        """The (exact) empty answer, shaped like the enumerators': an
        empty ``(0, k+1)`` path matrix / zero count / False."""
        if q.output is Output.PATHS:
            return QueryResult(q, _store=PathsStore(empty(1, q.k + 1)))
        if q.output is Output.EXISTS:
            return QueryResult(q, _exists=False)
        return QueryResult(q, _count=0, _exists=False)

    def _cluster_work(self, queries, index: QueryIndex, plus: bool,
                      min_sb: int, cluster: list[int]):
        """One sharing cluster end-to-end: detect → plan execution →
        per-query ⊕ assembly. Returns ``({qi: QueryResult}, cstats)``.

        This is the executor's unit of placement: it touches only
        replica-local state (``self.dg``, ``self.cache``) plus read-only
        inputs (host graph, the index on the replica's device), so
        distinct clusters run concurrently on distinct replicas.
        """
        cstats = {"n_psi_nodes": 0, "n_materialized": 0,
                  "n_cache_hits": 0, "n_cache_misses": 0,
                  "n_rows_assembled": 0}
        with self.obs.span("detect.cluster", size=len(cluster)) as sd:
            halves_f = {}
            halves_b = {}
            ends_f = {}
            ends_b = {}
            for qi in cluster:
                s, t, k = queries[qi]
                a, b = self._split(qi, index, plus)
                halves_f[qi] = (s, a)
                halves_b[qi] = (t, b)
                ends_f[qi] = (t, k)
                ends_b[qi] = (s, k)
            hop_f = self._hop_ok(index, cluster, forward=True)
            hop_b = self._hop_ok(index, cluster, forward=False)
            plan_f = detect_common_queries(self.g, cluster, halves_f, hop_f,
                                           reverse=False,
                                           min_shared_budget=min_sb,
                                           endpoints=ends_f)
            plan_b = detect_common_queries(self.g, cluster, halves_b, hop_b,
                                           reverse=True,
                                           min_shared_budget=min_sb,
                                           endpoints=ends_b)
            cstats["n_shared"] = plan_f.n_shared + plan_b.n_shared
            # deduped half-queries: halves mapped onto an existing node,
            # counted per direction (identical queries collapse entirely)
            cstats["n_dedup"] = (
                len(cluster) - len(set(plan_f.half_of_query.values()))
                + len(cluster) - len(set(plan_b.half_of_query.values())))
            cstats["n_share_edges"] = (
                sum(len(n.in_edges) for n in plan_f.nodes)
                + sum(len(n.in_edges) for n in plan_b.nodes))
        cstats["t_detect"] = sd.duration

        with self.obs.span("enumerate.cluster", size=len(cluster)) as se:
            cache_f = self._run_plan(plan_f, index, forward=True,
                                     stats=cstats)
            cache_b = self._run_plan(plan_b, index, forward=False,
                                     stats=cstats)
            # identical (halves, k, output, limit) -> identical payloads
            assembled: dict = {}
            results: dict[int, QueryResult] = {}
            for qi in cluster:
                q = queries[qi]
                with self.obs.span("assemble.query", qi=qi) as sq:
                    a = halves_f[qi][1]
                    b = halves_b[qi][1]
                    fid = plan_f.half_of_query[qi]
                    bid = plan_b.half_of_query[qi]
                    key = (fid, bid, a, b, q.k, q.t, q.output, q.limit)
                    if key not in assembled:
                        fl = cache_f[fid]
                        assembled[key] = self._payload(
                            q, fl, a, lambda bid=bid: cache_b[bid], b,
                            cstats)
                    results[qi] = self._wrap(q, assembled[key])
                results[qi].time_s = sq.duration
        cstats["t_enumerate"] = se.duration
        return results, cstats

    # ------------------------------------------------------------------
    # plan execution: materialize needed Ψ nodes in topological order,
    # consulting the cross-batch SharedPathCache first
    # ------------------------------------------------------------------
    @staticmethod
    def _plan_children(plan: DirectionPlan, node: PlanNode) -> list[int]:
        """Splice children after dedupe (same root vertex: keep max budget)."""
        seen_src: dict[int, int] = {}
        for cid in node.in_edges:
            c = plan.nodes[cid]
            if c.src in seen_src and plan.nodes[seen_src[c.src]].budget >= c.budget:
                continue
            seen_src[c.src] = cid
        return list(seen_src.values())

    def _node_stop(self, plan: DirectionPlan, node: PlanNode,
                   index: QueryIndex, forward: bool) -> int:
        # dedicated-node optimization: a half used by exactly one query
        # and spliced by nobody may stop at its own endpoint (Alg 1)
        if (node.query is not None and len(node.consumers) == 1
                and not node.out_edges):
            qi = node.consumers[0][0]
            s_, t_, _ = index.queries[qi]
            return t_ if forward else s_
        return -2

    def _run_plan(self, plan: DirectionPlan, index: QueryIndex, forward: bool,
                  stats: Optional[dict] = None):
        cache: dict[int, list[PathSet]] = {}
        children_of = {n.nid: self._plan_children(plan, n) for n in plan.nodes}
        stops = {n.nid: self._node_stop(plan, n, index, forward)
                 for n in plan.nodes}
        keys: dict[int, tuple] = {}
        if self.cache is not None:
            keys = {n.nid: n.signature + (stops[n.nid],)
                    for n in plan.nodes if n.signature is not None}
        # a node must be present iff it is a query half or spliced by a
        # materialized (cache-miss) node; children of hits are never touched.
        # Cache fetches all happen here — before any put — so entries taken
        # as device copies stay valid for this plan even if evicted later.
        need: set[int] = set()
        mat: list[int] = []
        stack = sorted(set(plan.half_of_query.values()))
        while stack:
            nid = stack.pop()
            if nid in need:
                continue
            need.add(nid)
            if nid in keys:
                with self.obs.span("cache.get") as sg:
                    got = self.cache.get(keys[nid])
                    sg.set(hit=got is not None)
            else:
                got = None
            if got is not None:
                cache[nid] = got
            else:
                mat.append(nid)
                stack.extend(children_of[nid])
        for nid in plan.topo:
            if nid not in need or nid in cache:
                continue
            node = plan.nodes[nid]
            slack = self._node_slack(index, node.consumers, forward)
            children = [(plan.nodes[cid].src, plan.nodes[cid].budget, cache[cid])
                        for cid in children_of[nid]]
            cache[nid] = self._run_node(not forward, node.src, node.budget,
                                        slack, children, stop_vertex=stops[nid])
            if self.cache is not None and nid in keys:
                with self.obs.span("cache.put"):
                    self.cache.put(keys[nid], cache[nid])
        if stats is not None:
            stats["n_psi_nodes"] += len(plan.nodes)
            stats["n_materialized"] += len(mat)
            if self.cache is not None:
                stats["n_cache_hits"] += len(need) - len(mat)
                stats["n_cache_misses"] += len(mat)
        return cache

    # ------------------------------------------------------------------
    # node enumeration with overflow retry
    # ------------------------------------------------------------------
    def _run_node(self, reverse: bool, source: int, budget: int, slack,
                  children, stop_vertex: int = -2):
        self._n_nodes.inc()
        with self.obs.span("enumerate.node", src=source, budget=budget,
                           reverse=reverse):
            caps = self._plan_caps(reverse, source, budget, slack)
            for attempt in range(8):
                if attempt:
                    self._node_retries.inc()
                out = self._run_node_once(reverse, source, budget, slack,
                                          children, stop_vertex, caps)
                if out is not None:
                    return out
                caps = [min(c * 4, self.cfg.hard_cap) for c in caps]
                if all(c >= self.cfg.hard_cap for c in caps[1:]):
                    raise EngineOverflow(
                        f"node (src={source}, budget={budget}) exceeds "
                        f"hard_cap")
            raise EngineOverflow("retry limit reached")

    def _run_node_once(self, reverse, source, budget, slack, children,
                       stop_vertex, caps):
        ell_idx, _ = self.dg.direction(reverse)
        width = budget + 1
        n = self.dg.n
        # children's roots and budgets, padded to a pow2 bucket with the
        # sentinel row n (its splice budget stays -1)
        n_pad = _pow2(max(len(children), 1))
        csrcs = np.full(n_pad, n, np.int32)
        cbs = np.full(n_pad, -1, np.int8)
        for i, (csrc, cb, _) in enumerate(children):
            csrcs[i], cbs[i] = csrc, cb
        csrcs = jnp.asarray(csrcs)
        # slack + splice stacked once per node; every expand level then
        # pays a single fused prune gather (see enumerate.prune_table)
        prune_tbl = splice_table(slack, csrcs, jnp.asarray(cbs))
        stop = jnp.int32(stop_vertex)

        pools: list[list[PathSet]] = [[] for _ in range(budget + 1)]
        frontier = singleton(source, width)
        pools[0].append(frontier)
        obs, syncs = self.obs, self._node_syncs
        for lvl in range(budget):
            if int(self._host(frontier.count, syncs)) == 0:
                break
            # per-level expand superstep: the overflow read is the level's
            # host sync point, so the span charges the level's device work
            # to itself even without fencing
            with obs.span("enumerate.level", level=lvl,
                          reverse=reverse) as sl:
                out = expand_level(frontier.verts, frontier.count, ell_idx,
                                   prune_tbl, stop,
                                   level=lvl, budget=budget,
                                   out_cap=caps[lvl + 1],
                                   backend=self._kb)
                sl.fence(out.frontier.verts)
                overflow = bool(self._host(out.frontier.overflow, syncs))
            if overflow:
                return None
            hit = (self._host(splice_hits(out.nbrs, out.splice_hit, csrcs,
                                          n=n), syncs)
                   if children else ())
            for (csrc, cb, clevels), any_hit in zip(children, hit):
                if not any_hit:
                    continue
                with obs.span("join.splice", level=lvl):
                    rmask = (out.splice_hit & (out.nbrs == csrc)).any(axis=1)
                    prefixes = extract_rows(frontier.verts, rmask,
                                            out_cap=frontier.cap)
                    n_pre = int(self._host(prefixes.count, syncs))
                    if n_pre == 0:
                        continue
                    for lam in range(0, min(cb, budget - lvl - 1) + 1):
                        cl = clevels[lam]
                        n_cl = int(self._host(cl.count, syncs))
                        if n_cl == 0:
                            continue
                        res = self._retry_join(
                            lambda cap: cross_join(
                                prefixes.verts, prefixes.count,
                                cl.verts, cl.count,
                                p_col=lvl, c_col=lam, out_cap=cap,
                                out_width=width,
                                backend=self._kb),
                            est=n_pre * n_cl, syncs=syncs)
                        pools[lvl + 1 + lam].append(res)
            frontier = out.frontier
            pools[lvl + 1].append(out.frontier)
        merged = [concat(p) if p else empty(1, width) for p in pools]
        return [self._shrink(ps) for ps in merged]

    def _shrink(self, ps: PathSet) -> PathSet:
        """Slice a packed PathSet down to a tight capacity bucket — keeps
        the downstream join/sort jit cache to a handful of shapes."""
        tight = _bucket(int(self._host(ps.count, self._node_syncs)),
                        self.cfg.min_cap)
        if tight >= ps.cap:
            return ps
        return PathSet(ps.verts[:tight], ps.count, ps.overflow)

    def _retry_capacity(self, fn, est: int, syncs: obsmetrics.Counter):
        """Run ``fn(cap) -> (result, overflow)`` with cap-doubling retry;
        each overflow read counts on ``syncs``."""
        cap = _bucket(min(max(est, self.cfg.min_cap), self.cfg.join_cap),
                      self.cfg.min_cap)
        while True:
            res, overflow = fn(cap)
            if not bool(self._host(overflow, syncs)):
                return res
            if cap >= self.cfg.hard_cap:
                raise EngineOverflow("join exceeds hard_cap")
            cap = min(cap * 4, self.cfg.hard_cap)
            self._join_retries.inc()

    def _retry_join(self, fn, est: int, syncs: obsmetrics.Counter
                    ) -> PathSet:
        def attempt(cap):
            ps = fn(cap)
            return ps, ps.overflow
        return self._retry_capacity(attempt, est, syncs)

    # ------------------------------------------------------------------
    # final ⊕ assembly (exact split, each result exactly once), dispatched
    # per query output kind: paths are materialized (lazily host-visible),
    # counts/existence use counting joins and never assemble a path row
    # ------------------------------------------------------------------
    def _payload(self, q: PathQuery, fwd_levels, a: int, bwd: Levels,
                 b: int, stats: dict):
        """The (shareable) answer payload for one query: a PathsStore for
        output=paths (duplicate queries alias it, so the host transfer
        happens once), an int for count/exists. ``bwd`` is a thunk —
        count/exists/limit queries answered by the forward levels alone
        never enumerate the backward half (basic planners)."""
        if q.output is Output.PATHS:
            ps = self._assemble(fwd_levels, a, bwd, b, q.t, q.k,
                                limit=q.limit)
            stats["n_rows_assembled"] += int(self._host(ps.count,
                                                   self._asm_syncs))
            return PathsStore(ps)
        limit = 1 if q.output is Output.EXISTS else q.limit
        return self._assemble_count(fwd_levels, a, bwd, b, q.t, q.k,
                                    limit=limit)

    @staticmethod
    def _wrap(q: PathQuery, payload) -> QueryResult:
        if q.output is Output.PATHS:
            return QueryResult(q, _store=payload)
        if q.output is Output.EXISTS:
            return QueryResult(q, _exists=payload > 0)
        return QueryResult(q, _count=payload, _exists=payload > 0)

    def _assemble(self, fwd_levels, a: int, bwd: Levels, b: int, t: int,
                  k: int, limit: Optional[int] = None):
        """``bwd`` is a thunk, only forced when the bidirectional stage is
        reached — a limit already met by forward completions skips the
        backward enumeration entirely (basic planners)."""
        width = k + 1
        syncs = self._asm_syncs
        outs = []
        found = 0
        for lvl in range(1, min(a, len(fwd_levels) - 1) + 1):
            if limit is not None and found >= limit:
                break
            ps = fwd_levels[lvl]
            if int(self._host(ps.count, syncs)) == 0:
                continue
            sel = select_ending_at(ps.verts, ps.count, jnp.int32(t),
                                   col=lvl, out_cap=ps.cap)
            n_sel = int(self._host(sel.count, syncs))
            if n_sel:
                outs.append(_pad_width(sel, width))
                found += n_sel
        if (not (limit is not None and found >= limit) and b >= 1
                and len(fwd_levels) > a
                and int(self._host(fwd_levels[a].count, syncs)) > 0):
            bwd_levels = bwd()
            fa = fwd_levels[a]
            sa = sort_by_last(fa.verts, fa.count, col=a)
            for lam in range(1, min(b, len(bwd_levels) - 1) + 1):
                if limit is not None and found >= limit:
                    break
                bs = bwd_levels[lam]
                n_bs = int(self._host(bs.count, syncs))
                if n_bs == 0:
                    continue
                with self.obs.span("join.keyed", lam=lam):
                    res = self._retry_join(
                        lambda cap: keyed_join(sa, bs.verts, bs.count,
                                               a_col=a, b_col=lam,
                                               out_cap=cap, out_width=width,
                                               backend=self._kb),
                        est=max(int(self._host(fa.count, syncs)), n_bs),
                        syncs=syncs)
                n_res = int(self._host(res.count, syncs))
                if n_res:
                    outs.append(res)
                    found += n_res
        if not outs:
            return empty(1, width)
        out = concat(outs)
        if limit is not None:
            out = PathSet(out.verts, jnp.minimum(out.count, jnp.int32(limit)),
                          out.overflow)
        return out

    def _assemble_count(self, fwd_levels, a: int, bwd: Levels, b: int,
                        t: int, k: int, limit: Optional[int] = None) -> int:
        """Exact ⊕ count without assembling paths: forward completions are
        mask reductions, the bidirectional part a counting join. ``limit``
        early-terminates (1 for exists-only) and clamps the total."""
        syncs = self._asm_syncs
        total = 0
        for lvl in range(1, min(a, len(fwd_levels) - 1) + 1):
            ps = fwd_levels[lvl]
            if int(self._host(ps.count, syncs)) == 0:
                continue
            total += int(self._host(count_ending_at(ps.verts, ps.count,
                                               jnp.int32(t), col=lvl),
                               syncs))
            if limit is not None and total >= limit:
                return limit
        if (b >= 1 and len(fwd_levels) > a
                and int(self._host(fwd_levels[a].count, syncs)) > 0):
            bwd_levels = bwd()
            fa = fwd_levels[a]
            sa = sort_by_last(fa.verts, fa.count, col=a)
            for lam in range(1, min(b, len(bwd_levels) - 1) + 1):
                bs = bwd_levels[lam]
                n_bs = int(self._host(bs.count, syncs))
                if n_bs == 0:
                    continue
                with self.obs.span("join.keyed", lam=lam, count=True):
                    total += self._retry_count(
                        lambda cap: keyed_join_count(sa, bs.verts, bs.count,
                                                     a_col=a, b_col=lam,
                                                     pair_cap=cap,
                                                     backend=self._kb),
                        est=max(int(self._host(fa.count, syncs)), n_bs))
                if limit is not None and total >= limit:
                    return limit
        return total if limit is None else min(total, limit)

    def _retry_count(self, fn, est: int) -> int:
        syncs = self._asm_syncs
        return int(self._host(self._retry_capacity(fn, est, syncs), syncs))

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _split(self, qi: int, index: QueryIndex, plus: bool) -> tuple[int, int]:
        s, t, k = index.queries[qi]
        a, b = midpoint_split(k)   # shared with cache.dedicated_keys
        if not plus or k <= 2:
            return a, b
        # "+" variants: pick the split minimizing estimated search cost
        fs = self._dedicated_slack(index, qi, forward=True)
        bs = self._dedicated_slack(index, qi, forward=False)
        cf = self._walk_counts(False, s, fs, k - 1)
        cb = self._walk_counts(True, t, bs, k - 1)
        best, best_cost = a, None
        for cand in range(1, k):
            cost = cf[:cand + 1].sum() + cb[:k - cand + 1].sum()
            if best_cost is None or cost < best_cost:
                best, best_cost = cand, cost
        return best, k - best

    def _dedicated_slack(self, index: QueryIndex, qi: int, forward: bool):
        return self._node_slack(index, [(qi, 0)], forward)

    def _node_slack(self, index: QueryIndex, consumers, forward: bool):
        """(n+1,) int8 device slack of a search whose consumers are
        (query, offset) pairs, computed where the index lives."""
        qs = [qi for qi, _ in consumers]
        reach = (np.array([index.queries[qi][2] for qi in qs], np.int32)
                 - np.array([off for _, off in consumers], np.int32))
        dist, cols = index.table(forward)
        return slack_vector(dist, jnp.asarray(
            column_reach(dist.shape[1], cols[qs], reach)), index.INF)

    def _hop_ok(self, index: QueryIndex, cluster, forward: bool) -> np.ndarray:
        """(n,) bool on the host: vertices within the cluster's largest k
        of some cluster endpoint (the detection's loose filter)."""
        k_max = max(index.queries[qi][2] for qi in cluster)
        hop = self._node_slack(index, [(qi, index.queries[qi][2] - k_max)
                                       for qi in cluster], forward)
        return np.asarray(hop)[:-1] >= 0

    def _kernel_dg(self) -> DeviceGraph:
        """Edge lists the index kernels sweep: the GSPMD-sharded
        mesh view on a primary engine with an executor, the local device
        view on replicas (``executor is None``) and plain engines. While
        a cluster fan-out is in flight the primary (= replica 0) also
        answers with its local view — a mesh-wide collective launched
        from one replica thread would contend with every other replica's
        per-device work."""
        if self.executor is not None and not self.executor.in_fanout:
            return self.executor.index_dg
        return self.dg

    def _walk_counts(self, reverse: bool, source: int, slack,
                     budget: int) -> np.ndarray:
        """Per-level pruned-walk counts of one search (``index.walk_counts``
        over the host CSR) under the node's device slack, fetched once."""
        g = self.g
        indptr, indices = ((g.r_indptr, g.r_indices) if reverse
                           else (g.indptr, g.indices))
        return walk_counts(indptr, indices, source, np.asarray(slack), budget)

    def _plan_caps(self, reverse: bool, source: int, budget: int, slack):
        if not self.cfg.plan_caps:
            return [self.cfg.min_cap] * (budget + 1)
        tot = self._walk_counts(reverse, source,
                                self._host(slack, self._node_syncs), budget)
        caps = [_bucket(min(int(min(t, 2**31)), self.cfg.max_cap),
                        self.cfg.min_cap) for t in tot]
        return caps


def _pad_width(ps: PathSet, width: int) -> PathSet:
    pad = width - ps.verts.shape[1]
    if pad <= 0:
        return ps
    verts = jnp.pad(ps.verts, ((0, 0), (0, pad)), constant_values=-1)
    return PathSet(verts, ps.count, ps.overflow)
