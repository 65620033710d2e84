"""Multi-device semantics tests.

Two families:

* sharded-engine tests (`core.distributed`) on the ``jax.sharding.Mesh``
  + ``NamedSharding`` GSPMD API. The multi-device ones run under 8 fake
  CPU devices via subprocess — the XLA device-count flag must be set before jax initializes, so they get
  isolated interpreters; the placement/identity unit tests run in-process
  on the single default device (a mesh of size 1 is the identity).
* model-stack tests (flash decode, checkpoint reshard, ring aggregate)
  on jax.make_mesh axis_types / jax.set_mesh / jax.shard_map.
"""
import subprocess
import sys
import textwrap

import numpy as np


def _run(code: str) -> str:
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


PREAMBLE = """
import os, sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "src")
"""


def test_flash_decode_matches_baseline():
    """shard_map flash-decoding == gathered-KV decode on a (2, 4) mesh."""
    out = _run(PREAMBLE + """
import dataclasses
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_bundle
from repro.models.sharding import Rules
from repro.config import RunOptions
from repro.models import transformer
from repro import configs as cr

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = Rules(mesh)
cfg = cr.get("granite-8b").REDUCED
B, S = 4, 32
params = transformer.init_lm_params(jax.random.PRNGKey(0), cfg, tp=4)
toks = jax.random.randint(jax.random.PRNGKey(1), (B, 1), 0, cfg.vocab)
outs = {}
with jax.set_mesh(mesh):
    for fd in [False, True]:
        opts = RunOptions(flash_decode=fd, attn_chunk=8, seq_parallel=False)
        cache = transformer.init_cache(cfg, B, S, dtype=jnp.float32)
        # pre-fill some cache content at positions 0..9
        k0 = jax.random.normal(jax.random.PRNGKey(2),
                               (cfg.n_layers, B, 10, cfg.n_kv_heads, cfg.hd))
        cache["k"] = cache["k"].at[:, :, :10].set(k0)
        cache["v"] = cache["v"].at[:, :, :10].set(k0 * 0.5)
        cache["pos"] = jnp.int32(10)
        c_spec = jax.tree.map(
            lambda ax: rules.sharding(*ax) if isinstance(ax, tuple) else rules.sharding(),
            transformer.cache_logical(False),
            is_leaf=lambda x: isinstance(x, tuple))
        cache = jax.device_put(cache, c_spec)
        constrain = lambda x, axes: jax.lax.with_sharding_constraint(
            x, rules.sharding(*axes))
        logits, _ = jax.jit(
            lambda p, t, c: transformer.decode_step(p, t, c, cfg, opts,
                                                    constrain))(params, toks, cache)
        outs[fd] = np.asarray(logits)
err = np.abs(outs[True] - outs[False]).max()
print("MAXERR", err)
assert err < 2e-3, err
""")
    assert "MAXERR" in out


def test_distributed_msbfs_matches_single_device():
    """Edge-sharded MS-BFS under GSPMD == single-device reference (via
    the classic Mesh API, so this runs on old and new jax alike)."""
    out = _run(PREAMBLE + """
from repro.core.graph import DeviceGraph
from repro.core import generators
from repro.core.distributed import shard_edges
from repro.core.msbfs import msbfs_dist
from jax.sharding import Mesh

g = generators.erdos(512, 4.0, seed=0)
dg = DeviceGraph.build(g, pad=False)   # exact m: forces a sharding pad
srcs = jnp.asarray(np.arange(16, dtype=np.int32))
ref = np.asarray(msbfs_dist(dg.esrc, dg.edst, srcs, n=g.n, k_max=4))

mesh = Mesh(np.array(jax.devices()), ("cells",))
esrc, edst = shard_edges(dg.esrc, dg.edst, mesh, n=g.n)
m8 = -(-g.m // 8) * 8
assert esrc.shape[0] == m8
# the device-multiple pad is the sentinel (n, n), never a repeated edge
assert np.all(np.asarray(esrc)[g.m:] == g.n)
assert np.all(np.asarray(edst)[g.m:] == g.n)
dist = np.asarray(msbfs_dist(esrc, edst, srcs, n=g.n, k_max=4))
print("EQ", np.array_equal(ref, dist))
assert np.array_equal(ref, dist)
""")
    assert "EQ True" in out


def test_elastic_checkpoint_reshard():
    """Save on a (4,2) mesh, restore onto (2,2) — elastic scaling."""
    out = _run(PREAMBLE + """
import tempfile
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpoint import save_checkpoint, restore_checkpoint

tree = {"w": jnp.arange(64.0).reshape(8, 8), "s": jnp.float32(3.0)}
m1 = jax.make_mesh((4, 2), ("data", "model"),
                   axis_types=(jax.sharding.AxisType.Auto,) * 2)
placed = {"w": jax.device_put(tree["w"], NamedSharding(m1, P("data", "model"))),
          "s": tree["s"]}
with tempfile.TemporaryDirectory() as d:
    save_checkpoint(d, 3, placed)
    m2 = jax.make_mesh((2, 2), ("data", "model"),
                       axis_types=(jax.sharding.AxisType.Auto,) * 2)
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    sh = {"w": NamedSharding(m2, P("data", "model")),
          "s": NamedSharding(m2, P())}
    got, step, _ = restore_checkpoint(d, abstract, sh)
    assert step == 3
    assert np.array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))
    assert got["w"].sharding.mesh.devices.size == 4
print("RESHARD OK")
""")
    assert "RESHARD OK" in out


def test_ring_aggregate_matches_segment_sum():
    """GNN ring SpMM (collective_permute schedule) == local segment_sum."""
    out = _run(PREAMBLE + """
from jax.sharding import PartitionSpec as P
from repro.models.gnn import ring_aggregate

P_DEV = 8
N_loc, F, Eb = 16, 5, 40
N = P_DEV * N_loc
rng = np.random.default_rng(0)
h = rng.standard_normal((N, F)).astype(np.float32)
# random edges; bucket by (dst_owner, src_owner)
E = 500
src = rng.integers(0, N, E)
dst = rng.integers(0, N, E)
es = np.zeros((P_DEV, P_DEV, Eb), np.int32)
ed = np.zeros((P_DEV, P_DEV, Eb), np.int32)
em = np.zeros((P_DEV, P_DEV, Eb), bool)
fill = np.zeros((P_DEV, P_DEV), int)
kept = []
for s_, d_ in zip(src, dst):
    po, so = d_ // N_loc, s_ // N_loc
    i = fill[po, so]
    if i >= Eb:
        continue
    es[po, so, i] = s_ % N_loc
    ed[po, so, i] = d_ % N_loc
    em[po, so, i] = True
    fill[po, so] += 1
    kept.append((s_, d_))
ref = np.zeros((N, F), np.float32)
for s_, d_ in kept:
    ref[d_] += h[s_]

mesh = jax.make_mesh((P_DEV,), ("cells",),
                     axis_types=(jax.sharding.AxisType.Auto,))
fn = jax.shard_map(
    lambda hh, a, b, c: ring_aggregate(hh, a[0], b[0], c[0], "cells"),
    mesh=mesh,
    in_specs=(P("cells"), P("cells"), P("cells"), P("cells")),
    out_specs=P("cells"), check_vma=False)
got = np.asarray(fn(h.reshape(P_DEV * N_loc, F), es, ed, em))
print("MAXERR", np.abs(got - ref).max())
assert np.allclose(got, ref, atol=1e-5)
""")
    assert "MAXERR" in out


# ----------------------------------------------------------------------
# sharded-engine subsystem (core.distributed): placement units run
# in-process; end-to-end parity runs under 8 forced CPU devices
# ----------------------------------------------------------------------

def test_plan_clusters_balance_and_uneven_shapes():
    from repro.core.distributed import plan_clusters

    # more clusters than replicas: every cluster placed exactly once
    costs = [5.0, 1.0, 4.0, 2.0, 3.0, 1.0, 8.0]
    assign, loads = plan_clusters(costs, 3)
    placed = sorted(ci for a in assign for ci in a)
    assert placed == list(range(len(costs)))
    # greedy LPT keeps the makespan near the mean: no replica exceeds
    # the heaviest single cluster + mean of the rest
    assert max(loads) <= max(costs) + sum(costs) / 3
    # fewer clusters than replicas: trailing replicas stay empty
    assign, loads = plan_clusters([2.0, 1.0], 4)
    assert sorted(ci for a in assign for ci in a) == [0, 1]
    assert sum(1 for a in assign if not a) == 2
    # zero clusters
    assign, loads = plan_clusters([], 4)
    assert all(a == [] for a in assign) and loads == [0.0] * 4
    # heaviest first onto distinct replicas
    assign, _ = plan_clusters([10.0, 9.0, 1.0], 2)
    heavy = [a for a in assign if 0 in a][0]
    assert 1 not in heavy
    # all-zero costs: ties must spread round-robin, not serialize on
    # replica 0 (load ties break on assignment count)
    assign, loads = plan_clusters([0.0] * 6, 3)
    assert [len(a) for a in assign] == [2, 2, 2]
    assert sorted(ci for a in assign for ci in a) == list(range(6))
    assert loads == [0.0] * 3
    # zero-cost remainder spreads too (4 ties over 3 replicas: 2/1/1)
    assign, _ = plan_clusters([0.0] * 4, 3)
    assert sorted(len(a) for a in assign) == [1, 1, 2]


def test_edge_bucket_alignment():
    from repro.core.distributed import edge_bucket_for

    assert edge_bucket_for(1000, 8) == 1024          # pow2 stays pow2
    assert edge_bucket_for(3, 8) == 8                # floor at n_dev
    assert edge_bucket_for(1024, 8) == 1024
    assert edge_bucket_for(1000, 6) % 6 == 0         # non-pow2 aligns
    assert edge_bucket_for(1000, 6) >= 1024


def test_mesh_size_one_is_identity():
    """n_devices=1 runs the sharded code path on one device and must be
    indistinguishable from the plain engine (same results, same stats
    shape, one replica, index view is the engine's own)."""
    from repro.core import BatchPathEngine, EngineConfig, generators

    g = generators.community(400, n_comm=4, avg_deg=4.0, seed=0)
    qs = generators.similar_queries(g, 8, 0.5, (3, 4), seed=1)
    plain = BatchPathEngine(g, EngineConfig(min_cap=128))
    one = BatchPathEngine(g, EngineConfig(min_cap=128, n_devices=1))
    assert one.executor.n_replicas == 1 and not one.executor.sharded
    r0 = plain.run(qs, planner="batch")
    r1 = one.run(qs, planner="batch")
    for qi in range(len(qs)):
        assert np.array_equal(r0[qi].paths, r1[qi].paths)
    assert "per_device" not in r1.stats   # no fan-out happened
    # empty batch through the same path
    assert len(one.run([])) == 0


def test_cluster_costs_monotone_in_hops():
    from repro.core import build_index, generators
    from repro.core.graph import DeviceGraph
    from repro.core.distributed import cluster_costs

    g = generators.erdos(300, 4.0, seed=2)
    dg = DeviceGraph.build(g)
    from repro.core.oracle import bfs_dist_from
    s = 0
    d = bfs_dist_from(g, s, 6)
    ts = np.flatnonzero((d >= 1) & (d <= 3))
    t = int(ts[0])
    index = build_index(dg, [(s, t, 2), (s, t, 6)])
    c_small, c_big = cluster_costs(index, [[0], [1]])
    assert c_big >= c_small > 0


def test_clustering_min_clusters_floor():
    from repro.core.clustering import cluster_queries

    mu = np.full((6, 6), 0.9)
    np.fill_diagonal(mu, 1.0)
    assert len(cluster_queries(mu, 0.5)) == 1
    assert len(cluster_queries(mu, 0.5, min_clusters=3)) == 3
    # floor above Q degrades to singletons
    assert len(cluster_queries(mu, 0.5, min_clusters=10)) == 6


def test_sharded_batch_matches_single_device():
    """8-device cluster-parallel BatchEnum == single-device, bit-equal,
    across planners and uneven cluster/device ratios."""
    out = _run(PREAMBLE + """
from repro.core import BatchPathEngine, EngineConfig, generators

assert len(jax.devices()) == 8
# 12 disconnected communities -> ~12 clusters over 8 devices (more
# clusters than devices); the 3-query subset exercises fewer-than-devices
g = generators.community(1200, n_comm=12, avg_deg=4.0, p_intra=1.0, seed=0)
qs = generators.random_queries(g, 16, k_range=(4, 5), seed=1)
e1 = BatchPathEngine(g, EngineConfig(min_cap=128))
e8 = BatchPathEngine(g, EngineConfig(min_cap=128, n_devices=8))
pd = None
for planner in ("batch", "batch+", "basic"):
    r1 = e1.run(qs, planner=planner)
    r8 = e8.run(qs, planner=planner)
    assert r1.stats.get("n_clusters") == r8.stats.get("n_clusters")
    for qi in range(len(qs)):
        assert np.array_equal(r1[qi].paths, r8[qi].paths), (planner, qi)
    if planner == "batch":
        pd = r8.stats.get("per_device")
        n_clusters = r8.stats["n_clusters"]
assert pd is not None and len(pd) == 8
assert sum(d["n_clusters"] for d in pd) == n_clusters
# fewer clusters than devices
sub = qs[:3]
r1 = e1.run(sub); r8 = e8.run(sub)
for qi in range(len(sub)):
    assert np.array_equal(r1[qi].paths, r8[qi].paths)
# zero queries
assert len(e8.run([])) == 0
# count/exists parity (no path assembly on either side)
from repro.core import PathQuery
cq = [PathQuery(s, t, k, output="count") for s, t, k in qs[:6]]
r1 = e1.run(cq); r8 = e8.run(cq)
assert [r.count for r in r1] == [r.count for r in r8]
print("SHARDED PARITY OK")
""")
    assert "SHARDED PARITY OK" in out


def test_sharded_apply_delta_parity():
    """Delta churn on a sharded engine: results stay bit-equal to the
    single-device engine and every replica cache sees the same epoch."""
    out = _run(PREAMBLE + """
from repro.core import BatchPathEngine, EngineConfig, GraphDelta, generators

g = generators.community(900, n_comm=6, avg_deg=4.0, p_intra=1.0, seed=0)
qs = generators.random_queries(g, 12, k_range=(4, 4), seed=1)
e1 = BatchPathEngine(g, EngineConfig(min_cap=128, cache_bytes=16 << 20))
e8 = BatchPathEngine(g, EngineConfig(min_cap=128, cache_bytes=16 << 20,
                                     n_devices=8))
rng = np.random.default_rng(0)
r1 = e1.run(qs); r8 = e8.run(qs)      # warm caches on both engines
for rnd in range(4):
    src = np.repeat(np.arange(g.n), np.diff(e1.g.indptr))
    dst = e1.g.indices
    pick = rng.choice(src.size, 6, replace=False)
    rem = list(zip(src[pick].tolist(), dst[pick].tolist()))
    adds = []
    while len(adds) < 6:
        u, v = (int(x) for x in rng.integers(0, g.n, 2))
        if u != v:
            adds.append((u, v))
    delta = GraphDelta.from_pairs(add=adds, remove=rem)
    rep1 = e1.apply_delta(delta)
    rep8 = e8.apply_delta(delta)
    eps = rep8.get("cache_epochs")
    assert eps and len(set(eps)) == 1, eps       # lockstep epochs
    assert rep8["n_touched"] == rep1["n_touched"]
    r1 = e1.run(qs); r8 = e8.run(qs)
    for qi in range(len(qs)):
        assert np.array_equal(r1[qi].paths, r8[qi].paths), (rnd, qi)
# replica caches exist and agree with the primary epoch
caches = e8._all_caches()
assert len(caches) == 8
assert len({c.epoch for c in caches}) == 1
print("DELTA PARITY OK epochs", sorted({c.epoch for c in caches}))
""")
    assert "DELTA PARITY OK" in out


def test_sharded_streaming_server():
    """StreamingServer over a sharded engine: admission fans the micro-
    batch across the mesh and results match the single-device server."""
    out = _run(PREAMBLE + """
from repro.core import BatchPathEngine, EngineConfig, generators
from repro.launch.serve import AdmissionPolicy, StreamingServer

g = generators.community(800, n_comm=8, avg_deg=4.0, p_intra=1.0, seed=0)
qs = generators.random_queries(g, 12, k_range=(4, 4), seed=1)
def serve(n_devices):
    eng = BatchPathEngine(g, EngineConfig(
        min_cap=128, cache_bytes=16 << 20, n_devices=n_devices))
    srv = StreamingServer(eng, policy=AdmissionPolicy(max_batch=12,
                                                      max_delay_s=0.0))
    qids = [srv.submit(q) for q in qs]
    srv.drain()
    return srv, [srv.take(qid).paths for qid in qids]
srv1, p1 = serve(None)
srv8, p8 = serve(8)
for a, b in zip(p1, p8):
    assert np.array_equal(a, b)
log = srv8.batch_log[-1]
assert log["n_devices"] == 8 and len(log["per_device"]) == 8
assert srv8.sched.steals == 0          # the mesh replaces the stealing loop
print("STREAMING SHARDED OK", log["n_clusters"], "clusters")
""")
    assert "STREAMING SHARDED OK" in out
